"""The benchmark: one cell of BENCHMARK.json, once, through the wire.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It starts the program's `MySQLServer`, loads
the configuration's data from `--seed` over the wire, warms up the cell's
statements and proves that they ride the path the cell is there for (all
of that is `setup_s`), then lets the mix's clients, each a `MiniClient` on
its own TCP connection, repeat the mix's operation in a closed loop for
`--seconds`.  When the window has closed the rows that the timed
statements returned are compared with the configuration's plain
reference.  The last line of stdout is the result; every earlier line is
one JSON object too (see README.md).  `--control` puts the
configuration's control in the program's place for the comparison.

A mix that writes runs each operation as attempts (`WritingOperation`),
keeps every statement sent after the load in the judge's history, and
reads back every row written once the clients are through and before the
server closes (`read_back`); a mix that only reads takes none of that.

There is one path through this file.  `tests/` drives it on the CPU by
putting a stand-in in `engine.device`'s place and a manifest of small
configurations in `catalog.MANIFEST`'s.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

from harness.catalog import CHECKOUT, Cell, peaks  # noqa: E402  (stdlib only: safe before the environment is set)

sys.path.insert(0, CHECKOUT)
# the persistent compile cache: where the environment says, else at a fixed
# path inside the checkout (the path is part of the cache's key)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".xla_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from harness import engine, judge, spans, xplane  # noqa: E402
from harness.traffic import Mix, Step, client_rng  # noqa: E402

WIRE_TIMEOUT = 1100.0      # a first execution compiles; the wire must wait
WARMUP_SEED = 0            # warm-up draws the same literals whatever --seed: its programs stay cached
PROFILE_SECONDS = 5.0      # the profiler's window inside a traced run
LATE_ANSWER_SECONDS = 60.0  # how long past the close an answer is waited for
MAX_ATTEMPTS = 100         # a restarted operation gives up after this many attempts


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


class Operation:
    """One pass through the mix's steps by one client."""

    __slots__ = ("client", "traced", "t0", "t1", "steps", "answers", "spans", "error")

    def __init__(self, client: int, traced: bool, steps: list):
        self.client, self.traced, self.steps = client, traced, steps
        self.t0 = self.t1 = 0.0
        self.answers: list = []   # per step: rows (plain), span tree (traced), None (raw)
        self.spans: list = []     # per step: (t0, t1)
        self.error: str | None = None

    def run(self, conn, annotate) -> None:
        self.t0 = time.perf_counter()
        try:
            for step in self.steps:
                t = time.perf_counter()
                if step.name is None:
                    conn.query(step.sql)
                    self.answers.append(None)
                else:
                    with annotate(xplane.CLIENT_MARK + step.name):
                        _, rows = conn.query(("trace format='json' " if self.traced else "") + step.sql)
                    self.answers.append(spans.parse(rows[0][0]) if self.traced else rows)
                self.spans.append((t, time.perf_counter()))
        except Exception as e:  # noqa: BLE001 - a failed operation is counted, not fatal
            self.error = f"{type(e).__name__}: {e}"
        self.t1 = time.perf_counter()


class TracedError(Exception):
    """A traced statement whose span tree carries an error: TRACE answers
    the statement's failure as a row, with no error code."""


class WritingOperation(Operation):
    """One operation of a mix that writes: a BEGIN ... COMMIT attempt, and
    another with fresh draws from the client's stream each time an attempt
    is answered with an error code of the mix's `restart_on`.  An attempt
    that ends in an error is rolled back.  `steps`, `answers` and `spans`
    hold every statement of every attempt (the ROLLBACKs too), `attempts`
    what the judge's history keeps of each (`judge.Attempt`).  `broken`: a
    statement got no reply, so the connection carries nothing more."""

    __slots__ = ("attempts", "broken")

    def __init__(self, client: int, traced: bool, steps: list):
        super().__init__(client, traced, steps)
        self.attempts: list = []
        self.broken = False

    def run(self, conn, annotate, mix: Mix, rng) -> None:
        self.t0 = time.perf_counter()
        steps, self.steps = self.steps, []
        for n in range(MAX_ATTEMPTS):
            attempt, code = self._attempt(conn, annotate, steps)
            self.attempts.append(attempt)
            if attempt.error is None or self.broken or code not in mix.restart_on or n + 1 == MAX_ATTEMPTS:
                break
            attempt.restarted = True
            steps = mix.operation(rng)
        self.error = attempt.error
        self.t1 = time.perf_counter()

    def _attempt(self, conn, annotate, steps: list):
        answers, times, error, code, outcome = [], [], None, None, "committed"
        for step in steps:
            t = time.perf_counter()
            try:
                if step.name is None:
                    conn.query(step.sql)
                    answer = None
                else:
                    with annotate(xplane.CLIENT_MARK + step.name):
                        got = conn.query(("trace format='json' " if self.traced else "") + step.sql)
                    if self.traced:
                        answer = spans.parse(got[1][0][0])
                        if "error" in answer.get("attrs", {}):
                            raise TracedError(answer["attrs"]["error"])
                    else:
                        answer = got[1] if isinstance(got, tuple) else got   # rows, or rows affected
            except Exception as e:  # noqa: BLE001 - a failed attempt is kept, not fatal
                times.append((t, time.perf_counter()))
                error, code = f"{type(e).__name__}: {e}", getattr(e, "code", None)
                replied = isinstance(e, TracedError) or isinstance(code, int)
                self.broken = not replied
                outcome = "unknown" if not replied and judge.verb(step) == "commit" else "aborted"
                break
            answers.append(answer)
            times.append((t, time.perf_counter()))
        attempt = judge.Attempt(self.client, "", self.traced, steps[:len(times)], answers, times, error, outcome)
        self.steps += attempt.steps
        self.answers += answers + [None] * (len(times) - len(answers))
        self.spans += times
        if error is not None and not self.broken:
            rollback = Step("rollback")
            t = time.perf_counter()
            try:
                conn.query(rollback.sql)
            except Exception as e:  # noqa: BLE001
                self.broken = not isinstance(getattr(e, "code", None), int)
            self.steps.append(rollback)
            self.answers.append(None)
            self.spans.append((t, time.perf_counter()))
        return attempt, code


class Clients:
    """The mix's client threads; each owns one connection."""

    def __init__(self, srv, mix: Mix, seed: int, trace: bool, writing: bool = False):
        self.mix, self.seed, self.trace, self.writing = mix, seed, trace, writing
        self.conns = [engine.connect(srv, WIRE_TIMEOUT) for _ in range(mix.clients)]
        engines = mix.spec["read_engines"]
        for c in self.conns:
            c.query(f"set tidb_isolation_read_engines = '{engines}'")
        self.annotate = engine.annotation(trace)

    def drive(self, phase: int, until: float | None = None, count: int | None = None) -> None:
        """Start every client repeating the operation, until the clock
        passes `until` or `count` operations are done; `join` collects."""
        done = [[] for _ in self.conns]
        start = threading.Barrier(len(self.conns) + 1)

        def loop(i: int) -> None:
            rng = client_rng(self.seed if phase else WARMUP_SEED, i, phase)
            start.wait()
            n = 0
            while (count is None or n < count) and (until is None or time.perf_counter() < until):
                # in a traced run every second operation is sent as TRACE
                if self.writing:
                    op = WritingOperation(i, self.trace and n % 2 == 1, self.mix.operation(rng))
                    op.run(self.conns[i], self.annotate, self.mix, rng)
                else:
                    op = Operation(i, self.trace and n % 2 == 1, self.mix.operation(rng))
                    op.run(self.conns[i], self.annotate)
                done[i].append(op)
                n += 1
                if getattr(op, "broken", False):
                    break

        threads = [threading.Thread(target=loop, args=(i,), daemon=True) for i in range(len(self.conns))]
        for t in threads:
            t.start()
        self.threads, self.done = threads, done
        start.wait()

    def join(self, timeout: float) -> tuple:
        """(operations, clients that never answered) once every client is
        through, or `timeout` seconds have passed."""
        deadline = time.perf_counter() + timeout
        for t in self.threads:
            t.join(max(deadline - time.perf_counter(), 0.0))
        unanswered = sum(t.is_alive() for t in self.threads)
        return [op for ops in self.done for op in ops], unanswered

    def close(self) -> None:
        for c in self.conns:
            try:
                c.close()
            except OSError:
                pass


def prove_paths(mix: Mix, conn, checker) -> None:
    """One operation on one connection, step by step, with the counters
    read around each step: no oracle fallback, and for the statements the
    mix names, a columnar scan and a traced Pallas kernel.  A statement's
    first execution builds and compiles its program here."""
    proofs = mix.spec.get("proofs", {})
    pallas = engine.pallas_mode()
    writing = isinstance(checker, judge.History)
    steps, answers, times = mix.operation(client_rng(WARMUP_SEED, mix.clients, 0)), [], []
    for step in steps:
        before = engine.counters()
        t = time.perf_counter()
        got = conn.query(step.sql)
        wall = time.perf_counter() - t
        if writing:   # the history judges the proof with the rest, once the run is over
            answers.append(None if step.name is None else got[1] if isinstance(got, tuple) else got)
            times.append((t, t + wall))
        moved = engine.moved(before)
        if step.name is None:
            continue
        if not writing:
            checker.statement(step, got[1], where="warm-up")
        if moved["oracle_fallbacks"]:
            raise SystemExit(f"{step.name}: {moved['oracle_fallbacks']} oracle fallback(s) in warm-up")
        if step.name in proofs.get("columnar", ()):
            if moved["columnar_scans"] < 1 or moved["columnar_fallbacks"]:
                raise SystemExit(f"{step.name}: not served by the columnar replica: {moved}")
        if step.name in proofs.get("pallas", ()) and moved["programs_built"]:
            if pallas is None:
                emit(note=f"{step.name}: Pallas is off on this backend, kernel proof skipped")
            elif not moved["kernels"]:
                raise SystemExit(f"{step.name}: no Pallas kernel was traced into its program: {moved}")
        emit(warmup=step.name, wall_s=round(wall, 4), **moved)
    if writing:
        checker.record(judge.Attempt(mix.clients, "warm-up", False, steps, answers, times))


def read_back(checker, conn, ops: list, mix: Mix) -> None:
    """Every row written during the run read back over the admin
    connection, untimed, after the clients are through: the deployment's
    read-back statements, kept in the history as one more attempt."""
    t0 = time.perf_counter()
    steps = [Step(mix.statements[name].format(**params), name, params)
             for name, params in checker.read_back_steps(ops)]
    answers, times, error = [], [], None
    for step in steps:
        t = time.perf_counter()
        try:
            answers.append(conn.query(step.sql)[1])
        except Exception as e:  # noqa: BLE001 - an unanswered read-back statement is a mismatch
            error = f"{type(e).__name__}: {e}"
            break
        times.append((t, time.perf_counter()))
    checker.record(judge.Attempt(-1, "read-back", False, steps, answers, times, error))
    emit(phase="read_back", wall_s=round(time.perf_counter() - t0, 3), statements=len(steps),
         answered=len(answers), error=error)


def profile_window(seconds: float, t_open: float):
    """Hold the profiler over PROFILE_SECONDS of steady traffic, a quarter
    into the window.  Returns (trace_dir, window_s, (t_start, t_stop))."""
    import jax

    length = min(PROFILE_SECONDS, seconds / 2)
    time.sleep(max(t_open + min(seconds / 4, 5.0) - time.perf_counter(), 0.0))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the python tracer would slow the served path
    options.host_tracer_level = 1     # TraceAnnotations only
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t_start = time.perf_counter()
    time.sleep(length)
    t_stop = time.perf_counter()
    jax.profiler.stop_trace()
    return trace_dir, t_stop - t_start, (t_start, t_stop)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="compare the configuration's control in the program's place: correct must read false")
    args = ap.parse_args(argv)
    return run(args.workload, args.seed, args.seconds, bool(args.trace), args.control)


def set_up(cell: Cell, config: dict, mix: Mix, seed: int, trace: bool):
    """Data from the seed, the served entry, load, replica, warm-up and
    its proofs.  Returns (server, admin connection, clients, checker)."""
    dep = cell.deployment
    engine.prepare()
    data = dep.generate(config, seed)
    emit(phase="generate", wall_s=round(time.perf_counter() - T0, 3))
    srv = engine.start_server()
    admin = engine.connect(srv, WIRE_TIMEOUT)
    before = engine.counters()
    dep.load(admin, data, config, emit)
    if "columnar" in mix.spec["read_engines"]:
        engine.fill_replica(srv, admin, config, dep.replica_tables(config), emit)
    emit(phase="loaded", wall_s=round(time.perf_counter() - T0, 3), **engine.moved(before))

    writing = judge.writes(dep, mix)
    checker = (judge.History(dep, data, mix, config.get("control", "").split(":")[0]) if writing
               else judge.Checker(dep, data))
    clients = Clients(srv, mix, seed, trace, writing)
    before = engine.counters()
    prove_paths(mix, clients.conns[0], checker)
    clients.drive(phase=0, count=int(mix.spec.get("warmup_operations", 1)))
    warm, unanswered = clients.join(WIRE_TIMEOUT)
    for op in warm:
        checker.operation(op, where="warm-up")
    if unanswered:
        raise SystemExit(f"warm-up: {unanswered} client(s) never answered")
    emit(phase="warmup", wall_s=round(time.perf_counter() - T0, 3), operations=len(warm),
         **engine.moved(before))
    if not mix.spec.get("persistent_cache_in_window", True):
        # fresh literals build programs in the window: a repeated seed must
        # not find its own on disk, or two runs of one seed would differ
        engine.persistent_cache_off()
    gc.collect()
    return srv, admin, clients, checker


def read_profile(profiled, ops: list, cell: Cell, config: dict, device: dict) -> dict:
    """The profiler's window reduced: device busy time, the bytes that the
    statements which ended inside it had to read, the breakdown."""
    trace_dir, window_s, (p0, p1) = profiled
    try:
        reduced = xplane.reduce_dir(trace_dir, window_s)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if reduced is None:
        raise SystemExit("the profiler's window holds no device operation")
    ended = [step.name for op in ops for step, (_, t1) in zip(op.steps, op.spans)
             if step.name is not None and p0 <= t1 <= p1]
    # the bytes a statement has to read, where the configuration keeps them
    scan_bytes = getattr(cell.deployment, "scan_bytes", lambda _name, _config: None)
    needs = [scan_bytes(name, config) for name in ended]
    return {
        "busy_s": reduced["busy_s"], "window_s": window_s, "planes": reduced["planes"],
        "statements": len(ended),
        "needed_bytes": None if not needs or None in needs else sum(needs),
        "peaks": peaks(device["kind"]),
        "breakdown": {k: reduced[k] for k in ("device_ops", "idle_gaps")},
    }


def run(workload: str, seed: int, seconds: float, trace: bool, control: bool = False) -> int:
    cell = Cell(workload)
    config = cell.config
    device = engine.device(cell.chips)
    emit(phase="device", workload=workload, seed=seed, seconds=seconds, trace=int(trace),
         pallas_mode=engine.pallas_mode(), **device)
    mix = Mix(cell.traffic, cell.statements, config)
    srv, admin, clients, checker = set_up(cell, config, mix, seed, trace)

    # ---- the window
    before = engine.counters()
    t_open = time.perf_counter()
    close_at = t_open + seconds
    clients.drive(phase=1, until=close_at)
    profiled = profile_window(seconds, t_open) if trace else None
    time.sleep(max(close_at - time.perf_counter(), 0.0))
    ops, unanswered = clients.join(LATE_ANSWER_SECONDS)
    window = engine.moved(before)
    device["memory_peak_bytes"] = engine.memory_peak_bytes()
    if isinstance(checker, judge.History):
        read_back(checker, admin, ops, mix)
    clients.close()
    admin.close()
    srv.close()
    del srv, clients
    gc.collect()

    # ---- reduce
    # an operation in flight at the close counts by the share of its time
    # that lay inside the window: all the work of the window, and no more
    done = [op for op in ops if op.error is None]
    in_window = sum((min(op.t1, close_at) - op.t0) / (op.t1 - op.t0) for op in done)
    facts = {
        "workload": workload, "seconds": seconds, "setup_s": t_open - T0,
        "operations": in_window, "attempted": len(ops),
        "latencies_ms": sorted((op.t1 - op.t0) * 1e3 for op in done),
        "counters": window, "config": config, "device": device,
        "traced": [spans.layers([a for a in op.answers if a is not None], int((op.t1 - op.t0) * 1e9))
                   for op in done if op.traced],
    }
    emit(phase="window", operations=round(in_window, 3), attempted=len(ops), unanswered=unanswered,
         ended_inside=sum(op.t1 <= close_at for op in done), samples=len(done),
         statement_p50_ms=judge.statement_medians(ops), **window)
    breakdown = None
    if trace:
        facts["profile"] = read_profile(profiled, ops, cell, config, device)
        facts["self_times_ms_per_op"] = judge.self_times_per_op(ops)
        breakdown = facts["profile"].pop("breakdown")
        device.update(busy_s=facts["profile"]["busy_s"], window_s=facts["profile"]["window_s"])
        emit(phase="profile", planes=facts["profile"]["planes"], statements=facts["profile"]["statements"],
             needed_bytes=facts["profile"]["needed_bytes"],
             self_times_ms_per_op=facts["self_times_ms_per_op"], **breakdown)

    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(group):
        value = cell.reader(group, m["name"])(facts)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # ---- correct: the timed statements' rows against the plain reference
    t = time.perf_counter()
    verdict = checker.window(ops, unanswered)
    if control:
        emit(program=verdict)
        verdict = checker.under_control().window(ops, unanswered)
    emit(phase="reference", wall_s=round(time.perf_counter() - t, 3), examples=verdict.pop("examples"))
    result = {
        "correct": verdict.pop("correct"), "attempted": len(ops) + unanswered,
        "failed": len(ops) - len(done) + unanswered, "metrics": metrics, "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    if control:
        result["control"] = config.get("control", True)
    result["compared"] = verdict
    print(json.dumps(result), flush=True)
    for name, reading in verdict.items():
        print(f"compared {name}: {json.dumps(reading)}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
