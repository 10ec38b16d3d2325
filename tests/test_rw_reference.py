"""sysbench `oltp_read_write` (the `rw_uniform` mix of the configuration
`sysbench_32x16k_rw`) under real contention, over the wire, judged by the
benchmark's own history judge (`benchmarks/harness/judge.py` `History`):
eight clients write the four rows of one table, so their transactions wait
for each other's locks and some close a cycle, are answered 1213 and start
again, as sysbench restarts them.  Every read of every transaction has to
match one snapshot the history allows, every affected-row count its
transaction's writes over it, and every row written has to read back as
the commits left it; the control `lost_commit` (each client's last commit
left out of the history) has to read wrong.  The harness's own operation
runner, read-back and judge are used, with the deployment module, the
statements and the mix loaded by path."""

import contextlib
import json
import os
import sys
import threading

import pytest

from test_tpch_columnar_reference import BENCH, _json, _load

from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.util import metrics

CONFIG_DIR = os.path.join(BENCH, "configs", "sysbench_32x16k_rw")
SIZES = {"tables": 1, "table_size": 4, "insert_batch_rows": 4}
CLIENTS = 8
OPERATIONS = 8   # a client's, one after the other
SEEDS = (2147487749, 3000000041)
COUNTERS = ("TXN_LOCK_WAITS", "TXN_DEADLOCKS", "TXN_LOCK_WAIT_TIMEOUTS", "TXN_WRITE_CONFLICTS",
            "TXN_PESSIMISTIC_RETRIES")


def _harness():
    """`benchmarks/run.py`, whose `harness` imports want the benchmark's
    directory on the path."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return _load(os.path.join(BENCH, "run.py"), "bench_run_rw")


def contended_run(seed: int) -> dict:
    bench = _harness()
    config = dict(_json(os.path.join(CONFIG_DIR, "config.json")), **SIZES)
    dep = _load(os.path.normpath(os.path.join(CONFIG_DIR, config["deployment"])), "sysbench_rw_deployment")
    mix = bench.Mix(dict(_json(os.path.join(BENCH, "traffic", "rw_uniform.json")), clients=CLIENTS),
                    _json(os.path.join(CONFIG_DIR, "statements.json")), config)
    data = dep.generate(config, seed)
    srv = MySQLServer(port=0)
    srv.start_background()
    conns = [MiniClient(srv.host, srv.port, timeout=300.0) for _ in range(CLIENTS + 1)]
    admin, clients = conns[0], conns[1:]
    try:
        dep.load(admin, data, config, lambda **_line: None)
        checker = bench.judge.History(dep, data, mix, config["control"].split(":")[0])
        for c in clients:
            c.query(f"set tidb_isolation_read_engines = '{mix.spec['read_engines']}'")
        plain = lambda _name: contextlib.nullcontext()   # noqa: E731 - no profiler here
        warm = bench.WritingOperation(CLIENTS, False, mix.operation(bench.client_rng(0, CLIENTS, 0)))
        warm.run(clients[0], plain, mix, bench.client_rng(0, CLIENTS, 0))   # the range programs compile here
        checker.operation(warm, "warm-up")
        before = {n: getattr(metrics, n).value for n in COUNTERS}
        done = [[] for _ in clients]

        def loop(i: int) -> None:
            rng = bench.client_rng(seed, i, 1)
            for _ in range(OPERATIONS):
                op = bench.WritingOperation(i, False, mix.operation(rng))
                op.run(clients[i], plain, mix, rng)
                done[i].append(op)

        threads = [threading.Thread(target=loop, args=(i,), daemon=True) for i in range(CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300.0)
        assert not any(t.is_alive() for t in threads), "a client never answered"
        moved = {n: getattr(metrics, n).value - before[n] for n in COUNTERS}
        ops = [op for ops in done for op in ops]
        bench.read_back(checker, admin, ops, mix)
        return {"ops": ops, "moved": moved, "verdict": checker.window(ops, 0),
                "control": checker.under_control().window(ops, 0)}
    finally:
        for c in conns:
            c.close()
        srv.close()


_RUNS: dict = {}


@pytest.fixture(params=SEEDS)
def run(request):
    if request.param not in _RUNS:
        _RUNS[request.param] = contended_run(request.param)
    return _RUNS[request.param]


def test_contended_mix_is_correct(run):
    verdict, moved = run["verdict"], run["moved"]
    assert verdict["correct"] is True, (verdict["examples"], json.dumps(verdict, default=str)[:2000])
    assert verdict["failed_operations"] == {"value": 0, "limit": 0, "of": CLIENTS * OPERATIONS}
    assert verdict["histories_over_cap"]["value"] == 0 and verdict["read_back_mismatches"]["value"] == 0
    assert verdict["rows_read_back"]["value"] >= 1 and verdict["statements_compared"]["value"] > 0
    # the contention the run is sized for: locks waited for, cycles refused and restarted
    assert moved["TXN_LOCK_WAITS"] > 0 and moved["TXN_PESSIMISTIC_RETRIES"] > 0
    assert verdict["restarted_attempts"]["value"] > 0
    assert verdict["restarted_attempts"]["value"] == moved["TXN_DEADLOCKS"]   # each a 1213, restarted
    assert moved["TXN_LOCK_WAIT_TIMEOUTS"] == 0 and moved["TXN_WRITE_CONFLICTS"] == 0
    codes = {a.error.split(")")[0] for op in run["ops"] for a in op.attempts if a.error}
    assert codes == {"ClientError: (1213"}, codes


def test_lost_commit_control_is_not_correct(run):
    control = run["control"]
    assert control["correct"] is False
    assert control["read_back_mismatches"]["value"] > 0
