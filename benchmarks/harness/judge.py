"""The comparison that decides `correct`: every statement that the timed
operations sent, its served rows against the configuration's plain
reference, once the window has closed.  Exact, so every limit is 0.

`Checker` judges a mix that only reads: each statement's answer is one
function of its literals and the data made from the seed.  `History`
judges a mix that writes (a statement of it is one for which the
deployment's `writes` says so): every transaction the harness sent after
the load is kept with its times and outcome, and its reads are compared
with the states that snapshot isolation allows at that point of the
history (see `History`)."""

from __future__ import annotations

import collections
import itertools
import json
import math
import statistics

from . import spans

EXAMPLES = 5
CAP = 4096   # candidate states one transaction may be compared with; more is counted, never passed


class Checker:
    def __init__(self, deployment, data, control: bool = False):
        self.dep, self.data, self.control = deployment, data, control
        self._wanted: dict = {}
        self._control_rows: dict = {}
        self.compared = self.wrong = self.traced_compared = self.traced_wrong = 0
        self.examples: list = []

    def _cached(self, cache: dict, fn, step):
        """`fn` of the statement, once per distinct (statement, literals)."""
        key = (step.name, json.dumps(step.params, sort_keys=True))
        if key not in cache:
            cache[key] = fn(step.name, step.params, self.data)
        return cache[key]

    def _want(self, step):
        return self._cached(self._wanted, self.dep.reference, step)

    def _note(self, where: str, what: str) -> None:
        if len(self.examples) < EXAMPLES:
            self.examples.append(f"{where}: {what}")

    def statement(self, step, rows, where: str) -> None:
        """A plain statement's rows; under `control`, the control's rows
        in their place."""
        if self.control:
            rows = self._cached(self._control_rows, self.dep.control, step)
        self.compared += 1
        diff = self.dep.mismatch(step.name, self._want(step), rows)
        if diff:
            self.wrong += 1
            self._note(where, diff)

    def traced_statement(self, step, tree, where: str) -> None:
        """TRACE returns the span tree in place of the rows: the root
        carries the row count and any error."""
        attrs = tree.get("attrs", {})
        want = self.dep.expected_rows(step.name, self._want(step))
        self.traced_compared += 1
        if "error" in attrs or attrs.get("rows") != want:
            self.traced_wrong += 1
            self._note(where, f"{step.name} traced: {attrs}, want {want} rows")

    def operation(self, op, where: str) -> None:
        for step, answer in zip(op.steps, op.answers):
            if step.name is None:
                continue
            if op.traced:
                self.traced_statement(step, answer, where)
            else:
                self.statement(step, answer, where)
        if op.error:
            self._note(where, f"client {op.client}: {op.error}")

    def window(self, ops: list, unanswered: int) -> dict:
        """Every number compared, beside its limit; `correct` is all of
        them inside."""
        for op in ops:
            self.operation(op, "window")
        return _verdict(self, ops, unanswered, {})

    def under_control(self) -> "Checker":
        """A checker with the configuration's control in the program's place."""
        return Checker(self.dep, self.data, control=True)


def _verdict(judged, ops: list, unanswered: int, more: dict) -> dict:
    failed = sum(op.error is not None for op in ops)
    out = {
        "wrong_answers": {"value": judged.wrong, "limit": 0, "of": judged.compared},
        "traced_wrong_row_counts": {"value": judged.traced_wrong, "limit": 0, "of": judged.traced_compared},
        "failed_operations": {"value": failed, "limit": 0, "of": len(ops)},
        "unanswered_clients": {"value": unanswered, "limit": 0},
        "statements_compared": {"value": judged.compared, "at_least": 1},
        **more,
    }
    out["correct"] = (all(r["value"] <= r["limit"] for r in out.values() if "limit" in r)
                      and all(r["value"] >= r["at_least"] for r in out.values() if "at_least" in r))
    out["examples"] = judged.examples
    return out


def writes(dep, mix) -> bool:
    """Whether the mix sends a statement that the deployment says writes."""
    is_write = getattr(dep, "writes", None)
    return is_write is not None and any(is_write(name) for name in mix.statement_names())


# --------------------------------------------------------------------------
# the history judge: a mix that writes
# --------------------------------------------------------------------------

def verb(step) -> str | None:
    """The first word of a raw step (`begin`, `commit`), else None."""
    return step.sql.split(None, 1)[0].lower() if step.name is None else None


class Attempt:
    """One transaction of one client, as the history keeps it: the steps it
    sent, the answers of those that were answered (rows, an affected-row
    count, or a span tree where the operation was traced), each step's
    client-side (send, return) times, and its outcome: `committed` (COMMIT
    returned OK), `aborted` (an error before COMMIT returned, or ROLLBACK:
    its writes must be absent) or `unknown` (COMMIT was sent and no reply
    came: its writes may or may not be there)."""

    __slots__ = ("client", "where", "traced", "steps", "answers", "spans", "error", "outcome", "restarted")

    def __init__(self, client: int, where: str, traced: bool, steps: list, answers: list, spans: list,
                 error: str | None = None, outcome: str = "committed", restarted: bool = False):
        self.client, self.where, self.traced = client, where, traced
        self.steps, self.answers, self.spans = steps, answers, spans
        self.error, self.outcome, self.restarted = error, outcome, restarted

    def answered(self) -> list:
        """[(step, answer, (t0, t1))] of the named steps that were answered."""
        return [(s, a, t) for s, a, t in zip(self.steps, self.answers, self.spans) if s.name is not None]

    def _time(self, word: str, end: int) -> float | None:
        for step, span in zip(self.steps, self.spans):
            if verb(step) == word:
                return span[end]
        return None

    @property
    def begin(self) -> float:
        t = self._time("begin", 0)
        return self.spans[0][0] if t is None else t

    @property
    def first_read(self) -> float:
        """When the first statement's answer came back: the latest a
        snapshot can be taken."""
        got = self.answered()
        return got[0][2][1] if got else self.spans[-1][1]

    @property
    def commit_sent(self) -> float:
        t = self._time("commit", 0)
        return self.spans[-1][0] if t is None else t

    @property
    def commit_returned(self) -> float:
        if self.outcome == "unknown":
            return math.inf
        t = self._time("commit", 1) if len(self.answers) == len(self.steps) else None
        return self.spans[-1][1] if t is None else t


def attempt_of(op, where: str) -> list:
    """The attempts that a writing operation (`run.WritingOperation`) kept."""
    for a in op.attempts:
        a.where = where
    return op.attempts


class _Writer:
    """A committed (or unknown) attempt's writes and its commit interval:
    it committed at some instant between `sent` and `returned`."""

    __slots__ = ("attempt", "sent", "returned", "writes", "keys", "order")

    def __init__(self, attempt: Attempt, dep, order: int):
        self.attempt, self.order = attempt, order
        self.sent, self.returned = attempt.commit_sent, attempt.commit_returned
        self.writes = [(step, set(dep.keys(step.name, step.params)))
                       for step, _, _ in attempt.answered() if dep.writes(step.name)]
        self.keys = set().union(*(k for _, k in self.writes)) if self.writes else set()


def _extensions(writers: list, limit: int) -> list:
    """The orders in which `writers` (sorted by `sent`) may have committed:
    A before B is forced where A's COMMIT returned before B's was sent.
    At most `limit` + 1 are made."""
    out: list = []

    def grow(done: list, left: list) -> None:
        if len(out) > limit:
            return
        if not left:
            out.append(tuple(done))
            return
        for i, w in enumerate(left):
            if all(not (o.returned < w.sent) for o in left if o is not w):
                grow(done + [w], left[:i] + left[i + 1:])

    grow([], list(writers))
    return out


class History:
    """The judge of a mix that writes.

    Every statement the harness sends after the load is in the history (the
    path proof, the warm-up, the window and the read-back), grouped into
    attempts (`Attempt`).  A writer is an attempt that committed, or whose
    COMMIT got no reply, with its writes; it committed at an instant
    between its COMMIT's send and return.

    The reads of one attempt T are compared with single states, each of
    which holds every writer whose COMMIT returned before T's BEGIN was
    sent, none whose COMMIT was sent after T's first answer came back, and,
    of the writers in between, the set that commit instants before some one
    snapshot instant in that span would give; where two writers of one row
    may have committed in either order, each order is a state.  Only the
    writers of rows that T reads or writes are enumerated: with none in
    between, T is compared with exactly one state.  T's own writes are
    applied to the state in its order, and each write's affected rows are
    compared too.  T passes where one state answers every statement as the
    program did; more than `CAP` states count in `histories_over_cap`.

    A writer's effect is folded in at its commit, in commit order (`k = k +
    1` adds to the latest committed value).  After the run every row that
    any attempt wrote is read back (the deployment's `read_back`) as one
    more attempt, which every writer precedes."""

    def __init__(self, dep, data, mix, control: str, controlled: bool = False):
        ops = mix.spec["operation"]
        if not (ops and ops[0].get("sql", "").strip().lower() == "begin"
                and ops[-1].get("sql", "").strip().lower() == "commit"):
            raise ValueError("a mix that writes sends its operation as one BEGIN ... COMMIT")
        if control not in HISTORY_CONTROLS:
            raise ValueError(f"control {control!r}: a configuration that writes names one of {sorted(HISTORY_CONTROLS)}")
        self.dep, self.data, self.mix = dep, data, mix
        self.control, self.controlled = control, controlled
        self.recorded: list = []     # attempts of the proof, the warm-up and the read-back

    def operation(self, op, where: str) -> None:
        self.recorded += attempt_of(op, where)

    def record(self, attempt: Attempt) -> None:
        self.recorded.append(attempt)

    def under_control(self) -> "History":
        """The same history with the configuration's control applied to it."""
        twin = History(self.dep, self.data, self.mix, self.control, controlled=True)
        twin.recorded = self.recorded
        return twin

    # ---- the read-back ------------------------------------------------
    def read_back_steps(self, ops: list) -> list:
        """`[(statement, params)]` that read back every row written: the
        deployment's `read_back` over each row's load value and every value
        it held in commit order."""
        attempts = self.recorded + [a for op in ops for a in attempt_of(op, "window")]
        written = {key for a in attempts for step, _, _ in a.answered() if self.dep.writes(step.name)
                   for key in self.dep.keys(step.name, step.params)}
        writers = sorted((_Writer(a, self.dep, i) for i, a in enumerate(attempts)
                          if a.outcome != "aborted"), key=lambda w: w.sent)
        state = self.dep.load_state(self.data)
        values = {key: {state.get(key)} for key in written}
        for w in writers:
            for step, keys in w.writes:
                self.dep.apply(step.name, step.params, state)
                for key in keys & written:
                    values[key].add(state.get(key))
        return self.dep.read_back(values)

    # ---- the verdict ---------------------------------------------------
    def window(self, ops: list, unanswered: int) -> dict:
        attempts = self.recorded + [a for op in ops for a in attempt_of(op, "window")]
        if self.controlled:
            attempts = HISTORY_CONTROLS[self.control](attempts)
        judged = _Judged()
        writers = [_Writer(a, self.dep, i) for i, a in enumerate(attempts) if a.outcome != "aborted"]
        writers = [w for w in writers if w.writes]
        by_key, by_table = collections.defaultdict(list), collections.defaultdict(list)
        for w in writers:
            for key in w.keys:
                by_key[key].append(w)
                by_table[key[0]].append(w)
        committed_writes = any(w.attempt.outcome == "committed" for w in writers)
        for a in attempts:
            self._judge(a, by_key, by_table, judged)
        restarted = sum(a.restarted for a in attempts)
        more = {
            "read_back_mismatches": {"value": judged.rb_wrong, "limit": 0, "of": judged.rb_compared},
            "rows_read_back": ({"value": judged.rows_read_back, "at_least": 1} if committed_writes
                               else {"value": judged.rows_read_back}),
            "histories_over_cap": {"value": judged.over_cap, "limit": 0, "of": judged.histories},
            "restarted_attempts": {"value": restarted},
            "reads_with_concurrent_writers": {"value": judged.concurrent_reads},
        }
        return _verdict(judged, ops, unanswered, more)

    def _judge(self, t: Attempt, by_key: dict, by_table: dict, judged: "_Judged") -> None:
        dep = self.dep
        got = t.answered()
        read_back = t.where == "read-back"
        if not got:
            if read_back:
                judged.rb_compared += len(t.steps)
                judged.rb_wrong += len(t.steps)
            return
        judged.histories += 1
        step_keys = [set(dep.keys(s.name, s.params)) for s, _, _ in got]
        wanted = set().union(*step_keys)
        rel = set()
        for key in wanted:
            rel.update(by_table[key[0]] if key[1] is None else by_key.get(key, ()))
        rel = [w for w in rel if w.attempt is not t]
        begin, first = t.begin, t.first_read
        before = [w for w in rel if w.returned < begin]
        pool = [w for w in rel if not w.returned < begin and not w.sent > first]
        if not read_back:
            touched = set().union(*(w.keys for w in pool)) if pool else set()
            judged.concurrent_reads += sum(1 for (s, _, _), ks in zip(got, step_keys)
                                           if not dep.writes(s.name) and ks & touched)
        subsets = _snapshots(pool, begin, first)
        if subsets is None:
            judged.over_cap += 1
            judged.note(t, f"more than {CAP} snapshots")
            return
        # the rows whose value a state decides: every concrete key read or
        # written, and for a key of a whole table, every row written there
        rows = {k for k in wanted if k[1] is not None}
        tables = {k[0] for k in wanted if k[1] is None}
        best = None
        n_states = 0
        cache: dict = {}
        for chosen in subsets:
            included = before + [w for w in pool if w.order in chosen]
            held = {w.order for w in included}
            mine = rows | {k for w in included for k in w.keys if k[0] in tables} if tables else rows
            choices = []
            for key in sorted(mine, key=repr):
                seq = sorted((w for w in by_key.get(key, ()) if w.order in held), key=lambda w: w.sent)
                if not seq:
                    continue
                values, orders = [], _extensions(seq, CAP)
                if len(orders) > CAP:
                    judged.over_cap += 1
                    judged.note(t, f"more than {CAP} commit orders of one row")
                    return
                for order in orders:
                    sig = (key, tuple(w.order for w in order))
                    if sig not in cache:
                        cache[sig] = self._fold(key, order)
                    if cache[sig] not in values:
                        values.append(cache[sig])
                choices.append((key, values))
            n = math.prod(len(v) for _, v in choices)
            n_states += n
            if n_states > CAP:
                judged.over_cap += 1
                judged.note(t, f"more than {CAP} states")
                return
            for combo in itertools.product(*(v for _, v in choices)):
                state = dep.load_state(self.data)
                for (key, _), value in zip(choices, combo):
                    state.put(key, value)
                diffs = self._walk(t, got, state)
                if best is None or len(diffs) < len(best):
                    best = diffs
                if not diffs:
                    break
            if not best:
                break
        n_got = len(got)
        if read_back:     # a read-back statement that got no answer is a mismatch
            judged.rb_compared += len(t.steps)
            judged.rb_wrong += len(best) + len(t.steps) - n_got
            judged.rows_read_back += sum(1 for s, a, _ in got if s.name == "pk_read_back" and a)
        elif t.traced:
            judged.traced_compared += n_got
            judged.traced_wrong += len(best)
        else:
            judged.compared += n_got
            judged.wrong += len(best)
        for diff in best[:EXAMPLES]:
            judged.note(t, diff)

    def _fold(self, key, order: tuple):
        """The value of row `key` after `order`'s writes, each at its commit."""
        state = self.dep.load_state(self.data)
        for w in order:
            for step, keys in w.writes:
                if key in keys:
                    self.dep.apply(step.name, step.params, state)
        return state.get(key)

    def _walk(self, t: Attempt, got: list, state) -> list:
        """T's statements over `state`, its own writes applied in its order:
        what differed."""
        dep, diffs = self.dep, []
        for step, answer, _ in got:
            if dep.writes(step.name):
                want = dep.apply(step.name, step.params, state)
                have = answer.get("attrs", {}).get("rows") if t.traced else answer
                if want is None or have != want:
                    diffs.append(f"{step.name} {step.params.get('t')}/{step.params.get('id')}: "
                                 f"{have} rows affected, want {'an error' if want is None else want}")
                continue
            want = dep.reference_at(step.name, step.params, state)
            if t.traced:
                attrs = answer.get("attrs", {})
                rows = dep.expected_rows(step.name, want)
                if "error" in attrs or attrs.get("rows") != rows:
                    diffs.append(f"{step.name} traced: {attrs}, want {rows} rows")
            else:
                diff = dep.mismatch(step.name, want, answer)
                if diff:
                    diffs.append(diff)
        return diffs


def _lost_commit(attempts: list) -> list:
    """The control of a configuration that writes: the last committed
    transaction of every client in the window left out of the history."""
    last: dict = {}
    for a in attempts:
        if a.where == "window" and a.outcome == "committed":
            if a.client not in last or a.begin > last[a.client].begin:
                last[a.client] = a
    gone = {id(a) for a in last.values()}
    return [a for a in attempts if id(a) not in gone]


HISTORY_CONTROLS = {"lost_commit": _lost_commit}


def _snapshots(pool: list, begin: float, first: float):
    """The sets of `pool`'s writers (by `order`) that one snapshot instant
    in (begin, first) can hold, each writer committing at an instant of its
    own interval; None where there are more than CAP."""
    cuts = sorted({begin, first} | {x for w in pool for x in (w.sent, w.returned) if begin < x < first})
    out: list = []
    seen: set = set()
    for lo, hi in zip(cuts, cuts[1:]):
        held = [w.order for w in pool if w.returned <= lo]
        free = [w.order for w in pool if w.sent <= lo and w.returned >= hi]
        if 2 ** len(free) > CAP:
            return None
        for r in range(len(free) + 1):
            for pick in itertools.combinations(free, r):
                chosen = frozenset(held + list(pick))
                if chosen not in seen:
                    seen.add(chosen)
                    out.append(chosen)
        if len(out) > CAP:
            return None
    return out or [frozenset()]


class _Judged:
    """What the history judge counted."""

    def __init__(self):
        self.compared = self.wrong = self.traced_compared = self.traced_wrong = 0
        self.rb_compared = self.rb_wrong = self.rows_read_back = 0
        self.histories = self.over_cap = self.concurrent_reads = 0
        self.examples: list = []

    def note(self, t: Attempt, what: str) -> None:
        if len(self.examples) < EXAMPLES:
            self.examples.append(f"{t.where}: client {t.client}: {what}")


def statement_medians(ops: list) -> dict:
    """{statement: median client-side ms} over the plain operations."""
    by = collections.defaultdict(list)
    for op in ops:
        if op.traced or op.error:
            continue
        for step, (t0, t1) in zip(op.steps, op.spans):
            by[step.name or step.sql].append((t1 - t0) * 1e3)
    return {k: round(statistics.median(v), 3) for k, v in by.items()}


def self_times_per_op(ops: list) -> dict:
    """{span name: self time, ms per traced operation}."""
    total, n = collections.Counter(), 0
    for op in ops:
        if not op.traced or op.error:
            continue
        n += 1
        for tree in op.answers:
            if tree is not None:
                spans.self_times(tree, total)
    return {k: round(v / n / 1e6, 4) for k, v in total.most_common()} if n else {}
