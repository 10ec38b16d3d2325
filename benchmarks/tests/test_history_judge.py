"""The judge of a mix that writes (`harness/judge.py` `History`), first over
histories written by hand against the sysbench deployment's state, then
over the program's own answers on the CPU: the write statements against
the reference with their affected rows, and a conflict between two
connections."""

import json
import os

import pytest

from harness import judge
from harness.catalog import BENCH_DIR, load_module
from harness.traffic import Mix, Step

CONFIG_DIR = os.path.join(BENCH_DIR, "configs", "sysbench_32x16k_rw")
SIZES = {"tables": 2, "table_size": 50, "insert_batch_rows": 25}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def dep():
    return load_module(os.path.join(CONFIG_DIR, "..", "sysbench_32x16k", "deployment.py"), "sysbench_rw_dep")


@pytest.fixture(scope="module")
def data(dep):
    return dep.generate(SIZES, seed=2**31 + 11)


@pytest.fixture(scope="module")
def mix():
    return Mix(_json(BENCH_DIR, "traffic", "rw_uniform.json"), _json(CONFIG_DIR, "statements.json"),
               {**_json(CONFIG_DIR, "config.json"), **SIZES})


def attempt(client, t0, body, outcome="committed", where="window", commit=True, traced=False):
    """An attempt that sends BEGIN at `t0`, then each `(name, params,
    answer)` of `body` 1 s apart, then COMMIT; the last statement's answer
    None leaves it unanswered (an error there)."""
    steps, answers, spans, t = [Step("begin")], [None], [(t0, t0 + 0.5)], t0 + 1.0
    error = None
    for name, params, answer in body:
        steps.append(Step(name, name, params))
        spans.append((t, t + 0.5))
        t += 1.0
        if answer is None:
            error = "ClientError: (1105) write conflict"
            break
        answers.append(answer)
    if commit and error is None:
        steps.append(Step("commit"))
        answers.append(None)
        spans.append((t, t + 0.5))
    return judge.Attempt(client, where, traced, steps, answers, spans, error, outcome)


def read_back(t0, rows):
    """The read-back attempt: `rows` are `((t, id), answer)`."""
    steps = [Step("pk", "pk_read_back", {"t": t, "id": i}) for (t, i), _ in rows]
    spans = [(t0 + n, t0 + n + 0.5) for n in range(len(rows))]
    return judge.Attempt(-1, "read-back", False, steps, [a for _, a in rows], spans)


def verdict(dep, data, mix, *attempts, control=False):
    h = judge.History(dep, data, mix, "lost_commit")
    for a in attempts:
        h.record(a)
    return (h.under_control() if control else h).window([], 0)


def row_text(dep, data, key, row=None):
    row = dep.load_state(data).get(key) if row is None else row
    return [[str(key[1]), str(row[0]), row[1], row[2]]]


def c_of(dep, data, key):
    return [[dep.load_state(data).get(key)[1]]]


# ---- snapshots ----------------------------------------------------------
@pytest.mark.parametrize("sees_it", [False, True])
def test_a_concurrent_writer_is_accepted_on_either_side(dep, data, mix, sees_it):
    w = attempt(1, 0.0, [("non_index_update", {"t": 1, "id": 5, "c": "new"}, 1)])   # COMMIT 2.0-2.5
    t = attempt(2, 1.8, [("point_select", {"t": 1, "id": 5}, [["new"]] if sees_it else c_of(dep, data, (1, 5)))])
    out = verdict(dep, data, mix, w, t)
    assert out["wrong_answers"]["value"] == 0, out["examples"]
    assert out["reads_with_concurrent_writers"]["value"] == 1
    assert out["wrong_answers"] == {"value": 0, "limit": 0, "of": 2}


def test_reads_from_two_snapshots_are_rejected(dep, data, mix):
    w = attempt(1, 0.0, [("non_index_update", {"t": 1, "id": 5, "c": "x5"}, 1),
                         ("non_index_update", {"t": 1, "id": 6, "c": "x6"}, 1)])     # COMMIT 3.0-3.5
    t = attempt(2, 2.8, [("point_select", {"t": 1, "id": 5}, [["x5"]]),                # returns 4.3
                         ("point_select", {"t": 1, "id": 6}, c_of(dep, data, (1, 6)))])
    out = verdict(dep, data, mix, w, t)
    assert out["correct"] is False
    assert out["wrong_answers"]["value"] == 1
    assert out["rows_read_back"] == {"value": 0, "at_least": 1}     # nothing read back here: false on that too
    # either snapshot alone is sound
    for answers in ([["x5"]], [["x6"]]), (c_of(dep, data, (1, 5)), c_of(dep, data, (1, 6))):
        t = attempt(2, 2.8, [("point_select", {"t": 1, "id": 5}, answers[0]),
                             ("point_select", {"t": 1, "id": 6}, answers[1])])
        assert verdict(dep, data, mix, w, t)["wrong_answers"]["value"] == 0


def test_a_stale_read_is_rejected(dep, data, mix):
    w = attempt(1, 0.0, [("index_update", {"t": 2, "id": 7}, 1)])                    # COMMIT 2.0-2.5
    k = dep.load_state(data).get((2, 7))[0]
    fresh = attempt(2, 3.0, [("sum_range", {"t": 2, "a": 7, "b": 7}, [[str(k + 1)]])])
    assert verdict(dep, data, mix, w, fresh)["wrong_answers"]["value"] == 0
    stale = attempt(2, 3.0, [("sum_range", {"t": 2, "a": 7, "b": 7}, [[str(k)]])])
    out = verdict(dep, data, mix, w, stale)
    assert out["correct"] is False and out["wrong_answers"]["value"] == 1


def test_a_snapshot_cannot_hold_a_commit_sent_after_its_first_answer(dep, data, mix):
    t = attempt(2, 0.0, [("point_select", {"t": 1, "id": 9}, [["later"]])])          # first answer 1.5
    w = attempt(1, 0.5, [("non_index_update", {"t": 1, "id": 9, "c": "later"}, 1)])  # COMMIT 2.5-3.0
    assert verdict(dep, data, mix, w, t)["wrong_answers"]["value"] == 1


def test_affected_rows_and_own_writes(dep, data, mix):
    # a delete then an insert of one id in one transaction, then a read of it
    body = [("delete", {"t": 1, "id": 3}, 1),
            ("insert", {"t": 1, "id": 3, "k": 4, "c": "c3", "pad": "p3"}, 1),
            ("point_select", {"t": 1, "id": 3}, [["c3"]])]
    assert verdict(dep, data, mix, attempt(1, 0.0, body))["wrong_answers"] == {"value": 0, "limit": 0, "of": 3}
    wrong = body[:1] + [("insert", {"t": 1, "id": 3, "k": 4, "c": "c3", "pad": "p3"}, 0)] + body[2:]
    out = verdict(dep, data, mix, attempt(1, 0.0, wrong))
    assert out["correct"] is False and "rows affected" in out["examples"][0]
    # an insert of an id that is there has to fail
    out = verdict(dep, data, mix, attempt(1, 0.0, [("insert", {"t": 1, "id": 4, "k": 1, "c": "c", "pad": "p"}, 1)]))
    assert out["correct"] is False and "want an error" in out["examples"][0]


def test_increments_fold_at_commit_in_either_order(dep, data, mix):
    """Two concurrent committed `k = k + 1` of one row: both count, as
    pessimistic DML reads the latest committed value."""
    a = attempt(1, 0.0, [("index_update", {"t": 1, "id": 2}, 1)])                    # COMMIT 2.0-2.5
    b = attempt(2, 0.2, [("index_update", {"t": 1, "id": 2}, 1)])                    # COMMIT 2.2-2.7
    k, c, pad = dep.load_state(data).get((1, 2))
    out = verdict(dep, data, mix, a, b, read_back(10.0, [((1, 2), row_text(dep, data, (1, 2), (k + 2, c, pad)))]))
    assert out["correct"] is True, out["examples"]
    out = verdict(dep, data, mix, a, b, read_back(10.0, [((1, 2), row_text(dep, data, (1, 2), (k + 1, c, pad)))]))
    assert out["correct"] is False and out["read_back_mismatches"]["value"] == 1


def test_concurrent_overwrites_allow_either_final_value(dep, data, mix):
    a = attempt(1, 0.0, [("non_index_update", {"t": 1, "id": 8, "c": "A"}, 1)])      # COMMIT 2.0-2.5
    b = attempt(2, 0.2, [("non_index_update", {"t": 1, "id": 8, "c": "B"}, 1)])      # COMMIT 2.2-2.7
    k, _, pad = dep.load_state(data).get((1, 8))
    for last in ("A", "B"):
        rb = read_back(10.0, [((1, 8), row_text(dep, data, (1, 8), (k, last, pad)))])
        assert verdict(dep, data, mix, a, b, rb)["correct"] is True
    # where a's COMMIT returned before b's was sent, only b's value is final
    b = attempt(2, 1.6, [("non_index_update", {"t": 1, "id": 8, "c": "B"}, 1)])      # COMMIT 3.6-4.1
    rb = read_back(10.0, [((1, 8), row_text(dep, data, (1, 8), (k, "A", pad)))])
    assert verdict(dep, data, mix, a, b, rb)["correct"] is False


# ---- the read-back ------------------------------------------------------
def test_a_dropped_committed_write_is_rejected_at_read_back(dep, data, mix):
    w = attempt(1, 0.0, [("non_index_update", {"t": 2, "id": 11, "c": "kept"}, 1)])
    k, _, pad = dep.load_state(data).get((2, 11))
    kept = read_back(5.0, [((2, 11), row_text(dep, data, (2, 11), (k, "kept", pad)))])
    out = verdict(dep, data, mix, w, kept)
    assert out["correct"] is True and out["rows_read_back"] == {"value": 1, "at_least": 1}
    dropped = read_back(5.0, [((2, 11), row_text(dep, data, (2, 11)))])
    out = verdict(dep, data, mix, w, dropped)
    assert out["correct"] is False and out["read_back_mismatches"] == {"value": 1, "limit": 0, "of": 1}


def test_an_applied_aborted_write_is_rejected_at_read_back(dep, data, mix):
    a = attempt(1, 0.0, [("delete", {"t": 1, "id": 12}, 1),
                         ("insert", {"t": 1, "id": 12, "k": 3, "c": "ghost", "pad": "p"}, 1),
                         ("index_update", {"t": 1, "id": 13}, None)], outcome="aborted")
    assert a.error is not None
    absent = read_back(9.0, [((1, 12), row_text(dep, data, (1, 12)))])
    out = verdict(dep, data, mix, a, absent)
    assert out["correct"] is True, out["examples"]
    assert "at_least" not in out["rows_read_back"]         # nothing was committed
    applied = read_back(9.0, [((1, 12), [["12", "3", "ghost", "p"]])])
    out = verdict(dep, data, mix, a, applied)
    assert out["correct"] is False and out["read_back_mismatches"]["value"] == 1


def test_an_unknown_commit_may_or_may_not_be_there(dep, data, mix):
    u = attempt(1, 0.0, [("non_index_update", {"t": 1, "id": 14, "c": "maybe"}, 1)], outcome="unknown")
    k, _, pad = dep.load_state(data).get((1, 14))
    for c in ("maybe", dep.load_state(data).get((1, 14))[1]):
        rb = read_back(9.0, [((1, 14), row_text(dep, data, (1, 14), (k, c, pad)))])
        assert verdict(dep, data, mix, u, rb)["read_back_mismatches"]["value"] == 0


def test_read_back_statements_cover_every_value_of_k(dep, data, mix):
    h = judge.History(dep, data, mix, "lost_commit")
    h.record(attempt(1, 0.0, [("index_update", {"t": 1, "id": 2}, 1), ("index_update", {"t": 1, "id": 2}, 1),
                              ("delete", {"t": 2, "id": 3}, 1)]))
    h.record(attempt(2, 0.0, [("non_index_update", {"t": 1, "id": 4, "c": "z"}, 1),
                              ("index_update", {"t": 1, "id": 5}, None)], outcome="aborted"))
    steps = h.read_back_steps([])
    k2, k4 = dep.load_state(data).get((1, 2))[0], dep.load_state(data).get((1, 4))[0]
    k3 = dep.load_state(data).get((2, 3))[0]
    assert steps[:3] == [("pk_read_back", {"t": 1, "id": 2}), ("pk_read_back", {"t": 1, "id": 4}),
                         ("pk_read_back", {"t": 2, "id": 3})]
    assert {(p["t"], p["k"]) for _, p in steps[3:]} == {(1, k2), (1, k2 + 1), (1, k2 + 2), (1, k4), (2, k3)}


def test_the_control_leaves_out_each_clients_last_commit(dep, data, mix):
    first = attempt(1, 0.0, [("non_index_update", {"t": 1, "id": 20, "c": "one"}, 1)])
    last = attempt(1, 5.0, [("non_index_update", {"t": 1, "id": 21, "c": "two"}, 1)])
    state = dep.load_state(data)
    rows = []
    for key, c in (((1, 20), "one"), ((1, 21), "two")):
        k, _, pad = state.get(key)
        rows.append((key, row_text(dep, data, key, (k, c, pad))))
    out = verdict(dep, data, mix, first, last, read_back(20.0, rows))
    assert out["correct"] is True
    out = verdict(dep, data, mix, first, last, read_back(20.0, rows), control=True)
    assert out["correct"] is False and out["read_back_mismatches"]["value"] == 1


# ---- the cap ------------------------------------------------------------
def test_a_history_over_the_cap_is_counted(dep, data, mix):
    """13 writers of 13 rows that one range reads, all concurrent with it:
    2**13 snapshots, more than the cap."""
    writers = [attempt(10 + i, 0.0, [("non_index_update", {"t": 1, "id": 30 + i, "c": f"w{i}"}, 1)])
               for i in range(13)]                                                 # COMMIT 2.0-2.5 each
    state = dep.load_state(data)
    rows = [[state.get((1, i))[1]] for i in range(30, 43)]
    t = attempt(2, 1.9, [("simple_range", {"t": 1, "a": 30, "b": 42}, rows)])      # first answer 3.4
    out = verdict(dep, data, mix, *writers, t)
    assert 2 ** 13 > judge.CAP
    assert out["histories_over_cap"]["value"] == 1 and out["correct"] is False
    # twelve are under it, and the same answers pass
    out = verdict(dep, data, mix, *writers[:12], attempt(2, 1.9, [("simple_range", {"t": 1, "a": 30, "b": 41},
                                                                   rows[:12])]))
    assert out["histories_over_cap"] == {"value": 0, "limit": 0, "of": 13} and out["wrong_answers"]["value"] == 0


# ---- the program on the CPU ---------------------------------------------
@pytest.fixture(scope="module")
def served(dep):
    from tidb_tpu.server import MiniClient, MySQLServer

    sizes = {"tables": 2, "table_size": 2000, "insert_batch_rows": 500}
    data = dep.generate(sizes, seed=2**31 + 41)
    statements = _json(CONFIG_DIR, "statements.json")
    srv = MySQLServer(port=0)
    srv.start_background()
    conns = [MiniClient(srv.host, srv.port, timeout=600) for _ in range(2)]
    try:
        dep.load(conns[0], data, sizes, lambda **line: None)
        yield data, statements, conns
    finally:
        for c in conns:
            c.close()
        srv.close()


def _send(conn, statements, name, params):
    got = conn.query(statements[name].format(**params))
    return got[1] if isinstance(got, tuple) else got


def test_write_statements_equal_the_reference(dep, served):
    data, statements, (conn, _) = served
    state = dep.load_state(data)
    body = [("index_update", {"t": 1, "id": 5}), ("index_update", {"t": 1, "id": 5}),
            ("non_index_update", {"t": 2, "id": 6, "c": "0" * 11 + "-" + "1" * 11}),
            ("delete", {"t": 1, "id": 7}), ("delete", {"t": 1, "id": 7}),
            ("insert", {"t": 1, "id": 7, "k": 3, "c": "new-c", "pad": "new-pad"}),
            ("index_update", {"t": 2, "id": 2001}),                       # no such row: 0 affected
            ("point_select", {"t": 1, "id": 7}), ("sum_range", {"t": 1, "a": 1, "b": 10})]
    conn.query("begin")
    for name, params in body:
        have = _send(conn, statements, name, params)
        if dep.writes(name):
            assert have == dep.apply(name, params, state), (name, params)
        else:
            assert dep.mismatch(name, dep.reference_at(name, params, state), have) is None
    conn.query("commit")
    with pytest.raises(Exception) as err:     # the reference's None: an insert of an id that is there fails
        _send(conn, statements, "insert", {"t": 1, "id": 7, "k": 3, "c": "x", "pad": "y"})
    assert getattr(err.value, "code", None) == 1062 or "duplicate" in str(err.value)
    assert dep.apply("insert", {"t": 1, "id": 7, "k": 3, "c": "x", "pad": "y"}, state) is None
    # read back by primary key and through k_1, over the state
    values = {key: {state.get(key)} for key in ((1, 5), (1, 7), (2, 6))}
    for name, params in dep.read_back(values):
        want = dep.reference_at(name, params, state)
        assert dep.mismatch(name, want, _send(conn, statements, name, params)) is None, (name, params)
    assert dep.reference_at("pk_read_back", {"t": 1, "id": 7}, state) == [["7", "3", "new-c", "new-pad"]]


def test_a_conflict_is_errno_1105_and_the_aborted_attempt_leaves_nothing(dep, served):
    """Two connections write one row: today's program answers the second at
    once with errno 1105 (no lock wait; TiDB answers 1205, 1213 or 9007,
    which the mix restarts), and what the aborted attempt wrote before
    stays out."""
    data, statements, (a, b) = served
    state = dep.load_state(data)
    a.query("begin")
    b.query("begin")
    assert _send(a, statements, "index_update", {"t": 2, "id": 40}) == 1
    assert _send(b, statements, "non_index_update", {"t": 2, "id": 41, "c": "lost"}) == 1
    with pytest.raises(Exception) as err:
        _send(b, statements, "index_update", {"t": 2, "id": 40})
    assert err.value.code == 1105
    b.query("rollback")
    a.query("commit")
    dep.apply("index_update", {"t": 2, "id": 40}, state)
    for key in ((2, 40), (2, 41)):
        params = {"t": key[0], "id": key[1]}
        have = _send(a, statements, "pk_read_back", params)
        assert have == dep.reference_at("pk_read_back", params, state), key
    assert _send(a, statements, "pk_read_back", {"t": 2, "id": 41})[0][2] == data["c"][1][40].decode()
