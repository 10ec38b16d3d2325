"""Fused join+stream-agg kernel (ops/joinagg.py): differential parity vs
the row-at-a-time oracle AND vs the general hash_join+group_aggregate path,
plus the overflow contracts (duplicate build keys -> join overflow -> the
driver's unique-hint drop lands on the general kernel; group capacity ->
grow) — the shapes TPC-H Q3 rides in chip_smoke.py (ref:
pkg/executor/join/hash_join_v2.go, agg_stream_executor.go)."""

import numpy as np
import pytest

import jax


@pytest.fixture(autouse=True, scope="module")
def _fresh_jax_caches():
    """jax 0.4.x: jitted subfunctions cached by earlier tests under a
    different x64 weak-type state poison the Pallas kernels' lowering
    (i32/i64 verifier mismatch). A clean cache per kernel module keeps
    these hermetic; newer jax keys the cache correctly."""
    jax.clear_caches()

from tidb_tpu.chunk import Chunk
from tidb_tpu.exec import (
    Aggregation,
    ColumnInfo,
    DAGRequest,
    Join,
    ProgramCache,
    Selection,
    TableScan,
    run_dag_on_chunks,
    run_dag_reference,
)
from tidb_tpu.exec.executor import datum_group_key
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.types import Datum, new_longlong

LL = new_longlong()
BOOL = new_longlong(notnull=True)


def canon(rows):
    return sorted(tuple(datum_group_key(d) for d in r) for r in rows)


def _mk(fts, cols_np):
    rows = []
    n = len(cols_np[0])
    for i in range(n):
        rows.append([Datum.NULL if c[i] is None else Datum.i64(int(c[i])) for c in cols_np])
    return Chunk.from_rows(fts, rows)


def _dag(aggs, build_unique=True, probe_sel=None, group_key=0):
    pfts = [LL, LL]  # okey, v
    bfts = [LL, LL]  # okey, w
    ps = TableScan(1, (ColumnInfo(1, pfts[0]), ColumnInfo(2, pfts[1])))
    bs = TableScan(2, (ColumnInfo(1, bfts[0]), ColumnInfo(2, bfts[1])))
    j = Join(build=(bs,), probe_keys=(col(group_key, pfts[0]),),
             build_keys=(col(0, bfts[0]),), join_type="inner",
             build_unique=build_unique)
    agg = Aggregation(group_by=(col(group_key, pfts[0]),), aggs=tuple(aggs))
    execs = [ps]
    if probe_sel is not None:
        execs.append(probe_sel)
    execs += [j, agg]
    n_out = len(aggs) + 1
    return DAGRequest(tuple(execs), output_offsets=tuple(range(n_out)))


def _fused_calls(monkeypatch):
    """Spy on BOTH fused kernels (packed int fast path + general
    stream-agg path); either counts as the fused route."""
    import tidb_tpu.ops.joinagg as ja

    calls = []
    og, op = ja.join_stream_agg, ja.packed_join_groupsum

    def spy_g(*a, **k):
        calls.append("general")
        return og(*a, **k)

    def spy_p(*a, **k):
        calls.append("packed")
        return op(*a, **k)

    monkeypatch.setattr(ja, "join_stream_agg", spy_g)
    monkeypatch.setattr(ja, "packed_join_groupsum", spy_p)
    return calls


def test_fused_parity_and_trigger(monkeypatch):
    calls = _fused_calls(monkeypatch)
    rng = np.random.default_rng(0)
    n, nb = 600, 40
    probe = _mk([LL, LL], [rng.integers(0, 64, n), rng.integers(0, 100, n)])
    build = _mk([LL, LL], [np.arange(nb), rng.integers(0, 9, nb)])
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("count", ()),
                AggDesc("min", (col(1, LL),)), AggDesc("first_row", (col(1, LL),))])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=256)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert calls, "fused join+agg path did not trigger"


def test_fused_null_keys_excluded(monkeypatch):
    calls = _fused_calls(monkeypatch)
    probe = _mk([LL, LL], [[1, None, 2, None, 1], [10, 20, 30, 40, 50]])
    build = _mk([LL, LL], [[1, 2, 3], [7, 8, 9]])
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("count", ())])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=64)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert calls


def test_fused_with_probe_selection(monkeypatch):
    calls = _fused_calls(monkeypatch)
    rng = np.random.default_rng(1)
    n = 500
    probe = _mk([LL, LL], [rng.integers(0, 32, n), rng.integers(0, 100, n)])
    build = _mk([LL, LL], [np.arange(24), rng.integers(0, 9, 24)])
    sel = Selection((func("gt", BOOL, col(1, LL), lit(40, LL)),))
    dag = _dag([AggDesc("avg", (col(1, LL),)), AggDesc("max", (col(1, LL),))],
               probe_sel=sel)
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=128)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert calls


def test_duplicate_build_keys_fall_back_correctly(monkeypatch):
    """A false unique-build promise: the fused kernel raises the join
    overflow, the driver drops the hint and the general kernel (fan-out
    expansion) still returns the right multiset."""
    calls = _fused_calls(monkeypatch)
    probe = _mk([LL, LL], [[5, 5, 6, 7], [1, 2, 3, 4]])
    build = _mk([LL, LL], [[5, 5, 7, 8], [100, 200, 300, 400]])
    dag = _dag([AggDesc("count", ()), AggDesc("sum", (col(1, LL),))])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=64)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert calls, "fused path must run first (and overflow)"
    # key 5 matches two build rows -> count doubles through expansion
    assert any(int(r[0].val) == 4 for r in got.rows())


def test_mostly_unmatched_probes(monkeypatch):
    calls = _fused_calls(monkeypatch)
    rng = np.random.default_rng(2)
    n = 400
    probe = _mk([LL, LL], [rng.integers(0, 1000, n), rng.integers(0, 50, n)])
    build = _mk([LL, LL], [np.arange(5), np.arange(5)])
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("count", ())])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=2048)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert calls


def test_group_capacity_overflow_grows(monkeypatch):
    """More distinct matched keys than capacity: the group flag drives the
    retry ladder, and the resolved run matches the oracle."""
    calls = _fused_calls(monkeypatch)
    rng = np.random.default_rng(3)
    n = 800
    probe = _mk([LL, LL], [rng.integers(0, 300, n), rng.integers(0, 10, n)])
    build = _mk([LL, LL], [np.arange(300), np.zeros(300)])
    dag = _dag([AggDesc("sum", (col(1, LL),))])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=16)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    # the packed path has no group capacity at all (boundary-layout
    # outputs); the general fused path would retry through the ladder
    assert calls, "fused path did not trigger"


def test_filtered_runs_do_not_trip_capacity():
    """Build∪probe key runs that contribute nothing must not raise the
    group overflow (the precise surviving-row condition): 4 output groups
    through a capacity of 8 despite ~100 distinct unmatched probe keys."""
    from tidb_tpu.exec.builder import build_program
    from tidb_tpu.chunk import to_device_batch

    rng = np.random.default_rng(4)
    probe = _mk([LL, LL], [
        np.concatenate([rng.integers(0, 4, 64), rng.integers(1000, 1100, 100)]),
        rng.integers(0, 10, 164),
    ])
    build = _mk([LL, LL], [np.arange(4), np.arange(4)])
    dag = _dag([AggDesc("sum", (col(1, LL),))])
    batches = [to_device_batch(c, capacity=256) for c in (probe, build)]
    prog = build_program(dag, tuple(b.capacity for b in batches), group_capacity=8)
    packed, valid, n_out, (g_ovf, j_ovf, t_ovf, *_needs), _ = prog.host(*batches)
    assert not bool(g_ovf) and not bool(j_ovf)
    assert int(n_out) == 4


def test_packed_negative_values_and_nulls(monkeypatch):
    """Negative agg values exercise the non-negativity shift unwind; NULL
    args exercise the per-combo non-null count lanes."""
    calls = _fused_calls(monkeypatch)
    rng = np.random.default_rng(5)
    n = 500
    vals = [int(v) if v % 3 else None for v in rng.integers(-10**6, 10**6, n)]
    probe = _mk([LL, LL], [rng.integers(0, 40, n), vals])
    build = _mk([LL, LL], [np.arange(30), np.zeros(30)])
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("avg", (col(1, LL),)),
                AggDesc("count", (col(1, LL),)), AggDesc("count", ())])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=128)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert "packed" in calls


def test_packed_chain_three_tables(monkeypatch):
    """The q3 shape: lineitem joins orders joins customer, GROUP BY okey —
    the membership chain plus packed groupsum, diffed against the oracle."""
    calls = _fused_calls(monkeypatch)
    rng = np.random.default_rng(6)
    nl, no, nc = 800, 100, 20
    lfts = [LL, LL]
    ofts = [LL, LL]
    cfts = [LL, LL]
    ls = TableScan(1, (ColumnInfo(1, lfts[0]), ColumnInfo(2, lfts[1])))
    os_ = TableScan(2, (ColumnInfo(1, ofts[0]), ColumnInfo(2, ofts[1])))
    cs = TableScan(3, (ColumnInfo(1, cfts[0]), ColumnInfo(2, cfts[1])))
    cust_sel = Selection((func("eq", BOOL, col(1, cfts[1]), lit(1, LL)),))
    inner = Join(build=(cs, cust_sel), probe_keys=(col(1, ofts[1]),),
                 build_keys=(col(0, cfts[0]),), join_type="inner", build_unique=True)
    outer = Join(build=(os_, inner), probe_keys=(col(0, lfts[0]),),
                 build_keys=(col(0, ofts[0]),), join_type="inner", build_unique=True)
    lsel = Selection((func("gt", BOOL, col(1, lfts[1]), lit(5, LL)),))
    agg = Aggregation(group_by=(col(0, lfts[0]),),
                      aggs=(AggDesc("sum", (col(1, lfts[1]),)), AggDesc("count", ())))
    dag = DAGRequest((ls, lsel, outer, agg), output_offsets=(0, 1, 2))
    lchunk = _mk(lfts, [rng.integers(0, no, nl), rng.integers(0, 100, nl)])
    ochunk = _mk(ofts, [np.arange(no), rng.integers(0, nc, no)])
    cchunk = _mk(cfts, [np.arange(nc), rng.integers(0, 3, nc)])
    got = run_dag_on_chunks(dag, [lchunk, ochunk, cchunk], group_capacity=256)
    want = run_dag_reference(dag, [lchunk, ochunk, cchunk])
    assert canon(got.rows()) == canon(want)
    assert "packed" in calls


def test_packed_int32_min_key_no_phantom_join(monkeypatch):
    """ADVICE r5 high, pinned end-to-end: jnp.abs(INT32_MIN) wraps to
    INT32_MIN (negative) and used to PASS the packed range gate, so key
    -2^31 shifted left wrapped to packed key 0 and silently joined as a
    phantom key-0 group with no overflow flag. The int64-domain range
    check must flag it instead, and the driver's retry must land on a
    correct general-kernel run (same contract as any out-of-range key)."""
    calls = _fused_calls(monkeypatch)
    INT32_MIN = -(1 << 31)
    # build key INT32_MIN + probe key 0: the ADVICE repro — before the fix
    # probe rows with key 0 joined the INT32_MIN build row as key 0
    probe = _mk([LL, LL], [[0, 0, 5, INT32_MIN], [10, 20, 30, 40]])
    build = _mk([LL, LL], [[INT32_MIN, 5], [7, 8]])
    dag = _dag([AggDesc("sum", (col(1, LL),)), AggDesc("count", ())])
    got = run_dag_on_chunks(dag, [probe, build], group_capacity=64)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert "packed" in calls, "packed path must run (and overflow-retry)"
    # sanity on the oracle itself: key 0 must NOT appear (no build row 0),
    # and the INT32_MIN probe row joins its real build row
    keys = {r[-1][1] for r in canon(want)}
    assert 0 not in keys and INT32_MIN in keys and 5 in keys


def test_membership_chain_int32_min_payload_key(monkeypatch):
    """The same wrap through membership_chain (the 3-table packed chain):
    an INT32_MIN key on the chain's inner join must not alias key 0."""
    import jax.numpy as jnp

    from tidb_tpu.ops.joinagg import membership_chain

    INT32_MIN = -(1 << 31)
    outer = jnp.asarray([0, INT32_MIN, 7], jnp.int64)
    inner = jnp.asarray([INT32_MIN, 7], jnp.int64)
    ok = jnp.ones(3, bool)
    iok = jnp.ones(2, bool)
    payload = jnp.asarray([1, 2, 3], jnp.int64)
    _pay, _ok_out, overflow = membership_chain(outer, ok, inner, iok, payload)
    # out-of-range key must raise the overflow flag -> general-kernel retry
    assert bool(overflow)


def test_packed_wide_key_range_falls_back(monkeypatch):
    """Keys spanning more than 2^30 trip the packed range check; the
    driver's retry lands on a correct general-path run."""
    calls = _fused_calls(monkeypatch)
    probe = _mk([LL, LL], [[0, 1 << 40, 5], [10, 20, 30]])
    build = _mk([LL, LL], [[0, 1 << 40], [0, 0]])
    dag = _dag([AggDesc("sum", (col(1, LL),))])
    # a cache of its own: the shared one hands this plan shape the input
    # rung an earlier test picked, with its program, and nothing is traced
    got = run_dag_on_chunks(dag, [probe, build], cache=ProgramCache(), group_capacity=64)
    want = run_dag_reference(dag, [probe, build])
    assert canon(got.rows()) == canon(want)
    assert "packed" in calls, "packed path must run (and overflow)"
