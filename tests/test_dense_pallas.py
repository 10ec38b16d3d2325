"""Parity tests for the one-pass Pallas small-G kernel (ops/dense_pallas.py)
against the sort kernel, run in Pallas interpret mode on CPU (the compiled
path runs on the TPU in chip_smoke.py's Q1 and in the cell tpch_q1q6_params)."""

import numpy as np
import pytest
import jax.numpy as jnp

from tidb_tpu.expr import AggDesc, col
from tidb_tpu.ops.aggregate import group_aggregate
from tidb_tpu.types import Datum, MyDecimal, new_decimal, new_longlong, new_varchar
from tidb_tpu.chunk import Chunk

from test_ops import eval_vals, make_data


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("TIDB_TPU_PALLAS", "interpret")


def _assert_same(ref, pal):
    assert bool(pal.overflow) == bool(ref.overflow)
    ng = int(ref.n_groups)
    assert int(pal.n_groups) == ng
    assert jnp.array_equal(ref.group_rep[:ng], pal.group_rep[:ng])
    for rs, ps in zip(ref.states, pal.states):
        for (rv, rn), (pv, pn) in zip(rs, ps):
            assert jnp.array_equal(rv[:ng], pv[:ng]), (rv[:ng], pv[:ng])
            assert jnp.array_equal(rn[:ng], pn[:ng])


def _pallas_engaged(group_bys, aggs):
    from tidb_tpu.ops.dense_pallas import dense_pallas_eligible, pallas_mode

    return pallas_mode() == "interpret" and dense_pallas_eligible(
        group_bys, aggs, merge=False
    )


class TestDensePallas:
    def test_int_key_count_sum_avg(self):
        fts, ch = make_data(n=300, k_card=5)
        db, vals = eval_vals(fts, ch, [col(0, fts[0]), col(1, fts[1])])
        g, d = vals
        aggs = [
            (AggDesc("count", ()), []),
            (AggDesc("count", (col(1, fts[1]),)), [d]),
            (AggDesc("sum", (col(1, fts[1]),)), [d]),
            (AggDesc("avg", (col(1, fts[1]),)), [d]),
        ]
        assert _pallas_engaged([g], aggs)
        rng = np.random.default_rng(3)
        valid = db.row_valid & jnp.asarray(rng.random(300) < 0.8)
        ref = group_aggregate([g], aggs, valid, 64)
        pal = group_aggregate([g], aggs, valid, 64, small_groups=8)
        _assert_same(ref, pal)

    def test_string_key_with_nulls(self):
        fts, ch = make_data(n=257, k_card=4, null_p=0.25)
        db, vals = eval_vals(fts, ch, [col(3, fts[3]), col(1, fts[1])])
        s, d = vals
        aggs = [(AggDesc("count", ()), []), (AggDesc("sum", (col(1, fts[1]),)), [d])]
        assert _pallas_engaged([s], aggs)
        ref = group_aggregate([s], aggs, db.row_valid, 64)
        pal = group_aggregate([s], aggs, db.row_valid, 64, small_groups=8)
        _assert_same(ref, pal)

    def test_two_keys(self):
        fts, ch = make_data(n=300, k_card=3)
        db, vals = eval_vals(fts, ch, [col(0, fts[0]), col(3, fts[3]), col(1, fts[1])])
        g, s, d = vals
        aggs = [(AggDesc("sum", (col(1, fts[1]),)), [d]), (AggDesc("count", ()), [])]
        assert _pallas_engaged([g, s], aggs)
        ref = group_aggregate([g, s], aggs, db.row_valid, 64)
        pal = group_aggregate([g, s], aggs, db.row_valid, 64, small_groups=32)
        _assert_same(ref, pal)

    def test_overflow_when_hint_wrong(self):
        fts, ch = make_data(n=200, k_card=30, null_p=0.0)
        db, vals = eval_vals(fts, ch, [col(0, fts[0])])
        (g,) = vals
        aggs = [(AggDesc("count", ()), [])]
        assert _pallas_engaged([g], aggs)
        pal = group_aggregate([g], aggs, db.row_valid, 64, small_groups=8)
        assert bool(pal.overflow)

    def test_value_range_overflow(self):
        ft = new_longlong()
        big = 1 << 50
        rows = [[Datum.i64(1), Datum.i64(big)], [Datum.i64(1), Datum.i64(3)]]
        ch = Chunk.from_rows([ft, ft], rows)
        db, vals = eval_vals([ft, ft], ch, [col(0, ft), col(1, ft)])
        g, v = vals
        aggs = [(AggDesc("sum", (col(1, ft),)), [v])]
        assert _pallas_engaged([g], aggs)
        pal = group_aggregate([g], aggs, db.row_valid, 64, small_groups=8)
        assert bool(pal.overflow)

    def test_negative_values_exact(self):
        ft = new_longlong()
        rng = np.random.default_rng(0)
        rows = []
        for _ in range(1500):
            rows.append([
                Datum.i64(int(rng.integers(0, 6))),
                Datum.i64(int(rng.integers(-(2**45), 2**45))),
            ])
        ch = Chunk.from_rows([ft, ft], rows)
        db, vals = eval_vals([ft, ft], ch, [col(0, ft), col(1, ft)])
        g, v = vals
        aggs = [(AggDesc("sum", (col(1, ft),)), [v]), (AggDesc("avg", (col(1, ft),)), [v])]
        assert _pallas_engaged([g], aggs)
        ref = group_aggregate([g], aggs, db.row_valid, 64)
        pal = group_aggregate([g], aggs, db.row_valid, 64, small_groups=8)
        _assert_same(ref, pal)

    def test_row_count_bound_gates_eligibility(self):
        """ADVICE r5 medium, pinned at the boundary: the 12-bit limb
        accumulators wrap past int32 around 2^26 rows, so eligibility is
        a strict n < MAX_ROWS — the old docstring's 2^31 claim was wrong.
        The 2^26 case uses zero-copy broadcast views: the gate must reject
        on SHAPE alone, before any value work could materialize 512MB."""
        from tidb_tpu.expr.compile import CompVal
        from tidb_tpu.ops.dense_pallas import MAX_ROWS, dense_pallas_eligible

        assert MAX_ROWS == 1 << 26  # (N/128 rows) * 4095 < 2^31 -> N < ~2^26
        n = MAX_ROWS
        big_v = np.broadcast_to(np.int64(0), (n,))
        big_n = np.broadcast_to(False, (n,))
        g = CompVal(big_v, big_n, new_longlong())
        aggs = [(AggDesc("count", ()), [])]
        assert not dense_pallas_eligible([g], aggs, merge=False)

    def test_row_count_bound_is_strict(self, monkeypatch):
        """Boundary semantics (< not <=) without 512MB allocations: shrink
        the bound and check both sides of it."""
        import tidb_tpu.ops.dense_pallas as dp
        from tidb_tpu.expr.compile import CompVal

        fts, ch = make_data(n=64, k_card=4)
        db, vals = eval_vals(fts, ch, [col(0, fts[0]), col(1, fts[1])])
        g, d = vals
        aggs = [(AggDesc("sum", (col(1, fts[1]),)), [d])]
        n = g.null.shape[0]
        monkeypatch.setattr(dp, "MAX_ROWS", n)
        assert not dp.dense_pallas_eligible([g], aggs, merge=False)
        monkeypatch.setattr(dp, "MAX_ROWS", n + 1)
        assert dp.dense_pallas_eligible([g], aggs, merge=False)

    def test_ineligible_falls_back(self):
        """min/max and DOUBLE args route to the XLA dense kernel unchanged."""
        fts, ch = make_data(n=120, k_card=4)
        db, vals = eval_vals(fts, ch, [col(0, fts[0]), col(1, fts[1]), col(2, fts[2])])
        g, d, r = vals
        aggs = [
            (AggDesc("min", (col(1, fts[1]),)), [d]),
            (AggDesc("avg", (col(2, fts[2]),)), [r]),
        ]
        assert not _pallas_engaged([g], aggs)
        ref = group_aggregate([g], aggs, db.row_valid, 64)
        pal = group_aggregate([g], aggs, db.row_valid, 64, small_groups=8)
        ng = int(ref.n_groups)
        assert int(pal.n_groups) == ng
