"""Radix-partitioned hash join (ISSUE 13): partition-count sweep vs the
oracle, the skewed-key escape hatch, Pallas-vs-general byte-equality over
the full key-type matrix (signed/unsigned incl. INT32_MIN boundary keys,
NULLs), capacity-ladder rung reuse (retries hit cached rungs — zero
recompiles, asserted via ProgramCache stats), the never-starve
overflow-degrade contract, and a mesh-tier join run matching the pool
tier, plus EXPLAIN ANALYZE / TRACE `join_radix` attribution."""

import numpy as np
import pytest

import jax.numpy as jnp

from tidb_tpu.chunk import Chunk, to_device_batch
from tidb_tpu.exec import (
    Aggregation,
    ColumnInfo,
    DAGRequest,
    Join,
    TableScan,
    run_dag_reference,
)
from tidb_tpu.exec.builder import ProgramCache, build_program
from tidb_tpu.exec.executor import datum_group_key, decode_outputs, drive_program_info
from tidb_tpu.exec.ladder import RUNG_BASE, next_rung, rung_for, rungs_up_to
from tidb_tpu.expr import AggDesc, col
from tidb_tpu.expr.compile import CompVal
from tidb_tpu.ops.radix_join import radix_hash_join, radix_plan
from tidb_tpu.types import Datum, new_longlong

LL = new_longlong()
NN = new_longlong(notnull=True)


def _cv(vals, nulls, ft=LL):
    vals = np.asarray(vals, np.int64)
    nulls = np.zeros(len(vals), bool) if nulls is None else np.asarray(nulls, bool)
    return CompVal(jnp.asarray(vals), jnp.asarray(nulls), ft)


def _ref_unique_join(bk, b_ok, pk, p_ok):
    """(build_idx, matched) oracle: first build row per key; None on dup."""
    table = {}
    dup = False
    for i, (k, ok) in enumerate(zip(bk, b_ok)):
        if ok:
            if k in table:
                dup = True
            else:
                table[k] = i
    idx = np.full(len(pk), -1, np.int64)
    for j, (k, ok) in enumerate(zip(pk, p_ok)):
        if ok and k in table:
            idx[j] = table[k]
    return idx, dup


def _run_kernel(bk, bnull, pk, pnull, plan, strategy, ft=LL, jc=4096):
    bkv, pkv = _cv(bk, bnull, ft), _cv(pk, pnull, ft)
    nb, np_ = len(bk), len(pk)
    res, esc = radix_hash_join(
        [bkv], [pkv], jnp.ones(nb, bool), jnp.ones(np_, bool),
        "inner", jc, plan, strategy=strategy,
    )
    return (np.asarray(res.build_idx), np.asarray(res.out_valid),
            bool(res.overflow), int(res.need), int(esc))


class TestLadder:
    def test_rungs(self):
        assert rung_for(0) == RUNG_BASE
        assert rung_for(64) == 64
        assert rung_for(65) == 128
        assert rung_for(4096) == 4096
        assert next_rung(64) == 256
        assert rungs_up_to(512) == [64, 128, 256, 512]

    def test_overflow_step_policy(self):
        from tidb_tpu.exec.ladder import RUNG_MAX, overflow_step

        # pure capacity miss: direct jump, hints kept
        gc, jc, drop = overflow_step(64, 64, True, True, 700, 4096)
        assert (gc, jc, drop) == (1024, 4096, False)
        # hintless join overflow: step + drop (the re-salt dual action)
        _gc, jc, drop = overflow_step(64, 64, False, True, 0, 0)
        assert jc == 256 and drop
        # RUNG_MAX ceiling: the jump saturates — the retry must still
        # change the program, so the hints drop instead of a stall
        _gc, jc, drop = overflow_step(64, RUNG_MAX, False, True, 0, RUNG_MAX * 4)
        assert drop


class TestRadixKernel:
    @pytest.mark.parametrize("n_parts", [2, 8, 32])
    @pytest.mark.parametrize("strategy", ["dense", "search"])
    def test_partition_sweep_parity(self, n_parts, strategy):
        rng = np.random.default_rng(n_parts)
        nb, np_ = 64, 1024
        bk = rng.permutation(np.arange(-32, nb - 32)).astype(np.int64)
        pk = rng.integers(-40, 48, np_).astype(np.int64)
        bnull = rng.random(nb) < 0.1
        pnull = rng.random(np_) < 0.1
        plan = (n_parts, 128, max(8, 2 * np_ // n_parts), 1024)
        bidx, ov, overflow, _need, _esc = _run_kernel(bk, bnull, pk, pnull, plan, strategy)
        assert not overflow
        want, _dup = _ref_unique_join(bk, ~bnull, pk, ~pnull)
        assert (bidx == want).all()
        assert (ov == (want >= 0)).all()

    def test_skewed_key_escape_hatch(self):
        """A heavy-hitter probe key overflows its partition's probe table;
        the escape hatch routes the whole partition through the general
        merge kernel and the result stays exact."""
        rng = np.random.default_rng(3)
        nb, np_ = 32, 512
        bk = np.arange(nb, dtype=np.int64)
        pk = np.where(rng.random(np_) < 0.5, np.int64(7),
                      rng.integers(0, 40, np_)).astype(np.int64)
        plan = (8, 16, 64, 1024)
        bidx, ov, overflow, _need, esc = _run_kernel(bk, None, pk, None, plan, "dense")
        assert not overflow
        assert esc > 0  # the hot partition escaped
        want, _ = _ref_unique_join(bk, np.ones(nb, bool), pk, np.ones(np_, bool))
        assert (bidx == want).all()

    def test_escape_overflow_reports_need(self):
        """Escape rows past esc_cap raise join-overflow WITH the rung
        that clears it (the ladder retry's direct-jump hint)."""
        rng = np.random.default_rng(4)
        nb, np_ = 32, 512
        bk = np.arange(nb, dtype=np.int64)
        pk = np.full(np_, 7, np.int64)
        plan = (8, 16, 16, 64)  # esc_cap 64 << the ~512 escaping rows
        _bidx, _ov, overflow, need, _esc = _run_kernel(bk, None, pk, None, plan, "dense")
        assert overflow and need > 0
        # the hinted rung sizes the escape buffer past the skew
        from tidb_tpu.ops.radix_join import ESC_DIV

        assert need >= 512 * ESC_DIV // 2

    def test_unique_violation_flags_zero_need(self):
        bk = np.array([1, 2, 2, 3], np.int64)
        pk = np.array([2, 1, 9], np.int64)
        for strategy in ("dense", "search"):
            _bidx, _ov, overflow, need, _esc = _run_kernel(
                bk, None, pk, None, (2, 8, 8, 64), strategy)
            assert overflow and need == 0  # growth cannot help: drop hints

    @pytest.mark.parametrize("case", ["signed", "int32_min", "unsigned", "nulls"])
    def test_pallas_vs_general_key_matrix(self, case, monkeypatch):
        """Byte-equality of the Pallas probe (interpret mode), the dense
        XLA probe and the search probe over the key-type matrix — incl.
        INT32_MIN/INT64 boundary keys and unsigned keys living in the
        bit-flipped top half of the word domain."""
        monkeypatch.setenv("TIDB_TPU_PALLAS", "interpret")
        rng = np.random.default_rng(5)
        nb, np_ = 64, 1024
        ft = LL
        bnull = pnull = None
        if case == "signed":
            bk = (rng.permutation(nb).astype(np.int64) - 32) * (1 << 37)
            bk[0], bk[1] = np.iinfo(np.int64).min, np.iinfo(np.int64).max
        elif case == "int32_min":
            bk = np.arange(nb, dtype=np.int64) - 31
            bk[0] = -(1 << 31)  # INT32_MIN: the packed-kernel wrap class
            bk[1] = (1 << 31) - 1
        elif case == "unsigned":
            ft = new_longlong(unsigned=True)
            bk = rng.permutation(nb).astype(np.int64) * (1 << 40)
            bk[0] = -1  # u64 max bit pattern
        else:
            bk = np.arange(nb, dtype=np.int64)
            bnull = rng.random(nb) < 0.2
            pnull = rng.random(np_) < 0.2
        pk = bk[rng.integers(0, nb, np_)]
        pk[::5] = 999_999_999_999  # unmatched lane
        plan = (2, 128, 1024, 1024)  # pallas-eligible shape
        outs = {}
        for strategy in (None, "dense", "search"):
            outs[strategy] = _run_kernel(bk, bnull, pk, pnull, plan, strategy, ft=ft)
        from tidb_tpu.ops.radix_join import probe_strategy

        assert probe_strategy(*plan[:3]) == "pallas-interpret"
        base_idx, base_ov = outs[None][0], outs[None][1]
        for strategy in ("dense", "search"):
            assert (outs[strategy][0] == base_idx).all()
            assert (outs[strategy][1] == base_ov).all()
        want, _ = _ref_unique_join(
            bk, np.ones(nb, bool) if bnull is None else ~bnull,
            pk, np.ones(np_, bool) if pnull is None else ~pnull)
        assert (base_idx == want).all()

    def test_plan_gates(self):
        assert radix_plan(64, 64, 4096) is None  # build-heavy: monolithic
        plan = radix_plan(512, 1 << 16, 4096)
        assert plan is not None
        n_parts, part_cap, probe_cap, esc_cap = plan
        assert n_parts * part_cap >= 2 * 512  # slack holds the build side
        assert probe_cap * n_parts >= 2 * (1 << 16)


def _join_dag(join_type="inner", build_unique=True, agg=None, offsets=None):
    ls = TableScan(1, (ColumnInfo(1, NN), ColumnInfo(2, NN)))
    os_ = TableScan(2, (ColumnInfo(1, NN), ColumnInfo(2, NN)))
    join = Join(build=(os_,), probe_keys=(col(0, NN),), build_keys=(col(0, NN),),
                join_type=join_type, build_unique=build_unique)
    execs = (ls, join) if agg is None else (ls, join, agg)
    if offsets is None:
        offsets = (0, 1, 2, 3) if join_type in ("inner", "left_outer") else (0, 1)
    return DAGRequest(execs, output_offsets=offsets)


def _chunks(np_=512, nb=32, seed=0, dup_build=False):
    rng = np.random.default_rng(seed)
    pk = rng.integers(0, nb + 8, np_)
    prows = [[Datum.i64(int(k)), Datum.i64(i)] for i, k in enumerate(pk)]
    brows = [[Datum.i64(k % nb if dup_build else k), Datum.i64(k * 3)]
             for k in range(nb if not dup_build else nb * 4)]
    return Chunk.from_rows([NN, NN], prows), Chunk.from_rows([NN, NN], brows)


def _canon(rows):
    return sorted(tuple(datum_group_key(d) for d in r) for r in rows)


class TestRadixThroughDAG:
    @pytest.mark.parametrize("jt", ["inner", "left_outer", "semi", "anti"])
    def test_join_type_parity(self, jt):
        probe, build = _chunks()
        dag = _join_dag(jt)
        batches = [to_device_batch(c, capacity=_pow2(c.num_rows())) for c in (probe, build)]
        prog = build_program(dag, tuple(b.capacity for b in batches), group_capacity=64)
        packed, valid, _n, ovfs, _ex = prog.host(*batches)
        assert prog.radix_info, "eligible join must ride the radix kernel"
        assert not any(bool(x) for x in ovfs[:3])
        got = _canon(decode_outputs(packed, valid, prog.out_fts).rows())
        want = _canon(run_dag_reference(dag, [probe, build]))
        assert got == want

    def test_build_heavy_stays_monolithic(self):
        probe, build = _chunks(np_=64, nb=64)
        dag = _join_dag()
        batches = [to_device_batch(c, capacity=64) for c in (probe, build)]
        prog = build_program(dag, (64, 64), group_capacity=64)
        packed, valid, _n, ovfs, _ex = prog.host(*batches)
        assert not prog.radix_info  # ratio gate: monolithic kernel
        assert not any(bool(x) for x in ovfs[:3])

    def test_rung_reuse_zero_recompiles(self):
        """The pinned acceptance test: with the ladder warm, an overflow
        on rung 1 re-dispatches a CACHED rung — ProgramCache stats show
        zero new compiles across the retry (the recompile-per-retry class
        that gave q3 its 131s first call)."""
        rng = np.random.default_rng(9)
        probe, build = _chunks(np_=512, nb=32, seed=9)
        # group by the probe payload: ~512 groups >> rung 1 (64)
        agg = Aggregation(group_by=(col(1, NN),),
                          aggs=(AggDesc("count", ()),))
        dag = _join_dag(agg=agg, offsets=(0, 1))
        batches = [to_device_batch(c, capacity=_pow2(c.num_rows())) for c in (probe, build)]
        caps = tuple(b.capacity for b in batches)
        cache = ProgramCache()
        jc = rung_for(max(caps))
        for rung in rungs_up_to(1024):  # precompile the ladder
            prog = cache.get(dag, caps, group_capacity=rung, join_capacity=jc)
            prog.fn(*batches)
        s0 = cache.stats()
        chunk, _counts, _info = drive_program_info(cache, dag, batches, group_capacity=64)
        s1 = cache.stats()
        assert s1["compiles"] == s0["compiles"], "retry must hit a cached rung"
        assert s1["hits"] >= s0["hits"] + 2  # first rung + the retry rung
        want = _canon(run_dag_reference(dag, [probe, build]))
        assert _canon(chunk.rows()) == want

    def test_overflow_on_rung_one_degrades_and_reports(self):
        """Never-starve: a cold cache and a rung-1 overflow still return
        a correct result — the need hint jumps the retry straight to the
        covering rung (ONE extra compile, not a blind 4x walk)."""
        probe, build = _chunks(np_=512, nb=32, seed=11)
        agg = Aggregation(group_by=(col(1, NN),), aggs=(AggDesc("count", ()),))
        dag = _join_dag(agg=agg, offsets=(0, 1))
        batches = [to_device_batch(c, capacity=_pow2(c.num_rows())) for c in (probe, build)]
        cache = ProgramCache()
        chunk, _counts, _info = drive_program_info(cache, dag, batches, group_capacity=64)
        stats = cache.stats()
        assert stats["compiles"] == 2  # rung 1 + the hinted rung, nothing between
        assert _canon(chunk.rows()) == _canon(run_dag_reference(dag, [probe, build]))

    def test_join_need_hint_jumps_to_exact_rung(self):
        """General (non-unique) expansion join: out-capacity overflow
        carries the exact fan-out, so the retry lands in one step."""
        probe, build = _chunks(np_=512, nb=32, dup_build=True, seed=13)
        dag = _join_dag(build_unique=False)
        batches = [to_device_batch(c, capacity=_pow2(c.num_rows())) for c in (probe, build)]
        cache = ProgramCache()
        chunk, _counts, _info = drive_program_info(
            cache, dag, batches, group_capacity=64, join_capacity=64)
        assert cache.stats()["compiles"] == 2  # 64 -> rung_for(true fan-out)
        assert _canon(chunk.rows()) == _canon(run_dag_reference(dag, [probe, build]))


def _pow2(n: int) -> int:
    c = 1
    while c < max(n, 1):
        c *= 2
    return c


class TestMeshAndSurfaces:
    def test_mesh_tier_join_matches_pool(self):
        """A radix-eligible join + Partial1 agg dispatched through the
        MESH tier (on-device psum of the per-region partials) returns the
        same merged state as the pool/batch tier."""
        from tidb_tpu.codec import tablecodec
        from tidb_tpu.distsql import KVRequest, full_table_ranges, select
        from tidb_tpu.store import TPUStore

        rng = np.random.default_rng(17)
        store = TPUStore()
        nb, np_ = 8, 400
        for h in range(np_):
            store.put_row(1, h, [1, 2], [Datum.i64(int(rng.integers(0, nb + 2))), Datum.i64(h)], ts=10)
        for i in range(1, 4):
            store.cluster.split(tablecodec.encode_row_key(1, i * 100))
        build = Chunk.from_rows([NN, NN], [[Datum.i64(k), Datum.i64(k * 7)] for k in range(nb)])
        agg = Aggregation(group_by=(), aggs=(
            AggDesc("sum", (col(1, NN),)), AggDesc("count", ())), partial=True)
        dag = _join_dag(agg=agg, offsets=(0, 1))
        res_pool = select(store, KVRequest(dag, full_table_ranges(1), start_ts=100,
                                           aux_chunks=[build], mesh=False))
        res_mesh = select(store, KVRequest(dag, full_table_ranges(1), start_ts=100,
                                           aux_chunks=[build], mesh=True))
        pool = Chunk.concat([c for c in res_pool.chunks if c is not None])
        mesh = Chunk.concat([c for c in res_mesh.chunks if c is not None])
        # pool answers one partial per region, mesh ONE merged state: the
        # folded totals must agree
        def fold(ch):
            s = c_ = 0
            for r in ch.rows():
                s += int(str(r[0].val))  # sum state decodes as decimal
                c_ += int(r[1].val)
            return s, c_

        assert fold(pool) == fold(mesh)

    def test_explain_analyze_and_trace_attribution(self):
        """EXPLAIN ANALYZE grows a `join_radix` row (partitions, rung,
        escapes) and TRACE carries the exec.join_radix span."""
        from tidb_tpu.sql.session import Session

        s = Session()
        s.execute("CREATE TABLE o (id BIGINT PRIMARY KEY, w BIGINT)")
        s.execute("CREATE TABLE l (id BIGINT PRIMARY KEY, ok BIGINT NOT NULL, v BIGINT NOT NULL)")
        s.execute("INSERT INTO o VALUES " + ",".join(f"({k},{k * 3})" for k in range(32)))
        s.execute("INSERT INTO l VALUES " + ",".join(
            f"({i},{i % 40},{i % 97})" for i in range(512)))
        sql = "SELECT sum(l.v), count(*) FROM l JOIN o ON l.ok = o.id"
        assert s.execute(sql).rows  # warm + correctness smoke
        rows = s.execute("EXPLAIN ANALYZE " + sql).rows
        radix_rows = [r for r in rows if str(r[0].val) == "join_radix"]
        assert radix_rows, [str(r[0].val) for r in rows]
        # partitions reports what EXECUTED: 1 on CPU-class backends (the
        # search strategy probes one un-partitioned sorted build table)
        assert int(radix_rows[0][1].val) >= 1
        assert "rung=" in str(radix_rows[0][5].val)
        trace = s.execute("TRACE FORMAT='row' " + sql).rows
        names = "\n".join(str(r[0].val) for r in trace)
        assert "exec.join_radix" in names
