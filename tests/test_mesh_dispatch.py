"""Mesh-unified dispatch (ISSUE 11): the ONE execution planner routes the
standard `distsql.select` path onto the device mesh — partial aggregate
states psum-reduced over the region axis under shard_map, one merged state
per store instead of R per-region partials for the host to fold (SURVEY
§3.1/§5; ref: TiDB's MPP partial/final split lowered onto SPMD collectives).
"""

import os
import sys

import pytest

from tidb_tpu.codec import tablecodec
from tidb_tpu.codec.wire import (
    decode_cop_request,
    decode_cop_response,
    encode_cop_request,
    encode_cop_response,
)
from tidb_tpu.distsql.dispatch import KVRequest, full_table_ranges, select, select_stream
from tidb_tpu.distsql.planner import TierDecision, choose_tier, mesh_merge_kind
from tidb_tpu.distsql.root import execute_root, split_dag
from tidb_tpu.exec.dag import Aggregation, ColumnInfo, DAGRequest, Selection, TableScan, TopN
from tidb_tpu.exec.executor import run_dag_reference
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.store import CopRequest, TPUStore
from tidb_tpu.store.store import CopResponse
from tidb_tpu.types import Datum, new_longlong
from tidb_tpu.util import metrics

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

TID = 21
I = new_longlong()
BOOL = new_longlong(notnull=True)


def fill_store(rows=180, regions=6, stores=2):
    store = TPUStore()
    for h in range(rows):
        store.put_row(TID, h, [1, 2], [Datum.i64(h % 7), Datum.i64(h)], ts=10)
    for i in range(1, regions):
        store.cluster.split(tablecodec.encode_row_key(TID, i * rows // regions))
    if stores > 1:
        store.cluster.set_stores(stores)
        store.cluster.scatter()
    return store


def scan():
    return TableScan(TID, (ColumnInfo(1, I), ColumnInfo(2, I)))


def scalar_partial_dag():
    agg = Aggregation(group_by=(), aggs=(
        AggDesc("count", ()), AggDesc("sum", (col(1, I),)),
        AggDesc("min", (col(1, I),)), AggDesc("max", (col(1, I),)),
    ), partial=True)
    pred = func("gt", BOOL, col(0, I), lit(1, I))
    return DAGRequest((scan(), Selection((pred,)), agg), output_offsets=tuple(range(4)))


def logical_dag(aggs, group_by=()):
    agg = Aggregation(group_by=group_by, aggs=aggs)
    return DAGRequest((scan(), agg),
                      output_offsets=tuple(range(len(aggs) + len(group_by))))


def oracle_rows(store, dag, rows=180):
    chunk_rows = [[Datum.i64(h % 7), Datum.i64(h)] for h in range(rows)]
    from tidb_tpu.chunk import Chunk

    return run_dag_reference(dag, Chunk.from_rows([I, I], chunk_rows))


# ------------------------------------------------------------ the planner

def test_planner_tier_rules():
    store = fill_store()
    tasks = list(range(6))  # only len() is consulted
    pdag = scalar_partial_dag()
    sdag = DAGRequest((scan(),), output_offsets=(0, 1))
    assert choose_tier(store, KVRequest(pdag, [], 100), tasks) == \
        TierDecision("mesh", "scalar")
    # plain scans never mesh; batch_cop claims them
    assert choose_tier(store, KVRequest(sdag, [], 100, batch_cop=True), tasks).tier == "batch"
    assert choose_tier(store, KVRequest(sdag, [], 100), tasks).tier == "pool"
    # paging pins the per-task path (resume cursors are sequential state)
    assert choose_tier(store, KVRequest(pdag, [], 100, paging_size=16), tasks).tier == "pool"
    # single task: nothing to merge
    assert choose_tier(store, KVRequest(pdag, [], 100), tasks[:1]).tier == "single"
    # the kill switch pins the pre-mesh tiers
    assert choose_tier(store, KVRequest(pdag, [], 100, mesh=False), tasks).tier == "pool"
    assert choose_tier(store, KVRequest(pdag, [], 100, mesh=False, batch_cop=True), tasks).tier == "batch"
    # data-size floor: an absurd min-rows hint pushes it off the mesh
    assert choose_tier(store, KVRequest(pdag, [], 100, mesh_min_rows=1 << 30), tasks).tier == "pool"


def test_mesh_merge_kind_gate():
    pdag = scalar_partial_dag()
    assert mesh_merge_kind(pdag) == "scalar"
    # grouped partial -> "group"
    gagg = Aggregation(group_by=(col(0, I),),
                       aggs=(AggDesc("sum", (col(1, I),)),), partial=True)
    assert mesh_merge_kind(DAGRequest((scan(), gagg), output_offsets=(0, 1))) == "group"
    # TopN -> "topn"
    tdag = DAGRequest((scan(), TopN(order_by=((col(1, I), True),), limit=5)),
                      output_offsets=(0, 1))
    assert mesh_merge_kind(tdag) == "topn"
    # Complete-mode aggregation: the root owns the finalize — no mesh
    cagg = Aggregation(group_by=(), aggs=(AggDesc("count", ()),))
    assert mesh_merge_kind(DAGRequest((scan(), cagg), output_offsets=(0,))) is None
    # DISTINCT states are not mergeable
    dagg = Aggregation(group_by=(), aggs=(
        AggDesc("count", (col(1, I),), distinct=True),), partial=True)
    assert mesh_merge_kind(DAGRequest((scan(), dagg), output_offsets=(0,))) is None
    # reordered output offsets: the positional merge plan would misalign
    from dataclasses import replace

    assert mesh_merge_kind(replace(pdag, output_offsets=(1, 0, 2, 3))) is None


# -------------------------------------------- the acceptance: psum on device

def test_scalar_psum_one_merged_state_per_store():
    """THE acceptance bar: a standard select() over a multi-device mesh
    executes via shard_map, partial states psum-reduce on device, and each
    store answers ONE merged state — byte-identical to the per-region
    host-merge result."""
    store = fill_store(rows=180, regions=6, stores=2)
    dag = scalar_partial_dag()
    l0 = metrics.MESH_COP_LANES.value
    b0 = metrics.MESH_COP_BATCHES.value
    res = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100))
    assert metrics.MESH_COP_LANES.value - l0 == 6
    assert metrics.MESH_COP_BATCHES.value - b0 == 2  # one launch per store
    assert res.batch_stats["mesh_lanes"] == 6
    assert res.batch_stats["mesh_batches"] == 2
    # one merged state per STORE at root — no per-region host merge
    live = [c for c in res.chunks if c is not None and c.num_rows()]
    assert len(live) == 2
    # the merged partials equal the per-region path's root-merge input
    ref = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100,
                                  mesh=False))
    from tidb_tpu.chunk import Chunk

    def folded(chunks):
        merge = split_dag(logical_dag((
            AggDesc("count", ()), AggDesc("sum", (col(1, I),)),
            AggDesc("min", (col(1, I),)), AggDesc("max", (col(1, I),)),
        ))).root_dag  # Final merge over the partial schema
        rows = run_dag_reference(merge, Chunk.concat(chunks))
        return [[str(d) for d in r] for r in rows]

    assert folded([c for c in res.chunks if c is not None]) == \
        folded([c for c in ref.chunks if c is not None])


def test_execute_root_scalar_matches_oracle():
    store = fill_store()
    dag = logical_dag((
        AggDesc("count", ()), AggDesc("sum", (col(1, I),)),
        AggDesc("avg", (col(1, I),)), AggDesc("min", (col(0, I),)),
        AggDesc("max", (col(1, I),)), AggDesc("first_row", (col(0, I),)),
    ))
    l0 = metrics.MESH_COP_LANES.value
    out = execute_root(store, dag, full_table_ranges(TID), start_ts=100)
    assert metrics.MESH_COP_LANES.value - l0 > 0  # the mesh tier ran
    want = oracle_rows(store, dag)
    assert [[str(d) for d in r] for r in out.rows()] == \
        [[str(d) for d in r] for r in want]


def test_execute_root_grouped_matches_oracle():
    """GROUP BY partials merge on device too (all_gather + merge-mode
    re-group): one merged group table per store."""
    store = fill_store()
    dag = logical_dag((
        AggDesc("count", ()), AggDesc("sum", (col(1, I),)),
        AggDesc("max", (col(1, I),)),
    ), group_by=(col(0, I),))
    l0 = metrics.MESH_COP_LANES.value
    out = execute_root(store, dag, full_table_ranges(TID), start_ts=100)
    assert metrics.MESH_COP_LANES.value - l0 > 0
    want = oracle_rows(store, dag)
    assert sorted(map(str, out.rows())) == sorted(map(str, want))


def test_execute_root_topn_matches_oracle():
    store = fill_store()
    dag = DAGRequest((scan(), TopN(order_by=((col(1, I), True),), limit=9)),
                     output_offsets=(0, 1))
    l0 = metrics.MESH_COP_LANES.value
    out = execute_root(store, dag, full_table_ranges(TID), start_ts=100)
    assert metrics.MESH_COP_LANES.value - l0 > 0
    want = oracle_rows(store, dag)
    assert [[str(d) for d in r] for r in out.rows()] == \
        [[str(d) for d in r] for r in want]


def test_select_stream_mesh_yields_merged_states():
    store = fill_store(rows=180, regions=6, stores=2)
    dag = scalar_partial_dag()
    got = list(select_stream(store, KVRequest(dag, full_table_ranges(TID), start_ts=100)))
    live = [c for c, _sums in got if c.num_rows()]
    assert len(live) == 2  # one merged state per store
    ref = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100, mesh=False))
    from tidb_tpu.chunk import Chunk

    merge = split_dag(logical_dag((
        AggDesc("count", ()), AggDesc("sum", (col(1, I),)),
        AggDesc("min", (col(1, I),)), AggDesc("max", (col(1, I),)),
    ))).root_dag
    a = run_dag_reference(merge, Chunk.concat(live))
    b = run_dag_reference(merge, Chunk.concat([c for c in ref.chunks if c is not None]))
    assert [[str(d) for d in r] for r in a] == [[str(d) for d in r] for r in b]


# ---------------------------------------------------- robustness contracts

def test_epoch_mismatch_falls_out_of_mesh_batch():
    """A concurrent split between task build and dispatch: the stale lane
    falls out of the mesh batch into the single-task retry path; the other
    lanes' states still merge on device and the total stays correct."""
    store = fill_store(rows=180, regions=6, stores=1)
    dag = scalar_partial_dag()
    orig = store.batch_coprocessor
    fired = []

    def hijack(reqs, **kw):
        if not fired:
            fired.append(1)
            store.cluster.split(tablecodec.encode_row_key(TID, 5))
        return orig(reqs, **kw)

    store.batch_coprocessor = hijack
    r0 = metrics.DISTSQL_RETRIES.value
    res = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100))
    assert metrics.DISTSQL_RETRIES.value - r0 >= 1  # the split lane retried
    assert res.batch_stats["mesh_lanes"] >= 4  # the rest still merged
    store.batch_coprocessor = orig
    ref = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100,
                                  mesh=False))
    merge = split_dag(logical_dag((
        AggDesc("count", ()), AggDesc("sum", (col(1, I),)),
        AggDesc("min", (col(1, I),)), AggDesc("max", (col(1, I),)),
    ))).root_dag
    from tidb_tpu.chunk import Chunk

    def folded(chunks):
        rows = run_dag_reference(merge, Chunk.concat([c for c in chunks if c is not None]))
        return [[str(d) for d in r] for r in rows]

    assert folded(res.chunks) == folded(ref.chunks)


def test_min_group_rows_floor_degrades_to_vmap():
    store = fill_store()
    store.MESH_MIN_GROUP_ROWS = 10_000  # instance override of the env knob
    dag = scalar_partial_dag()
    l0 = metrics.MESH_COP_LANES.value
    res = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100))
    assert metrics.MESH_COP_LANES.value == l0  # mesh declined on data size
    assert res.batch_stats["mesh_lanes"] == 0
    assert res.batch_stats["regions"] > 0  # the vmapped tier served instead


def test_mesh_min_rows_hint_enforced_on_actual_rows():
    """The tidb_tpu_mesh_min_rows hint rides the cop requests and the
    STORE enforces it against the group's actually-decoded rows — a floor
    above the table's real size keeps the query off the mesh even though
    the client-side estimate (whole-store keys) passed."""
    store = fill_store(rows=180, stores=1)  # one group of 180 decoded rows
    dag = scalar_partial_dag()
    l0 = metrics.MESH_COP_LANES.value
    res = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100,
                                  mesh_min_rows=120))
    assert metrics.MESH_COP_LANES.value > l0  # 180 rows >= 120: mesh ran
    # another table's keys inflate the CLIENT estimate (whole-store keys)
    # past the floor — exactly the case the store-side check exists for
    for h in range(100):
        store.put_row(TID + 1, h, [1, 2], [Datum.i64(h), Datum.i64(h)], ts=11)
    l0 = metrics.MESH_COP_LANES.value
    res = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=101,
                                  mesh_min_rows=200))
    assert metrics.MESH_COP_LANES.value == l0  # 180 decoded rows < 200
    assert res.batch_stats["mesh_lanes"] == 0
    assert res.batch_stats["regions"] > 0


def test_skewed_capacities_degrade_to_vmap_buckets():
    """One post-split giant among tiny regions: padding every mesh lane
    to the max pow2 capacity would blow the stacked footprint toward
    lanes*max (#review), so the skew guard degrades the group to the
    vmapped tier, whose capacity BUCKETING right-sizes the launches."""
    store = TPUStore()
    for h in range(220):
        store.put_row(TID, h, [1, 2], [Datum.i64(h % 7), Datum.i64(h)], ts=10)
    # region 0 keeps ~200 rows; five tiny regions of 4 rows each
    for i in range(5):
        store.cluster.split(tablecodec.encode_row_key(TID, 200 + i * 4))
    dag = scalar_partial_dag()
    l0 = metrics.MESH_COP_LANES.value
    f0 = metrics.MESH_COP_FALLBACKS.value
    res = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100))
    assert metrics.MESH_COP_LANES.value == l0  # mesh declined on skew
    assert metrics.MESH_COP_FALLBACKS.value - f0 == 1
    assert res.batch_stats["regions"] > 0  # vmapped buckets served
    merge = split_dag(logical_dag((
        AggDesc("count", ()), AggDesc("sum", (col(1, I),)),
        AggDesc("min", (col(1, I),)), AggDesc("max", (col(1, I),)),
    ))).root_dag
    from tidb_tpu.chunk import Chunk

    rows = run_dag_reference(merge, Chunk.concat([c for c in res.chunks if c is not None]))
    assert int(rows[0][0].val) == sum(1 for h in range(220) if h % 7 > 1)


def test_mesh_off_pins_old_paths():
    store = fill_store()
    dag = scalar_partial_dag()
    l0 = metrics.MESH_COP_LANES.value
    res = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100, mesh=False))
    assert metrics.MESH_COP_LANES.value == l0
    assert res.batch_stats is None  # pool tier: per-region dispatch


def test_wire_roundtrip_mesh_fields():
    dag = scalar_partial_dag()
    # min-rows rides as i64: the sysvar range (1<<40) exceeds i32 (#review)
    req = CopRequest(dag, full_table_ranges(TID), 100, 3, 1, mesh=True,
                     mesh_min_rows=1 << 33)
    back = decode_cop_request(encode_cop_request(req))
    assert back.mesh is True and back.mesh_min_rows == 1 << 33
    assert back.whole_dag is None
    resp = CopResponse(chunk=None, region_error="x", batched=2, mesh_merged=5)
    rback = decode_cop_response(encode_cop_response(resp))
    assert rback.batched == 2 and rback.mesh_merged == 5 and rback.root_fused is False
    # the root's half rides the request, and the marker the response (ISSUE 37)
    whole = logical_dag((AggDesc("count", ()), AggDesc("sum", (col(1, I),))), group_by=(col(0, I),))
    req = CopRequest(split_dag(whole).push_dag, full_table_ranges(TID), 100, 3, 1, mesh=True, whole_dag=whole)
    back = decode_cop_request(encode_cop_request(req))
    assert back.whole_dag.fingerprint() == whole.fingerprint() and back.dag.fingerprint() == req.dag.fingerprint()
    assert decode_cop_response(encode_cop_response(CopResponse(chunk=None, mesh_merged=5, root_fused=True))).root_fused is True


def test_run_sharded_partial_agg_rejects_grouped_dag():
    """The exported scalar entry point must fail fast on a grouped DAG
    (#review): its positional psum plan cannot align per-region group
    tables — silence here would return garbage states."""
    import jax

    from tidb_tpu.parallel import region_mesh, run_sharded_partial_agg, stack_region_batches
    from tidb_tpu.chunk import Chunk

    rows = [[Datum.i64(i % 3), Datum.i64(i)] for i in range(8)]
    chunks = [Chunk.from_rows([I, I], rows)] * 2
    gagg = Aggregation(group_by=(col(0, I),),
                       aggs=(AggDesc("sum", (col(1, I),)),), partial=True)
    dag = DAGRequest((scan(), gagg), output_offsets=(0, 1))
    stacked = stack_region_batches(chunks, n_total=8)
    with pytest.raises(AssertionError, match="scalar"):
        run_sharded_partial_agg(dag, stacked, region_mesh())


def test_wire_mode_select_meshes():
    """use_wire routes the batch frames through the serialized seam — the
    mesh marker must survive it."""
    store = fill_store()
    dag = scalar_partial_dag()
    l0 = metrics.MESH_COP_LANES.value
    res = select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100, use_wire=True))
    assert metrics.MESH_COP_LANES.value - l0 == 6
    assert res.batch_stats["mesh_lanes"] == 6


@pytest.mark.parametrize("regions,stores", [(1, 1), (6, 1), (6, 2)], ids=["lone_task", "mesh_group", "two_stores"])
def test_wire_mode_carries_the_roots_half_and_the_marker(regions, stores):
    """Through the serialized seam the request still carries the unsplit
    DAG and the response the marker: one store's mesh group (or the lone
    task) answers the statement's rows, two stores' groups their merged
    states, which the root would merge (ISSUE 37)."""
    store = fill_store(regions=regions, stores=stores)
    whole = logical_dag((AggDesc("count", ()), AggDesc("sum", (col(1, I),))), group_by=(col(0, I),))
    plan = split_dag(whole)
    res = select(store, KVRequest(plan.push_dag, full_table_ranges(TID), start_ts=100, use_wire=True, whole_dag=whole))
    assert res.root_fused is (stores == 1)
    got = res.merged()
    if stores == 1:
        want = oracle_rows(store, whole)
        assert sorted((r[2].val, r[0].val, str(r[1].val)) for r in got.rows()) == sorted((r[2].val, r[0].val, str(r[1].val)) for r in want)
    else:
        assert got.num_cols() == len(plan.push_dag.output_fts()) and res.batch_stats["mesh_batches"] == 2


# ----------------------------------------------------------- SQL + chaos

def test_sql_mesh_explain_and_trace():
    from tidb_tpu.sql.session import Session
    from tidb_tpu.util import tracing

    s = Session()
    s.execute("CREATE TABLE mt (id BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO mt VALUES " + ",".join(f"({i},{i % 13})" for i in range(400)))
    tid = s.catalog.table("mt").table_id
    for i in range(1, 8):
        s.store.cluster.split(tablecodec.encode_row_key(tid, i * 50))
    q = "SELECT count(*), sum(v), min(v), max(v) FROM mt WHERE v < 9"
    s.execute("SET tidb_enable_tpu_mesh = OFF")
    want = s.execute(q).values()  # per-region host-merge reference
    s.execute("SET tidb_enable_tpu_mesh = ON")
    s.store.evict_caches()  # cop-cache-served lanes fall out BEFORE the
    # mesh grouping (by design) — drain so the launch itself is attributed
    got = s.execute(q).values()
    assert got == want
    s.store.evict_caches()
    rows = s.execute("EXPLAIN ANALYZE " + q).values()
    by_exec = {r[0]: r for r in rows}
    mc = by_exec["mesh_cop"]
    assert mc[1] == 8 and mc[2] >= 1  # 8 lanes merged into >=1 launches
    assert mc[5].startswith("merged=8->")
    with tracing.trace("t") as root:
        s.execute(q)
    spans = root.find("distsql.batch_cop")
    assert spans and spans[0].attrs.get("tier") == "mesh"
    assert root.sum_attr("distsql.batch_cop", "mesh_lanes_merged") == 8
    mesh_exec = root.find("cop.mesh_execute")
    assert mesh_exec and mesh_exec[0].attrs.get("kind") == "scalar"


@pytest.mark.slow
def test_chaos_storm_with_mesh_tier():
    """The chaos acceptance bar with the mesh tier enabled (it is ON by
    default — this pins that the storm actually exercised it): seeded
    splits/outages/transfers, zero wrong results, and on-device merges
    really happened."""
    from chaos import run_chaos

    l0 = metrics.MESH_COP_LANES.value
    report = run_chaos(seed=17, statements=80)
    assert report["wrong_results"] == []
    assert report["untyped_errors"] == []
    assert metrics.MESH_COP_LANES.value > l0  # the storm rode the mesh


def test_chaos_small_storm_mesh_quick():
    """Tier-1-sized storm (the slow one above is the full bar): the mesh
    tier stays zero-wrong-results under topology churn."""
    from chaos import run_chaos

    l0 = metrics.MESH_COP_LANES.value
    report = run_chaos(seed=23, statements=30)
    assert report["wrong_results"] == []
    assert report["untyped_errors"] == []
    assert metrics.MESH_COP_LANES.value > l0
