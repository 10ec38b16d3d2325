"""The mesh tier keeps what it stacked (PR 35): the stacked lanes of a mesh
launch stay on the devices, sharded over the region axis as the program
reads them, under the data version they were made from and the snapshot rule
of the store's other version caches (`_SnapshotCache`, `TPUStore._may_file`),
inside the device budget of the regions' own batches; a join's build side in
several regions is one object a data version (`TPUStore.build_side`) and, for
a mesh launch, an upload of its own replicated over the mesh's devices.  A
later statement over the same lanes with no write between finds the batch
resident; a snapshot that predates a commit misses, stacks its own rows and
files nothing; any committed write, an epoch change and `evict_caches()` drop
what is there.  Small tables on the suite's eight host devices, the store
driven directly and through sessions."""

import threading

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from test_store_decode_cache import run_threads

from tidb_tpu.chunk import Chunk
from tidb_tpu.chunk.device import to_stacked_device_batch
from tidb_tpu.codec import tablecodec
from tidb_tpu.distsql import full_table_ranges
from tidb_tpu.distsql.root import execute_root
from tidb_tpu.exec import ColumnInfo, DAGRequest, TableScan
from tidb_tpu.exec.dag import Aggregation, Selection
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.sql import Session
from tidb_tpu.store import TPUStore
from tidb_tpu.types import Datum, new_longlong
from tidb_tpu.util import failpoint, metrics, tracing

TID = 35
I = new_longlong()
BOOL = new_longlong(notnull=True)
FULL = full_table_ranges(TID)
NAMES = ("MESH_STACK_HITS", "MESH_STACK_MISSES", "MESH_COP_BATCHES", "MESH_COP_FALLBACKS", "COP_DECODE_DEVICE_BYTES",
         "COP_DECODE_EVICTIONS", "COP_AUX_UPLOADS", "PROGRAM_COMPILES")


def fill(rows=256, regions=8, ts=10) -> TPUStore:
    store = TPUStore()
    for h in range(rows):
        store.put_row(TID, h, [1, 2], [Datum.i64(h % 7), Datum.i64(h)], ts=ts)
    for i in range(1, regions):
        store.cluster.split(tablecodec.encode_row_key(TID, i * rows // regions))
    return store


def dag(floor=1) -> DAGRequest:
    """count(*), sum(b) where a > floor: the literal rides as an operand, so
    every `floor` is one program and one set of lanes."""
    scan = TableScan(TID, (ColumnInfo(1, I), ColumnInfo(2, I)))
    agg = Aggregation(group_by=(), aggs=(AggDesc("count", ()), AggDesc("sum", (col(1, I),))))
    return DAGRequest((scan, Selection((func("gt", BOOL, col(0, I), lit(floor, I)),)), agg), output_offsets=(0, 1))


def want(rows, floor=1) -> list:
    kept = [h for h in rows if h % 7 > floor]
    return [str(len(kept)), str(sum(kept))]


def ask(store, ts, floor=1, mesh=None) -> list:
    out = execute_root(store, dag(floor), FULL, start_ts=ts, mesh=mesh)
    (row,) = out.rows()
    return [str(d.val) for d in row]


def stacked_entries(store) -> list:
    """(key, batch) of the stacked batches that the store holds."""
    with store._cop_lock:
        return [(k, v) for k, (v, _ts, _cost) in store._batch_cache._entries.items() if k[1][0] == "mesh.stack"]


def ints(row) -> list:
    return [int(str(d.val)) for d in row]      # count(*) is an integer, sum() a decimal


class Moved:
    def __enter__(self):
        self.before = {n: getattr(metrics, n).value for n in NAMES}
        return self

    def __exit__(self, *exc):
        self.by = {n: getattr(metrics, n).value - self.before[n] for n in NAMES}

    def stack(self) -> tuple:
        return self.by["MESH_STACK_HITS"], self.by["MESH_STACK_MISSES"]


@pytest.mark.parametrize("regions,devices,lanes", [(8, 8, 8), (6, 6, 6), (10, 8, 16)])
def test_the_second_statement_finds_the_lanes_resident_and_sharded_over_the_region_axis(regions, devices, lanes):
    store = fill(regions=regions)
    with Moved() as first:
        assert ask(store, 100) == want(range(256))
    with Moved() as second:
        assert ask(store, 200, floor=3) == want(range(256), 3)     # another literal, the same lanes
    assert first.stack() == (0, 1) and second.stack() == (1, 0)
    assert first.by["MESH_COP_BATCHES"] == second.by["MESH_COP_BATCHES"] == 1 and second.by["PROGRAM_COMPILES"] == 0
    ((key, batch),) = stacked_entries(store)
    cap = {8: 32, 6: 64, 10: 32}[regions]           # the widest lane's rows, to a power of two
    assert key[1][1:4] == (cap, lanes, devices) and len(key[1][4]) == regions
    assert first.by["COP_DECODE_DEVICE_BYTES"] == batch.nbytes() and second.by["COP_DECODE_DEVICE_BYTES"] == 0
    leaves = jax.tree_util.tree_leaves(batch)
    assert len(leaves) == 2 * 2 + 2          # data and null of two columns, row_valid, n_rows
    for leaf in leaves:
        assert isinstance(leaf.sharding, NamedSharding) and leaf.sharding.spec == PartitionSpec("region")
        assert leaf.sharding.mesh.devices.tolist() == jax.devices()[:devices] and leaf.shape[0] == lanes
        assert sorted(s.device.id for s in leaf.addressable_shards) == [d.id for d in jax.devices()[:devices]]
        assert {s.data.shape[0] for s in leaf.addressable_shards} == {lanes // devices}
    cuts = [i * 256 // regions for i in range(regions + 1)]
    assert np.asarray(batch.n_rows).tolist() == [b - a for a, b in zip(cuts, cuts[1:])] + [0] * (lanes - regions)
    assert batch.row_valid.shape == (lanes, cap)
    assert ask(store, 300, mesh=False) == want(range(256))


def test_stacking_without_a_sharding_is_what_it_was():
    """The vmapped batch tier and the jaxpr auditor call it so: every leaf
    on the default device, the values those of the sharded batch."""
    store = fill(64, regions=4)
    chunks = [store.region_chunk(r, FULL, dag(), 100) for r in store.cluster.regions()]
    assert [c.num_rows() for c in chunks] == [16] * 4
    plain = to_stacked_device_batch(chunks, 16)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("region",))
    sharded = to_stacked_device_batch(chunks, 16, NamedSharding(mesh, PartitionSpec("region")))
    for a, b in zip(jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(sharded)):
        assert isinstance(a.sharding, SingleDeviceSharding) and a.devices() == {jax.devices()[0]}
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    assert plain.nbytes() == sharded.nbytes()


def test_any_committed_write_makes_the_next_statement_miss_once_and_see_the_new_row():
    store = fill()
    assert ask(store, 100) == want(range(256))
    store.put_row(TID + 1, 0, [1], [Datum.i64(7)], ts=110)        # another table: the version is the store's
    with Moved() as other:
        assert ask(store, 120) == want(range(256))
    store.put_row(TID, 256, [1, 2], [Datum.i64(256 % 7), Datum.i64(256)], ts=130)
    with Moved() as own, tracing.trace("t") as root:
        assert ask(store, 140) == want(range(257))
    with Moved() as after:
        assert ask(store, 150, floor=2) == want(range(257), 2)
    assert (other.stack(), own.stack(), after.stack()) == ((0, 1), (0, 1), (1, 0))
    (span,) = root.find("mesh.stack")
    assert span.attrs["hit"] is False and span.attrs["rows"] == 257
    # the write dropped the dead entry: one stacked batch is held, and the account says so
    ((_key, batch),) = stacked_entries(store)
    assert store._batch_cache.used == batch.nbytes() and int(np.asarray(batch.n_rows).sum()) == 257


def test_a_snapshot_that_predates_a_commit_misses_reads_its_own_rows_and_files_nothing():
    store = fill()
    store.put_row(TID, 256, [1, 2], [Datum.i64(256 % 7), Datum.i64(256)], ts=150)
    with Moved() as pinned:
        assert ask(store, 100) == want(range(256))
    assert pinned.stack() == (0, 1) and not stacked_entries(store) and pinned.by["COP_DECODE_DEVICE_BYTES"] == 0
    assert ask(store, 200) == want(range(257))                       # a fresh statement beside it: sees the row, and files
    with Moved() as again:
        assert ask(store, 100) == want(range(256))                   # the entry's start_ts is 200: the old snapshot still misses
        assert ask(store, 210) == want(range(257)) and ask(store, 220, floor=4) == want(range(257), 4)
    assert again.stack() == (2, 1) and len(stacked_entries(store)) == 1


def test_a_pinned_transaction_beside_fresh_statements_over_split_regions():
    a = Session()
    a.execute("create table t (id bigint primary key, v bigint)")
    a.execute("insert into t values " + ", ".join(f"({i}, {i * 10})" for i in range(1, 65)))
    a.execute("split table t between (1) and (65) regions 4")
    b = Session(store=a.store, catalog=a.catalog)
    q = "select count(*), sum(v) from t where v > {}"

    def run(s, floor=0):
        return ints(s.execute(q.format(floor)).rows[0])

    assert run(b) == [64, 20800]
    a.execute("begin")
    assert run(a) == [64, 20800]
    b.execute("insert into t values (65, 650)")
    with Moved() as m:
        assert run(a, 5) == [64, 20800]          # its snapshot predates the commit: misses, files nothing
        assert run(b, 5) == [65, 21450]          # misses once (the write), files
        assert run(b, 15) == [64, 21440]         # hits
        assert run(a, 15) == [63, 20790]         # still misses
    a.execute("commit")
    assert m.stack() == (1, 3) and m.by["MESH_COP_BATCHES"] == 4 and m.by["MESH_COP_FALLBACKS"] == 0
    with Moved() as m:
        assert run(a, 25) == [63, 21420]
    assert m.stack() == (1, 0)
    for s in (a, b):
        s.execute("set tidb_enable_tpu_mesh = OFF")
        s.execute("set tidb_allow_mpp = OFF")
    assert run(a, 25) == run(b, 25) == [63, 21420] and run(b, 5) == [65, 21450]


def test_a_commit_applied_but_not_yet_counted_refuses_the_hit():
    """txn.commit applies the rows, delivers them, and only then bumps the
    write version. A statement whose start_ts is drawn in between is past
    the commit_ts: the resident batch lacks the row and may not answer."""
    a = Session()
    a.execute("create table t (id bigint primary key, v bigint)")
    a.execute("insert into t values " + ", ".join(f"({i}, {i * 10})" for i in range(1, 65)))
    a.execute("split table t between (1) and (65) regions 4")
    b = Session(store=a.store, catalog=a.catalog)
    q = "select count(*), sum(v) from t where v > {}"
    assert ints(b.execute(q.format(0)).rows[0]) == [64, 20800]
    with Moved() as warm:
        assert ints(b.execute(q.format(5)).rows[0]) == [64, 20800]
    assert warm.stack() == (1, 0)
    seen = []

    def between():
        failpoint.disable("store/before-bump-write-ver")        # the statement below must not come back here
        with Moved() as m:
            seen.append(ints(b.execute(q.format(15)).rows[0]))
        seen.append(m.stack())

    with failpoint.enabled("store/before-bump-write-ver", between):
        a.execute("insert into t values (65, 650)")
    assert seen == [[64, 21440], (0, 1)]
    with Moved() as m:
        assert ints(b.execute(q.format(25)).rows[0]) == [63, 21420]   # after the bump: misses once more, files
        assert ints(b.execute(q.format(35)).rows[0]) == [62, 21390]
    assert m.stack() == (1, 1)


def test_a_split_of_a_lanes_region_misses():
    s = Session()
    s.execute("create table t (id bigint primary key, v bigint)")
    s.execute("insert into t values " + ", ".join(f"({i}, {i * 10})" for i in range(1, 65)))
    s.execute("split table t between (1) and (65) regions 4")
    q = "select count(*), sum(v) from t where v > {}"
    assert ints(s.execute(q.format(0)).rows[0]) == [64, 20800]
    with Moved() as hit:
        s.execute(q.format(5))
    s.execute("split table t by (9)")              # cuts the first lane's region: both halves get a new epoch
    with Moved() as cut:
        assert ints(s.execute(q.format(15)).rows[0]) == [63, 20790]
    with Moved() as after:
        assert ints(s.execute(q.format(25)).rows[0]) == [62, 20770]
    assert (hit.stack(), cut.stack(), after.stack()) == ((1, 0), (0, 1), (1, 0))
    assert cut.by["MESH_COP_BATCHES"] == after.by["MESH_COP_BATCHES"] == 1


def test_the_gauge_rises_by_the_batch_once_and_returns_after_evict_caches():
    g0 = metrics.COP_DECODE_DEVICE_BYTES.value
    store = fill()
    for i in range(4):
        assert ask(store, 100 + i, floor=i) == want(range(256), i)
    ((_key, batch),) = stacked_entries(store)
    assert metrics.COP_DECODE_DEVICE_BYTES.value == g0 + batch.nbytes() == g0 + store._batch_cache.used
    store.evict_caches()
    assert not stacked_entries(store) and metrics.COP_DECODE_DEVICE_BYTES.value == g0
    with Moved() as m:
        assert ask(store, 200) == want(range(256))
    assert m.stack() == (0, 1) and m.by["COP_DECODE_DEVICE_BYTES"] == batch.nbytes()
    store.evict_caches()
    assert metrics.COP_DECODE_DEVICE_BYTES.value == g0


def test_a_batch_over_the_budget_is_not_kept_and_serves_its_own_statement():
    store = fill()
    assert ask(store, 100) == want(range(256))
    ((_key, batch),) = stacked_entries(store)
    store.evict_caches()
    store._device_budget = batch.nbytes() - 1
    with Moved() as m:
        assert ask(store, 200) == want(range(256)) and ask(store, 201, floor=2) == want(range(256), 2)
    assert m.stack() == (0, 2) and not stacked_entries(store)
    assert m.by["COP_DECODE_DEVICE_BYTES"] == 0 and m.by["COP_DECODE_EVICTIONS"] == 0 and m.by["MESH_COP_FALLBACKS"] == 0
    store._device_budget = batch.nbytes()
    with Moved() as m:
        assert ask(store, 202) == want(range(256)) and ask(store, 203, floor=3) == want(range(256), 3)
    assert m.stack() == (1, 1) and store._batch_cache.used == batch.nbytes()


def test_the_stacked_batch_shares_the_budget_with_the_regions_own_batches_lru():
    store = fill()
    assert ask(store, 100) == want(range(256))
    ((_key, batch),) = stacked_entries(store)
    regions = [r for r in store.cluster.regions() if r.start_key][:2]
    one = store._region_read(regions[0], FULL, dag(), 101, device=True)[1]
    store._device_budget = batch.nbytes() + one.nbytes()           # room for the stack and one region's batch
    with Moved() as m:
        store._region_read(regions[1], FULL, dag(), 102, device=True)  # the oldest leaves: the stacked batch
    assert m.by["COP_DECODE_EVICTIONS"] == 1 and not stacked_entries(store)
    assert store._batch_cache.used == 2 * one.nbytes()
    with Moved() as m:
        assert ask(store, 103) == want(range(256))                  # stacked again; the older region batch leaves for it
    assert m.stack() == (0, 1) and m.by["COP_DECODE_EVICTIONS"] == 1 and store._batch_cache.used == batch.nbytes() + one.nbytes()


def test_two_threads_on_one_cold_key_both_answer_and_the_account_balances():
    g0 = metrics.COP_DECODE_DEVICE_BYTES.value
    store = fill()
    ask(store, 50)                 # the program is built; then the lanes are dropped
    store.evict_caches()
    start = threading.Barrier(2)
    got = [None, None]

    def reader(i):
        start.wait(60)
        got[i] = ask(store, 100 + i, floor=i)

    with Moved() as m:
        run_threads([lambda i=i: reader(i) for i in range(2)])
    assert got == [want(range(256), 0), want(range(256), 1)]
    assert sum(m.stack()) == 2 and m.by["MESH_STACK_MISSES"] >= 1
    ((_key, batch),) = stacked_entries(store)
    assert store._batch_cache.used == batch.nbytes() and metrics.COP_DECODE_DEVICE_BYTES.value == g0 + batch.nbytes()
    with Moved() as m:
        assert ask(store, 200) == want(range(256))
    assert m.stack() == (1, 0)
    store.evict_caches()
    assert metrics.COP_DECODE_DEVICE_BYTES.value == g0


# ------------------------------------------------------- a join's build sides

def chunk_of(vals) -> Chunk:
    return Chunk.from_rows([I], [[Datum.i64(v)] for v in vals])


def test_a_build_side_in_several_regions_is_one_object_for_the_same_parts():
    store = TPUStore()
    parts = [chunk_of(range(4)), chunk_of(range(4, 8)), chunk_of(range(8, 12))]
    one = store.build_side(parts)
    assert [r[0].val for r in one.rows()] == list(range(12))
    assert store.build_side(list(parts)) is one                       # the same objects: the same concatenation
    assert store.build_side(parts[:2]) is not one and store.build_side(parts[:1]) is parts[0]
    assert store.build_side([]) is None
    again = [chunk_of(range(4)), parts[1], parts[2]]                 # equal rows in a new object: another data version's
    other = store.build_side(again)
    assert other is not one and [r[0].val for r in other.rows()] == list(range(12))
    # bounded like the aux batches, oldest use first; the entry pins its parts
    for i in range(store._AUX_CACHE_MAX):
        store.build_side([chunk_of([i]), chunk_of([i + 1])])
        assert store.build_side(parts) is one                         # used: stays
    assert len(store._build_side_cache) == store._AUX_CACHE_MAX and store.build_side(again) is not other
    store.evict_caches()
    assert not store._build_side_cache and store.build_side(parts) is not one


def test_a_mesh_launch_takes_the_build_side_replicated_and_the_one_chip_entry_stays():
    store = TPUStore()
    ch = chunk_of(range(10))
    with Moved() as m:
        plain, again = store._aux_batch(ch), store._aux_batch(ch)
        mesh, mesh_again = store._aux_batch(ch, mesh_devices=4), store._aux_batch(ch, mesh_devices=4)
        wider = store._aux_batch(ch, mesh_devices=8)
    assert again is plain and mesh_again is mesh and mesh is not plain and wider is not mesh
    assert m.by["COP_AUX_UPLOADS"] == 3 and len(store._aux_batch_cache) == 3
    for leaf in jax.tree_util.tree_leaves(plain):
        assert leaf.devices() == {jax.devices()[0]}
    for batch, n in ((mesh, 4), (wider, 8)):
        for leaf, ref in zip(jax.tree_util.tree_leaves(batch), jax.tree_util.tree_leaves(plain)):
            assert isinstance(leaf.sharding, NamedSharding) and leaf.sharding.is_fully_replicated
            assert leaf.devices() == set(jax.devices()[:n])
            assert leaf.dtype == ref.dtype and np.array_equal(np.asarray(leaf), np.asarray(ref))


def test_a_join_over_split_tables_hits_the_stack_and_finds_both_build_sides_uploaded():
    s = Session()
    s.execute("create table o (id bigint primary key, c bigint)")
    s.execute("create table l (id bigint primary key, o_id bigint, v bigint)")
    s.execute("insert into o values " + ", ".join(f"({i}, {i % 5})" for i in range(1, 33)))
    s.execute("insert into l values " + ", ".join(f"({i}, {i % 32 + 1}, {i})" for i in range(1, 129)))
    s.execute("split table o between (1) and (33) regions 4")
    s.execute("split table l between (1) and (129) regions 8")
    s.execute("analyze table o")
    s.execute("analyze table l")
    q = "select count(*), sum(l.v) from l join o on l.o_id = o.id where o.c < {} and l.v > {}"

    def ref(c, v):
        rows = [i for i in range(1, 129) if (i % 32 + 1) % 5 < c and i > v]
        return [len(rows), sum(rows)]

    def run(c, v):
        with Moved() as m:
            rows = s.execute(q.format(c, v)).rows
        return rows, m

    rows, first = run(3, 0)
    assert ints(rows[0]) == ref(3, 0)
    assert first.by["MESH_COP_BATCHES"] == 1 and first.stack() == (0, 1) and first.by["COP_AUX_UPLOADS"] == 1, first.by
    for c, v in ((2, 10), (4, 50)):
        rows, m = run(c, v)
        assert ints(rows[0]) == ref(c, v)
        assert m.stack() == (1, 0) and m.by["COP_AUX_UPLOADS"] == 0 and m.by["MESH_COP_BATCHES"] == 1, m.by
    with tracing.trace("t") as root:
        rows, m = run(1, 20)
    (stack,) = root.find("mesh.stack")
    assert stack.attrs["hit"] is True and stack.attrs["lanes"] == 8 and stack.attrs["rows"] == 128
    assert [(a.attrs["rows"], a.attrs["hit"]) for a in root.find("cop.aux_batch")] == [(32, True)]
    s.execute("insert into o values (33, 0)")
    rows, m = run(3, 0)
    assert ints(rows[0]) == ref(3, 0) and m.stack() == (0, 1) and m.by["COP_AUX_UPLOADS"] == 1
    rows, m = run(3, 5)
    assert m.stack() == (1, 0) and m.by["COP_AUX_UPLOADS"] == 0
