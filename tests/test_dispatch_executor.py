"""Cop dispatch starts no thread per statement (distsql/dispatch.py): a
request whose tasks form one store group runs it on the statement's own
thread, and a fan-out (several store groups, the pool tier's several
tasks) goes to the one long-lived executor, at most `concurrency` tasks of
a request at once.  Retries, region-error fall-outs and the circuit
breaker serve an inline batch exactly as a fanned-out one."""

import threading
import time
from concurrent.futures import wait

import pytest

from tidb_tpu.codec import tablecodec
from tidb_tpu.distsql import KVRequest, dispatch, full_table_ranges, select
from tidb_tpu.distsql.dispatch import BreakerBoard
from tidb_tpu.exec import Aggregation, ColumnInfo, DAGRequest, TableScan
from tidb_tpu.expr import AggDesc
from tidb_tpu.store import TPUStore
from tidb_tpu.types import Datum, new_longlong
from tidb_tpu.util import metrics, tracing

TID = 93
FT = new_longlong()


def fill_store(rows=160, regions=8, stores=1, layout="scatter"):
    """`rows` rows of (v = 2*handle) in `regions` regions over `stores`
    stores: scattered, or every region's leader on store 0 ("pinned")."""
    store = TPUStore()
    for h in range(rows):
        store.put_row(TID, h, [1], [Datum.i64(h * 2)], ts=10)
    for i in range(1, regions):
        store.cluster.split(tablecodec.encode_row_key(TID, i * rows // regions))
    if stores > 1:
        store.cluster.set_stores(stores)
        store.cluster.scatter()
        if layout == "pinned":
            for r in store.cluster.regions():
                store.cluster.set_store(r.region_id, 0)
    return store


def scan_dag():
    return DAGRequest((TableScan(TID, (ColumnInfo(1, FT),)),), output_offsets=(0,))


def count_dag():
    agg = Aggregation(group_by=(), aggs=(AggDesc("count", ()),), partial=True)
    return DAGRequest((TableScan(TID, (ColumnInfo(1, FT),)), agg), output_offsets=(0,))


def kvreq(dag, **kw):
    return KVRequest(dag, full_table_ranges(TID), start_ts=100, **kw)


def values(res) -> list:
    return sorted(r[0].val for r in res.merged().rows())


def threads_of(store) -> list:
    """Wrap the store's batch endpoint: the threads it was called on."""
    seen, orig = [], store.batch_coprocessor

    def spy(reqs, **kw):
        seen.append(threading.current_thread())
        return orig(reqs, **kw)

    store.batch_coprocessor = spy
    return seen


# ------------------------------------------------------- one unit of work
@pytest.mark.parametrize("kind", ["mesh", "batch"])
def test_a_one_store_request_runs_its_batch_on_the_calling_thread(kind):
    store = fill_store()
    seen = threads_of(store)
    req = kvreq(count_dag()) if kind == "mesh" else kvreq(scan_dag(), batch_cop=True, mesh=False)
    inline0, wait0 = metrics.DISTSQL_INLINE_DISPATCHES.value, metrics.HOST_WAIT_TASKS_NS.value
    with tracing.trace("stmt") as root:
        res = select(store, req)
    assert seen == [threading.current_thread()]
    assert metrics.DISTSQL_INLINE_DISPATCHES.value == inline0 + 1
    assert metrics.HOST_WAIT_TASKS_NS.value == wait0 and not root.find("distsql.wait_tasks")
    (batch,) = root.find("distsql.batch_cop")
    assert batch.attrs["tier"] == kind and batch.thread == root.thread
    assert len(res.exec_summaries) == 8
    if kind == "batch":
        assert values(res) == [h * 2 for h in range(160)]


def test_several_store_groups_fan_out_to_the_executors_threads():
    store = fill_store(stores=3)
    seen = threads_of(store)
    inline0, wait0 = metrics.DISTSQL_INLINE_DISPATCHES.value, metrics.HOST_WAIT_TASKS_NS.value
    with tracing.trace("stmt") as root:
        res = select(store, kvreq(scan_dag(), batch_cop=True, mesh=False))
    assert len(seen) == 3 and threading.current_thread() not in seen
    assert all(t.name.startswith("distsql-dispatch-") and t.daemon for t in seen)
    assert metrics.DISTSQL_INLINE_DISPATCHES.value == inline0
    assert metrics.HOST_WAIT_TASKS_NS.value > wait0
    (waited,) = root.find("distsql.wait_tasks")
    assert waited.attrs["tasks"] == 3 and len(root.find("distsql.batch_cop")) == 3
    assert values(res) == [h * 2 for h in range(160)]


# ------------------------------------------------------- the one executor
def test_twenty_pooled_statements_start_no_thread_after_the_first():
    store = fill_store()
    select(store, kvreq(scan_dag(), concurrency=4, mesh=False))
    started, alive = metrics.DISTSQL_POOL_THREADS_STARTED.value, threading.active_count()
    assert started == dispatch.POOL_THREADS   # one executor a process, all its threads at once
    for _ in range(20):
        store.evict_caches()
        res = select(store, kvreq(scan_dag(), concurrency=4, mesh=False))
        assert values(res) == [h * 2 for h in range(160)]
        assert (metrics.DISTSQL_POOL_THREADS_STARTED.value, threading.active_count()) == (started, alive)


@pytest.mark.parametrize("concurrency", [2, 3])
def test_a_request_never_has_more_than_concurrency_tasks_in_flight(concurrency):
    store = fill_store()
    orig, lock = store.coprocessor, threading.Lock()
    flight = {"now": 0, "peak": 0, "calls": 0}

    def slow(creq):
        with lock:
            flight["now"] += 1
            flight["calls"] += 1
            flight["peak"] = max(flight["peak"], flight["now"])
        try:
            time.sleep(0.05)
            return orig(creq)
        finally:
            with lock:
                flight["now"] -= 1

    store.coprocessor = slow
    res = select(store, kvreq(scan_dag(), concurrency=concurrency, mesh=False))
    assert flight["calls"] == 8 and 2 <= flight["peak"] <= concurrency
    assert values(res) == [h * 2 for h in range(160)]
    # EXPLAIN ANALYZE's summaries stay in task order whatever the finishing order
    assert len(res.exec_summaries) == 8


def test_a_dispatch_from_the_executors_own_threads_completes():
    """Every thread of the executor busy with a statement that fans out
    itself: each runs its tasks where it is, so none waits on a queue that
    only it could drain."""
    store = fill_store()
    select(store, kvreq(scan_dag(), concurrency=4, mesh=False))   # the executor exists
    pool = dispatch._dispatch_executor()
    futs = [pool.submit(select, store, kvreq(scan_dag(), concurrency=4, mesh=False))
            for _ in range(dispatch.POOL_THREADS)]
    done, pending = wait(futs, timeout=120)
    assert not pending
    for f in done:
        assert values(f.result()) == [h * 2 for h in range(160)]


def test_a_failure_is_raised_once_every_task_has_ended():
    store = fill_store()
    orig, ended = store.coprocessor, []

    def failing(creq):
        if creq.region_id == store.cluster.regions()[1].region_id:
            raise RuntimeError("region two")
        resp = orig(creq)
        ended.append(creq.region_id)
        return resp

    store.coprocessor = failing
    with pytest.raises(RuntimeError, match="region two"):
        select(store, kvreq(scan_dag(), concurrency=2, mesh=False))
    assert len(ended) == 7


# ------------------------------------- faults: inline served as fanned out
LAYOUTS = {"inline": "pinned", "fanned_out": "scatter"}


def path_of(layout, inline0) -> None:
    moved = metrics.DISTSQL_INLINE_DISPATCHES.value - inline0
    assert moved == (1 if layout == "inline" else 0)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_stale_region_falls_out_of_the_batch_alone(layout):
    store = fill_store(stores=3, layout=LAYOUTS[layout])
    orig, fired = store.batch_coprocessor, []

    def hijack(reqs, **kw):
        if not fired:
            fired.append(1)
            store.cluster.split(tablecodec.encode_row_key(TID, 7))
        return orig(reqs, **kw)

    store.batch_coprocessor = hijack
    r0, inline0 = metrics.DISTSQL_RETRIES.value, metrics.DISTSQL_INLINE_DISPATCHES.value
    res = select(store, kvreq(scan_dag(), batch_cop=True, mesh=False))
    path_of(layout, inline0)
    assert metrics.DISTSQL_RETRIES.value - r0 == 1      # only the split region
    assert res.batch_stats["regions"] == 7               # the other seven stayed batched
    assert values(res) == [h * 2 for h in range(160)]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_down_store_mid_batch_fails_its_lanes_over(layout):
    store = fill_store(stores=3, layout=LAYOUTS[layout])
    store.set_down(0)
    inline0 = metrics.DISTSQL_INLINE_DISPATCHES.value
    res = select(store, kvreq(scan_dag(), batch_cop=True, mesh=False))
    path_of(layout, inline0)
    assert values(res) == [h * 2 for h in range(160)]
    assert store.cluster.counts_per_store().get(0, 0) == 0


@pytest.mark.parametrize("layout", LAYOUTS)
def test_an_open_breaker_sends_the_stores_lanes_the_single_way(layout):
    store = fill_store(stores=3, layout=LAYOUTS[layout])
    store.breakers = BreakerBoard(threshold=3, probe_after=99.0)   # stays open for the whole select
    for _ in range(3):
        store.breakers.record_failure(0)
    c0, inline0 = metrics.COP_ERRORS.value, metrics.DISTSQL_INLINE_DISPATCHES.value
    res = select(store, kvreq(scan_dag(), batch_cop=True, mesh=False))
    path_of(layout, inline0)
    assert values(res) == [h * 2 for h in range(160)]
    assert metrics.COP_ERRORS.value == c0                 # failed over before sending
    assert store.cluster.counts_per_store().get(0, 0) == 0
    assert store.breakers.states()[0] == "open"
