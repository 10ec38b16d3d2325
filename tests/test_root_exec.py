"""Root executor (VERDICT next #4): a logical Complete-mode DAG splits into
per-region Partial1 + root Final merge invisibly; per-region TopN/Limit are
re-applied globally. Every test compares against the single-shot oracle over
all rows — the merge must be caller-invisible.

Since ISSUE 37 the root's half rides the request: where the pushdown comes
back as one state (a lone cop task, or a mesh group of every task of the
request) the store's program finishes the statement and the root merges
nothing.  `TestRootHalfRidesTheRequest` holds that answer to the split
path's rows, row for row, and every decline to the split path's rows and
launch count."""

import contextlib
import dataclasses

import numpy as np
import pytest

from tidb_tpu.chunk import Chunk
from tidb_tpu.codec import tablecodec
from tidb_tpu.distsql import execute_root, full_table_ranges, split_dag
from tidb_tpu.distsql import root as root_mod
from tidb_tpu.exec import (
    Aggregation,
    ColumnInfo,
    DAGRequest,
    Join,
    Limit,
    Projection,
    Selection,
    Sort,
    TableScan,
    TopN,
    run_dag_reference,
)
from tidb_tpu.exec import executor as executor_mod
from tidb_tpu.exec.executor import OverflowRetryError, datum_group_key
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.store import TPUStore
from tidb_tpu.store import store as store_mod
from tidb_tpu.types import Datum, MyDecimal, new_decimal, new_longlong, new_varchar
from tidb_tpu.util import metrics

BOOL = new_longlong(notnull=True)
TID = 77
FTS = [new_longlong(), new_decimal(10, 2), new_varchar(8), new_longlong(unsigned=True)]
C = lambda i: col(i, FTS[i])


def canon(rows):
    return sorted(tuple(datum_group_key(d) for d in r) for r in rows)


def fill_store(n=260, regions=4, seed=11, null_p=0.05):
    store = TPUStore()
    rng = np.random.default_rng(seed)
    rows = []
    words = ["ox", "ant", "bee", "Cat", "dog", ""]
    for h in range(n):
        def maybe(d):
            return Datum.NULL if rng.random() < null_p else d

        row = [
            maybe(Datum.i64(int(rng.integers(0, 7)))),
            maybe(Datum.dec(MyDecimal(f"{int(rng.integers(-9999, 9999))/100:.2f}"))),
            maybe(Datum.string(words[int(rng.integers(len(words)))])),
            maybe(Datum.u64(int(rng.integers(0, 1 << 62)))),
        ]
        rows.append(row)
        store.put_row(TID, h, [1, 2, 3, 4], row, ts=10)
    for i in range(1, regions):
        store.cluster.split(tablecodec.encode_row_key(TID, i * n // regions))
    return store, rows


def scan():
    return TableScan(TID, tuple(ColumnInfo(i + 1, ft) for i, ft in enumerate(FTS)))


def check(store, rows, dag, sort=True):
    got = execute_root(store, dag, full_table_ranges(TID), start_ts=100)
    want = run_dag_reference(dag, Chunk.from_rows(FTS, rows))
    if sort:
        assert canon(got.rows()) == canon(want)
    else:
        g = [tuple(datum_group_key(d) for d in r) for r in got.rows()]
        w = [tuple(datum_group_key(d) for d in r) for r in want]
        assert g == w, f"\ngot ={g[:4]}\nwant={w[:4]}"
    return got


class TestRootExecutor:
    def test_grouped_agg_split(self):
        store, rows = fill_store()
        agg = Aggregation(
            group_by=(C(0), C(2)),
            aggs=(
                AggDesc("count", ()),
                AggDesc("sum", (C(1),)),
                AggDesc("avg", (C(1),)),
                AggDesc("min", (C(2),)),       # string min via gather state
                AggDesc("max", (C(3),)),       # unsigned max
                AggDesc("first_row", (C(1),)),
            ),
        )
        dag = DAGRequest((scan(), agg), output_offsets=tuple(range(8)))
        plan = split_dag(dag)
        assert plan.root_dag is not None and plan.push_dag.executors[-1].partial
        check(store, rows, dag)

    def test_scalar_agg_split(self):
        store, rows = fill_store(n=150, regions=3)
        agg = Aggregation(group_by=(), aggs=(AggDesc("count", ()), AggDesc("sum", (C(1),)), AggDesc("min", (C(1),))))
        dag = DAGRequest((scan(), agg), output_offsets=(0, 1, 2))
        check(store, rows, dag)

    def test_multi_region_topn_reapplied(self):
        """Per-region TopN concatenation is NOT the global TopN — the root
        must re-apply (VERDICT weak #5)."""
        store, rows = fill_store(n=200, regions=4)
        t = TopN(order_by=((C(1), True), (C(0), False)), limit=7)
        dag = DAGRequest((scan(), t), output_offsets=(0, 1, 2))
        got = check(store, rows, dag, sort=False)
        assert got.num_rows() == 7

    def test_multi_region_limit_reapplied(self):
        store, rows = fill_store(n=120, regions=3)
        dag = DAGRequest((scan(), Limit(10)), output_offsets=(0, 1))
        got = execute_root(store, dag, full_table_ranges(TID), start_ts=100)
        assert got.num_rows() == 10
        # rows must come from the table (limit over unordered scan is any-10)
        table = {tuple(datum_group_key(d) for d in (r[0], r[1])) for r in rows}
        for r in got.rows():
            assert tuple(datum_group_key(d) for d in r) in table

    def test_distinct_agg_runs_at_root(self):
        store, rows = fill_store(n=180, regions=3)
        agg = Aggregation(group_by=(C(0),), aggs=(AggDesc("count", (C(1),), distinct=True), AggDesc("sum", (C(1),))))
        dag = DAGRequest((scan(), agg), output_offsets=(0, 1, 2))
        plan = split_dag(dag)
        assert plan.push_dag.executors[-1] is plan.push_dag.executors[0] or not isinstance(plan.push_dag.executors[-1], Aggregation)
        check(store, rows, dag)

    def test_having_after_agg(self):
        """Selection after the aggregation (HAVING) runs at root over the
        merged finals."""
        store, rows = fill_store(n=200, regions=4)
        agg = Aggregation(group_by=(C(0),), aggs=(AggDesc("count", ()), AggDesc("sum", (C(1),))))
        having = Selection((func("gt", BOOL, col(0, agg.aggs[0].ft), lit(20, new_longlong())),))
        t = TopN(order_by=((col(1, agg.aggs[1].ft), True),), limit=3)
        dag = DAGRequest((scan(), agg, having, t), output_offsets=(0, 1, 2))
        check(store, rows, dag, sort=False)

    def test_selection_then_agg(self):
        store, rows = fill_store(n=220, regions=4)
        sel = Selection((func("ge", BOOL, C(1), lit("0.00", new_decimal(3, 2))),))
        agg = Aggregation(group_by=(C(2),), aggs=(AggDesc("avg", (C(1),)), AggDesc("count", ())))
        dag = DAGRequest((scan(), sel, agg), output_offsets=(0, 1, 2))
        check(store, rows, dag)

    def test_plain_scan_no_root(self):
        store, rows = fill_store(n=90, regions=3)
        dag = DAGRequest((scan(), Selection((func("isnull", BOOL, C(2)),))), output_offsets=(0, 2))
        plan = split_dag(dag)
        assert plan.root_dag is None
        check(store, rows, dag)

    def test_empty_table(self):
        store = TPUStore()
        agg = Aggregation(group_by=(), aggs=(AggDesc("count", ()),))
        dag = DAGRequest((scan(), agg), output_offsets=(0,))
        got = execute_root(store, dag, full_table_ranges(TID), start_ts=100)
        assert got.num_rows() == 1 and got.row(0)[0].val == 0


def test_q3_via_root_executor():
    """The hand-rolled Q3 merge from test_join_dag, now through the generic
    root executor: logical DAG in, globally-correct TopN out."""
    import tests.test_join_dag as J

    lrows, orows, crows = J.make_tables(nl=300, no=60, nc=20)
    store = TPUStore()
    for h, r in enumerate(lrows):
        store.put_row(1, h, [1, 2, 3, 4], r, ts=10)
    for h, r in enumerate(orows):
        store.put_row(2, h, [1, 2, 3, 4], r, ts=10)
    for h, r in enumerate(crows):
        store.put_row(3, h, [1, 2], r, ts=10)
    for frac in (1, 2):
        store.cluster.split(tablecodec.encode_row_key(1, frac * 100))

    from tidb_tpu.distsql import KVRequest, select

    ls, os_, cs = J.scans()
    och = select(store, KVRequest(DAGRequest((os_,), output_offsets=tuple(range(4))), full_table_ranges(2), start_ts=100)).merged()
    cch = select(store, KVRequest(DAGRequest((cs,), output_offsets=tuple(range(2))), full_table_ranges(3), start_ts=100)).merged()

    base = J.q3_dag(partial=False)
    topn = TopN(order_by=((col(0, base.executors[-1].aggs[0].ft), True),), limit=10)
    dag = DAGRequest(base.executors + (topn,), output_offsets=base.output_offsets)
    got = execute_root(store, dag, full_table_ranges(1), start_ts=100, aux_chunks=[och, cch])
    want = run_dag_reference(dag, [Chunk.from_rows(J.LFTS, lrows), Chunk.from_rows(J.OFTS, orows), Chunk.from_rows(J.CFTS, crows)])
    got_rev = sorted(str(r[0].val) for r in got.rows())
    want_rev = sorted(str(r[0].val) for r in want)
    assert got_rev == want_rev


# ------------------------------------------- the root's half rides the request
@contextlib.contextmanager
def root_half_left_off():
    """The split path as it was before the root's half rode the request
    (ISSUE 37): every `KVRequest` of the block goes out without its
    `whole_dag`, so the pushdown comes back as partial states and the root
    merges them in a second program.  What a fused answer is compared with,
    row for row."""
    real = root_mod.select
    root_mod.select = lambda store, req: real(store, dataclasses.replace(req, whole_dag=None))
    try:
        yield
    finally:
        root_mod.select = real


COUNTERS = ("PROGRAM_LAUNCHES", "PROGRAM_FETCHES", "PROGRAM_COMPILES", "ROOT_FUSED_STATEMENTS", "ROOT_FUSE_FALLBACKS",
            "MESH_COP_BATCHES", "MESH_COP_FALLBACKS")


def counted(store, dag, **kw):
    """(rows in the order served, what the always-on counters moved by)."""
    before = {n: getattr(metrics, n).value for n in COUNTERS}
    got = execute_root(store, dag, full_table_ranges(TID), start_ts=100, **kw)
    rows = [tuple(datum_group_key(d) for d in r) for r in got.rows()]
    return rows, {n: getattr(metrics, n).value - before[n] for n in COUNTERS}


def tied_store(regions: int, stores: int = 1):
    """240 rows whose groups tie: key h % 6 (NULL where h % 6 == 5) has 40
    rows each, so a TopN by count has nothing but the input order to break
    its ties with; the amounts are exact decimals."""
    store = TPUStore()
    rows = []
    for h in range(240):
        row = [Datum.NULL if h % 6 == 5 else Datum.i64(h % 6), Datum.dec(MyDecimal(f"{(h * 37) % 1000 / 100:.2f}")),
               Datum.string(("ox", "ant", "bee")[h % 3]), Datum.u64(h)]
        rows.append(row)
        store.put_row(TID, h, [1, 2, 3, 4], row, ts=10)
    for i in range(1, regions):
        store.cluster.split(tablecodec.encode_row_key(TID, i * 240 // regions))
    if stores > 1:
        store.cluster.set_stores(stores)
        store.cluster.scatter()
    return store, rows


I64 = new_longlong()
GROUPED = Aggregation(group_by=(C(0),), aggs=(AggDesc("count", ()), AggDesc("sum", (C(1),)), AggDesc("avg", (C(1),))))
G = lambda i: col(i, GROUPED.output_fts()[i])   # count, sum, avg, key
SCALAR = Aggregation(group_by=(), aggs=(AggDesc("count", ()), AggDesc("sum", (C(1),)), AggDesc("avg", (C(1),)),
                                         AggDesc("min", (C(3),)), AggDesc("max", (C(1),)), AggDesc("first_row", (C(3),))))
NONE_PASS = Selection((func("lt", BOOL, C(3), lit(0, new_longlong(unsigned=True))),))
TAILS = {
    # name: (executors behind the scan, output offsets)
    "having_topn_with_ties": ((GROUPED, Selection((func("gt", BOOL, G(1), lit("100.00", new_decimal(10, 2))),)),
                               TopN(order_by=((G(0), True),), limit=4)), (0, 1, 2, 3)),
    "sort": ((GROUPED, Sort(order_by=((G(3), True),))), (0, 1, 2, 3)),
    "sort_limit": ((GROUPED, Sort(order_by=((G(1), False), (G(3), False))), Limit(3)), (0, 1, 2, 3)),
    "reordered_offsets": ((GROUPED, TopN(order_by=((G(3), False),), limit=5)), (3, 0, 2)),
    "projection_with_a_literal": ((GROUPED, Sort(order_by=((G(3), False),)),
                                   Projection((func("plus", I64, G(0), lit(1000, I64)), G(3)))), (1, 0)),
    "scalar": ((SCALAR,), (0, 1, 2, 3, 4, 5)),
    "scalar_over_no_rows": ((NONE_PASS, SCALAR), (0, 1, 2, 3, 4, 5)),
    "groups_over_no_rows": ((NONE_PASS, GROUPED, Sort(order_by=((G(3), False),))), (0, 1, 2, 3)),
    "topn_of_rows": ((Selection((func("ge", BOOL, C(1), lit("2.00", new_decimal(3, 2))),)),
                      TopN(order_by=((C(1), True), (C(0), False)), limit=9)), (3, 1, 0)),
}


class TestRootHalfRidesTheRequest:
    @pytest.mark.parametrize("regions", [1, 4], ids=["lone_task", "mesh_group"])
    @pytest.mark.parametrize("tail", list(TAILS))
    def test_one_program_answers_the_split_paths_rows(self, tail, regions):
        """One launch and one read-back where the split path has two; the
        rows are the split path's, in its order (ties, NULL group, empty
        input, reordered offsets), and the oracle's."""
        store, rows = tied_store(regions)
        executors, offsets = TAILS[tail]
        dag = DAGRequest((scan(), *executors), output_offsets=offsets)
        fused, m = counted(store, dag)
        with root_half_left_off():
            split, ms = counted(store, dag)
        assert fused == split
        assert sorted(fused) == canon(run_dag_reference(dag, Chunk.from_rows(FTS, rows)))
        assert (m["PROGRAM_LAUNCHES"], m["PROGRAM_FETCHES"], m["ROOT_FUSED_STATEMENTS"], m["ROOT_FUSE_FALLBACKS"]) == (1, 1, 1, 0), m
        assert (ms["PROGRAM_LAUNCHES"], ms["PROGRAM_FETCHES"], ms["ROOT_FUSED_STATEMENTS"], ms["ROOT_FUSE_FALLBACKS"]) == (2, 2, 0, 1), ms
        assert m["MESH_COP_BATCHES"] == ms["MESH_COP_BATCHES"] == (regions > 1) and m["MESH_COP_FALLBACKS"] == 0
        if tail == "having_topn_with_ties":
            assert len({r[0] for r in fused}) == 1 and len(fused) == 4      # four of six equal counts: the order decided
        if tail == "sort":
            assert fused[0][3] == (0, None) or fused[-1][3] == (0, None)      # the NULL group is among the rows

    @pytest.mark.parametrize("regions", [1, 4], ids=["lone_task", "mesh_group"])
    def test_two_literals_of_one_shape_share_the_one_program(self, regions):
        """The tail's literal is an operand like the pushdown's: the key of
        the fused program is the unsplit DAG's shape."""
        store, rows = tied_store(regions)

        def dag(floor: str, bonus: int):
            sel = Selection((func("ge", BOOL, C(1), lit(floor, new_decimal(3, 2))),))
            having = Selection((func("gt", BOOL, G(0), lit(bonus, I64)),))
            proj = Projection((func("plus", I64, G(0), lit(bonus, I64)), G(3)))
            return DAGRequest((scan(), sel, GROUPED, having, Sort(order_by=((G(3), False),)), proj), output_offsets=(0, 1))

        first, m1 = counted(store, dag("1.00", 7))
        second, m2 = counted(store, dag("3.00", 11))
        assert m1["PROGRAM_COMPILES"] == 1 and m2["PROGRAM_COMPILES"] == 0 and m2["PROGRAM_LAUNCHES"] == 1
        assert m1["ROOT_FUSED_STATEMENTS"] == m2["ROOT_FUSED_STATEMENTS"] == 1
        for got, d in ((first, dag("1.00", 7)), (second, dag("3.00", 11))):
            assert sorted(got) == canon(run_dag_reference(d, Chunk.from_rows(FTS, rows)))
        assert first != second

    DECLINES = ["two_stores", "several_tasks_no_mesh_shape", "mesh_degrades_to_vmap", "mesh_program_overflows",
                "lone_program_overflows", "lone_program_fails", "host_only_operator", "explain_analyze", "low_memory",
                "paging", "build_side", "a_lane_from_the_cop_cache", "a_lane_retried", "the_lone_task_retried"]

    @pytest.mark.parametrize("why", DECLINES)
    def test_every_decline_answers_the_split_paths_rows_and_is_counted(self, why, monkeypatch):
        """The store leaves the root's half off and the root merges as
        before: the same rows as the split path, a second program, one
        fall-back counted and no fused statement."""
        lone = why in ("lone_program_overflows", "lone_program_fails", "paging", "the_lone_task_retried")
        store, rows = tied_store(1 if lone else 4, stores=2 if why == "two_stores" else 1)
        tail = (GROUPED, Selection((func("gt", BOOL, G(1), lit("100.00", new_decimal(10, 2))),)), TopN(order_by=((G(0), True),), limit=4))
        offsets, kw = (0, 1, 2, 3), {}
        if why in ("several_tasks_no_mesh_shape", "paging"):
            tail, offsets = (Selection((func("ge", BOOL, C(1), lit("2.00", new_decimal(3, 2))),)), Sort(order_by=((C(1), True), (C(3), False)))), (3, 1)
        if why == "host_only_operator":
            tail = (GROUPED, Sort(order_by=((G(3), False),)),
                    Projection((G(0), func("replace", new_varchar(16), lit("a-b", new_varchar(8)), lit("-", new_varchar(8)), lit("+", new_varchar(8))))))
            offsets = (0, 1)
        dag = DAGRequest((scan(), *tail), output_offsets=offsets)
        with root_half_left_off():
            split, _ = counted(store, dag)
        store.evict_caches()     # the split run's lanes left cop results behind
        if why == "mesh_degrades_to_vmap":
            monkeypatch.setattr(store, "MESH_MIN_GROUP_ROWS", 10 ** 9)
        elif why == "mesh_program_overflows":
            real = executor_mod.drive_mesh_program_info

            def flagged(*a, root=False, **k):
                chunk, counts, info = real(*a, root=root, **k)
                return (None if root else chunk), counts, info   # the program's one global flag, raised by its root half

            monkeypatch.setattr(executor_mod, "drive_mesh_program_info", flagged)
        elif why in ("lone_program_overflows", "lone_program_fails"):
            real = store_mod.drive_program_info

            def refusing(cache, d, *a, **k):
                if d is dag:
                    raise OverflowRetryError("forced") if why == "lone_program_overflows" else TypeError("forced")
                return real(cache, d, *a, **k)

            monkeypatch.setattr(store_mod, "drive_program_info", refusing)
        elif why == "explain_analyze":
            kw["summary_sink"] = []
        elif why == "low_memory":
            kw["low_memory"] = True
        elif why == "paging":
            kw["paging_size"] = 16
        elif why == "build_side":
            kw["build_side"] = True
        elif why == "a_lane_from_the_cop_cache":
            with root_half_left_off():
                execute_root(store, dag, full_table_ranges(TID), start_ts=100, mesh=False)   # the pool tier files every lane's result
        elif why in ("a_lane_retried", "the_lone_task_retried"):
            real = store._coprocessor if lone else store.batch_coprocessor
            seen = []

            def stale_once(req, *a, **k):
                if not seen:            # the first request reaches the store with a stale epoch, as after a split
                    seen.append(1)
                    (req if lone else req[0]).region_epoch += 7
                return real(req, *a, **k)

            monkeypatch.setattr(store, "_coprocessor" if lone else "batch_coprocessor", stale_once)
        got, m = counted(store, dag, **kw)
        assert got == split, why
        assert sorted(got) == canon(run_dag_reference(dag, Chunk.from_rows(FTS, rows)))
        assert (m["ROOT_FUSED_STATEMENTS"], m["ROOT_FUSE_FALLBACKS"]) == (0, 1), (why, m)
        if why == "a_lane_from_the_cop_cache":
            assert m["PROGRAM_LAUNCHES"] == 1, m            # every lane a cop result: the root's merge alone
        elif why not in ("low_memory", "paging", "explain_analyze"):   # those count launches of their own kind
            assert m["PROGRAM_LAUNCHES"] >= 2, (why, m)
        if why in ("mesh_degrades_to_vmap", "mesh_program_overflows"):
            assert (m["MESH_COP_BATCHES"], m["MESH_COP_FALLBACKS"]) == (0, 1), m
        if why in ("two_stores", "a_lane_retried"):
            assert m["MESH_COP_BATCHES"] == (2 if why == "two_stores" else 1), m    # the mesh tier served; its state went to the root

    def test_a_fused_answer_is_filed_under_the_fused_request_and_never_answers_a_split_one(self):
        store, _rows = tied_store(1)
        dag = DAGRequest((scan(), GROUPED, Sort(order_by=((G(3), False),))), output_offsets=(0, 1, 2, 3))
        fused, m = counted(store, dag)
        again, m2 = counted(store, dag)            # the identical repeat: the whole answer is a cop result, no launch at all
        with root_half_left_off():
            split, ms = counted(store, dag)        # asks for the pushdown half: not that entry
            split2, ms2 = counted(store, dag)      # the pushdown half's own entry, then the merge
        assert fused == again == split == split2
        assert (m["PROGRAM_LAUNCHES"], m2["PROGRAM_LAUNCHES"], ms["PROGRAM_LAUNCHES"], ms2["PROGRAM_LAUNCHES"]) == (1, 0, 2, 1)
        assert m2["ROOT_FUSED_STATEMENTS"] == 1 and ms2["ROOT_FUSE_FALLBACKS"] == 1
