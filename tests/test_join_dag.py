"""Join executor in the DAG (VERDICT next #1): device hash join vs oracle
for every join type, nested build pipelines, TPC-H Q3 end-to-end through
distsql with broadcast build sides, and the overflow->oracle fallback."""

import numpy as np
import pytest

from tidb_tpu.chunk import Chunk
from tidb_tpu.codec import tablecodec
from tidb_tpu.distsql import KVRequest, full_table_ranges, select
from tidb_tpu.exec import (
    Aggregation,
    ColumnInfo,
    DAGRequest,
    Join,
    Selection,
    TableScan,
    TopN,
    run_dag_on_chunk,
    run_dag_on_chunks,
    run_dag_reference,
)
from tidb_tpu.exec.executor import datum_group_key
from tidb_tpu.expr import AggDesc, AggMode, col, func, lit
from tidb_tpu.store import TPUStore
from tidb_tpu.types import Datum, MyDecimal, MyTime, new_datetime, new_decimal, new_longlong, new_varchar

BOOL = new_longlong(notnull=True)

# lineitem-lite / orders-lite / customer-lite schemas
LFTS = [new_longlong(), new_decimal(10, 2), new_decimal(4, 2), new_datetime()]  # orderkey, price, disc, shipdate
OFTS = [new_longlong(), new_longlong(), new_datetime(), new_longlong()]  # orderkey, custkey, orderdate, shippriority
CFTS = [new_longlong(), new_varchar(10)]  # custkey, mktsegment

L = lambda i: col(i, LFTS[i])


def canon(rows):
    return sorted(tuple(datum_group_key(d) for d in r) for r in rows)


def rand_date(rng):
    return Datum.time(MyTime.from_ymd(1994 + int(rng.integers(3)), 1 + int(rng.integers(12)), 1 + int(rng.integers(28))))


def make_tables(nl=300, no=60, nc=20, seed=5, null_p=0.04):
    rng = np.random.default_rng(seed)

    def maybe(d):
        return Datum.NULL if rng.random() < null_p else d

    lrows = [
        [
            maybe(Datum.i64(int(rng.integers(0, no + 10)))),
            maybe(Datum.dec(MyDecimal(f"{int(rng.integers(100, 99999))/100:.2f}"))),
            maybe(Datum.dec(MyDecimal(f"0.0{int(rng.integers(10))}"))),
            maybe(rand_date(rng)),
        ]
        for _ in range(nl)
    ]
    orows = [
        [
            Datum.i64(k),
            maybe(Datum.i64(int(rng.integers(0, nc + 3)))),
            maybe(rand_date(rng)),
            Datum.i64(int(rng.integers(0, 3))),
        ]
        for k in range(no)
    ]
    crows = [
        [Datum.i64(k), maybe(Datum.string(["BUILDING", "AUTOMOBILE", "MACHINERY"][int(rng.integers(3))]))]
        for k in range(nc)
    ]
    return lrows, orows, crows


def scans():
    ls = TableScan(1, tuple(ColumnInfo(i + 1, ft) for i, ft in enumerate(LFTS)))
    os_ = TableScan(2, tuple(ColumnInfo(i + 1, ft) for i, ft in enumerate(OFTS)))
    cs = TableScan(3, tuple(ColumnInfo(i + 1, ft) for i, ft in enumerate(CFTS)))
    return ls, os_, cs


class TestJoinTypes:
    @pytest.mark.parametrize("jt", ["inner", "left_outer", "semi", "anti"])
    def test_parity(self, jt):
        lrows, orows, _ = make_tables()
        lch, och = Chunk.from_rows(LFTS, lrows), Chunk.from_rows(OFTS, orows)
        ls, os_, _ = scans()
        join = Join(build=(os_,), probe_keys=(L(0),), build_keys=(col(0, OFTS[0]),), join_type=jt)
        offs = tuple(range(8)) if jt in ("inner", "left_outer") else tuple(range(4))
        dag = DAGRequest((ls, join), output_offsets=offs)
        dev = run_dag_on_chunks(dag, [lch, och])
        ref = run_dag_reference(dag, [lch, och])
        assert canon(dev.rows()) == canon(ref)

    def test_string_key_join(self):
        _, _, crows = make_tables()
        c2 = [[r[1], Datum.i64(i)] for i, r in enumerate(crows)]  # (segment, id)
        fts2 = [CFTS[1], new_longlong()]
        pch = Chunk.from_rows(fts2, c2)
        bch = Chunk.from_rows([CFTS[1]], [[Datum.string("BUILDING")], [Datum.string("MACHINERY")]])
        ps = TableScan(5, (ColumnInfo(1, fts2[0]), ColumnInfo(2, fts2[1])))
        bs = TableScan(6, (ColumnInfo(1, CFTS[1]),))
        join = Join(build=(bs,), probe_keys=(col(0, fts2[0]),), build_keys=(col(0, CFTS[1]),), join_type="semi")
        dag = DAGRequest((ps, join), output_offsets=(0, 1))
        dev = run_dag_on_chunks(dag, [pch, bch])
        ref = run_dag_reference(dag, [pch, bch])
        assert canon(dev.rows()) == canon(ref)

    def test_key_type_mismatch_raises(self):
        lrows, orows, _ = make_tables(nl=10, no=5)
        lch, och = Chunk.from_rows(LFTS, lrows), Chunk.from_rows(OFTS, orows)
        ls, os_, _ = scans()
        # decimal(10,2) key vs int key: planner must cast; builder refuses
        join = Join(build=(os_,), probe_keys=(L(1),), build_keys=(col(0, OFTS[0]),), join_type="inner")
        dag = DAGRequest((ls, join), output_offsets=(0,))
        with pytest.raises(TypeError, match="join key class mismatch"):
            run_dag_on_chunks(dag, [lch, och])


def test_join_max_key_vs_null_collision():
    """A legitimate BIGINT-max join key must not collide with the +max mask
    used for NULL-key build rows (regression: unusable rows must sort
    strictly after usable rows of the max-key run)."""
    fts = [new_longlong()]
    mx = (1 << 63) - 1
    brows = [[Datum.NULL], [Datum.i64(mx)], [Datum.NULL], [Datum.i64(5)]]
    prows = [[Datum.i64(mx)], [Datum.i64(5)], [Datum.NULL]]
    pch, bch = Chunk.from_rows(fts, prows), Chunk.from_rows(fts, brows)
    ps = TableScan(1, (ColumnInfo(1, fts[0]),))
    bs = TableScan(2, (ColumnInfo(1, fts[0]),))
    for jt in ("inner", "left_outer", "semi", "anti"):
        join = Join(build=(bs,), probe_keys=(col(0, fts[0]),), build_keys=(col(0, fts[0]),), join_type=jt)
        offs = (0, 1) if jt in ("inner", "left_outer") else (0,)
        dag = DAGRequest((ps, join), output_offsets=offs)
        dev = run_dag_on_chunks(dag, [pch, bch])
        ref = run_dag_reference(dag, [pch, bch])
        assert canon(dev.rows()) == canon(ref), jt


def test_overflow_oracle_fallback():
    """Degenerate fan-out (all keys equal) exhausts the capacity retries;
    the spill analog (probe halving, exec/executor.py _spill_partitioned)
    then resolves it with device kernels only — no oracle needed."""
    from tidb_tpu.util import metrics

    n = 64
    fts = [new_longlong()]
    pch = Chunk.from_rows(fts, [[Datum.i64(1)] for _ in range(n)])
    bch = Chunk.from_rows(fts, [[Datum.i64(1)] for _ in range(n)])
    ps = TableScan(1, (ColumnInfo(1, fts[0]),))
    bs = TableScan(2, (ColumnInfo(1, fts[0]),))
    join = Join(build=(bs,), probe_keys=(col(0, fts[0]),), build_keys=(col(0, fts[0]),), join_type="inner")
    dag = DAGRequest((ps, join), output_offsets=(0, 1))
    out = run_dag_on_chunks(dag, [pch, bch], max_retries=0)  # 64*64 out rows >> 64 capacity
    assert out.num_rows() == n * n
    before = metrics.SPILL_PARTITIONS.value
    out2 = run_dag_on_chunks(dag, [pch, bch], max_retries=0, oracle_fallback=False)
    assert out2.num_rows() == n * n
    assert metrics.SPILL_PARTITIONS.value > before


def test_store_overflow_fallback_partial_agg():
    """Region cop task with degenerate join fan-out + Partial1 agg: the
    store's oracle fallback must handle partial mode (not just Complete)."""
    from tidb_tpu.store import CopRequest

    store = TPUStore()
    fts = [new_longlong()]
    n = 128
    for h in range(n):
        store.put_row(1, h, [1], [Datum.i64(1)], ts=5)  # all join keys equal
    bch = Chunk.from_rows(fts, [[Datum.i64(1)] for _ in range(n)])
    ps = TableScan(1, (ColumnInfo(1, fts[0]),))
    bs = TableScan(2, (ColumnInfo(1, fts[0]),))
    join = Join(build=(bs,), probe_keys=(col(0, fts[0]),), build_keys=(col(0, fts[0]),), join_type="inner")
    agg = Aggregation(group_by=(col(0, fts[0]),), aggs=(AggDesc("count", ()),), partial=True)
    dag = DAGRequest((ps, join, agg), output_offsets=(0, 1))
    region = store.cluster.regions_in_range(b"", b"\xff" * 20)[0]
    resp = store.coprocessor(CopRequest(dag, full_table_ranges(1), start_ts=100, region_id=region.region_id, region_epoch=region.epoch, aux_chunks=[bch]))
    assert resp.other_error is None, resp.other_error
    # 128*128 join rows >> capacity growth; fallback produced the state
    r = resp.chunk.rows()
    assert len(r) == 1 and r[0][0].val == n * n
    # summaries aligned with the device walk: [probe scan, build scan, join, agg]
    assert len(resp.exec_summaries) == 4


def q3_dag(partial: bool):
    """TPC-H Q3 shape: lineitem ⋈ (orders ⋈ customer) + filters + group agg.

    revenue = sum(l_extendedprice * (1 - l_discount)) grouped by
    (l_orderkey, o_orderdate, o_shippriority)."""
    ls, os_, cs = scans()
    cust_sel = Selection((func("eq", BOOL, col(1, CFTS[1]), lit("BUILDING", new_varchar(10))),))
    inner_join = Join(
        build=(cs, cust_sel),
        probe_keys=(col(1, OFTS[1]),),
        build_keys=(col(0, CFTS[0]),),
        join_type="inner",
    )
    build_pipeline = (os_, Selection((func("lt", BOOL, col(2, OFTS[2]), lit("1995-03-15", new_datetime())),)), inner_join)
    outer_join = Join(
        build=build_pipeline,
        probe_keys=(L(0),),
        build_keys=(col(0, OFTS[0]),),
        join_type="inner",
    )
    lineitem_sel = Selection((func("gt", BOOL, L(3), lit("1995-03-15", new_datetime())),))
    # post-join schema: l(4 cols) + o(4 cols) + c(2 cols)
    post = LFTS + OFTS + CFTS
    revenue = func(
        "mul",
        new_decimal(31, 4),
        col(1, post[1]),
        func("minus", new_decimal(12, 2), lit(1, new_longlong()), col(2, post[2])),
    )
    agg = Aggregation(
        group_by=(col(0, post[0]), col(6, post[6]), col(7, post[7])),
        aggs=(AggDesc("sum", (revenue,)),),
        partial=partial,
    )
    dag = DAGRequest((ls, lineitem_sel, outer_join, agg), output_offsets=(0, 1, 2, 3))
    return dag


def test_q3_single_chunk_parity():
    lrows, orows, crows = make_tables()
    chunks = [Chunk.from_rows(LFTS, lrows), Chunk.from_rows(OFTS, orows), Chunk.from_rows(CFTS, crows)]
    dag = q3_dag(partial=False)
    dev = run_dag_on_chunks(dag, chunks)
    ref = run_dag_reference(dag, chunks)
    assert len(ref) > 0, "Q3 test data must produce rows"
    assert canon(dev.rows()) == canon(ref)


def test_q3_through_distsql_broadcast():
    """Q3 over a region-split store: per-region broadcast join + Partial1
    agg, root Final merge + TopN — BASELINE config #5's execution shape."""
    lrows, orows, crows = make_tables(nl=400, no=80, nc=25)
    store = TPUStore()
    for h, r in enumerate(lrows):
        store.put_row(1, h, [1, 2, 3, 4], r, ts=10)
    for h, r in enumerate(orows):
        store.put_row(2, h, [1, 2, 3, 4], r, ts=10)
    for h, r in enumerate(crows):
        store.put_row(3, h, [1, 2], r, ts=10)
    for frac in (1, 2, 3):
        store.cluster.split(tablecodec.encode_row_key(1, frac * 100))

    # root: fetch broadcast operands (scan-only DAGs through distsql)
    ls, os_, cs = scans()
    odag = DAGRequest((os_,), output_offsets=tuple(range(4)))
    cdag = DAGRequest((cs,), output_offsets=tuple(range(2)))
    och = select(store, KVRequest(odag, full_table_ranges(2), start_ts=100)).merged()
    cch = select(store, KVRequest(cdag, full_table_ranges(3), start_ts=100)).merged()

    # per-region: join + Partial1 agg with broadcast aux chunks
    dag = q3_dag(partial=True)
    res = select(store, KVRequest(dag, full_table_ranges(1), start_ts=100, aux_chunks=[och, cch]))
    assert len(res.chunks) == 4  # one per region
    stacked = Chunk.concat(res.chunks)

    # root Final merge + TopN(revenue desc, orderdate) LIMIT 10
    pfts = stacked.field_types()  # [sum_state, l_orderkey, o_orderdate, o_shippriority]
    merge_agg = Aggregation(
        group_by=(col(1, pfts[1]), col(2, pfts[2]), col(3, pfts[3])),
        aggs=(AggDesc("sum", (col(0, pfts[0]),), mode=AggMode.Final),),
        merge=True,
    )
    topn = TopN(order_by=((col(0, pfts[0]), True), (col(2, pfts[2]), False)), limit=10)
    root = DAGRequest(
        (TableScan(0, tuple(ColumnInfo(i, ft) for i, ft in enumerate(pfts))), merge_agg, topn),
        output_offsets=(0, 1, 2, 3),
    )
    final = run_dag_on_chunk(root, stacked)

    # oracle: single-shot Complete Q3 + same TopN over all rows
    oracle_rows = run_dag_reference(
        q3_dag(partial=False), [Chunk.from_rows(LFTS, lrows), Chunk.from_rows(OFTS, orows), Chunk.from_rows(CFTS, crows)]
    )
    # oracle schema: [revenue, l_orderkey, o_orderdate, o_shippriority]
    ordered = sorted(
        oracle_rows,
        key=lambda r: (
            -(float(str(r[0].val)) if not r[0].is_null() else float("-inf")),
            r[2].val.packed if not r[2].is_null() else -1,
        ),
    )[:10]
    # compare revenue multisets of the top-10 (order ties can permute)
    got = sorted(str(r[0].val) for r in final.rows())
    want = sorted(str(r[0].val) for r in ordered)
    assert final.num_rows() == len(ordered)
    assert got == want, f"\ngot ={got}\nwant={want}"


def test_planner_marks_pk_build_unique():
    """Joins whose build keys are the build table's PK handle (or a unique
    index) carry build_unique=True; non-unique keys do not."""
    from tidb_tpu.exec.dag import Join
    from tidb_tpu.sql import Session

    s = Session()
    s.execute("create table orders (o_id bigint primary key, o_cust bigint)")
    s.execute("create table lineitem (l_id bigint primary key, l_oid bigint, qty bigint)")
    s.execute("create table tags (t bigint, name varchar(10))")
    s.execute("create unique index uq_t on tags (t)")
    s.execute("insert into orders values (1, 10), (2, 20)")
    s.execute("insert into lineitem values (1, 1, 5), (2, 1, 7), (3, 2, 9)")
    s.execute("insert into tags values (10, 'a'), (20, 'b')")

    from tidb_tpu.parser import parse_one
    from tidb_tpu.sql.planner import plan_select

    def joins_of(sql):
        plan = plan_select(parse_one(sql), s.catalog)
        return [e for e in plan.dag.executors if isinstance(e, Join)]

    js = joins_of("select count(*) from lineitem, orders where l_oid = o_id")
    assert len(js) == 1 and js[0].build_unique  # PK handle build key
    js = joins_of("select count(*) from orders, tags where o_cust = t")
    assert len(js) == 1 and js[0].build_unique  # unique index build key
    # self-join on a NON-unique column: neither side's key is unique
    js = joins_of("select count(*) from lineitem a, lineitem b where a.l_oid = b.l_oid")
    assert len(js) == 1 and not js[0].build_unique

    # end-to-end result through the unique fast path
    r = s.execute(
        "select o_id, sum(qty) from lineitem join orders on l_oid = o_id group by o_id order by o_id"
    )
    assert [(int(x[0].val), int(str(x[1].val))) for x in r.rows] == [(1, 12), (2, 9)]


def _tpch_lite():
    """customer/orders/lineitem with TPC-H's key columns, lineitem largest."""
    from tidb_tpu.sql import Session

    s = Session()
    s.execute("create table customer (c_custkey bigint primary key, c_mktsegment char(10))")
    s.execute("create table orders (o_orderkey bigint primary key, o_custkey bigint, o_orderdate date)")
    s.execute("create table lineitem (l_orderkey bigint, l_linenumber bigint, l_flag char(1), "
              "l_price decimal(15,2), l_shipdate date, primary key (l_orderkey, l_linenumber))")
    s.execute("insert into customer values (1, 'BUILDING'), (2, 'MACHINERY')")
    s.execute("insert into orders values (10, 1, '1995-03-01'), (11, 2, '1995-03-02'), (12, 1, '1995-04-01')")
    s.execute("insert into lineitem values (10, 1, 'A', 5.00, '1995-03-20'), (10, 2, 'N', 7.00, '1995-03-21'), "
              "(11, 1, 'A', 9.00, '1995-03-22'), (12, 1, 'N', 1.00, '1995-05-01'), (12, 2, 'N', 2.00, '1994-05-01')")
    return s


def _plan(s, sql):
    from tidb_tpu.parser import parse_one
    from tidb_tpu.sql.planner import plan_select

    return plan_select(parse_one(sql), s.catalog)


Q3_LITE = (
    "select l_orderkey, sum(l_price) as revenue, o_orderdate from customer, orders, lineitem "
    "where c_mktsegment = 'BUILDING' and c_custkey = o_custkey and l_orderkey = o_orderkey "
    "and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15' "
    "group by l_orderkey, o_orderdate order by revenue desc, o_orderdate limit 10"
)


def test_build_sides_follow_equi_connectivity():
    """TPC-H Q3 lists customer before orders, and lineitem (the probe) has
    no key in common with customer: orders must be joined first, or the
    first join is a cartesian product on constant keys."""
    from tidb_tpu.exec.dag import Join
    from tidb_tpu.expr.ir import Const

    s = _tpch_lite()
    plan = _plan(s, Q3_LITE)
    assert [m.name for m in plan.build_tables] == ["orders", "customer"]
    for j in (e for e in plan.dag.executors if isinstance(e, Join)):
        assert not any(isinstance(k, Const) for k in j.probe_keys)
    r = s.execute(Q3_LITE).values()
    assert [(int(x[0]), str(x[1]), str(x[2])[:10]) for x in r] == [(10, "12.00", "1995-03-01")]


@pytest.mark.parametrize("where,want", [
    ("l_shipdate >= date '1995-03-21'", 3),
    ("l_shipdate < date '1994-05-01' + interval '1' year", 4),
    ("l_shipdate <= date '1995-06-01' - interval '70' day", 4),
])
def test_date_literal_and_quoted_interval_stay_on_device(where, want):
    """The TPC-H text's `date '...' +/- interval '1' year`: a time constant
    and a numeric count, so no string cast reaches the device program and
    the statement does not ride the oracle fallback."""
    from tidb_tpu.util import metrics

    s = _tpch_lite()
    before = metrics.COP_FALLBACKS.value
    r = s.execute(f"select count(*) from lineitem where {where}").values()
    assert int(r[0][0]) == want
    assert metrics.COP_FALLBACKS.value == before


def test_ndv_hint_survives_order_by():
    """ANALYZE's NDV product reaches the aggregation although ORDER BY (or
    a projection) follows it in the DAG — every TPC-H text has one."""
    s = _tpch_lite()
    s.execute("analyze table lineitem")
    bare = _plan(s, "select l_flag, count(*) from lineitem group by l_flag")
    ordered = _plan(s, "select l_flag, count(*) + 1 from lineitem group by l_flag order by l_flag")
    assert bare.small_groups == 16 and ordered.small_groups == 16
