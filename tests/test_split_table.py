"""`SPLIT TABLE` executed (ISSUE 34): the served path's own way to cut a
loaded table into regions, which is what puts a statement's lanes on more
than one chip.  Over SQL: the regions counted and tiling the table, every
row read back, a scan after the split equal to the one before it, a second
split of the same range cutting nothing, what the store kept of the old
region missing once, and the errors."""

import pytest

from tidb_tpu.codec import tablecodec
from tidb_tpu.distsql import full_table_ranges
from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.sql.session import Session, SQLError
from tidb_tpu.util import metrics

ROWS = 1000


@pytest.fixture
def sess():
    s = Session()
    s.execute("create table t (id bigint primary key, v bigint, s varchar(20))")
    for lo in range(1, ROWS + 1, 250):
        s.execute("insert into t values " + ",".join(f"({i}, {i * 7 % 97}, 'r{i % 13}')" for i in range(lo, lo + 250)))
    s.execute("create table other (id bigint primary key, v bigint)")
    s.execute("insert into other values (1, 1), (2, 2)")
    return s


def table_regions(s, name):
    """The regions that hold a record key of the table, in key order."""
    rng = full_table_ranges(s.catalog.table(name).table_id)[0]
    return s.store.cluster.regions_in_range(rng.start, rng.end)


def handles(s, name, region):
    tid = s.catalog.table(name).table_id
    lo = max(region.start_key, full_table_ranges(tid)[0].start)
    return tablecodec.decode_row_key(lo)[1]


def test_between_cuts_the_table_into_the_regions_asked_for(sess):
    before = sess.execute("select id, v, s from t order by id").values()
    assert len(table_regions(sess, "t")) == 1
    n0 = metrics.SPLIT_TABLE_REGIONS.value
    got = sess.execute(f"split table t between (1) and ({ROWS + 1}) regions 4")
    assert got.columns == ["TOTAL_SPLIT_REGION", "SCATTER_FINISH_RATIO"]
    assert got.values() == [[4, 1.0]] and metrics.SPLIT_TABLE_REGIONS.value - n0 == 4
    regions = table_regions(sess, "t")
    assert len(regions) == 4
    # the ranges tile the table: each region starts where the one before ends,
    # the first at the table's first record key, at the handles of an even step
    assert all(a.end_key == b.start_key for a, b in zip(regions, regions[1:]))
    assert regions[0].start_key == full_table_ranges(sess.catalog.table("t").table_id)[0].start
    assert [handles(sess, "t", r) for r in regions[1:]] == [251, 501, 751]
    # every row is read back, a scan after the split equals the one before it
    assert sess.execute("select id, v, s from t order by id").values() == before
    count, total = sess.execute("select count(*), sum(v) from t").values()[0]
    assert (count, int(str(total))) == (ROWS, sum(r[1] for r in before))
    assert [sess.execute(f"select count(*) from t where id >= {lo} and id < {lo + 250}").scalar()
            for lo in (1, 251, 501, 751)] == [250] * 4
    assert sess.execute("select count(*) from other").scalar() == 2


def test_a_second_split_of_the_same_range_cuts_nothing(sess):
    sess.execute(f"split table t between (1) and ({ROWS + 1}) regions 4")
    n = len(sess.store.cluster.regions())
    assert sess.execute(f"split table t between (1) and ({ROWS + 1}) regions 4").values() == [[0, 1.0]]
    assert len(sess.store.cluster.regions()) == n
    # finer points only cut what is not cut yet
    assert sess.execute(f"split table t between (1) and ({ROWS + 1}) regions 8").values() == [[4, 1.0]]
    assert len(table_regions(sess, "t")) == 8


def test_by_points_and_region_for_spelling(sess):
    assert sess.execute("split table t by (100), (900), (100)").values() == [[3, 1.0]]
    assert [handles(sess, "t", r) for r in table_regions(sess, "t")[1:]] == [100, 900]
    assert sess.execute("split region for table t by (500)").values() == [[1, 1.0]]
    assert sess.execute("select count(*) from t where id < 500").scalar() == 499


def test_what_the_store_kept_of_the_old_region_misses_once_and_no_more(sess):
    """A split bumps the epochs of both sides (`Cluster.split`), which are
    in the key of the decoded chunks, their device batches and the cop
    results: the first statement after it reads the new regions, every
    later one finds what that one filed."""
    names = ("COP_CACHE_HITS", "COP_REQUESTS", "COP_DECODE_HITS", "COP_DECODE_MISSES")
    q = "select count(*), sum(v) from t where v < {n}"

    def moved(n):
        before = {k: getattr(metrics, k).value for k in names}
        got = sess.execute(q.format(n=n)).values()
        return got, {k: getattr(metrics, k).value - before[k] for k in names}

    want, first = moved(50)
    assert (first["COP_DECODE_MISSES"], first["COP_DECODE_HITS"]) == (1, 0)
    sess.execute(f"split table t between (1) and ({ROWS + 1}) regions 4")
    # another literal, so that no cop result answers: the four lanes of the
    # mesh tier decode once under the new epochs and are found from then on
    assert moved(51)[1] == {"COP_CACHE_HITS": 0, "COP_REQUESTS": 4, "COP_DECODE_HITS": 0, "COP_DECODE_MISSES": 4}
    assert moved(52)[1] == {"COP_CACHE_HITS": 0, "COP_REQUESTS": 4, "COP_DECODE_HITS": 4, "COP_DECODE_MISSES": 0}
    # the per-region tiers file a cop result a region: the same statement
    # misses once in each new region and hits in each afterwards
    sess.execute("set tidb_enable_tpu_mesh = OFF")
    got, m = moved(50)
    assert got == want and (m["COP_CACHE_HITS"], m["COP_REQUESTS"]) == (0, 4)
    got, m = moved(50)
    assert got == want and (m["COP_CACHE_HITS"], m["COP_REQUESTS"]) == (4, 4)


@pytest.mark.parametrize("sql,text", [
    ("split table nope between (0) and (10) regions 2", "unknown table"),
    ("split table t between (10) and (10) regions 2", "less than upper"),
    ("split table t between (0) and (3) regions 8", "too small"),
    ("split table t between (0) and (10) regions 0", "region count"),
    ("split table t between (0, 1) and (10, 1) regions 2", "one integer row handle"),
    ("split table t index idx between (0) and (10) regions 2", "INDEX is not supported"),
    ("split table t by (null)", "NULL"),
])
def test_errors_are_typed(sess, sql, text):
    with pytest.raises(SQLError, match=text):
        sess.execute(sql)
    assert len(table_regions(sess, "t")) == 1


def test_over_the_wire_and_traced():
    srv = MySQLServer(port=0)
    srv.start_background()
    try:
        c = MiniClient(srv.host, srv.port, timeout=60.0)
        c.query("create table w (a bigint primary key, b bigint)")
        c.query("insert into w values " + ",".join(f"({i}, {i})" for i in range(1, 401)))
        columns, rows = c.query("split table w between (1) and (401) regions 4")
        assert columns == ["TOTAL_SPLIT_REGION", "SCATTER_FINISH_RATIO"] and rows == [["4", "1"]]
        import json
        _, traced = c.query("trace format='json' split table w by (50)")
        tree = json.loads(traced[0][0])

        def find(node, name):
            return ([node] if node["name"] == name else []) + [n for ch in node.get("children", ()) for n in find(ch, name)]

        (sp,) = find(tree, "ddl.split_table")
        assert sp["attrs"] == {"table": "w", "regions": 1}
        with pytest.raises(Exception, match="unknown table"):
            c.query("split table nope by (1)")
        assert c.query("select count(*), sum(b) from w")[1] == [["400", str(400 * 401 // 2)]]
        c.close()
    finally:
        srv.close()
