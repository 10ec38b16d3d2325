"""The plan cache inside an explicit transaction: a SELECT is served from
the cache unless the transaction has buffered writes to a table it reads
(`txn_dirty`). A served statement reads the transaction's snapshot, as the
parse path does; FOR UPDATE and point writes keep the parse path, which
takes their locks (ref: TiDB serving cached plans inside transactions, with
UnionScan over the dirty tables)."""

import pytest

from tidb_tpu.sql.catalog import Catalog
from tidb_tpu.sql.session import Session, SQLError
from tidb_tpu.store import TPUStore
from tidb_tpu.util import metrics

# sysbench's reads (oltp_common.lua) and a selection the dag tier serves
STATEMENTS = {
    "point_select": ("select c from sb where id = {a}", "pointget"),
    "simple_range": ("select c from sb where id between {a} and {b}", "ast"),
    "sum_range": ("select sum(k) from sb where id between {a} and {b}", "ast"),
    "order_range": ("select c from sb where id between {a} and {b} order by c", "ast"),
    "distinct_range": ("select distinct c from sb where id between {a} and {b} order by c", "ast"),
    "selection": ("select id, c from sb where pad = 'p{p}'", "dag"),
}


def sql_of(name, a):
    return STATEMENTS[name][0].format(a=a, b=a + 9, p=a % 5)


def make_sessions(n=1):
    store, cat = TPUStore(), Catalog()
    out = [Session(store, cat) for _ in range(n)]
    s = out[0]
    s.execute("CREATE TABLE sb (id INT PRIMARY KEY, k INT NOT NULL DEFAULT 0, "
              "c CHAR(120) NOT NULL DEFAULT '', pad CHAR(60) NOT NULL DEFAULT '', KEY k_1 (k))")
    s.execute("INSERT INTO sb VALUES " + ",".join(
        f"({i},{i * 7 % 50},'c{i % 7:02d}','p{i % 5}')" for i in range(1, 65)))
    s.execute("CREATE TABLE u (id INT PRIMARY KEY, v INT)")
    s.execute("INSERT INTO u VALUES " + ",".join(f"({i},{i * 10})" for i in range(1, 9)))
    return out


def parsed_rows(s, sql):
    """The statement's rows on the parse path: the oracle for a served one."""
    s.execute("SET tidb_enable_plan_cache = OFF")
    try:
        return s.execute(sql).rows
    finally:
        s.execute("SET tidb_enable_plan_cache = ON")


def same_rows(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert [(d.kind, d.val) for d in ra] == [(d.kind, d.val) for d in rb]


def values(s, sql):
    return s.execute(sql).values()


@pytest.mark.parametrize("name", sorted(STATEMENTS))
def test_a_clean_transaction_serves_each_tier_as_the_parse_path_answers(name):
    (s,) = make_sessions()
    s.execute(sql_of(name, 1))  # install
    s.execute("BEGIN")
    for a in (3, 12, 40):
        sql = sql_of(name, a)
        oracle = parsed_rows(s, sql)
        h0 = metrics.PLAN_CACHE_HITS.value
        got = s.execute(sql).rows
        assert s._last_plan_cache == ("hit", "", STATEMENTS[name][1])
        assert metrics.PLAN_CACHE_HITS.value == h0 + 1
        same_rows(got, oracle)
    s.execute("COMMIT")


@pytest.mark.parametrize("name", ["point_select", "sum_range", "selection"])
def test_a_served_read_keeps_the_transactions_snapshot(name):
    a, b = make_sessions(2)
    sql = sql_of(name, 5)
    a.execute(sql)  # install
    a.execute("BEGIN")
    before = values(a, sql)
    assert a._last_plan_cache[0] == "hit"
    b.execute("UPDATE sb SET c = 'new', k = k + 1000 WHERE id = 5")  # autocommit: committed
    assert values(a, sql) == before  # repeatable read: the snapshot of BEGIN
    assert a._last_plan_cache[0] == "hit"
    a.execute("COMMIT")
    after = values(a, sql)
    assert after != before and after == values(b, sql)


@pytest.mark.parametrize("write, point, seen", [
    ("UPDATE sb SET c = 'new' WHERE id = 5", 5, [[5, "new"]]),
    ("DELETE FROM sb WHERE id = 5", 5, []),
    ("INSERT INTO sb VALUES (1000, 3, 'ins', 'p0')", 1000, [[1000, "ins"]]),
])
def test_a_transaction_that_wrote_a_table_reads_it_on_the_parse_path(write, point, seen):
    (s,) = make_sessions()
    for name in STATEMENTS:
        s.execute(sql_of(name, 1))  # install
    s.execute("select id, c from sb where id = 1")
    s.execute("select v from u where id = 1")
    s.execute("BEGIN")
    s.execute(write)
    d0, t0 = metrics.PLAN_CACHE_DECLINES.labels("txn_dirty").value, metrics.PLAN_CACHE_TXN_HITS.value
    point_row = values(s, f"select id, c from sb where id = {point}")
    assert s._last_plan_cache == ("decline", "txn_dirty", "")
    assert point_row == seen
    # every tier declines, and each answer holds the transaction's own write
    for name in STATEMENTS:
        sql = sql_of(name, point - 4)
        got = s.execute(sql).rows
        assert s._last_plan_cache == ("decline", "txn_dirty", "")
        same_rows(got, parsed_rows(s, sql))
    in_range = [r for r in values(s, sql_of("selection", point)) if r[0] == point]
    assert in_range == seen
    assert metrics.PLAN_CACHE_DECLINES.labels("txn_dirty").value == d0 + 2 + len(STATEMENTS)
    # a table the transaction has not written is still served
    assert values(s, "select v from u where id = 4") == [[40]]
    assert s._last_plan_cache == ("hit", "", "pointget")
    assert metrics.PLAN_CACHE_TXN_HITS.value == t0 + 1
    s.execute("ROLLBACK")
    assert values(s, f"select id, c from sb where id = {point}") == (
        [] if point == 1000 else [[5, "c05"]])
    assert s._last_plan_cache == ("hit", "", "pointget")


def test_select_for_update_is_never_served_and_takes_its_lock():
    a, b = make_sessions(2)
    a.execute("select c from sb where id = 1")  # install the plain shape
    a.execute("BEGIN")
    h0 = metrics.PLAN_CACHE_HITS.value
    for _ in range(2):
        assert values(a, "select c from sb where id = 1 for update") == [["c01"]]
        assert a._last_plan_cache == ("decline", "for_update", "")
    assert metrics.PLAN_CACHE_HITS.value == h0
    assert a.txn.locked
    b.execute("SET innodb_lock_wait_timeout = 1")
    b.execute("BEGIN")
    with pytest.raises(SQLError) as e:
        b.execute("UPDATE sb SET k = k + 1 WHERE id = 1")
    assert e.value.code == 1205
    b.execute("ROLLBACK")
    a.execute("COMMIT")


def test_a_point_write_inside_a_transaction_takes_the_parse_path():
    (s,) = make_sessions()
    s.execute("UPDATE sb SET k = k + 1 WHERE id = 2")  # install
    s.execute("UPDATE sb SET k = k + 1 WHERE id = 3")
    assert s._last_plan_cache == ("hit", "", "pointwrite")
    s.execute("BEGIN")
    d0 = metrics.PLAN_CACHE_DECLINES.labels("in_txn").value
    assert s.execute("UPDATE sb SET k = k + 1 WHERE id = 4").affected == 1
    assert s._last_plan_cache == ("decline", "in_txn", "")
    assert metrics.PLAN_CACHE_DECLINES.labels("in_txn").value == d0 + 1
    assert s.txn.locked and s.txn.row_ops
    s.execute("COMMIT")
    assert values(s, "select k from sb where id = 4") == [[4 * 7 % 50 + 1]]


@pytest.mark.parametrize("installed_in", ["transaction", "autocommit"])
def test_an_entry_serves_both_sides_of_begin(installed_in):
    (s,) = make_sessions()
    sql1, sql2 = sql_of("sum_range", 1), sql_of("sum_range", 20)
    if installed_in == "transaction":
        s.execute("BEGIN")
        s.execute(sql1)
        assert s._last_plan_cache == ("miss", "", "")
        s.execute("COMMIT")
        got = s.execute(sql2).rows
        assert s._last_plan_cache == ("hit", "", "ast")
    else:
        s.execute(sql1)
        assert s._last_plan_cache == ("miss", "", "")
        s.execute("BEGIN")
        got = s.execute(sql2).rows
        assert s._last_plan_cache == ("hit", "", "ast")
        s.execute("COMMIT")
    same_rows(got, parsed_rows(s, sql2))


def test_the_txn_hit_counter_counts_only_hits_inside_a_transaction():
    (s,) = make_sessions()
    point = sql_of("point_select", 1)
    t0 = metrics.PLAN_CACHE_TXN_HITS.value
    s.execute("BEGIN")
    s.execute(point)  # a miss inside the transaction
    s.execute("COMMIT")
    s.execute(sql_of("point_select", 2))  # an autocommit hit
    assert s._last_plan_cache[0] == "hit"
    assert metrics.PLAN_CACHE_TXN_HITS.value == t0
    s.execute("BEGIN")
    h0 = metrics.PLAN_CACHE_HITS.value
    for a in range(3, 8):
        s.execute(sql_of("point_select", a))
    s.execute(sql_of("simple_range", 1))  # a miss
    s.execute(sql_of("simple_range", 2))
    s.execute("PREPARE st FROM 'select c from sb where id = ?'")
    s.execute("SET @p = 9")
    s.execute("EXECUTE st USING @p")  # served through the parse path's look-up
    assert s._last_plan_cache == ("hit", "", "pointget")
    assert metrics.PLAN_CACHE_TXN_HITS.value == t0 + 7
    assert metrics.PLAN_CACHE_HITS.value == h0 + 7
    s.execute("INSERT INTO u VALUES (100, 1)")
    s.execute("select v from u where id = 3")  # dirty: declined, not counted
    assert metrics.PLAN_CACHE_TXN_HITS.value == t0 + 7
    s.execute("COMMIT")
    s.execute(sql_of("point_select", 10))
    assert metrics.PLAN_CACHE_TXN_HITS.value == t0 + 7
