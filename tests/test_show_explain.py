"""SHOW CREATE TABLE/COLUMNS/INDEX/STATUS + EXPLAIN ANALYZE
(ref: pkg/executor/show.go, explain.go with exec summaries)."""

import pytest

from tidb_tpu.sql.session import Session


@pytest.fixture()
def sess():
    s = Session()
    s.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT, s VARCHAR(8))")
    s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i % 7},'x{i % 3}')" for i in range(1, 101)))
    return s


def test_show_create_table_reimports(sess):
    ddl = sess.execute("SHOW CREATE TABLE t").values()[0][1]
    s2 = Session()
    s2.execute(ddl.rstrip().rstrip(";"))
    assert [c.name for c in s2.catalog.table("t").columns] == ["id", "v", "s"]


def test_show_columns(sess):
    rows = sess.execute("SHOW COLUMNS FROM t").values()
    # declared type spelling is preserved (TiDB prints int, not bigint)
    assert rows[0][:4] == ["id", "int", "NO", "PRI"]
    assert rows[2][0] == "s" and rows[2][1] == "varchar(8)"


def test_show_index(sess):
    sess.execute("CREATE UNIQUE INDEX uv ON t (id, v)")
    rows = sess.execute("SHOW INDEX FROM t").values()
    assert rows == [["t", 0, "uv", 1, "id"], ["t", 0, "uv", 2, "v"]]


def test_show_status_metrics(sess):
    rows = sess.execute("SHOW STATUS").values()
    names = [r[0] for r in rows]
    assert any("cop_requests" in n for n in names)


def test_explain_analyze_row_counts(sess):
    rows = sess.execute("EXPLAIN ANALYZE SELECT count(*) FROM t WHERE v < 3").values()
    by_exec = {r[0]: r for r in rows}
    assert by_exec["push[Selection]"][1] == 44  # rows surviving the filter
    assert by_exec["result"][1] == 1
    scan_row = rows[0]
    assert scan_row[0].startswith("push[") and scan_row[2] >= 1  # tasks


def test_explain_analyze_multi_region(sess):
    from tidb_tpu.codec import tablecodec

    tid = sess.catalog.table("t").table_id
    for h in (30, 60):
        sess.store.cluster.split(tablecodec.encode_row_key(tid, h))
    rows = sess.execute("EXPLAIN ANALYZE SELECT count(*) FROM t").values()
    by_exec = {r[0]: r for r in rows}
    assert by_exec["push[TableScan]"][1] == 100
    assert by_exec["push[TableScan]"][2] == 3  # one summary per region task


def test_explain_analyze_attribution_columns(sess):
    """The device-time attribution columns (ref: EXPLAIN ANALYZE execution
    info: cop task compile time + coprocessor-cache hit ratio + bytes)."""
    res = sess.execute("EXPLAIN ANALYZE SELECT count(*) FROM t WHERE v < 3")
    assert res.columns == ["executor", "rows", "tasks", "time", "compile", "cache", "bytes"]
    by_exec = {r[0]: r for r in res.values()}
    scan = by_exec["push[TableScan]"]
    n_tasks = scan[2]
    hits, total = scan[5].split("/")
    assert int(total) == n_tasks and 0 <= int(hits) <= n_tasks
    assert scan[4].endswith("ms")  # compile time, shared per fused program
    assert scan[6] > 0  # decoded region bytes ride the scan row
    # the SAME query again: every per-task program now comes from the cache
    res2 = sess.execute("EXPLAIN ANALYZE SELECT count(*) FROM t WHERE v < 3")
    scan2 = {r[0]: r for r in res2.values()}["push[TableScan]"]
    hits2, total2 = scan2[5].split("/")
    assert hits2 == total2  # all cache hits, no recompiles
    assert scan2[4] == "0.00ms"


def test_explain_analyze_is_the_same_beside_a_statement_whose_root_half_was_fused(sess):
    """A lone cop task runs the whole statement in one program (ISSUE 37);
    EXPLAIN ANALYZE declines that, so its rows are what they were: the
    pushdown's executors with their counts, then the result."""
    from tidb_tpu.util import metrics

    q = "SELECT v, count(*) FROM t WHERE id > {} GROUP BY v ORDER BY v LIMIT 4"
    fused = metrics.ROOT_FUSED_STATEMENTS.value
    got = sess.execute(q.format(10)).values()
    assert metrics.ROOT_FUSED_STATEMENTS.value == fused + 1 and len(got) == 4
    declined = metrics.ROOT_FUSE_FALLBACKS.value
    rows = sess.execute("EXPLAIN ANALYZE " + q.format(11)).values()
    assert metrics.ROOT_FUSE_FALLBACKS.value == declined + 1 and metrics.ROOT_FUSED_STATEMENTS.value == fused + 1
    assert [r[0] for r in rows][:5] == ["push[TableScan]", "push[Selection]", "push[Aggregation]",
                                        "root[Aggregation]", "root[TopN]"]
    by_exec = {r[0]: r for r in rows}
    assert (by_exec["push[Selection]"][1], by_exec["push[Aggregation]"][1], by_exec["result"][1]) == (89, 7, 4)
