"""Slow-query log + statement summary (VERDICT r3 missing #9; ref:
pkg/executor/adapter.go LogSlowQuery, pkg/util/stmtsummary)."""

from tidb_tpu.sql import Session
from tidb_tpu.util.stmtlog import normalize_sql


class TestStmtSummary:
    def test_digest_groups_literal_variants(self):
        n1, d1 = normalize_sql("select * from t where a = 5")
        n2, d2 = normalize_sql("SELECT * FROM t WHERE a = 99")
        n3, d3 = normalize_sql("select * from t where b = 5")
        assert d1 == d2 and n1 == n2 == "select * from t where a = ?"
        assert d3 != d1

    def test_summary_via_information_schema(self):
        s = Session()
        s.execute("create table t (a bigint primary key)")
        s.execute("insert into t values (1),(2),(3)")
        for v in (1, 2, 3):
            s.execute(f"select * from t where a = {v}")
        r = s.execute(
            "select exec_count, sum_rows from information_schema.statements_summary "
            "where digest_text = 'select * from t where a = ?'"
        )
        assert len(r.rows) == 1
        assert int(r.rows[0][0].val) == 3 and int(r.rows[0][1].val) == 3

    def test_errors_counted(self):
        s = Session()
        try:
            s.execute("select * from missing_table")
        except Exception:
            pass
        r = s.execute(
            "select errors from information_schema.statements_summary "
            "where digest_text = 'select * from missing_table'"
        )
        assert int(r.rows[0][0].val) == 1

    def test_summary_toggle(self):
        s = Session()
        s.execute("set tidb_enable_stmt_summary = OFF")
        s.execute("select 1")
        r = s.execute("select count(*) from information_schema.statements_summary")
        # only the OFF-window statements are absent; the SET itself ran
        # before the toggle applied... simplest: nothing recorded while OFF
        n_off = int(r.rows[0][0].val)
        s.execute("set tidb_enable_stmt_summary = ON")
        s.execute("select 1")
        r = s.execute("select count(*) from information_schema.statements_summary")
        assert int(r.rows[0][0].val) > n_off


class TestSlowLog:
    def test_slow_statement_lands_in_slow_query(self):
        s = Session()
        s.execute("create table t (a bigint primary key)")
        s.execute("set tidb_slow_log_threshold = 0")  # everything is slow now
        s.execute("insert into t values (42)")
        s.execute("set tidb_slow_log_threshold = 300")
        r = s.execute(
            "select query, success from information_schema.slow_query "
            "where digest = %r" % normalize_sql("insert into t values (42)")[1]
        )
        assert len(r.rows) >= 1
        assert "insert into t values (42)" in str(r.rows[0][0].val)
        assert int(r.rows[0][1].val) == 1

    def test_disabled_slow_log_records_nothing(self):
        s = Session()
        s.execute("set tidb_enable_slow_log = OFF")
        s.execute("set tidb_slow_log_threshold = 0")
        s.execute("select 1")
        s.execute("set tidb_slow_log_threshold = 300")
        s.execute("set tidb_enable_slow_log = ON")
        assert s.catalog.stmtlog.slow_entries() == []


def test_top_sql_cpu_attribution():
    """Top SQL (ISSUE 17; ref: pkg/util/topsql): per-digest CPU time
    accumulates into the windowed reporter and
    information_schema.tidb_top_sql surfaces it ranked by cpu+device."""
    from tidb_tpu import topsql
    from tidb_tpu.sql import Session

    topsql.COLLECTOR.reset()
    s = Session()
    s.execute("create table t (a bigint primary key, b bigint)")
    s.execute("insert into t values " + ",".join(f"({i},{i})" for i in range(300)))
    for i in range(5):
        s.execute(f"select sum(b) from t where a > {i}")
    s.execute("select 1")
    digest = normalize_sql("select sum(b) from t where a > 0")[1]
    rows = s.execute(
        "select exec_count, cpu_ns, cost_class from information_schema.tidb_top_sql "
        f"where digest = '{digest}'"
    ).values()
    # one row per window: the first, compiling execution can land in an
    # earlier window than the other four
    assert sum(r[0] for r in rows) == 5 and all(r[1] > 0 for r in rows)
    assert all(r[2] in ("point", "small", "scan", "heavy") for r in rows)
    # rows come out ranked by cumulative cpu+device within each window:
    # the repeated aggregation outranks `select 1`
    top = s.execute(
        "select digest from information_schema.tidb_top_sql limit 3"
    ).values()
    assert any(r[0] == digest for r in top)
