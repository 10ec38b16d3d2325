"""Statement tracing: the span tree behind TRACE (ref: pkg/util/tracing +
executor/trace.go), the tracing primitives' threading contract, the
device-time attribution riding the exec summaries, and the Prometheus
exposition contract enforced by tools/scrape_check."""

import json
import os
import sys
import threading

import pytest

from tidb_tpu.codec import tablecodec
from tidb_tpu.sql.session import Session
from tidb_tpu.util import tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
from scrape_check import validate  # noqa: E402


@pytest.fixture()
def sess():
    s = Session()
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT)")
    s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i % 5})" for i in range(1, 61)))
    tid = s.catalog.table("t").table_id
    for h in (20, 40):  # 3 regions
        s.store.cluster.split(tablecodec.encode_row_key(tid, h))
    return s


# ---------------------------------------------------------------- primitives
class TestSpanPrimitives:
    def test_span_is_noop_without_trace(self):
        assert tracing.current_span() is None
        with tracing.span("anything") as sp:
            assert sp is None  # zero bookkeeping when tracing is off
        assert tracing.current_span() is None

    def test_nesting_and_attrs(self):
        with tracing.trace("root") as root:
            with tracing.span("child", k=1) as c:
                c.set("rows", 7)
                with tracing.span("grand"):
                    pass
        assert [c.name for c in root.children] == ["child"]
        assert root.children[0].attrs == {"k": 1, "rows": 7}
        assert [g.name for g in root.children[0].children] == ["grand"]
        # every span finished, children contained in the parent window
        assert root.end_ns is not None
        assert root.children[0].duration_ns <= root.duration_ns

    def test_exception_recorded_and_reraised(self):
        with tracing.trace("root") as root:
            with pytest.raises(ValueError):
                with tracing.span("boom"):
                    raise ValueError("no")
        assert "ValueError: no" in root.children[0].attrs["error"]
        assert root.children[0].end_ns is not None

    def test_cross_thread_parent_handoff(self):
        """Pool workers don't inherit contextvars; the explicit parent=
        handoff is how dispatch parents its cop-task spans."""
        with tracing.trace("root") as root:
            parent = tracing.current_span()

            def worker():
                assert tracing.current_span() is None  # not inherited
                with tracing.span("task", parent=parent, region_id=9):
                    pass

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert [c.name for c in root.children] == ["task"]
        assert root.children[0].attrs["region_id"] == 9

    def test_find_and_rows_render(self):
        with tracing.trace("root") as root:
            with tracing.span("a"):
                with tracing.span("b"):
                    pass
            with tracing.span("b"):
                pass
        assert len(root.find("b")) == 2
        ops = [r[0] for r in root.rows()]
        assert ops == ["root", "  a", "    b", "  b"]


# ---------------------------------------------------------------- TRACE stmt
class TestTraceStatement:
    def _tree(self, sess, sql):
        res = sess.execute(f"TRACE FORMAT='json' {sql}")
        assert res.columns == ["trace"]
        return json.loads(res.values()[0][0])

    @staticmethod
    def _find(node, name):
        out = [node] if node["name"] == name else []
        for c in node.get("children", []):
            out.extend(TestTraceStatement._find(c, name))
        return out

    def test_multi_region_aggregate_span_shape(self, sess):
        tree = self._tree(sess, "SELECT v, count(*) FROM t GROUP BY v")
        assert tree["name"] == "session"
        assert self._find(tree, "session.execute")
        assert self._find(tree, "planner.plan")
        # dispatch level: execute_root or the mpp fragment path —
        # whichever the gate picked on this host
        dispatch = (self._find(tree, "distsql.execute_root")
                    + self._find(tree, "mpp.dispatch"))
        assert dispatch
        cop = self._find(tree, "distsql.cop_task")
        assert len(cop) == 3  # one child span per region
        assert sorted(c["attrs"]["region_id"] for c in cop) == [1, 2, 3]
        assert all(c["attrs"]["rows"] >= 1 for c in cop)
        # program compile/cache level spans exist, and the program compiled
        # at most once across the per-region tasks (cache hits after)
        progs = self._find(tree, "exec.program")
        assert progs and any("cache_hit" in p["attrs"] for p in progs)
        assert sum(1 for p in progs if not p["attrs"]["cache_hit"]) <= 2  # push + root merge

    def test_durations_sum_consistently(self, sess):
        tree = self._tree(sess, "SELECT v, count(*) FROM t GROUP BY v")

        def check(node):
            for c in node.get("children", []):
                assert c["duration_ns"] <= node["duration_ns"]
                check(c)

        check(tree)
        dispatch = (self._find(tree, "distsql.execute_root")
                    + self._find(tree, "mpp.dispatch"))[0]
        cop = self._find(tree, "distsql.cop_task")
        assert cop and all(c["duration_ns"] <= dispatch["duration_ns"] for c in cop)

    def test_row_format(self, sess):
        res = sess.execute("TRACE SELECT count(*) FROM t")
        assert res.columns == ["operation", "start_us", "duration_us", "attrs"]
        ops = [r[0] for r in res.values()]
        assert ops[0] == "session"
        assert any(op.lstrip().startswith("distsql.cop_task") for op in ops)
        # indentation encodes the tree depth
        assert any(op.startswith("  ") for op in ops)

    def test_trace_of_failing_statement_returns_partial_tree(self, sess):
        res = sess.execute("TRACE FORMAT='json' SELECT * FROM no_such_table")
        tree = json.loads(res.values()[0][0])
        assert "error" in tree["attrs"]
        assert self._find(tree, "session.execute")  # the partial tree survived

    def test_trace_dml(self, sess):
        tree = self._tree(sess, "INSERT INTO t VALUES (1000, 1)")
        assert tree["attrs"].get("rows") == 1
        assert sess.execute("SELECT v FROM t WHERE id = 1000").values() == [[1]]


# ------------------------------------------------------- summary attribution
class TestExecSummaryAttribution:
    def test_summaries_carry_compile_and_bytes(self, sess):
        from tidb_tpu.distsql import full_table_ranges
        from tidb_tpu.exec.dag import DAGRequest, TableScan

        meta = sess.catalog.table("t")
        scan = TableScan(meta.table_id, meta.scan_columns())
        dag = DAGRequest((scan,), output_offsets=(0, 1))
        from tidb_tpu.distsql.dispatch import KVRequest, select

        res = select(sess.store, KVRequest(dag, full_table_ranges(meta.table_id), sess.store.next_ts()))
        assert len(res.exec_summaries) == 3  # one per region task
        for task_sums in res.exec_summaries:
            assert task_sums[0].num_bytes > 0  # decoded region bytes
        # a second identical dispatch: every program comes from the cache
        res2 = select(sess.store, KVRequest(dag, full_table_ranges(meta.table_id), sess.store.next_ts()))
        assert all(s[0].cache_hit for s in res2.exec_summaries)
        assert all(s[0].time_compile_ns == 0 for s in res2.exec_summaries)

    def test_wire_roundtrip_preserves_attribution(self):
        from tidb_tpu.codec.wire import decode_cop_response, encode_cop_response
        from tidb_tpu.store.store import CopResponse, ExecSummary

        resp = CopResponse(
            chunk=None,
            exec_summaries=[ExecSummary(10, 5, 1, time_compile_ns=77, cache_hit=True, num_bytes=123)],
        )
        out = decode_cop_response(encode_cop_response(resp))
        s = out.exec_summaries[0]
        assert (s.time_compile_ns, s.cache_hit, s.num_bytes) == (77, True, 123)


# ------------------------------------------------------------ slow-log links
class TestSlowLogArtifacts:
    def test_fast_failure_leaves_slow_log_entry(self, sess):
        from tidb_tpu.util import failpoint

        sess.execute("SET tidb_slow_log_threshold = 100000")  # nothing is slow
        failpoint.enable("cop-other-error", 1)
        try:
            with pytest.raises(Exception, match="injected"):
                sess.execute("SELECT sum(v) FROM t")
        finally:
            failpoint.disable("cop-other-error")
        rows = sess.execute(
            "SELECT query, success, error FROM information_schema.slow_query"
        ).values()
        failed = [r for r in rows if r[1] == 0]
        assert failed and any("injected" in (r[2] or "") for r in failed)

    def test_plan_digest_joins_slow_log(self, sess):
        sess.execute("SET tidb_slow_log_threshold = 0")  # everything is slow
        sess.execute("SELECT sum(v) FROM t")
        rows = sess.execute(
            "SELECT plan_digest, query FROM information_schema.slow_query"
        ).values()
        digests = [r[0] for r in rows if "sum(v)" in r[1].lower()]
        assert digests and all(len(d) == 32 for d in digests)


# ------------------------------------------------------------- metrics/text
class TestMetricsExposition:
    def test_dump_passes_scrape_check(self, sess):
        sess.execute("SELECT sum(v) FROM t")  # move some instruments
        from tidb_tpu.util import metrics

        text = metrics.REGISTRY.dump()
        assert validate(text) == []
        assert "# HELP tidb_tpu_cop_requests_total" in text
        assert "# TYPE tidb_tpu_cop_duration_seconds histogram" in text
        assert 'tidb_tpu_cop_duration_seconds_bucket{le="+Inf"}' in text

    def test_labeled_vec_exposition(self):
        from tidb_tpu.util import metrics

        metrics.STATEMENTS.labels("select", "ok").inc(3)
        metrics.DISTSQL_TASK_DURATION.labels("table").observe(0.02)
        text = metrics.REGISTRY.dump()
        assert validate(text) == []
        assert 'tidb_tpu_statements_total{type="select",status="ok"}' in text
        assert 'tidb_tpu_distsql_task_duration_seconds_bucket{scan="table",le="0.05"}' in text

    def test_gauge_moves_both_ways(self, sess):
        from tidb_tpu.util import metrics

        base = metrics.OPEN_TXNS.value
        sess.execute("BEGIN")
        assert metrics.OPEN_TXNS.value == base + 1
        sess.execute("ROLLBACK")
        assert metrics.OPEN_TXNS.value == base

    def test_scrape_check_rejects_bad_expositions(self):
        assert validate('# TYPE h histogram\nh_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\nh_sum 1.0\nh_count 3\n')
        assert validate("# TYPE c counter\nc -4\n")
        assert validate("# TYPE c counter\nc 1\nc 1\n")  # duplicate series
        assert validate('# TYPE h histogram\nh_bucket{le="+Inf"} 1\nh_count 1\n')  # no _sum
