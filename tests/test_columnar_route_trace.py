"""What PR 28 added to the columnar route (columnar/route.py, replica.py):
the loud-failure gate (`cop-debug-raise` armed: an error on the replica's
path fails the statement instead of being served by the row store), the
`columnar.gate` span and the `resident` / `stable_rows` / `delta_rows`
attributes of `columnar.scan`, and the counters COLUMNAR_RESIDENT_SCANS,
COLUMNAR_GATE_WAIT_NS and the gauge COLUMNAR_DEVICE_BYTES.  Counts and
shapes only: no timing thresholds."""

import json
import os
import sys
import urllib.request

import pytest

from tidb_tpu.columnar import route
from tidb_tpu.sql.session import Session
from tidb_tpu.util import failpoint, metrics, tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

ROWS = 40
AGG = "SELECT g, count(*), sum(v) FROM t GROUP BY g ORDER BY g"
COUNTERS = ("COLUMNAR_SCANS", "COLUMNAR_RESIDENT_SCANS", "COLUMNAR_FALLBACKS", "COLUMNAR_GATE_WAIT_NS",
            "COP_REQUESTS")


class Moved:
    """Counter deltas around a block."""

    def __enter__(self):
        self.before = {n: getattr(metrics, n).value for n in COUNTERS}
        return self

    def __exit__(self, *exc):
        self.by = {n: getattr(metrics, n).value - self.before[n] for n in COUNTERS}


@pytest.fixture()
def sess():
    s = Session()
    s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT, g BIGINT)")
    s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{(i * 7) % 13},{i % 3})" for i in range(ROWS)))
    s.execute("ALTER TABLE t SET COLUMNAR REPLICA 1")
    s.store.pd.tick()  # birth incremental scan + first compaction
    s.execute("SET tidb_isolation_read_engines = 'tpu,columnar'")
    return s


def row_store(s, sql):
    s.execute("SET tidb_isolation_read_engines = 'tpu'")
    try:
        return s.execute(sql).values()
    finally:
        s.execute("SET tidb_isolation_read_engines = 'tpu,columnar'")


def traced(s, sql) -> dict:
    return json.loads(s.execute(f"TRACE FORMAT='json' {sql}").values()[0][0])


def find(node, name) -> list:
    out = [node] if node["name"] == name else []
    for c in node.get("children", []):
        out.extend(find(c, name))
    return out


def broken_run(*_a, **_k):
    raise RuntimeError("injected: the replica's program failed")


# ------------------------------------------------------- the loud-failure gate
class TestDebugRaise:
    def test_armed_an_error_on_the_replica_path_fails_the_statement(self, sess, monkeypatch):
        monkeypatch.setattr(route, "_run", broken_run)
        with failpoint.enabled("cop-debug-raise"), Moved() as m:
            with pytest.raises(Exception, match="injected"):
                sess.execute(AGG)
        # not served by the row store either: no cop request went out
        assert m.by["COP_REQUESTS"] == 0 and m.by["COLUMNAR_SCANS"] == 0 and m.by["COLUMNAR_FALLBACKS"] == 0

    def test_unarmed_it_degrades_to_the_row_store_and_counts(self, sess, monkeypatch):
        want = row_store(sess, AGG)
        monkeypatch.setattr(route, "_run", broken_run)
        with Moved() as m:
            got = sess.execute(AGG).values()
        assert got == want
        assert m.by["COLUMNAR_FALLBACKS"] == 1 and m.by["COLUMNAR_SCANS"] == 0 and m.by["COP_REQUESTS"] >= 1

    def test_armed_a_lagging_frontier_is_still_a_counted_fallback(self, sess):
        """ColumnarNotReady and the gate's verdict are no errors: the
        failpoint leaves them alone."""
        sess.execute("INSERT INTO t VALUES (50, 9, 0)")  # no tick: the frontier trails
        with failpoint.enabled("cop-debug-raise"), Moved() as m:
            got = sess.execute("SELECT count(*) FROM t").values()
        assert got[0][0] == ROWS + 1
        assert m.by["COLUMNAR_FALLBACKS"] == 1 and m.by["COLUMNAR_SCANS"] == 0

    def test_armed_a_torn_floor_between_gate_and_scan_is_a_counted_fallback(self, sess, monkeypatch):
        from tidb_tpu.columnar.replica import ColumnarNotReady

        def not_ready(*_a, **_k):
            raise ColumnarNotReady("t", 1, 0, 2)

        monkeypatch.setattr(route, "_run", not_ready)
        with failpoint.enabled("cop-debug-raise"), Moved() as m:
            got = sess.execute("SELECT count(*) FROM t").values()
        assert got[0][0] == ROWS and m.by["COLUMNAR_FALLBACKS"] == 1

    def test_armed_a_sound_statement_is_served_by_the_replica(self, sess):
        want = row_store(sess, AGG)
        with failpoint.enabled("cop-debug-raise"), Moved() as m:
            assert sess.execute(AGG).values() == want
        assert m.by["COLUMNAR_SCANS"] == 1 and m.by["COLUMNAR_FALLBACKS"] == 0


# ------------------------------------------------------------------ the spans
class TestSpans:
    def test_gate_and_scan_carry_their_attributes(self, sess):
        tree = traced(sess, AGG)
        (gate,), (scan,) = find(tree, "columnar.gate"), find(tree, "columnar.scan")
        assert gate["attrs"]["waited"] is False
        assert gate["attrs"]["snapshot_ts"] == scan["attrs"]["snapshot_ts"] > 0
        assert scan["attrs"]["resident"] is True
        assert (scan["attrs"]["stable_rows"], scan["attrs"]["delta_rows"]) == (ROWS, 0)
        # the gate ends before the scan begins, and the program's spans are the scan's
        assert gate["start_ns"] + gate["duration_ns"] <= scan["start_ns"]
        assert find(scan, "exec.readback") and (find(scan, "exec.launch") or find(scan, "exec.compile"))

    def test_gate_is_on_the_profilers_clock(self):
        assert "columnar.gate" in tracing.HOST_STATES

    def test_a_trailing_frontier_takes_the_back_off_and_says_so(self, sess):
        sess.execute("INSERT INTO t VALUES (50, 9, 0)")  # no tick
        with Moved() as m:
            tree = traced(sess, "SELECT count(*) FROM t")
        (gate,) = find(tree, "columnar.gate")
        assert gate["attrs"]["waited"] is True and gate["attrs"]["snapshot_ts"] is None
        assert find(tree, "columnar.scan") == []
        assert m.by["COLUMNAR_FALLBACKS"] == 1 and m.by["COLUMNAR_GATE_WAIT_NS"] >= gate["duration_ns"] > 0

    def test_resident_is_false_over_an_unfolded_delta_and_true_again_after_the_fold(self, sess):
        with failpoint.enabled("columnar/compact-stall"):
            sess.execute("UPDATE t SET v = 500 WHERE id = 1")
            sess.store.pd.tick()  # applied, not compacted: one delta row over the stable layer
            with Moved() as m:
                tree = traced(sess, "SELECT max(v), count(*) FROM t")
        (scan,) = find(tree, "columnar.scan")
        assert scan["attrs"]["resident"] is False
        assert (scan["attrs"]["stable_rows"], scan["attrs"]["delta_rows"], scan["attrs"]["rows"]) == (ROWS, 1, 1)
        assert m.by["COLUMNAR_SCANS"] == 1 and m.by["COLUMNAR_RESIDENT_SCANS"] == 0
        sess.store.pd.tick()  # the fold: the stable batch is uploaded anew
        with Moved() as m:
            tree = traced(sess, "SELECT max(v), count(*) FROM t")
        (scan,) = find(tree, "columnar.scan")
        assert scan["attrs"]["resident"] is True and scan["attrs"]["delta_rows"] == 0
        assert m.by["COLUMNAR_SCANS"] == 1 and m.by["COLUMNAR_RESIDENT_SCANS"] == 1
        (row,) = sess.execute("SELECT max(v), count(*) FROM t").values()
        assert (row[0], row[1]) == (500, ROWS)


# ------------------------------------------------------- counters and the gauge
class TestCountersAndGauge:
    def test_device_bytes_follow_the_stable_batch(self):
        held = metrics.COLUMNAR_DEVICE_BYTES.value
        s = Session()
        s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT, g BIGINT)")
        s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i},{i % 3})" for i in range(ROWS)))
        s.execute("ALTER TABLE t SET COLUMNAR REPLICA 1")
        assert metrics.COLUMNAR_DEVICE_BYTES.value == held  # nothing uploaded before the first fold
        s.store.pd.tick()
        (table,) = s.store.columnar.tables()
        view = table.view()
        # capacity 64 (the next power of two over 40 rows): three int64 columns with
        # their null masks, the row mask and the row count
        assert view["on_device"] and view["device_bytes"] >= 64 * (3 * 8 + 3 + 1)
        assert metrics.COLUMNAR_DEVICE_BYTES.value == held + view["device_bytes"]
        s.execute("INSERT INTO t VALUES " + ",".join(f"({i},{i},{i % 3})" for i in range(ROWS, 3 * ROWS)))
        s.store.pd.tick()  # 120 rows: capacity 128, the old batch is let go
        grown = table.view()["device_bytes"]
        assert grown > view["device_bytes"] and metrics.COLUMNAR_DEVICE_BYTES.value == held + grown
        s.execute("ALTER TABLE t SET COLUMNAR REPLICA 0")
        assert metrics.COLUMNAR_DEVICE_BYTES.value == held

    def test_resident_scans_are_counted_with_the_scans(self, sess):
        with Moved() as m:
            for _ in range(3):
                sess.execute(AGG)
        assert m.by["COLUMNAR_SCANS"] == m.by["COLUMNAR_RESIDENT_SCANS"] == 3
        assert m.by["COLUMNAR_GATE_WAIT_NS"] > 0 and m.by["COLUMNAR_FALLBACKS"] == 0

    def test_the_new_families_are_on_get_metrics(self, sess):
        from scrape_check import validate

        from tidb_tpu.server.http_api import StatusServer

        sess.execute(AGG)
        srv = StatusServer(sess).start_background()
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/metrics") as r:
                text = r.read().decode()
        finally:
            srv.close()
        for family, kind in (("tidb_tpu_columnar_resident_scans_total", "counter"),
                             ("tidb_tpu_columnar_gate_wait_ns_total", "counter"),
                             ("tidb_tpu_columnar_device_bytes", "gauge")):
            assert f"# TYPE {family} {kind}" in text, family
        assert validate(text) == []
