"""The launch boundary (exec/launch.py): every call of a compiled program
is split into exec.compile / exec.launch / exec.wait / exec.readback, as
spans under TRACE, as annotations on the profiler's clock and as counters;
the wire server clocks its commands; programs carry shape names.  Counts
and shapes only: no timing thresholds."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from tidb_tpu.codec import tablecodec
from tidb_tpu.distsql.dispatch import KVRequest, full_table_ranges, select
from tidb_tpu.exec.dag import Aggregation, ColumnInfo, DAGRequest, Selection, TableScan
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.sql.session import Session
from tidb_tpu.store import TPUStore
from tidb_tpu.types import Datum, new_longlong
from tidb_tpu.util import metrics, tracing

LAUNCH_COUNTERS = ("PROGRAM_COMPILES", "PROGRAM_LAUNCHES", "XLA_COMPILES", "XLA_EAGER_COMPILES",
                   "XLA_TRACE_LOWER_NS", "XLA_BACKEND_COMPILE_NS", "PROGRAM_WAIT_NS", "PROGRAM_READBACK_NS",
                   "PROGRAM_READBACK_TRANSFERS", "PROGRAM_READBACK_BYTES")
SERVER_COUNTERS = ("SERVER_COMMANDS", "SERVER_HANDLE_NS", "SERVER_WRITE_NS", "SERVER_PACKETS_OUT")


class Moved:
    """Counter deltas around a block."""

    def __init__(self, names):
        self.names = names

    def __enter__(self):
        self.before = {n: getattr(metrics, n).value for n in self.names}
        return self

    def __exit__(self, *exc):
        self.by = {n: getattr(metrics, n).value - self.before[n] for n in self.names}


@pytest.fixture()
def sess():
    s = Session()
    s.execute("CREATE TABLE sb (id BIGINT PRIMARY KEY, k BIGINT, c CHAR(20))")
    s.execute("INSERT INTO sb VALUES " + ",".join(f"({i},{i * 7 % 100},'c{i % 13:03d}')" for i in range(1, 301)))
    return s


def traced(sess, sql) -> dict:
    return json.loads(sess.execute(f"TRACE FORMAT='json' {sql}").values()[0][0])


def find(node, name) -> list:
    out = [node] if node["name"] == name else []
    for c in node.get("children", []):
        out.extend(find(c, name))
    return out


def names_under(node) -> list:
    return [c["name"] for c in node.get("children", [])]


def assert_children_inside(node) -> None:
    end = node["start_ns"] + node["duration_ns"]
    for c in node.get("children", []):
        assert node["start_ns"] <= c["start_ns"] and c["start_ns"] + c["duration_ns"] <= end, (node["name"], c["name"])
        assert_children_inside(c)


# ------------------------------------------------------------------ spans
class TestLaunchSpans:
    def test_fresh_shape_compiles_and_a_fresh_literal_launches(self, sess):
        with Moved(LAUNCH_COUNTERS) as first:
            tree = traced(sess, "SELECT SUM(k) FROM sb WHERE id BETWEEN 11 AND 110")
        (cop,) = find(tree, "cop.execute")
        assert names_under(cop) == ["exec.program", "exec.compile", "exec.wait", "exec.readback"]
        (comp,) = find(cop, "exec.compile")
        assert comp["attrs"]["program"] == "cop_scan_sel_agg"
        assert 0 < comp["attrs"]["xla_ns"] <= comp["duration_ns"]
        assert comp["attrs"]["trace_ns"] > 0 and comp["attrs"]["lower_ns"] > 0
        assert comp["attrs"]["persistent_cache"] in ("hit", "miss", "off")
        # every backend compile hangs under the call as a span of its own
        assert sum(x["duration_ns"] for x in find(comp, "exec.xla_compile")) == comp["attrs"]["xla_ns"]
        assert_children_inside(tree)
        for node in find(tree, "exec.wait") + find(tree, "exec.readback"):
            assert node["thread"] > 0 and 0 <= node["cpu_ns"]
        # one backend compile per program built, the root merge's included
        assert first.by["XLA_COMPILES"] == first.by["PROGRAM_COMPILES"] == len(find(tree, "exec.compile")) >= 1
        assert first.by["XLA_BACKEND_COMPILE_NS"] >= sum(c["attrs"]["xla_ns"] for c in find(tree, "exec.compile"))
        assert first.by["XLA_TRACE_LOWER_NS"] > 0 and first.by["PROGRAM_WAIT_NS"] > 0

        assert comp["attrs"]["params"] == 2   # BETWEEN's two literals, handed over as operands

        # the same shape with fresh literals calls the program that is there:
        # a literal is an operand, not part of the program's key
        with Moved(LAUNCH_COUNTERS + ("PROGRAM_PARAMS_BOUND",)) as again:
            tree = traced(sess, "SELECT SUM(k) FROM sb WHERE id BETWEEN 57 AND 156")
        (cop,) = find(tree, "cop.execute")
        assert names_under(cop) == ["exec.program", "exec.launch", "exec.wait", "exec.readback"]
        assert find(cop, "exec.launch")[0]["attrs"] == {"program": "cop_scan_sel_agg", "params": 2}
        assert again.by["PROGRAM_PARAMS_BOUND"] == 2   # the root merge's program has none
        assert not find(tree, "exec.compile")
        assert again.by["XLA_COMPILES"] == again.by["PROGRAM_COMPILES"] == 0
        assert again.by["XLA_TRACE_LOWER_NS"] == 0
        assert again.by["PROGRAM_LAUNCHES"] == len(find(tree, "exec.launch")) >= 1

    def test_compile_histogram_hears_the_first_call(self, sess):
        before = metrics.PROGRAM_COMPILE_DURATION.sum
        tree = traced(sess, "SELECT SUM(k) FROM sb WHERE id BETWEEN 12 AND 111")
        compiles = find(tree, "exec.compile")
        assert compiles
        moved_ns = (metrics.PROGRAM_COMPILE_DURATION.sum - before) * 1e9
        assert moved_ns >= sum(c["attrs"]["xla_ns"] for c in compiles)

    def test_eager_compiles_are_counted_apart(self):
        with Moved(LAUNCH_COUNTERS) as m:
            jnp.clip(jnp.arange(37, dtype=jnp.int32), 3, 29).block_until_ready()  # a shape no program uses
        assert m.by["XLA_EAGER_COMPILES"] >= 1
        assert m.by["XLA_COMPILES"] == 0 and m.by["PROGRAM_LAUNCHES"] == 0
        assert m.by["XLA_BACKEND_COMPILE_NS"] > 0 and m.by["XLA_TRACE_LOWER_NS"] == 0

    def test_readback_counts_the_arrays_converted(self, sess):
        # two BIGINT columns out: per launch the row counts, the validity
        # mask, and (value, null) per column
        with Moved(LAUNCH_COUNTERS) as m:
            tree = traced(sess, "SELECT id, k FROM sb WHERE id BETWEEN 13 AND 112")
        backs = find(tree, "exec.readback")
        assert [b["attrs"]["transfers"] for b in backs] == [2 + 2 * 2] * len(backs)
        assert m.by["PROGRAM_READBACK_TRANSFERS"] == sum(b["attrs"]["transfers"] for b in backs)
        assert m.by["PROGRAM_READBACK_BYTES"] == sum(b["attrs"]["bytes"] for b in backs) > 0
        # a CHAR column comes back as (null, bytes, lengths): its packed words stay on the device
        tree = traced(sess, "SELECT c FROM sb WHERE id BETWEEN 13 AND 112")
        assert [b["attrs"]["transfers"] for b in find(tree, "exec.readback")] == [2 + 3]

    def test_no_span_is_built_without_a_trace(self, sess, monkeypatch):
        built = []
        real = tracing.Span.__init__

        def counting(self, name, **attrs):
            built.append(name)
            real(self, name, **attrs)

        monkeypatch.setattr(tracing.Span, "__init__", counting)
        with Moved(LAUNCH_COUNTERS) as m:
            assert str(sess.execute("SELECT SUM(k) FROM sb WHERE id BETWEEN 14 AND 113").values()[0][0]) == "4950"
        assert m.by["PROGRAM_LAUNCHES"] >= 1 and m.by["PROGRAM_READBACK_TRANSFERS"] >= 4
        assert built == []
        traced(sess, "SELECT SUM(k) FROM sb WHERE id BETWEEN 14 AND 113")
        assert "exec.readback" in built


# ------------------------------------------------- the batched and mesh drivers
TID = 26
I = new_longlong()


def region_store(rows=180, regions=6, stores=2) -> TPUStore:
    store = TPUStore()
    for h in range(rows):
        store.put_row(TID, h, [1, 2], [Datum.i64(h % 7), Datum.i64(h)], ts=10)
    for i in range(1, regions):
        store.cluster.split(tablecodec.encode_row_key(TID, i * rows // regions))
    store.cluster.set_stores(stores)
    store.cluster.scatter()
    return store


def partial_sum_dag() -> DAGRequest:
    scan = TableScan(TID, (ColumnInfo(1, I), ColumnInfo(2, I)))
    pred = func("gt", new_longlong(notnull=True), col(0, I), lit(1, I))
    agg = Aggregation(group_by=(), aggs=(AggDesc("count", ()), AggDesc("sum", (col(1, I),))), partial=True)
    return DAGRequest((scan, Selection((pred,)), agg), output_offsets=(0, 1))


@pytest.mark.parametrize("tier,outer,request_kw", [
    ("batch", "cop.batch_execute", {"batch_cop": True, "mesh": False}),
    ("mesh", "cop.mesh_execute", {}),
])
def test_batched_and_mesh_drivers_emit_the_same_spans(tier, outer, request_kw):
    store = region_store()
    dag = partial_sum_dag()
    for first in (True, False):
        with tracing.trace("root") as root:
            select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100 + first, **request_kw))
        outers = root.find(outer)
        assert len(outers) == 2, [r[0] for r in root.rows()]  # one launch per store
        calls = []
        for sp in outers:
            program, call, wait, back = [c for c in sp.children if c.name.startswith("exec.")]
            assert (program.name, wait.name, back.name) == ("exec.program", "exec.wait", "exec.readback"), tier
            assert call.attrs["program"].startswith("cop_scan_sel_agg_" + ("b" if tier == "batch" else "m"))
            assert back.attrs["transfers"] > 0
            calls.append(call.name)
        # the stores share the program: the first to call it compiles
        assert sorted(calls) == (["exec.compile", "exec.launch"] if first else ["exec.launch"] * 2), tier
        store.put_row(TID, 10_000, [1, 2], [Datum.i64(0), Datum.i64(0)], ts=50)  # past the result cache


# ------------------------------------------------------------------ names
def test_program_names_come_from_the_shape_not_the_literals(sess):
    def programs(sql):
        return [c["attrs"]["program"] for c in find(traced(sess, sql), "exec.compile")]

    a = programs("SELECT SUM(k) FROM sb WHERE id BETWEEN 21 AND 120")
    b = programs("SELECT SUM(k) FROM sb WHERE id BETWEEN 131 AND 230")
    d = programs("SELECT DISTINCT c FROM sb WHERE id BETWEEN 21 AND 120 ORDER BY c")
    assert a and a[0] == "cop_scan_sel_agg"
    assert b == []   # the same shape with other literals: the program that is there is called
    assert d[0] == "cop_scan_sel_distinct"
    assert not set(a) & set(d)


# ------------------------------------------------------------- the wire server
def test_one_query_moves_the_server_counters():
    import time

    from tidb_tpu.server import MiniClient, MySQLServer

    def handled(n: int, since: int) -> None:
        """The server books a command once its last reply byte is out, which
        the client may see first: wait until `n` commands are booked."""
        deadline = time.monotonic() + 10
        while metrics.SERVER_COMMANDS.value - since < n and time.monotonic() < deadline:
            time.sleep(0.002)
        assert metrics.SERVER_COMMANDS.value - since == n

    srv = MySQLServer(port=0)
    srv.start_background()
    try:
        start = metrics.SERVER_COMMANDS.value
        c = MiniClient(srv.host, srv.port, timeout=120)
        c.query("CREATE TABLE w (a BIGINT PRIMARY KEY, b BIGINT, c BIGINT)")
        c.query("INSERT INTO w VALUES (1,2,3),(2,3,4),(3,4,5),(4,5,6),(5,6,7)")
        handled(2, start)   # the handshake is no command
        with Moved(SERVER_COUNTERS) as m:
            cols, rows = c.query("SELECT a, b, c FROM w WHERE a <= 4")
            handled(3, start)
        assert (len(cols), len(rows)) == (3, 4)
        # column count, a definition per column, EOF, a packet per row, EOF
        assert m.by["SERVER_PACKETS_OUT"] == 1 + 3 + 1 + 4 + 1
        assert 0 < m.by["SERVER_WRITE_NS"] <= m.by["SERVER_HANDLE_NS"]
        with Moved(SERVER_COUNTERS) as m:
            c.query("INSERT INTO w VALUES (9,9,9)")
            handled(4, start)
        assert m.by["SERVER_PACKETS_OUT"] == 1  # one OK packet
        c.close()
    finally:
        srv.close()


# ------------------------------------------------------- the profiler's clock
def test_engine_states_are_on_the_profilers_clock_without_trace(sess, tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert str(sess.execute("SELECT SUM(k) FROM sb WHERE id BETWEEN 15 AND 114").values()[0][0]) == "4950"
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    seen = {ev.name for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}
    assert {"cop.decode", "exec.compile", "exec.wait", "exec.readback", "planner.plan"} <= seen
    # enclosing spans stay off the clock: a label would be its ancestors
    assert not {"session.execute", "distsql.execute_root", "cop.execute"} & seen
