"""The launch boundary (exec/launch.py): every call of a compiled program
is split into exec.compile / exec.launch / exec.wait / exec.readback, as
spans under TRACE, as annotations on the profiler's clock and as counters;
every program hands all that the host reads back as one byte buffer
(`HostOutputs`), whose copy the launch starts as the call returns
(`Fetch`: PROGRAM_FETCHES) and beside which nothing is converted (`late`);
the wire server clocks its commands; programs carry shape names.  Counts
and shapes only: no timing thresholds."""

import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.chunk import to_device_batch
from tidb_tpu.codec import tablecodec
from tidb_tpu.distsql.dispatch import KVRequest, full_table_ranges, select
from tidb_tpu.exec import executor, launch, run_dag_reference
from tidb_tpu.exec.builder import ProgramCache
from tidb_tpu.exec.dag import Aggregation, ColumnInfo, DAGRequest, Selection, TableScan
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.sql.session import Session
from tidb_tpu.store import TPUStore
from tidb_tpu.types import Datum, new_datetime, new_longlong, new_varchar
from tidb_tpu.util import metrics, tracing

LAUNCH_COUNTERS = ("PROGRAM_COMPILES", "PROGRAM_LAUNCHES", "XLA_COMPILES", "XLA_EAGER_COMPILES",
                   "XLA_TRACE_LOWER_NS", "XLA_BACKEND_COMPILE_NS", "PROGRAM_WAIT_NS", "PROGRAM_READBACK_NS",
                   "PROGRAM_READBACK_TRANSFERS", "PROGRAM_READBACK_BYTES", "PROGRAM_FETCHES",
                   "PROGRAM_READBACK_LATE")
SERVER_COUNTERS = ("SERVER_COMMANDS", "SERVER_HANDLE_NS", "SERVER_WRITE_NS", "SERVER_PACKETS_OUT",
                   "SERVER_SOCKET_SENDS")


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
        # two BIGINT columns out: flags, row counts, the validity mask and (value, null) per column
        # all arrive as the program's one buffer
        with Moved(LAUNCH_COUNTERS) as m:
            tree = traced(sess, "SELECT id, k FROM sb WHERE id BETWEEN 13 AND 112")
        backs = find(tree, "exec.readback")
        assert [b["attrs"]["transfers"] for b in backs] == [1] * len(backs)
        assert m.by["PROGRAM_READBACK_TRANSFERS"] == sum(b["attrs"]["transfers"] for b in backs)
        assert m.by["PROGRAM_READBACK_BYTES"] == sum(b["attrs"]["bytes"] for b in backs)
        rows = 128   # the batch's capacity over 100 rows: 8 + 1 bytes a column, 1 of validity
        assert all(rows * (2 * 9 + 1) <= b["attrs"]["bytes"] < rows * (2 * 9 + 1) + 128 for b in backs)
        # nothing was converted that the launch had not fetched
        assert [b["attrs"]["late"] for b in backs] == [0] * len(backs)
        assert m.by["PROGRAM_READBACK_LATE"] == 0 and m.by["PROGRAM_FETCHES"] == m.by["PROGRAM_LAUNCHES"] == len(backs)
        # a CHAR column comes back as (null, bytes, lengths): its packed words (32 bytes a row) stay on the device
        tree = traced(sess, "SELECT c FROM sb WHERE id BETWEEN 13 AND 112")
        (back,) = find(tree, "exec.readback")
        assert (back["attrs"]["transfers"], back["attrs"]["late"]) == (1, 0)
        assert rows * (1 + 4 + 4 + 1) <= back["attrs"]["bytes"] < rows * (1 + 4 + 4 + 1) + 128
        # a float leaf rides beside the buffer: the TPU keeps no IEEE float64 to bit-cast
        tree = traced(sess, "SELECT k * 1.5e0, id FROM sb WHERE id BETWEEN 13 AND 112")
        assert [(b["attrs"]["transfers"], b["attrs"]["late"]) for b in find(tree, "exec.readback")] == [(2, 0)]

    def test_no_span_is_built_without_a_trace(self, sess, monkeypatch):
        built = []
        real = tracing.Span.__init__

        def counting(self, name, **attrs):
            built.append(name)
            real(self, name, **attrs)

        monkeypatch.setattr(tracing.Span, "__init__", counting)
        with Moved(LAUNCH_COUNTERS) as m:
            assert str(sess.execute("SELECT SUM(k) FROM sb WHERE id BETWEEN 14 AND 113").values()[0][0]) == "4950"
        assert m.by["PROGRAM_READBACK_TRANSFERS"] == m.by["PROGRAM_LAUNCHES"] >= 1
        assert built == []
        traced(sess, "SELECT SUM(k) FROM sb WHERE id BETWEEN 15 AND 114")   # the identical repeat is a cop result: no launch at all
        assert "exec.readback" in built


    @pytest.mark.parametrize("regions", [1, 4], ids=["lone_task", "mesh_group"])
    def test_a_fused_statement_shows_one_program_and_no_root_merge(self, sess, regions):
        """Where the pushdown comes back as one state the store's program
        ran the root's half too (ISSUE 37): TRACE shows the one launch with
        `root_fused` on its cop span and no `distsql.root_merge`; EXPLAIN
        ANALYZE keeps its per-executor rows, so it keeps the split path."""
        if regions > 1:
            sess.execute(f"SPLIT TABLE sb BETWEEN (0) AND (300) REGIONS {regions}")
        sql = "SELECT c, SUM(k) FROM sb WHERE id BETWEEN {} AND 290 GROUP BY c ORDER BY SUM(k) DESC, c LIMIT 3"
        want = sess.execute(sql.format(5)).values()
        with Moved(LAUNCH_COUNTERS + ("ROOT_FUSED_STATEMENTS", "ROOT_FUSE_FALLBACKS")) as m:
            tree = traced(sess, sql.format(6))
        (cop,) = find(tree, "cop.mesh_execute" if regions > 1 else "cop.execute")
        assert cop["attrs"]["root_fused"] is True and not find(tree, "distsql.root_merge")
        (launched,) = find(tree, "exec.launch")
        assert launched["attrs"]["program"] == "cop_scan_sel_groupagg_topn" + (f"_m{regions}x{regions}" if regions > 1 else "")
        assert launched["attrs"]["params"] == 2 and len(find(tree, "exec.readback")) == 1
        assert (m.by["PROGRAM_LAUNCHES"], m.by["PROGRAM_FETCHES"], m.by["PROGRAM_COMPILES"]) == (1, 1, 0)
        assert (m.by["ROOT_FUSED_STATEMENTS"], m.by["ROOT_FUSE_FALLBACKS"]) == (1, 0)
        assert tree["attrs"]["rows"] == len(want) == 3
        with Moved(("ROOT_FUSED_STATEMENTS", "ROOT_FUSE_FALLBACKS")) as m:
            rows = sess.execute("EXPLAIN ANALYZE " + sql.format(7)).values()
        assert (m.by["ROOT_FUSED_STATEMENTS"], m.by["ROOT_FUSE_FALLBACKS"]) == (0, 1)
        by_exec = {r[0]: r for r in rows}
        assert by_exec["push[TableScan]"][2] == regions and by_exec["result"][1] == 3
        assert by_exec["push[Aggregation]"][1] >= 3 and by_exec["push[Selection]"][1] == 290 - 7 + 1


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
        with tracing.trace("root") as root, Moved(LAUNCH_COUNTERS) as m:
            select(store, KVRequest(dag, full_table_ranges(TID), start_ts=100 + first, **request_kw))
        assert_one_fetch_per_launch(m, root.find("exec.readback"))
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


# ------------------------------------------------- one round trip per launch
def assert_one_fetch_per_launch(m: Moved, backs: list) -> None:
    """Every launch started one batch of copies, and nothing was converted
    in read-back that was not in it."""
    assert m.by["PROGRAM_FETCHES"] == m.by["PROGRAM_LAUNCHES"] >= 1
    assert m.by["PROGRAM_READBACK_LATE"] == 0
    assert backs and all(b.attrs["late"] == 0 and b.attrs["transfers"] > 0 for b in backs)
    assert m.by["PROGRAM_READBACK_TRANSFERS"] == sum(b.attrs["transfers"] for b in backs)


def drive_single(_sess):
    select(region_store(regions=2, stores=1), KVRequest(partial_sum_dag(), full_table_ranges(TID), start_ts=100,
                                                         batch_cop=False, mesh=False))
    return "cop.execute"


def drive_strings(sess):
    # two string columns (null, bytes, lengths each; their packed words stay behind) beside a BIGINT, out of one launch
    rows = sess.execute("SELECT c, IF(k > 50, c, 'low'), k FROM sb WHERE id BETWEEN 31 AND 40").values()
    assert len(rows) == 10
    return None


def drive_columnar(sess):
    sess.execute("ALTER TABLE sb SET COLUMNAR REPLICA 1")
    sess.store.pd.tick()
    sess.execute("SET tidb_isolation_read_engines = 'tpu,columnar'")
    assert len(sess.execute("SELECT k % 3, COUNT(*), SUM(k) FROM sb GROUP BY k % 3 ORDER BY 1").values()) == 3
    return "columnar.scan"


def drive_grouped_mesh(_sess):
    # the grouped shard_map program of parallel/grouped.py, decoded by parallel/mesh.py
    from tidb_tpu.chunk import Chunk
    from tidb_tpu.parallel import region_mesh, run_sharded_grouped_agg, stack_region_batches

    chunks = [Chunk.from_rows([I, I], [[Datum.i64((r * 20 + h) % 7), Datum.i64(h)] for h in range(20)]) for r in range(8)]
    scan = TableScan(TID, (ColumnInfo(1, I), ColumnInfo(2, I)))
    agg = Aggregation(group_by=(col(0, I),), aggs=(AggDesc("count", ()), AggDesc("sum", (col(1, I),))))
    dag = DAGRequest((scan, agg), output_offsets=(0, 1, 2))
    chunk, overflow = run_sharded_grouped_agg(dag, stack_region_batches(chunks, n_total=8), region_mesh(8), group_capacity=64)
    assert not overflow and chunk.num_rows() == 7
    return None


@pytest.mark.parametrize("drive", [drive_single, drive_strings, drive_columnar, drive_grouped_mesh],
                         ids=["single_region", "string_columns", "columnar_resident", "grouped_mesh"])
def test_every_driver_fetches_once_per_launch(sess, drive):
    with tracing.trace("root") as root, Moved(LAUNCH_COUNTERS) as m:
        outer = drive(sess)
    assert_one_fetch_per_launch(m, root.find("exec.readback"))
    if outer is not None:   # the order under the span that encloses the launch is what it was
        sp = root.find(outer)[0]
        assert sp.attrs.get("resident", True) is True
        program, call, wait, back = [c.name for c in sp.children]
        assert (program, wait, back) == ("exec.program", "exec.wait", "exec.readback")
        assert call in ("exec.compile", "exec.launch")   # a program another test built is called as it is


STR = new_varchar(20)
COLUMN_KINDS = {
    # out_ft, the column's leaves in `packed` as the program returns them, the values a decoded row holds
    "string_raw_bytes": (STR, lambda: (jnp.zeros((4, 4), jnp.int64), jnp.array([False, True, False, False]),
                                       jnp.array([list(b"ab\0\0"), list(b"\0\0\0\0"), list(b"xyz\0"), list(b"q\0\0\0")], jnp.uint8),
                                       jnp.array([2, 0, 3, 1], jnp.int32)),
                         [b"ab", None, b"xyz"]),
    "string_words_only": (STR, lambda: (jnp.array([[(0x6162 << 48) ^ -(1 << 63), 2], [-(1 << 63), 0],
                                                    [(0x78797A << 40) ^ -(1 << 63), 3], [0, 0]], jnp.int64),
                                        jnp.array([False, True, False, False])),
                          [b"ab", None, b"xyz"]),
    "numeric": (I, lambda: (jnp.array([7, 0, -3, 9], jnp.int64), jnp.array([False, True, False, False])),
                [7, None, -3]),
    "unsigned": (new_longlong(unsigned=True), lambda: (jnp.array([7, 0, -1, 9], jnp.int64), jnp.array([False, True, False, False])),
                 [7, None, (1 << 64) - 1]),
    "time": (new_datetime(), lambda: (jnp.array([1 << 40, 0, 1 << 41, 9], jnp.int64), jnp.array([False, True, False, False])),
             [1 << 40, None, 1 << 41]),
}


@pytest.mark.parametrize("kind", list(COLUMN_KINDS))
def test_decode_outputs_converts_only_the_leaves_the_helper_names(kind):
    ft, leaves, want = COLUMN_KINDS[kind]
    packed = [leaves()]
    valid = jnp.array([True, True, True, False])
    (named,) = executor.output_leaves(packed)
    assert len(named) == (3 if kind == "string_raw_bytes" else 2)
    allowed = {id(a) for a in named} | {id(valid)}

    def to_host(x):
        assert id(x) in allowed, "decode_outputs converted a leaf that output_leaves did not name"
        allowed.discard(id(x))
        return np.asarray(x)

    chunk = executor.decode_outputs(packed, valid, [ft], to_host)
    assert not allowed, "output_leaves named a leaf that decode_outputs never converted"
    (column,) = chunk.columns
    got = [None if column.null[i] else (column.get_bytes(i) if column.is_varlen() else int(column.data[i]))
           for i in range(chunk.num_rows())]
    assert got == want


def test_a_conversion_outside_the_fetch_is_counted_late():
    outputs = launch.HostOutputs(lambda x: (x + 1, x * 2, x.sum() > 99, x * 0.5), reads=lambda o: (o[0], o[2], o[3]))
    x, stray = jnp.arange(4), jnp.arange(3)
    with tracing.trace("root") as root, Moved(LAUNCH_COUNTERS) as m:
        (plus, flag, half), fetch, _ = launch.run_program(outputs, (x,), first_call=True)
        assert all(isinstance(a, np.ndarray) for a in (plus, flag, half))
        assert plus.tolist() == [1, 2, 3, 4] and not flag and half.tolist() == [0.0, 0.5, 1.0, 1.5]
        assert (fetch.transfers, fetch.bytes) == (2, 40 + 4 * 8)   # the buffer (32 + 1, each to 8 bytes) and the float leaf
        with launch.read_back(fetch) as to_host:
            assert to_host(plus) is plus                             # a host array is no transfer
            assert to_host(stray).tolist() == [0, 1, 2]              # a device array is one, of its own
    assert [r[0].strip() for r in root.rows()][1:] == ["exec.compile", "exec.xla_compile", "exec.wait", "exec.readback"]
    (back,) = root.find("exec.readback")
    assert (back.attrs["transfers"], back.attrs["late"], back.attrs["bytes"]) == (3, 1, 72 + 3 * 8)
    assert (m.by["PROGRAM_FETCHES"], m.by["PROGRAM_LAUNCHES"], m.by["PROGRAM_READBACK_LATE"]) == (1, 1, 1)


HOST_LEAVES = {
    "bool": np.array([True, False, True]),
    "int64": np.array([[-1, 2 ** 62], [-(2 ** 63), 7]], np.int64),
    "int32": np.array([-5, 6, 2 ** 31 - 1], np.int32),
    "uint8": np.arange(21, dtype=np.uint8).reshape(3, 7),
    "uint64": np.array([2 ** 64 - 1, 1], np.uint64),
    "scalar": np.int64(-42),
    "float64": np.array([1.5, -0.0, np.inf]),
    "float32": np.array([0.25, -3.0], np.float32),
    "megabyte": np.arange(1 << 17, dtype=np.int64),   # of this size a leaf stays an array of its own
}


@pytest.mark.parametrize("batch", [None, 3], ids=["one_region", "vmapped"])
def test_host_outputs_lays_every_leaf_into_one_buffer_and_reads_it_back(batch):
    leaves = {k: jnp.asarray(v) for k, v in HOST_LEAVES.items()}

    def program(x):   # every leaf depends on the argument, none is a constant the compiler folds
        return {k: (a ^ (x > 0)) if a.dtype == bool else a + x.astype(a.dtype) for k, a in leaves.items()}, x

    outputs = launch.HostOutputs(program if batch is None else jax.vmap(program), reads=lambda o: o[0])
    x = jnp.int64(0) if batch is None else jnp.zeros(batch, jnp.int64)
    returned = outputs.fn(x)
    buf = returned.buf
    assert buf.dtype == jnp.uint8 and buf.ndim == 1 and len(returned.own) == 3   # the float leaves and the large one ride beside it
    got = returned.read()
    assert sorted(got) == sorted(HOST_LEAVES)
    for k, want in HOST_LEAVES.items():
        want = np.asarray(want) if batch is None else np.stack([want] * batch)
        assert got[k].dtype == want.dtype and got[k].shape == want.shape and np.array_equal(got[k], want), k
    sizes = [(1 if batch is None else batch) * np.asarray(v).nbytes for k, v in HOST_LEAVES.items() if "float" not in k and k != "megabyte"]
    assert buf.nbytes == sum(n + -n % 8 for n in sizes)   # each leaf starts on 8 bytes


def test_a_call_is_read_by_the_layout_of_the_trace_that_served_it():
    # a string column's byte width follows the batch, so one program is traced for several shapes;
    # the layout travels with the function's output tree, per compiled signature
    outputs = launch.HostOutputs(lambda x: (x.sum(), x[:, :1] > 0, x + 1))
    narrow, wide = jnp.arange(6, dtype=jnp.int64).reshape(3, 2), jnp.arange(15, dtype=jnp.int64).reshape(3, 5)
    first = outputs.fn(narrow)
    for x in (wide, narrow, wide):
        total, flag, plus = outputs.fn(x).read()
        assert int(total) == int(x.sum()) and flag.shape == (3, 1) and np.array_equal(plus, np.asarray(x) + 1)
    assert np.array_equal(first.read()[2], np.asarray(narrow) + 1)   # read after the function was traced for another shape


@pytest.mark.parametrize("knob", ["group", "join"])
def test_overflow_retry_lands_on_the_exact_rung_with_the_prefetch_in_place(knob):
    from test_radix_join import _canon, _chunks, _join_dag, _pow2

    if knob == "group":   # ~512 groups over rung 1 (64): the need hint names the covering rung
        probe, build = _chunks(np_=512, nb=32, seed=11)
        dag = _join_dag(agg=Aggregation(group_by=(col(1, new_longlong(notnull=True)),), aggs=(AggDesc("count", ()),)),
                        offsets=(0, 1))
        capacities = {"group_capacity": 64}
    else:                 # a non-unique build side: the out-capacity overflow carries the exact fan-out
        probe, build = _chunks(np_=512, nb=32, dup_build=True, seed=13)
        dag = _join_dag(build_unique=False)
        capacities = {"group_capacity": 64, "join_capacity": 64}
    batches = [to_device_batch(c, capacity=_pow2(c.num_rows())) for c in (probe, build)]
    cache = ProgramCache()
    with tracing.trace("root") as root, Moved(LAUNCH_COUNTERS) as m:
        chunk, _counts, _info = executor.drive_program_info(cache, dag, batches, **capacities)
    assert cache.stats()["compiles"] == 2   # the first rung and the hinted one, nothing between
    assert _canon(chunk.rows()) == _canon(run_dag_reference(dag, [probe, build]))
    assert m.by["PROGRAM_FETCHES"] == m.by["PROGRAM_LAUNCHES"] == 2 and m.by["PROGRAM_READBACK_LATE"] == 0
    retried, served = root.find("exec.readback")
    assert (retried.attrs["transfers"], retried.attrs["late"]) == (served.attrs["transfers"], served.attrs["late"]) == (1, 0)
    assert retried.attrs["bytes"] < served.attrs["bytes"]   # the rung that overflowed was the smaller one


# ------------------------------------------------------------------ names
def test_program_names_come_from_the_shape_not_the_literals(sess):
    def programs(sql):
        return [c["attrs"]["program"] for c in find(traced(sess, sql), "exec.compile")]

    a = programs("SELECT SUM(k) FROM sb WHERE id BETWEEN 21 AND 120")
    b = programs("SELECT SUM(k) FROM sb WHERE id BETWEEN 131 AND 230")
    d = programs("SELECT DISTINCT c FROM sb WHERE id BETWEEN 21 AND 120 ORDER BY c")
    assert a and a[0] == "cop_scan_sel_agg"
    assert b == []   # the same shape with other literals: the program that is there is called
    assert d[0] == "cop_scan_sel_distinct_sort"   # one region: the lone cop task's program holds the root's stages too
    assert not set(a) & set(d)


# ------------------------------------------------------------- the wire server
def handled(n: int, since: int) -> None:
    """The server books a command once its last reply byte is out, which
    the client may see first: wait until `n` commands are booked."""
    deadline = time.monotonic() + 10
    while metrics.SERVER_COMMANDS.value - since < n and time.monotonic() < deadline:
        time.sleep(0.002)
    assert metrics.SERVER_COMMANDS.value - since == n


def test_one_query_moves_the_server_counters():
    from tidb_tpu.server import MiniClient, MySQLServer

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


@pytest.fixture(scope="module")
def wire():
    """A served table of 400 rows of 126 bytes on the wire, and a client."""
    from tidb_tpu.server import MiniClient, MySQLServer

    srv = MySQLServer(port=0)
    srv.start_background()
    start = metrics.SERVER_COMMANDS.value
    c = MiniClient(srv.host, srv.port, timeout=120)
    c.query("CREATE TABLE ws (id INT PRIMARY KEY, c CHAR(120))")
    c.query("INSERT INTO ws VALUES " + ",".join(f"({i},'{str(1000 + i) * 30}')" for i in range(1, 401)))
    handled(2, start)
    yield c
    c.close()
    srv.close()


WIRE_COMMANDS = {   # a command -> (its reply's packets, the sends that carry them)
    "select": ("SELECT id, c FROM ws WHERE id <= 4", 1 + 2 + 1 + 4 + 1, 1),
    "insert": ("INSERT INTO ws VALUES (1001, 'n')", 1, 1),
    "error": ("SELECT * FROM no_such_ws", 1, 1),
    "two_statements": ("SELECT 1; SELECT id FROM ws WHERE id <= 2", (1 + 1 + 1 + 1 + 1) + (1 + 1 + 1 + 2 + 1), 1),
    "ping": (None, 1, 1),
    "hundred_rows": ("SELECT c FROM ws WHERE id BETWEEN 101 AND 200", 1 + 1 + 1 + 100 + 1, 1),
}


@pytest.mark.parametrize("case", list(WIRE_COMMANDS))
def test_a_command_s_reply_is_one_send_and_one_receive(wire, case):
    from tidb_tpu.server.client import ClientError
    from tidb_tpu.server.protocol import FLUSH_BYTES, RECV_BYTES

    sql, packets, sends = WIRE_COMMANDS[case]
    start, recvs = metrics.SERVER_COMMANDS.value, wire.io.recvs
    with Moved(SERVER_COUNTERS) as m:
        if sql is None:
            assert wire.ping()
        elif case == "error":
            with pytest.raises(ClientError):
                wire.query(sql)
        else:
            wire.query(sql)
        handled(1, start)
    assert (m.by["SERVER_PACKETS_OUT"], m.by["SERVER_SOCKET_SENDS"]) == (packets, sends)
    assert 100 * 125 < min(FLUSH_BYTES, RECV_BYTES)  # the hundred rows fit both buffers
    assert wire.io.recvs - recvs == 1


def test_a_result_larger_than_the_buffer_leaves_in_several_sends(wire):
    from tidb_tpu.server.protocol import FLUSH_BYTES

    start = metrics.SERVER_COMMANDS.value
    with Moved(SERVER_COUNTERS) as m:
        _, rows = wire.query("SELECT id, c FROM ws WHERE id <= 400 ORDER BY id")
        handled(1, start)
    assert [r[0] for r in rows] == [str(i) for i in range(1, 401)] and all(len(r[1]) == 120 for r in rows)
    assert m.by["SERVER_PACKETS_OUT"] == 1 + 2 + 1 + 400 + 1
    # the buffer hands itself over each time it passes its size, and the command's flush sends the rest
    wire_bytes = 400 * (4 + 1 + len(rows[0][0]) + 1 + 120)
    assert 2 <= wire_bytes // FLUSH_BYTES <= m.by["SERVER_SOCKET_SENDS"] <= wire_bytes // FLUSH_BYTES + 1


def test_server_write_span_says_what_left_and_in_how_many_sends():
    """`server.write` closes over the send: packets, bytes and sends of one
    statement's result are its attributes (the server's threads have no
    ambient trace, so the connection is driven here, under one)."""
    import socket

    from tidb_tpu.server import MySQLServer
    from tidb_tpu.server.server import Connection

    srv = MySQLServer(port=0)
    ours, theirs = socket.socketpair()
    try:
        conn = Connection(theirs, srv, 1)
        conn.session.execute("CREATE TABLE sp (a BIGINT PRIMARY KEY, b BIGINT, c BIGINT)")
        conn.session.execute("INSERT INTO sp VALUES (1,2,3),(2,3,4),(3,4,5),(4,5,6),(5,6,7)")
        with tracing.trace("command") as root:
            conn.handle_query("INSERT INTO sp VALUES (6,7,8); SELECT a, b, c FROM sp WHERE a <= 4")
        first, last = root.find("server.write")
        # the first statement's OK waits in the buffer and leaves with the last statement's result
        assert (first.attrs["packets"], first.attrs["bytes"], first.attrs["sends"]) == (1, 4 + 7, 0)
        assert (last.attrs["packets"], last.attrs["sends"]) == (1 + 3 + 1 + 4 + 1, 1)
        ours.settimeout(10)
        assert len(ours.recv(1 << 16)) == first.attrs["bytes"] + last.attrs["bytes"] == conn.io.bytes_out
    finally:
        ours.close()
        theirs.close()
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
