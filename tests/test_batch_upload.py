"""A batch goes to the device in one host-to-device call (`chunk/device.py`
`_put`, `exec/launch.py` `upload`): its small integer and bool leaves laid
into one byte buffer and cut back apart on the device by one unpack
function per layout, the float leaves and the leaves of `_BESIDE_BYTES` and
more beside the buffer in the same call.  The batch that comes out is the
one a leaf-by-leaf `jnp.asarray` builds, leaf for leaf: values, shape,
dtype, `weak_type` and committedness; and a sysbench-shaped table answers
as its reference does with one transfer a region read and no program
retraced on the new batches."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tidb_tpu.chunk import Chunk, to_device_batch
from tidb_tpu.chunk import device as dv
from tidb_tpu.exec import launch
from tidb_tpu.sql.session import Session
from tidb_tpu.types import Datum, FieldType, Flag, MyDecimal, MyTime, TypeCode
from tidb_tpu.types import new_datetime, new_decimal, new_double, new_float, new_longlong, new_varchar
from tidb_tpu.util import metrics

UPLOAD_COUNTERS = ("COP_UPLOADS", "COP_UPLOAD_TRANSFERS", "COP_UPLOAD_PROGRAMS", "PROGRAM_COMPILES")


class Moved:
    """Counter deltas around a block."""

    def __init__(self, names=UPLOAD_COUNTERS):
        self.names = names

    def __enter__(self):
        self.before = {n: getattr(metrics, n).value for n in self.names}
        return self

    def __exit__(self, *exc):
        self.by = {n: getattr(metrics, n).value - self.before[n] for n in self.names}


def per_leaf(chunk: Chunk, capacity: int, str_widths=None) -> dv.DeviceBatch:
    """The batch as the leaf-by-leaf path built it: the same host arrays,
    each through `jnp.asarray` on its own."""
    n = chunk.num_rows()
    cols = [dv.DeviceColumn(*dv.host_column_arrays(c, capacity, (str_widths or {}).get(ci)), c.ft)
            for ci, c in enumerate(chunk.columns)]
    row_valid = np.zeros(capacity, bool)
    row_valid[:n] = True
    return jax.tree.map(jnp.asarray, dv.DeviceBatch(cols, row_valid, np.int32(n)))


def assert_same_batch(want: dv.DeviceBatch, got: dv.DeviceBatch) -> None:
    w_leaves, w_tree = jax.tree.flatten(want)
    g_leaves, g_tree = jax.tree.flatten(got)
    assert w_tree == g_tree
    for i, (w, g) in enumerate(zip(w_leaves, g_leaves)):
        assert isinstance(g, jax.Array), i
        assert (g.shape, g.dtype, g.weak_type, g._committed) == (w.shape, w.dtype, w.weak_type, w._committed), i
        assert g.devices() == w.devices(), i
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=str(i))


def duration_ft() -> FieldType:
    return FieldType(TypeCode.Duration, Flag.Binary, flen=10, decimal=0)


def char_ft(flen: int) -> FieldType:
    return FieldType(TypeCode.String, Flag(0), flen=flen, decimal=0, charset="utf8mb4")


def maybe(rng, d: Datum, p=0.2) -> Datum:
    return Datum.NULL if rng.random() < p else d


def typed_chunk(n: int, seed: int = 5) -> Chunk:
    """Every kind of column `chunk/device.py` maps, NULLs sprinkled in."""
    rng = np.random.default_rng(seed)
    fts = [new_longlong(), new_longlong(unsigned=True), new_double(), new_float(), new_decimal(15, 2),
           new_datetime(), duration_ft(), char_ft(12), new_varchar(40)]
    rows = []
    for i in range(n):
        rows.append([
            maybe(rng, Datum.i64(int(rng.integers(-2**62, 2**62)))),
            maybe(rng, Datum.u64(int(rng.integers(0, 2**63)) * 2 + 1)),   # above int64's range: bit-cast
            maybe(rng, Datum.f64(float(rng.normal()) * 1e6)),
            maybe(rng, Datum.f64(float(np.float32(rng.normal())))),
            maybe(rng, Datum.dec(MyDecimal(f"{int(rng.integers(-10**6, 10**6)) / 100:.2f}"))),
            maybe(rng, Datum.time(MyTime.from_ymd(1992 + i % 7, 1 + i % 12, 1 + i % 28, i % 24, i % 60, i % 60))),
            maybe(rng, Datum.duration(int(rng.integers(-10**12, 10**12)))),
            maybe(rng, Datum.string("" if i % 3 == 0 else "c" * (i % 12))),   # empty strings and NULLs
            maybe(rng, Datum.string("v%d" % i * (i % 4))),
        ])
    return Chunk.from_rows(fts, rows)


@pytest.mark.parametrize("rows,capacity", [(37, 37), (37, 64), (0, 8), (1, 1), (200, 256)],
                         ids=["exact", "padded", "zero_rows", "one_row", "rung"])
def test_one_call_batch_equals_the_per_leaf_batch(rows, capacity):
    chunk = typed_chunk(rows)
    with Moved() as m:
        got = to_device_batch(chunk, capacity=capacity)
    assert_same_batch(per_leaf(chunk, capacity), got)
    assert (m.by["COP_UPLOADS"], m.by["COP_UPLOAD_TRANSFERS"]) == (1, 1)


def test_given_string_widths_and_all_null_strings():
    rng = np.random.default_rng(11)
    rows = [[Datum.i64(i), Datum.NULL, maybe(rng, Datum.string("x" * (i % 5)), 0.5)] for i in range(50)]
    chunk = Chunk.from_rows([new_longlong(), new_varchar(8), char_ft(6)], rows)
    widths = {1: 8, 2: 6}
    assert_same_batch(per_leaf(chunk, 64, widths), to_device_batch(chunk, capacity=64, str_widths=widths))


def test_a_leaf_of_a_megabyte_rides_beside_the_buffer():
    n = launch._BESIDE_BYTES // 8   # one int64 column of exactly _BESIDE_BYTES
    chunk = Chunk.from_rows([new_longlong(), new_longlong()], [[Datum.i64(i * 3), Datum.i64(-i)] for i in range(n)])
    assert launch.rides_beside((n,), np.dtype(np.int64)) and not launch.rides_beside((n - 1,), np.dtype(np.int64))
    assert launch.rides_beside((3,), np.dtype(np.float32)) and not launch.rides_beside((), np.dtype(np.int32))
    with Moved() as m:
        got = to_device_batch(chunk, capacity=n)
    assert_same_batch(per_leaf(chunk, n), got)
    assert m.by["COP_UPLOAD_TRANSFERS"] == 1


def test_upload_of_leaves_that_all_ride_beside_makes_one_call():
    leaves = [np.arange(5, dtype=np.float64), np.ones((2, 3), np.float32)]
    with Moved() as m:
        got = launch.upload(leaves)
    for w, g in zip(leaves, got):
        assert (g.shape, g.dtype, g.weak_type, g._committed) == (w.shape, w.dtype, False, False)
        np.testing.assert_array_equal(np.asarray(g), w)
    assert m.by["COP_UPLOAD_TRANSFERS"] == 1


def test_every_integer_width_comes_back_bit_for_bit():
    rng = np.random.default_rng(3)
    leaves = [rng.integers(-128, 127, 9).astype(np.int8), rng.integers(0, 2**16, (3, 5)).astype(np.uint16),
              rng.integers(-2**31, 2**31 - 1, 7).astype(np.int32), np.array(-5, np.int32),
              rng.integers(-2**63, 2**63 - 1, 4, dtype=np.int64), rng.integers(0, 2**64 - 1, 3, dtype=np.uint64),
              rng.random(11) < 0.5, np.zeros(0, np.int64), rng.integers(0, 256, (4, 3)).astype(np.uint8)]
    with Moved() as m:
        got = launch.upload(leaves)
    assert m.by["COP_UPLOAD_TRANSFERS"] == 1
    for w, g in zip(leaves, got):
        want = jnp.asarray(w)
        assert (g.shape, g.dtype, g.weak_type, g._committed) == (want.shape, want.dtype, want.weak_type, want._committed)
        np.testing.assert_array_equal(np.asarray(g), w)


def test_a_second_batch_of_the_layout_builds_nothing():
    first, second = typed_chunk(29, seed=1), typed_chunk(29, seed=2)
    widths = {7: 12, 8: 40}   # the string planes' widths fixed, so both batches share a layout
    to_device_batch(first, capacity=32, str_widths=widths)
    with Moved() as m:
        got = to_device_batch(second, capacity=32, str_widths=widths)
    assert_same_batch(per_leaf(second, 32, widths), got)
    assert m.by == {"COP_UPLOADS": 1, "COP_UPLOAD_TRANSFERS": 1, "COP_UPLOAD_PROGRAMS": 0, "PROGRAM_COMPILES": 0}


def test_a_new_layout_builds_one_unpack_and_compiles_it_as_a_program():
    chunk = Chunk.from_rows([new_longlong(), new_varchar(80)], [[Datum.i64(i), Datum.string("z" * 77)] for i in range(3)])
    names = UPLOAD_COUNTERS + ("XLA_COMPILES", "XLA_EAGER_COMPILES", "PROGRAM_LAUNCHES", "PROGRAM_READBACK_TRANSFERS")
    with Moved(names) as m:
        to_device_batch(chunk, capacity=3)   # a width of 77 bytes and three rows: no other test's layout
    assert m.by["COP_UPLOAD_PROGRAMS"] == m.by["PROGRAM_COMPILES"] == 1
    assert m.by["XLA_COMPILES"] == 1 and m.by["XLA_EAGER_COMPILES"] == 0
    # the unpack is no launch: nothing read back
    assert m.by["PROGRAM_LAUNCHES"] == m.by["PROGRAM_READBACK_TRANSFERS"] == 0


def test_threads_meeting_a_new_layout_build_one_unpack():
    import sys
    import threading

    chunk = Chunk.from_rows([new_longlong(), new_varchar(70)], [[Datum.i64(i), Datum.string("q" * 69)] for i in range(5)])
    want = per_leaf(chunk, 5)
    out, errors = [None] * 12, []

    def put(i):
        try:
            out[i] = to_device_batch(chunk, capacity=5)   # a width of 69 bytes and five rows: a layout of this test alone
        except Exception as e:  # handed to the main thread, which fails the test
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with Moved() as m:
            threads = [threading.Thread(target=put, args=(i,)) for i in range(len(out))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    for got in out:
        assert_same_batch(want, got)
    assert m.by["COP_UPLOAD_PROGRAMS"] == m.by["PROGRAM_COMPILES"] == 1
    assert m.by["COP_UPLOADS"] == m.by["COP_UPLOAD_TRANSFERS"] == len(out)


def test_the_sharded_branch_is_one_device_put_of_the_tree():
    from jax.sharding import NamedSharding, PartitionSpec

    from tidb_tpu.parallel.mesh import region_mesh

    chunks = [typed_chunk(20, seed=s) for s in range(4)]
    sharding = NamedSharding(region_mesh(4), PartitionSpec())
    with Moved() as m:
        got = dv.to_stacked_device_batch(chunks, 32, sharding=sharding)
    assert (m.by["COP_UPLOADS"], m.by["COP_UPLOAD_TRANSFERS"], m.by["COP_UPLOAD_PROGRAMS"]) == (1, 1, 0)
    plain = dv.to_stacked_device_batch(chunks, 32)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(plain)):
        assert g.sharding == sharding and g._committed and (g.shape, g.dtype) == (w.shape, w.dtype)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------------ served path
N_ROWS = 1024


def _groups(rng, k: int) -> list:
    """sysbench's '###########-###...': `k` groups of 11 digits."""
    return ["-".join(f"{v:011d}" for v in row) for row in rng.integers(0, 10**11, size=(N_ROWS, k)).tolist()]


@pytest.fixture(scope="module")
def sbtest():
    rng = np.random.default_rng(42)
    k, c, pad = rng.integers(1, N_ROWS + 1, N_ROWS).tolist(), _groups(rng, 10), _groups(rng, 5)
    s = Session()
    s.execute("""create table sbtest1 (
        id int not null auto_increment, k int not null default 0,
        c char(120) not null default '', pad char(60) not null default '',
        primary key (id), key k_1 (k))""")
    s.execute("insert into sbtest1 (id, k, c, pad) values "
              + ",".join(f"({i + 1},{k[i]},'{c[i]}','{pad[i]}')" for i in range(N_ROWS)))
    return s, k, c


def statements(a: int) -> list:
    """sysbench oltp_read_only's four range statements over ids a..a+99, and a point select."""
    b = a + 99
    return [
        ("simple", f"select c from sbtest1 where id between {a} and {b}"),
        ("sum", f"select sum(k) from sbtest1 where id between {a} and {b}"),
        ("order", f"select c from sbtest1 where id between {a} and {b} order by c"),
        ("distinct", f"select distinct c from sbtest1 where id between {a} and {b} order by c"),
        ("point", f"select c from sbtest1 where id = {a + 7}"),
    ]


def reference(name: str, sql: str, k: list, c: list) -> list:
    lo = int(sql.split("between ")[1].split(" ")[0]) if "between" in sql else int(sql.rsplit("= ", 1)[1])
    ids = range(lo, lo + 100)
    if name == "simple":
        return sorted([c[i - 1]] for i in ids)
    if name == "sum":
        return [[str(sum(k[i - 1] for i in ids))]]
    if name == "order":
        return [[v] for v in sorted(c[i - 1] for i in ids)]
    if name == "distinct":
        return [[v] for v in sorted({c[i - 1] for i in ids})]
    return [[c[lo - 1]]]


def find(node, name) -> list:
    out = [node] if node["name"] == name else []
    for ch in node.get("children", []):
        out.extend(find(ch, name))
    return out


def run(sess, k, c, a: int) -> list:
    """Each statement traced and checked against the reference; the
    `cop.decode` spans' (hit, transfers) in order."""
    decodes = []
    for name, sql in statements(a):
        tree = json.loads(sess.execute(f"TRACE FORMAT='json' {sql}").values()[0][0])
        got = [list(r) for r in sess.execute(sql).values()]
        got = sorted(got) if name == "simple" else got
        want = reference(name, sql, k, c)
        assert [[str(v) if name == "sum" else v for v in r] for r in got] == want, (sql, got[:2], want[:2])
        decodes += [(d["attrs"]["hit"], d["attrs"]["transfers"]) for d in find(tree, "cop.decode")]
    return decodes


def test_sysbench_ranges_answer_as_the_reference_with_one_transfer_a_miss(sbtest):
    sess, k, c = sbtest
    decodes = run(sess, k, c, 101)
    # the first range reads and uploads the region's 100 rows in one call;
    # the three after it find the batch resident; the point get reads no batch
    assert decodes == [(False, 1), (True, 0), (True, 0), (True, 0)]
    names = ("PROGRAM_COMPILES", "XLA_COMPILES", "COP_UPLOADS", "COP_UPLOAD_TRANSFERS", "COP_DECODE_MISSES")
    with Moved(names) as m:
        again = run(sess, k, c, 517)   # fresh ranges: new batches, of the same layout
    assert again == [(False, 1), (True, 0), (True, 0), (True, 0)]
    assert m.by["PROGRAM_COMPILES"] == m.by["XLA_COMPILES"] == 0   # no program retraced on the new batches
    assert m.by["COP_UPLOAD_TRANSFERS"] == m.by["COP_UPLOADS"] == m.by["COP_DECODE_MISSES"] >= 1
