"""String literals ride as operands (ISSUE 32): a non-NULL ASCII string
constant outside the `TRACE_TIME_ARGS` positions takes a row of the
program's byte operand, and the program's key holds the rung of its width
in place of its bytes, so TPC-H Q3's five SEGMENTs are one program.  The
operand has to compare exactly as the baked constant does; what reads a
constant while it traces (LIKE's pattern, a NULL, a non-ASCII literal for
the CI guards) stays in the key.  Counts and answers only."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from test_launch_spans import Moved, find, traced
from test_program_params import run_mesh, run_single, run_vmap

from tidb_tpu.chunk import Chunk, to_device_batch
from tidb_tpu.exec import Aggregation, ColumnInfo, DAGRequest, ProgramCache, Selection, TableScan
from tidb_tpu.exec.dag import operand_lanes
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.expr.compile import ExprCompiler
from tidb_tpu.expr.ir import STR_WIDTH_FLOOR, Const, Param, ParamSeats, str_width_rung
from tidb_tpu.sql.session import Session
from tidb_tpu.types import Datum, new_longlong, new_varchar
from tidb_tpu.types.field_type import Collation
from tidb_tpu.util import metrics

BOOL = new_longlong(notnull=True)
LL = new_longlong()
BIN = new_varchar(40)                                       # utf8mb4_bin
CI = new_varchar(40, collate=Collation.Utf8MB4GeneralCI)    # PAD SPACE, case-insensitive in MySQL
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
COUNTERS = ("PROGRAM_COMPILES", "XLA_COMPILES", "PROGRAM_LAUNCHES", "PROGRAM_PARAMS_BOUND",
            "PROGRAM_STR_PARAMS_BOUND", "COP_FALLBACKS")


def seg_chunk(n=200, seed=3, ft=BIN):
    rng = np.random.default_rng(seed)
    return Chunk.from_rows([LL, ft], [[Datum.i64(i), Datum.string(SEGMENTS[int(rng.integers(5))])] for i in range(n)])


def scan(tid, fts):
    return TableScan(tid, tuple(ColumnInfo(i + 1, ft) for i, ft in enumerate(fts)))


def by_segment(seg, tid=5, ft=BIN):
    """customer-like: count the rows of one market segment."""
    agg = Aggregation((), (AggDesc("count", ()),), partial=True)
    return DAGRequest((scan(tid, [LL, ft]), Selection((func("eq", BOOL, col(1, ft), lit(seg, ft)),)), agg),
                      output_offsets=(0,))


# ------------------------------------------------------------------ the rule
@pytest.mark.parametrize("nbytes,rung", [(0, 16), (1, 16), (15, 16), (16, 16), (17, 32), (32, 32), (33, 64), (200, 256)])
def test_width_ladder_starts_at_sixteen_and_doubles(nbytes, rung):
    assert STR_WIDTH_FLOOR == 16 and str_width_rung(nbytes) == rung


def test_five_segments_are_one_key_and_one_operand_shape():
    dags = [by_segment(s) for s in SEGMENTS]
    assert len({d.program_key() for d in dags}) == 1 and len({d.fingerprint() for d in dags}) == 5
    for seg, d in zip(SEGMENTS, dags):
        shape, key, operands = d.parameterized()
        assert operand_lanes(operands) == ("s", "n")
        rows, lengths = operands
        assert (rows.dtype, rows.shape, lengths.dtype, lengths.tolist()) == (np.uint8, (1, 16), np.int32, [len(seg)])
        assert bytes(rows[0, :len(seg)]) == seg.encode() and not rows[0, len(seg):].any()
        seat = shape.executors[1].conditions[0].args[1]
        assert isinstance(seat, Param) and (seat.lane, seat.slot, seat.width) == ("s", 0, 16)
        assert seg not in str(key) and seg in str(d.fingerprint())


def test_string_operands_follow_the_numbers_in_walk_order():
    cond = (func("gt", BOOL, col(0, LL), lit(5, LL)), func("eq", BOOL, col(1, BIN), lit("BUILDING", BIN)),
            func("ne", BOOL, col(1, BIN), lit("x" * 20, BIN)), func("lt", BOOL, col(0, LL), lit(90, LL)))
    dag = DAGRequest((scan(3, [LL, BIN]), Selection(cond)), output_offsets=(0,))
    ints, rows, lengths = dag.program_operands()
    assert operand_lanes(dag.program_operands()) == ("i", "s", "n")
    assert ints.tolist() == [5, 90] and rows.shape == (2, 32) and lengths.tolist() == [8, 20]   # the widest seat's rung
    seats = [c.args[1] for c in dag.parameterized()[0].executors[1].conditions]
    assert [(p.lane, p.slot, p.width) for p in seats] == [("i", 0, 0), ("s", 0, 16), ("s", 1, 32), ("i", 1, 0)]


STILL_IN_THE_KEY = {
    "null": lambda v: func("eq", BOOL, col(1, BIN), lit(v, BIN)),
    "like_pattern": lambda v: func("like", BOOL, col(1, BIN), lit(v, BIN)),
    "non_ascii": lambda v: func("eq", BOOL, col(1, BIN), lit(v, BIN)),
    "non_ascii_ci": lambda v: func("eq", BOOL, col(1, CI), lit(v, CI)),
    "bytes_non_ascii": lambda v: func("eq", BOOL, col(1, BIN), Const(Datum.bytes_(v), BIN)),
}
VALUES = {"null": (None, "BUILDING"), "like_pattern": ("BUI%", "MACH%"), "non_ascii": ("MÖBEL", "MÜBEL"),
          "non_ascii_ci": ("möbel", "mübel"), "bytes_non_ascii": (b"\xc3\x96", b"\xc3\x9c")}


@pytest.mark.parametrize("case", list(STILL_IN_THE_KEY))
def test_what_the_trace_reads_stays_in_the_key(case):
    a, b = (DAGRequest((scan(5, [LL, BIN]), Selection((STILL_IN_THE_KEY[case](v),))), output_offsets=(0,))
            for v in VALUES[case])
    assert a.program_key() != b.program_key()
    kept = a.parameterized()[0].executors[1].conditions[0].args[1]
    assert isinstance(kept, Const) and "s" not in operand_lanes(a.program_operands())


def test_an_ascii_literal_under_ci_is_an_operand_and_a_non_ascii_one_is_not():
    assert Const(Datum.string("Building"), CI).operand() == ("s", b"Building")
    assert Const(Datum.string("möbel"), CI).operand() is None
    assert Const(Datum.string(""), BIN).operand() == ("s", b"")
    assert Const(Datum.NULL, BIN).operand() is None


# ------------------------------------------------------------------ parity with the baked constant
LENGTHS = {"len0": "", "len15": "c" * 15, "len16": "c" * 16, "len17": "c" * 17, "len33": "c" * 32 + "d"}
COLUMN = ["", "c" * 15, "c" * 16, "c" * 17, "c" * 14 + "d", "c" * 16 + " ", "b", "d", "c" * 32 + "d", "c" * 32 + "e",
          "Building", "BUILDING", "building ", "building", "BUILDING  ", " building", "a\x00", "a"]
OPS = ("eq", "ne", "lt", "ge")


def _column(ft):
    ch = Chunk.from_rows([ft], [[Datum.string(v)] for v in COLUMN] + [[Datum.NULL]])
    return list(to_device_batch(ch, capacity=32).cols)


def _both_ways(expr, ft):
    """(lanes by the baked constant, lanes by the operand, null lanes of both)."""
    cols = _column(ft)
    baked = ExprCompiler([ft]).run([expr], cols)[0]
    seats = ParamSeats()
    seated = expr.seated(seats)
    assert seats.strs, "nothing was seated"
    dag = DAGRequest((scan(1, [ft]), Selection((expr,))), output_offsets=(0,))
    operands = dag.program_operands()
    params = {lane: jnp.asarray(o) for lane, o in zip(operand_lanes(operands), operands)}
    handed = ExprCompiler([ft], params).run([seated], cols)[0]
    n = len(COLUMN) + 1
    return (np.asarray(baked.value)[:n], np.asarray(handed.value)[:n],
            np.asarray(baked.null)[:n], np.asarray(handed.null)[:n])


@pytest.mark.parametrize("literal", list(LENGTHS))
@pytest.mark.parametrize("op", OPS)
def test_operand_and_baked_constant_agree_at_the_rungs_edge(op, literal):
    value = LENGTHS[literal]
    b, h, bn, hn = _both_ways(func(op, BOOL, col(0, BIN), lit(value, BIN)), BIN)
    assert b.tolist() == h.tolist() and bn.tolist() == hn.tolist()
    # and both say what bytes say (32 bytes and the length are compared: the packed-word contract)
    cmp = {"eq": lambda x: x == 0, "ne": lambda x: x != 0, "lt": lambda x: x < 0, "ge": lambda x: x >= 0}[op]
    key = lambda s: (s.encode()[:32], len(s.encode()))   # noqa: E731
    want = [int(cmp((key(v) > key(value)) - (key(v) < key(value)))) for v in COLUMN]
    assert h[:-1].tolist() == want and bool(hn[-1])


@pytest.mark.parametrize("literal", ["building", "BUILDING", "Building  ", "building ", " building", ""])
@pytest.mark.parametrize("op", OPS)
def test_operand_and_baked_constant_agree_under_general_ci(op, literal):
    """Mixed case and trailing spaces under a PAD SPACE, case-insensitive
    collation: whatever the baked constant's compare says, lane for lane."""
    b, h, bn, hn = _both_ways(func(op, BOOL, col(0, CI), lit(literal, CI)), CI)
    assert b.tolist() == h.tolist() and bn.tolist() == hn.tolist()
    if op == "eq" and literal == "building":
        assert [COLUMN[i] for i in np.flatnonzero(h[:-1])] == ["Building", "BUILDING", "building"]


@pytest.mark.parametrize("ft", [BIN, CI], ids=["bin", "general_ci"])
def test_in_list_of_strings_agrees_and_takes_a_seat_each(ft):
    expr = func("in", BOOL, col(0, ft), lit("BUILDING", ft), lit("c" * 17, ft), lit("", ft))
    b, h, bn, hn = _both_ways(expr, ft)
    assert b.tolist() == h.tolist() and bn.tolist() == hn.tolist() and h.sum() >= 3
    dag = DAGRequest((scan(1, [ft]), Selection((expr,))), output_offsets=(0,))
    rows, lengths = dag.program_operands()
    assert rows.shape == (3, 32) and lengths.tolist() == [8, 17, 0]


def test_a_non_ascii_literal_under_ci_takes_the_route_it_took():
    """The constant stays baked, the CI guard reads its bytes while the
    program traces and refuses: the plan goes to the oracle, as before."""
    expr = func("eq", BOOL, col(0, CI), lit("möbel", CI))
    with pytest.raises(NotImplementedError, match="non-ASCII constant under CI"):
        ExprCompiler([CI]).run([expr.seated(ParamSeats())], _column(CI))
    s = Session()
    s.execute("create table w (id int primary key, name varchar(20) collate utf8mb4_general_ci)")
    s.execute("insert into w values (1,'mobel'),(2,'MOBEL'),(3,'Building'),(4,'building ')")
    with Moved(COUNTERS) as m:
        got = s.execute("select id from w where name = 'MÖBEL' order by id").values()
    assert got == [] and m.by["PROGRAM_STR_PARAMS_BOUND"] == 0 and m.by["COP_FALLBACKS"] == 1
    with Moved(COUNTERS) as m:
        got = s.execute("select id from w where name = 'BUILDING'").values()
    assert [int(str(r[0])) for r in got] == [3] and m.by["PROGRAM_STR_PARAMS_BOUND"] == 1 and m.by["COP_FALLBACKS"] == 0


# ------------------------------------------------------------------ one program, every tier
TIERS = {"single": (run_single, 1), "vmap_batch": (run_vmap, 4), "mesh": (run_mesh, 8)}


@pytest.mark.parametrize("tier", list(TIERS))
def test_five_segments_build_one_program(tier):
    run, lanes = TIERS[tier]
    chunks = [seg_chunk(seed=10 + i) for i in range(lanes)]
    cache = ProgramCache()
    with Moved(COUNTERS) as first:
        run(cache, by_segment(SEGMENTS[0]), chunks)
    with Moved(COUNTERS) as rest:
        for seg in SEGMENTS[1:]:
            run(cache, by_segment(seg, tid=9), chunks)
    assert (cache.stats()["compiles"], cache.stats()["hits"]) == (1, 4)
    assert first.by["PROGRAM_COMPILES"] == 1 and rest.by["PROGRAM_COMPILES"] == rest.by["XLA_COMPILES"] == 0
    assert first.by["PROGRAM_STR_PARAMS_BOUND"] == 1 and rest.by["PROGRAM_STR_PARAMS_BOUND"] == 4
    assert rest.by["PROGRAM_PARAMS_BOUND"] == 4   # the strings are among the constants handed over


def test_a_longer_literal_builds_the_next_rung_once():
    chunk = Chunk.from_rows([LL, BIN], [[Datum.i64(i), Datum.string("s" * (i % 40))] for i in range(120)])
    cache = ProgramCache()
    for value in ("s" * 3, "s" * 16, "s" * 17, "s" * 31, "s" * 9):
        run_single(cache, by_segment(value), [chunk])
    assert (cache.stats()["compiles"], cache.stats()["hits"]) == (2, 3)


# ------------------------------------------------------------------ through the session
@pytest.fixture()
def customers():
    s = Session()
    s.execute("create table customer (c_custkey bigint primary key, c_mktsegment char(10) not null, c_acctbal bigint)")
    rng = random.Random(5)
    rows = [(i, rng.choice(SEGMENTS), rng.randrange(1000)) for i in range(1, 301)]
    s.execute("insert into customer values " + ",".join(f"({k},'{seg}',{b})" for k, seg, b in rows))
    return s, rows


def test_served_segments_build_one_program_and_rebind_from_the_plan_cache(customers):
    s, rows = customers
    sql = "select count(*), sum(c_acctbal) from customer where c_mktsegment = '{}' and c_acctbal < {}"
    hits = metrics.PLAN_CACHE_HITS.value
    built = []
    for i, seg in enumerate(SEGMENTS):
        with Moved(COUNTERS) as m:
            (got,) = s.execute(sql.format(seg, 900 - i)).values()
        want = [r for r in rows if r[1] == seg and r[2] < 900 - i]
        assert (int(str(got[0])), int(str(got[1]))) == (len(want), sum(r[2] for r in want)), seg
        assert m.by["PROGRAM_STR_PARAMS_BOUND"] == 1 and m.by["COP_FALLBACKS"] == 0
        built.append(m.by["PROGRAM_COMPILES"])
    assert built[0] >= 1 and built[1:] == [0, 0, 0, 0]
    assert metrics.PLAN_CACHE_HITS.value - hits == 4   # the cached plan took each new string as it takes a number


def test_prepared_statement_takes_a_string_user_variable(customers):
    s, rows = customers
    s.execute("prepare seg from 'select count(*) from customer where c_mktsegment = ?'")
    built = []
    for seg in SEGMENTS:
        s.execute(f"set @s = '{seg}'")
        with Moved(COUNTERS) as m:
            (got,) = s.execute("execute seg using @s").values()
        assert int(str(got[0])) == sum(r[1] == seg for r in rows)
        built.append(m.by["PROGRAM_COMPILES"])
    assert built[1:] == [0, 0, 0, 0]


def test_launch_span_counts_the_string_seats_and_metrics_page_has_the_counter(customers):
    s, _rows = customers
    sql = "select count(*) from customer where c_mktsegment = '{}' and c_acctbal between 10 and {}"
    s.execute(sql.format("BUILDING", 500))
    tree = traced(s, sql.format("MACHINERY", 600))
    (launch,) = [n for n in find(tree, "exec.launch") if n["attrs"]["program"].startswith("cop_scan_sel")]
    assert launch["attrs"]["params"] == 3 and not find(tree, "exec.compile")
    page = metrics.REGISTRY.dump()
    assert "tidb_tpu_program_str_params_bound_total" in page and "tidb_tpu_cop_aux_uploads_total" in page


# ------------------------------------------------------------------ the root merge's rung
def test_root_merge_input_rung_is_sticky_and_picked_with_room():
    cache, dag = ProgramCache(), by_segment("BUILDING")
    assert cache.input_capacity(dag, 0, 244) == 512                     # the ladder's rung for twice the first input
    assert [cache.input_capacity(dag, 0, n) for n in (212, 283, 512, 1)] == [512] * 4
    assert cache.input_capacity(dag, 0, 513) == 2048 and cache.input_capacity(dag, 0, 244) == 2048
    assert cache.input_capacity(by_segment("MACHINERY", tid=8), 0, 100) == 2048   # one plan shape, one rung
    assert cache.input_capacity(dag, 1, 100) == 256 and cache.input_capacity(dag, 1, 0) == 256
    assert ProgramCache().input_capacity(dag, 0, 0) == 64               # the ladder's floor


def test_group_counts_on_both_sides_of_a_rung_build_one_root_program(customers):
    """Q3's shape in small: the number of groups moves with the literal
    across a rung of the ladder (here 64); the root merge must not compile again."""
    s, rows = customers
    sql = "select c_custkey % 128, count(*) from customer where c_custkey % 128 < {} group by c_custkey % 128 order by 1"
    built = []
    for bound in (50, 70, 60, 90, 30):   # as many groups as the bound: both sides of 64, inside twice the first
        with Moved(COUNTERS) as m:
            got = s.execute(sql.format(bound)).values()
        assert [int(str(r[0])) for r in got] == list(range(bound))
        assert sum(int(str(r[1])) for r in got) == sum(r[0] % 128 < bound for r in rows)
        built.append(m.by["PROGRAM_COMPILES"])
    assert built[0] >= 1 and built[1:] == [0, 0, 0, 0], built
