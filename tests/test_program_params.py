"""One compiled program per plan shape (ISSUE 27): a parameterisable literal
is an operand of the cop program and the scan's identity is not in the
program's key, so requests that differ in either share one program; what
shapes the trace (LIKE's pattern, ROUND's digits, an interval's unit, a
string's width rung, a NULL, LIMIT) still splits the key.  Counts and
answers only."""

import decimal
import random

import numpy as np
import pytest

from test_launch_spans import Moved

from tidb_tpu.chunk import Chunk, to_device_batch
from tidb_tpu.chunk.device import to_stacked_device_batch
from tidb_tpu.distsql.planner import mesh_merge_kind
from tidb_tpu.exec import Aggregation, ColumnInfo, DAGRequest, Limit, ProgramCache, Projection, Selection, TableScan
from tidb_tpu.exec.dag import Sort
from tidb_tpu.exec.executor import (
    datum_group_key,
    drive_batched_program_info,
    drive_mesh_program_info,
    drive_program_info,
    run_dag_reference,
)
from tidb_tpu.expr import AggDesc, col, func, lit
from tidb_tpu.expr.ir import Const, Param
from tidb_tpu.sql.session import Session
from tidb_tpu.types import Datum, MyDecimal, MyTime, new_datetime, new_decimal, new_double, new_longlong, new_varchar
from tidb_tpu.util import metrics

BOOL = new_longlong(notnull=True)
LL, STR, DT, DEC, DBL = new_longlong(), new_varchar(20), new_datetime(), new_decimal(15, 2), new_double()
SB_FTS = [LL, LL, STR]            # sysbench-like: id, k, c
LI_FTS = [DT, DEC, DEC, DEC]      # lineitem-like: shipdate, quantity, extendedprice, discount
COUNTERS = ("PROGRAM_COMPILES", "XLA_COMPILES", "PROGRAM_LAUNCHES", "PROGRAM_PARAMS_BOUND", "COP_CACHE_HITS",
            "COP_UPLOAD_PROGRAMS")


def sb_chunk(n=200, first=1, seed=3):
    rng = np.random.default_rng(seed)
    return Chunk.from_rows(SB_FTS, [
        [Datum.i64(first + i), Datum.i64(int(rng.integers(1, 1000))), Datum.string(f"c{int(rng.integers(40)):03d}")]
        for i in range(n)])


def li_chunk(n=200, seed=5):
    rng = np.random.default_rng(seed)
    return Chunk.from_rows(LI_FTS, [
        [Datum.time(MyTime.from_ymd(1993 + int(rng.integers(3)), 1 + int(rng.integers(12)), 1 + int(rng.integers(28)))),
         Datum.dec(MyDecimal(f"{int(rng.integers(1, 51))}.00")),
         Datum.dec(MyDecimal(f"{int(rng.integers(90000, 900000)) / 100:.2f}")),
         Datum.dec(MyDecimal(f"0.0{int(rng.integers(10))}"))]
        for _ in range(n)])


def scan(tid, fts):
    return TableScan(tid, tuple(ColumnInfo(i + 1, ft) for i, ft in enumerate(fts)))


def between_id(lo, hi):
    return Selection((func("between", BOOL, col(0, LL), lit(lo, LL), lit(hi, LL)),))


# ---- the cell's four shapes and a Q6-like one: (table id, two literals) -> DAG
def scan_sel(tid, lo, hi):
    return DAGRequest((scan(tid, SB_FTS), between_id(lo, hi)), output_offsets=(2,))


def scan_sel_agg(tid, lo, hi):
    agg = Aggregation((), (AggDesc("sum", (col(1, LL),)),), partial=True)
    return DAGRequest((scan(tid, SB_FTS), between_id(lo, hi), agg), output_offsets=(0,))


def scan_sel_distinct(tid, lo, hi):
    agg = Aggregation((col(2, STR),), (), partial=True)
    return DAGRequest((scan(tid, SB_FTS), between_id(lo, hi), agg), output_offsets=(0,))


def scan_sort(tid, lo, hi):
    """The root's sort over what the regions sent: no literal in it."""
    return DAGRequest((scan(tid, SB_FTS), Sort(((col(2, STR), False),))), output_offsets=(2,))


def q6_like(tid, lo, hi):
    """date ge/lt, decimal between, int lt; `lo`/`hi` move the year and the discount."""
    year, disc = 1993 + lo % 3, hi % 7
    cond = (func("ge", BOOL, col(0, DT), lit(f"{year}-01-01", DT)),
            func("lt", BOOL, col(0, DT), lit(f"{year + 1}-01-01", DT)),
            func("between", BOOL, col(3, DEC), lit(f"0.0{disc}", new_decimal(3, 2)), lit(f"0.0{disc + 2}", new_decimal(3, 2))),
            func("lt", BOOL, col(1, DEC), lit(24 + lo % 2, LL)))
    revenue = func("mul", new_decimal(30, 4), col(2, DEC), col(3, DEC))
    agg = Aggregation((), (AggDesc("sum", (revenue,)), AggDesc("count", ())), partial=True)
    return DAGRequest((scan(tid, LI_FTS), Selection(cond), agg), output_offsets=(0, 1))


SHAPES = {"scan_sel": (scan_sel, sb_chunk), "scan_sel_agg": (scan_sel_agg, sb_chunk),
          "scan_sel_distinct": (scan_sel_distinct, sb_chunk), "scan_sort": (scan_sort, sb_chunk),
          "q6_like": (q6_like, li_chunk)}
MESH_SHAPES = [n for n, (make, _) in SHAPES.items() if mesh_merge_kind(make(1, 1, 2)) is not None]


def canon(rows):
    def one(d):
        k = datum_group_key(d)
        return (k[0], float(f"{k[1]:.12g}")) if isinstance(k[1], float) else k
    return [tuple(one(d) for d in r) for r in rows]


def assert_rows(chunk, want, ordered=False):
    got, want = canon(chunk.rows()), canon(want)
    if not ordered:
        got, want = sorted(got), sorted(want)
    assert got == want, (got[:4], want[:4], len(got), len(want))


def run_single(cache, dag, chunks):
    batch = to_device_batch(chunks[0], capacity=256)
    chunk, _counts, _info = drive_program_info(cache, dag, [batch], 64)
    assert_rows(chunk, run_dag_reference(dag, chunks[0]), ordered=isinstance(dag.executors[-1], Sort))


def run_vmap(cache, dag, chunks):
    per_region, _info = drive_batched_program_info(cache, dag, to_stacked_device_batch(chunks, 256), [], 64)
    assert len(per_region) == len(chunks)
    for (chunk, _counts), ch in zip(per_region, chunks):
        assert_rows(chunk, run_dag_reference(dag, ch), ordered=isinstance(dag.executors[-1], Sort))


def run_mesh(cache, dag, chunks):
    import jax

    assert len(jax.devices()) == 8  # tests/conftest.py's virtual CPU devices
    merged, lane_counts, _info = drive_mesh_program_info(
        cache, dag, to_stacked_device_batch(chunks, 256), [], 64, mesh_merge_kind(dag), 8)
    assert merged is not None and len(lane_counts) == len(chunks)
    # partial states are additive and group tables disjoint by key: the
    # merged state is the reference's over every lane's rows at once
    assert_rows(merged, run_dag_reference(dag, Chunk.concat(chunks)))


TIERS = {"single": (run_single, 1), "vmap_batch": (run_vmap, 4), "mesh": (run_mesh, 8)}
CASES = [(s, t) for s in SHAPES for t in ("single", "vmap_batch")] + [(s, "mesh") for s in MESH_SHAPES]


# ------------------------------------------------------------------ (a)
@pytest.mark.parametrize("shape,tier", CASES, ids=[f"{s}-{t}" for s, t in CASES])
@pytest.mark.parametrize("differ", ["literals", "table_id"])
def test_one_program_serves_other_literals_and_other_tables(shape, tier, differ):
    make, data = SHAPES[shape]
    run, lanes = TIERS[tier]
    chunks = [data(seed=10 + i) for i in range(lanes)]
    first = make(7, 20, 119)
    second = make(7, 61, 148) if differ == "literals" else make(31, 20, 119)
    if shape != "scan_sort" or differ == "table_id":
        assert first.fingerprint() != second.fingerprint()   # the result caches still tell them apart
    assert first.program_key() == second.program_key()
    cache = ProgramCache()
    with Moved(COUNTERS) as one:
        run(cache, first, chunks)
    with Moved(COUNTERS) as two:
        run(cache, second, chunks)
    assert (cache.stats()["compiles"], cache.stats()["hits"]) == (1, 1)
    # XLA compiled once, in the first call (the mesh tier's first call also
    # compiles the transfers that shard its inputs); an upload of a batch
    # layout new to the process builds and compiles its unpack function too
    unpacks = one.by["COP_UPLOAD_PROGRAMS"]
    assert one.by["PROGRAM_COMPILES"] - unpacks == 1 and one.by["XLA_COMPILES"] - unpacks >= 1
    assert one.by["XLA_COMPILES"] - unpacks == 1 or tier == "mesh"
    assert two.by["PROGRAM_COMPILES"] == two.by["XLA_COMPILES"] == 0
    assert one.by["PROGRAM_LAUNCHES"] == two.by["PROGRAM_LAUNCHES"] == 1
    n_params = sum(len(o) for o in first.program_operands())
    assert one.by["PROGRAM_PARAMS_BOUND"] == two.by["PROGRAM_PARAMS_BOUND"] == n_params
    assert n_params == {"scan_sort": 0, "q6_like": 5}.get(shape, 2)


def test_racing_first_calls_trace_and_compile_once():
    """Six clients meet one cold shape at once, each with its own literals:
    the cache builds one program, and its first call is single-flight too
    (exec/launch.py FirstCallGate): one call traces and compiles, where
    racing calls of an un-traced function would each trace it."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    cache, chunk = ProgramCache(), sb_chunk(seed=21)
    start = threading.Barrier(6, timeout=30)

    def client(i):
        start.wait()
        run_single(cache, scan_sel_agg(40 + i, 10 + 7 * i, 90 + 11 * i), [chunk])

    traced_calls = metrics.PROGRAM_COMPILE_DURATION.count   # calls in which JAX traced or compiled
    with Moved(COUNTERS) as m:
        with ThreadPoolExecutor(max_workers=6) as pool:
            for f in [pool.submit(client, i) for i in range(6)]:
                f.result()
    assert metrics.PROGRAM_COMPILE_DURATION.count - traced_calls == 1
    assert (cache.stats()["compiles"], cache.stats()["hits"]) == (1, 5)
    assert m.by["XLA_COMPILES"] == 1 and m.by["PROGRAM_LAUNCHES"] == 6 and m.by["PROGRAM_PARAMS_BOUND"] == 12


def test_first_call_gate_lets_one_call_in_until_one_has_returned():
    import threading
    import time

    from tidb_tpu.exec.launch import FirstCallGate

    gate, inside, peak = FirstCallGate(), [0], [0]
    start = threading.Barrier(5, timeout=30)

    def fn(i):
        inside[0] += 1
        peak[0] = max(peak[0], inside[0])
        time.sleep(0.02)   # the first call's trace and compile
        inside[0] -= 1
        return i

    def client(i, out):
        start.wait()
        out.append(gate.call(fn, (i,)))

    out: list = []
    threads = [threading.Thread(target=client, args=(i, out)) for i in range(5)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(out) == list(range(5)) and peak[0] == 1 and gate.done
    with pytest.raises(ZeroDivisionError):   # a first call that raises leaves the gate shut and the lock free
        FirstCallGate().call(lambda: 1 // 0, ())


def test_operands_are_two_typed_host_arrays_in_walk_order():
    like = func("like", BOOL, col(2, STR), lit("c0%", STR))
    cond = (func("gt", BOOL, col(0, LL), lit(5, LL)), func("lt", BOOL, col(3, DBL), lit(0.5, DBL)),
            func("le", BOOL, col(0, LL), lit(90, LL)), like)
    dag = DAGRequest((scan(3, SB_FTS + [DBL]), Selection(cond), Limit(7)), output_offsets=(0,))
    shape, key, operands = dag.parameterized()
    assert [(o.dtype, o.tolist()) for o in operands] == [(np.int64, [5, 90]), (np.float64, [0.5])]
    seats = [a for c in shape.executors[1].conditions for a in c.args if isinstance(a, Param)]
    assert [(p.lane, p.slot) for p in seats] == [("i", 0), ("f", 0), ("i", 1)]
    assert isinstance(shape.executors[1].conditions[3].args[1], Const)   # LIKE's pattern stays in the trace
    assert shape.executors[0].table_id == 0 and key == shape.fingerprint() == dag.program_key()
    assert dag.executors[0].table_id == 3 and "param" not in str(dag.fingerprint())   # the request's identity is as it was
    # no parameterisable constant: nothing is handed over, and the shape is the DAG without its table
    assert scan_sort(9, 0, 0).program_operands() == ()
    # a value outside int64 is not an operand: it stays in the trace and in the key
    big = func("lt", BOOL, col(0, LL), Const(Datum.u64(1 << 63), new_longlong(unsigned=True)))
    assert DAGRequest((scan(3, SB_FTS), Selection((big,))), output_offsets=(0,)).program_operands() == ()


# ------------------------------------------------------------------ (b)
def _sel(cond, fts=SB_FTS, out=(0,)):
    return DAGRequest((scan(5, fts), Selection((cond,))), output_offsets=out)


def _proj(expr, fts):
    return DAGRequest((scan(5, fts), Projection((expr,))), output_offsets=(0,))


STRUCTURAL = {
    "like_pattern": (sb_chunk, lambda v: _sel(func("like", BOOL, col(2, STR), lit(v, STR))), "c01%", "c02%"),
    "round_digits": (li_chunk, lambda v: _proj(func("round", new_decimal(15, 2), col(2, DEC), lit(v, LL)), LI_FTS), 0, 1),
    "date_add_unit": (li_chunk, lambda v: _proj(func("date_add", DT, col(0, DT), lit(3, LL), lit(v, STR)), LI_FTS),
                      "day", "month"),
    # a string's bytes are an operand since PR 32 (tests/test_str_operands.py); its width rung is a shape
    "string_width_rung": (sb_chunk, lambda v: _sel(func("ge", BOOL, col(2, STR), lit(v, STR))), "c010", "c0100000000000025"),
    "null_literal": (sb_chunk, lambda v: _sel(func("gt", BOOL, col(1, LL), lit(v, LL))), None, 500),
    "limit": (sb_chunk, lambda v: DAGRequest((scan(5, SB_FTS), Limit(v)), output_offsets=(0,)), 3, 9),
}


@pytest.mark.parametrize("case", list(STRUCTURAL))
def test_structural_constants_still_split_the_key(case):
    data, make, a, b = STRUCTURAL[case]
    first, second = make(a), make(b)
    assert first.program_key() != second.program_key()
    cache = ProgramCache()
    with Moved(COUNTERS) as m:
        run_single(cache, first, [data()])
        run_single(cache, second, [data()])
    assert (cache.stats()["compiles"], cache.stats()["hits"]) == (2, 0)
    assert m.by["XLA_COMPILES"] == 2
    if case == "date_add_unit":   # the amount beside the unit is an operand
        assert first.program_operands()[0].tolist() == [3]
        assert make("day").program_key() == DAGRequest(
            (scan(5, LI_FTS), Projection((func("date_add", DT, col(0, DT), lit(11, LL), lit("day", STR)),))),
            output_offsets=(0,)).program_key()


# ------------------------------------------------------------------ (c) (d)
RANGE_SQL = ("select c from {t} where id between {a} and {b}",
             "select sum(k) from {t} where id between {a} and {b}",
             "select c from {t} where id between {a} and {b} order by c",
             "select distinct c from {t} where id between {a} and {b} order by c")


@pytest.fixture()
def two_tables():
    s = Session()
    rows = {}
    for t, seed in (("sbtest1", 1), ("sbtest2", 2)):
        s.execute(f"create table {t} (id int not null auto_increment, k int not null default 0, "
                  f"c char(20) not null default '', primary key (id), key k_1 (k))")
        rng = random.Random(seed)
        rows[t] = [(i, rng.randrange(1, 1000), f"c{rng.randrange(60):03d}") for i in range(1, 401)]
        s.execute(f"insert into {t} values " + ",".join(f"({i},{k},'{c}')" for i, k, c in rows[t]))
    return s, rows


def want_range(rows, kind, a, b):
    hit = [r for r in rows if a <= r[0] <= b]
    if kind == 0:
        return sorted((r[2],) for r in hit)
    if kind == 1:
        return [(str(sum(r[1] for r in hit)),)]
    cs = sorted(r[2] for r in hit)
    return [(c,) for c in (cs if kind == 2 else sorted(set(cs)))]


def check_forty(s, rows, rng, execute):
    """40 range statements with fresh literals (no id drawn twice: a repeat
    would be the result cache's), tables drawn; every id range lies inside
    its table, so each statement decodes 100 rows."""
    for i, a in enumerate(rng.sample(range(1, 300), 40)):
        t, kind = rng.choice(list(rows)), i % 4
        got = [tuple(str(v) for v in r) for r in execute(kind, t, a, a + 99)]
        assert (sorted(got) if kind == 0 else got) == want_range(rows[t], kind, a, a + 99), (kind, t, a)


def test_forty_ranges_over_two_tables_build_one_program_per_shape(two_tables):
    s, rows = two_tables

    def execute(kind, t, a, b):
        return s.execute(RANGE_SQL[kind].format(t=t, a=a, b=b)).values()

    entries = s.store.programs.stats()["entries"]
    with Moved(COUNTERS) as first:
        check_forty(s, rows, random.Random(7), execute)
    built = first.by["PROGRAM_COMPILES"]
    # one per distinct (shape, capacity): the four pushed shapes and the root's
    # programs over them, whichever cache they live in; not one per statement
    assert 4 <= built <= 8 and first.by["XLA_COMPILES"] == built
    assert s.store.programs.stats()["entries"] - entries == 4
    assert first.by["PROGRAM_PARAMS_BOUND"] == 2 * 40   # BETWEEN's two literals, on the one pushed launch a statement
    assert first.by["COP_CACHE_HITS"] == 0              # every literal was fresh
    with Moved(COUNTERS) as again:
        check_forty(s, rows, random.Random(8), execute)
    assert again.by["PROGRAM_COMPILES"] == again.by["XLA_COMPILES"] == 0
    assert again.by["PROGRAM_LAUNCHES"] == first.by["PROGRAM_LAUNCHES"]
    assert again.by["PROGRAM_PARAMS_BOUND"] == 2 * 40
    # the result cache still keys on the values: the same statement again is a hit, another literal is not
    with Moved(COUNTERS) as repeat:
        for a in (33, 33, 34):
            s.execute(f"select sum(k) from sbtest1 where id between {a} and {a + 99}")
    assert repeat.by["COP_CACHE_HITS"] == 1 and repeat.by["PROGRAM_COMPILES"] == 0


def test_prepared_ranges_with_changing_user_variables(two_tables):
    s, rows = two_tables
    for kind, text in enumerate(RANGE_SQL):
        for t in rows:
            s.execute(f"prepare r{kind}_{t} from '{text.format(t=t, a='?', b='?')}'")

    def execute(kind, t, a, b):
        s.execute(f"set @a = {a}")
        s.execute(f"set @b = {b}")
        return s.execute(f"execute r{kind}_{t} using @a, @b").values()

    with Moved(COUNTERS) as first:
        check_forty(s, rows, random.Random(17), execute)
    built = first.by["PROGRAM_COMPILES"]
    assert 4 <= built <= 8 and first.by["XLA_COMPILES"] == built
    assert first.by["PROGRAM_PARAMS_BOUND"] == 2 * 40 and first.by["COP_CACHE_HITS"] == 0
    with Moved(COUNTERS) as again:
        check_forty(s, rows, random.Random(18), execute)
    assert again.by["PROGRAM_COMPILES"] == again.by["XLA_COMPILES"] == 0
    assert again.by["PROGRAM_PARAMS_BOUND"] == 2 * 40


# ------------------------------------------------------------------ (e)
Q6 = """select sum(l_extendedprice * l_discount) as revenue from lineitem
 where l_shipdate >= date '{date}' and l_shipdate < date '{date}' + interval '1' year
   and l_discount between {disc} - 0.01 and {disc} + 0.01 and l_quantity < {qty}"""
Q1 = """select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, sum(l_extendedprice) as sum_base_price,
   sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
   sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, count(*) as count_order
 from lineitem where l_shipdate <= date '1998-12-01' - interval '{delta}' day
 group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"""
D = decimal.Decimal


@pytest.fixture(scope="module")
def lineitem():
    s = Session()
    s.execute("""create table lineitem (l_orderkey bigint not null, l_linenumber bigint not null,
        l_quantity decimal(15,2) not null, l_extendedprice decimal(15,2) not null, l_discount decimal(15,2) not null,
        l_tax decimal(15,2) not null, l_returnflag char(1) not null, l_linestatus char(1) not null,
        l_shipdate date not null, primary key (l_orderkey, l_linenumber))""")
    rng = np.random.default_rng(11)
    start = np.datetime64("1992-01-02")
    rows = []
    for i in range(300):
        ship = start + int(rng.integers(0, 2520))   # 1992-01-02 .. 1998-11-26, as dbgen's SHIPDATE spans
        rows.append((i, 1, D(int(rng.integers(1, 51))), D(int(rng.integers(90000, 9000000))).scaleb(-2),
                     D(int(rng.integers(0, 11))).scaleb(-2), D(int(rng.integers(0, 9))).scaleb(-2),
                     "ANR"[int(rng.integers(3))], "FO"[int(rng.integers(2))], ship))
    s.execute("insert into lineitem values " + ",".join(
        f"({o},{n},{q},{p},{d},{x},'{f}','{st}','{sh}')" for o, n, q, p, d, x, f, st, sh in rows))
    s.execute("analyze table lineitem")
    return s, rows


def ref_q6(rows, date, disc, qty):
    lo = np.datetime64(date)
    hi = np.datetime64(f"{int(date[:4]) + 1}{date[4:]}")
    return sum((r[3] * r[4] for r in rows
                if lo <= r[8] < hi and D(disc) - D("0.01") <= r[4] <= D(disc) + D("0.01") and r[2] < qty), D(0))


def ref_q1(rows, delta):
    cut = np.datetime64("1998-12-01") - delta
    out = []
    for f in "ANR":
        for st in "FO":
            g = [r for r in rows if r[6] == f and r[7] == st and r[8] <= cut]
            if g:
                out.append((f, st, sum(r[2] for r in g), sum(r[3] for r in g), sum(r[3] * (1 - r[4]) for r in g),
                            sum(r[3] * (1 - r[4]) * (1 + r[5]) for r in g), len(g)))
    return out


def test_tpch_q6_q1_with_drawn_parameters_build_nothing_the_second_time(lineitem):
    s, rows = lineitem
    rng = random.Random(23)   # TPC-H 2.4.6.3 / 2.4.1.3: DATE, DISCOUNT, QUANTITY, DELTA drawn per statement

    def draw():
        return {"date": f"{rng.randrange(1993, 1998)}-01-01", "disc": f"0.0{rng.randrange(2, 10)}",
                "qty": rng.choice((24, 25)), "delta": rng.randrange(60, 121)}

    def run(p):
        (got,) = s.execute(Q6.format(**p)).values()
        want = ref_q6(rows, p["date"], p["disc"], p["qty"])
        assert (D(str(got[0])) if got[0] is not None else D(0)) == want, (p, got, want)
        got = s.execute(Q1.format(**p)).values()
        want = ref_q1(rows, p["delta"])
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert (str(g[0]), str(g[1])) == w[:2] and [D(str(x)) for x in g[2:6]] == list(w[2:6]) and int(str(g[6])) == w[6]

    first = second = draw()
    while any(first[k] == second[k] for k in first):
        second = draw()
    with Moved(COUNTERS) as one:
        run(first)
    assert one.by["PROGRAM_COMPILES"] >= 2 and one.by["PROGRAM_PARAMS_BOUND"] > 0
    with Moved(COUNTERS) as two:
        run(second)
    assert two.by["PROGRAM_COMPILES"] == two.by["XLA_COMPILES"] == 0, two.by
    assert two.by["PROGRAM_LAUNCHES"] == one.by["PROGRAM_LAUNCHES"] and two.by["COP_CACHE_HITS"] == 0
