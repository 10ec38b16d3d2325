"""Chip smoke: the served SQL path, once, on the chip.

One process, one chip, the entry points a user calls: a `MySQLServer`, a
wire client, `LOAD DATA INFILE`, `ANALYZE`, TPC-H Q6/Q1/Q3 plus a point
get, an indexed range and an update/read-back from the row store, then Q6
and Q1 again from the columnar replica.  Every answer is compared with a
plain numpy computation over the generated arrays (scaled int64 for the
decimal sums), never with the engine's own oracle.  The first failed
phase ends the run with a non-zero exit code.

    python chip_smoke.py [--seed N] [--rows N] [--chips 4]

Each phase and each statement prints one JSON line; the last line of
stdout is `{"ok": true, "device": {...}}`.  With `--chips 4` the script
runs the mesh/MPP statements and their single-device comparison and no
other phase.  No number printed here is a benchmark.
"""

from __future__ import annotations

import argparse
import collections
import decimal
import functools
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(HERE, ".xla_cache"))

import numpy as np  # noqa: E402

# lineitem rows; orders = rows/4, customer = rows/32 (TPC-H's ratios, SF~0.044).
# The host bounds it, not the chip: LOAD DATA, ANALYZE and the replica's
# backfill are per-row Python, and a cold run must fit the smoke's time limit
DEFAULT_ROWS = 1 << 18
OUT_DIR = os.path.join(HERE, "chiprun_out")
WIRE_TIMEOUT = 1100.0  # a first execution compiles; the wire must wait

D = decimal.Decimal
EPOCH = np.datetime64("1992-01-01")
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
WORDS = ("furiously quickly carefully blithely slyly final regular special express "
         "pending ironic bold even unusual silent packages deposits requests accounts "
         "theodolites pinto beans foxes ideas instructions dependencies platelets").split()

DDL = [
    """create table customer (
        c_custkey bigint not null, c_name varchar(25) not null,
        c_address varchar(40) not null, c_nationkey bigint not null,
        c_phone char(15) not null, c_acctbal decimal(15,2) not null,
        c_mktsegment char(10) not null, c_comment varchar(117) not null,
        primary key (c_custkey))""",
    """create table orders (
        o_orderkey bigint not null, o_custkey bigint not null,
        o_orderstatus char(1) not null, o_totalprice decimal(15,2) not null,
        o_orderdate date not null, o_orderpriority char(15) not null,
        o_clerk char(15) not null, o_shippriority bigint not null,
        o_comment varchar(79) not null,
        primary key (o_orderkey), key idx_orderdate (o_orderdate))""",
    """create table lineitem (
        l_orderkey bigint not null, l_partkey bigint not null,
        l_suppkey bigint not null, l_linenumber bigint not null,
        l_quantity decimal(15,2) not null, l_extendedprice decimal(15,2) not null,
        l_discount decimal(15,2) not null, l_tax decimal(15,2) not null,
        l_returnflag char(1) not null, l_linestatus char(1) not null,
        l_shipdate date not null, l_commitdate date not null,
        l_receiptdate date not null, l_shipinstruct char(25) not null,
        l_shipmode char(10) not null, l_comment varchar(44) not null,
        primary key (l_orderkey, l_linenumber))""",
]

Q6 = """select sum(l_extendedprice * l_discount) as revenue from lineitem
 where l_shipdate >= date '1994-01-01'
   and l_shipdate < date '1994-01-01' + interval '1' year
   and l_discount between 0.06 - 0.01 and 0.06 + 0.01 and l_quantity < 24"""

Q1 = """select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
   sum(l_extendedprice) as sum_base_price,
   sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
   sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
   avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
   avg(l_discount) as avg_disc, count(*) as count_order
 from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
 group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"""

Q3 = """select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
   o_orderdate, o_shippriority
 from customer, orders, lineitem
 where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
   and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
   and l_shipdate > date '1995-03-15'
 group by l_orderkey, o_orderdate, o_shippriority
 order by revenue desc, o_orderdate limit 10"""

RANGE_SQL = """select o_orderkey, o_orderdate from orders
 where o_orderdate >= '1995-03-01' and o_orderdate < '1995-03-08'
 order by o_orderdate, o_orderkey limit 100"""

PALLAS_KERNELS = {
    "dense_pallas": ("group_aggregate_dense_pallas",),
    "joinscan": ("postsort_segscan", "membership_segscan"),
    "join_pallas": ("probe_tables_pallas",),
}
TRACED: collections.Counter = collections.Counter()
COLLECTIVES = ("all_reduce", "all_gather", "all_to_all", "reduce_scatter", "collective_permute")
MESH_PROGRAMS: list = []  # one record per mesh program, made at its first launch


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


# --------------------------------------------------------------------------
# data: dbgen's value rules for every column the statements read
# --------------------------------------------------------------------------

def _texts(rng, n: int, max_len: int) -> list:
    """Random comment text, at most `max_len` characters."""
    picks = rng.integers(0, len(WORDS), size=(n, 6))
    return [" ".join(WORDS[j] for j in row)[:max_len].rstrip() for row in picks]


def _cents(a) -> list:
    """Scaled-int64 cents -> decimal(15,2) text."""
    out = []
    for v in a.tolist():
        s, v = ("-", -v) if v < 0 else ("", v)
        out.append(f"{s}{v // 100}.{v % 100:02d}")
    return out


def generate(rows: int, seed: int) -> dict:
    """TPC-H lineitem/orders/customer as numpy arrays (money in cents,
    dates as days since 1992-01-01)."""
    rng = np.random.default_rng(seed)
    n_ord, n_cust = max(rows // 4, 1), max(rows // 32, 3)

    # customer
    c = {
        "custkey": np.arange(1, n_cust + 1, dtype=np.int64),
        "nationkey": rng.integers(0, 25, n_cust),
        "acctbal": rng.integers(-99999, 1000000, n_cust),
        "segment": rng.integers(0, len(SEGMENTS), n_cust),
    }

    # orders: sparse keys (dbgen uses 8 of every 32), custkey never a
    # multiple of 3, 1..7 lines each summing to `rows`
    i = np.arange(n_ord, dtype=np.int64)
    okey = (i // 8) * 32 + i % 8 + 1
    ocust = rng.integers(1, n_cust + 1, n_ord)
    ocust = np.where(ocust % 3 == 0, ocust - 1, ocust)
    ocust = np.where(ocust < 1, 1, ocust)
    odate = rng.integers(0, (np.datetime64("1998-08-02") - EPOCH).astype(int) + 1, n_ord)
    nlines = rng.integers(1, 8, n_ord)
    while (diff := rows - int(nlines.sum())) != 0:
        room = np.flatnonzero(nlines < 7 if diff > 0 else nlines > 1)
        pick = rng.choice(room, size=min(abs(diff), len(room)), replace=False)
        nlines[pick] += 1 if diff > 0 else -1

    # lineitem
    oidx = np.repeat(i, nlines)
    first = np.cumsum(nlines) - nlines
    l = {
        "oidx": oidx,
        "orderkey": okey[oidx],
        "linenumber": np.arange(rows, dtype=np.int64) - first[oidx] + 1,
        "partkey": rng.integers(1, max(rows // 30, 200) + 1, rows),
        "quantity": rng.integers(1, 51, rows),
        "discount": rng.integers(0, 11, rows),
        "tax": rng.integers(0, 9, rows),
    }
    l["suppkey"] = l["partkey"] % max(rows // 600, 10) + 1
    pk = l["partkey"]
    retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)  # cents
    l["extendedprice"] = l["quantity"] * retail
    l["shipdate"] = odate[oidx] + rng.integers(1, 122, rows)
    l["commitdate"] = odate[oidx] + rng.integers(30, 91, rows)
    l["receiptdate"] = l["shipdate"] + rng.integers(1, 31, rows)
    current = (np.datetime64("1995-06-17") - EPOCH).astype(int)
    l["returnflag"] = np.where(
        l["receiptdate"] <= current, np.where(rng.integers(0, 2, rows) == 0, "R", "A"), "N")
    l["linestatus"] = np.where(l["shipdate"] > current, "O", "F")
    l["shipinstruct"] = rng.integers(0, len(INSTRUCTS), rows)
    l["shipmode"] = rng.integers(0, len(MODES), rows)

    n_open = np.bincount(oidx, weights=(l["linestatus"] == "O"), minlength=n_ord).astype(np.int64)
    total = np.zeros(n_ord, np.int64)
    np.add.at(total, oidx, l["extendedprice"] * (100 + l["tax"]) * (100 - l["discount"]) // 10000)
    o = {
        "orderkey": okey, "custkey": ocust, "orderdate": odate, "totalprice": total,
        "status": np.where(n_open == nlines, "O", np.where(n_open == 0, "F", "P")),
        "priority": rng.integers(0, len(PRIORITIES), n_ord),
        "clerk": rng.integers(1, max(rows // 6000, 1) + 1, n_ord),
        "shippriority": np.zeros(n_ord, np.int64),
    }
    return {"customer": c, "orders": o, "lineitem": l, "rng": rng}


def _dates(days) -> list:
    return np.datetime_as_string(EPOCH + days.astype("timedelta64[D]")).tolist()


def write_files(data: dict, out_dir: str) -> dict:
    """One '|'-separated file per table, every column of the DDL."""
    rng = data["rng"]
    c, o, l = data["customer"], data["orders"], data["lineitem"]
    os.makedirs(out_dir, exist_ok=True)
    cols = {
        "customer": [
            c["custkey"].tolist(),
            [f"Customer#{k:09d}" for k in c["custkey"].tolist()],
            _texts(rng, len(c["custkey"]), 40),
            c["nationkey"].tolist(),
            [f"{10 + n}-{k % 900 + 100}-{k % 800 + 100}-{k % 9000 + 1000}"
             for n, k in zip(c["nationkey"].tolist(), c["custkey"].tolist())],
            _cents(c["acctbal"]),
            [SEGMENTS[s] for s in c["segment"].tolist()],
            _texts(rng, len(c["custkey"]), 117),
        ],
        "orders": [
            o["orderkey"].tolist(), o["custkey"].tolist(), o["status"].tolist(),
            _cents(o["totalprice"]), _dates(o["orderdate"]),
            [PRIORITIES[p] for p in o["priority"].tolist()],
            [f"Clerk#{k:09d}" for k in o["clerk"].tolist()],
            o["shippriority"].tolist(),
            _texts(rng, len(o["orderkey"]), 79),
        ],
        "lineitem": [
            l["orderkey"].tolist(), l["partkey"].tolist(), l["suppkey"].tolist(),
            l["linenumber"].tolist(),
            [f"{q}.00" for q in l["quantity"].tolist()],
            _cents(l["extendedprice"]),
            [f"0.{d:02d}" for d in l["discount"].tolist()],
            [f"0.{t:02d}" for t in l["tax"].tolist()],
            l["returnflag"].tolist(), l["linestatus"].tolist(),
            _dates(l["shipdate"]), _dates(l["commitdate"]), _dates(l["receiptdate"]),
            [INSTRUCTS[s] for s in l["shipinstruct"].tolist()],
            [MODES[m] for m in l["shipmode"].tolist()],
            _texts(rng, len(l["orderkey"]), 44),
        ],
    }
    paths = {}
    for table, columns in cols.items():
        paths[table] = os.path.join(out_dir, f"{table}.tbl")
        with open(paths[table], "w") as f:
            f.writelines("|".join(map(str, row)) + "\n" for row in zip(*columns))
    return paths


# --------------------------------------------------------------------------
# the plain reference: numpy over the generated arrays, exact
# --------------------------------------------------------------------------

def _day(s: str) -> int:
    return int((np.datetime64(s) - EPOCH).astype(int))


def _scaled(v: int, scale: int) -> D:
    return D(int(v)).scaleb(-scale)


def ref_q6(data) -> D:
    l = data["lineitem"]
    m = ((l["shipdate"] >= _day("1994-01-01")) & (l["shipdate"] < _day("1995-01-01"))
         & (l["discount"] >= 5) & (l["discount"] <= 7) & (l["quantity"] < 24))
    return _scaled((l["extendedprice"][m] * l["discount"][m]).sum(), 4)


def ref_q1(data) -> list:
    """[(flag, status, sum_qty, sum_price, sum_disc_price, sum_charge,
    (avg numerators...), count)] in key order; averages stay exact
    fractions (sum, count) for the caller to round at the engine's scale."""
    l = data["lineitem"]
    m = l["shipdate"] <= _day("1998-09-02")
    disc_price = l["extendedprice"] * (100 - l["discount"])
    charge = disc_price * (100 + l["tax"])
    out = []
    for flag in "ANR":
        for status in "FO":
            g = m & (l["returnflag"] == flag) & (l["linestatus"] == status)
            n = int(g.sum())
            if not n:
                continue
            qty, price, disc = (int(l[k][g].sum()) for k in ("quantity", "extendedprice", "discount"))
            out.append((flag, status, D(qty), _scaled(price, 2),
                        _scaled(disc_price[g].sum(), 4), _scaled(charge[g].sum(), 6),
                        (D(qty), n), (_scaled(price, 2), n), (_scaled(disc, 2), n), n))
    return out


def ref_q3(data) -> dict:
    """orderkey -> (revenue, orderdate text, shippriority) for every
    qualifying group; the caller applies ORDER BY ... LIMIT 10."""
    c, o, l = data["customer"], data["orders"], data["lineitem"]
    building = c["segment"] == SEGMENTS.index("BUILDING")
    o_ok = (o["orderdate"] < _day("1995-03-15")) & building[o["custkey"] - 1]
    l_ok = (l["shipdate"] > _day("1995-03-15")) & o_ok[l["oidx"]]
    rev = np.zeros(len(o["orderkey"]), np.int64)
    np.add.at(rev, l["oidx"][l_ok], (l["extendedprice"] * (100 - l["discount"]))[l_ok])
    hit = np.zeros(len(o["orderkey"]), bool)
    hit[l["oidx"][l_ok]] = True
    dates = _dates(o["orderdate"][hit])
    return {
        int(k): (_scaled(r, 4), d, int(p))
        for k, r, d, p in zip(o["orderkey"][hit], rev[hit], dates, o["shippriority"][hit])
    }


def check_q6(rows, data) -> dict:
    assert len(rows) == 1, rows
    want = ref_q6(data)
    assert D(rows[0][0]) == want, (rows, want)
    return {"revenue": str(want)}


def _round_like(frac, text: str) -> D:
    """Exact sum/count rounded half-up to the scale the engine printed."""
    s, n = frac
    scale = len(text.partition(".")[2])
    assert scale >= 4, f"avg printed at scale {scale}: {text!r}"
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        return (s / n).quantize(D(1).scaleb(-scale), rounding=decimal.ROUND_HALF_UP)


def check_q1(rows, data) -> dict:
    want = ref_q1(data)
    assert len(rows) == len(want), (rows, want)
    for got, w in zip(rows, want):
        assert (got[0], got[1]) == (w[0], w[1]), (got, w)
        for j in (2, 3, 4, 5):
            assert D(got[j]) == w[j], (j, got, w)
        for j in (6, 7, 8):
            assert D(got[j]) == _round_like(w[j], got[j]), (j, got, w)
        assert int(got[9]) == w[9], (got, w)
    return {"groups": len(want)}


def check_q3(rows, data) -> dict:
    ref = ref_q3(data)
    top = sorted(ref.values(), key=lambda v: (-v[0], v[1]))[:10]
    assert len(rows) == len(top), (len(rows), len(top))
    for got, w in zip(rows, top):
        # ties on (revenue, orderdate) may order either way: the sort keys
        # must match position by position, the row itself its own group
        assert (D(got[1]), got[2]) == (w[0], w[1]), (got, w)
        assert ref[int(got[0])] == (D(got[1]), got[2], int(got[3])), (got, ref[int(got[0])])
    return {"groups": len(ref), "returned": len(rows)}


# --------------------------------------------------------------------------
# harness
# --------------------------------------------------------------------------

def watch_pallas_kernels() -> None:
    """Counting wrappers on the four Pallas entry points.  Their callers
    import them at call time, so a wrapper on the module attribute sees
    every trace; ProgramCache needs no new API."""
    import importlib

    for mod_name, fns in PALLAS_KERNELS.items():
        mod = importlib.import_module(f"tidb_tpu.ops.{mod_name}")
        for name in fns:
            fn = getattr(mod, name)

            def counted(*a, _fn=fn, _name=name, **k):
                TRACED[_name] += 1
                return _fn(*a, **k)

            setattr(mod, name, functools.wraps(fn)(counted))


def watch_mesh_programs() -> None:
    """A program built for the mesh records, at its first launch, the
    collectives in its lowered text and how many devices its outputs
    live on.  build_program is looked up in its module at call time."""
    import jax

    from tidb_tpu.exec import builder

    build = builder.build_program

    def watched(*a, **k):
        prog = build(*a, **k)
        if k.get("mesh_lanes") is None:
            return prog
        fn = prog.outputs.fn

        def launch(*args):
            out = fn(*args)
            if not any(r["fn"] is fn for r in MESH_PROGRAMS):
                text = fn.lower(*args).as_text()
                MESH_PROGRAMS.append({
                    "fn": fn, "kind": k.get("mesh_kind"), "lanes": k["mesh_lanes"],
                    "collectives": [c for c in COLLECTIVES if c in text],
                    "devices": max(len(x.sharding.device_set) for x in jax.tree.leaves(out)),
                })
            return out

        prog.outputs.fn = launch
        return prog

    builder.build_program = watched


def require_pallas(stmt: str, traced: dict) -> None:
    """Q1 and Q3 must have traced a Pallas kernel, not the XLA branch
    beside it."""
    assert traced, f"{stmt}: no Pallas kernel was traced into its program"


class Probe:
    """Metric deltas around a block: compile seconds (program calls that
    traced or compiled, whole; XLA's backend compiles alone), launches, and
    the counters that must not move."""

    def __init__(self):
        from tidb_tpu.util import metrics

        self.m = metrics
        self.t0 = time.perf_counter()
        self.compile0 = metrics.PROGRAM_COMPILE_DURATION.sum
        self.launch0 = metrics.PROGRAM_LAUNCHES.value
        self.oracle0 = metrics.COP_FALLBACKS.value
        self.traced0 = collections.Counter(TRACED)
        self.xla0 = metrics.XLA_BACKEND_COMPILE_NS.value

    def done(self) -> dict:
        m = self.m
        return {
            "wall_s": round(time.perf_counter() - self.t0, 3),
            "compile_s": round(m.PROGRAM_COMPILE_DURATION.sum - self.compile0, 3),
            "xla_compile_s": round((m.XLA_BACKEND_COMPILE_NS.value - self.xla0) / 1e9, 3),
            "launches": m.PROGRAM_LAUNCHES.value - self.launch0,
            "oracle_fallbacks": m.COP_FALLBACKS.value - self.oracle0,
            "kernels": dict(TRACED - self.traced0),
        }


def prepare_engine() -> None:
    """Before the first statement: nothing stale, nothing swallowed."""
    from tidb_tpu import native
    from tidb_tpu.util import failpoint

    # the chip tool copies the tree as it stands, librowcodec.so included:
    # what runs is built from src/rowcodec.cpp on this machine
    shutil.rmtree(os.path.join(os.path.dirname(native.__file__), "_build"), ignore_errors=True)
    # a device error raises instead of turning into CopResponse.other_error
    failpoint.enable("cop-debug-raise")
    watch_pallas_kernels()


def run_twice(name: str, run, check, data, want_pallas: bool = False) -> None:
    """First execution may compile; the second must not."""
    p = Probe()
    first = check(run(0), data)
    a = p.done()
    p = Probe()
    check(run(1), data)
    b = p.done()
    assert a["oracle_fallbacks"] == 0 and b["oracle_fallbacks"] == 0, (name, a, b)
    assert b["compile_s"] == 0 and b["xla_compile_s"] == 0 and not b["kernels"], (
        f"{name}: second execution compiled: {b}")
    if want_pallas:
        require_pallas(name, a["kernels"])
    emit(stmt=name, first_s=a["wall_s"], second_s=b["wall_s"], compile_s=a["compile_s"],
         xla_compile_s=a["xla_compile_s"], launches=a["launches"] + b["launches"],
         kernels=a["kernels"], **first)


class Ctx:
    """What the phases share: the server, two connections, the data."""

    def __init__(self, rows: int, seed: int):
        self.rows, self.seed = rows, seed
        self.srv = self.client = self.client2 = self.data = None

    def connect(self):
        from tidb_tpu.server import MiniClient

        return MiniClient(self.srv.host, self.srv.port, timeout=WIRE_TIMEOUT)

    def runner(self, sql: str):
        """run_twice's `run`: the statement's rows over the first connection."""
        return lambda _i: self.client.query(sql)[1]

    def close(self) -> None:
        for c in (self.client, self.client2):
            if c is not None:
                c.close()
        if self.srv is not None:
            self.srv.close()


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_device(want_count: int) -> dict:
    import jax

    from tidb_tpu.ops.dense_pallas import pallas_mode

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if dev["platform"] != "tpu":
        raise RuntimeError(f"chip_smoke needs a TPU; JAX reports {dev}")
    assert dev["count"] == want_count, f"want {want_count} device(s), JAX reports {dev}"
    assert pallas_mode() == "tpu", pallas_mode()
    emit(phase="device", pallas_mode=pallas_mode(), **dev)
    return dev


def phase_load(ctx: Ctx) -> None:
    from tidb_tpu import native
    from tidb_tpu.server import MySQLServer

    p = Probe()
    t0 = time.perf_counter()
    ctx.data = generate(ctx.rows, ctx.seed)
    data_dir = os.path.join(OUT_DIR, f"chip_smoke_data_{os.getpid()}")
    paths = write_files(ctx.data, data_dir)
    gen_s = time.perf_counter() - t0

    ctx.srv = MySQLServer(port=0)
    ctx.srv.start_background()
    ctx.client, ctx.client2 = ctx.connect(), ctx.connect()
    loaded = {}
    t0 = time.perf_counter()
    for ddl in DDL:
        ctx.client.query(ddl)
    for table in ("customer", "orders", "lineitem"):
        n = ctx.client.query(
            f"load data infile '{paths[table]}' into table {table} fields terminated by '|'")
        want = len(ctx.data[table]["orderkey" if table != "customer" else "custkey"])
        assert n == want, (table, n, want)
        loaded[table] = n
    load_s = time.perf_counter() - t0
    shutil.rmtree(data_dir)
    t0 = time.perf_counter()
    ctx.client.query("analyze table customer, orders, lineitem")
    analyze_s = time.perf_counter() - t0
    _, counts = ctx.client.query("select count(*) from lineitem")
    assert int(counts[0][0]) == ctx.rows, counts
    emit(phase="load", rows=loaded, generate_s=round(gen_s, 3), load_s=round(load_s, 3),
         load_rows_per_s=round(sum(loaded.values()) / load_s, 1), analyze_s=round(analyze_s, 3),
         native=native.available(), **p.done())


def phase_row_store(ctx: Ctx) -> None:
    c, data, q = ctx.client, ctx.data, ctx.runner
    p = Probe()
    run_twice("q6", q(Q6), check_q6, data)
    run_twice("q1", q(Q1), check_q1, data, want_pallas=True)
    run_twice("q3", q(Q3), check_q3, data, want_pallas=True)

    o = data["orders"]
    k = len(o["orderkey"]) // 2

    def check_point(rows, _d):
        want = [[str(o["orderkey"][k]), str(o["custkey"][k]), _cents(o["totalprice"][k:k + 1])[0],
                 _dates(o["orderdate"][k:k + 1])[0]]]
        assert rows == want, (rows, want)
        return {"rows": 1}

    run_twice("point_get", q("select o_orderkey, o_custkey, o_totalprice, o_orderdate "
                             f"from orders where o_orderkey = {o['orderkey'][k]}"),
              check_point, data)

    def check_range(rows, _d):
        m = (o["orderdate"] >= _day("1995-03-01")) & (o["orderdate"] < _day("1995-03-08"))
        order = np.lexsort((o["orderkey"][m], o["orderdate"][m]))[:100]
        want = [[str(a), b] for a, b in
                zip(o["orderkey"][m][order].tolist(), _dates(o["orderdate"][m][order]))]
        assert rows == want, (rows[:3], want[:3])
        return {"rows": len(want)}

    run_twice("index_range_limit", q(RANGE_SQL), check_range, data)

    def update_read(i):
        text = f"chip smoke seed {ctx.seed} pass {i}"
        n = c.query(f"update orders set o_comment = '{text}' where o_orderkey = {o['orderkey'][k]}")
        assert n == 1, n
        got = ctx.client2.query(f"select o_comment from orders where o_orderkey = {o['orderkey'][k]}")[1]
        assert got == [[text]], (got, text)
        return got

    run_twice("update_read_back", update_read, lambda _r, _d: {"rows": 1}, data)
    emit(phase="row_store", **p.done())


def phase_columnar(ctx: Ctx) -> None:
    from tidb_tpu.util import metrics

    c, data = ctx.client, ctx.data
    p = Probe()
    c.query("alter table lineitem set tiflash replica 1")
    ticks = 0
    while True:
        ctx.srv.store.pd.tick()
        ticks += 1
        view = ctx.srv.store.columnar.views()[0]
        if (view["state"] == "normal" and view["stable_rows"] == ctx.rows
                and view["stable_chunks"] == view["pids"] and not view["delta_rows"]):
            break
        assert ticks < 8, f"columnar replica not available after {ticks} ticks: {view}"
    fill_s = time.perf_counter() - p.t0
    scans0, fallbacks0 = metrics.COLUMNAR_SCANS.value, metrics.COLUMNAR_FALLBACKS.value
    run_twice("columnar_q6", ctx.runner(Q6), check_q6, data)
    run_twice("columnar_q1", ctx.runner(Q1), check_q1, data, want_pallas=True)
    scans = metrics.COLUMNAR_SCANS.value - scans0
    fallbacks = metrics.COLUMNAR_FALLBACKS.value - fallbacks0
    assert scans >= 4 and fallbacks == 0, (scans, fallbacks)
    emit(phase="columnar", replica_fill_s=round(fill_s, 3), ticks=ticks,
         columnar_scans=scans, columnar_fallbacks=fallbacks, **p.done())


def phase_mesh(ctx: Ctx, n_devices: int) -> None:
    """Q6, Q1 and Q3 with the defaults — mesh and MPP tiers on — and again
    with both off, which is what they are compared with.  Both cross-chip
    tiers degrade in silence, so a right answer proves nothing alone: the
    counters, the devices and the collectives are asserted too."""
    from tidb_tpu.util import metrics as m

    c, data, store = ctx.client, ctx.data, ctx.srv.store
    # the mesh tier shards REGIONS over the devices: let PD's split checker
    # cut the loaded tables (64Ki keys / 4 MiB a region) until it is done
    ticks, n_regions = 0, len(store.cluster.regions())
    while True:
        store.pd.tick()
        ticks += 1
        now = len(store.cluster.regions())
        if now == n_regions:
            break
        n_regions = now
        assert ticks < 16, f"regions still splitting after {ticks} ticks: {now}"
    assert n_regions >= n_devices, f"{n_regions} region(s) cannot span {n_devices} devices"
    emit(phase="split", ticks=ticks, regions=n_regions)

    counters = ("MPP_SELECTS", "MESH_COP_BATCHES", "MPP_FALLBACKS", "MESH_COP_FALLBACKS")
    checks = (("q6", Q6, check_q6), ("q1", Q1, check_q1), ("q3", Q3, check_q3))
    for mode in ("mesh", "single_device"):
        if mode == "single_device":
            c.query("set tidb_enable_tpu_mesh = OFF")
            c.query("set tidb_allow_mpp = OFF")
        for name, sql, check in checks:
            p = Probe()
            before = {k: getattr(m, k).value for k in counters}
            n_progs = len(MESH_PROGRAMS)
            result = check(c.query(sql)[1], data)
            moved = {k: getattr(m, k).value - before[k] for k in counters}
            progs = [{k: v for k, v in r.items() if k != "fn"} for r in MESH_PROGRAMS[n_progs:]]
            line = p.done()
            assert line["oracle_fallbacks"] == 0, (name, line)
            if mode == "mesh":
                assert moved["MPP_SELECTS"] + moved["MESH_COP_BATCHES"] > 0, (name, moved)
                assert moved["MPP_FALLBACKS"] == 0 and moved["MESH_COP_FALLBACKS"] == 0, (name, moved)
                for r in progs:
                    assert r["collectives"] and r["devices"] == n_devices, (name, r)
                assert progs or moved["MPP_SELECTS"], f"{name}: no mesh program was launched"
            else:
                assert not any(moved.values()) and not progs, (name, moved, progs)
            emit(stmt=f"{mode}_{name}", tiers=moved, mesh_programs=progs, **line, **result)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS, help="lineitem rows")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    dev = phase_device(args.chips)
    prepare_engine()
    ctx = Ctx(args.rows, args.seed)
    try:
        phase_load(ctx)
        if args.chips == 1:
            phase_row_store(ctx)
            phase_columnar(ctx)
        else:  # the cross-chip tiers and what they are compared with, nothing else
            watch_mesh_programs()
            phase_mesh(ctx, args.chips)
    finally:
        ctx.close()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
