"""TPC-H lineitem/orders/customer at the sizes of `config.json`: data from
the seed by dbgen's value rules, the load over the wire, and the plain
reference: numpy over the generated arrays, decimal sums in scaled int64.

Generator, DDL, statement texts and references are copies of
`chip_smoke.py`'s (proved on the chip in PR 22), the references
generalised over the statements' literals.  Nothing here imports the
program: the harness hands `load` a connected wire client.
"""

from __future__ import annotations

import datetime
import decimal
import os
import shutil
import tempfile
import time

import numpy as np

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

# logical bytes a statement has to read from HBM, per lineitem row: the
# columns it names at the widths of the DDL (date 4, decimal(15,2) 8,
# char(1) 1), whatever program implements it
SCAN_BYTES_PER_ROW = {
    "q6": 4 + 8 + 8 + 8,               # shipdate, discount, quantity, extendedprice
    "q1": 4 + 1 + 1 + 8 + 8 + 8 + 8,   # shipdate, flag, status, quantity, price, discount, tax
}


# --------------------------------------------------------------------------
# data: dbgen's value rules for every column the statements read
# --------------------------------------------------------------------------

def _texts(rng, n: int, max_len: int) -> list:
    picks = rng.integers(0, len(WORDS), size=(n, 6))
    return [" ".join(WORDS[j] for j in row)[:max_len].rstrip() for row in picks]


def _cents(a) -> list:
    """Scaled-int64 cents -> decimal(15,2) text."""
    out = []
    for v in a.tolist():
        s, v = ("-", -v) if v < 0 else ("", v)
        out.append(f"{s}{v // 100}.{v % 100:02d}")
    return out


def _dates(days) -> list:
    return np.datetime_as_string(EPOCH + days.astype("timedelta64[D]")).tolist()


def generate(sizes: dict, seed: int) -> dict:
    """The three tables as numpy arrays (money in cents, dates as days
    since 1992-01-01)."""
    rows = int(sizes["lineitem_rows"])
    rng = np.random.default_rng(seed)
    # the spec's ratios: ORDERS 1.5M x SF with 1-7 lines each (4 on average),
    # CUSTOMER 150K x SF, so ten orders a customer
    n_ord = max(rows // 4, 1)
    n_cust = max(n_ord // 10, 3)

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


def write_files(data: dict, out_dir: str) -> dict:
    """One '|'-separated file per table, every column of the DDL."""
    rng = data["rng"]
    c, o, l = data["customer"], data["orders"], data["lineitem"]
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


def load(client, data: dict, config: dict, emit) -> dict:
    """DDL, `LOAD DATA INFILE` of every column over the wire, and the
    configuration's ANALYZE.  Returns {table: rows}."""
    t0 = time.perf_counter()
    data_dir = tempfile.mkdtemp(prefix="tpch_tbl_")  # under TMPDIR, gone again below
    try:
        paths = write_files(data, data_dir)
        write_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for ddl in DDL:
            client.query(ddl)
        loaded = {}
        for table in ("customer", "orders", "lineitem"):
            n = client.query(
                f"load data infile '{paths[table]}' into table {table} fields terminated by '|'")
            want = len(data[table]["custkey" if table == "customer" else "orderkey"])
            if n != want:
                raise RuntimeError(f"load {table}: {n} rows acknowledged, {want} sent")
            loaded[table] = n
        load_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    t0 = time.perf_counter()
    client.query(config["analyze"])
    analyze_s = time.perf_counter() - t0
    emit(phase="load", rows=loaded, write_files_s=round(write_s, 3), load_s=round(load_s, 3),
         load_rows_per_s=round(sum(loaded.values()) / load_s, 1), analyze_s=round(analyze_s, 3))
    return loaded


def replica_tables(config: dict) -> dict:
    """{table: rows} that the columnar replica has to hold before a
    columnar read is timed."""
    table = config["columnar_replica"]["table"]
    return {table: int(config[f"{table}_rows"])}


# --------------------------------------------------------------------------
# the plain reference: numpy over the generated arrays, exact
# --------------------------------------------------------------------------

def _day(s: str) -> int:
    return int((np.datetime64(s) - EPOCH).astype(int))


def _scaled(v, scale: int) -> D:
    return D(int(v)).scaleb(-scale)


def _cents_of(text) -> int:
    return int((D(str(text)) * 100).to_integral_exact())


def _q6_mask(l, p):
    lo = datetime.date.fromisoformat(p["date"])
    hi = lo.replace(year=lo.year + 1)
    disc = _cents_of(p["discount"])
    return ((l["shipdate"] >= _day(lo.isoformat())) & (l["shipdate"] < _day(hi.isoformat()))
            & (l["discount"] >= disc - 1) & (l["discount"] <= disc + 1)
            & (l["quantity"] < int(p["quantity"])))


def ref_q6(data, p) -> D:
    l = data["lineitem"]
    m = _q6_mask(l, p)
    return _scaled((l["extendedprice"][m] * l["discount"][m]).sum(), 4)


def _q1_groups(l, p):
    last = np.datetime64("1998-12-01") - np.timedelta64(int(p["delta"]), "D")
    m = l["shipdate"] <= int((last - EPOCH).astype(int))
    for flag in "ANR":
        for status in "FO":
            g = m & (l["returnflag"] == flag) & (l["linestatus"] == status)
            if g.any():
                yield flag, status, g


def ref_q1(data, p) -> list:
    """[(flag, status, sum_qty, sum_price, sum_disc_price, sum_charge,
    (avg numerators...), count)] in key order; averages stay exact
    fractions (sum, count), rounded by the caller at the printed scale."""
    l = data["lineitem"]
    disc_price = l["extendedprice"] * (100 - l["discount"])
    charge = disc_price * (100 + l["tax"])
    out = []
    for flag, status, g in _q1_groups(l, p):
        n = int(g.sum())
        qty, price, disc = (int(l[k][g].sum()) for k in ("quantity", "extendedprice", "discount"))
        out.append((flag, status, D(qty), _scaled(price, 2),
                    _scaled(disc_price[g].sum(), 4), _scaled(charge[g].sum(), 6),
                    (D(qty), n), (_scaled(price, 2), n), (_scaled(disc, 2), n), n))
    return out


def _q3_lines(data, p):
    c, o, l = data["customer"], data["orders"], data["lineitem"]
    in_segment = c["segment"] == SEGMENTS.index(p["segment"])
    o_ok = (o["orderdate"] < _day(p["date"])) & in_segment[o["custkey"] - 1]
    return (l["shipdate"] > _day(p["date"])) & o_ok[l["oidx"]]


def ref_q3(data, p) -> dict:
    """orderkey -> (revenue, orderdate text, shippriority) for every
    qualifying group; the caller applies ORDER BY ... LIMIT 10."""
    o, l = data["orders"], data["lineitem"]
    l_ok = _q3_lines(data, p)
    rev = np.zeros(len(o["orderkey"]), np.int64)
    np.add.at(rev, l["oidx"][l_ok], (l["extendedprice"] * (100 - l["discount"]))[l_ok])
    hit = np.zeros(len(o["orderkey"]), bool)
    hit[l["oidx"][l_ok]] = True
    dates = _dates(o["orderdate"][hit])
    return {
        int(k): (_scaled(r, 4), d, int(q))
        for k, r, d, q in zip(o["orderkey"][hit], rev[hit], dates, o["shippriority"][hit])
    }


REFERENCES = {"q1": ref_q1, "q3": ref_q3, "q6": ref_q6}


def reference(name: str, params: dict, data: dict):
    """The statement's exact answer, in the form `mismatch` reads."""
    return REFERENCES[name](data, params)


def expected_rows(name: str, want) -> int:
    """How many rows the answer has (what a traced statement reports)."""
    if name == "q6":
        return 1
    return min(len(want), 10) if name == "q3" else len(want)


def _round_like(frac, text: str) -> D:
    """Exact sum/count rounded half-up to the scale the engine printed."""
    s, n = frac
    scale = len(text.partition(".")[2])
    if scale < 4:
        raise ValueError(f"avg printed at scale {scale}: {text!r}")
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        return (s / n).quantize(D(1).scaleb(-scale), rounding=decimal.ROUND_HALF_UP)


def mismatch(name: str, want, rows) -> str | None:
    """None where the served rows say what the reference says, else what
    differs.  Decimal text is compared by value, so '1.50' equals '1.5'."""
    try:
        return _MISMATCH[name](want, rows)
    except (ValueError, TypeError, IndexError, KeyError, decimal.InvalidOperation) as e:
        return f"{name}: unreadable answer ({type(e).__name__}: {e}): {rows[:2]!r}"


def _mismatch_q6(want, rows):
    if len(rows) != 1 or rows[0][0] is None or D(rows[0][0]) != want:
        return f"q6: got {rows!r}, want {want}"
    return None


def _mismatch_q1(want, rows):
    if len(rows) != len(want):
        return f"q1: {len(rows)} groups, want {len(want)}"
    for got, w in zip(rows, want):
        if (got[0], got[1]) != (w[0], w[1]):
            return f"q1: group {got[:2]}, want {w[:2]}"
        for j in (2, 3, 4, 5):
            if D(got[j]) != w[j]:
                return f"q1 {w[0]}{w[1]} column {j}: got {got[j]}, want {w[j]}"
        for j in (6, 7, 8):
            if D(got[j]) != _round_like(w[j], got[j]):
                return f"q1 {w[0]}{w[1]} column {j}: got {got[j]}, want {_round_like(w[j], got[j])}"
        if int(got[9]) != w[9]:
            return f"q1 {w[0]}{w[1]} count: got {got[9]}, want {w[9]}"
    return None


def _mismatch_q3(want, rows):
    top = sorted(want.values(), key=lambda v: (-v[0], v[1]))[:10]
    if len(rows) != len(top):
        return f"q3: {len(rows)} rows, want {len(top)}"
    for got, w in zip(rows, top):
        # ties on (revenue, orderdate) may order either way: the sort keys
        # must match position by position, the row itself its own group
        if (D(got[1]), got[2]) != (w[0], w[1]):
            return f"q3: sort keys {got[1:3]}, want {w[:2]}"
        if want.get(int(got[0])) != (D(got[1]), got[2], int(got[3])):
            return f"q3: row {got}, want {want.get(int(got[0]))}"
    return None


_MISMATCH = {"q1": _mismatch_q1, "q3": _mismatch_q3, "q6": _mismatch_q6}


# --------------------------------------------------------------------------
# the control: the same reference with every sum accumulated in float32
# --------------------------------------------------------------------------

def _f32_text(values, scale: int) -> str:
    """A float32 running sum of scaled integers, printed at `scale`."""
    total = np.float32(0)
    for chunk in np.array_split(np.asarray(values, np.float32), max(len(values) // 4096, 1)):
        total = np.float32(total + chunk.sum(dtype=np.float32))
    return f"{D(float(total)).scaleb(-scale):.{scale}f}"


def control(name: str, params: dict, data: dict) -> list:
    """The rows a float32 engine would have served for the statement, as
    wire text.  Put in the program's place, they have to read as wrong."""
    l = data["lineitem"]
    if name == "q6":
        m = _q6_mask(l, params)
        prod = l["extendedprice"][m].astype(np.float32) * l["discount"][m].astype(np.float32)
        return [[_f32_text(prod, 4)]]
    if name == "q1":
        price = l["extendedprice"].astype(np.float32)
        disc_price = price * (100 - l["discount"]).astype(np.float32)
        charge = disc_price * (100 + l["tax"]).astype(np.float32)
        rows = []
        for flag, status, g in _q1_groups(l, params):
            n = int(g.sum())
            qty, base, disc = (_f32_text(l[k][g], s) for k, s in
                               (("quantity", 0), ("extendedprice", 2), ("discount", 2)))
            rows.append([flag, status, qty + ".00", base, _f32_text(disc_price[g], 4),
                         _f32_text(charge[g], 6),
                         f"{D(qty) / n:.6f}", f"{D(base) / n:.6f}", f"{D(disc) / n:.6f}", str(n)])
        return rows
    if name == "q3":
        o = data["orders"]
        l_ok = _q3_lines(data, params)
        rev = np.zeros(len(o["orderkey"]), np.float32)
        np.add.at(rev, l["oidx"][l_ok],
                  (l["extendedprice"].astype(np.float32) * (100 - l["discount"]).astype(np.float32))[l_ok])
        hit = np.flatnonzero(rev > 0)
        order = hit[np.lexsort((o["orderdate"][hit], -rev[hit].astype(np.float64)))][:10]
        dates = _dates(o["orderdate"][order])
        return [[str(int(o["orderkey"][i])), f"{D(float(rev[i])).scaleb(-4):.4f}", d,
                 str(int(o["shippriority"][i]))] for i, d in zip(order, dates)]
    raise KeyError(name)


def scan_bytes(name: str, config: dict) -> int | None:
    """Bytes the statement has to read from HBM at logical widths, or
    None where no such count is kept (q3: its scans come from the result
    cache, so a share of the tables' bytes would read over 100%)."""
    per_row = SCAN_BYTES_PER_ROW.get(name)
    return None if per_row is None else per_row * int(config["lineitem_rows"])
