"""TPC-H Q18 over four chips is `tpch_sf0p02_mesh4`'s deployment (its
generator, its load with `SPLIT TABLE` and its plain numpy module) with
Q18's reference, control and bytes beside it: this file re-exports that
module, loaded by its path, and adds them, with a check of the inner
statement and a warm-up of every QUANTITY the mix draws, both made in
set-up.  Nothing here imports the program."""

import decimal
import importlib.util
import json
import os
import time

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "deployment_tpch_mesh4",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "tpch_sf0p02_mesh4", "deployment.py"))
_mesh4 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mesh4)
globals().update({k: v for k, v in vars(_mesh4).items() if not k.startswith("__")})

LIMIT = 100
# Q18's inner statement word for word, so that the set-up check calls the
# program the window calls (its literal is an operand)
INNER = "select l_orderkey from lineitem group by l_orderkey having sum(l_quantity) > {quantity}"
# thresholds of the set-up check: every order (a group lost or split
# across chips shows), and thousands and hundreds of orders near the cut
CHECK_QUANTITIES = (0, 150, 250)
SPEC_QUANTITIES = (312, 313, 314, 315)

# logical bytes a Q18 has to read at the DDL's widths (bigint 8,
# decimal(15,2) 8, date 4, varchar(25) 25), whatever programs implement it:
# the inner statement reads l_orderkey and l_quantity of every lineitem
# row, the outer statement the same two again, orders' o_orderkey,
# o_custkey, o_totalprice, o_orderdate and customer's c_custkey, c_name
Q18_BYTES_PER_ROW = {"lineitem": 2 * (8 + 8), "orders": 8 + 8 + 8 + 4, "customer": 8 + 25}


# --------------------------------------------------------------------------
# the plain reference: numpy over the generated arrays, exact
# --------------------------------------------------------------------------

def order_quantities(data) -> np.ndarray:
    """sum(l_quantity) of every order, in whole units (l_quantity is
    decimal(15,2) with no cents in dbgen's rule): int64 by order index."""
    l = data["lineitem"]
    total = np.zeros(len(data["orders"]["orderkey"]), np.int64)
    np.add.at(total, l["oidx"], l["quantity"])
    return total


def ref_q18_inner(data, p, sums=None) -> dict:
    """`select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey
    having sum(l_quantity) > QUANTITY`: orderkey -> sum."""
    sums = order_quantities(data) if sums is None else sums
    hit = np.flatnonzero(sums > int(p["quantity"]))
    return {int(data["orders"]["orderkey"][i]): int(sums[i]) for i in hit}


def ref_q18(data, p, sums=None) -> dict:
    """orderkey -> (c_name, c_custkey, o_orderdate text, o_totalprice,
    sum(l_quantity)) for every order of the inner answer, joined with its
    customer (one group per order); the caller applies ORDER BY ...
    LIMIT 100."""
    o = data["orders"]
    qty = order_quantities(data)
    inner = ref_q18_inner(data, p, qty if sums is None else sums)
    idx = np.flatnonzero(np.isin(o["orderkey"], np.fromiter(inner, np.int64, len(inner))))
    dates = _dates(o["orderdate"][idx])
    return {
        int(o["orderkey"][i]): (f"Customer#{int(o['custkey'][i]):09d}", int(o["custkey"][i]), d,
                                _scaled(o["totalprice"][i], 2), D(int(qty[i])))
        for i, d in zip(idx, dates)
    }


def _top(want: dict) -> list:
    """The statement's rows in its order: o_totalprice desc, o_orderdate."""
    return sorted(want.items(), key=lambda kv: (-kv[1][3], kv[1][2]))[:LIMIT]


# --------------------------------------------------------------------------
# the set-up check: at the spec's QUANTITY most windows compare empty
# answers, which no fault that under-counts can change, so the inner
# statement is compared here, where every order or thousands of them
# qualify, and a wrong answer ends the run before its window
# --------------------------------------------------------------------------

def load(client, data: dict, config: dict, emit) -> dict:
    loaded = _mesh4.load(client, data, config, emit)
    check_inner(client, data, emit)
    warm_spec_quantities(client, data, emit)
    return loaded


def warm_spec_quantities(client, data: dict, emit) -> None:
    """Q18 once at every QUANTITY the mix draws.  How many orders the inner
    statement keeps there (0-3) depends on the seed's data, and an engine
    whose outer program takes its shape from that count (FALSE for none, a
    list of that many literals) would otherwise meet a shape that the
    warm-up's few draws missed inside the window, where a cold four-chip
    join program compiles for about 100 s.  Emits each answer's size and
    whether it matches the reference; the window's comparison decides."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "statements.json")) as f:
        text = json.load(f)["q18"]
    for q in SPEC_QUANTITIES:
        t = time.perf_counter()
        _, rows = client.query(text.format(quantity=q))
        diff = mismatch("q18", reference("q18", {"quantity": q}, data), rows)
        emit(warm="q18", quantity=q, rows=len(rows), equal=diff is None,
             wall_s=round(time.perf_counter() - t, 3))


def check_inner(client, data: dict, emit) -> None:
    """The inner statement at CHECK_QUANTITIES over the wire, its orderkeys
    as a multiset against the reference's (a key twice is a group that two
    chips finished); SystemExit on the first difference.  Emits, per
    threshold, the rows compared, and for the spec's thresholds how many
    orders the reference keeps: the share of them with a non-empty answer
    is the share of the window's statements that a fault which drops
    qualifying orders could change."""
    sums = order_quantities(data)
    client.query("set tidb_isolation_read_engines = 'tpu'")
    for q in CHECK_QUANTITIES:
        _, rows = client.query(INNER.format(quantity=q))
        got = sorted(int(r[0]) for r in rows)
        want = sorted(ref_q18_inner(data, {"quantity": q}, sums))
        emit(check="q18_inner", quantity=q, rows=len(got), want=len(want), equal=got == want)
        if got != want:
            raise SystemExit(f"q18's inner statement at QUANTITY {q}: {len(got)} keys "
                             f"({len(got) - len(set(got))} twice), want {len(want)}; "
                             f"{len(set(want) - set(got))} missing, {len(set(got) - set(want))} not wanted")
    kept = {q: int((sums > q).sum()) for q in SPEC_QUANTITIES}
    emit(check="q18_spec_answers", orders=kept, non_empty_share=sum(n > 0 for n in kept.values()) / len(kept))


def reference(name: str, params: dict, data: dict):
    if name == "q18":
        return ref_q18(data, params)
    return _mesh4.reference(name, params, data)


def expected_rows(name: str, want) -> int:
    if name == "q18":
        return min(len(want), LIMIT)
    return _mesh4.expected_rows(name, want)


def _mismatch_q18(want, rows):
    top = _top(want)
    if len(rows) != len(top):
        return f"q18: {len(rows)} rows, want {len(top)}"
    for got, (_key, w) in zip(rows, top):
        # ties on (o_totalprice, o_orderdate) may order either way: the sort
        # keys must match position by position, the row itself its own order
        if (D(got[4]), got[3]) != (w[3], w[2]):
            return f"q18: sort keys {got[3:5]}, want {(w[2], w[3])}"
        have = (got[0], int(got[1]), got[3], D(got[4]), D(got[5]))
        if want.get(int(got[2])) != have:
            return f"q18: row {got}, want {want.get(int(got[2]))}"
    return None


def mismatch(name: str, want, rows) -> str | None:
    if name != "q18":
        return _mesh4.mismatch(name, want, rows)
    try:
        return _mismatch_q18(want, rows)
    except (ValueError, TypeError, IndexError, KeyError, decimal.InvalidOperation) as e:
        return f"q18: unreadable answer ({type(e).__name__}: {e}): {rows[:2]!r}"


# --------------------------------------------------------------------------
# the control: the inner sums of a merge that counted every lane's partial
# state twice
# --------------------------------------------------------------------------

def control(name: str, params: dict, data: dict) -> list:
    """The rows an engine whose exchange merged two copies of every group's
    partial state would have served, as wire text: the inner statement
    keeps the orders whose doubled sum passes QUANTITY (thousands at this
    scale, where the reference keeps 0-3), the outer statement sums
    l_quantity itself.  Put in the program's place, they read as wrong."""
    if name != "q18":
        return _mesh4.control(name, params, data)
    want = ref_q18(data, params, sums=2 * order_quantities(data))
    return [[w[0], str(w[1]), str(k), w[2], f"{w[3]:.2f}", f"{w[4]:.2f}"] for k, w in _top(want)]


def scan_bytes(name: str, config: dict) -> int | None:
    """Bytes the statement has to read from HBM at logical widths."""
    if name != "q18":
        return _mesh4.scan_bytes(name, config)
    return sum(per_row * int(config[f"{table}_rows"]) for table, per_row in Q18_BYTES_PER_ROW.items())
