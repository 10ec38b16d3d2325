"""The test suite's plain TPC-H Q18 reference: numpy over the arrays that
the benchmark's generator makes (`benchmarks/configs/tpch_sf0p02/
deployment.py` `generate`), exact, written apart from the program and from
the benchmark's own copy in `configs/tpch_sf0p02_q18_mesh4/deployment.py`,
which `tests/test_tpch_q18_reference.py` holds to the same answers."""

import decimal

import numpy as np

D = decimal.Decimal
EPOCH = np.datetime64("1992-01-01")
LIMIT = 100


def order_sums(data) -> np.ndarray:
    """sum(l_quantity) of every order, by order index, in whole units
    (dbgen's quantities are whole: decimal(15,2) with .00)."""
    out = np.zeros(len(data["orders"]["orderkey"]), np.int64)
    np.add.at(out, data["lineitem"]["oidx"], data["lineitem"]["quantity"])
    return out


def ref_q18_inner(data, quantity: int) -> dict:
    """select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey
    having sum(l_quantity) > quantity: {orderkey: sum}."""
    sums = order_sums(data)
    keys = data["orders"]["orderkey"]
    return {int(keys[i]): int(sums[i]) for i in np.flatnonzero(sums > quantity)}


def ref_q18(data, quantity: int) -> dict:
    """Q18's groups before its ORDER BY ... LIMIT: {orderkey: (c_name,
    c_custkey, o_orderdate, o_totalprice, sum(l_quantity))}, one group per
    order of the inner answer (o_orderkey is orders' key, c_custkey
    customer's), decimals exact."""
    o = data["orders"]
    sums = order_sums(data)
    out = {}
    for i in np.flatnonzero(sums > quantity):
        cust = int(o["custkey"][i])
        day = str(EPOCH + np.timedelta64(int(o["orderdate"][i]), "D"))
        out[int(o["orderkey"][i])] = (f"Customer#{cust:09d}", cust, day,
                                      D(int(o["totalprice"][i])).scaleb(-2), D(int(sums[i])))
    return out


def top(groups: dict) -> list:
    """[(orderkey, group)] in the statement's order: o_totalprice desc,
    o_orderdate asc, the first 100."""
    return sorted(groups.items(), key=lambda kv: (-kv[1][3], kv[1][2]))[:LIMIT]


def q18_mismatch(groups: dict, rows) -> str | None:
    """None where the served wire rows are Q18's answer: its sort keys in
    the reference's order position by position (rows tied on both may come
    either way round), and each row the group of its own order."""
    want = top(groups)
    if len(rows) != len(want):
        return f"{len(rows)} rows, want {len(want)}"
    for got, (_k, w) in zip(rows, want):
        if (D(got[4]), got[3]) != (w[3], w[2]):
            return f"sort keys {got[3:5]}, want {w[2:4]}"
        if groups.get(int(got[2])) != (got[0], int(got[1]), got[3], D(got[4]), D(got[5])):
            return f"row {got}, want {groups.get(int(got[2]))}"
    return None
