"""sysbench's `sbtest1..sbtestN` at the sizes of `config.json`: rows from the
seed by `oltp_common.lua`'s value rules, the load by multi-row INSERT as
`sysbench prepare` does, table after table, and the plain reference for
`oltp_read_only.lua`'s five statement shapes: slices of the generated arrays.

Nothing here imports the program: the harness hands `load` a connected
wire client.
"""

from __future__ import annotations

import time

import numpy as np

DDL = """create table sbtest{t} (
    id int not null auto_increment, k int not null default 0,
    c char(120) not null default '', pad char(60) not null default '',
    primary key (id), key k_1 (k))"""


def _groups(rng, n: int, k: int) -> list:
    """`k` groups of 11 random digits joined by '-' (sysbench's '###...-###...')."""
    digits = rng.integers(0, 10**11, size=(n, k))
    return ["-".join(f"{v:011d}" for v in row) for row in digits.tolist()]


def generate(sizes: dict, seed: int) -> dict:
    """Table t (1-based) is row t-1 of `k` and `c`; id i is column i-1."""
    tables, n = int(sizes["tables"]), int(sizes["table_size"])
    rng = np.random.default_rng(seed)
    return {
        "tables": tables,
        "n": n,
        "insert_batch_rows": int(sizes["insert_batch_rows"]),
        "k": rng.integers(1, n + 1, (tables, n)),   # sysbench.rand.default(1, table_size), uniform
        "c": np.array(_groups(rng, tables * n, 10), dtype="S119").reshape(tables, n),
        "pad": _groups(rng, tables * n, 5),
    }


def load(client, data: dict, config: dict, emit) -> dict:
    t0 = time.perf_counter()
    tables, n, batch = data["tables"], data["n"], data["insert_batch_rows"]
    for t in range(1, tables + 1):
        client.query(DDL.format(t=t))
    for t in range(1, tables + 1):
        k, c = data["k"][t - 1].tolist(), [v.decode() for v in data["c"][t - 1].tolist()]
        pad = data["pad"][(t - 1) * n:t * n]
        for lo in range(0, n, batch):
            hi = min(lo + batch, n)
            values = ",".join(f"({i + 1},{k[i]},'{c[i]}','{pad[i]}')" for i in range(lo, hi))
            got = client.query(f"insert into sbtest{t} (id, k, c, pad) values " + values)
            if got != hi - lo:
                raise RuntimeError(f"sbtest{t}: insert of ids {lo + 1}..{hi}: {got} rows acknowledged")
    load_s = time.perf_counter() - t0
    emit(phase="load", rows={"sbtest1..sbtest%d" % tables: tables * n}, load_s=round(load_s, 3),
         load_rows_per_s=round(tables * n / load_s, 1))
    return {f"sbtest{t}": n for t in range(1, tables + 1)}


def replica_tables(config: dict) -> dict:
    return {}


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def _answer(name: str, p: dict, data: dict, n: int) -> list:
    """The statement's rows as wire text over ids 1..n of its table."""
    k, c = data["k"][int(p["t"]) - 1], data["c"][int(p["t"]) - 1]
    if name == "point_select":
        i = int(p["id"])
        return [[c[i - 1].decode()]] if 1 <= i <= n else []
    lo, hi = max(int(p["a"]), 1), min(int(p["b"]), n)
    span = slice(lo - 1, max(hi, lo - 1))
    if name == "simple_range":
        return [[v.decode()] for v in c[span].tolist()]
    if name == "sum_range":
        return [[str(int(k[span].sum()))]] if hi >= lo else [[None]]
    if name == "order_range":
        return [[v.decode()] for v in sorted(c[span].tolist())]
    if name == "distinct_range":
        return [[v.decode()] for v in sorted(set(c[span].tolist()))]
    raise KeyError(name)


def reference(name: str, params: dict, data: dict) -> list:
    return _answer(name, params, data, data["n"])


def expected_rows(name: str, want) -> int:
    return len(want)


def mismatch(name: str, want, rows) -> str | None:
    # without ORDER BY the rows may come in any order
    same = sorted(map(tuple, rows)) == sorted(map(tuple, want)) if name == "simple_range" else rows == want
    if same:
        return None
    return f"{name}: {len(rows)} rows {rows[:1]!r}.., want {len(want)} rows {want[:1]!r}.."


def control(name: str, params: dict, data: dict) -> list:
    """What a store whose every table (a region each) lost its last
    acknowledged INSERT of the load would serve: the same statements over
    ids 1..n-lost."""
    lost = data["n"] % data["insert_batch_rows"] or data["insert_batch_rows"]
    return _answer(name, params, data, data["n"] - lost)
