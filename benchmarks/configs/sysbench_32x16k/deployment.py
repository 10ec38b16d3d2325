"""sysbench's `sbtest1..sbtestN` at the sizes of `config.json`: rows from the
seed by `oltp_common.lua`'s value rules, the load by multi-row INSERT as
`sysbench prepare` does, table after table, and the plain reference for
`oltp_read_only.lua`'s five statement shapes: slices of the generated arrays.

For `oltp_read_write.lua` (`../sysbench_32x16k_rw/`) the same module keeps
the optional functions of a configuration that writes, at the end of the
file: which statements write, the state at the end of the load (`State`:
the generated arrays beneath, every row a write has set above them), a
write's effect on a state with its affected rows, the reads over a state,
and the read-back statements.

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


# --------------------------------------------------------------------------
# a configuration that writes (oltp_read_write.lua): the optional functions
# the history judge calls (harness/judge.py `History`)
# --------------------------------------------------------------------------

WRITES = frozenset({"index_update", "non_index_update", "delete", "insert"})
RANGES = frozenset({"simple_range", "sum_range", "order_range", "distinct_range"})


def writes(name: str) -> bool:
    return name in WRITES


class State:
    """The tables at one point of a history: the load's arrays beneath, and
    above them every row that a write has set, `(k, c, pad)` or None for a
    deleted row, by table and id.  A key is `(table, id)`."""

    def __init__(self, data: dict):
        self.data = data
        self.rows: dict = {}

    def get(self, key):
        t, i = key
        ids = self.rows.get(t)
        if ids is not None and i in ids:
            return ids[i]
        data, n = self.data, self.data["n"]
        if not (1 <= t <= data["tables"] and 1 <= i <= n):
            return None
        return (int(data["k"][t - 1][i - 1]), data["c"][t - 1][i - 1].decode(), data["pad"][(t - 1) * n + i - 1])

    def put(self, key, row) -> None:
        self.rows.setdefault(key[0], {})[key[1]] = row


def load_state(data: dict) -> State:
    """The state at the end of the load: every generated row, as loaded."""
    return State(data)


def keys(name: str, params: dict) -> list:
    """The rows a statement reads or writes; `(table, None)` is every row of
    the table (the read-back's look-up by `k`)."""
    t = int(params["t"])
    if name in RANGES:
        return [(t, i) for i in range(int(params["a"]), int(params["b"]) + 1)]
    if name == "k_read_back":
        return [(t, None)]
    return [(t, int(params["id"]))]


def apply(name: str, params: dict, state: State):
    """A write's effect on `state`, with the rows it affects; None where
    the statement has to fail (an INSERT of an id that is there).  An
    UPDATE affects the row it finds: the drawn strings never repeat, so a
    row found is a row changed.  `k = k + 1` adds to the value in `state`,
    which the judge folds at commit, as pessimistic DML reads at
    `for_update_ts`."""
    key = (int(params["t"]), int(params["id"]))
    row = state.get(key)
    if name == "insert":
        if row is not None:
            return None
        state.put(key, (int(params["k"]), params["c"], params["pad"]))
        return 1
    if row is None:
        return 0
    k, c, pad = row
    if name == "index_update":
        state.put(key, (k + 1, c, pad))
    elif name == "non_index_update":
        state.put(key, (k, params["c"], pad))
    elif name == "delete":
        state.put(key, None)
    else:
        raise KeyError(name)
    return 1


def reference_at(name: str, params: dict, state: State) -> list:
    """The statement's rows as wire text over `state`."""
    t = int(params["t"])
    if name == "pk_read_back":
        row = state.get((t, int(params["id"])))
        return [] if row is None else [[str(params["id"]), str(row[0]), row[1], row[2]]]
    if name == "k_read_back":
        k, data = int(params["k"]), state.data
        ids = set(np.flatnonzero(data["k"][t - 1] == k) + 1) if 1 <= t <= data["tables"] else set()
        for i, row in state.rows.get(t, {}).items():
            (ids.add if row is not None and row[0] == k else ids.discard)(i)
        return [[str(i)] for i in sorted(ids)]
    if name == "point_select":
        row = state.get((t, int(params["id"])))
        return [] if row is None else [[row[1]]]
    a, b = int(params["a"]), int(params["b"])
    rows = [row for row in (state.get((t, i)) for i in range(a, b + 1)) if row is not None]
    if name == "simple_range":
        return [[c] for _, c, _ in rows]
    if name == "sum_range":
        return [[str(sum(k for k, _, _ in rows))]] if rows else [[None]]
    if name == "order_range":
        return [[c] for c in sorted(c for _, c, _ in rows)]
    if name == "distinct_range":
        return [[c] for c in sorted({c for _, c, _ in rows})]
    raise KeyError(name)


def read_back(values: dict) -> list:
    """The read-back after the run, `[(statement, params)]`: every row
    written (`values`: key -> the rows it may hold or has held, None for
    absent) by primary key, then through `k_1` every `k` it may hold or
    has held, so that an index entry left behind is read too."""
    steps = [("pk_read_back", {"t": t, "id": i}) for t, i in sorted(values)]
    ks = sorted({(t, row[0]) for (t, _), rows in values.items() for row in rows if row is not None})
    return steps + [("k_read_back", {"t": t, "k": k}) for t, k in ks]
