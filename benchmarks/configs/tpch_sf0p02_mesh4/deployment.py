"""The four-chip deployment of TPC-H Q1, Q6 and Q3 is `tpch_sf0p02`'s tables,
generator and plain numpy reference without the columnar replica, cut into
regions after the load: one generator and one reference serve the three
configurations, so this file re-exports that module, loaded by its path,
and wraps `load`.  Nothing here imports the program: the harness hands
`load` a connected wire client, and the regions are laid out the way a
client of the served path lays them out, with `SPLIT TABLE`."""

import importlib.util
import os
import time

_spec = importlib.util.spec_from_file_location(
    "deployment_tpch_tables",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "tpch_sf0p02", "deployment.py"))
_tables = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tables)
globals().update({k: v for k, v in vars(_tables).items() if not k.startswith("__")})


def _must_split(client) -> None:
    """Before a row is loaded: a program that parses `SPLIT TABLE` and does
    not execute it answers "not supported" for any table, one that executes
    it names the table it cannot find.  Fails here, in seconds, where the
    deployment cannot be laid out, and not after a minute of loading."""
    try:
        client.query("split table tpch_split_probe between (0) and (2) regions 2")
    except Exception as e:  # noqa: BLE001 - the wire client's error, whatever its class
        if "unknown table" in str(e):
            return
        raise RuntimeError(f"the program cannot execute SPLIT TABLE: {e}") from e
    raise RuntimeError("SPLIT TABLE of a table that is not there was acknowledged")


def load(client, data: dict, config: dict, emit) -> dict:
    """The shared load and ANALYZE, then every statement of `layout.split`
    over the wire, the configuration's sizes formatted in.  `SPLIT TABLE`
    answers one row, (TOTAL_SPLIT_REGION, SCATTER_FINISH_RATIO): on the
    freshly loaded table the regions newly cut are the regions asked for."""
    _must_split(client)
    loaded = _tables.load(client, data, config, emit)
    t0 = time.perf_counter()
    sizes = {k: v for k, v in config.items() if isinstance(v, int)}
    regions = dict.fromkeys(loaded, 1)
    for text in config["layout"]["split"]:
        sql = text.format(**sizes)
        words = sql.split()
        table, want = words[2], int(words[-1])
        columns, rows = client.query(sql)
        got = dict(zip(columns, rows[0])) if rows else {}
        if int(got.get("TOTAL_SPLIT_REGION", -1)) != want or float(got.get("SCATTER_FINISH_RATIO", 0)) != 1.0:
            raise RuntimeError(f"{sql!r}: answered {columns} {rows}, want {want} regions cut and scattered")
        regions[table] = want
    emit(phase="split", regions=regions, wall_s=round(time.perf_counter() - t0, 3))
    return loaded
