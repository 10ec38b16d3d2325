"""Chip smoke: the served SQL path, once, on the chip.

One process, one chip, the entry points a user calls: a `MySQLServer`, a
wire client, `LOAD DATA INFILE`, `ANALYZE`, TPC-H Q6/Q1/Q3 plus a point
get, an indexed range and an update/read-back from the row store, then Q6
and Q1 again from the columnar replica.  Every answer is compared with a
plain numpy computation over the generated arrays (scaled int64 for the
decimal sums; generator and references are those of the benchmark's
`tpch_sf0p02` deployment), never with the engine's own oracle.  The first
failed phase ends the run with a non-zero exit code.

    python chip_smoke.py [--seed N] [--rows N] [--chips 4]

Each phase and each statement prints one JSON line; the last line of
stdout is `{"ok": true, "device": {...}}`.  With `--chips 4` the script
runs the mesh/MPP statements and their single-device comparison and no
other phase.  No number printed here is a benchmark.
"""

from __future__ import annotations

import argparse
import collections
import functools
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(HERE, ".xla_cache"))

import numpy as np  # noqa: E402

# lineitem rows; orders = rows/4, customer = orders/10 (TPC-H's ratios, SF~0.044).
# The host bounds it, not the chip: LOAD DATA, ANALYZE and the replica's
# backfill are per-row Python, and a cold run must fit the smoke's time limit
DEFAULT_ROWS = 1 << 18
OUT_DIR = os.path.join(HERE, "chiprun_out")
WIRE_TIMEOUT = 1100.0  # a first execution compiles; the wire must wait

# the benchmark's TPC-H deployment, by file path as its harness loads it:
# generator, `.tbl` writer, DDL, statement texts and the numpy references
# have one home
TPCH_DIR = os.path.join(HERE, "benchmarks", "configs", "tpch_sf0p02")
_spec = importlib.util.spec_from_file_location(
    "tpch_sf0p02_deployment", os.path.join(TPCH_DIR, "deployment.py"))
TPCH = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(TPCH)
# the validation parameters of TPC-H 2.4.1.3 / 2.4.3.3 / 2.4.6.3: the
# statements here carry fixed literals, the benchmark's cells draw theirs
PARAMS = {
    "q1": {"delta": 90},
    "q3": {"segment": "BUILDING", "date": "1995-03-15"},
    "q6": {"date": "1994-01-01", "discount": "0.06", "quantity": 24},
}
with open(os.path.join(TPCH_DIR, "statements.json")) as _f:
    TEMPLATES = json.load(_f)
SQL = {name: text.format(**PARAMS[name]) for name, text in TEMPLATES.items()}
# Q3 again with other substitution parameters (2.4.3.3): the program the
# first execution built has to serve them
Q3_DRAWS = ({"segment": "MACHINERY", "date": "1995-03-15"}, {"segment": "MACHINERY", "date": "1995-03-29"},
            {"segment": "AUTOMOBILE", "date": "1995-03-02"})

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


def _day(s: str) -> int:
    return int((np.datetime64(s) - TPCH.EPOCH).astype(int))


def _dates(days) -> list:
    return np.datetime_as_string(TPCH.EPOCH + days.astype("timedelta64[D]")).tolist()


def check_tpch(name: str):
    """run_twice's `check` for a TPC-H statement: the served rows against
    the deployment's numpy reference at this file's fixed parameters."""
    def check(rows, data) -> dict:
        bad = TPCH.mismatch(name, TPCH.reference(name, PARAMS[name], data), rows)
        assert bad is None, bad
        return {"rows": len(rows)}

    return check


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


def q3_other_parameters(ctx, mesh: bool = False) -> None:
    """A second SEGMENT and a second DATE call the program that Q3's
    first execution built: the literals are its operands, strings too.
    Fails where `PROGRAM_COMPILES` or XLA's compile count moves; on one
    chip also where the row store decodes or uploads `lineitem` again,
    on the mesh where the draw is not one cross-chip launch over lanes
    found resident (the store keeps the stacked, sharded batch by data
    version since PR 35)."""
    from tidb_tpu.util import metrics

    names = ("PROGRAM_COMPILES", "XLA_COMPILES", "PROGRAM_PARAMS_BOUND", "PROGRAM_STR_PARAMS_BOUND", "PROGRAM_LAUNCHES") + (
        ("MESH_COP_BATCHES", "MPP_SELECTS", "MESH_COP_FALLBACKS", "MPP_FALLBACKS", "MESH_STACK_HITS", "MESH_STACK_MISSES") if mesh
        else ("COP_AUX_UPLOADS", "COP_CACHE_HITS", "COP_DECODE_HITS", "COP_DECODE_MISSES"))
    for params in Q3_DRAWS:
        before = {n: getattr(metrics, n).value for n in names}
        p = Probe()
        rows = ctx.client.query(TEMPLATES["q3"].format(**params))[1]
        got = p.done()
        bad = TPCH.mismatch("q3", TPCH.reference("q3", params, ctx.data), rows)
        assert bad is None, (params, bad)
        moved = {n.lower(): getattr(metrics, n).value - before[n] for n in names}
        assert moved["program_compiles"] == 0 and moved["xla_compiles"] == 0 and got["compile_s"] == 0, (
            f"q3 {params}: another SEGMENT or DATE built a program: {moved} {got}")
        assert moved["program_str_params_bound"] == 1 and got["oracle_fallbacks"] == 0, (params, moved, got)
        if mesh:
            assert moved["mesh_cop_batches"] + moved["mpp_selects"] == 1, (params, moved)
            assert moved["mesh_cop_fallbacks"] == moved["mpp_fallbacks"] == 0, (params, moved)
            assert (moved["mesh_stack_hits"], moved["mesh_stack_misses"]) == (1, 0), (
                f"q3 {params}: the lanes were stacked and put onto the devices again: {moved}")
        else:
            assert (moved["cop_decode_hits"], moved["cop_decode_misses"]) == (1, 0), (
                f"q3 {params}: lineitem was decoded or uploaded again: {moved}")
        emit(stmt="mesh_q3_params" if mesh else "q3_params", wall_s=got["wall_s"], rows=len(rows), **params, **moved)


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
    ctx.data = TPCH.generate({"lineitem_rows": ctx.rows}, ctx.seed)
    data_dir = os.path.join(OUT_DIR, f"chip_smoke_data_{os.getpid()}")
    os.makedirs(data_dir)
    paths = TPCH.write_files(ctx.data, data_dir)
    gen_s = time.perf_counter() - t0

    ctx.srv = MySQLServer(port=0)
    ctx.srv.start_background()
    ctx.client, ctx.client2 = ctx.connect(), ctx.connect()
    loaded = {}
    t0 = time.perf_counter()
    for ddl in TPCH.DDL:
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
    run_twice("q6", q(SQL["q6"]), check_tpch("q6"), data)
    run_twice("q1", q(SQL["q1"]), check_tpch("q1"), data, want_pallas=True)
    run_twice("q3", q(SQL["q3"]), check_tpch("q3"), data, want_pallas=True)
    q3_other_parameters(ctx)

    o = data["orders"]
    k = len(o["orderkey"]) // 2

    def check_point(rows, _d):
        price = TPCH.D(int(o["totalprice"][k])).scaleb(-2)  # cents -> decimal(15,2) text
        want = [[str(o["orderkey"][k]), str(o["custkey"][k]), str(price),
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
    run_twice("columnar_q6", ctx.runner(SQL["q6"]), check_tpch("q6"), data)
    run_twice("columnar_q1", ctx.runner(SQL["q1"]), check_tpch("q1"), data, want_pallas=True)
    scans = metrics.COLUMNAR_SCANS.value - scans0
    fallbacks = metrics.COLUMNAR_FALLBACKS.value - fallbacks0
    assert scans >= 4 and fallbacks == 0, (scans, fallbacks)
    emit(phase="columnar", replica_fill_s=round(fill_s, 3), ticks=ticks,
         columnar_scans=scans, columnar_fallbacks=fallbacks, **p.done())


def phase_mesh(ctx: Ctx, n_devices: int) -> None:
    """Q6, Q1 and Q3 with the defaults — mesh and MPP tiers on — and again
    with both off, which is what they are compared with; on the mesh each a
    second time, which must find its lanes resident.  Both cross-chip
    tiers degrade in silence, so a right answer proves nothing alone: the
    counters, the devices and the collectives are asserted too."""
    from tidb_tpu.util import metrics as m

    c, data, store = ctx.client, ctx.data, ctx.srv.store
    # the mesh tier shards REGIONS over the devices, and a freshly loaded
    # table is one region: cut lineitem and orders the way a client of the
    # served path does (two lanes a device, one for the join's build side)
    regions = {}
    for table, n in (("lineitem", 2 * n_devices), ("orders", n_devices)):
        columns, rows = c.query(f"split table {table} between (1) and ({ctx.rows} + 1) regions {n}")
        assert columns == ["TOTAL_SPLIT_REGION", "SCATTER_FINISH_RATIO"] and rows == [[str(n), "1"]], (table, columns, rows)
        regions[table] = n
    assert len(store.cluster.regions()) == 1 + sum(regions.values()), store.cluster.regions()
    emit(phase="split", regions=regions)

    counters = ("MPP_SELECTS", "MESH_COP_BATCHES", "MPP_FALLBACKS", "MESH_COP_FALLBACKS")
    checks = tuple((name, SQL[name], check_tpch(name)) for name in ("q6", "q1", "q3"))
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
                # the shape's second draw finds the lanes stacked and sharded on the devices
                stack0 = (m.MESH_STACK_HITS.value, m.MESH_STACK_MISSES.value)
                check(c.query(sql)[1], data)
                stack = (m.MESH_STACK_HITS.value - stack0[0], m.MESH_STACK_MISSES.value - stack0[1])
                assert stack == (1, 0), f"{name}: the second draw stacked its lanes again: hits, misses = {stack}"
                result["second_draw_stack"] = {"hits": stack[0], "misses": stack[1]}
            else:
                assert not any(moved.values()) and not progs, (name, moved, progs)
            emit(stmt=f"{mode}_{name}", tiers=moved, mesh_programs=progs, **line, **result)
        if mode == "mesh":
            q3_other_parameters(ctx, mesh=True)


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
