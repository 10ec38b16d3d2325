"""What the benchmark takes from the program: the served entry (server and
wire client), its counters, its kernels' names, the device as JAX reports
it.  Everything else under `benchmarks/` is the yardstick."""

from __future__ import annotations

import collections
import contextlib
import functools
import importlib
import time

from .catalog import program_names

TRACED: collections.Counter = collections.Counter()   # Pallas entry points traced into programs
XLA = {"compile_s": 0.0, "compiles": 0}               # backend compiles, as JAX's monitoring reports them

NAMES = program_names()   # the counters and kernel entry points read, by name (program_names*.json)


def device(chips: int) -> dict:
    """The devices as JAX reports them; exits where the cell's chips are
    not there."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if dev["platform"] != "tpu" or dev["count"] < chips:
        raise SystemExit(f"the cell needs {chips} TPU chip(s); JAX reports {dev}")
    dev["count"] = chips
    return dev


def pallas_mode():
    from tidb_tpu.ops.dense_pallas import pallas_mode as mode

    return mode()


def prepare() -> None:
    """Before the first statement: a device error raises instead of
    turning into an oracle fallback, and the watchers are on."""
    import jax

    from tidb_tpu.util import failpoint

    failpoint.enable("cop-debug-raise")
    # counting wrappers on the Pallas entry points: their callers import
    # them at call time, so a wrapper on the module attribute sees every trace
    for mod_name, fns in NAMES["pallas_kernels"].items():
        mod = importlib.import_module(f"tidb_tpu.ops.{mod_name}")
        for name in fns:
            fn = getattr(mod, name)

            def counted(*a, _fn=fn, _name=name, **k):
                TRACED[_name] += 1
                return _fn(*a, **k)

            setattr(mod, name, functools.wraps(fn)(counted))

    # PROGRAM_COMPILE_DURATION times only the trace; the XLA compile runs at
    # the first call and JAX reports it as an event (PR 22)
    def on_event(event: str, seconds: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            XLA["compile_s"] += seconds
            XLA["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)


def persistent_cache_off() -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def start_server():
    from tidb_tpu.server import MySQLServer

    srv = MySQLServer(port=0)
    srv.start_background()
    return srv


def connect(srv, timeout: float):
    from tidb_tpu.server import MiniClient

    return MiniClient(srv.host, srv.port, timeout=timeout)


def counters() -> dict:
    from tidb_tpu.util import metrics

    out = {k: getattr(metrics, v).value for k, v in NAMES["counters"].items()}
    out.update(xla_compiles=XLA["compiles"], xla_compile_s=XLA["compile_s"], kernels=collections.Counter(TRACED))
    return out


def moved(before: dict) -> dict:
    now = counters()
    out = {k: now[k] - before[k] for k in now if k != "kernels"}
    out["xla_compile_s"] = round(out["xla_compile_s"], 3)
    out["kernels"] = dict(now["kernels"] - before["kernels"])
    return out


def fill_replica(srv, conn, config: dict, tables: dict, emit) -> None:
    """Attach the columnar replica and tick PD until its view says that a
    read may be timed: state normal, no delta rows, every row stable."""
    t0 = time.perf_counter()
    conn.query(config["columnar_replica"]["ddl"])
    for ticks in range(1, 9):
        srv.store.pd.tick()
        views = {v["table"]: v for v in srv.store.columnar.views()}
        if all(t in views and views[t]["state"] == "normal" and not views[t]["delta_rows"]
               and views[t]["stable_rows"] == rows and views[t]["stable_chunks"] == views[t]["pids"]
               for t, rows in tables.items()):
            emit(phase="replica", wall_s=round(time.perf_counter() - t0, 3), ticks=ticks,
                 views=[{k: v[k] for k in ("table", "state", "delta_rows", "stable_rows")}
                        for v in views.values()])
            return
    raise SystemExit(f"columnar replica not readable after {ticks} ticks: {views}")


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, 0 where the backend keeps no count."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks))


def annotation(on: bool):
    """`jax.profiler.TraceAnnotation` in a traced run, else nothing."""
    if not on:
        return lambda _name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation
