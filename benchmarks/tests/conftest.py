"""The benchmark's own tests run on the CPU at small sizes:

    python -m pytest benchmarks/tests -q -p no:cacheprovider

`small_run` drives the whole of `run.py`, the one path there is, with
three stand-ins put underneath it from here: `engine.device` answers for
the CPU, `catalog.MANIFEST` names a manifest whose configurations are the
real ones at small sizes (and which holds, beside BENCHMARK.json's cells,
the entries kept ready in `data/tpch_cells.json` and `data/write_cells.json`),
and the trace reduction takes the XLA CPU client's threads for a device
plane.  Their numbers are no measurements.
"""

import json
import os
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip
os.environ.setdefault("JAX_ENABLE_X64", "1")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {"lineitem_rows": 4096, "orders_rows": 1024, "customer_rows": 102,
         "tables": 3, "table_size": 1500, "insert_batch_rows": 400}


def host_cpu_events(profile) -> dict:
    """Stand-in for a device plane where JAX runs on the CPU: the XLA CPU
    client's own threads."""
    events = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            if ln.name.startswith("tf_XLAPjRtCpuClient"):
                events += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in ln.events if ev.duration_ns > 0]
    return {"/host:CPU xla client": events} if events else {}


def small_manifest(tmp_dir: str, sizes: dict | None = None) -> str:
    """BENCHMARK.json plus the entries kept ready, every configuration's
    file rewritten at SMALL sizes (`sizes` over them, by configuration),
    pointing at the real deployment module and statements."""
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for kept in ("tpch_cells.json", "write_cells.json"):
        with open(os.path.join(HERE, "data", kept)) as f:
            waiting = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            have = {e["name"] for e in manifest[key]}
            manifest[key] += [e for e in waiting[key] if e["name"] not in have]
    for entry in manifest["configs"]:
        real = os.path.join(CHECKOUT, entry["file"])
        with open(real) as f:
            config = json.load(f)
        config.update({k: v for k, v in SMALL.items() if k in config})
        config.update((sizes or {}).get(entry["name"], {}))
        for key, default in (("deployment", "deployment.py"), ("statements", "statements.json")):
            config[key] = os.path.normpath(os.path.join(os.path.dirname(real), config.get(key, default)))
        entry["file"] = os.path.join(tmp_dir, entry["name"] + ".json")
        with open(entry["file"], "w") as f:
            json.dump(config, f)
    path = os.path.join(tmp_dir, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


@pytest.fixture
def small_run(tmp_path, monkeypatch, capsys):
    """`small_run(workload, seed, seconds, trace=False, control=False)` ->
    the result line of a whole run of the harness on the CPU."""
    return small_run_at(tmp_path, monkeypatch, capsys)


def small_run_at(tmp_path, monkeypatch, capsys, sizes: dict | None = None, clients: int | None = None):
    """`small_run`, with `sizes` over SMALL by configuration, and the mix's
    clients taken down to `clients` where the CPU cannot hold its count."""
    import run as bench
    from harness import catalog, engine, xplane

    monkeypatch.setattr(catalog, "MANIFEST", small_manifest(str(tmp_path), sizes))
    if clients:
        real_mix = bench.Mix
        monkeypatch.setattr(bench, "Mix", lambda spec, *a: real_mix({**spec, "clients": clients}, *a))
    monkeypatch.setattr(engine, "device", lambda chips: {"platform": "cpu", "kind": "cpu", "count": chips})
    monkeypatch.setattr(bench, "peaks", lambda kind: None)
    real = xplane.device_events
    monkeypatch.setattr(xplane, "device_events", lambda profile: real(profile) or host_cpu_events(profile))

    def go(workload, seed, seconds, trace=False, control=False):
        assert bench.run(workload, seed, seconds, trace, control) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go

