"""Finds everything a cell is made of by the names in BENCHMARK.json: the
configuration's file and deployment module, the traffic mix, the metric
readers, the peaks.  Adding a cell, a mix, a configuration or a metric is
adding files and entries; nothing here names one."""

from __future__ import annotations

import glob
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(CHECKOUT, "BENCHMARK.json")


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, with what its names resolve to."""

    def __init__(self, workload: str):
        self.manifest = _json(MANIFEST)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it has {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        config_entry = next(c for c in self.manifest["configs"] if c["name"] == self.entry["config"])
        config_path = os.path.join(CHECKOUT, config_entry["file"])
        self.config = _json(config_path)
        # a configuration that shares another's statements and deployment
        # module (the same schema at another scale) names them in its file
        config_dir = os.path.dirname(config_path)
        self.statements = _json(os.path.join(config_dir, self.config.get("statements", "statements.json")))
        self.deployment = load_module(os.path.join(config_dir, self.config.get("deployment", "deployment.py")),
                                      f"deployment_{self.entry['config']}")
        self.traffic = _json(os.path.join(BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))

    def metrics(self, group: str) -> list:
        """The metrics of `end_to_end` or `per_layer` that this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    @staticmethod
    def reader(group: str, metric: str):
        """`read(run) -> number | None` of one metric, from its own file."""
        folder = {"end_to_end": "end_to_end_metrics", "per_layer": "layer_metrics"}[group]
        return load_module(os.path.join(BENCH_DIR, folder, metric + ".py"), f"{folder}_{metric}").read


def program_names() -> dict:
    """The counters and kernel entry points that the harness reads from the
    program: every `program_names*.json`, merged."""
    out = {"counters": {}, "pallas_kernels": {}}
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "program_names*.json"))):
        table = _json(path)
        out["counters"].update(table.get("counters", {}))
        for mod, fns in table.get("pallas_kernels", {}).items():
            out["pallas_kernels"].setdefault(mod, [])
            out["pallas_kernels"][mod] += [f for f in fns if f not in out["pallas_kernels"][mod]]
    return out


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in benchmarks/peaks.json")
    return table[device_kind]
