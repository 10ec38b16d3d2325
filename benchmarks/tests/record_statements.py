"""Records what `test_statements_unchanged.py` compares: for every mix that a
cell of BENCHMARK.json runs and three seeds, the digest of the statements
that each client's first operations send (the proof's stream, the warm-up's
and the window's), as the traffic generator of the tree it runs in draws them.

    python benchmarks/tests/record_statements.py > benchmarks/tests/data/statements_drawn.json
"""

import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, BENCH_DIR)

from harness.traffic import Mix, client_rng  # noqa: E402

SEEDS = (7, 2**31 + 9, 3_000_000_019)
OPERATIONS = 3      # the first operations of each client's stream


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cells(manifest: dict) -> dict:
    """{mix: (traffic spec, statements, configuration)} for every cell."""
    checkout = os.path.dirname(BENCH_DIR)
    configs = {c["name"]: os.path.join(checkout, c["file"]) for c in manifest["configs"]}
    out = {}
    for w in manifest["workloads"]:
        path = configs[w["config"]]
        config = _json(path)
        statements = _json(os.path.dirname(path), config.get("statements", "statements.json"))
        out[w["traffic"]] = (_json(BENCH_DIR, "traffic", w["traffic"] + ".json"), statements, config)
    return out


def digest(mix: Mix, seed: int, client: int, phase: int) -> str:
    rng = client_rng(seed, client, phase)
    h = hashlib.sha256()
    for _ in range(OPERATIONS):
        for step in mix.operation(rng):
            h.update(step.sql.encode() + b"\n")
    return h.hexdigest()


def record(manifest: dict) -> dict:
    out = {}
    for name, (spec, statements, config) in sorted(cells(manifest).items()):
        mix = Mix(spec, statements, config)
        streams = {"proof": digest(mix, 0, mix.clients, 0)}    # run.py's prove_paths stream
        for client in range(mix.clients):
            streams[f"warmup/{client}"] = digest(mix, 0, client, 0)   # warm-up draws at seed 0
            for seed in SEEDS:
                streams[f"{seed}/window/{client}"] = digest(mix, seed, client, 1)
        out[name] = streams
    return out


if __name__ == "__main__":
    manifest = _json(os.path.dirname(BENCH_DIR), "BENCHMARK.json")
    json.dump({"seeds": list(SEEDS), "operations": OPERATIONS, "mixes": record(manifest)},
              sys.stdout, indent=0, sort_keys=True)
    print()
