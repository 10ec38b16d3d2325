"""A mix that only reads draws what it drew before the traffic rules of a
mix that writes came: for every cell of BENCHMARK.json and three seeds,
the statements of each client's first operations (the proof's stream, the
warm-up's and the window's) are byte for byte the ones that
`record_statements.py` recorded from the generator before those rules
(`data/statements_drawn.json`)."""

import json
import os

import pytest

import record_statements as rec

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "data", "statements_drawn.json")) as f:
    RECORDED = json.load(f)
with open(os.path.join(os.path.dirname(rec.BENCH_DIR), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def test_the_recording_covers_every_cell():
    assert RECORDED["seeds"] == list(rec.SEEDS) and RECORDED["operations"] == rec.OPERATIONS
    assert set(RECORDED["mixes"]) == {w["traffic"] for w in MANIFEST["workloads"]}


@pytest.mark.parametrize("mix", sorted(RECORDED["mixes"]))
def test_statements_are_byte_identical(mix):
    now = rec.record(MANIFEST)[mix]
    assert now == RECORDED["mixes"][mix]
