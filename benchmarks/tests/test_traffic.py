"""The one traffic generator over the mixes kept as data: the same seed
gives the same statements, every seed the same shapes, and each rule
draws what the source says."""

import glob
import json
import os

import pytest

from harness.catalog import BENCH_DIR
from harness.traffic import Mix, client_rng

MIXES = sorted(os.path.basename(p)[:-5] for p in glob.glob(os.path.join(BENCH_DIR, "traffic", "*.json")))
CONFIG_OF = {"ro_uniform": "sysbench_32x16k", "q1q6_params": "tpch_sf0p02", "q3_params": "tpch_sf0p02"}


def _mix(name: str) -> Mix:
    def load(*parts):
        with open(os.path.join(BENCH_DIR, *parts)) as f:
            return json.load(f)

    config = CONFIG_OF[name]
    return Mix(load("traffic", name + ".json"), load("configs", config, "statements.json"),
               load("configs", config, "config.json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_statements_every_seed_same_shapes(name):
    mix = _mix(name)
    a = [s.sql for s in mix.operation(client_rng(2**31 + 9, 3, 1))]
    b = [s.sql for s in mix.operation(client_rng(2**31 + 9, 3, 1))]
    c = mix.operation(client_rng(5, 3, 1))
    assert a == b and a != [s.sql for s in c]
    assert [s.name for s in c] == [s.name for s in mix.operation(client_rng(6, 0, 1))]
    assert "{" not in "".join(a)          # every parameter of every text was drawn


def test_sysbench_draws_as_oltp_common_does():
    mix = _mix("ro_uniform")
    rng = client_rng(1, 0, 1)
    tables, ids, range_tables = set(), set(), set()
    for _ in range(300):
        steps = mix.operation(rng)
        assert [s.name for s in steps] == ([None] + ["point_select"] * 10 + [
            "simple_range", "sum_range", "order_range", "distinct_range"] + [None])
        points = [s.params for s in steps[1:11]]
        assert len({p["t"] for p in points}) == 1          # one table for a transaction's point selects
        assert len({p["id"] for p in points}) > 1
        tables.add(points[0]["t"])
        ids.update(p["id"] for p in points)
        for s in steps[11:15]:
            assert s.params["b"] == s.params["a"] + 99 and 1 <= s.params["a"] <= 16384
            range_tables.add(s.params["t"])
    assert tables == range_tables == set(range(1, 33))
    assert min(ids) >= 1 and max(ids) <= 16384 and max(ids) > 16000


def test_tpch_parameters_stay_inside_the_specs_ranges():
    rng = client_rng(2, 0, 1)
    q1q6, q3 = _mix("q1q6_params"), _mix("q3_params")
    seen = {"delta": set(), "date": set(), "discount": set(), "quantity": set(), "segment": set(), "q3date": set()}
    for _ in range(400):
        one, six = q1q6.operation(rng)
        seen["delta"].add(one.params["delta"])
        for k in ("date", "discount", "quantity"):
            seen[k].add(six.params[k])
        (three,) = q3.operation(rng)
        seen["segment"].add(three.params["segment"])
        seen["q3date"].add(three.params["date"])
    assert min(seen["delta"]) == 60 and max(seen["delta"]) == 120
    assert seen["date"] == {f"{y}-01-01" for y in range(1993, 1998)}
    assert seen["discount"] == {f"0.0{d}" for d in range(2, 10)} and seen["quantity"] == {24, 25}
    assert len(seen["segment"]) == 5 and seen["q3date"] == {f"1995-03-{d:02d}" for d in range(1, 32)}
