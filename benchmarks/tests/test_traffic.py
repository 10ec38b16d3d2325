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
CONFIG_OF = {"ro_uniform": "sysbench_32x16k", "q1q6_params": "tpch_sf0p02", "q3_params": "tpch_sf0p02",
             "q1q6q3_params": "tpch_sf0p02_mesh4", "q18_params": "tpch_sf0p02_q18_mesh4",
             "rw_uniform": "sysbench_32x16k_rw"}


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


def test_sysbench_read_write_draws_as_oltp_common_does():
    mix = _mix("rw_uniform")
    assert mix.restart_on == {1205, 1213, 9007}
    rng = client_rng(2**31 + 3, 5, 1)
    tables, ks = set(), set()
    for _ in range(300):
        steps = mix.operation(rng)
        names = [s.name for s in steps]
        assert names == ([None] + ["point_select"] * 10 + ["simple_range", "sum_range", "order_range",
                                                            "distinct_range", "index_update", "non_index_update",
                                                            "delete", "insert"] + [None])
        assert [s.sql for s in steps if s.name is None] == ["begin", "commit"]   # 20 statements: 14 reads, 4 writes, 2 other
        update, non_index, delete, insert = steps[15:19]
        assert len(non_index.params["c"]) == 119 and non_index.params["c"].count("-") == 9
        assert (delete.params["t"], delete.params["id"]) == (insert.params["t"], insert.params["id"])  # one draw
        assert len(insert.params["c"]) == 119 and len(insert.params["pad"]) == 59 and insert.params["pad"].count("-") == 4
        assert insert.sql == (f"insert into sbtest{insert.params['t']} (id, k, c, pad) values "
                              f"({insert.params['id']}, {insert.params['k']}, '{insert.params['c']}', '{insert.params['pad']}')")
        for s in (update, non_index, delete):
            tables.add(s.params["t"])
            assert 1 <= s.params["id"] <= 16384
        ks.add(insert.params["k"])
    assert tables == set(range(1, 33)) and min(ks) >= 1 and max(ks) <= 16384 and max(ks) > 16000


def test_sb_string_is_the_deployments_rule():
    """The mix's `sb_string` and the load's `_groups` make the same strings
    from the same stream."""
    from harness.catalog import load_module

    dep = load_module(os.path.join(BENCH_DIR, "configs", "sysbench_32x16k", "deployment.py"), "sysbench_dep_t")
    mix = Mix({"loop": "closed", "clients": 1, "operation": []}, {}, {})
    a, b = client_rng(9, 0, 1), client_rng(9, 0, 1)
    drawn = [mix._draw({"c": {"sb_string": 10}}, a)["c"] for _ in range(3)]
    assert drawn == [dep._groups(b, 1, 10)[0] for _ in range(3)]
