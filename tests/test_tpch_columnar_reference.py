"""The `tpch_sf0p02` deployment against its plain reference, in tier-1: Q1
and Q6 with TPC-H's drawn substitution parameters (2.4.1.3, 2.4.6.3),
served over the wire from the columnar replica of `lineitem`, compared
exactly with `benchmarks/configs/tpch_sf0p02/deployment.py`'s numpy
reference over the arrays made from the seed.  The benchmark makes the
same comparison on the chip at 131,072 rows (`correct`); here it is 4,096
rows on the CPU.  The deployment module, the statements and the mix are
loaded by path: nothing of `benchmarks/` is imported as a package."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.util import metrics

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
CONFIG_DIR = os.path.join(BENCH, "configs", "tpch_sf0p02")
ROWS = 4096
SEEDS = (2147483777, 20260928)   # one past 32 signed bits, as the driver's are
DRAWS = 6


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod   # a dataclass looks its module up by name
    spec.loader.exec_module(mod)
    return mod


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def forget_root_programs() -> None:
    """The root's merges are built into the one cache that every store of a
    process shares (`exec.executor.DEFAULT_PROGRAM_CACHE`): a file that ran
    the same statement on this worker before has left its merge program and
    its input rung there.  A module that counts what its first executions
    build empties that cache first, as a fresh server's is."""
    from tidb_tpu.exec.executor import DEFAULT_PROGRAM_CACHE

    DEFAULT_PROGRAM_CACHE._cache.clear()
    DEFAULT_PROGRAM_CACHE._input_rungs.clear()


class Served:
    """One seed's deployment, loaded and replicated, behind a wire client."""

    def __init__(self, seed: int):
        self.dep = _load(os.path.join(CONFIG_DIR, "deployment.py"), "tpch_sf0p02_deployment")
        traffic = _load(os.path.join(BENCH, "harness", "traffic.py"), "bench_traffic")
        self.config = dict(_json(os.path.join(CONFIG_DIR, "config.json")), lineitem_rows=ROWS)
        self.mix = traffic.Mix(_json(os.path.join(BENCH, "traffic", "q1q6_params.json")),
                               _json(os.path.join(CONFIG_DIR, "statements.json")), self.config)
        self.rng = traffic.client_rng(seed, 0, 1)
        self.data = self.dep.generate(self.config, seed)
        self.srv = MySQLServer(port=0)
        self.srv.start_background()
        self.conn = MiniClient(self.srv.host, self.srv.port, timeout=600.0)
        self.dep.load(self.conn, self.data, self.config, lambda **_line: None)
        self.conn.query(self.config["columnar_replica"]["ddl"])
        self.srv.store.pd.tick()
        (view,) = [v for v in self.srv.store.columnar.views() if v["table"] == "lineitem"]
        assert (view["state"], view["delta_rows"], view["stable_rows"]) == ("normal", 0, ROWS), view
        self.conn.query(f"set tidb_isolation_read_engines = '{self.mix.spec['read_engines']}'")
        # every draw is made up front, so that a case sees the same statements
        # whichever cases ran before it
        self.operations = [self.mix.operation(self.rng) for _ in range(DRAWS)]

    def close(self):
        self.conn.close()
        self.srv.close()


@pytest.fixture(scope="module", params=SEEDS, ids=lambda s: f"seed{s}")
def served(request):
    s = Served(request.param)
    yield s
    s.close()


@pytest.mark.parametrize("draw", range(DRAWS))
def test_served_q1_q6_equal_the_plain_reference(served, draw):
    """Exact: decimal sums in scaled integers, averages rounded half-up
    at the printed scale, every group and the count."""
    for step in served.operations[draw]:
        _, rows = served.conn.query(step.sql)
        want = served.dep.reference(step.name, step.params, served.data)
        assert served.dep.mismatch(step.name, want, rows) is None, (step.params, rows)
        assert len(rows) == served.dep.expected_rows(step.name, want)


def test_every_statement_rode_the_replica_and_none_fell_back(served):
    for op in served.operations:
        for step in op:
            scans, fallbacks = metrics.COLUMNAR_SCANS.value, metrics.COLUMNAR_FALLBACKS.value
            resident, oracle = metrics.COLUMNAR_RESIDENT_SCANS.value, metrics.COP_FALLBACKS.value
            served.conn.query(step.sql)
            assert metrics.COLUMNAR_SCANS.value == scans + 1, step.name
            assert metrics.COLUMNAR_RESIDENT_SCANS.value == resident + 1, step.name
            assert metrics.COLUMNAR_FALLBACKS.value == fallbacks, step.name
            assert metrics.COP_FALLBACKS.value == oracle, step.name


def test_the_float32_control_is_caught(served):
    """`control` answers with every sum accumulated in float32, the
    precision below the scaled int64 that the configuration states: the
    exact comparison has to call it wrong, or `correct` decides nothing."""
    wrong = compared = 0
    for op in served.operations:
        for step in op:
            want = served.dep.reference(step.name, step.params, served.data)
            rows = served.dep.control(step.name, step.params, served.data)
            compared += 1
            wrong += served.dep.mismatch(step.name, want, rows) is not None
    q1 = [s for op in served.operations for s in op if s.name == "q1"]
    assert compared == 2 * DRAWS and wrong >= len(q1), (wrong, compared)


def test_the_draws_follow_the_specs_rules(served):
    """2.4.1.3: DELTA in [60, 120]; 2.4.6.3: DATE the first of January of
    1993..1997, DISCOUNT in [0.02, 0.09], QUANTITY 24 or 25."""
    for op in served.operations:
        q1, q6 = op
        assert (q1.name, q6.name) == ("q1", "q6")
        assert 60 <= q1.params["delta"] <= 120
        assert q6.params["date"] in [f"{y}-01-01" for y in range(1993, 1998)]
        assert q6.params["discount"] in [f"0.0{d}" for d in range(2, 10)]
        assert q6.params["quantity"] in (24, 25)
    assert len({json.dumps(s.params, sort_keys=True) for op in served.operations for s in op}) > DRAWS


def test_the_generated_table_has_the_specs_shapes(served):
    line = served.data["lineitem"]
    assert len(line["orderkey"]) == ROWS and len(served.data["orders"]["orderkey"]) == ROWS // 4
    lines = np.bincount(line["oidx"])
    assert lines.min() >= 1 and lines.max() <= 7
    assert set(np.unique(line["returnflag"])) <= {"A", "N", "R"} and set(np.unique(line["linestatus"])) <= {"F", "O"}
    assert served.dep.scan_bytes("q1", served.config) == 38 * ROWS
    assert served.dep.scan_bytes("q6", served.config) == 28 * ROWS
