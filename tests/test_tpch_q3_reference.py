"""The `tpch_sf0p02_rowstore` deployment's Q3 against its plain reference, in tier-1:
"Shipping Priority" (TPC-H 2.4.3) with the substitution parameters of
2.4.3.3 (SEGMENT of five, DATE in March 1995), served over the wire from
the row store (`tidb_isolation_read_engines='tpu'`), compared exactly with
`benchmarks/configs/tpch_sf0p02_rowstore/deployment.py`'s numpy `ref_q3`
(that file re-exports `tpch_sf0p02`'s module: one generator and one reference
for both configurations) over the arrays made from the seed.  The benchmark's cell `tpch_q3_params` makes
the same comparison on the chip at 131,072 rows; here it is 4,096 on the
CPU.  One join program serves every SEGMENT and DATE (the literals are
its operands, the string too), the build sides come from the cop result
cache and stay uploaded, nothing falls back to the oracle.  The deployment
module, the statements and the mix are loaded by path."""

import json
import os

import pytest

from test_tpch_columnar_reference import BENCH, _json, _load

from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.util import metrics

CONFIG_DIR = os.path.join(BENCH, "configs", "tpch_sf0p02_rowstore")
ROWS = 4096
SEED = 2147483777   # one past 32 signed bits, as the driver's are
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
DATES = ("1995-03-01", "1995-03-15", "1995-03-31")
NAMES = ("PROGRAM_COMPILES", "XLA_COMPILES", "PROGRAM_LAUNCHES", "PROGRAM_PARAMS_BOUND", "PROGRAM_STR_PARAMS_BOUND",
         "COP_AUX_UPLOADS", "COP_CACHE_HITS", "COP_REQUESTS", "COP_FALLBACKS")


class Served:
    """The deployment loaded behind a wire client, Q3 run once (its first
    execution builds the programs), then every case with the counters read
    around it."""

    def __init__(self):
        self.dep = _load(os.path.join(CONFIG_DIR, "deployment.py"), "tpch_sf0p02_rowstore_deployment")
        self.config = dict(_json(os.path.join(CONFIG_DIR, "config.json")), lineitem_rows=ROWS)
        self.sql = _json(os.path.join(CONFIG_DIR, "statements.json"))["q3"]
        self.mix = _json(os.path.join(BENCH, "traffic", "q3_params.json"))
        self.data = self.dep.generate(self.config, SEED)
        self.srv = MySQLServer(port=0)
        self.srv.start_background()
        self.conn = MiniClient(self.srv.host, self.srv.port, timeout=600.0)
        self.dep.load(self.conn, self.data, self.config, lambda **_line: None)
        self.conn.query(f"set tidb_isolation_read_engines = '{self.mix['read_engines']}'")
        self.first = self.run({"segment": "BUILDING", "date": "1995-03-15"})   # the spec's validation parameters
        self.cases = {(s, d): self.run({"segment": s, "date": d}) for s in SEGMENTS for d in DATES}

    def run(self, params: dict, trace: bool = False) -> dict:
        before = {n: getattr(metrics, n).value for n in NAMES}
        _, rows = self.conn.query(("trace format='json' " if trace else "") + self.sql.format(**params))
        return {"params": params, "rows": rows, "moved": {n: getattr(metrics, n).value - before[n] for n in NAMES}}

    def close(self):
        self.conn.close()
        self.srv.close()


@pytest.fixture(scope="module")
def served():
    s = Served()
    yield s
    s.close()


@pytest.mark.parametrize("date", DATES)
@pytest.mark.parametrize("segment", SEGMENTS)
def test_served_q3_equals_the_plain_reference(served, segment, date):
    """Exact: the revenue in scaled integers, the ten largest in order."""
    got = served.cases[segment, date]
    want = served.dep.reference("q3", got["params"], served.data)
    assert served.dep.mismatch("q3", want, got["rows"]) is None, (got["params"], got["rows"])
    assert len(got["rows"]) == served.dep.expected_rows("q3", want)


def test_the_first_execution_builds_one_join_program_and_no_draw_another(served):
    first = served.first["moved"]
    # two build scans, the join, the root's merge; every launch after it calls what is there
    assert first["PROGRAM_COMPILES"] == 4 and first["COP_AUX_UPLOADS"] == 2 and first["COP_FALLBACKS"] == 0
    for key, got in served.cases.items():
        m = got["moved"]
        assert m["PROGRAM_COMPILES"] == m["XLA_COMPILES"] == 0, (key, m)
        assert m["COP_FALLBACKS"] == 0 and m["PROGRAM_LAUNCHES"] == 2, (key, m)
        assert m["PROGRAM_STR_PARAMS_BOUND"] == 1 and m["PROGRAM_PARAMS_BOUND"] == 4, (key, m)   # SEGMENT; two DATEs, 1 - l_discount


def test_build_sides_come_from_the_result_cache_and_stay_uploaded(served):
    """`orders` and `customer` are scanned without a literal: from the second
    statement on both are result-cache hits, and the chunk they hand over is
    the one already on the device, so `COP_AUX_UPLOADS` stays flat."""
    for key, got in served.cases.items():
        m = got["moved"]
        assert (m["COP_REQUESTS"], m["COP_CACHE_HITS"], m["COP_AUX_UPLOADS"]) == (3, 2, 0), (key, m)


def test_the_groups_straddle_a_rung_of_the_root_merge(served):
    """What the sticky rung is for (`ProgramCache.input_capacity`): the
    number of groups that reach the root moves with SEGMENT and DATE."""
    groups = {k: len(served.dep.reference("q3", v["params"], served.data)) for k, v in served.cases.items()}
    assert len(set(groups.values())) > 3, groups


def test_trace_shows_the_build_fetch_and_the_uploaded_build_sides(served):
    got = served.run({"segment": "MACHINERY", "date": "1995-03-09"}, trace=True)
    tree = json.loads(got["rows"][0][0])

    def find(node, name):
        return ([node] if node["name"] == name else []) + [n for c in node.get("children", ()) for n in find(c, name)]

    (build,) = find(tree, "session.join_build")
    orders, customer = len(served.data["orders"]["orderkey"]), len(served.data["customer"]["custkey"])
    assert build["attrs"]["tables"] == 2 and build["attrs"]["rows"] == orders + customer and build["attrs"]["bytes"] > 0
    assert [c["name"] for c in build["children"]] == ["distsql.execute_root", "distsql.execute_root"]
    assert [(a["attrs"]["rows"], a["attrs"]["hit"]) for a in find(tree, "cop.aux_batch")] == [(orders, True), (customer, True)]
    assert [n["attrs"]["program"] for n in find(tree, "exec.launch")] == ["cop_scan_sel_join_join_groupagg", "cop_scan_groupagg_topn"]
    assert find(tree, "cop.decode") and not find(tree, "exec.compile") and not find(tree, "cop.oracle_fallback")
    assert tree["attrs"]["rows"] == served.dep.expected_rows(
        "q3", served.dep.reference("q3", got["params"], served.data))


def test_the_float32_control_is_caught(served):
    wrong = 0
    for got in served.cases.values():
        want = served.dep.reference("q3", got["params"], served.data)
        wrong += served.dep.mismatch("q3", want, served.dep.control("q3", got["params"], served.data)) is not None
    assert wrong >= len(served.cases) // 2, wrong


def test_the_mix_draws_the_specs_parameters():
    """2.4.3.3: SEGMENT one of the five, DATE a day of March 1995."""
    (step,) = _json(os.path.join(BENCH, "traffic", "q3_params.json"))["operation"]
    assert step["statement"] == "q3" and sorted(step["params"]["segment"]["choice"]) == sorted(SEGMENTS)
    assert step["params"]["date"]["choice"] == [f"1995-03-{d:02d}" for d in range(1, 32)]
