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
cache and stay uploaded, `lineitem`'s decoded chunk and device batch stay
resident from one statement to the next (PR 33), nothing falls back to the
oracle.  The table is one region, so its one cop task is the statement's
whole input and runs the unsplit DAG: one program and one read-back a
statement, no merge at the root, the rows those of the split path (ISSUE
37).  The deployment module, the statements and the mix are loaded by path."""

import json
import os

import numpy as np
import pytest

from test_root_exec import root_half_left_off
from test_tpch_columnar_reference import BENCH, _json, _load, forget_root_programs

from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.util import metrics

CONFIG_DIR = os.path.join(BENCH, "configs", "tpch_sf0p02_rowstore")
ROWS = 4096
SEED = 2147483777   # one past 32 signed bits, as the driver's are
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
DATES = ("1995-03-01", "1995-03-15", "1995-03-31")
NAMES = ("PROGRAM_COMPILES", "XLA_COMPILES", "PROGRAM_LAUNCHES", "PROGRAM_PARAMS_BOUND", "PROGRAM_STR_PARAMS_BOUND",
         "COP_AUX_UPLOADS", "COP_CACHE_HITS", "COP_REQUESTS", "COP_FALLBACKS", "COP_DECODE_HITS", "COP_DECODE_MISSES",
         "NATIVE_DECODES", "PROGRAM_FETCHES", "ROOT_FUSED_STATEMENTS", "ROOT_FUSE_FALLBACKS")


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
        forget_root_programs()   # the first executions below are counted as a fresh server's
        self.first = self.run({"segment": "BUILDING", "date": "1995-03-15"})   # the spec's validation parameters
        self.cases = {(s, d): self.run({"segment": s, "date": d}) for s in SEGMENTS for d in DATES}
        with root_half_left_off():
            self.split = {(s, d): self.run({"segment": s, "date": d}) for s in SEGMENTS for d in DATES}

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
    # two build scans and the statement's one program; every launch after it calls what is there
    assert first["PROGRAM_COMPILES"] == 3 and first["COP_AUX_UPLOADS"] == 2 and first["COP_FALLBACKS"] == 0
    for key, got in served.cases.items():
        m = got["moved"]
        assert m["PROGRAM_COMPILES"] == m["XLA_COMPILES"] == 0, (key, m)
        assert m["COP_FALLBACKS"] == 0 and m["PROGRAM_LAUNCHES"] == m["PROGRAM_FETCHES"] == 1, (key, m)
        assert (m["ROOT_FUSED_STATEMENTS"], m["ROOT_FUSE_FALLBACKS"]) == (1, 0), (key, m)
        assert m["PROGRAM_STR_PARAMS_BOUND"] == 1 and m["PROGRAM_PARAMS_BOUND"] == 4, (key, m)   # SEGMENT; two DATEs, 1 - l_discount


@pytest.mark.parametrize("date", DATES)
@pytest.mark.parametrize("segment", SEGMENTS)
def test_the_one_program_answers_the_split_paths_rows(served, segment, date):
    """The lone cop task ran the unsplit DAG; with the root's half left off
    the same statement is the join program and the root's merge, two
    launches and two read-backs, and answers the same ten rows in the same
    order."""
    fused, split = served.cases[segment, date], served.split[segment, date]
    assert fused["rows"] == split["rows"]
    m = split["moved"]
    assert m["PROGRAM_LAUNCHES"] == m["PROGRAM_FETCHES"] == 2 and m["COP_FALLBACKS"] == 0, m
    assert (m["ROOT_FUSED_STATEMENTS"], m["ROOT_FUSE_FALLBACKS"]) == (0, 1), m


def test_build_sides_come_from_the_result_cache_and_stay_uploaded(served):
    """`orders` and `customer` are scanned without a literal: from the second
    statement on both are result-cache hits, and the chunk they hand over is
    the one already on the device, so `COP_AUX_UPLOADS` stays flat."""
    for key, got in served.cases.items():
        m = got["moved"]
        assert (m["COP_REQUESTS"], m["COP_CACHE_HITS"], m["COP_AUX_UPLOADS"]) == (3, 2, 0), (key, m)


def test_lineitem_is_decoded_once_and_found_resident_by_every_later_draw(served):
    """The probe scan's result depends on the drawn literals and cannot be
    cached; its input, `lineitem`'s decoded columns on the device, is the
    same for every draw: the first Q3 decodes the three tables, every
    later one finds `lineitem` resident."""
    first = served.first["moved"]
    assert (first["COP_DECODE_MISSES"], first["COP_DECODE_HITS"], first["NATIVE_DECODES"]) == (3, 0, 3)
    for key, got in served.cases.items():
        m = got["moved"]
        assert (m["COP_DECODE_HITS"], m["COP_DECODE_MISSES"], m["NATIVE_DECODES"]) == (1, 0, 0), (key, m)


def test_the_groups_straddle_a_rung_of_the_root_merge(served):
    """What the sticky rung is for (`ProgramCache.input_capacity`): the
    number of groups that reach the root moves with SEGMENT and DATE, and
    where the root still merges (the split path) every draw after the
    first calls the one merge program."""
    groups = {k: len(served.dep.reference("q3", v["params"], served.data)) for k, v in served.cases.items()}
    assert len(set(groups.values())) > 3, groups
    assert sum(v["moved"]["PROGRAM_COMPILES"] for v in served.split.values()) == 2   # the partial join program, the merge


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
    assert [n["attrs"]["program"] for n in find(tree, "exec.launch")] == ["cop_scan_sel_join_join_groupagg_topn"]
    (execute,) = [n for n in find(tree, "cop.execute") if find(n, "exec.launch")]
    assert execute["attrs"]["root_fused"] is True and not find(tree, "distsql.root_merge")
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


def _find(node, name):
    return ([node] if node["name"] == name else []) + [n for c in node.get("children", ()) for n in _find(c, name)]


def test_three_draws_hit_then_an_insert_misses_once_and_the_answer_follows_the_data(served):
    """Last in the file: it writes to `lineitem` (and takes the row out
    again)."""
    draws = [{"segment": "FURNITURE", "date": "1995-03-05"}, {"segment": "HOUSEHOLD", "date": "1995-03-22"},
             {"segment": "AUTOMOBILE", "date": "1995-03-29"}]
    for p in draws:
        plain, traced = served.run(p), served.run(p, trace=True)
        want = served.dep.reference("q3", p, served.data)
        assert served.dep.mismatch("q3", want, plain["rows"]) is None, p
        (decode,) = _find(json.loads(traced["rows"][0][0]), "cop.decode")     # the build scans end above it
        assert decode["attrs"]["hit"] is True and decode["attrs"]["rows"] == ROWS
        for m in (plain["moved"], traced["moved"]):
            assert (m["COP_DECODE_MISSES"], m["COP_DECODE_HITS"], m["COP_AUX_UPLOADS"], m["NATIVE_DECODES"]) == (0, 1, 0, 0), m

    # one more line on the order that leads the first draw's answer, large enough to be seen
    p = draws[0]
    want = served.dep.reference("q3", p, served.data)
    lead = max(want, key=lambda k: want[k][0])
    o, l = served.data["orders"], served.data["lineitem"]
    oidx = int(np.flatnonzero(o["orderkey"] == lead)[0])
    line = {"oidx": oidx, "extendedprice": 9_999_999_00, "discount": 5,
            "shipdate": int((np.datetime64("1998-01-01") - served.dep.EPOCH).astype(int))}
    served.conn.query(
        f"insert into lineitem values ({lead}, 1, 1, 8, 1.00, 9999999.00, 0.05, 0.00, 'N', 'O', "
        "'1998-01-01', '1998-01-01', '1998-01-02', 'NONE', 'AIR', 'the row store keeps what it decoded')")
    try:
        grown = dict(served.data, lineitem={k: np.append(v, line[k]) if k in line else v for k, v in l.items()})
        want_new = served.dep.reference("q3", p, grown)
        assert want_new[lead][0] > want[lead][0]
        fourth, fifth = served.run(p, trace=True), served.run(p)
        decodes = _find(json.loads(fourth["rows"][0][0]), "cop.decode")
        assert sorted((d["attrs"]["rows"], d["attrs"]["hit"]) for d in decodes) == sorted(
            [(len(served.data["customer"]["custkey"]), False), (len(o["orderkey"]), False), (ROWS + 1, False)])
        assert (fourth["moved"]["COP_DECODE_MISSES"], fourth["moved"]["COP_DECODE_HITS"]) == (3, 0)   # each table once
        assert (fifth["moved"]["COP_DECODE_MISSES"], fifth["moved"]["COP_DECODE_HITS"]) == (0, 1)
        assert served.dep.mismatch("q3", want_new, fifth["rows"]) is None
        assert served.dep.mismatch("q3", want, fifth["rows"]) is not None
    finally:
        served.conn.query(f"delete from lineitem where l_orderkey = {lead} and l_linenumber = 8")
    assert served.dep.mismatch("q3", want, served.run(p)["rows"]) is None
