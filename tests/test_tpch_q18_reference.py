"""The `tpch_sf0p02_q18_mesh4` deployment against its plain reference, in
tier-1 (ISSUE 38): TPC-H Q18 and its inner statement, served over the wire
from the row store of tables that the deployment's own `load` cut into
regions with `SPLIT TABLE` (lineitem 8, orders 4), on the suite's eight
host devices with `tidb_enable_tpu_mesh` and `tidb_allow_mpp` at their
defaults and `cop-debug-raise` armed.  QUANTITY runs from 150, where
hundreds of orders qualify, to the spec's 312-315, where none or a few do;
every answer is compared exactly with `tests/tpch_reference.py` (every
qualifying orderkey and its sum for the inner statement).  The inner
GROUP BY ... HAVING rides the exchange tier with its HAVING behind the
final aggregate in the same program; the outer statement is one program
whatever the inner answer's size (a semi join against the materialised set
at a sticky capacity rung); the lanes stacked for the one tier serve the
other; the ORDER BY statements of `tpch_sf0p02_mesh4` stay on the
per-request mesh tier.  The benchmark's cell `tpch_q18_mesh4` makes the
comparison on four chips at 131,072 rows; here it is 4,096."""

import json
import os

import pytest

import tpch_reference as ref
from test_tpch_columnar_reference import BENCH, _json, _load, forget_root_programs

from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.util import failpoint, metrics

CONFIG_DIR = os.path.join(BENCH, "configs", "tpch_sf0p02_q18_mesh4")
ROWS = 4096
SEED = 2147483791
QUANTITIES = (150, 200, 250, 300, 312, 313, 314, 315)
INNER = ("select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey "
         "having sum(l_quantity) > {quantity}")
NAMES = ("PROGRAM_COMPILES", "XLA_COMPILES", "PROGRAM_LAUNCHES", "MPP_SELECTS", "MPP_FALLBACKS", "MESH_COP_BATCHES",
         "MESH_COP_FALLBACKS", "COP_FALLBACKS", "MESH_STACK_HITS", "MESH_STACK_MISSES", "MPP_TAIL_STATEMENTS",
         "SUBQUERY_MATERIALIZED_ROWS", "ROOT_FUSED_STATEMENTS")


class Served:
    def __init__(self):
        from tidb_tpu.mpp import dispatch

        self.dep = _load(os.path.join(CONFIG_DIR, "deployment.py"), "tpch_sf0p02_q18_mesh4_deployment")
        self.config = dict(_json(os.path.join(CONFIG_DIR, "config.json")), lineitem_rows=ROWS)
        self.sql = dict(_json(os.path.join(CONFIG_DIR, "statements.json")), inner=INNER)
        self.data = self.dep.generate(self.config, SEED)
        self.srv = MySQLServer(port=0)
        self.srv.start_background()
        self.conn = MiniClient(self.srv.host, self.srv.port, timeout=600.0)
        # the tables as the cell loads them, without its set-up check of
        # the inner statement (tested on its own below): the statements
        # here are a fresh server's first
        self.dep._mesh4.load(self.conn, self.data, self.config, lambda **_line: None)
        self.conn.query("set tidb_isolation_read_engines = 'tpu'")
        forget_root_programs()
        dispatch._LADDER_HINTS.clear()    # counted as a fresh server's first statements
        failpoint.enable("cop-debug-raise")
        try:
            self.cases = {}
            for q in QUANTITIES:
                for name in ("q18", "inner"):
                    self.cases[name, q] = self.run(name, q)
            self.conn.query("set tidb_allow_mpp = OFF")
            self.off = {(name, q): self.run(name, q) for q in QUANTITIES for name in ("q18", "inner")}
            self.conn.query("set tidb_allow_mpp = ON")
        finally:
            failpoint.disable("cop-debug-raise")

    def run(self, name: str, q: int, trace: bool = False) -> dict:
        before = {n: getattr(metrics, n).value for n in NAMES}
        _, rows = self.conn.query(("trace format='json' " if trace else "") + self.sql[name].format(quantity=q))
        return {"rows": rows, "moved": {n: getattr(metrics, n).value - before[n] for n in NAMES}}

    def close(self):
        self.conn.close()
        self.srv.close()


@pytest.fixture(scope="module")
def served():
    s = Served()
    yield s
    s.close()


def find(node: dict, name: str) -> list:
    return ([node] if node["name"] == name else []) + [n for c in node.get("children", ()) for n in find(c, name)]


def test_the_thresholds_cover_answers_of_hundreds_of_orders_and_empty_ones(served):
    sizes = [len(ref.ref_q18_inner(served.data, q)) for q in QUANTITIES]
    assert sizes[0] > 100 and sizes == sorted(sizes, reverse=True) and sizes[-1] < 5 and 0 in sizes


@pytest.mark.parametrize("q", QUANTITIES)
def test_q18_equals_the_plain_reference(served, q):
    got = served.cases["q18", q]["rows"]
    assert ref.q18_mismatch(ref.ref_q18(served.data, q), got) is None, got[:3]


@pytest.mark.parametrize("q", QUANTITIES)
def test_the_inner_statement_answers_every_qualifying_order_and_its_sum(served, q):
    got = {int(k): s for k, s in served.cases["inner", q]["rows"]}
    want = ref.ref_q18_inner(served.data, q)
    assert len(got) == len(served.cases["inner", q]["rows"]) and set(got) == set(want)
    assert all(got[k] == f"{want[k]}.00" for k in want)


@pytest.mark.parametrize("q", QUANTITIES)
def test_each_statement_rides_the_exchange_tier_once_and_nothing_falls_back(served, q):
    for name in ("q18", "inner"):
        m = served.cases[name, q]["moved"]
        assert (m["MPP_SELECTS"], m["MPP_FALLBACKS"], m["COP_FALLBACKS"], m["MESH_COP_FALLBACKS"]) == (1, 0, 0, 0), m
        assert m["MPP_TAIL_STATEMENTS"] == 1, m                 # HAVING behind the final aggregate, in the program
    m = served.cases["q18", q]["moved"]
    assert (m["MESH_COP_BATCHES"], m["ROOT_FUSED_STATEMENTS"]) == (1, 1), m   # the outer join: one mesh program
    assert m["SUBQUERY_MATERIALIZED_ROWS"] == len(ref.ref_q18_inner(served.data, q))


@pytest.mark.parametrize("q", QUANTITIES)
def test_the_per_request_tier_answers_the_same_rows_with_mpp_off(served, q):
    assert served.off["q18", q]["rows"] == served.cases["q18", q]["rows"]
    assert sorted(served.off["inner", q]["rows"]) == sorted(served.cases["inner", q]["rows"])
    for name in ("q18", "inner"):
        assert served.off[name, q]["moved"]["MPP_SELECTS"] == 0


def test_one_outer_program_whatever_the_inner_answer(served):
    """The first threshold builds the programs; no later one builds any,
    from hundreds of orders in the set down to none."""
    first = served.cases["q18", QUANTITIES[0]]["moved"]
    assert first["PROGRAM_COMPILES"] >= 2, first                 # the exchange program and the outer join's
    for name in ("q18", "inner"):
        for q in QUANTITIES[1:]:
            m = served.cases[name, q]["moved"]
            assert m["PROGRAM_COMPILES"] == m["XLA_COMPILES"] == 0, (name, q, m)
    assert served.cases["q18", QUANTITIES[1]]["moved"]["PROGRAM_LAUNCHES"] == 2    # the exchange, the join


def test_no_statement_after_the_first_stacks_the_lanes_again(served):
    """Both tiers read lineitem's same columns and ranges: the exchange tier
    stacks the lanes once, and every later launch of either finds them."""
    first = served.cases["q18", QUANTITIES[0]]["moved"]
    assert (first["MESH_STACK_MISSES"], first["MESH_STACK_HITS"]) == (1, 1)
    for name in ("q18", "inner"):
        for q in QUANTITIES:
            if (name, q) != ("q18", QUANTITIES[0]):
                m = served.cases[name, q]["moved"]
                assert (m["MESH_STACK_MISSES"], m["MESH_STACK_HITS"]) == (0, 2 if name == "q18" else 1), (name, q, m)


@pytest.mark.parametrize("q", (312, 313, 314, 315))
def test_the_control_is_told_apart_from_the_reference(served, q):
    want = served.dep.reference("q18", {"quantity": q}, served.data)
    assert served.dep.mismatch("q18", want, served.dep.control("q18", {"quantity": q}, served.data)) is not None
    assert served.dep.mismatch("q18", want, served.cases["q18", q]["rows"]) is None


@pytest.mark.parametrize("q", QUANTITIES)
def test_the_benchmarks_reference_is_the_suites(served, q):
    mine, theirs = ref.ref_q18(served.data, q), served.dep.reference("q18", {"quantity": q}, served.data)
    assert mine == theirs and ref.ref_q18_inner(served.data, q) == served.dep.ref_q18_inner(served.data, {"quantity": q})


def test_a_traced_q18_shows_the_subquery_the_exchange_and_its_tail(served):
    q = 250
    got = served.run("q18", q, trace=True)
    tree = json.loads(got["rows"][0][0])
    (sub,) = find(tree, "session.subquery")
    n = len(ref.ref_q18_inner(served.data, q))
    assert sub["attrs"]["form"] == "semi_join" and sub["attrs"]["rows"] == n
    (dispatch,) = find(sub, "mpp.dispatch")
    assert [c["name"] for c in dispatch["children"]] == ["mpp.scan", "mesh.stack", "mpp.exchange", "mpp.tail"]
    stack, exchange, tail = dispatch["children"][1:]
    assert stack["attrs"]["hit"] is True and exchange["attrs"]["retries"] == 0
    assert tail["attrs"] == {"executors": ["Selection"], "in_program": True,
                             "rows_in": len(served.data["orders"]["orderkey"]), "rows_out": n}
    (launch,) = find(exchange, "exec.launch")
    assert launch["attrs"]["program"] == "mesh_exchange_group_agg" and not find(tree, "exec.compile")
    outer = [r for r in find(tree, "distsql.execute_root") if find(r, "cop.mesh_execute")]
    assert [n_["attrs"]["program"] for r in outer for n_ in find(r, "exec.launch")] == [
        "cop_scan_join_join_semijoin_groupagg_topn_m8x8"]


@pytest.mark.parametrize("name", ("q1", "q6", "q3"))
def test_the_order_by_statements_of_the_mesh_cell_stay_off_the_exchange_tier(served, name):
    sql = _json(os.path.join(BENCH, "configs", "tpch_sf0p02_mesh4", "statements.json"))[name]
    params = {"q1": {"delta": 90}, "q6": {"date": "1994-01-01", "discount": "0.06", "quantity": 24},
              "q3": {"segment": "BUILDING", "date": "1995-03-15"}}[name]
    before = {n: getattr(metrics, n).value for n in NAMES}
    _, rows = served.conn.query(sql.format(**params))
    moved = {n: getattr(metrics, n).value - before[n] for n in NAMES}
    assert moved["MPP_SELECTS"] == 0 and moved["MESH_COP_BATCHES"] == 1, moved
    want = served.dep.reference(name, params, served.data)
    assert served.dep.mismatch(name, want, rows) is None


class _Dropping:
    """A connection whose inner statement loses the orders on one chip's
    lanes (`drop`) or answers each order of those lanes twice (`twice`),
    as an exchange that dropped a chip's partial state or finished one
    group on two chips would."""

    def __init__(self, conn, keys, fault: str):
        self.conn, self.keys, self.fault = conn, {str(k) for k in keys}, fault

    def query(self, sql):
        if "having" not in sql:
            return self.conn.query(sql)
        cols, rows = self.conn.query(sql)
        if self.fault == "drop":
            return cols, [r for r in rows if r[0] not in self.keys]
        return cols, rows + [r for r in rows if r[0] in self.keys]


@pytest.mark.parametrize("fault", (None, "drop", "twice"))
def test_the_set_up_check_compares_the_inner_statement_and_ends_a_wrong_run(served, fault):
    """The cell's set-up check (`deployment.check_inner`) runs the inner
    statement at QUANTITY 0, 150 and 250 on the exchange tier and compares
    its orderkeys as a multiset: an answer that lost or doubled the orders
    of one lane ends the run; the right one passes and says how many of the
    spec's thresholds keep any order."""
    lines = []
    lane = served.data["orders"]["orderkey"][served.data["lineitem"]["oidx"][: ROWS // 8]]
    conn = served.conn if fault is None else _Dropping(served.conn, lane, fault)
    before = metrics.MPP_SELECTS.value
    if fault is None:
        served.dep.check_inner(conn, served.data, lambda **line: lines.append(line))
        assert [(x["quantity"], x["equal"]) for x in lines[:3]] == [(0, True), (150, True), (250, True)]
        assert lines[0]["rows"] == len(served.data["orders"]["orderkey"])
        assert lines[3]["check"] == "q18_spec_answers" and set(lines[3]["orders"]) == {312, 313, 314, 315}
        assert metrics.MPP_SELECTS.value - before == 3
    else:
        with pytest.raises(SystemExit, match="QUANTITY 0"):
            served.dep.check_inner(conn, served.data, lambda **line: lines.append(line))
        assert lines[-1]["equal"] is False


class _Recording:
    """A connection that records the statements it is sent."""

    def __init__(self, conn):
        self.conn, self.sent = conn, []

    def query(self, sql):
        self.sent.append(sql)
        return self.conn.query(sql)


def test_the_set_up_warms_q18_at_every_quantity_the_mix_draws(served):
    """The cell's set-up ends with Q18, the mix's text word for word, once
    at each of QUANTITY 312-315 (`deployment.warm_spec_quantities`): an
    engine whose outer program takes its shape from the inner answer's size
    builds every such program there and not in the window.  Here the outer
    program is one already, so the warm-up builds nothing, and each answer
    is the reference's."""
    lines, conn = [], _Recording(served.conn)
    before = metrics.PROGRAM_COMPILES.value
    served.dep.warm_spec_quantities(conn, served.data, lambda **line: lines.append(line))
    assert conn.sent == [served.sql["q18"].format(quantity=q) for q in (312, 313, 314, 315)]
    assert [(x["warm"], x["quantity"], x["equal"]) for x in lines] == [("q18", q, True) for q in (312, 313, 314, 315)]
    assert [x["rows"] for x in lines] == [min(len(ref.ref_q18(served.data, q)), 100) for q in (312, 313, 314, 315)]
    assert metrics.PROGRAM_COMPILES.value == before
