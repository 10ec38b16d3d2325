"""The `tpch_sf0p02_q18_mesh4` deployment at the cell's own size, 131,072
lineitem rows, in tier-1 (ISSUE 38): at the spec's QUANTITY (312-315) most
of the cell's answers are empty, so a fault that drops qualifying orders
leaves them as they are.  Here the tables are loaded by the deployment's
own `load`, which ends with the cell's set-up check (the inner statement
at QUANTITY 0, 150 and 250, compared as a multiset of orderkeys, raising
on a difference, then Q18 once at each of 312-315), and then Q18 and its inner statement are compared
exactly with `tests/tpch_reference.py` at QUANTITY 150 and 250, where
thousands and hundreds of orders qualify, on the suite's host devices with
`cop-debug-raise` armed."""

import os

import pytest

import tpch_reference as ref
from test_tpch_columnar_reference import BENCH, _json, _load

from tidb_tpu.server import MiniClient, MySQLServer
from tidb_tpu.util import failpoint, metrics

CONFIG_DIR = os.path.join(BENCH, "configs", "tpch_sf0p02_q18_mesh4")
SEED = 2038000404
QUANTITIES = (150, 250)
INNER = ("select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey "
         "having sum(l_quantity) > {quantity}")


@pytest.fixture(scope="module")
def served():
    dep = _load(os.path.join(CONFIG_DIR, "deployment.py"), "tpch_sf0p02_q18_mesh4_scale")
    config = _json(os.path.join(CONFIG_DIR, "config.json"))
    data = dep.generate(config, SEED)
    srv = MySQLServer(port=0)
    srv.start_background()
    conn = MiniClient(srv.host, srv.port, timeout=600.0)
    lines = []
    selects = metrics.MPP_SELECTS.value
    dep.load(conn, data, config, lambda **line: lines.append(line))
    checked = metrics.MPP_SELECTS.value - selects
    # the outer five-key GROUP BY holds one group per qualifying order:
    # 7,373 at QUANTITY 150, past the session's default table
    conn.query("set tidb_tpu_group_capacity = 16384")
    failpoint.enable("cop-debug-raise")
    try:
        got = {}
        for q in QUANTITIES:
            for name, sql in (("q18", _json(os.path.join(CONFIG_DIR, "statements.json"))["q18"]), ("inner", INNER)):
                before = metrics.MPP_SELECTS.value
                _, rows = conn.query(sql.format(quantity=q))
                got[name, q] = (rows, metrics.MPP_SELECTS.value - before)
    finally:
        failpoint.disable("cop-debug-raise")
        conn.close()
        srv.close()
    yield {"data": data, "lines": lines, "checked": checked, "got": got, "rows": config["lineitem_rows"]}


def test_the_set_up_check_compared_every_order_on_the_exchange_tier(served):
    checks = [x for x in served["lines"] if x.get("check") == "q18_inner"]
    assert [(x["quantity"], x["equal"]) for x in checks] == [(0, True), (150, True), (250, True)]
    assert checks[0]["rows"] == len(served["data"]["orders"]["orderkey"]) == served["rows"] // 4
    warm = [x for x in served["lines"] if x.get("warm") == "q18"]
    assert [(x["quantity"], x["equal"]) for x in warm] == [(q, True) for q in (312, 313, 314, 315)]
    assert served["checked"] == 3 + len(warm)   # every statement of set-up rode the exchange tier once
    (spec,) = [x for x in served["lines"] if x.get("check") == "q18_spec_answers"]
    assert all(n < 10 for n in spec["orders"].values())   # the spec's cut keeps a handful at this scale


@pytest.mark.parametrize("q", QUANTITIES)
def test_q18_at_the_cells_size_equals_the_plain_reference(served, q):
    rows, selects = served["got"]["q18", q]
    want = ref.ref_q18(served["data"], q)
    assert len(want) > 100 and ref.q18_mismatch(want, rows) is None, rows[:3]
    assert selects == 1


@pytest.mark.parametrize("q", QUANTITIES)
def test_the_inner_statement_at_the_cells_size_answers_every_order_and_its_sum(served, q):
    rows, selects = served["got"]["inner", q]
    want = ref.ref_q18_inner(served["data"], q)
    got = {int(k): s for k, s in rows}
    assert len(got) == len(rows) == len(want) and all(got[k] == f"{want[k]}.00" for k in want)
    assert selects == 1
