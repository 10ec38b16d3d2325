"""The sysbench reference against the program's five statement shapes, on
the CPU at 2 tables of 2,000 rows over the wire; and its control, which has to differ."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG_DIR = os.path.join(os.path.dirname(HERE), "configs", "sysbench_32x16k")


def _deployment():
    from harness.catalog import load_module

    return load_module(os.path.join(CONFIG_DIR, "deployment.py"), "sysbench_dep")


@pytest.fixture(scope="module")
def served():
    from tidb_tpu.server import MiniClient, MySQLServer

    dep = _deployment()
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        config = json.load(f)
    config.update(tables=2, table_size=2000, insert_batch_rows=500)
    with open(os.path.join(CONFIG_DIR, "statements.json")) as f:
        statements = json.load(f)
    data = dep.generate(config, seed=7)
    srv = MySQLServer(port=0)
    srv.start_background()
    conn = MiniClient(srv.host, srv.port, timeout=600)
    try:
        dep.load(conn, data, config, lambda **line: None)
        yield dep, data, statements, conn
    finally:
        conn.close()
        srv.close()


def test_value_rules():
    dep = _deployment()
    sizes = {"tables": 3, "table_size": 300, "insert_batch_rows": 100}
    data = dep.generate(sizes, seed=2**31 + 5)
    assert data["k"].shape == data["c"].shape == (3, 300) and len(data["pad"]) == 900
    assert len(data["c"][0, 0]) == 119 and data["c"][0, 0].count(b"-") == 9
    assert len(data["pad"][0]) == 59 and data["pad"][0].count("-") == 4
    assert 1 <= data["k"].min() and data["k"].max() <= 300
    assert (data["c"][0] != data["c"][1]).all()          # every table has rows of its own
    again = dep.generate(sizes, seed=2**31 + 5)
    assert (again["k"] == data["k"]).all() and (again["c"] == data["c"]).all()


@pytest.mark.parametrize("name", ["point_select", "simple_range", "sum_range", "order_range", "distinct_range"])
def test_statement_equals_reference(served, name):
    dep, data, statements, conn = served
    rng = np.random.default_rng(11)
    conn.query("begin")
    for a in [int(rng.integers(1, 2000 + 1)), int(rng.integers(1, 2000 + 1)), 1950]:   # the last runs off the table's end
        t = int(rng.integers(1, 3))
        params = {"t": t, "id": a} if name == "point_select" else {"t": t, "a": a, "b": a + 99}
        _, rows = conn.query(statements[name].format(**params))
        want = dep.reference(name, params, data)
        assert dep.mismatch(name, want, rows) is None
        assert dep.expected_rows(name, want) == len(rows)
    conn.query("commit")


def test_reference_tells_a_wrong_answer(served):
    dep, data, statements, conn = served
    params = {"t": 2, "a": 10, "b": 109}
    for name in ("simple_range", "order_range", "distinct_range"):
        _, rows = conn.query(statements[name].format(**params))
        assert dep.mismatch(name, dep.reference(name, params, data), rows[:-1]) is not None
        rows[0] = [rows[0][0][:-1] + ("0" if rows[0][0][-1] != "0" else "1")]
        assert dep.mismatch(name, dep.reference(name, params, data), rows) is not None
    # order matters where the statement orders
    _, rows = conn.query(statements["order_range"].format(**params))
    assert dep.mismatch("order_range", dep.reference("order_range", params, data), rows[::-1]) is not None


def test_control_loses_each_tables_last_insert(served):
    dep, data, statements, conn = served
    lost = {"t": 1, "a": 1950, "b": 2000}
    for name in ("simple_range", "sum_range", "order_range", "distinct_range"):
        assert dep.mismatch(name, dep.reference(name, lost, data), dep.control(name, lost, data)) is not None
    assert dep.control("point_select", {"t": 2, "id": 1999}, data) == []
    kept = {"t": 2, "a": 100, "b": 199}   # rows that the control still holds read the same
    for name in ("simple_range", "sum_range", "order_range", "distinct_range"):
        assert dep.mismatch(name, dep.reference(name, kept, data), dep.control(name, kept, data)) is None
