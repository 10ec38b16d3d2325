"""The cell `tpch_q1q6q3_mesh4` (configuration `tpch_sf0p02_mesh4`, mix
`q1q6q3_params`) and its per-layer metrics.  The mix through
`test_traffic.py`'s rules; whole small runs of the cell on the CPU's host
devices, plain, traced and under `--control`; the counter readers on a
window's counters with `program_names.mesh.json`'s names and without; the
span readers on two recorded trees: TPC-H Q3 served by the per-request mesh
tier (4,096 lineitem rows in 8 regions over 8 host devices; `mesh.stack`
inside `cop.mesh_execute`) and a grouped join served by the exchange tier
(`mpp.dispatch` with `mpp.scan`, `mesh.stack`, `mpp.exchange` inside
`distsql.execute_root`).  The cross-chip tiers need more than one device, so
this file asks the CPU backend for four before JAX starts one: `small_run`
of the other cells reads one region a table and is served as with one."""

import json
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()

import pytest  # noqa: E402

import test_traffic  # noqa: E402
from harness import catalog, spans  # noqa: E402
from harness.traffic import client_rng  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tpch_q1q6q3_mesh4"
COP = "distsql + store cop / columnar route"
# test_traffic.py finds every mix by its file and its configuration in this
# table, which a PR that adds a mix cannot edit: the new mix's row is put
# there as the tests are collected, so its rules run over it too
test_traffic.CONFIG_OF.setdefault("q1q6q3_params", "tpch_sf0p02_mesh4")


def data_of(fixture: str) -> dict:
    with open(os.path.join(HERE, "data", fixture)) as f:
        return json.load(f)


def run_of(fixture: str) -> dict:
    tree = data_of(fixture)
    return {"self_times_ms_per_op": {k: round(v / 1e6, 4) for k, v in spans.self_times(tree).items()},
            "traced": [spans.layers([tree], latency_ns=tree["duration_ns"])], "attempted": 1, "counters": {}}


def find(node: dict, name: str) -> list:
    return ([node] if node["name"] == name else []) + [n for c in node.get("children", ()) for n in find(c, name)]


# ---- the manifest

def test_the_cell_is_in_the_manifest_with_its_configuration_and_metrics():
    cell = catalog.Cell(CELL)
    assert (cell.chips, cell.entry["config"], cell.entry["traffic"]) == (4, "tpch_sf0p02_mesh4", "q1q6q3_params")
    assert cell.config["layout"]["chips"] == 4 and len(cell.config["layout"]["split"]) == 2
    assert set(cell.statements) == {"q1", "q6", "q3"} and callable(cell.deployment.load)
    per_layer = {m["name"]: m for m in cell.metrics("per_layer")}
    for name in ("mesh_statements_per_op", "mesh_fallbacks_per_op", "mesh_stack_ms_per_op"):
        assert per_layer[name]["workloads"] == [CELL] and per_layer[name]["layer"] == COP
        assert callable(cell.reader("per_layer", name))
    assert {"frontend_ms_per_op", "cop_host_ms_per_op", "launches_per_op", "programs_built_per_op",
            "cop_cache_hits_per_op", "device_idle_pct"} <= set(per_layer)
    assert "device_roofline" not in per_layer and "mpp_exchange_ms_per_op" not in per_layer
    four = [w["name"] for w in cell.manifest["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= len(cell.manifest["workloads"]) // 2


def test_program_names_mesh_names_only_counters_the_program_had_before():
    names = data_of(os.path.join(os.pardir, os.pardir, "program_names.mesh.json"))["counters"]
    assert sorted(names.values()) == ["MESH_COP_BATCHES", "MESH_COP_FALLBACKS", "MESH_COP_LANES",
                                      "MPP_EXCHANGED_BYTES", "MPP_FALLBACKS", "MPP_SELECTS"]
    assert set(names) <= set(catalog.program_names()["counters"])
    from tidb_tpu.util import metrics
    assert all(hasattr(getattr(metrics, attr), "value") for attr in names.values())


# ---- the mix

def test_the_mix_draws_every_parameter_of_the_three_statements_inside_the_specs_ranges():
    mix = test_traffic._mix("q1q6q3_params")
    assert mix.clients == 2 and mix.spec["read_engines"] == "tpu" and mix.statement_names() == ["q1", "q6", "q3"]
    rng = client_rng(2**31 + 11, 0, 1)
    seen = {"delta": set(), "date6": set(), "discount": set(), "quantity": set(), "segment": set(), "date3": set()}
    for _ in range(400):
        q1, q6, q3 = mix.operation(rng)
        assert (q1.name, q6.name, q3.name) == ("q1", "q6", "q3") and "{" not in q1.sql + q6.sql + q3.sql
        seen["delta"].add(q1.params["delta"])
        seen["date6"].add(q6.params["date"]); seen["discount"].add(q6.params["discount"]); seen["quantity"].add(q6.params["quantity"])
        seen["segment"].add(q3.params["segment"]); seen["date3"].add(q3.params["date"])
    assert seen["delta"] == set(range(60, 121))
    assert seen["date6"] == {f"{y}-01-01" for y in range(1993, 1998)}
    assert seen["discount"] == {f"0.0{d}" for d in range(2, 10)} and seen["quantity"] == {24, 25}
    assert seen["segment"] == {"AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"}
    assert seen["date3"] == {f"1995-03-{d:02d}" for d in range(1, 32)}
    # what a statement draws here is what the one-chip cells draw for it
    for name, other in (("q1", "q1q6_params"), ("q6", "q1q6_params"), ("q3", "q3_params")):
        mine = next(s for s in mix.spec["operation"] if s["statement"] == name)
        theirs = next(s for s in test_traffic._mix(other).spec["operation"] if s["statement"] == name)
        assert mine == theirs


# ---- whole small runs (conftest's small_run): no measurements

def test_sound_run_is_correct_and_every_statement_is_a_cross_chip_program(small_run, capsys):
    line = small_run(CELL, 2**31 + 23, 8.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["wrong_answers"]["value"] == 0 and line["compared"]["statements_compared"]["value"] >= 3
    assert {"ops_per_s", "op_p50_ms", "setup_s"} == set(line["metrics"])
    assert line["device"]["count"] == 4


def test_traced_run_reports_the_cells_metrics(small_run):
    line = small_run(CELL, 31, 8.0, trace=True)
    assert line["correct"] is True and line["compared"]["traced_wrong_row_counts"]["of"] > 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    wanted = {x["name"] for x in catalog.Cell(CELL).metrics("per_layer")}
    assert wanted <= set(m)
    assert m["mesh_statements_per_op"] == 3.0 and m["mesh_fallbacks_per_op"] == 0.0
    assert m["programs_built_per_op"] == 0.0 and m["mesh_stack_ms_per_op"] > 0
    # one launch a statement since the root's half rides the mesh program
    assert m["launches_per_op"] == 3.0 and m["cop_host_ms_per_op"] > m["mesh_stack_ms_per_op"]
    assert line["breakdown"]["device_ops"]


def test_control_is_not_correct(small_run):
    line = small_run(CELL, 2**31 + 17, 8.0, control=True)
    assert line["correct"] is False and line["failed"] == 0
    assert line["compared"]["wrong_answers"]["value"] > 0 and line["control"].startswith("float32_sums")


# ---- the readers

def test_counter_readers_on_a_window_with_the_names_and_without():
    window = data_of("mesh_counters.json")["window"]
    statements = catalog.Cell.reader("per_layer", "mesh_statements_per_op")
    fallbacks = catalog.Cell.reader("per_layer", "mesh_fallbacks_per_op")
    assert statements(window) == 3.0 and fallbacks(window) == 0.0
    degraded = {"attempted": 4, "counters": dict(window["counters"], mesh_cop_batches=8, mesh_cop_fallbacks=3, mpp_fallbacks=1)}
    assert statements(degraded) == 2.0 and fallbacks(degraded) == 1.0
    exchanged = {"attempted": 4, "counters": dict(window["counters"], mesh_cop_batches=8, mpp_selects=4)}
    assert statements(exchanged) == 3.0
    # a program whose counters are not named (no program_names.mesh.json): nothing, and no error
    unnamed = {k: v for k, v in window["counters"].items() if not k.startswith(("mesh_", "mpp_"))}
    assert statements({"attempted": 4, "counters": unnamed}) is None and fallbacks({"attempted": 4, "counters": unnamed}) is None
    assert statements(dict(window, attempted=0)) is None and fallbacks(dict(window, attempted=0)) is None


def test_recorded_mesh_tree_and_the_stack_reader():
    tree = data_of("trace_tree_mesh.json")
    (probe,) = [r for r in find(tree, "distsql.execute_root") if find(r, "cop.mesh_execute")]
    (execute,) = find(probe, "cop.mesh_execute")
    (stack,) = find(execute, "mesh.stack")
    assert stack["attrs"] == {"lanes": 8, "devices": 8, "rows": 4096, "bytes": stack["attrs"]["bytes"]} and not stack.get("children")
    assert [n["attrs"]["program"] for n in find(execute, "exec.launch")] == ["cop_scan_sel_join_join_groupagg_m8x8"]
    assert not find(tree, "mpp.dispatch") and not find(tree, "exec.compile")
    read = catalog.Cell.reader("per_layer", "mesh_stack_ms_per_op")
    assert read(run_of("trace_tree_mesh.json")) == round(stack["duration_ns"] / 1e6, 4) > 0
    assert read(run_of("trace_tree_q3.json")) is None          # one region a table: no cross-chip tier, no such span
    assert read({"traced": [], "attempted": 0, "counters": {}}) is None
    assert catalog.Cell.reader("per_layer", "mpp_exchange_ms_per_op")(run_of("trace_tree_mesh.json")) is None


def test_recorded_exchange_tree_lies_under_the_dispatch_span_and_the_waiting_reader_reads_it():
    tree = data_of("trace_tree_mpp.json")
    (root,) = [r for r in find(tree, "distsql.execute_root") if find(r, "mpp.dispatch")]
    (dispatch,) = find(root, "mpp.dispatch")
    assert [c["name"] for c in dispatch["children"]] == ["mpp.scan", "mesh.stack", "mpp.exchange"]
    exchange = dispatch["children"][2]
    kids = sum(c["duration_ns"] for c in exchange["children"])
    read = catalog.Cell.reader("per_layer", "mpp_exchange_ms_per_op")
    assert read(run_of("trace_tree_mpp.json")) == round((exchange["duration_ns"] - kids) / 1e6, 4) > 0
    assert catalog.Cell.reader("per_layer", "mesh_stack_ms_per_op")(run_of("trace_tree_mpp.json")) == round(
        dispatch["children"][1]["duration_ns"] / 1e6, 4)
    # the exchange program's spans are inside `distsql.execute_root`: the six
    # cell-wide metrics book the tier to the dispatch layer and not to the front end
    layers = spans.layers([tree], latency_ns=tree["duration_ns"])
    assert layers["cop_host_ns"] > dispatch["duration_ns"] * 0.9 and layers["frontend_ns"] < dispatch["duration_ns"]


def test_load_fails_before_loading_where_the_program_cannot_split():
    """The parent of the PR that brought the cell parses SPLIT TABLE and
    answers "not supported": `load` has to end there, before the first row."""
    dep = catalog.Cell(CELL).deployment
    sent = []

    class Parent:
        def query(self, sql):
            sent.append(sql)
            raise RuntimeError("(1105) statement SplitTableStmt not supported yet")

    with pytest.raises(RuntimeError, match="cannot execute SPLIT TABLE"):
        dep.load(Parent(), {}, {}, lambda **_line: None)
    assert len(sent) == 1 and sent[0].startswith("split table")
