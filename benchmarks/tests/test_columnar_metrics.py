"""The per-layer metrics of the cell `tpch_q1q6_params` that read the
columnar route: `columnar_scan_ms_per_op` and `columnar_gate_ms_per_op`
from spans, on a tree recorded from a program that has both (TPC-H Q1 with
a drawn DELTA over the replica of 4,096 lineitem rows, over the wire on
the CPU, second execution) and on recordings of programs without the
route, where they return None; `columnar_fallbacks_per_op` from the
counter the parent already has.  And the manifest: the cell is in
BENCHMARK.json and in `data/tpch_cells.json` at once, and resolves."""

import json
import os

import pytest

from harness import catalog, spans

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tpch_q1q6_params"
ROUTE = "distsql + store cop / columnar route"
SPAN_READERS = ["columnar_scan_ms_per_op", "columnar_gate_ms_per_op"]


def data_of(fixture: str) -> dict:
    with open(os.path.join(HERE, "data", fixture)) as f:
        return json.load(f)


def run_of(fixture: str) -> dict:
    """What `run.py` hands a reader, for one traced operation of one statement."""
    tree = data_of(fixture)
    return {"self_times_ms_per_op": {k: round(v / 1e6, 4) for k, v in spans.self_times(tree).items()},
            "traced": [spans.layers([tree], latency_ns=tree["duration_ns"])], "attempted": 1,
            "counters": {"columnar_fallbacks": 0, "columnar_scans": 1}}


def test_recorded_tree_rode_the_resident_batch():
    tree = data_of("trace_tree_columnar.json")
    st = spans.self_times(tree)
    # read off the file by hand
    assert st["columnar.gate"] == 34109
    assert st["columnar.scan"] == 3507086 - (3013 + 491335 + 2061142 + 476910)
    assert st["exec.launch"] == 491335 and st["exec.wait"] == 2061142 and st["exec.readback"] == 476910
    assert "cop.execute" not in st and "distsql.cop_task" not in st   # the row store's spans are not on this path
    root = tree["children"][0]["children"][0]["children"][1]
    assert root["name"] == "distsql.execute_root"
    gate, scan = root["children"]
    assert gate["attrs"] == {"start_ts": 132, "waited": False, "snapshot_ts": 130}
    assert (scan["attrs"]["resident"], scan["attrs"]["stable_rows"], scan["attrs"]["delta_rows"]) == (True, 4096, 0)
    # the layers read as for any other statement: `exec.program` is the closure only
    assert spans.layers([tree], latency_ns=6_000_000) == {
        "frontend_ns": 6_000_000 - 3669670, "cop_host_ns": 3669670 - 3013, "program_ns": 3013}


def test_span_readers_on_a_run_with_the_route():
    run = run_of("trace_tree_columnar.json")
    got = {m: catalog.Cell.reader("per_layer", m)(run) for m in SPAN_READERS}
    assert got == {"columnar_scan_ms_per_op": 0.4747, "columnar_gate_ms_per_op": 0.0341}


@pytest.mark.parametrize("metric", SPAN_READERS)
@pytest.mark.parametrize("fixture", ["trace_tree.json", "trace_tree_launch.json"])
def test_span_readers_return_nothing_without_the_route(metric, fixture):
    read = catalog.Cell.reader("per_layer", metric)
    assert read(run_of(fixture)) is None                 # a row-store statement: neither span
    assert read({"traced": [], "attempted": 0}) is None  # not a traced run


def test_gate_reader_returns_nothing_on_the_parent():
    """The parent of PR 28 has `columnar.scan` and no `columnar.gate`."""
    run = run_of("trace_tree_columnar.json")
    del run["self_times_ms_per_op"]["columnar.gate"]
    assert catalog.Cell.reader("per_layer", "columnar_gate_ms_per_op")(run) is None
    assert catalog.Cell.reader("per_layer", "columnar_scan_ms_per_op")(run) == 0.4747


def test_fallbacks_reader_counts_the_window():
    read = catalog.Cell.reader("per_layer", "columnar_fallbacks_per_op")
    assert read({"attempted": 1500, "counters": {"columnar_fallbacks": 0}}) == 0.0
    assert read({"attempted": 1500, "counters": {"columnar_fallbacks": 3}}) == 0.002
    assert read({"attempted": 0, "counters": {"columnar_fallbacks": 0}}) is None


def test_the_counter_it_reads_was_named_before_this_pr():
    assert catalog.program_names()["counters"]["columnar_fallbacks"] == "COLUMNAR_FALLBACKS"
    # and no file names a counter that the parent of PR 28 lacks
    new = {"COLUMNAR_RESIDENT_SCANS", "COLUMNAR_GATE_WAIT_NS", "COLUMNAR_DEVICE_BYTES"}
    assert not new & set(catalog.program_names()["counters"].values())


# ---- the manifest
def test_manifest_has_the_cell_and_its_metrics():
    with open(catalog.MANIFEST) as f:
        manifest = json.load(f)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch_sf0p02", "q1q6_params", 1)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for metric, source, moves in (("device_roofline", "device_trace", "ops_per_s"),
                                  ("columnar_scan_ms_per_op", "program_span", "op_p50_ms"),
                                  ("columnar_gate_ms_per_op", "program_span", "op_p50_ms"),
                                  ("columnar_fallbacks_per_op", "program_counter", "ops_per_s")):
        assert (entries[metric]["source"], entries[metric]["moves"]) == (source, moves)
        assert entries[metric]["workloads"] == [CELL]
        assert entries[metric]["layer"] == ("kernels" if metric == "device_roofline" else ROUTE)
    # the cell reports every metric that lists no cells (these and any appended
    # later), and none of sysbench's own
    reported = {m["name"] for m in catalog.Cell(CELL).metrics("per_layer")}
    assert not {"trace_lower_ms_per_op", "xla_compile_ms_per_op", "cop_decode_ms_per_op"} & reported
    assert reported >= {"frontend_ms_per_op", "cop_host_ms_per_op", "launches_per_op", "programs_built_per_op",
                        "cop_cache_hits_per_op", "device_idle_pct", "device_roofline", "columnar_scan_ms_per_op",
                        "columnar_gate_ms_per_op", "columnar_fallbacks_per_op"}
    assert {m["name"] for m in catalog.Cell(CELL).metrics("end_to_end")} == {"ops_per_s", "op_p50_ms", "setup_s"}


def test_the_configuration_entry_is_the_waiting_one_as_it_stood():
    with open(catalog.MANIFEST) as f:
        (config,) = [c for c in json.load(f)["configs"] if c["name"] == "tpch_sf0p02"]
    (waiting,) = data_of("tpch_cells.json")["configs"]
    assert config == waiting
    assert catalog.Cell(CELL).config["lineitem_rows"] == 131072
    assert catalog.Cell(CELL).traffic["clients"] == 2 and "persistent_cache_in_window" not in catalog.Cell(CELL).traffic


def test_small_manifest_resolves_with_the_cell_in_both_files(tmp_path, monkeypatch):
    """`conftest.small_manifest` merges `tpch_cells.json` by name: the cell
    and the configuration are taken once, from BENCHMARK.json; what still
    waits (`tpch_q3_params`, `op_p95_ms`) comes from the other file."""
    from conftest import SMALL, small_manifest

    path = small_manifest(str(tmp_path))
    with open(path) as f:
        manifest = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[key]]
        assert len(names) == len(set(names)), (key, names)
    names = [w["name"] for w in manifest["workloads"]]
    assert names[:3] == ["sysbench_ro_uniform", CELL, "tpch_q3_params"] and "tpch_q1q6q3_mesh4" in names
    (mine,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert "131,072" in mine["why"]     # BENCHMARK.json's entry, not the waiting one
    monkeypatch.setattr(catalog, "MANIFEST", path)
    cell = catalog.Cell(CELL)
    assert cell.config["lineitem_rows"] == SMALL["lineitem_rows"]
    assert {"q1", "q6"} <= set(cell.statements) and callable(cell.deployment.scan_bytes)
    assert "op_p95_ms" in {m["name"] for m in cell.metrics("end_to_end")}


# ---- waiting: the counters that a later PR names (data/columnar_counters.json)
def test_waiting_counters_exist_in_the_program():
    from tidb_tpu.util import metrics

    waiting = data_of("columnar_counters.json")
    for name, attribute in waiting["program_names"]["counters"].items():
        assert isinstance(getattr(metrics, attribute).value, (int, float)), name
    with open(catalog.MANIFEST) as f:
        have = {m["name"] for m in json.load(f)["per_layer"]}
    assert not have & {m["name"] for m in waiting["per_layer"]}
    assert all(m["layer"] == ROUTE and m["workloads"] == [CELL] for m in waiting["per_layer"])
