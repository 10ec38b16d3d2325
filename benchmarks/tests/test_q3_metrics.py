"""The cell `tpch_q3_params` and its per-layer metrics.  `cop_decode_ms_per_op`
reads a span the parent already has, on a tree recorded from TPC-H Q3 with
a drawn SEGMENT and DATE (4,096 lineitem rows from the row store, over the
wire on the CPU, the second SEGMENT: one join program, no compile) and on
the columnar recording, where it returns None.  `str_params_per_op` and
`aux_uploads_per_op` read counters that wait to be named
(`data/q3_counters.json`): on a window's counters with the names and on one
without.  And the manifest: the cell is in BENCHMARK.json and in
`data/tpch_cells.json` at once, and resolves from both."""

import json
import os

import pytest

from harness import catalog, spans

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tpch_q3_params"
COP = "distsql + store cop / columnar route"


def data_of(fixture: str) -> dict:
    with open(os.path.join(HERE, "data", fixture)) as f:
        return json.load(f)


def run_of(fixture: str) -> dict:
    """What `run.py` hands a reader, for one traced operation of one statement."""
    tree = data_of(fixture)
    return {"self_times_ms_per_op": {k: round(v / 1e6, 4) for k, v in spans.self_times(tree).items()},
            "traced": [spans.layers([tree], latency_ns=tree["duration_ns"])], "attempted": 1, "counters": {}}


def find(node: dict, name: str) -> list:
    return ([node] if node["name"] == name else []) + [n for c in node.get("children", ()) for n in find(c, name)]


def test_recorded_tree_is_one_join_program_over_three_scans():
    tree = data_of("trace_tree_q3.json")
    st = spans.self_times(tree)
    # read off the file by hand
    assert st["cop.decode"] == 59576718
    assert st["cop.aux_batch"] == 13607 + 5821
    assert st["session.join_build"] == 777693 - (397682 + 206875)
    assert st["exec.wait"] == 8201499 + 2574764 and "exec.compile" not in st
    (build,) = find(tree, "session.join_build")
    assert build["attrs"] == {"tables": 2, "rows": 1126, "bytes": 177487}
    assert [c["name"] for c in build["children"]] == ["distsql.execute_root"] * 2   # the two build scans, from the result cache
    assert [a["attrs"] for a in find(tree, "cop.aux_batch")] == [{"rows": 1024, "hit": True}, {"rows": 102, "hit": True}]
    launches = [n["attrs"] for n in find(tree, "exec.launch")]
    assert launches == [{"program": "cop_scan_sel_join_join_groupagg", "params": 4},   # two dates, 1 - l_discount's 1, the SEGMENT
                        {"program": "cop_scan_groupagg_topn", "params": 0}]
    # three `execute_root`s: the build fetches are host time of the cop layer too
    assert spans.layers([tree], latency_ns=80_000_000) == {
        "frontend_ns": 80_000_000 - (397682 + 206875 + 74983661),
        "cop_host_ns": 397682 + 206875 + 74983661 - (4431 + 5308), "program_ns": 4431 + 5308}


def test_decode_reader_on_a_row_store_join_and_without_one():
    read = catalog.Cell.reader("per_layer", "cop_decode_ms_per_op")
    assert read(run_of("trace_tree_q3.json")) == 59.5767
    assert read(run_of("trace_tree_columnar.json")) is None   # the replica's route decodes nothing
    assert read({"traced": [], "attempted": 0, "counters": {}}) is None   # not a traced run


@pytest.mark.parametrize("metric,want", [("str_params_per_op", 1.0), ("aux_uploads_per_op", 0.0)])
def test_waiting_counter_readers(metric, want):
    waiting = data_of("q3_counters.json")
    read = catalog.Cell.reader("per_layer", metric)
    assert read(dict(waiting["window"])) == want
    # the names not read (today, and on any parent): nothing, and no error
    unnamed = {k: v for k, v in waiting["window"]["counters"].items() if k not in waiting["program_names"]["counters"]}
    assert read({"attempted": 6, "counters": unnamed}) is None
    assert read({"attempted": 0, "counters": waiting["window"]["counters"]}) is None
    # the counters exist in the program under the names the file gives
    from tidb_tpu.util import metrics
    assert all(hasattr(getattr(metrics, attr), "value") for attr in waiting["program_names"]["counters"].values())
    assert not set(waiting["program_names"]["counters"]) & set(catalog.program_names()["counters"])   # still waiting


def test_cell_resolves_from_the_manifest():
    cell = catalog.Cell(CELL)
    assert (cell.entry["config"], cell.entry["traffic"], cell.chips) == ("tpch_sf0p02_rowstore", "q3_params", 1)
    assert cell.config["lineitem_rows"] == 131072 and list(cell.statements) == ["q3"]
    assert "columnar_replica" not in cell.config   # the deployment has none; the mix reads the row store
    assert cell.traffic["read_engines"] == "tpu" and cell.traffic["proofs"] == {"pallas": ["q3"]}
    assert cell.deployment.scan_bytes("q3", cell.config) is None   # no roofline in this cell
    per_layer = {m["name"]: m for m in cell.metrics("per_layer")}
    assert set(per_layer) >= {"frontend_ms_per_op", "cop_host_ms_per_op", "launches_per_op", "programs_built_per_op",
                              "cop_cache_hits_per_op", "device_idle_pct", "cop_decode_ms_per_op"}
    assert per_layer["cop_decode_ms_per_op"]["layer"] == COP and per_layer["cop_decode_ms_per_op"]["moves"] == "op_p50_ms"
    assert {m["name"] for m in cell.metrics("end_to_end")} == {"ops_per_s", "op_p50_ms", "setup_s"}
    for m in per_layer:
        assert callable(catalog.Cell.reader("per_layer", m))


def test_cell_is_in_both_files_once(tmp_path, monkeypatch):
    """`conftest.small_manifest` merges `data/tpch_cells.json` by name."""
    from conftest import small_manifest

    monkeypatch.setattr(catalog, "MANIFEST", small_manifest(str(tmp_path)))
    cell = catalog.Cell(CELL)
    assert [w["name"] for w in cell.manifest["workloads"]].count(CELL) == 1
    assert cell.config["lineitem_rows"] == 4096 and cell.entry["traffic"] == "q3_params"
