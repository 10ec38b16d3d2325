"""The per-layer metrics that read the launch boundary's spans
(`exec.compile`, `exec.xla_compile`, `exec.wait`, `exec.readback`), on a
tree recorded from a program that has them (a sysbench sum_range inside
BEGIN with a fresh literal, over the wire on the CPU; the root merge's
program was warm) and on the older recording of a program that has not:
there every reader returns None and the line leaves the metric out."""

import json
import os

import pytest

from harness import catalog, spans

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ["trace_lower_ms_per_op", "xla_compile_ms_per_op", "device_wait_ms_per_op", "readback_ms_per_op"]


def run_of(fixture: str) -> dict:
    """What `run.py` hands a reader, for one traced operation of one statement."""
    with open(os.path.join(HERE, "data", fixture)) as f:
        tree = json.load(f)
    return {"self_times_ms_per_op": {k: round(v / 1e6, 4) for k, v in spans.self_times(tree).items()},
            "traced": [spans.layers([tree], latency_ns=tree["duration_ns"])], "attempted": 1}


def test_recorded_tree_has_the_launch_states():
    with open(os.path.join(HERE, "data", "trace_tree_launch.json")) as f:
        tree = json.load(f)
    st = spans.self_times(tree)
    # read off the file by hand: the push program compiled, the root merge's was warm
    assert st["exec.xla_compile"] == 124057769
    assert st["exec.compile"] == 155949463 - 124057769
    assert st["exec.launch"] == 84172
    assert st["exec.wait"] == 106819 + 85514
    assert st["exec.readback"] == 134635 + 91514
    assert st["cop.execute"] == 156619384 - (225362 + 155949463 + 106819 + 134635)   # 0.2 ms is left unnamed
    # the older layers read as before: the new spans are not `exec.program`
    assert spans.layers([tree], latency_ns=170_000_000) == {
        "frontend_ns": 170_000_000 - 160460120, "cop_host_ns": 160460120 - (225362 + 3789),
        "program_ns": 225362 + 3789}


def test_readers_on_a_run_with_the_boundary():
    run = run_of("trace_tree_launch.json")
    got = {m: catalog.Cell.reader("per_layer", m)(run) for m in NEW}
    assert got == {"trace_lower_ms_per_op": 31.8917, "xla_compile_ms_per_op": 124.0578,
                   "device_wait_ms_per_op": 0.1923, "readback_ms_per_op": 0.2261}
    # a window in which nothing compiled reads 0, not nothing
    for name in ("exec.compile", "exec.xla_compile"):
        del run["self_times_ms_per_op"][name]
    assert catalog.Cell.reader("per_layer", "trace_lower_ms_per_op")(run) == 0.0
    assert catalog.Cell.reader("per_layer", "xla_compile_ms_per_op")(run) == 0.0


@pytest.mark.parametrize("metric", NEW)
def test_readers_return_nothing_without_the_boundary(metric):
    read = catalog.Cell.reader("per_layer", metric)
    assert read(run_of("trace_tree.json")) is None      # recorded before the program had the spans
    assert read({"traced": [], "attempted": 0}) is None  # not a traced run


def test_manifest_names_the_readers():
    with open(catalog.MANIFEST) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for metric in NEW:
        assert entries[metric]["source"] == "program_span" and entries[metric]["moves"] == "op_p50_ms"
        assert entries[metric]["workloads"] == ["sysbench_ro_uniform"]


# ---- waiting: the counters that a later PR names (data/launch_counters.json)
def waiting() -> dict:
    with open(os.path.join(HERE, "data", "launch_counters.json")) as f:
        return json.load(f)


def test_waiting_counters_exist_in_the_program():
    from tidb_tpu.util import metrics

    for name, attribute in waiting()["program_names"]["counters"].items():
        assert isinstance(getattr(metrics, attribute).value, int), name


def test_waiting_readers_on_a_recorded_window():
    # window deltas as `engine.moved` gives them, 81 operations attempted
    run = {"attempted": 81, "counters": {"launches": 567, "xla_eager_compiles": 0, "readback_transfers": 4212,
                                         "server_handle_ns": 792_180_000_000}}
    read = {m["name"]: catalog.Cell.reader("per_layer", m["name"]) for m in waiting()["per_layer"]}
    assert read["eager_compiles_per_op"](run) == 0.0
    assert read["readback_transfers_per_op"](run) == 52.0
    assert read["server_ms_per_op"](run) == 9780.0
    for r in read.values():
        assert r({"attempted": 0, "counters": run["counters"]}) is None   # nothing attempted
        assert r({"attempted": 81, "counters": {"launches": 567}}) is None  # the counter is not named yet


def test_program_names_merges_the_waiting_file(tmp_path, monkeypatch):
    import shutil

    shutil.copy(os.path.join(catalog.BENCH_DIR, "program_names.json"), tmp_path)
    with open(tmp_path / "program_names.tracing.json", "w") as f:
        json.dump(waiting()["program_names"], f)
    monkeypatch.setattr(catalog, "BENCH_DIR", str(tmp_path))
    names = catalog.program_names()
    assert names["counters"]["launches"] == "PROGRAM_LAUNCHES"          # what was there stays
    assert names["counters"]["server_handle_ns"] == "SERVER_HANDLE_NS"  # what the file adds
    assert names["pallas_kernels"]["dense_pallas"] == ["group_aggregate_dense_pallas"]
