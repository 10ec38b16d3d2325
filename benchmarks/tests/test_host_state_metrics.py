"""The per-layer metrics that read the host-state clock (tidb_tpu/util/
tracing.py, PR 36).  Named now, because their counters were in the parent
(`program_names.tracing.json`): `server_ms_per_op`, `server_write_ms_per_op`,
`program_wait_ms_per_op`, `readback_transfers_per_op`,
`eager_compiles_per_op`; and, from a span, `dispatch_wait_ms_per_op` on a
tree recorded from a program that has `distsql.wait_tasks` (a grouped
aggregate over a table in four regions, on the CPU's four host devices) and
on the older mesh recording of one that has not.  Waiting for the next PR to
name their counters (`data/host_state_counters.json`): `host_cpu_ms_per_op`,
`host_unnamed_pct`, on a recorded window with the names and on one without."""

import json
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()

import pytest  # noqa: E402

from harness import catalog, spans  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NAMED = ["server_ms_per_op", "server_write_ms_per_op", "program_wait_ms_per_op", "readback_transfers_per_op",
         "eager_compiles_per_op"]
WAITING = ["host_cpu_ms_per_op", "host_unnamed_pct"]
LAYERS = {"server + session + planner", "distsql + store cop / columnar route", "exec program", "kernels", "device"}


def data_of(fixture: str) -> dict:
    with open(os.path.join(HERE, "data", fixture)) as f:
        return json.load(f)


def reader(metric: str):
    return catalog.Cell.reader("per_layer", metric)


def window() -> dict:
    """What `run.py` hands a reader of counters: the recorded window."""
    w = data_of("host_state_counters.json")["window"]
    return {"attempted": w["attempted"], "counters": dict(w["counters"])}


# ---- named now
def test_the_tracing_names_are_the_block_that_waited_and_exist_in_the_program():
    from tidb_tpu.util import metrics

    named = data_of(os.path.join("..", "..", "program_names.tracing.json"))["counters"]
    assert named == data_of("launch_counters.json")["program_names"]["counters"] and len(named) == 14
    for name, attribute in named.items():
        assert isinstance(getattr(metrics, attribute).value, int), name
    assert catalog.program_names()["counters"]["program_wait_ns"] == "PROGRAM_WAIT_NS"
    assert catalog.program_names()["counters"]["launches"] == "PROGRAM_LAUNCHES"   # what was there stays


def test_manifest_appends_the_named_metrics_for_every_cell():
    with open(catalog.MANIFEST) as f:
        per_layer = json.load(f)["per_layer"]
    # the block as it was appended; later cells append their metrics after it
    names = [m["name"] for m in per_layer]
    at = names.index(NAMED[0])
    block = per_layer[at:at + len(NAMED) + 1]
    assert [m["name"] for m in block] == NAMED + ["dispatch_wait_ms_per_op"]
    for m in block[:-1]:
        assert "workloads" not in m and m["source"] == "program_counter" and m["layer"] in LAYERS
    assert "tpch_q1q6q3_mesh4" in block[-1]["workloads"] and block[-1]["source"] == "program_span"
    entries = {m["name"]: m for m in per_layer}
    assert (entries["program_wait_ms_per_op"]["layer"], entries["program_wait_ms_per_op"]["moves"]) == ("device", "op_p50_ms")
    assert entries["server_write_ms_per_op"]["layer"] == entries["server_ms_per_op"]["layer"] == "server + session + planner"
    # what launch_counters.json kept waiting is entered as it stood there
    for waited in data_of("launch_counters.json")["per_layer"]:
        assert entries[waited["name"]] == waited


def test_named_readers_on_the_recorded_window():
    run = window()
    c, n = run["counters"], run["attempted"]
    assert reader("server_ms_per_op")(run) == c["server_handle_ns"] / 1e6 / n
    assert reader("server_write_ms_per_op")(run) == c["server_write_ns"] / 1e6 / n > 0
    assert reader("program_wait_ms_per_op")(run) == c["program_wait_ns"] / 1e6 / n > 0
    assert reader("readback_transfers_per_op")(run) == c["readback_transfers"] / n
    assert reader("eager_compiles_per_op")(run) == 0.0
    # the wait is part of what the server handled, pool threads' waits included
    assert c["server_write_ns"] < c["server_handle_ns"]


@pytest.mark.parametrize("metric", ["server_write_ms_per_op", "program_wait_ms_per_op"])
def test_new_named_readers_return_nothing_without_the_names(metric):
    assert reader(metric)({"attempted": 57, "counters": {"launches": 342}}) is None   # a harness without the file
    assert reader(metric)({"attempted": 0, "counters": window()["counters"]}) is None  # nothing attempted


def test_dispatch_wait_reads_the_waiting_spans_self_time():
    tree = data_of("trace_tree_wait_tasks.json")
    self_ms = {k: round(v / 1e6, 4) for k, v in spans.self_times(tree).items()}
    (root,) = [c for c in tree["children"][0]["children"] if c["name"] == "distsql.execute_root"]
    wait, batch = root["children"][0], root["children"][1]
    # the statement's thread waits while a worker's thread runs the store's batch
    assert (wait["name"], batch["name"]) == ("distsql.wait_tasks", "distsql.batch_cop")
    assert wait["thread"] == root["thread"] != batch["thread"] and not wait.get("children")
    assert wait["duration_ns"] >= batch["duration_ns"]
    run = {"self_times_ms_per_op": self_ms, "attempted": 1, "counters": {}}
    assert reader("dispatch_wait_ms_per_op")(run) == round(wait["duration_ns"] / 1e6, 4)
    # the older layers read the tree as they read the parent's
    assert spans.layers([tree], latency_ns=tree["duration_ns"])["cop_host_ns"] == (
        root["duration_ns"] - spans.under(tree, "distsql.execute_root", "exec.program"))


def test_dispatch_wait_returns_nothing_on_the_parents_program():
    old = data_of("trace_tree_mesh.json")   # recorded at PR 34: no such span
    run = {"self_times_ms_per_op": {k: round(v / 1e6, 4) for k, v in spans.self_times(old).items()}, "attempted": 1}
    assert reader("dispatch_wait_ms_per_op")(run) is None
    assert reader("dispatch_wait_ms_per_op")({"attempted": 0}) is None   # not a traced run


# ---- waiting: the counters that the next PR names (data/host_state_counters.json)
def test_waiting_counters_exist_in_the_program_and_are_named_by_no_file_yet():
    from tidb_tpu.util import metrics, tracing

    waiting = data_of("host_state_counters.json")
    names = {**waiting["program_names"]["counters"], **waiting["held_back"]["counters"]}
    for name, attribute in names.items():
        assert isinstance(getattr(metrics, attribute).value, int), name
    assert not set(waiting["program_names"]["counters"]) & set(catalog.program_names()["counters"])
    # every state's counter is in the block or was named before it
    have = set(names.values()) | set(catalog.program_names()["counters"].values())
    for state, wall in tracing.HOST_STATES.items():
        (attr,) = [a for a in dir(metrics) if getattr(metrics, a) is wall]
        assert attr in have, state
    assert {"SERVER_CPU_NS", "HOST_POOL_NS", "HOST_POOL_CPU_NS"} <= have


def test_the_recorded_window_conserves():
    c = window()["counters"]
    wall = [v for k, v in c.items() if k.startswith("host_") and k not in ("host_pool_ns", "host_pool_cpu_ns")]
    wall += [c["program_wait_ns"], c["readback_ns"], c["server_write_ns"], c["columnar_gate_wait_ns"]]
    assert sum(wall) - c["host_pool_ns"] == c["server_handle_ns"]
    assert 0 < c["host_pool_cpu_ns"] <= c["host_pool_ns"] and c["host_wait_tasks_ns"] > 0
    assert 0 < c["server_cpu_ns"] < c["server_handle_ns"]


def test_waiting_readers_on_the_recorded_window():
    run = window()
    c, n = run["counters"], run["attempted"]
    got = {m: reader(m)(run) for m in WAITING}
    assert got["host_cpu_ms_per_op"] == (c["server_cpu_ns"] + c["host_pool_cpu_ns"]) / 1e6 / n > 0
    assert got["host_unnamed_pct"] == 100.0 * c["host_server_command_ns"] / c["server_handle_ns"] < 10


@pytest.mark.parametrize("metric", WAITING)
def test_waiting_readers_return_nothing_until_their_counters_are_named(metric):
    run = window()
    named_today = set(catalog.program_names()["counters"])
    run["counters"] = {k: v for k, v in run["counters"].items() if k in named_today}
    assert reader(metric)(run) is None
    if metric != "host_unnamed_pct":   # a share of the window's time, not a time per operation
        assert reader(metric)({"attempted": 0, "counters": window()["counters"]}) is None


def test_waiting_entries_are_ready_to_copy():
    waiting = data_of("host_state_counters.json")
    with open(catalog.MANIFEST) as f:
        manifest = json.load(f)
    assert [m["name"] for m in waiting["per_layer"]] == WAITING
    have = {m["name"] for m in manifest["per_layer"]}
    layers = {m["layer"] for m in manifest["per_layer"]}
    for m in waiting["per_layer"]:
        assert m["name"] not in have and m["layer"] in layers and m["moves"] == "ops_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"} and callable(reader(m["name"]))


# ---- whole small runs
def test_a_traced_small_run_prints_the_named_metrics(small_run):
    line = small_run("tpch_q1q6_params", 7, 3.0, trace=True)
    assert line["correct"] is True
    for metric in NAMED:
        assert metric in line["metrics"], metric
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["server_write_ms_per_op"] < m["server_ms_per_op"] and m["program_wait_ms_per_op"] > 0
    assert m["readback_transfers_per_op"] == m["launches_per_op"] == 2.0 and m["eager_compiles_per_op"] == 0.0
    assert "dispatch_wait_ms_per_op" not in m   # the four-chip cell's


def test_the_four_chip_cell_reads_its_dispatch_wait(small_run):
    line = small_run("tpch_q1q6q3_mesh4", 9, 3.0, trace=True)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True and m["dispatch_wait_ms_per_op"] > 0 and m["mesh_statements_per_op"] == 3.0
    assert set(NAMED) <= set(m)
