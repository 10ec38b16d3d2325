"""The write path's per-layer metrics of the cell `sysbench_rw_uniform`, on a
recorded transaction (`data/trace_trees_rw.json`: its four writes and its
COMMIT, each traced, the first write having waited for another
transaction's lock): `lock_wait_ms_per_op` reads `txn.lock`,
`txn_commit_ms_per_op` `txn.prewrite` + `txn.commit`, `cache_drop_ms_per_op`
`store.cache_drop`, and each reads nothing on a program without those
spans.  A traced window of the harness holds the writes' trees and not the
COMMIT's (run.py sends raw steps untraced), so the first is in
BENCHMARK.json for the cell and the other two wait in
`data/commit_metrics.json`."""

import collections
import json
import os

from harness import catalog, spans

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "sysbench_rw_uniform"
COP = "distsql + store cop / columnar route"
METRICS = ("lock_wait_ms_per_op", "txn_commit_ms_per_op", "cache_drop_ms_per_op")


def data_of(fixture: str) -> dict:
    with open(os.path.join(HERE, "data", fixture)) as f:
        return json.load(f)


def recorded() -> dict:
    return dict(data_of("trace_trees_rw.json")["statements"])


def run_of(trees: list) -> dict:
    """What `run.py` hands a reader for one traced operation made of `trees`."""
    total = collections.Counter()
    for tree in trees:
        spans.self_times(tree, total)
    return {"self_times_ms_per_op": {k: round(v / 1e6, 4) for k, v in total.items()}}


def find(node: dict, name: str) -> list:
    return ([node] if node["name"] == name else []) + [n for c in node.get("children", ()) for n in find(c, name)]


def test_recorded_transaction_carries_the_write_spans():
    trees = recorded()
    assert list(trees) == ["index_update", "non_index_update", "delete", "insert", "commit"]
    waited, retry = find(trees["index_update"], "txn.lock"), find(trees["index_update"], "txn.retry")
    assert [lock["attrs"]["waited"] for lock in waited] == [True, False]   # the wait, then the retry's own lock
    assert waited[0]["attrs"]["outcome"] == "locked" and waited[0]["attrs"]["wait_ms"] > 20
    assert len(retry) == 1 and retry[0]["attrs"]["for_update_ts"] > 0
    for name in ("non_index_update", "delete", "insert"):
        (lock,) = find(trees[name], "txn.lock")
        assert lock["attrs"] == {"keys": 1, "waited": False, "wait_ms": 0.0, "outcome": "locked"}
    (prewrite,), (commit,) = find(trees["commit"], "txn.prewrite"), find(trees["commit"], "txn.commit")
    assert prewrite["attrs"] == commit["attrs"] == {"keys": 8}   # 3 row keys, 5 index entries
    (drop,) = find(commit, "store.cache_drop")
    assert drop["attrs"]["entries"] >= 1
    assert not find(trees["commit"], "txn.lock")


def test_readers_on_the_recorded_transaction():
    trees = recorded()
    st = collections.Counter()
    for tree in trees.values():
        spans.self_times(tree, st)
    run = run_of(list(trees.values()))
    read = {m: catalog.Cell.reader("per_layer", m) for m in METRICS}
    assert read["lock_wait_ms_per_op"](run) == round(st["txn.lock"] / 1e6, 4) > 20
    assert read["txn_commit_ms_per_op"](run) == round(round(st["txn.prewrite"] / 1e6, 4)
                                                      + round(st["txn.commit"] / 1e6, 4), 4)
    assert read["cache_drop_ms_per_op"](run) == round(st["store.cache_drop"] / 1e6, 4)


def test_a_traced_window_of_the_harness_reads_the_lock_alone():
    """The harness traces the named steps: the writes, not the COMMIT."""
    writes = [tree for name, tree in recorded().items() if name != "commit"]
    run = run_of(writes)
    assert catalog.Cell.reader("per_layer", "lock_wait_ms_per_op")(run) > 0
    assert catalog.Cell.reader("per_layer", "txn_commit_ms_per_op")(run) is None
    assert catalog.Cell.reader("per_layer", "cache_drop_ms_per_op")(run) is None


def test_readers_read_nothing_without_the_spans():
    for metric in METRICS:
        read = catalog.Cell.reader("per_layer", metric)
        assert read(run_of([data_of("trace_tree.json")])) is None   # a program without the write path's spans
        assert read({"traced": [], "attempted": 0, "counters": {}}) is None   # not a traced run


def test_manifest_entries():
    with open(catalog.MANIFEST) as f:
        manifest = json.load(f)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    lock = per_layer["lock_wait_ms_per_op"]
    assert (lock["layer"], lock["moves"], lock["source"], lock["workloads"]) == (COP, "op_p50_ms", "program_span", [CELL])
    waiting = data_of("commit_metrics.json")["per_layer"]
    assert [m["name"] for m in waiting] == ["txn_commit_ms_per_op", "cache_drop_ms_per_op"]
    for m in waiting:
        assert m["name"] not in per_layer
        assert {k: v for k, v in m.items() if k != "name"} == {k: v for k, v in lock.items() if k != "name"}
    cell = catalog.Cell(CELL)
    assert (cell.entry["config"], cell.entry["traffic"], cell.chips) == ("sysbench_32x16k_rw", "rw_uniform", 1)
    assert "lock_wait_ms_per_op" in {m["name"] for m in cell.metrics("per_layer")}
    assert cell.traffic["restart_on"] == [1205, 1213, 9007]
