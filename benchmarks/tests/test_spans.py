"""The span reduction on a recorded `TRACE FORMAT='json'` tree (a
sysbench sum_range statement inside BEGIN, recorded over the wire on the
CPU) and on a hand-made tree with children that overlap."""

import json
import os

from harness import spans

HERE = os.path.dirname(os.path.abspath(__file__))


def recorded():
    with open(os.path.join(HERE, "data", "trace_tree.json")) as f:
        return json.load(f)


def test_recorded_tree_layers():
    tree = recorded()
    # read off the file by hand
    assert tree["name"] == "session" and tree["duration_ns"] == 154544020
    assert spans.outermost(tree, "distsql.execute_root") == 150118053
    assert spans.outermost(tree, "exec.program") == 289638 + 4204
    assert spans.under(tree, "distsql.execute_root", "exec.program") == 289638 + 4204
    got = spans.layers([tree], latency_ns=160_000_000)
    assert got == {"frontend_ns": 160_000_000 - 150118053,
                   "cop_host_ns": 150118053 - 293842, "program_ns": 293842}


def test_recorded_tree_self_times_add_up():
    tree = recorded()
    st = spans.self_times(tree)
    # no children overlap in this tree, so self times sum to the root's duration
    assert sum(st.values()) == tree["duration_ns"]
    assert st["cop.execute"] == 145391768 - 289638
    assert st["planner.plan"] == 422827 + 412166  # two spans of one name
    assert st["session"] == 154544020 - 154519336


def test_overlapping_children_clamp_at_zero():
    tree = {"name": "distsql.execute_root", "duration_ns": 100, "children": [
        {"name": "distsql.cop_task", "duration_ns": 80, "children": [
            {"name": "exec.program", "duration_ns": 70}]},
        {"name": "distsql.cop_task", "duration_ns": 90, "children": [
            {"name": "exec.program", "duration_ns": 60}]},
    ]}
    st = spans.self_times(tree)
    assert st["distsql.execute_root"] == 0          # 100 - 170, clamped
    assert st["distsql.cop_task"] == 10 + 30
    # programs in parallel lanes cannot take more than the span that holds them
    assert spans.under(tree, "distsql.execute_root", "exec.program") == 100
    assert spans.layers([tree], latency_ns=120) == {
        "frontend_ns": 20, "cop_host_ns": 0, "program_ns": 100}


def test_nested_same_name_counts_once():
    tree = {"name": "a", "duration_ns": 50, "children": [{"name": "a", "duration_ns": 20}]}
    assert spans.outermost(tree, "a") == 50
