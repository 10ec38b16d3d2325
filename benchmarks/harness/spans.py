"""Reduction of `TRACE FORMAT='json'` span trees to times per layer.  A
node is {name, duration_ns, attrs, children} with no start time, so a
node's self time is its duration less its children's, clamped at 0 where
children that ran in parallel (the dispatch pool) sum to more."""

from __future__ import annotations

import collections
import json


def parse(text: str) -> dict:
    return json.loads(text)


def outermost(tree: dict, name: str) -> int:
    """Summed duration of the spans called `name` that no span of the
    same name contains."""
    if tree.get("name") == name:
        return int(tree["duration_ns"])
    return sum(outermost(c, name) for c in tree.get("children", ()))


def under(tree: dict, outer: str, inner: str) -> int:
    """Summed duration of the outermost `inner` spans inside each
    outermost `outer` span, each sum clamped to its `outer` span."""
    if tree.get("name") == outer:
        return min(outermost(tree, inner), int(tree["duration_ns"]))
    return sum(under(c, outer, inner) for c in tree.get("children", ()))


def self_times(tree: dict, into=None) -> dict:
    """{span name: self time in ns}, summed over the tree."""
    into = collections.Counter() if into is None else into
    kids = tree.get("children", ())
    into[tree["name"]] += max(int(tree["duration_ns"]) - sum(int(c["duration_ns"]) for c in kids), 0)
    for c in kids:
        self_times(c, into)
    return into


def layers(trees: list, latency_ns: int) -> dict:
    """One traced operation: its client-side latency split into the front
    end (wire, parse, session, planner, result encoding), the host part of
    distsql/store/columnar, and the device programs' spans."""
    root = sum(outermost(t, "distsql.execute_root") for t in trees)
    program = sum(under(t, "distsql.execute_root", "exec.program") for t in trees)
    return {
        "frontend_ns": max(latency_ns - root, 0),
        "cop_host_ns": max(root - program, 0),
        "program_ns": program,
    }
