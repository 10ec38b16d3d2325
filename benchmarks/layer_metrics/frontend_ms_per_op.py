"""server + session + planner: a traced operation's client-side latency
less its `distsql.execute_root` spans (wire, parse, plan, result encoding)."""

import statistics


def read(run: dict):
    traced = run["traced"]
    return statistics.mean(t["frontend_ns"] for t in traced) / 1e6 if traced else None
