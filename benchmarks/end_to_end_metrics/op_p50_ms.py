"""Median client-side latency of all operations started in the window."""

import statistics


def read(run: dict):
    return statistics.median(run["latencies_ms"]) if run["latencies_ms"] else None
