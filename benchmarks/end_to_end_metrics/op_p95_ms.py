"""95th percentile (nearest rank) of the same sample as op_p50_ms."""

import math


def read(run: dict):
    sample = run["latencies_ms"]  # sorted
    return sample[max(math.ceil(0.95 * len(sample)) - 1, 0)] if sample else None
