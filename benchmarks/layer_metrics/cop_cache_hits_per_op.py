"""store cop: coprocessor result-cache hits per operation, in the window."""


def read(run: dict):
    return run["counters"]["cop_cache_hits"] / run["attempted"] if run["attempted"] else None
