"""exec program: device program launches per operation, in the window."""


def read(run: dict):
    return run["counters"]["launches"] / run["attempted"] if run["attempted"] else None
