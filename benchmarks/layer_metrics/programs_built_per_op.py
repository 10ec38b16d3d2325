"""exec program: programs built (ProgramCache misses) per operation, in the window."""


def read(run: dict):
    return run["counters"]["programs_built"] / run["attempted"] if run["attempted"] else None
