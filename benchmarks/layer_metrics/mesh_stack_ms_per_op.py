"""distsql + store cop: ms per traced operation inside `mesh.stack` itself
(the span's self time): the region lanes of a statement stacked on the host
into one rectangular batch and handed to the devices, which both cross-chip
tiers do anew for every statement.  Nothing to read where no operation was
traced or the program has no such span."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("mesh.stack")
