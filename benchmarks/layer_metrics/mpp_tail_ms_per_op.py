"""distsql + store cop: ms per traced operation inside `mpp.tail` itself
(the span's self time): the statement's tail behind the exchange's final
aggregate, HAVING and projection.  Where the tail is traced into the
exchange program the span holds the output offsets taken of its result;
where it stays at the root (a host-only operator, a string computed) the
root's program over the exchange's groups is its child.  Nothing to read
where no operation was traced or the program has no such span (before
PR 38)."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("mpp.tail")
