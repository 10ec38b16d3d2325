"""distsql + store cop: ms per traced operation inside the two phases of a
transaction's commit, the self times of `txn.prewrite` (the conflict and
lock checks, and the locks put on every key written) and `txn.commit` (the
commit ts drawn, the writes applied, the locks released; the store's drop
of its version caches is a span of its own inside it, read by
`cache_drop_ms_per_op`).  Nothing to read where no operation was traced or
the program has no such spans."""


def read(run: dict):
    st = run.get("self_times_ms_per_op") or {}
    parts = [st[name] for name in ("txn.prewrite", "txn.commit") if name in st]
    return round(sum(parts), 4) if parts else None
