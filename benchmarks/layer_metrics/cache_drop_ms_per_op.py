"""distsql + store cop: ms per traced operation inside `store.cache_drop`
(the span's self time): a commit drops every version-keyed cache of the
store, the cop result cache, the decoded region chunks and their device
batches, and lets the batches' device memory go.  Nothing to read where no
operation was traced or the program has no such span."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("store.cache_drop")
