"""distsql + store cop: ms per traced operation inside `txn.lock` (the
span's self time): a pessimistic statement's lock acquire, with its wait
for another transaction's lock on the same rows until that one commits or
rolls back, and for the transaction engine's and the store's mutexes.
Nothing to read where no operation was traced or the program has no such
span."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("txn.lock")
