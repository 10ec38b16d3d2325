"""distsql + store cop: ms per traced operation that the statement's own
thread spent blocked on its dispatch pool (`distsql.wait_tasks`, the span's
self time): pool start, the wait for the tasks' futures, pool shutdown.
The tasks' host work is the workers', under `distsql.cop_task` and the
store's spans.  Nothing to read where no operation was traced or the
program has no such span."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("distsql.wait_tasks")
