"""device: ms per operation between a program call's return and its
outputs' arrival on the host (`PROGRAM_WAIT_NS`: the wall time of the host
state `exec.wait`: device queue, execution, the launch's one transfer),
over every operation of the window and every thread that launched.  What
`device_wait_ms_per_op` reads from the spans of traced operations, in every
cell and over the plain ones too.  Nothing to read where the counter is not
named (program_names.tracing.json)."""


def read(run: dict):
    ns = run["counters"].get("program_wait_ns")
    return ns / 1e6 / run["attempted"] if ns is not None and run["attempted"] else None
