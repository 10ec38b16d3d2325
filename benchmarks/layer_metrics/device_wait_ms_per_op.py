"""device: ms per traced operation between a program call's return and its
overflow flags' arrival on the host (`exec.wait`: device queue, execution,
the flags' transfer)."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("exec.wait")
