"""distsql + store cop / columnar route: ms per traced operation in the
replica's staleness gate (`columnar.gate`: frontier against snapshot, and
the `data_not_ready` back-off where the frontier trails).  Nothing to
read on a program without the span."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("columnar.gate")
