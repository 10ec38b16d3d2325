"""distsql + store cop / columnar route: ms per traced operation inside
`columnar.scan` itself (the span's self time: the replica's scan, the
batch hand-over and the driver's Python around the program's own spans
`exec.launch`, `exec.wait` and `exec.readback`, which are its children)."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("columnar.scan")
