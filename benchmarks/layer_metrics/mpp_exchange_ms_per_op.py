"""exec program: ms per traced operation that the exchange tier spends
around its program: the self time of `mpp.exchange` (the capacity ladder
and the decode of the merged group table around `exec.program`,
`exec.launch`, `exec.wait` and `exec.readback`, which are its children).
Waits for a cell whose statements take the exchange tier: TPC-H's Q1 and Q3
end in ORDER BY and their scans carry wide strings, so in
`tpch_q1q6q3_mesh4` the per-request mesh tier serves them and there is no
such span to read (tests/test_mesh_metrics.py reads a recorded tree)."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("mpp.exchange")
