"""exec program: JAX's tracing and MLIR lowering, ms per traced operation:
the self time of the `exec.compile` spans (program calls in which JAX
traced or compiled; the backend compiles hang under them as
`exec.xla_compile`).  None where the program has no launch boundary."""


def read(run: dict):
    spans = run.get("self_times_ms_per_op") or {}
    return spans.get("exec.compile", 0.0) if "exec.wait" in spans else None
