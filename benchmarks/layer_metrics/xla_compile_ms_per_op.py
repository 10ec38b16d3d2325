"""exec program: XLA backend compiles of programs, ms per traced operation:
the `exec.xla_compile` spans, one per `backend_compile_duration` event that
the program's listener heard during a program call.  None where the
program has no launch boundary."""


def read(run: dict):
    spans = run.get("self_times_ms_per_op") or {}
    return spans.get("exec.xla_compile", 0.0) if "exec.wait" in spans else None
