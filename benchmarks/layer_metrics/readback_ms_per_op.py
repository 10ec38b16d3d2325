"""exec program: ms per traced operation reading program outputs back and
decoding them (`exec.readback`: every device-to-host transfer after the
flags, and `decode_outputs`)."""


def read(run: dict):
    return (run.get("self_times_ms_per_op") or {}).get("exec.readback")
