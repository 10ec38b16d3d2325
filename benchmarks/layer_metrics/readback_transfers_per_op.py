"""exec program: device arrays converted to host arrays in read-back, per
operation in the window (`PROGRAM_READBACK_TRANSFERS`).  Waits for the
counter to be named (tests/data/launch_counters.json)."""


def read(run: dict):
    n = run["counters"].get("readback_transfers")
    return n / run["attempted"] if n is not None and run["attempted"] else None
