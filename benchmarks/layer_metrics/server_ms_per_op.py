"""server + session + planner: ms per operation inside the wire server,
from a command's packet read until its last reply byte is handed to the
socket (`SERVER_HANDLE_NS`); the client's latency less this is the wire.
Waits for the counter to be named (tests/data/launch_counters.json)."""


def read(run: dict):
    ns = run["counters"].get("server_handle_ns")
    return ns / 1e6 / run["attempted"] if ns is not None and run["attempted"] else None
