"""server + session + planner: the share of the time inside wire commands
that no host state names: `server.command`'s own wall time
(`HOST_SERVER_COMMAND_NS`) over `SERVER_HANDLE_NS`, in percent.  Has to stay
under 10: above it a state is missing from the clock (util/tracing.py).
Waits for the counter to be named (tests/data/host_state_counters.json):
nothing to read until then."""


def read(run: dict):
    c = run["counters"]
    if "host_server_command_ns" not in c or not c.get("server_handle_ns"):
        return None
    return 100.0 * c["host_server_command_ns"] / c["server_handle_ns"]
