"""server + session + planner: ms per operation encoding result sets and
handing them to the socket (`SERVER_WRITE_NS`: the wall time of the host
state `server.write`), over every operation of the window, the plain ones
with their whole result sets included (a `TRACE` statement answers one
row).  Nothing to read where the counter is not named
(program_names.tracing.json)."""


def read(run: dict):
    ns = run["counters"].get("server_write_ns")
    return ns / 1e6 / run["attempted"] if ns is not None and run["attempted"] else None
