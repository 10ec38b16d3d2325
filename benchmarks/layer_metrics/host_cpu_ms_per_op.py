"""server + session + planner: thread-CPU ms per operation of the threads
that serve it: the serving threads' from one wire command's close to the
next's (`SERVER_CPU_NS`) plus the dispatch pool workers' from task to task
(`HOST_POOL_CPU_NS`), over all operations of the window, plain and traced.
Times `ops_per_s` it is the share of one core that the statements' own
threads keep busy: near 1 the cell is set by the interpreter (native code
that releases the GIL counts too, so it can pass 1).  Read once a command
and once a task, not by state: on the chip's host the thread CPU clock is a
5.5 us system call that ticks at 10 ms (util/tracing.py).  Waits for the
counters to be named (tests/data/host_state_counters.json): nothing to
read until then."""


def read(run: dict):
    c = run["counters"]
    if "server_cpu_ns" not in c or "host_pool_cpu_ns" not in c or not run["attempted"]:
        return None
    return (c["server_cpu_ns"] + c["host_pool_cpu_ns"]) / 1e6 / run["attempted"]
