"""exec program: XLA compiles of eager `jnp` operations, outside any
program, per operation in the window (`XLA_EAGER_COMPILES`).  Waits for
the counter to be named (tests/data/launch_counters.json)."""


def read(run: dict):
    n = run["counters"].get("xla_eager_compiles")
    return n / run["attempted"] if n is not None and run["attempted"] else None
