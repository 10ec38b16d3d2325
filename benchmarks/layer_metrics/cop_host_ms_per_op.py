"""distsql + store cop / columnar route, host side: `distsql.execute_root`
less the `exec.program` spans under it.  A program's first-call XLA compile
lands here; `programs_built_per_op` says when."""

import statistics


def read(run: dict):
    traced = run["traced"]
    return statistics.mean(t["cop_host_ns"] for t in traced) / 1e6 if traced else None
