"""What the host-state clock costs on this host, without a device: ns per
call of the two clocks it reads (`time.perf_counter_ns` at every
transition, `time.thread_time_ns` once a command) and of their siblings,
and ns per
enter/exit pair of a host state and of a plain span (`util/tracing.py`),
untraced, on 1 and 16 threads.

    python tools/host_clock_probe.py [--device] [checkout ...]

A checkout is a directory holding `tidb_tpu/` (default: this file's); each
is probed in a process of its own, so a parent commit unpacked beside the
tree is measured in the same call.  `--device` starts JAX's default backend
in the process before the clocks are timed (on the chip's host the TPU
runtime's threads and its sandbox are then there, as in the server); the
pairs always run with JAX held to the CPU.  One JSON line a reading."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import timeit

PAIRS = 200_000


def clocks() -> None:
    for name in ("perf_counter_ns", "thread_time_ns", "monotonic_ns", "process_time_ns"):
        fn = getattr(time, name)
        best = min(timeit.repeat(fn, number=200_000, repeat=5)) / 200_000 * 1e9
        print(json.dumps({"clock": name, "ns_per_call": round(best, 1),
                          "resolution_ns": round(time.get_clock_info(name[:-3]).resolution * 1e9, 1)}), flush=True)


def pairs(checkout: str) -> None:
    sys.path.insert(0, checkout)
    from tidb_tpu.util import tracing

    def loop(n: int, state: str) -> None:
        span = tracing.span
        for _ in range(n):
            with span(state):
                pass

    def run(threads: int, state: str) -> float:
        def work():
            if hasattr(tracing, "host_state"):
                with tracing.host_state("server.command"):
                    loop(PAIRS // threads, state)
            else:
                loop(PAIRS // threads, state)

        ts = [threading.Thread(target=work) for _ in range(threads)]
        t0 = time.perf_counter_ns()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return (time.perf_counter_ns() - t0) / PAIRS

    for state in ("exec.wait", "cop.execute"):   # a host state, a plain span
        for threads in (1, 16):
            best = min(run(threads, state) for _ in range(3))
            print(json.dumps({"checkout": checkout, "span": state, "threads": threads,
                              "ns_per_pair": round(best, 1)}), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--pairs":
        pairs(sys.argv[2])
    else:
        args = sys.argv[1:]
        if "--device" in args:
            args.remove("--device")
            import jax

            print(json.dumps({"device": jax.devices()[0].platform, "count": len(jax.devices())}), flush=True)
        clocks()
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for checkout in args or [here]:
            subprocess.run([sys.executable, os.path.abspath(__file__), "--pairs", os.path.abspath(checkout)],
                           check=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
