"""Records the small profiler trace that `test_xplane.py` reads: a few
jitted calls on the device under two client marks, with gaps between.

    python benchmarks/tests/record_fixture.py <out_dir>      (on the chip)
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    trace_dir = tempfile.mkdtemp(prefix="fixture_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for i in range(6):
        with jax.profiler.TraceAnnotation("stmt:q1" if i % 2 else "stmt:q6"):
            f(x).block_until_ready()
        time.sleep(0.005)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
               key=os.path.getmtime)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(trace_dir)
    print(jax.devices()[0].device_kind, os.path.getsize(os.path.join(out_dir, "small.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])
