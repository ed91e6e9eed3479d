#!/usr/bin/env python3
"""How ``tiny_v5e.xplane.pb`` was recorded (on the chip, PR 25): five runs
of one small jitted program, 30 ms of host sleep between them, under the
profiler. ``selfcheck.py`` holds the reduction to the numbers this trace is
known to give. Run it on a machine with a TPU:

    python3 benchmark/fixtures/record.py <out dir>
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out):
    @jax.jit
    def tiny_step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.full((512, 512), 0.01, jnp.bfloat16)
    tiny_step(x, w).block_until_ready()
    trace_dir = os.path.join(out, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(5):
        with jax.profiler.TraceAnnotation("dispatch"):
            y = tiny_step(x, w)
        with jax.profiler.TraceAnnotation("fence"):
            y.block_until_ready()
        time.sleep(0.03)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    shutil.copy(found[-1], os.path.join(out, "tiny_v5e.xplane.pb"))
    print(os.path.getsize(found[-1]), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
