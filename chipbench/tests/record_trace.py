#!/usr/bin/env python3
"""Record the small trace the trace-reduction tests read.

    python3 chipbench/tests/record_trace.py <out.xplane.pb>

Three steps of two small programs (``jit_step``, ``jit_finish``) under
the benchmark's own spans (``chipbench.window``, ``.step``, ``.wait``,
``.idle``), traced by the JAX profiler on whatever device JAX finds: on a
TPU chip the file holds a ``/device:TPU:0`` plane, on the CPU only host
planes, which ``trace.reduce`` reads only with ``host_ops_as_chip``.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time


def record(out: str) -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w) + x

    @jax.jit
    def finish(x):
        return jnp.sum(x * x)

    x = jnp.ones((512, 512), jnp.float32) * 0.01
    w = jnp.eye(512, dtype=jnp.float32)
    jax.block_until_ready(finish(step(x, w)))          # compile outside
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("chipbench.step"):
                    x = step(x, w)
                with jax.profiler.TraceAnnotation("chipbench.wait"):
                    jax.block_until_ready(finish(x))
                with jax.profiler.TraceAnnotation("chipbench.idle"):
                    time.sleep(0.002)
        jax.profiler.stop_trace()
        found = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))
        shutil.copyfile(found[-1], out)


if __name__ == "__main__":
    record(sys.argv[1])
