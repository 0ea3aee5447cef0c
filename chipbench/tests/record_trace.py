#!/usr/bin/env python3
"""Record the small trace that `test_tracing.py` reads, on a TPU.

    python3 chipbench/tests/record_trace.py chipbench/tests/data/small.xplane.pb

Three rounds of: a `bench.step` span around a jitted step (an XLA matmul
and a Pallas kernel named `_double_kernel`), then a `bench.wait` span
around a 2 ms sleep, all inside a `bench.window` span.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


@jax.jit
def step(x):
    y = jnp.tanh(x @ x)
    return pl.pallas_call(_double_kernel,
                          out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype))(y)


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    x = jnp.ones((512, 512), jnp.float32) / 512.0
    step(x).block_until_ready()
    d = tempfile.mkdtemp(prefix="record_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    step(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.002)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        shutil.copy(src[0], out)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
