#!/usr/bin/env python3
"""How benchmark/tests/data/small.xplane.pb was made (one v5e chip):

    python3 benchmark/tests/record_trace.py <out dir>

Two harness-style sessions around a few small jitted programs, traced with
the options run.py uses. test_devtrace.py reads the file it wrote.
"""

import glob
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(2):
        with TraceAnnotation("bench.session"):
            with TraceAnnotation("bench.open"):
                f(x).block_until_ready()
            with TraceAnnotation("bench.actions"):
                for _ in range(3):
                    f(x).block_until_ready()
            with TraceAnnotation("bench.close"):
                pass
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
