#!/usr/bin/env python3
"""Record the small profiler trace that ``test_bench_trace_reduce.py``
reads: on a TPU, a window annotated as the harness annotates it, with two
kinds of jitted work and host pauses between them.

    python bench/tests/make_trace_data.py OUT.xplane.pb
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    assert jax.devices()[0].platform == "tpu", "record the test trace on a TPU"
    mm = jax.jit(lambda x: x @ x)
    sm = jax.jit(lambda x: jnp.tanh(x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    mm(x).block_until_ready()
    sm(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.submit"):
                y = mm(x)
                z = sm(y)
            with jax.profiler.TraceAnnotation("bench.wait"):
                z.block_until_ready()
            with jax.profiler.TraceAnnotation("host.pause"):
                time.sleep(0.02 * (i + 1))
    jax.profiler.stop_trace()
    shutil.copy(sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1], out)
    shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
