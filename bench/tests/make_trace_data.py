"""Records the small CPU trace that ``test_bench_trace.py`` reduces.

    JAX_PLATFORMS=cpu python bench/tests/make_trace_data.py

Three steps, each a matmul on the device (the CPU backend here) followed by
20 ms of host work inside a ``bench.host_attn`` span, all inside
``bench.window``; the reduction must find the matmuls busy and blame the
gaps on ``bench.host_attn``.
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "cpu_trace.xplane.pb")


def main():
    f = jax.jit(lambda x: (x @ x) @ x)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
                    with jax.profiler.TraceAnnotation("bench.host_attn"):
                        time.sleep(0.02)
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0], OUT)
    print(OUT, os.path.getsize(OUT))


if __name__ == "__main__":
    main()
