"""Records the small CPU trace and the program spans that
``test_bench_program_trace.py`` reduces together.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tests/make_program_trace_data.py

Inside ``bench.window``, three steps.  Each step is an engine ``step`` span
of the program's tracer holding a matmul on the device (the CPU backend
here), 20 ms of host work in a ``prefill.place`` span (engine thread) and a
second matmul; after it, outside the ``step`` span, 20 ms of host work in a
``bench.host_attn`` annotation with a ``hostattn-lane1`` span (a lane
thread's track).  ``SpanTracer.sync`` anchors the clock after the trace
starts, after each step and before it stops.  The reduction must blame the
first gaps on ``prefill.place``, and the second on ``bench.host_attn``
(lane-thread spans label no gap).
"""

import glob
import json
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.obs.tracer import SpanTracer

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "cpu_program_trace.xplane.pb")
EVENTS = os.path.join(DATA, "cpu_program_trace.events.json")


def main():
    f = jax.jit(lambda x: (x @ x) @ x)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    tracer = SpanTracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        tracer.sync()
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(3):
                t0 = time.perf_counter()
                f(x).block_until_ready()
                p0 = time.perf_counter()
                time.sleep(0.02)
                tracer.emit("prefill", "prefill.place", p0, time.perf_counter(),
                            {"iter": i, "rid": i})
                f(x).block_until_ready()
                tracer.emit("engine", "step", t0, time.perf_counter(), {"iter": i})
                with jax.profiler.TraceAnnotation("bench.host_attn"):
                    h0 = time.perf_counter()
                    time.sleep(0.02)
                    tracer.emit("hostattn-lane1", "L0", h0, time.perf_counter())
                tracer.sync()
        tracer.sync()
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0], XPLANE)
    with open(EVENTS, "w") as fh:
        json.dump({"dropped": tracer.dropped, "events": [list(e) for e in tracer.events()]},
                  fh, indent=0)
    print(XPLANE, os.path.getsize(XPLANE), EVENTS, os.path.getsize(EVENTS))


if __name__ == "__main__":
    main()
