"""Records the small chip trace kept beside the tests (run once, on the chip:
``python3 benchmark/tests/record_trace.py`` writes
``chiprun_out/small_trace.xplane.pb``). A few jitted steps under the
benchmark's span names, so that the reduction can be checked against a trace
of the kind it will meet."""

import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]


def main():
    import jax
    import jax.numpy as jnp

    from harness import trace

    assert jax.devices()[0].platform == "tpu", "record this on the chip"
    f = jax.jit(lambda a: jnp.tanh(a @ a) * 0.5)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(f(x))
    d = os.path.join(ROOT, ".bench_out", "small_trace")
    shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench/engine_step"):
                x = jax.block_until_ready(f(x))
            with jax.profiler.TraceAnnotation("bench/add_request"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    src = trace.find_xplane(d)
    shutil.copy(src, os.path.join(out, "small_trace.xplane.pb"))
    tr = trace.reduce_file(src)
    print("planes reduced:", {k: len(v) for k, v in tr["devices"].items()},
          "modules", {k: [m[2] for m in v][:3] for k, v in tr["modules"].items()},
          "spans", len(tr["spans"]), "window", trace.window_seconds(tr),
          "busy", trace.busy_seconds(tr), "top", trace.top_ops(tr, 5),
          "gaps", trace.idle_gaps_by_span(tr))
    from jax.profiler import ProfileData
    for pl in ProfileData.from_file(src).planes:
        print("PLANE", pl.name, [(l.name, len(list(l.events))) for l in pl.lines][:12])
    print("size", os.path.getsize(src))


if __name__ == "__main__":
    main()
