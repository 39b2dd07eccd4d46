"""Op micro-benchmark gate (tools/ci_op_benchmark.sh +
check_op_benchmark_result.py analog, SURVEY §4 CI tooling).

Times a representative op set and compares against a JSON baseline:

    python tools/op_benchmark.py --save baseline.json      # record
    python tools/op_benchmark.py --check baseline.json     # gate (exit 1 on
                                                           #  >threshold regression)

The reference gates PRs against a rolling baseline service; here the baseline
is a file checked in or produced by a previous CI run. Each timing ends in
``block_until_ready``; it runs on whatever device jax selects, and the output
names it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def build_cases():
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle

    rng = np.random.RandomState(0)
    big = 1024 if paddle.core.place.on_tpu() else 256
    a = jnp.asarray(rng.randn(big, big).astype(np.float32))
    v = jnp.asarray(rng.randn(4, big).astype(np.float32))
    img = jnp.asarray(rng.randn(8, 16, 32, 32).astype(np.float32))
    ker = jnp.asarray(rng.randn(16, 16, 3, 3).astype(np.float32))

    t_a = paddle.to_tensor(a)
    t_v = paddle.to_tensor(v)
    t_img = paddle.to_tensor(img)
    t_ker = paddle.to_tensor(ker)
    ln_w = paddle.ones([int(v.shape[-1])])
    ln_b = paddle.zeros([int(v.shape[-1])])

    return {
        "matmul": lambda: paddle.matmul(t_a, t_a),
        "softmax": lambda: paddle.nn.functional.softmax(t_v, axis=-1),
        "layer_norm": lambda: paddle.nn.functional.layer_norm(
            t_v, [int(v.shape[-1])], weight=ln_w, bias=ln_b),
        "conv2d": lambda: paddle.nn.functional.conv2d(t_img, t_ker, padding=1),
        "reduce_sum": lambda: paddle.sum(t_a, axis=-1),
        "transpose": lambda: paddle.transpose(t_a, [1, 0]),
        "gelu": lambda: paddle.nn.functional.gelu(t_a),
    }


def measure(fn, repeats: int = 5) -> float:
    import jax

    jax.block_until_ready(fn()._value)  # compile/warmup
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn()._value)
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--save", help="write baseline JSON to this path")
    ap.add_argument("--check", help="compare against this baseline JSON")
    ap.add_argument("--threshold", type=float, default=1.5,
                    help="fail if median time exceeds baseline x threshold")
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    import jax

    results = {name: measure(fn, args.repeats) for name, fn in build_cases().items()}
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    for name, t in sorted(results.items()):
        print(f"{name:12s} {t * 1e6:10.1f} us")

    if args.save:
        with open(args.save, "w") as f:
            json.dump(results, f, indent=2)
        print(f"baseline written to {args.save}")
    if args.check:
        with open(args.check) as f:
            baseline = json.load(f)
        failures = []
        ungated = sorted(set(results) - set(baseline))
        orphaned = sorted(set(baseline) - set(results))
        if ungated:
            print(f"WARNING: ops with no baseline entry (ungated): {ungated}")
        if orphaned:
            print(f"WARNING: stale baseline entries with no current op: {orphaned}")
        for name, t in results.items():
            base = baseline.get(name)
            if base is not None and t > base * args.threshold:
                failures.append(f"{name}: {t * 1e6:.1f}us vs baseline "
                                f"{base * 1e6:.1f}us (> x{args.threshold})")
        if failures:
            print("OP BENCHMARK REGRESSIONS:")
            for f_ in failures:
                print(" ", f_)
            sys.exit(1)
        print("no regressions vs baseline")


if __name__ == "__main__":
    main()
