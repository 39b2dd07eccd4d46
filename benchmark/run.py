"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run, on the machine it is started on. It fails on anything
but the TPU chips the cell asks for (no CPU fallback, no interpret mode),
makes weights and traffic from ``--seed``, warms up every program the cell's
traffic reaches (set-up), measures for ``--seconds``, then frees the program
and checks what the window produced against benchmark/reference/gpt.py.
Every line names platform, device kind and device count; the LAST stdout line
is the contract's one JSON object. Everything is found by name: the cell in
workloads/, its configuration in configs/, its traffic in traffic/, each
per-layer metric's reader in layer_metrics/ (see README.md).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(man: dict, cell: str, kind: str):
    """The metric entries of ``kind`` (end_to_end | per_layer) that the cell
    reports: those that list it under ``workloads``, and those with no such
    key."""
    return [m for m in man[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    from harness import common

    path = common.find("layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def drive(run, man: dict) -> dict:
    """Everything after the look for a chip: run the cell's runner, reduce,
    and build the result object."""
    import jax

    from harness import device, trace

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    run.compile_log = device.CompileLog()
    run.say(f"cell {run.cell_name}: config {run.cell['config']}, traffic "
            f"{run.cell['traffic']}, seed {run.seed}, {run.seconds:.0f} s, "
            f"trace {int(run.trace_on)}; compile cache at "
            f"{jax.config.jax_compilation_cache_dir} (max size "
            f"{jax.config.jax_compilation_cache_max_size})")
    runner = importlib.import_module("harness.run_" + run.config["runner"])
    runner.run(run)

    run.say(f"set-up {run.setup_s:.2f} s, of which {run.setup_split}; "
            f"compiles {run.compile_log.snapshot()}; check took "
            f"{run.reference_s:.1f} s (not set-up)")
    if run.failures:
        run.attempted = max(run.attempted, 1)
        run.failed = run.attempted
    d0 = run.devices[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(run.devices),
           "memory_peak_bytes": getattr(run, "memory_peak", 0)}
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": {}, "device": dev}
    if not run.trace_on:
        values = dict(run.end_to_end, setup_s=run.setup_s)
        for m in metrics_of(man, run.cell_name, "end_to_end"):
            if values.get(m["name"]) is not None:
                out["metrics"][m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
    else:
        t0 = time.perf_counter()
        for m in metrics_of(man, run.cell_name, "per_layer"):
            v = reader(m["name"])(run)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        t1 = time.perf_counter()
        if run.trace is not None:
            dev["busy_s"] = trace.busy_seconds(run.trace)
            dev["window_s"] = trace.window_seconds(run.trace)
            out["breakdown"] = {
                "device_ops": trace.top_ops(run.trace, 10),
                "idle_gaps": trace.idle_gaps_by_span(run.trace, 10)}
        t2 = time.perf_counter()
        run.say(f"after the window: per-layer readers {t1 - t0:.1f} s, "
                f"breakdown {t2 - t1:.1f} s; result line at "
                f"{t2 - run.t_start:.1f} s from process start")
    return out


def new_run(workload, seed, seconds, trace, t_start=None):
    """A Run on the chips its cell asks for (exits 2 without them), with the
    table of peaks; the program is imported last, which places the compile
    cache in the checkout."""
    from harness import common, device

    run = common.Run(workload, seed, seconds, trace,
                     time.perf_counter() if t_start is None else t_start)
    run.devices = device.gate(run.cell["chips"])
    d0 = run.devices[0]
    run.dev_tag = f"{d0.platform} {d0.device_kind} x{len(run.devices)}"
    run.peaks = device.peaks(d0.device_kind)
    import paddle_tpu  # noqa: F401

    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    man = manifest()
    if a.workload not in [w["name"] for w in man["workloads"]]:
        print(f"benchmark: no cell {a.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    run = new_run(a.workload, a.seed, a.seconds, a.trace, T_START)
    out = drive(run, man)
    print(json.dumps(out), flush=True)  # nothing after it on stdout
    return 0


if __name__ == "__main__":
    sys.exit(main())
