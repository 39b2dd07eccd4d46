"""Runner "train": one compiled ShardedTrainStep, fed seeded batches.

Set-up builds ONE step object with its state, drives it through its first
steps by the window's own call and feed (these are the steps the reference
follows), and hands that same object to the window.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from . import check, common, device, schedule

TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "layer_norm_fwd", "fused_adamw")


def build(run):
    """(model, step, shapes): the program's trainer as a user builds it
    (recipe of chip_smoke ``_build_trainer``, PR 21), seeded weights in."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.utils import (make_sharded_train_step,
                                                    param_shardings)
    from paddle_tpu.distributed.topology import get_hybrid_communicate_group

    cfg, par, o = run.config, run.config["parallel"], run.config["optimizer"]
    common.init_fleet(dp_degree=par["dp_degree"], mp_degree=par["mp_degree"])
    mesh = get_hybrid_communicate_group().get_mesh()
    model, shapes = common.build_model(
        run, shardings_of=lambda m: param_shardings(m, mesh),
        use_recompute=cfg["runner_settings"]["use_recompute"],
        loss_chunk=cfg["runner_settings"]["loss_chunk"])
    with run.phase("step_construct"):
        opt = paddle.optimizer.AdamW(
            learning_rate=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], epsilon=o["epsilon"],
            weight_decay=o["weight_decay"], parameters=model.parameters(),
            moment_dtype=o["moment_dtype"])
        step = make_sharded_train_step(model, opt)
    return model, step, shapes


def one_step(run, step, it):
    import jax

    with run.span("bench/next_batch"):
        x, y = next(it)
    with run.span("bench/train_step"):
        loss = float(jax.block_until_ready(step(x, y)))
    return x, y, loss


def first_steps(run, step, it, shapes):
    """Drive the step through the steps the check compares; returns the
    program's numbers and the batches it was fed."""
    import jax

    from . import weights

    m, o, ck = run.config["model"], run.config["optimizer"], run.config["check"]
    prog, fed = {"loss": []}, []
    for i in range(ck["program_steps"]):
        t0 = time.perf_counter()
        x, y, loss = one_step(run, step, it)
        run.say(f"first steps: step {i + 1} loss {loss:.6f} "
                f"({time.perf_counter() - t0:.2f} s)")
        prog["loss"].append(loss)
        if i < ck["reference_steps"]:
            fed.append((np.array(x), np.array(y)))
        if i == 0:
            m1 = check.leaf_norms({k: v["moment1"]
                                   for k, v in step.opt_state.items()})
            prog["grad_norm"] = {k: v / (1 - o["beta1"]) for k, v in m1.items()}
        if i == ck["reference_steps"] - 1:
            p0 = weights.make(run.seed, shapes, m["initializer_range"],
                              m["dtype"], dict(step._p_shard))
            prog["delta_norm"] = check.diff_norms(dict(step.params), p0)
            del p0
    return prog, fed


def run(run):
    import jax

    log = run.compile_log
    chips = len(run.devices)
    traffic, m = run.traffic, run.config["model"]
    B, S = traffic["batch"], traffic["seq_len"]
    model, step, shapes = build(run)
    it = schedule.batches(traffic, m["vocab_size"], run.seed)
    with run.phase("first_steps"):
        prog, fed = first_steps(run, step, it, shapes)
    sites = step.kernel_sites
    missing = [k for k in TRAIN_KERNELS if sites.get(k, 0) < 1]
    run.say(f"Mosaic calls in the step: {sites}")
    if missing:
        run.fail_run(f"Mosaic kernels absent from the compiled step: {missing}")
    (exe,) = step._exe.values()   # one batch signature, one executable
    run.exe_bytes["train_step"] = device.executable_bytes(exe)
    run.say(f"step executable bytes (TPU compiler): {run.exe_bytes}")
    compiles_before = log.requests

    # ------------------------------------------------------ the window
    run.setup_s = time.perf_counter() - run.t_start
    if run.trace_on:
        common.start_trace(run)
    trace_s = run.cell.get("trace_seconds", 20.0)
    step_s, losses = [], []
    t0 = time.perf_counter()
    with run.span("bench/window"):
        while True:
            ts = time.perf_counter()
            _, _, loss = one_step(run, step, it)
            te = time.perf_counter()
            step_s.append(te - ts)
            losses.append(loss)
            if run.trace_on and run.trace is None and te - t0 >= min(
                    trace_s, run.seconds):
                break
            if te - t0 >= run.seconds:
                break
    t1 = time.perf_counter()
    if run.trace_on:
        common.stop_trace(run)
    run.window = (t0, t1)
    n = len(step_s)
    run.attempted = n
    run.failed = sum(1 for l in losses if not np.isfinite(l))
    if log.requests != compiles_before:
        run.fail_run(f"{log.requests - compiles_before} compile request(s) "
                     "inside the measured window")
    run.end_to_end["train_tok_s_chip"] = n * B * S / (t1 - t0) / chips
    run.counters.update(steps=n, step_seconds=step_s, tokens_per_step=B * S,
                        shapes=shapes, batch=B, seq_len=S, chips=chips,
                        kernel_sites=sites)
    run.say(f"window: {n} steps of {B}x{S} tokens in {t1 - t0:.3f} s on "
            f"{chips} chip(s); last loss {losses[-1]:.4f}")
    run.memory_peak = device.memory_peak_bytes(run.devices)

    # ------------------------------------- the check, program state freed
    p_shard = dict(step._p_shard) if chips > 1 else None
    del step, model, it
    gc.collect()
    t_ref = time.perf_counter()
    ck = run.config["check"]
    want = check.reference_train(
        m, run.config["optimizer"], shapes, run.seed, fed,
        ck["reference_steps"], ck["rows_per_block"], shardings=p_shard,
        say=run.say)
    run.reference_s = time.perf_counter() - t_ref
    run.correct = check.compare_train(run, prog, want, ck["limits"])
    if run.with_control:
        run.say("control: the reference with fp8 matmuls in the program's "
                "place")
        low = check.reference_train(
            m, run.config["optimizer"], shapes, run.seed, fed,
            ck["reference_steps"], ck["rows_per_block"], mm_name="fp8",
            shardings=p_shard, say=run.say)
        sound, run.compared = run.compared, []
        run.control_correct = check.compare_train(run, low, want, ck["limits"])
        run.control_compared, run.compared = run.compared, sound
