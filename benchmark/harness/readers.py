"""The readers behind benchmark/layer_metrics/*: each takes the run and
returns a number, or None where it finds nothing to read (the harness then
leaves the metric out of the line). Shares that could be miscounted past
100% are built from required work and published peaks only."""

from __future__ import annotations

import math
import sys
from statistics import median

from . import stats, trace
from .common import BENCH

sys.path.insert(0, BENCH)
from roofline import flash, fused_adamw, paged_decode  # noqa: E402


def _pct(x):
    return None if x is None else 100.0 * x


# ----------------------------------------------------------------- L5 host

def device_idle_share(run):
    if run.trace is None or trace.window_seconds(run.trace) <= 0:
        return None
    return _pct(1.0 - trace.busy_seconds(run.trace)
                / trace.window_seconds(run.trace))


def gen_lateness_p90_ms(run):
    return stats.percentile(run.counters.get("lateness_ms", []), 90)


# ------------------------------------------------------ L3 scheduler/engine

def batch_occupancy(run):
    steps = run.counters.get("steps")
    if not steps or not isinstance(steps, list):
        return None
    return _pct(sum(s[2] for s in steps) / len(steps)
                / run.counters["max_batch_size"])


def prefix_hit_share(run):
    adm = run.counters.get("prompt_tokens_admitted")
    if not adm:
        return None
    return _pct(run.counters["prompt_tokens_hit"] / adm)


def ttft_p90_ms(run):
    return stats.percentile(run.counters.get("ttft_ms", []), 90)


def tpot_p90_ms(run):
    return stats.percentile(run.counters.get("tpot_ms", []), 90)


def tok_gap_p95_ms(run):
    return stats.percentile(run.counters.get("gaps_ms", []), 95)


# -------------------------------------------------------- L3 step / program

def step_ms(run):
    s = run.counters.get("step_seconds")
    return None if not s else 1e3 * median(s)


_is_decode = lambda n: "decode" in n
_is_prefill = lambda n: "prefill" in n or "extend" in n


def decode_step_ms(run):
    """Median device time of one decode program execution."""
    if run.trace is None:
        return None
    runs = trace.module_runs(run.trace, _is_decode)
    return 1e3 * median(runs) if runs else None


def prefill_busy_share(run):
    """Prefill and extend programs' share of all program time on the
    device."""
    if run.trace is None:
        return None
    every = trace.module_runs(run.trace, lambda n: True)
    if not every:
        return None
    return _pct(sum(trace.module_runs(run.trace, _is_prefill)) / sum(every))


def required_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 x the matmul
    parameters (blocks and the output head; the embedding tables are
    look-ups) plus causal attention at half the square. Recompute is the
    program's choice and is not counted."""
    h, L, ffn = model["hidden_size"], model["num_layers"], model["intermediate_size"]
    block = 4 * h * h + 2 * h * ffn          # qkv 3h^2, proj h^2, fc1, fc2
    matmul_params = L * block + model["vocab_size"] * h
    attention = L * 6 * seq_len * h          # 3 x (QK^T + PV) x S/2 x 2 h
    return 6.0 * matmul_params + attention


def mfu(run):
    c = run.counters
    if "tokens_per_step" not in c or not c.get("step_seconds"):
        return None
    tok_s_chip = c["tokens_per_step"] * len(c["step_seconds"]) \
        / sum(c["step_seconds"]) / c["chips"]
    return _pct(required_flops_per_token(run.config["model"], c["seq_len"])
                * tok_s_chip / run.peaks["flops_per_s"])


# ---------------------------------------------------------------- L3 memory

def hbm_fill(run):
    """The fullest program: arguments (the resident state is among them) +
    temporaries + outputs that alias no argument, as the TPU compiler counted
    them, over the device's limit."""
    from . import device

    limit = device.bytes_limit(run.devices)
    if not run.exe_bytes or not limit:
        return None
    need = max(b["argument"] + b["temp"] + b["output"] - b["alias"]
               for b in run.exe_bytes.values())
    return _pct(need / limit)


# ----------------------------------------------------------- L4 collectives

def exposed_collective_share(run):
    if run.trace is None or len(run.trace["devices"]) < 2:
        return None
    return _pct(trace.exposed_collective_seconds(run.trace)
                / trace.window_seconds(run.trace))


# --------------------------------------------------------------- L2 kernels

def _share(run, min_s, kernel_s, bound, what):
    if kernel_s <= 0:
        return None
    run.say(f"roofline {what}: least {min_s:.4f} s ({bound}-bound) over "
            f"{kernel_s:.4f} s of kernel time")
    return _pct(min_s / kernel_s)


def flash_roofline(run):
    if run.trace is None:
        return None
    c, m = run.counters, run.config["model"]
    mp = run.config["parallel"]["mp_degree"]
    dp = run.config["parallel"]["dp_degree"]
    B, S = c["batch"] // dp, c["seq_len"]
    H, D = m["num_heads"] // mp, m["hidden_size"] // m["num_heads"]
    n_fwd = trace.op_count(run.trace, lambda n: "flash_fwd" in n)
    n_bwd = trace.op_count(run.trace, lambda n: "flash_bwd_dq" in n)
    secs = trace.op_seconds(run.trace, lambda n: "flash_fwd" in n
                            or "flash_bwd" in n)
    tf, bf = flash.min_seconds(flash.fwd(B, H, S, D), run.peaks)
    tb, _ = flash.min_seconds(flash.bwd(B, H, S, D), run.peaks)
    return _share(run, n_fwd * tf + n_bwd * tb, secs, bf, "flash")


def fused_adamw_roofline(run):
    if run.trace is None:
        return None
    c = run.counters
    mp = run.config["parallel"]["mp_degree"]
    big = [n for n in map(math.prod, c["shapes"].values()) if n >= 1 << 16]
    calls = trace.op_count(run.trace, lambda n: "fused_adamw" in n)
    secs = trace.op_seconds(run.trace, lambda n: "fused_adamw" in n)
    if not big or not calls:
        return None
    steps = calls / len(big)
    # under mp each chip updates its own 1/mp of a sharded leaf; counting
    # every big leaf as sharded undercounts (never overcounts) the work
    t, bound = fused_adamw.min_seconds(
        fused_adamw.update(sum(big) / mp), run.peaks)
    return _share(run, steps * t, secs, bound, "fused_adamw")


def paged_decode_roofline(run):
    if run.trace is None or run.trace_host is None:
        return None
    m = run.counters["model"]
    ta, tb = run.trace_host
    # steps wholly inside the traced stretch: their kernels are all in the
    # trace (a step cut by its edge adds kernel time and no required work)
    ctx = sum(s[3] for s in run.counters["steps"] if ta <= s[0] and s[1] <= tb)
    secs = trace.op_seconds(run.trace, lambda n: "paged_decode" in n)
    H, D = m["num_heads"], m["hidden_size"] // m["num_heads"]
    t, bound = paged_decode.min_seconds(
        paged_decode.call(ctx * m["num_layers"], H, D), run.peaks)
    return _share(run, t, secs, bound, "paged_decode")
