"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # every leg the visible devices allow

Drives the two main paths once, through the entry points a user calls, at
the full width of GPT-3 1.3B (models.GPT3_1p3B: hidden 2048, 24 layers, 16
heads, vocab 50304, S=2048; bf16; weights random from a seed):

  gate       jax.devices()[0].platform must be "tpu" — else exit 2 before
             any work. It fails on a CPU; it never shrinks to fit one.
  kernels    each Pallas kernel, compiled by Mosaic (never interpreted), at
             one real shape against the jnp reference that lives beside it.
  trainer    fleet.init + make_sharded_train_step + AdamW(bf16 moments),
             B=16 S=2048, recompute, chunked loss: five steps on one batch.
  server     serving.Engine over the SAME weights, paged KV, 8 slots, S_max
             2048: pass A plain, pass B prefix cache + speculative k=4; then
             an exact token-identity check on a small f32 model (compiled
             paged kernel == oracle == pass-B settings).
  four_chip  with >= 4 devices: the trainer under dp2 x mp2, global B=16.

One process holds the chip for the whole run (nothing is spawned). A failed
check raises: the exit code is non-zero and no result line is printed. Every
line names the device. The line before last is ``summary: {"legs": ...,
"compile_cache": ..., "claim": null}`` — this script claims no speed, it only
shows the program is right where users run it — and the last stdout line is
exactly ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
with the device as jax reports it.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

LEGS = ("kernels", "trainer", "server", "four_chip")
TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "layer_norm_fwd", "fused_adamw")
#: activations + workspace of the 1.3B step per batch row: the TPU compiler's
#: memory_analysis() gives temp 6.17 GiB at B=16 S=2048 (PR 21, compile-only
#: run against the v5e topology) — used only to pick a batch that fits
TEMP_BYTES_PER_ROW = int(6.17 * 2**30 / 16)

_DEV = "?"


def say(msg: str) -> None:
    print(f"[{_DEV}] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")
    say(f"ok: {what}")


class CompileLog:
    """Counts what jax compiled and what its persistent cache answered."""

    def __init__(self):
        import jax

        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"requests": self.requests, "cache_hits": self.hits,
                "cache_misses": self.misses}


def result_line(devices) -> str:
    """The last stdout line of a passing run: exactly the keys ``ok`` and
    ``device`` (``platform``, ``kind``, ``count``), the device as jax
    reports it. Everything else the run learned goes on the summary line
    before it — the driver's check refuses any other key here."""
    d0 = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}})


def close(got, want, tol: float) -> float:
    """max |got - want| over the reference's max magnitude; raises past
    tol (a wrong kernel is off by O(1), bf16 rounding by O(1e-2))."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise AssertionError(f"shape/finite: {got.shape} vs {want.shape}")
    err = float(np.abs(got - want).max() / (np.abs(want).max() or 1.0))
    if err > tol:
        raise AssertionError(f"relative error {err:.4g} > {tol}")
    return round(err, 5)


# --------------------------------------------------------------- kernels

def _compiled(fn, args, kernel: str, present: bool = True):
    """AOT-compile a fresh trace of fn; ``kernel`` must (or, for the
    reference path, must not) be a Mosaic call in the program."""
    import jax

    from paddle_tpu.kernels.mesh import kernel_sites

    # a new function object per call: jit caches traces by function
    # identity, and the kernel/reference choice is made at trace time
    exe = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    sites = kernel_sites(exe)
    if (sites.get(kernel, 0) >= 1) != present:
        raise AssertionError(f"{kernel} present={not present} in {sites}")
    return exe


def leg_kernels(S: int = 2048, H: int = 16, D: int = 128, hidden: int = 2048,
                tol: float = 5e-2) -> dict:
    """Flash fwd+bwd, fused LN fwd+bwd, fused AdamW, paged decode: the
    kernel path (flag on) against the jnp reference path (flag off) of the
    same public functional, both compiled for this device, bf16."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn import functional as F
    from paddle_tpu.kernels.paged_attention import (decode_attend,
                                                    paged_attention)
    from paddle_tpu.kernels.pools import paged_gather

    rng = np.random.RandomState(0)
    bf = lambda *shape, scale=1.0: jnp.asarray(
        rng.randn(*shape) * scale, jnp.bfloat16)
    errs = {}

    def both(fn, args, kernel):
        """(kernel path, reference path) outputs of fn(*args): the same
        public functional, FLAGS_use_pallas_kernels on and off."""
        got = _compiled(fn, args, kernel)(*args)
        paddle.set_flags({"use_pallas_kernels": False})
        try:
            want = _compiled(fn, args, kernel, present=False)(*args)
        finally:
            paddle.set_flags({"use_pallas_kernels": True})
        say(f"ok: {kernel} is a Mosaic call in the compiled program, and "
            "absent from the reference")
        return jax.block_until_ready(got), jax.block_until_ready(want)

    # flash attention, causal, fwd + bwd through the functional's autodiff
    q, k, v, do = (bf(2, S, H, D) for _ in range(4))

    def attn(q, k, v):
        with paddle.no_grad():  # jax differentiates; the eager tape stays off
            return F.scaled_dot_product_attention(
                Tensor(q), Tensor(k), Tensor(v), is_causal=True)._value

    def attn_vjp(q, k, v, do):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(do)

    got, want = both(attn_vjp, (q, k, v, do), "flash_fwd")
    errs["flash"] = [close(g, w, tol) for g, w in zip(got, want)]
    say(f"ok: flash fwd+bwd [2,{S},{H},{D}] bf16 vs _sdpa_ref: rel err "
        f"out/dq/dk/dv {errs['flash']}")

    # fused LayerNorm fwd (kernel) + bwd
    x, dy = bf(2 * S, hidden), bf(2 * S, hidden)
    w = jnp.asarray(1 + 0.1 * rng.randn(hidden), jnp.bfloat16)
    b = jnp.asarray(0.1 * rng.randn(hidden), jnp.bfloat16)

    def ln(x, w, b):
        with paddle.no_grad():
            return F.layer_norm(Tensor(x), hidden, Tensor(w), Tensor(b))._value

    def ln_vjp(x, w, b, dy):
        out, vjp = jax.vjp(ln, x, w, b)
        return (out,) + vjp(dy)

    got, want = both(ln_vjp, (x, w, b, dy), "layer_norm_fwd")
    errs["layer_norm"] = [close(g, w_, tol) for g, w_ in zip(got, want)]
    say(f"ok: fused LayerNorm fwd+bwd [{2 * S},{hidden}] bf16: rel err "
        f"y/dx/dw/db {errs['layer_norm']}")

    # fused AdamW: two steps of the optimizer's own pure update
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                                 moment_dtype="bfloat16")
    p0 = {"w": bf(hidden, hidden, scale=0.02)}
    g0 = {"w": bf(hidden, hidden, scale=0.01)}
    s0 = opt.init_state_pytree(p0)

    def adamw(p, g, s):
        p, s = opt.apply_gradients(p, g, s, lr=jnp.float32(1e-2))
        p, s = opt.apply_gradients(p, g, s, lr=jnp.float32(1e-2))
        return p["w"], s["w"]["moment1"], s["w"]["moment2"]

    got, want = both(adamw, (p0, g0, s0), "fused_adamw")
    errs["adamw"] = [close(g, w_, tol) for g, w_ in zip(got, want)]
    say(f"ok: fused AdamW [{hidden},{hidden}] bf16 param + bf16 moments:"
        f" rel err p/m/v {errs['adamw']}")

    # paged decode: ragged batches, page 16, MHA H/H. The second has what
    # the kernel's own page walk can get wrong: a dead slot between live
    # ones, contexts of one chunk (128 tokens), one chunk and a token, and
    # several chunks with a partial last one
    B, ps = 8, 16

    def paged_case(ctx, Hq, Hkv, nb):
        """Operands of a ragged batch (None = a dead slot) and its live
        rows; every slot's pages lie in a stretch of the pool of its own."""
        table = np.full((B, nb), -1, np.int32)
        pos = np.zeros(B, np.int32)
        live = np.asarray([i for i, c in enumerate(ctx) if c is not None])
        for i in live:
            pos[i] = min(ctx[i], nb * ps - 1)
            n = pos[i] // ps + 1
            table[i, :n] = 1 + i * nb + np.arange(n)
        return (bf(B, Hq, 1, D), bf(B * nb + 1, Hkv, ps, D),
                bf(B * nb + 1, Hkv, ps, D), jnp.asarray(table),
                jnp.asarray(pos)), live

    # the third is GQA at 32 / 2 heads, where a chunk is 64 pages (1,024
    # tokens): contexts of several chunks with a partial last one, exactly
    # one, one and a token, under one, and a dead slot
    errs["paged_decode"] = []
    for ctx, Hq, Hkv, nb in (
            ([5, 17, 100, 511, 700, 1023, 1500, S - 1], H, H, S // ps),
            ([300, None, 127, 128, None, 0, 1029, S - 1], H, H, S // ps),
            ([3700, None, 1023, 1024, 2048 + 700, 4351, 40, 2047], 32, 2,
             272)):
        args, live = paged_case(ctx, Hq, Hkv, nb)
        got = _compiled(paged_attention, args, "paged_decode")(*args)
        want = _compiled(
            lambda q, k, v, table, pos: decode_attend(
                q, paged_gather(k, table), paged_gather(v, table),
                pos), args, "paged_decode", present=False)(*args)
        errs["paged_decode"].append(close(got[live], want[live], tol))
        if np.delete(np.asarray(got, np.float32), live, axis=0).any():
            raise AssertionError("paged decode wrote into a dead slot's row")
    say(f"ok: paged decode B={B} H={H} D={D} page {ps} x{S // ps} bf16 vs "
        f"oracle, ragged and ragged with dead slots: rel err "
        f"{errs['paged_decode'][:2]}")
    say(f"ok: paged decode GQA B={B} 32 / 2 heads D={D} page {ps} x272 bf16 "
        f"vs oracle, contexts of several 64-page chunks and a dead slot: "
        f"rel err {errs['paged_decode'][2]}")
    return errs


# --------------------------------------------------------------- trainer

def _init_fleet(**degrees):
    from paddle_tpu.distributed import collective, fleet, mesh, topology

    collective.destroy_process_group()
    mesh.reset_global_mesh()
    topology.set_hybrid_communicate_group(None)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = degrees
    fleet.init(is_collective=True, strategy=strategy)


def _build_trainer(model_kw: dict):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(**{**model_kw, "dropout": 0.0, "use_recompute": True,
                       "loss_chunk": 128})
    paddle.seed(0)
    model = GPTForCausalLM(cfg).astype("bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters(),
                                 moment_dtype="bfloat16")
    return model, make_sharded_train_step(model, opt)


def _batch(B: int, S: int, vocab: int):
    x = np.random.RandomState(0).randint(0, vocab, size=(B, S), dtype=np.int32)
    return x, np.roll(x, -1, axis=1)


def _train_steps(step, x, y, n: int, log: CompileLog):
    """n steps on one batch, each ended by block_until_ready. Returns the
    losses and how many compile requests steps 2..n made (must be none)."""
    import jax

    losses, after_first = [], None
    for i in range(n):
        t0 = time.perf_counter()
        loss = float(jax.block_until_ready(step(x, y)))
        say(f"step {i + 1}: loss {loss:.4f}  ({time.perf_counter() - t0:.2f}"
            f" s{', compile included' if i == 0 else ''})")
        losses.append(loss)
        if i == 0:
            after_first = log.requests
    return losses, log.requests - after_first


def _check_training(step, losses, late_compiles):
    check(all(np.isfinite(losses)), f"losses finite: {losses}")
    check(losses[-1] < losses[0], f"loss fell: {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}")
    check(len(step._exe) == 1 and late_compiles == 0,
          f"one step executable, {late_compiles} compile requests after "
          "step 1")
    sites = step.kernel_sites
    check(all(sites.get(k, 0) >= 1 for k in TRAIN_KERNELS),
          f"compiled step holds Mosaic calls {sites}")
    return sites


def leg_trainer(model_kw: dict, B: int, S: int, log: CompileLog):
    """One chip. Returns (result, model, step) — the server leg reuses the
    model's weights."""
    import jax

    _init_fleet(dp_degree=1, mp_degree=1)
    model, step = _build_trainer(model_kw)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    if "bytes_limit" in stats:
        in_use, limit = stats["bytes_in_use"], stats["bytes_limit"]
        say(f"state on device {in_use / 2**30:.2f} GiB of "
            f"{limit / 2**30:.2f} GiB")
        while B > 1 and in_use + B * TEMP_BYTES_PER_ROW > limit:
            B //= 2
            say(f"B lowered to {B}: state + ~{TEMP_BYTES_PER_ROW / 2**20:.0f}"
                " MiB of activations per row would not fit (width kept)")
    x, y = _batch(B, S, model_kw["vocab_size"])
    losses, late = _train_steps(step, x, y, 5, log)
    sites = _check_training(step, losses, late)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    say(f"trainer: B={B} S={S} peak_bytes_in_use {peak}")
    return {"B": B, "S": S, "losses": losses, "kernel_sites": sites,
            "peak_bytes_in_use": peak}, model, step


def leg_four_chip(model_kw: dict, B: int, S: int, first_loss: float,
                  log: CompileLog):
    """The same trainer under dp2 x mp2 on four devices."""
    import jax

    _init_fleet(dp_degree=2, mp_degree=2)
    model, step = _build_trainer(model_kw)
    devs = list(step.mesh.devices.flat)
    check(len(devs) == 4, f"mesh spans 4 devices {dict(step.mesh.shape)}")
    x, y = _batch(B, S, model_kw["vocab_size"])
    losses, late = _train_steps(step, x, y, 5, log)
    sites = _check_training(step, losses, late)
    # nothing "all on device 0": every param has a shard on every device,
    # and an mp-sharded weight holds half its bytes per device
    for name, arr in step.params.items():
        held = {s.device for s in arr.addressable_shards}
        if held != set(devs):
            raise AssertionError(f"{name} lives on {held}, not {devs}")
    qkv = next(a for n, a in step.params.items() if n.endswith("qkv.weight"))
    shard = qkv.addressable_shards[0].data
    check(shard.nbytes * 2 == qkv.nbytes, "every param has shards on all 4 "
          f"devices; qkv.weight {qkv.shape} holds {shard.nbytes} of "
          f"{qkv.nbytes} bytes per device ({qkv.sharding.spec})")
    check(abs(losses[0] - first_loss) <= 0.05, "first-step loss "
          f"{losses[0]:.4f} matches the one-chip leg's {first_loss:.4f}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    say(f"four_chip: dp2 x mp2 global B={B} per-device peak_bytes_in_use "
        f"{peaks}")
    return {"B": B, "losses": losses, "kernel_sites": sites,
            "peak_bytes_in_use": peaks}


# ---------------------------------------------------------------- server

def _prompts(vocab: int, lengths, shared: int):
    """Seeded prompts; the first two share their first ``shared`` tokens."""
    rng = np.random.RandomState(1)
    out = [rng.randint(0, vocab, size=n).tolist() for n in lengths]
    out[1][:shared] = out[0][:shared]
    return out


def _counter(name: str, **labels) -> float:
    from paddle_tpu import observability

    key = name + ("{" + ",".join(f"{k}={v}" for k, v in sorted(
        labels.items())) + "}" if labels else "")
    v = observability.snapshot()["counters"].get(key, 0)
    return v["total"] if isinstance(v, dict) else v


def _serve(model, prompts, new_tokens: int, vocab: int, **engine_kw):
    """One engine over ``model``; returns (outputs, engine) after the
    checks every pass shares."""
    from paddle_tpu import observability
    from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

    observability.reset()
    eng = Engine(model, EngineConfig(**engine_kw))
    t0 = time.perf_counter()
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=new_tokens))
    say(f"served {len(prompts)} requests x {new_tokens} tokens in "
        f"{time.perf_counter() - t0:.1f} s (compiles included)")
    check(all(len(o) == new_tokens for o in outs)
          and all(0 <= t < vocab for o in outs for t in o),
          f"{len(outs)} requests finished with {new_tokens} valid token ids")
    misses = _counter("jit.compile.cache_miss", site="serving.decode")
    check(misses == 1, f"exactly one serving.decode compile ({misses})")
    return outs, eng


def leg_server(model, vocab: int, S_max: int, lengths, shared: int,
               new_tokens: int = 32):
    """Pass A (plain), pass B (prefix cache + speculative) over one set of
    weights."""
    prompts = _prompts(vocab, lengths, shared)
    envelope = dict(max_batch_size=8, max_seq_len=S_max)

    outs_a, eng = _serve(model, prompts, new_tokens, vocab, **envelope)
    sites = eng.kernel_sites
    check(sites[("decode",)].get("paged_decode", 0) >= 1
          and all(v.get("flash_fwd", 0) >= 1 for k, v in sites.items()
                  if k[0] == "prefill"),
          f"decode holds the paged Mosaic call, prefills the flash call: "
          f"{ {'/'.join(map(str, k)): v for k, v in sites.items()} }")
    del eng
    gc.collect()

    outs_b, eng = _serve(model, prompts, new_tokens, vocab, **envelope,
                         prefix_cache=True, speculative=4)
    hits = _counter("serving.prefix.hits")
    accepted = _counter("serving.spec.accepted_tokens")
    check(hits >= 1 and accepted >= 1,
          f"pass B: {hits} prefix hits, {accepted} accepted draft tokens")
    agree = float(np.mean([a == b for oa, ob in zip(outs_a, outs_b)
                           for a, b in zip(oa, ob)]))
    say(f"pass B tokens agreeing with pass A: {agree:.3f} (printed, not "
        "gated: seeded bf16 weights give near-flat logits)")
    del eng
    gc.collect()
    return {"requests": len(prompts), "new_tokens": new_tokens,
            "kernel_sites": {"/".join(map(str, k)): v
                             for k, v in sites.items()},
            "prefix_hits": hits, "accepted_draft_tokens": accepted,
            "pass_b_agreement": agree}


def leg_server_exact(new_tokens: int = 24):
    """Small f32 model, exact: greedy output under the compiled paged
    kernel, under the oracle and under pass-B settings is token-identical."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import use_paged_attention_impl

    cfg = GPTConfig(vocab_size=512, hidden_size=256, num_layers=2,
                    num_heads=2, max_seq_len=256, dropout=0.0,
                    initializer_range=0.1)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    prompts = _prompts(cfg.vocab_size, (40, 44, 9, 70), shared=32)
    envelope = dict(max_batch_size=4, max_seq_len=256)
    with jax.default_matmul_precision("highest"):
        # the tier is baked in as a program is traced: each context holds
        # an engine's construction and its first generate
        with use_paged_attention_impl("pallas"):
            kernel, eng = _serve(model, prompts, new_tokens, cfg.vocab_size,
                                 **envelope)
        check(eng.kernel_sites[("decode",)].get("paged_decode", 0) >= 1,
              "small-model decode runs the compiled paged kernel")
        with use_paged_attention_impl("oracle"):
            oracle, eng = _serve(model, prompts, new_tokens, cfg.vocab_size,
                                 **envelope)
        check(not eng.kernel_sites[("decode",)].get("paged_decode", 0),
              "the oracle engine's decode holds no paged kernel")
        spec, _ = _serve(model, prompts, new_tokens, cfg.vocab_size,
                         **envelope, prefix_cache=True, speculative=4)
    check(kernel == oracle == spec, "f32 greedy output token-identical: "
          "compiled paged kernel == oracle == prefix cache + speculative")
    return {"requests": len(prompts), "new_tokens": new_tokens}


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    global _DEV
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help=f"comma-separated subset of {LEGS} (four_chip "
                         "needs trainer)")
    args = ap.parse_args(argv)
    legs = [leg for leg in args.legs.split(",") if leg]
    if set(legs) - set(LEGS):
        ap.error(f"unknown legs {sorted(set(legs) - set(LEGS))}")

    import jax

    devices = jax.devices()  # a backend that cannot start raises here
    d0 = devices[0]
    _DEV = f"{d0.platform} {d0.device_kind} x{len(devices)}"
    if d0.platform != "tpu":
        print(f"[{_DEV}] chip_smoke needs a TPU; jax found "
              f"{d0.platform!r}. Not shrinking to fit: failing.",
              file=sys.stderr)
        return 2

    import paddle_tpu as paddle
    from paddle_tpu import observability
    from paddle_tpu.core.cache import cache_root
    from paddle_tpu.models import GPT3_1p3B

    log = CompileLog()
    observability.enable()
    say(f"paddle_tpu {paddle.__version__}, jax {jax.__version__}; compile "
        f"cache at {jax.config.jax_compilation_cache_dir} (cache_root "
        f"{cache_root()})")
    t_start = time.perf_counter()
    result = {}
    model_kw = dict(GPT3_1p3B)
    S = model_kw["max_seq_len"]

    if "kernels" in legs:
        result["kernels"] = leg_kernels()
    first_loss = None
    if "trainer" in legs:
        result["trainer"], model, step = leg_trainer(model_kw, 16, S, log)
        first_loss = result["trainer"]["losses"][0]
        if "server" in legs:
            # the server answers from the weights the trainer just updated
            step.sync_to_model()
        del step
        gc.collect()
        if "server" in legs:
            result["server"] = leg_server(
                model, model_kw["vocab_size"], S,
                lengths=(60, 58, 40, 100, 120, 90, 200, 250), shared=48)
            result["server_exact"] = leg_server_exact()
        del model
        gc.collect()
    elif "server" in legs:
        ap.error("the server leg serves the trainer leg's weights")
    if "four_chip" in legs:
        if len(devices) >= 4 and first_loss is not None:
            result["four_chip"] = leg_four_chip(model_kw, 16, S, first_loss,
                                                log)
        else:
            say(f"four_chip: not run ({len(devices)} device)")
            result["four_chip"] = f"not run ({len(devices)} device)"

    say(f"all legs passed in {time.perf_counter() - t_start:.0f} s; "
        f"compiles {log.snapshot()}")
    say("summary: " + json.dumps({
        "legs": result,
        "compile_cache": {"dir": jax.config.jax_compilation_cache_dir,
                          **log.snapshot()},
        "claim": None}))
    print(result_line(devices), flush=True)  # nothing after it on stdout
    return 0


if __name__ == "__main__":
    sys.exit(main())
