"""Runner "serve_phi4": ``run_serve``'s loop for a description-built
decoder-hybrid-decoder (``paddle_tpu.models.decoder``: Mamba-1 layers on slot
state beside sliding-window differential attention on a page group of its
own, ONE full differential layer whose K/V pool the cross layers of the
second half read, gated memory units, and an admission whose second half
runs on the last real token alone), WHOLE on one chip, checked against its
own plain reference (``reference/phi4_mini_flash.py``: every layer's
equations, the token-by-token recurrence).

The loop, the schedule, the statistics and the check's sampling and
comparison are ``run_serve``'s, ``schedule``'s, ``stats``'s and ``check``'s,
by import; the spans' window and the host-phase print are
``run_serve_hybrid``'s and ``run_serve_decoder``'s, the traffic's buckets
``run_serve_hybrid``'s (multi-turn sessions that ``stagger_start`` opens
part-way). ``build``, ``warm_up``, ``served_gap`` and ``run`` are COPIES of
``run_serve_mamba``'s (whose own are copies, for the reason given there: the
runners' shared body is a ``benchmark`` issue's to part): they name their
module's weights, reference and configuration keys, which import cannot
replace. What differs: this model's description, an engine over state AND a
window group, the kernels asked of the decode program, and a reference that
carries a memory and one layer's keys and values from layer to layer.

The model's new parts are imported at the top: on a commit without them this
runner fails at once, before any device work.
"""

from __future__ import annotations

import gc
import sys
import time

from paddle_tpu.kernels.mamba1 import mamba1_scan, mamba1_step  # noqa: E402,F401
from paddle_tpu.models.decoder import (DecoderConfig, DecoderLM,  # noqa: E402
                                       differential_attention,  # noqa: F401
                                       gmu, mamba1, param_shapes)  # noqa: F401

from . import check, common, device, phi4_weights, stats
from .common import BENCH
from .run_serve import Loop
from .run_serve_decoder import say_host_phases
from .run_serve_hybrid import program_buckets, window_spans

sys.path.insert(0, BENCH)
from reference import phi4_mini_flash as ref  # noqa: E402

#: the reference's kinds of layer -> the program's
KINDS = {"mamba": "mamba1", "sliding": "sliding", "full": "dense",
         "gmu": "gmu", "cross": "cross"}


def layer_plan(c: dict):
    """(the reference's kind, the earlier layer it reads) of every layer,
    from ``mb_per_layer`` 2 and the split at the middle: the last Mamba-1
    layer (16) is the memory's, the layer behind it (17) the shared K/V's."""
    assert c["mb_per_layer"] == 2 and c["num_hidden_layers"] % 4 == 0
    kinds = ref.kinds(c["num_hidden_layers"])
    memory, shared = kinds.index("full") - 1, kinds.index("full")
    return [(k, memory if k == "gmu" else shared if k == "cross" else None)
            for k in kinds]


def decoder_config(c: dict, **extra) -> DecoderConfig:
    """The program's description of the layers that the configuration file's
    published keys (and its ``assumed`` list) state."""
    plan = layer_plan(c)
    assert c["hidden_act"] == "silu" and c["tie_word_embeddings"]
    assert not (c["mlp_bias"] or c["lm_head_bias"])
    assert c["hidden_size"] % c["num_attention_heads"] == 0
    a = c["assumed_sizes"]
    return DecoderConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        max_context=c["engine"]["max_seq_len"], norm="layer",
        norm_eps=c["layer_norm_eps"], norm_placement="pre", position="none",
        qk_norm=False, kv_layout="head",
        layer_types=tuple(KINDS[k] for k, _ in plan),
        layer_sources=tuple(s for _, s in plan),
        sliding_window=c["sliding_window"], differential=True,
        attn_bias=True, ssm1_inner=a["mamba_inner"],
        ssm_state=a["mamba_state"], ssm_conv_kernel=a["mamba_conv"],
        ssm1_dt_rank=a["mamba_dt_rank"], ffn="swiglu",
        intermediate_size=c["intermediate_size"],
        tie_word_embeddings=c["tie_word_embeddings"],
        initializer_range=c["initializer_range"], dtype=c["dtype"],
        **c.get("program", {}), **extra)


def reference_config(c: dict) -> dict:
    """The same, in the reference's own keys."""
    plan, a = layer_plan(c), c["assumed_sizes"]
    return {"layer_types": [k for k, _ in plan],
            "memory_layer": next(s for k, s in plan if k == "gmu"),
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["hidden_size"] // c["num_attention_heads"],
            "inner": a["mamba_inner"], "state": a["mamba_state"],
            "dt_rank": a["mamba_dt_rank"], "conv_kernel": a["mamba_conv"],
            "norm_eps": c["layer_norm_eps"],
            "sliding_window": c["sliding_window"]}


def build_engine(model, c: dict):
    from paddle_tpu.serving import Engine, EngineConfig

    e = c["engine"]
    return Engine(model, EngineConfig(
        max_batch_size=e["max_batch_size"], max_seq_len=e["max_seq_len"],
        prefill_buckets=tuple(e["prefill_buckets"]), page_size=e["page_size"],
        kv_pages=e["kv_pages"], group_pages=dict(e["group_pages"]),
        prefix_cache=e["prefix_cache"], speculative=e["speculative"],
        state_snapshots=e["state_snapshots"]))


def build_model(c: dict):
    """The model with nothing drawn, constructed on the host: its zeros
    stand in host memory until the seeded weights replace them leaf by
    leaf."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        return DecoderLM(decoder_config(c, init="zeros"))


def build(run):
    import jax

    c = run.config
    with run.phase("model_construct"):
        model = build_model(c)
    shapes = param_shapes(model.cfg)
    with run.phase("weights"):
        phi4_weights.compile_makers(shapes, c["initializer_range"],
                                     c["dtype"])
        for n, p in model.named_parameters():   # leaf by leaf, on the chip
            p._set_value_raw(phi4_weights.make(
                run.seed, shapes, c["initializer_range"], c["dtype"], [n])[n])
        jax.block_until_ready([p._value for p in model.parameters()])
    with run.phase("engine_construct"):
        eng = build_engine(model, c)
    return model, eng, shapes


def warm_up(run, eng):
    """Compile (or load from the persistent cache) every program this cell's
    traffic can reach, side by side (``Engine.compile_programs``)."""
    prefill, extend = program_buckets(run.config, run.traffic)
    eng.compile_programs(prefill=prefill, extend=extend)
    sites = {"/".join(map(str, k)): v for k, v in eng.kernel_sites.items()}
    run.say(f"engine programs and their Mosaic calls: {sites}")
    dec = eng.kernel_sites[("decode",)]
    for kernel in ("mamba1_decode_step", "paged_decode", "window_decode"):
        if dec.get(kernel, 0) < 1:
            run.fail_run(f"Mosaic kernel {kernel} absent from the decode "
                         "program")
    for key, sites in eng.kernel_sites.items():
        want = {"prefill": ("mamba1_scan",),
                "extend": ("mamba1_scan", "paged_decode")}.get(key[0], ())
        # (the smallest extend's window view is a block: no flash there)
        if key[0] == "extend" and key[1] >= 128:
            want += ("window_extend_flash",)
        for kernel in want:
            if sites.get(kernel, 0) < 1:
                run.fail_run(f"Mosaic kernel {kernel} absent from "
                             f"{'/'.join(map(str, key))}")
    for k, exe in eng._exe.items():
        run.exe_bytes["/".join(map(str, k))] = device.executable_bytes(exe)
    run.say(f"engine executable bytes (TPU compiler): {run.exe_bytes}")


def served_gap(c: dict, shapes: dict, seed: int, sample,
               max_answer: int = 1152, control: bool = False, say=print):
    """``check.served_gap`` for this model (after
    ``run_serve_mamba.served_gap``): per sampled request the reference runs
    ONCE over the whole context (prompt plus served tokens, padded to the
    engine's budget: one shape, one compile; nothing behind a token reaches
    it), layer by layer, each layer's weights made from the seed as it
    goes; what a layer hands to later ones (the memory, layer 17's keys and
    values) goes along. Layer 17 is asked for its output at the served
    tokens' positions only, and the layers behind it, in which a token
    depends on its own row alone, run on those rows (the reference's
    ``rows``). Returns (widest gap, mean gap, tokens compared) of the served
    tokens' logits below the reference's best; with ``control`` the tokens
    judged are the ones an fp8 reference puts first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rc = reference_config(c)
    kinds = rc["layer_types"]
    L, S, qb = len(kinds), c["engine"]["max_seq_len"], c["check"]["q_block"]
    make = lambda names: phi4_weights.make(
        seed, shapes, c["initializer_range"], c["dtype"], names)
    top = make(["embed.weight", "final_norm.weight", "final_norm.bias"])

    def layer_weights(l):
        pre = f"layers.{l}."
        return {n[len(pre):]: v for n, v in
                make([n for n in shapes if n.startswith(pre)]).items()}

    mms = [ref.mm_highest] + ([check.mm_fp8] if control else [])
    embed = jax.jit(ref.embed)
    # one compile a (kind, whether its memory is handed on, matmul), not a
    # layer: the layer's index enters through lambda_init alone, a traced
    # scalar here (32 compiles of 3 s each were most of the check's time)
    layer = {}

    def run_layer(l, i, x, carry, p, rows):
        key = (kinds[l], l == rc["memory_layer"], i)
        if key not in layer:
            kind, keeps, _ = key
            layer[key] = jax.jit(
                lambda x, carry, p, rows, lam0, mm=mms[i]: ref.layer_of(
                    x, p, kind, rc, carry, lam0, keeps, mm, qb,
                    rows if kind == "full" else None),
                donate_argnums=0)
        return layer[key](x, carry, p, rows,
                          jnp.float32(ref.lambda_init(l)))

    def gaps(xs, toks, n, final_norm, final_bias, table):
        at = jnp.arange(toks.shape[0])
        rows = ref.logits(xs[0], at, final_norm, final_bias, table, rc)
        if control:
            low = ref.logits(xs[1], at, final_norm, final_bias, table, rc,
                             check.mm_fp8)
            judged = jnp.argmax(low, -1)
        else:
            judged = toks
        gap = rows.max(-1) - jnp.take_along_axis(rows, judged[:, None], 1)[:, 0]
        gap = jnp.where(at < n, gap, 0.0)
        return gap.max(), gap.sum()

    gaps = jax.jit(gaps)
    R = max([max_answer] + [len(r["output"]) for r in sample])
    R = -(-R // qb) * qb        # whole blocks of queries
    xs_all, rows_all, carries = [], [], []
    for r in sample:
        text = list(r["prompt"]) + list(r["output"][:-1])
        ids = np.zeros((S,), np.int32)
        ids[:len(text)] = text
        x0 = embed(jnp.asarray(ids), top["embed.weight"])
        xs_all.append([x0] + [jnp.copy(x0) for _ in mms[1:]])
        carries.append([{"qpos": jnp.arange(S)} for _ in mms])
        # the positions whose logits chose the served tokens
        rows_all.append(jnp.clip(len(r["prompt"]) - 1 + jnp.arange(R), 0, S - 1))
    # layers outermost: a layer's weights are made once for all requests
    for l in range(L):
        p = layer_weights(l)
        for xs, cs, rows in zip(xs_all, carries, rows_all):
            for i in range(len(mms)):
                xs[i], cs[i] = run_layer(l, i, xs[i], cs[i], p, rows)
        del p
    widest, total, count = 0.0, 0.0, 0
    for r, xs in zip(sample, xs_all):
        out = r["output"]
        toks = np.zeros((R,), np.int32)
        toks[:len(out)] = out
        g, t = gaps(xs, jnp.asarray(toks), jnp.int32(len(out)),
                    top["final_norm.weight"], top["final_norm.bias"],
                    top["embed.weight"])
        widest, total = max(widest, float(g)), total + float(t)
        count += len(out)
    return widest, total / max(count, 1), count


def say_loop_metrics(run):
    """The six new per-layer readings, printed: ``BENCHMARK.json``'s
    ``per_layer`` list is full (128 entries), so they have no entry there
    yet (benchmark/README.phi4.md)."""
    from . import readers_phi4

    names = ("shared_kv_decode_roofline", "shared_kv_read_share",
             "window_decode_roofline", "mamba1_step_roofline",
             "mamba1_scan_roofline", "cross_rows_share")
    try:
        run.say("loop metrics: " + str(
            {n + ".loop": getattr(readers_phi4, n)(run) for n in names}))
    except Exception as e:      # a print: it never costs a run
        run.say(f"loop metrics: not read ({type(e).__name__}: {e})")


def run(run):
    # ---- copied from run_serve_mamba.run (see the module's docstring) ----
    log = run.compile_log
    t = run.traffic
    run.config["model"] = {"vocab_size": run.config["vocab_size"]}  # Loop reads it
    seconds = (min(run.cell.get("trace_seconds", 20.0), run.seconds)
               if run.trace_on else run.seconds)
    model, eng, shapes = build(run)
    with run.phase("programs"):
        warm_up(run, eng)
    loop = Loop(run, eng)
    loop.start()
    with run.phase("run_in"):
        loop.run_until(lambda: len(loop.ended) >= t["run_in_completed"])
    run.say(f"run-in: {len(loop.ended)} requests ended, "
            f"{len(loop.token_stamps)} tokens, {len(loop.steps)} engine steps; "
            f"{eng.prefix_cache.num_nodes} trie nodes, "
            f"{eng.page_alloc.num_free} pages free, "
            f"{eng.snapshot_alloc.num_free} snapshots free")
    compiles_before = log.requests

    # ------------------------------------------------------ the window
    run.setup_s = time.perf_counter() - run.t_start
    if run.trace_on:
        common.start_trace(run)
    t0 = time.perf_counter()
    with run.span("bench/window"):
        loop.run_until(lambda: time.perf_counter() - t0 >= seconds)
    t1 = t0 + seconds
    if run.trace_on:
        common.stop_trace(run)
    run.window = (t0, t1)
    if log.requests != compiles_before:
        run.fail_run(f"{log.requests - compiles_before} compile request(s) "
                     "inside the measured window")

    # ------------------------------------------------------ reduction
    done = [r for r in loop.ended if t0 <= r["end"] < t1]
    ok = [r for r in done if r["reason"] == "length"]
    run.attempted, run.failed = len(done), len(done) - len(ok)
    tokens = stats.tokens_in_window(loop.token_stamps, t0, t1)
    per_tok = [(r["end"] - r["due"]) / len(r["output"]) * 1e3 for r in ok]
    gaps = [g * 1e3 for g in stats.gaps_in_window(
        [r["stamps"] for r in loop.ended] + [lv.stamps for lv in loop.live],
        t0, t1)]
    e2e = run.end_to_end
    e2e["serve_out_tok_s"] = tokens / seconds
    if per_tok:
        e2e["latency_per_tok_p50_ms"] = stats.percentile(per_tok, 50)
    if gaps:
        e2e["tok_gap_p95_ms"] = stats.percentile(gaps, 95)
    run.say(f"window: {len(done)} requests ended ({run.failed} failed), "
            f"{tokens} tokens emitted, {len(per_tok)} latency samples, "
            f"{len(gaps)} token gaps, in {seconds:.0f} s")
    admitted = [r for r in loop.ended + [
        {"handed": lv.handed, "prompt": lv.req.prompt_ids, "due": lv.due,
         "stamps": lv.stamps,
         "hit_tokens": lv.req.prefix_hit_blocks * eng.cache.page_size}
        for lv in loop.live] if t0 <= r["handed"] < t1]
    # what the snapshot rule did, from the program's own spans
    adm = [a for a in window_spans(t0, t1, "serving/admit")
           if "snapshot_blocks" in a]
    snaps = window_spans(t0, t1, "serving/snapshot")
    grow = window_spans(t0, t1, "serving/decode/grow_pages")
    cache_full = sum(a.get("cache_full", 0) for a in grow)
    run.say(f"snapshots: {len(adm)} admissions, "
            f"{sum(a['prompt_tokens'] for a in adm)} prompt tokens, "
            f"{sum(a['hit_blocks'] for a in adm)} pages matched, "
            f"{sum(a['snapshot_blocks'] for a in adm)} restored, "
            f"{sum(a['recomputed_tokens'] for a in adm)} tokens run again for "
            f"want of a snapshot, {sum(a['snapshot_blocks'] == 0 for a in adm)} "
            f"cold; {len(snaps)} snapshots taken "
            f"({sum(1 for s in snaps if s.get('reason') == 'branch')} at a "
            f"branch), {sum(s.get('evicted', 0) for s in snaps)} evicted; "
            f"{eng.snapshot_alloc.num_allocated} of "
            f"{eng.snapshot_alloc.num_allocatable} held at the end; "
            f"{cache_full} cache_full")
    if cache_full:
        run.fail_run(f"{cache_full} request(s) ended cache_full in the window")
    run.counters.update(
        steps=[s for s in loop.steps if t0 <= s[1] < t1],
        max_batch_size=run.config["engine"]["max_batch_size"],
        lateness_ms=[l * 1e3 for h, l in loop.lateness if t0 <= h < t1],
        prompt_tokens_admitted=sum(len(r["prompt"]) for r in admitted),
        prompt_tokens_hit=sum(r["hit_tokens"] for r in admitted),
        ttft_ms=[(r["stamps"][0] - r["due"]) * 1e3 for r in admitted
                 if r["stamps"]],
        tpot_ms=[(r["stamps"][-1] - r["stamps"][0]) / (len(r["stamps"]) - 1)
                 * 1e3 for r in ok if len(r["stamps"]) > 1],
        gaps_ms=gaps, waiting_end=len(eng.scheduler.waiting),
        admit_prompt_tokens=sum(a["prompt_tokens"] for a in adm),
        admit_recomputed_tokens=sum(a["recomputed_tokens"] for a in adm),
        snapshots_held=eng.snapshot_alloc.num_allocated,
        snapshots_capacity=eng.snapshot_alloc.num_allocatable,
        window_pages_live=eng.page_allocs[1].num_allocated,
        window_pages=eng.page_allocs[1].num_allocatable,
        admit_cross_rows=sum(a.get("cross_rows", 0) for a in adm),
        admit_prompt_rows=sum(a["prompt_tokens"] for a in adm
                              if "cross_rows" in a))
    run.memory_peak = device.memory_peak_bytes(run.devices)
    if run.trace_on:
        say_host_phases(run)
        say_loop_metrics(run)

    # ------------------------------------- the check, engine freed first
    ck = run.config["check"]
    sample = check.pick_sample(ok, run.seed, ck["sample_requests"])
    del loop, eng, model
    gc.collect()
    t_ref = time.perf_counter()
    if not sample:
        run.say("check: no request finished in the window; nothing to compare")
        run.correct = False
    else:
        gap, mean, n = served_gap(run.config, shapes, run.seed, sample,
                                  control=run.with_control, say=run.say)
        run.say(f"check: {len(sample)} finished requests, {n} served tokens "
                f"(longest {max(len(r['prompt']) + len(r['output']) for r in sample)} "
                "tokens of context)")
        what = "gap of a served token's logit below the reference's best"
        lim = ck["limits"]
        if run.with_control:
            # served_gap judged the fp8 reference's tokens: that is the
            # control's reading; the sound one is a second pass
            run.say("control: the tokens an fp8 reference puts first, in "
                    "the served tokens' place")
            run.control_correct = bool(
                run.compare("mean " + what, mean, lim["served_gap_mean"])
                & run.compare("widest " + what, gap, lim["served_gap_widest"]))
            run.control_compared, run.compared = run.compared, []
            gap, mean, n = served_gap(run.config, shapes, run.seed, sample,
                                      say=run.say)
        run.correct = bool(
            run.compare("mean " + what, mean, lim["served_gap_mean"])
            & run.compare("widest " + what, gap, lim["served_gap_widest"]))
    run.reference_s = time.perf_counter() - t_ref
