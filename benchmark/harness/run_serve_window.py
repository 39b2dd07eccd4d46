"""Runner "serve_window": ``run_serve``'s loop for a description-built
decoder of the Command A+ kind that is ONE CHIP'S SHARE of a layer shared
by several (sliding-window and full attention layers on page groups of
their own, a parallel attention + FFN block, routed experts of which this
chip holds some plus four averaged shared ones, a slice of the tied
vocabulary: ``paddle_tpu.models.decoder``), checked against its own plain
reference given the same share (``reference/command_a_plus.py``).

The loop, the schedule, the statistics and the check's sampling and
comparison are ``run_serve``'s, ``schedule``'s, ``stats``'s and ``check``'s,
by import; the host-phase print is ``run_serve_decoder``'s, the weights'
rule ``decoder_weights``'s (through ``window_weights``), the buckets
``run_serve_latent``'s and the span ring's reader ``run_serve_hybrid``'s,
by import too. ``run`` is a COPY of ``run_serve_latent``'s (whose
``run`` is a copy of ``run_serve.run``'s body, for the reason given there:
the runners' shared body is a ``benchmark`` issue's to part): it names its
module's ``build``, ``warm_up``, reference and configuration keys, which
import cannot replace. What differs: this model's description and share,
the engine's second page group, the kernels asked of the programs, what the
window rule did (from the program's own spans and counters), and a document
counts as cached only where a session can RESUME at its end.

The model's new parts are imported at the top: on a commit without them this
runner fails at once, before any device work.
"""

from __future__ import annotations

import gc
import sys
import time

from paddle_tpu.models.decoder import (DecoderConfig, DecoderLM,  # noqa: E402
                                       param_shapes, rope_gptj,  # noqa: F401
                                       sigmoid_topk)  # noqa: F401

from . import check, common, device, schedule, stats, window_weights
from .common import BENCH
from .run_serve import Loop
from .run_serve_decoder import say_host_phases
from .run_serve_hybrid import window_spans
from .run_serve_latent import program_buckets

sys.path.insert(0, BENCH)
from reference import command_a_plus as ref  # noqa: E402

#: the program's names for the published layer kinds
KINDS = {"sliding_attention": "sliding", "full_attention": "dense"}


def held(c: dict):
    """(how many, from which index) of the published experts this chip
    holds: share ``chip`` of ``chips_per_layer``."""
    d = c["deployment_share"]
    n = c["num_experts"]
    assert n * d["chips_per_layer"] == c["num_experts_published"]
    return n, d["chip"] * n


def _asserted(c: dict):
    """What the description below takes as read of the published keys."""
    assert c["expert_selection_fn"] == "sigmoid" and c["hidden_act"] == "silu"
    assert c["position_embedding_type"] == "rope_gptj" and c["rotary_pct"] == 1
    assert c["use_parallel_block"] and c["use_gated_activation"]
    assert not c["attention_bias"] and not c["use_qk_norm"]
    assert c["first_k_dense_replace"] == 0 and c["rms_norm_eps"] is None
    assert c["shared_expert_combination_strategy"] == "average"
    assert c["order_of_interleaved_layers"] == "local_attn_first"
    assert c["rope_parameters"]["rope_theta"] == c["rope_theta"]


def decoder_config(c: dict, **extra) -> DecoderConfig:
    """The program's description of the block that the configuration file's
    published keys (and its ``assumed`` list) state."""
    _asserted(c)
    return DecoderConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        max_context=c["engine"]["max_seq_len"], norm="layer_nobias",
        norm_eps=c["layer_norm_eps"], norm_placement="parallel",
        position="rope_gptj", position_by_kind={"dense": "none"},
        rope_theta=float(c["rope_theta"]), qk_norm=False,
        layer_types=tuple(KINDS[k] for k in c["layer_types"]),
        sliding_window=c["sliding_window"], kv_layout="head",
        ffn="moe_swiglu", intermediate_size=c["intermediate_size"],
        router="sigmoid_topk", num_experts=c["num_experts_published"],
        experts_held=held(c), shared_experts=c["num_shared_experts"],
        shared_combine="mean", experts_per_token=c["num_experts_per_tok"],
        norm_topk_prob=c["norm_topk_prob"],
        tie_word_embeddings=c["tie_word_embeddings"],
        logit_scale=float(c["logit_scale"]),
        initializer_range=c["initializer_range"], dtype=c["dtype"],
        **c.get("program", {}), **extra)


def reference_config(c: dict) -> dict:
    """The same, in the reference's own keys."""
    _asserted(c)
    return {"layer_types": list(c["layer_types"]),
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "norm_eps": c["layer_norm_eps"],
            "rope_theta": float(c["rope_theta"]),
            "sliding_window": c["sliding_window"],
            "experts_per_token": c["num_experts_per_tok"],
            "norm_topk_prob": c["norm_topk_prob"], "experts_held": held(c),
            "shared_experts": c["num_shared_experts"],
            "logit_scale": float(c["logit_scale"])}


def build_model(c: dict):
    """The model with nothing drawn, constructed on the host: its zeros
    stand in host memory until the seeded weights replace them leaf by
    leaf."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        return DecoderLM(decoder_config(c, init="zeros"))


def build_engine(model, c: dict):
    from paddle_tpu.serving import Engine, EngineConfig

    e = c["engine"]
    return Engine(model, EngineConfig(
        max_batch_size=e["max_batch_size"], max_seq_len=e["max_seq_len"],
        prefill_buckets=tuple(e["prefill_buckets"]), page_size=e["page_size"],
        kv_pages=e["kv_pages"], group_pages=dict(e["group_pages"]),
        prefix_cache=e["prefix_cache"], speculative=e["speculative"]))


def build(run):
    import jax

    c = run.config
    with run.phase("model_construct"):
        model = build_model(c)
    shapes = param_shapes(model.cfg)
    with run.phase("weights"):
        window_weights.compile_makers(shapes, c["initializer_range"],
                                      c["dtype"])
        for n, p in model.named_parameters():   # leaf by leaf, on the chip
            p._set_value_raw(window_weights.make(
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
    for kernel in ("window_decode", "paged_decode", "moe_grouped_matmul"):
        if dec.get(kernel, 0) < 1:
            run.fail_run(f"Mosaic kernel {kernel} absent from the decode "
                         "program")
    for key, found in eng.kernel_sites.items():
        if key[0] == "prefill" and found.get("flash_fwd", 0) < 1:
            run.fail_run("Mosaic kernel flash_fwd absent from the "
                         + "/".join(map(str, key)) + " program")
    for k, exe in eng._exe.items():
        run.exe_bytes["/".join(map(str, k))] = device.executable_bytes(exe)
    run.say(f"engine executable bytes (TPU compiler): {run.exe_bytes}")


def documents_cached(eng, loop, run) -> int:
    """How many of the traffic's shared documents a session can open on:
    the trie holds them whole AND a request can resume at their end (the
    window before it still has its pages)."""
    docs = schedule.system_prompts(run.traffic, loop.vocab, run.seed)
    ps = eng.cache.page_size
    return sum(eng.prefix_cache.match_groups(d + [0])[1] == len(d) // ps
               for d in docs)


def served_gap(c: dict, shapes: dict, seed: int, sample,
               max_answer: int = 576, control: bool = False, say=print):
    """``check.served_gap`` for this model: per sampled request the
    reference runs ONCE over the whole context (prompt plus served tokens,
    padded to the engine's budget: one shape, one compile; nothing behind a
    token reaches it), layer by layer, each layer's weights made from the
    seed as it goes (again for every request: one request's residual
    streams are on the chip at a time); the last layer is asked for its
    output at the served tokens' positions only. Returns (widest gap, mean
    gap, tokens compared) of the served tokens' logits below the
    reference's best; with ``control`` the tokens judged are the ones an
    fp8 reference puts first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rc = reference_config(c)
    kinds = rc["layer_types"]
    L, S, qb = len(kinds), c["engine"]["max_seq_len"], c["check"]["q_block"]
    make = lambda names: window_weights.make(
        seed, shapes, c["initializer_range"], c["dtype"], names)
    top = make(["embed.weight", "final_norm.weight"])

    def layer_weights(l):
        pre = f"layers.{l}."
        return {n[len(pre):]: v for n, v in
                make([n for n in shapes if n.startswith(pre)]).items()}

    mms = [ref.mm_highest] + ([check.mm_fp8] if control else [])
    embed = jax.jit(ref.embed)
    layer = {(kind, i): jax.jit(
        lambda x, p, kind=kind, mm=mm: ref.layer(x, p, kind, rc, mm, qb),
        donate_argnums=0) for kind in set(kinds) for i, mm in enumerate(mms)}
    # the last layer owes its output only where logits are compared
    last = [jax.jit(lambda x, p, rows, mm=mm: ref.layer(
        x, p, kinds[-1], rc, mm, qb, rows)) for mm in mms]

    def gaps(xs, toks, n, final_norm, table):
        at = jnp.arange(toks.shape[0])
        rows = ref.logits(xs[0], at, final_norm, table, rc)
        if control:
            low = ref.logits(xs[1], at, final_norm, table, rc, check.mm_fp8)
            judged = jnp.argmax(low, -1)
        else:
            judged = toks
        gap = rows.max(-1) - jnp.take_along_axis(rows, judged[:, None], 1)[:, 0]
        gap = jnp.where(at < n, gap, 0.0)
        return gap.max(), gap.sum()

    gaps = jax.jit(gaps)
    R = max([max_answer] + [len(r["output"]) for r in sample])
    R = -(-R // qb) * qb                  # whole blocks of queries
    widest, total, count = 0.0, 0.0, 0
    for r in sample:
        t0 = time.perf_counter()
        out = r["output"]
        text = list(r["prompt"]) + list(out[:-1])
        ids = np.zeros((S,), np.int32)   # causal: the padding changes nothing
        ids[:len(text)] = text
        x0 = embed(jnp.asarray(ids), top["embed.weight"])
        xs = [x0] + [jnp.copy(x0) for _ in mms[1:]]
        # the positions whose logits chose the served tokens
        rows = jnp.clip(len(r["prompt"]) - 1 + jnp.arange(R), 0, S - 1)
        for l in range(L):
            p = layer_weights(l)
            for i in range(len(mms)):
                xs[i] = (layer[kinds[l], i](xs[i], p) if l < L - 1
                         else last[i](xs[i], p, rows))
            del p
        toks = np.zeros((R,), np.int32)
        toks[:len(out)] = out
        g, t = gaps(xs, jnp.asarray(toks), jnp.int32(len(out)),
                    top["final_norm.weight"], top["embed.weight"])
        widest, total = max(widest, float(g)), total + float(t)
        count += len(out)
        del xs, x0
        say(f"check: a request of {len(text) + 1} tokens, {len(out)} served, "
            f"in {time.perf_counter() - t0:.1f} s")
    return widest, total / max(count, 1), count


def run(run):
    # ---- copied from run_serve_latent.run (see the module's docstring) ----
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
    n_docs = len(t["system_prompt_counts"])
    window = eng.page_allocs[1]
    run.say(f"run-in: {len(loop.ended)} requests ended, "
            f"{len(loop.token_stamps)} tokens, {len(loop.steps)} engine steps; "
            f"{documents_cached(eng, loop, run)} of {n_docs} documents cached, "
            f"{eng.prefix_cache.num_nodes} trie nodes, "
            f"{eng.page_alloc.num_free} global and {window.num_free} window "
            f"pages free, {eng.resume_cut_tokens} matched tokens run again")
    compiles_before = log.requests
    cut_before, freed_before = eng.resume_cut_tokens, eng.window_pages_freed

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
    # a document that left the cache (or whose tail left the window group)
    # shows as a request admitted without it (the whole 16k prompt then went
    # through the long prefill), or as a document no session can open on
    # when the window ends
    cold = sum(r["hit_tokens"] < t["system_prompt_tokens"] for r in admitted)
    held_docs = documents_cached(eng, loop, run)
    # what the window rule did, from the program's own spans and counters
    adm = [a for a in window_spans(t0, t1, "serving/admit")
           if "resume_blocks" in a]
    grow = window_spans(t0, t1, "serving/decode/grow_pages")
    cache_full = sum(a.get("cache_full", 0) for a in grow)
    run.say(f"documents: {held_docs} of {n_docs} resumable at the window's "
            f"end, {cold} request(s) admitted without their document; "
            f"window group: {window.num_allocated} of "
            f"{window.num_allocatable} pages live, "
            f"{eng.window_pages_freed - freed_before} references dropped "
            f"behind windows, {eng.resume_cut_tokens - cut_before} matched "
            f"tokens run again; global group: {eng.page_alloc.num_allocated} "
            f"of {eng.page_alloc.num_allocatable}; {cache_full} cache_full")
    if cold or held_docs < n_docs:
        run.fail_run(f"a shared document left the prefix cache in the window "
                     f"({cold} cold admissions, {held_docs} of {n_docs} held)")
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
        documents_cached=held_docs, cold_admissions=cold,
        admit_prompt_tokens=sum(a["prompt_tokens"] for a in adm),
        admit_recomputed_tokens=sum(a["recomputed_tokens"] for a in adm),
        window_pages_live=window.num_allocated,
        window_pages=window.num_allocatable)
    run.memory_peak = device.memory_peak_bytes(run.devices)
    if run.trace_on:
        say_host_phases(run)

    # ------------------------------------- the check, engine freed first
    ck = run.config["check"]
    sample = check.pick_sample(ok, run.seed, ck["sample_requests"])
    del loop, eng, model, window
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
