"""Drives the ``serve_latent`` runner (one chip's share of a DeepSeek-V3
layer: latent attention with YaRN, a leading dense layer, some of the
routed experts under sigmoid group-limited routing and a shared one, a
slice of the vocabulary) on the CPU at a tiny size, from an overlay of new
files: the whole run comes out correct against ``reference/deepseek_v3.py``
given the same share; the latent layer's
counts come out of the program's ``serving/decode`` spans; the two new
readers and ``roofline/latent_decode.py`` read a recorded run; and the
configuration file is held to the catalog's row."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest


def _bench_conftest():
    """``benchmark/tests/conftest.py``: the module pytest imported as
    ``conftest`` when these cases run from their own directory, loaded by
    path when they are collected from ``tests/`` (whose conftest has that
    name there)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "conftest.py")
    mod = sys.modules.get("conftest")
    if mod is not None and os.path.abspath(mod.__file__) == path:
        return mod
    name = "benchmark_tests_conftest"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


_bc = _bench_conftest()
TINY_MANIFEST, drive_tiny, overlay = (_bc.TINY_MANIFEST, _bc.drive_tiny,
                                      _bc.overlay)

from harness import common  # noqa: E402

YARN = {"type": "yarn", "factor": 4.0, "original_max_position_embeddings": 32,
        "beta_fast": 4, "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0}
TINY_LATENT = {
    "configs/tiny-latent.json": {
        "name": "tiny-latent", "runner": "serve_latent",
        "attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "hidden_size": 64, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "vocab_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 100,
        "rope_scaling": YARN, "tie_word_embeddings": False,
        "n_routed_experts": 4, "n_routed_experts_published": 16,
        "n_group": 4, "topk_group": 2, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "num_experts_per_tok": 4, "num_nextn_predict_layers": 0,
        "deployment_share": {"chips_per_layer": 4, "chip": 1},
        "initializer_range": 0.02, "dtype": "bfloat16",
        "program": {"query_chunk": 32},
        "engine": {"max_batch_size": 5, "max_seq_len": 128, "page_size": 16,
                   "kv_pages": 48, "prefix_cache": True, "speculative": None,
                   "prefill_buckets": [16, 32, 64, 128]},
        "check": {"sample_requests": 3, "q_block": 16, "head_block": 2,
                  "parts": 2, "limits": {"served_gap_mean": 0.02,
                                         "served_gap_widest": 0.5}}},
    "traffic/tiny-docs.json": {
        "kind": "sessions", "live_sessions": 5, "turns": 2,
        "system_prompt_tokens": 32, "system_prompt_counts": [3, 2],
        "pairing_seed": 5, "page_size": 16, "stagger_start": True,
        "run_in_completed": 6,
        "new_tokens": {"dist": "uniform", "min": 6, "max": 14},
        "answer": {"dist": "uniform", "min": 6, "max": 20}},
    "workloads/tiny-docs.json": {
        "name": "tiny-docs", "config": "tiny-latent", "traffic": "tiny-docs",
        "chips": 1, "why": "test"},
}


@pytest.fixture()
def latent_overlay(overlay):
    for rel, obj in TINY_LATENT.items():
        (overlay / rel).write_text(json.dumps(obj))
    TINY_MANIFEST["workloads"].append({"name": "tiny-docs"})
    TINY_MANIFEST["end_to_end"][2]["workloads"].append("tiny-docs")
    yield overlay
    TINY_MANIFEST["workloads"].pop()
    TINY_MANIFEST["end_to_end"][2]["workloads"].pop()


def test_latent_cell_drives_and_is_correct(latent_overlay):
    from harness import program_spans
    from paddle_tpu import observability as obs

    obs.enable()       # the program's spans record, as under a traced run
    try:
        out, r = drive_tiny("tiny-docs", seconds=2.0)
        steps = [a for _, _, n, a in program_spans.ring()
                 if n == "serving/decode" and "latent_tokens_read" in a]
    finally:
        obs.disable()
        obs.reset()
        # this model's steps carry ``local_rows`` too: leave none in the ring
        # for a later test of another cell in this process to pick up
        obs.clear_spans()
    assert out["correct"] is True, r.compared
    assert out["metrics"]["latency_per_tok_p50_ms"]["value"] > 0
    assert out["attempted"] >= 1 and r.counters["steps"]
    assert r.counters["prompt_tokens_hit"] > 0
    assert r.counters["documents_cached"] == 2
    # every layer reads the running slots' contexts; the shared documents'
    # pages count once however many sessions map them
    assert steps
    for a in steps:
        assert a["latent_tokens_read"] == [a["ctx_tokens"]] * 3
        assert 0 < a["distinct_pages"] <= a["live_pages"]
    assert any(a["distinct_pages"] < a["live_pages"] for a in steps)
    # the share's counts: 4 of 16 experts held; the dense layer counts none
    assert all(a["routed_rows"][0] == 0 and a["routed_rows"][1] > 0
               for a in steps)
    assert not any("compile request" in f or "cache_full" in f
                   for f in r.failures), r.failures


def test_buckets_follow_the_schedule():
    from harness.run_serve_latent import program_buckets

    c, t = (TINY_LATENT["configs/tiny-latent.json"],
            TINY_LATENT["traffic/tiny-docs.json"])
    assert program_buckets(c, t) == ([64, 128], [16, 32, 64])
    cell = common.load_json("configs", "deepseek-v3-l5-ep16-serve.json")
    docs = common.load_json("traffic", "doc-sessions-32k-b64.json")
    assert program_buckets(cell, docs) == ([34816],
                                           [128, 256, 512, 1024, 2048])
    # the cell's traffic, to the letter of ISSUE 40
    assert (docs["live_sessions"], docs["turns"], docs["page_size"]) == (
        64, 3, 16) == (cell["engine"]["max_batch_size"], 3,
                       cell["engine"]["page_size"])
    assert docs["system_prompt_tokens"] == 32768
    assert docs["system_prompt_counts"] == [16, 16, 16, 16]
    assert docs["new_tokens"] == {"dist": "uniform", "min": 64, "max": 192}
    assert docs["answer"] == {"dist": "uniform", "min": 192, "max": 576}
    assert docs["stagger_start"] is True and docs["run_in_completed"] == 64
    # the longest context fits the engine's budget
    assert 32768 + 3 * (192 + 576) <= cell["engine"]["max_seq_len"]


def test_reference_layer_at_rows_is_those_rows_of_the_layer():
    import jax
    import jax.numpy as jnp

    from harness import latent_weights
    from harness.run_serve_latent import decoder_config, reference_config
    from paddle_tpu.models.decoder import param_shapes
    from reference import deepseek_v3 as ref

    c = TINY_LATENT["configs/tiny-latent.json"]
    shapes = param_shapes(decoder_config(c, init="zeros"))
    assert shapes["layers.0.ffn.w1"] == (64, 96)         # the dense layer
    assert shapes["layers.1.ffn.w1"] == (4, 64, 32)      # the held
    assert shapes["layers.1.ffn.router"] == (64, 16)     # all of them
    w = latent_weights.make(7, shapes, 0.02, "bfloat16")
    bias = np.asarray(w["layers.1.ffn.router.bias"], np.float32)
    assert 0.03 < bias.std() < 0.3 and np.abs(bias).min() > 0   # not zero
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 64), jnp.float32)
    rc = reference_config(c)
    assert rc["experts_held"] == (4, 4)                  # chip 1 of 4
    rows = jnp.asarray([95, 3, 40, 41, 42, 17, 0, 64])
    for l, kind in ((0, "dense"), (1, "moe")):
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        whole = ref.layer(x, p, kind, rc, q_block=16)
        some = ref.layer(x, p, kind, rc, q_block=4, rows=rows)
        np.testing.assert_allclose(np.asarray(some), np.asarray(whole)[rows],
                                   rtol=1e-5, atol=1e-6)
        # blocking alone: other head groups, other parts, the same numbers
        other = ref.layer(x, p, kind, {**rc, "head_block": 4, "parts": 1},
                          q_block=32)
        np.testing.assert_allclose(np.asarray(other), np.asarray(whole),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------ the new readers on a recorded run

def recorded_run(kernel_s=0.8, other_s=0.4, steps=4, slots=64, ctx=33000,
                 distinct=None):
    """A run as the readers see one: a reduced trace of ``steps`` decode
    programs (each the named kernel five times for ``kernel_s / 5`` and one
    other op) and one extend program that ALSO runs an op of that name
    (which no decode-program reader may count), with the ``serving/decode``
    spans the program would have put beside them. Numbers of a run recorded
    by hand at the cell's shapes, not a device measurement."""
    from harness import program_spans

    layers = 5
    distinct = 4 * 2048 + slots * 15 if distinct is None else distinct
    ops, mods, ring, t = [], [], [], 100.0
    name = "fusion.7/latent_paged_decode.1"
    for i in range(steps):
        s = t
        for _ in range(layers):
            ops.append((t, t + kernel_s / layers, name, "bf16[64,128,512]"))
            t += kernel_s / layers
        ops.append((t, t + other_s, "fusion.9", "bf16[64,7168]"))
        t += other_s
        mods.append((s, t, "jit_paged_decode_fn"))
        ring.append((s, t, "serving/decode", {
            "ctx_tokens": slots * ctx, "running": slots,
            "latent_tokens_read": [slots * ctx] * layers,
            "distinct_pages": distinct, "live_pages": slots * (ctx // 16 + 1),
            "experts_touched": [0, 14, 15, 16, 13],
            "local_rows": [0, 30, 34, 32, 31],
            "routed_rows": [0] + [slots * 8] * 4}))
        t += 0.01
    ops.append((t, t + 0.3, name, "bf16[1,128,512]"))
    for _ in range(layers):      # an admission of 400 tokens behind 33,000
        ops.append((t + 0.3, t + 0.32, "fusion.3/latent_flash.2",
                    "bf16[8,512,128]"))
    mods.append((t, t + 0.5, "jit_extend_fn"))
    ring.append((t, t + 0.5, "serving/admit/extend{bucket=512}",
                 {"tokens": 400, "bucket": 512, "start": 33000}))

    class Run:
        trace = {"devices": {0: ops}, "modules": {0: mods}}
        trace_host = (99.0, t + 1.0)
        config = common.load_json("configs",
                                  "deepseek-v3-l5-ep16-serve.json")
        peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        counters = {}
        said = []

        def say(self, msg):
            self.said.append(msg)

    return Run(), ring, program_spans


@pytest.fixture()
def ring(monkeypatch):
    def install(run_ring):
        run, spans, program_spans = run_ring
        monkeypatch.setattr(program_spans, "ring", lambda: spans)
        return run
    return install


def test_roofline_reader_on_a_recorded_run(ring):
    from harness import readers_latent

    run = ring(recorded_run())
    share = readers_latent.latent_decode_roofline(run)
    # by hand: 4 steps x 5 layers x 64 slots x 33,000 tokens x 128 heads x
    # (2 x 512 + 64) x 2 FLOPs over the peak, against 4 x 0.8 s of kernel
    # time in decode programs (the extend program's 0.3 s is not counted)
    want = 4 * 5 * 64 * 33000 * 128 * 1088 * 2 / 197e12 / 3.2
    assert abs(share - 100 * want) < 1e-6 and 0 < share < 100
    assert "compute-bound" in run.said[-1]
    assert abs(readers_latent.latent_decode_share(run)
               - 100 * 0.8 / 1.2) < 1e-6
    assert abs(readers_latent.experts_touched_share(run)
               - 100 * (14 + 15 + 16 + 13) / 4 / 16) < 1e-9
    # the expanded form's kernel: 400 queries behind 33,000 cached tokens,
    # 128 heads, (192 + 128) x 2 FLOPs a visible key, five layers, over the
    # five calls' 0.02 s each; the bucket's padding is not required work
    visible = 400 * 33000 + 400 * 401 / 2
    want = 5 * 2 * 128 * 320 * visible / 197e12 / 0.1
    assert abs(readers_latent.latent_flash_roofline(run) - 100 * want) < 1e-6
    assert "compute-bound" in run.said[-1]


def test_share_cannot_pass_100_and_shared_reads_do_not_raise_it(ring):
    """At the chip's best (the kernel as fast as the peak allows) the share
    reads 100; halving the distinct pages (a kernel that shares the reads
    of sessions on one document would still be asked for each page once)
    does not raise the required time while compute decides."""
    from harness import readers_latent
    from roofline import latent_decode

    args = (64 * 33000, 9152, 64, 128, 512, 64)
    t, bound = latent_decode.min_seconds(latent_decode.call(*args),
                                         {"flops_per_s": 197e12,
                                          "hbm_bytes_per_s": 819e9})
    assert bound == "compute"
    half = latent_decode.call(args[0], args[1] // 2, *args[2:])
    assert latent_decode.min_seconds(half, {"flops_per_s": 197e12,
                                            "hbm_bytes_per_s": 819e9}) \
        == (t, "compute")
    # every slot on pages of its own: more bytes, and memory may decide
    own = latent_decode.call(args[0], 64 * 2063, *args[2:])
    assert own["bytes"] > latent_decode.call(*args)["bytes"]
    assert own["flops"] == 2.0 * 64 * 33000 * 128 * 1088
    run = ring(recorded_run(kernel_s=5 * t, steps=3))
    assert abs(readers_latent.latent_decode_roofline(run) - 100.0) < 1e-6
    fewer = ring(recorded_run(kernel_s=5 * t, steps=3, distinct=4000))
    assert abs(readers_latent.latent_decode_roofline(fewer) - 100.0) < 1e-6


@pytest.mark.parametrize("reader", [
    "latent_decode_roofline", "latent_decode_share", "experts_touched_share",
    "latent_flash_roofline"])
def test_readers_give_none_where_there_is_nothing_to_read(reader, ring):
    """An untraced run, a configuration of another kind, and a program
    that puts no such counts on its spans (the parent commit's) read
    None."""
    from harness import readers_latent

    class Run:
        trace = trace_host = None
        config = {"hidden_size": 64}
        counters = {}

    assert getattr(readers_latent, reader)(Run()) is None
    run, spans, ps = recorded_run()
    bare = [(s, e, n, {k: v for k, v in a.items()
                       if k in ("ctx_tokens", "running", "tokens", "bucket")})
            for s, e, n, a in spans]
    run.trace = {"devices": {0: [o for o in run.trace["devices"][0]
                                 if "latent" not in o[2]]},
                 "modules": run.trace["modules"]}
    assert getattr(readers_latent, reader)(ring((run, bare, ps))) is None


# ------------------------------------------- the file against the catalog

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_configuration_file_keeps_the_published_keys():
    """Every key of the published config under its name and value but the
    five in ``reduced``, whose published values stand beside them; no width
    among the reduced."""
    c = common.load_json("configs", "deepseek-v3-l5-ep16-serve.json")
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "model_type": "deepseek_v3",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    if os.path.exists(CATALOG):     # the row itself, where the guide is
        row = [json.loads(l) for l in open(CATALOG)
               if '"name": "DeepSeek-V3"' in l][0]
        assert row["config"] == published
        assert row["source_url"] == c["source"]
    reduced = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
               "n_routed_experts": 16, "vocab_size": 16160,
               "num_nextn_predict_layers": 0}
    assert c["reduced"] == list(reduced)
    for k, v in published.items():
        if k in reduced:
            assert c[k] == reduced[k] and c[k + "_published"] == v, k
        else:
            assert c[k] == v, k
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in reduced)
    d = c["deployment_share"]
    assert c["n_routed_experts"] * d["chips_per_layer"] == 256
    assert c["vocab_size"] * 8 == 129280
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    for k in ("deployment", "assumed", "fit", "check", "_keys"):
        assert c[k], k
