"""Drives the ``serve_window`` runner (one chip's share of a Command A+
layer: sliding-window and full attention layers on page groups of their
own, a parallel block, some of the routed experts under plain sigmoid
routing and four averaged shared ones, a slice of the tied vocabulary) on
the CPU at a tiny size, from an overlay of new files: the whole run comes
out correct against ``reference/command_a_plus.py`` given the same share;
the window's counts come out of the program's ``serving/decode`` spans; the
new readers and ``roofline/paged_decode_window.py`` read a recorded run;
and the configuration file is held to the catalog's row."""

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

CELL = "command-a-plus-l4-ep8-serve.json"
TINY_WINDOW = {
    "configs/tiny-window.json": {
        "name": "tiny-window", "runner": "serve_window",
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 16, "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 32, "layer_norm_eps": 1e-5,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "logit_scale": 1, "norm_topk_prob": True, "num_attention_heads": 4,
        "num_experts": 4, "num_experts_published": 16,
        "num_experts_per_tok": 4, "num_hidden_layers": 4,
        "num_key_value_heads": 2, "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj", "rms_norm_eps": None,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 32, "tie_word_embeddings": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_qk_norm": False, "vocab_size": 128,
        "deployment_share": {"chips_per_layer": 4, "chip": 1},
        "initializer_range": 0.02, "dtype": "bfloat16",
        "program": {"query_chunk": 16},
        "engine": {"max_batch_size": 5, "max_seq_len": 128, "page_size": 16,
                   "kv_pages": 48, "group_pages": {"window": 30},
                   "prefix_cache": True, "speculative": None,
                   "prefill_buckets": [16, 32, 64, 128]},
        "check": {"sample_requests": 3, "q_block": 16,
                  "limits": {"served_gap_mean": 0.02,
                             "served_gap_widest": 0.5}}},
    "traffic/tiny-rag.json": {
        "kind": "sessions", "live_sessions": 5, "turns": 2,
        "system_prompt_tokens": 48, "system_prompt_counts": [3, 2],
        "pairing_seed": 5, "page_size": 16, "stagger_start": True,
        "run_in_completed": 6,
        "new_tokens": {"dist": "uniform", "min": 6, "max": 14},
        "answer": {"dist": "uniform", "min": 6, "max": 20}},
    "workloads/tiny-rag.json": {
        "name": "tiny-rag", "config": "tiny-window", "traffic": "tiny-rag",
        "chips": 1, "why": "test"},
}


@pytest.fixture()
def window_overlay(overlay):
    for rel, obj in TINY_WINDOW.items():
        (overlay / rel).write_text(json.dumps(obj))
    TINY_MANIFEST["workloads"].append({"name": "tiny-rag"})
    TINY_MANIFEST["end_to_end"][2]["workloads"].append("tiny-rag")
    yield overlay
    TINY_MANIFEST["workloads"].pop()
    TINY_MANIFEST["end_to_end"][2]["workloads"].pop()


def test_window_cell_drives_and_is_correct(window_overlay):
    from harness import program_spans
    from paddle_tpu import observability as obs

    obs.enable()       # the program's spans record, as under a traced run
    try:
        out, r = drive_tiny("tiny-rag", seconds=2.0)
        ring = program_spans.ring()
        steps = [a for _, _, n, a in ring
                 if n == "serving/decode" and "window_tokens_read" in a]
        slides = [a for _, _, n, a in ring if n == "serving/window/slide"]
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
    assert r.counters["documents_cached"] == 2      # a session can open on each
    assert r.counters["cold_admissions"] == 0
    assert 0 < r.counters["window_pages_live"] <= r.counters["window_pages"] == 29
    # contexts pass the window of 32: a sliding layer reads less than the
    # full one, the three of them alike; pages behind the windows went
    assert steps and slides and sum(a["freed"] for a in slides) > 0
    for a in steps:
        w, f = a["window_tokens_read"], a["full_tokens_read"]
        assert w[0] == w[1] == w[2] > 0 == w[3] and f[:3] == [0, 0, 0]
        assert w[0] <= min(f[3], 32 * a["running"]) and f[3] == a["ctx_tokens"]
    assert any(a["window_tokens_read"][0] < a["full_tokens_read"][3]
               for a in steps)
    # the share's counts: 4 of 16 experts held
    assert all(0 < a["local_rows"][0] <= a["routed_rows"][0] for a in steps)
    assert not any("compile request" in f or "cache_full" in f
                   for f in r.failures), r.failures


def test_buckets_and_traffic_follow_the_issue():
    from harness.run_serve_window import program_buckets

    c, t = (TINY_WINDOW["configs/tiny-window.json"],
            TINY_WINDOW["traffic/tiny-rag.json"])
    assert program_buckets(c, t) == ([64, 128], [16, 32, 64])
    cell = common.load_json("configs", CELL)
    rag = common.load_json("traffic", "rag-sessions-16k.json")
    assert program_buckets(cell, rag) == ([18432],
                                          [128, 256, 512, 1024, 2048])
    # the cell's traffic, to the letter of ISSUE 42
    assert (rag["live_sessions"], rag["turns"], rag["page_size"]) == (
        32, 3, 16) == (cell["engine"]["max_batch_size"], 3,
                       cell["engine"]["page_size"])
    assert rag["kind"] == "sessions" and rag["pairing_seed"] == 20261002
    assert rag["system_prompt_tokens"] == 16384
    assert rag["system_prompt_counts"] == [2] * 16
    assert rag["new_tokens"] == {"dist": "uniform", "min": 64, "max": 192}
    assert rag["answer"] == {"dist": "uniform", "min": 192, "max": 576}
    assert rag["stagger_start"] is True and rag["run_in_completed"] == 32
    # the longest context fits the engine's budget, and is over four windows
    assert 16384 + 3 * (192 + 576) <= cell["engine"]["max_seq_len"]
    assert 16384 + 64 > 4 * cell["sliding_window"]
    wl = common.load_json("workloads", "serve-cmda-plus-ep8-rag16k.json")
    assert (wl["config"], wl["traffic"], wl["chips"], wl["trace_seconds"]) \
        == ("command-a-plus-l4-ep8-serve", "rag-sessions-16k", 1, 6)


def test_built_model_is_the_counted_share():
    """The cut's arithmetic, redone from the built model's shapes: 4,733M
    parameters = 8.82 GiB in bfloat16; the pools' bytes a page."""
    from harness.run_serve_window import decoder_config, reference_config
    from paddle_tpu.models.decoder import DecoderLM, param_shapes

    c = common.load_json("configs", CELL)
    cfg = decoder_config(c, init="zeros")
    shapes = param_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for n, s in shapes.items()
                             if keep(n))
    assert count(lambda n: n.startswith("layers.0.attn.")) == 142_606_336
    assert count(lambda n: n.startswith("layers.0.ffn.shared")) == 201_326_592
    assert count(lambda n: n.startswith("layers.0.ffn.w")) == 805_306_368
    assert shapes["layers.0.ffn.router"] == (4096, 128)
    assert shapes["layers.0.ffn.w1"] == (16, 4096, 4096)
    assert shapes["layers.0.ffn.shared.w1"] == (4096, 4 * 4096)
    assert shapes["embed.weight"] == (32768, 4096) and "head.weight" not in shapes
    assert "layers.0.ffn_norm.weight" not in shapes
    assert not any(n.endswith(".bias") for n in shapes)
    total = count(lambda n: True)
    assert total == 4 * 1_149_767_680 + 134_217_728 + 4096 == 4_733_292_544
    assert abs(total * 2 / 2**30 - 8.82) < 0.005
    pools = DecoderLM.cache_pools(type("M", (), {"cfg": cfg, "_pools": lambda
                                                 s, w: DecoderLM._pools(s, w)})())
    assert [(p[0], p[3]) for p in pools] == [
        ("k_window", (0, 1, 2)), ("v_window", (0, 1, 2)),
        ("k", (3,)), ("v", (3,))]
    assert pools[0][4] == ("window", 4096)
    page = 16 * 8 * 128 * 2 * 2                      # K and V, one layer
    e = c["engine"]
    assert page == 64 * 1024
    assert abs(e["kv_pages"] * page / 2**30 - 1.47) < 0.005
    assert abs(e["group_pages"]["window"] * 3 * page / 2**30 - 1.88) < 0.005
    assert reference_config(c)["experts_held"] == (16, 0)


def test_reference_layer_at_rows_is_those_rows_of_the_layer():
    import jax
    import jax.numpy as jnp

    from harness import window_weights
    from harness.run_serve_window import decoder_config, reference_config
    from paddle_tpu.models.decoder import param_shapes
    from reference import command_a_plus as ref

    c = TINY_WINDOW["configs/tiny-window.json"]
    shapes = param_shapes(decoder_config(c, init="zeros"))
    assert shapes["layers.1.ffn.w1"] == (4, 64, 32)      # the held
    assert shapes["layers.1.ffn.router"] == (64, 16)     # all of them
    w = window_weights.make(7, shapes, 0.02, "bfloat16")
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 64), jnp.float32)
    rc = reference_config(c)
    assert rc["experts_held"] == (4, 4)                  # chip 1 of 4
    rows = jnp.asarray([95, 3, 40, 41, 42, 17, 0, 64])
    for l, kind in ((0, "sliding_attention"), (3, "full_attention")):
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        whole = ref.layer(x, p, kind, rc, q_block=16)
        some = ref.layer(x, p, kind, rc, q_block=4, rows=rows)
        np.testing.assert_allclose(np.asarray(some), np.asarray(whole)[rows],
                                   rtol=1e-5, atol=1e-6)
    # the window is a mask: beyond it a sliding layer parts from a full one
    p = {k[len("layers.0."):]: v for k, v in w.items()
         if k.startswith("layers.0.")}
    wide = ref.layer(x, p, "sliding_attention", {**rc, "sliding_window": 96},
                     q_block=16)
    narrow = ref.layer(x, p, "sliding_attention", rc, q_block=16)
    np.testing.assert_allclose(narrow[:32], wide[:32], atol=1e-6)
    assert np.abs(np.asarray(narrow[32:] - wide[32:])).max() > 1e-4


# ------------------------------------ the new readers on a recorded run

def recorded_run(window_s=0.3, full_s=0.4, other_s=0.4, steps=4, slots=32,
                 ctx=17500):
    """A run as the readers see one: a reduced trace of ``steps`` decode
    programs (each the windowed kernel three times for ``window_s / 3``,
    the full kernel once for ``full_s`` and one other op) and one extend
    program that ALSO runs an op of the windowed kernel's name (which no
    decode-program reader may count), with the ``serving/decode`` spans the
    program would have put beside them. Numbers of a run recorded by hand
    at the cell's shapes, not a device measurement."""
    from harness import program_spans

    ops, mods, ring, t = [], [], [], 100.0
    for i in range(steps):
        s = t
        for _ in range(3):
            ops.append((t, t + window_s / 3, "fusion.7/window_decode.1",
                        "bf16[32,128,128]"))
            t += window_s / 3
        ops.append((t, t + full_s, "fusion.8/paged_decode.2",
                    "bf16[32,128,128]"))
        t += full_s
        ops.append((t, t + other_s, "fusion.9", "bf16[32,4096]"))
        t += other_s
        mods.append((s, t, "jit_paged_decode_fn"))
        ring.append((s, t, "serving/decode", {
            "ctx_tokens": slots * ctx, "running": slots,
            "window_tokens_read": [slots * min(ctx, 4096)] * 3 + [0],
            "full_tokens_read": [0, 0, 0, slots * ctx],
            "window_pages_live": 6000 + 100 * i, "window_pages": 10240,
            "experts_touched": [14, 15, 16, 13],
            "local_rows": [30, 34, 32, 31],
            "routed_rows": [slots * 8] * 4}))
        t += 0.01
    ops.append((t, t + 0.3, "fusion.7/window_decode.1", "bf16[1,128,128]"))
    mods.append((t, t + 0.5, "jit_extend_fn"))

    class Run:
        trace = {"devices": {0: ops}, "modules": {0: mods}}
        trace_host = (99.0, t + 1.0)
        config = common.load_json("configs", CELL)
        peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        counters = {"window_pages_live": 5000, "window_pages": 10240}
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


def test_readers_on_a_recorded_run(ring):
    from harness import readers_window

    run = ring(recorded_run())
    share = readers_window.window_decode_roofline(run)
    # by hand: 4 steps x 3 layers x 32 slots x 4,096 tokens x (K + V) x 8
    # heads x 128 x 2 B, + a query and an output of 128 x 128 x 2 B a slot
    # and layer, over the bandwidth, against 4 x 0.3 s of the windowed
    # kernel in decode programs (the extend program's 0.3 s is not counted)
    rows = 4 * 3 * 32
    want = (rows * 4096 * 2 * 8 * 128 * 2 + rows * 2 * 128 * 128 * 2) \
        / 819e9 / 1.2
    assert abs(share - 100 * want) < 1e-6 and 0 < share < 100
    assert "memory-bound" in run.said[-1]
    full = readers_window.paged_decode_roofline(run)
    want = 4 * 32 * 17500 * 2 * 8 * 128 * 2 / 819e9 / 1.6
    assert abs(full - 100 * want) < 1e-6 and 0 < full < 100
    assert abs(readers_window.window_read_share(run)
               - 100 * 4096 / 17500) < 1e-9
    assert abs(readers_window.window_pool_fill(run)
               - 100 * 6150 / 10240) < 1e-9
    assert abs(readers_window.experts_touched_share(run)
               - 100 * (14 + 15 + 16 + 13) / 4 / 16) < 1e-9
    assert abs(readers_window.local_rows_share(run)
               - 100 * (30 + 34 + 32 + 31) / (4 * 32 * 8)) < 1e-9
    assert readers_window.moe_experts_roofline(run) is None   # no such op


def test_share_cannot_pass_100_and_a_full_walk_reads_a_quarter(ring):
    """At the chip's best (the kernel as fast as the bandwidth allows for
    the window's bytes) the share reads 100; a kernel that walked the WHOLE
    context, timed as if it read all of it, reads the window's share of
    that: a quarter at four windows and a bit of context."""
    from harness import readers_window
    from roofline import paged_decode_window

    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    args = (32 * 4096, 32, 128, 8, 128)
    t, bound = paged_decode_window.min_seconds(
        paged_decode_window.call(*args), peaks)
    assert bound == "memory"
    assert paged_decode_window.call(*args)["flops"] == 4.0 * 32 * 4096 * 128 * 128
    run = ring(recorded_run(window_s=3 * t, steps=3))
    assert abs(readers_window.window_decode_roofline(run) - 100.0) < 1e-6
    whole, _ = paged_decode_window.min_seconds(
        paged_decode_window.call(32 * 17500, 32, 128, 8, 128), peaks)
    walked = ring(recorded_run(window_s=3 * whole, steps=3))
    share = readers_window.window_decode_roofline(walked)
    assert 23.0 < share < 24.0                       # 4,096 / 17,500 = 23.4%


@pytest.mark.parametrize("reader", [
    "window_decode_roofline", "paged_decode_roofline", "window_read_share",
    "window_pool_fill", "experts_touched_share", "local_rows_share",
    "moe_experts_roofline"])
def test_readers_give_none_where_there_is_nothing_to_read(reader, ring):
    """An untraced run, a configuration of another kind, and a program
    that puts no such counts on its spans (the parent commit's) read
    None."""
    from harness import readers_window

    class Run:
        trace = trace_host = None
        config = {"hidden_size": 64}
        counters = {}

    assert getattr(readers_window, reader)(Run()) is None
    run, spans, ps = recorded_run()
    run.counters = {}
    bare = [(s, e, n, {k: v for k, v in a.items()
                       if k in ("ctx_tokens", "running", "tokens", "bucket")})
            for s, e, n, a in spans]
    run.trace = {"devices": {0: [o for o in run.trace["devices"][0]
                                 if "decode" not in o[2]]},
                 "modules": run.trace["modules"]}
    assert getattr(readers_window, reader)(ring((run, bare, ps))) is None


# ------------------------------------------- the file against the catalog

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_configuration_file_keeps_the_published_keys():
    """Every key of the published config under its name and value but the
    four in ``reduced``, whose published values stand beside them; no width
    among the reduced."""
    c = common.load_json("configs", CELL)
    period = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4, "layer_types": period * 8,
        "logit_scale": 1, "max_position_embeddings": 200000,
        "model_type": "cohere2_moe", "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 8, "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj",
        "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 4096, "tf_legacy_loss": False,
        "tie_word_embeddings": True, "use_embedding_sharing": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_parallel_embedding": False, "use_qk_norm": False,
        "vocab_size": 262144}
    if os.path.exists(CATALOG):     # the row itself, where the guide is
        row = [json.loads(l) for l in open(CATALOG)
               if '"name": "command-a-plus-05-2026"' in l][0]
        assert row["config"] == published
        assert row["source_url"] == c["source"]
    reduced = {"num_hidden_layers": 4, "layer_types": period,
               "num_experts": 16, "vocab_size": 32768}
    assert c["reduced"] == list(reduced)
    for k, v in published.items():
        if k in reduced:
            assert c[k] == reduced[k] and c[k + "_published"], k
        else:
            assert c[k] == v, k
    assert (c["num_hidden_layers_published"], c["num_experts_published"],
            c["vocab_size_published"]) == (32, 128, 262144)
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in reduced)
    d = c["deployment_share"]
    assert c["num_experts"] * d["chips_per_layer"] == 128
    assert c["vocab_size"] * 8 == 262144
    assert c["num_hidden_layers"] == c["layer_switch"]       # a whole period
    assert len(c["assumed"]) >= 8
    for k in ("deployment", "assumed", "fit", "check", "_keys"):
        assert c[k], k
    manifest = json.load(open(os.path.join(common.ROOT, "BENCHMARK.json")))
    entry = [e for e in manifest["configs"] if e["name"] == c["name"]][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/" + CELL
