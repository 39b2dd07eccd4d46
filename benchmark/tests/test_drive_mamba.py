"""Drives the ``serve_mamba`` runner (one chip's share of a Nemotron 3 Nano
stage: layers of ONE part each, Mamba-2 state-space mixers on slot state,
ungated relu² experts of which some are held, a shared expert of a width of
its own, NoPE GQA, a slice of the vocabulary) on the CPU at a tiny size,
from an overlay of new files: the whole run comes out correct against
``reference/nemotron3_nano.py`` given the same share; the new counts come
out of the program's ``serving/decode`` spans; the three new readers and the
two new roofline functions read a recorded run; and the configuration file
is held to the catalog's row."""

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

CELL = "nemotron3-nano-30b-l13-ep2-serve.json"
WORKLOAD = "serve-nemotron3-nano-ep2-reason4k"
TINY_MAMBA = {
    "configs/tiny-mamba.json": {
        "name": "tiny-mamba", "runner": "serve_mamba",
        "attention_bias": False, "chunk_size": 8, "conv_kernel": 4,
        "head_dim": 16, "hidden_size": 64,
        "hybrid_override_pattern": "MEM*EME", "layer_norm_epsilon": 1e-5,
        "mamba_head_dim": 8, "mamba_hidden_act": "silu",
        "mamba_num_heads": 8, "mamba_proj_bias": False, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48, "n_group": 1,
        "n_groups": 2, "n_routed_experts": 8,
        "n_routed_experts_published": 16, "n_shared_experts": 1,
        "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4,
        "num_experts_per_tok": 3, "num_hidden_layers": 7,
        "num_key_value_heads": 2, "residual_in_fp32": False,
        "routed_scaling_factor": 2.5, "ssm_state_size": 16,
        "tie_word_embeddings": False, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "vocab_size": 128,
        "deployment_share": {"chips_per_layer": 2, "chip": 1},
        "initializer_range": 0.02, "dtype": "bfloat16",
        "program": {"query_chunk": 32},
        "engine": {"max_batch_size": 6, "max_seq_len": 128, "page_size": 16,
                   "kv_pages": 60, "state_snapshots": 6,
                   "prefix_cache": True, "speculative": None,
                   "prefill_buckets": [16, 32, 64]},
        "check": {"sample_requests": 3, "q_block": 16,
                  "limits": {"served_gap_mean": 0.02,
                             "served_gap_widest": 0.5}}},
    "traffic/tiny-reason.json": {
        "kind": "sessions", "live_sessions": 6, "turns": 1,
        "system_prompt_tokens": 32, "system_prompt_counts": [4, 2],
        "pairing_seed": 5, "page_size": 16, "run_in_completed": 9,
        "new_tokens": {"dist": "uniform", "min": 6, "max": 30},
        "answer": {"dist": "uniform", "min": 8, "max": 40}},
    "workloads/tiny-reason.json": {
        "name": "tiny-reason", "config": "tiny-mamba",
        "traffic": "tiny-reason", "chips": 1, "why": "test"},
}


@pytest.fixture()
def mamba_overlay(overlay):
    for rel, obj in TINY_MAMBA.items():
        (overlay / rel).write_text(json.dumps(obj))
    TINY_MANIFEST["workloads"].append({"name": "tiny-reason"})
    TINY_MANIFEST["end_to_end"][2]["workloads"].append("tiny-reason")
    yield overlay
    TINY_MANIFEST["workloads"].pop()
    TINY_MANIFEST["end_to_end"][2]["workloads"].pop()


def test_mamba_cell_drives_and_is_correct(mamba_overlay):
    from harness import program_spans
    from paddle_tpu import observability as obs

    obs.clear_spans()  # (another test's, of this kind of model, may stand)
    obs.enable()       # the program's spans record, as under a traced run
    try:
        out, r = drive_tiny("tiny-reason", seconds=2.0)
        steps = [a for _, _, n, a in program_spans.ring()
                 if n == "serving/decode" and "ssm_slots_stepped" in a]
    finally:
        obs.disable()
        obs.reset()
        obs.clear_spans()
    assert out["correct"] is True, r.compared
    assert out["metrics"]["latency_per_tok_p50_ms"]["value"] > 0
    assert out["attempted"] >= 1 and r.counters["steps"]
    assert r.counters["prompt_tokens_hit"] > 0
    # after run-in every admission restores a branch snapshot
    assert r.counters["admit_prompt_tokens"] > 0
    assert r.counters["admit_recomputed_tokens"] \
        < 0.2 * r.counters["admit_prompt_tokens"]
    assert r.counters["snapshots_capacity"] == 6
    # a Mamba-2 layer steps the running slots and routes nothing; an expert
    # layer routes 3 rows a slot, of which about half land on the 8 of 16
    pat = "MEM*EME"
    assert steps
    for a in steps:
        assert a["ssm_slots_stepped"] == [
            a["running"] if ch == "M" else 0 for ch in pat]
        assert a["routed_rows"] == [6 * 3 if ch == "E" else 0 for ch in pat]
    local = sum(map(sum, (a["local_rows"] for a in steps)))
    routed = sum(map(sum, (a["routed_rows"] for a in steps)))
    assert 0.2 < local / routed < 0.8
    assert all(t <= 8 for a in steps for t in a["experts_touched"])
    assert not any("compile request" in f or "cache_full" in f
                   for f in r.failures), r.failures


def test_buckets_and_traffic_follow_the_issue():
    from harness.run_serve_mamba import program_buckets

    c, t = (TINY_MAMBA["configs/tiny-mamba.json"],
            TINY_MAMBA["traffic/tiny-reason.json"])
    assert program_buckets(c, t) == ([32, 64], [16])
    cell = common.load_json("configs", CELL)
    mix = common.load_json("traffic", "reasoning-longgen-4k.json")
    assert program_buckets(cell, mix) == ([2048, 2560], [16, 128, 256, 512])
    # the cell's traffic, to the letter of ISSUE 46
    e = cell["engine"]
    assert (mix["live_sessions"], mix["turns"], mix["page_size"]) == (
        256, 1, 16) == (e["max_batch_size"], 1, e["page_size"])
    assert mix["kind"] == "sessions" and mix["pairing_seed"] == 20261004
    assert mix["system_prompt_tokens"] == 2048
    assert mix["system_prompt_counts"] == [64, 64, 32, 32, 16, 16, 16, 16]
    assert mix["new_tokens"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["answer"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert mix["run_in_completed"] == 256 and "stagger_start" not in mix
    assert 2048 + 512 + 1536 <= e["max_seq_len"] == 4352
    assert e["state_snapshots"] == 24 and e["prefix_cache"] is True
    wl = common.load_json("workloads", WORKLOAD + ".json")
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "nemotron3-nano-30b-l13-ep2-serve", "reasoning-longgen-4k", 1)
    assert 3 <= wl["trace_seconds"] <= 6 and len(wl["why"]) <= 200


def test_built_model_is_the_counted_share():
    """The cut's arithmetic, redone from the built model's shapes: 3,926M
    parameters = 7.31 GiB in bfloat16; the state's and the pools' bytes."""
    from harness.run_serve_mamba import decoder_config, reference_config
    from paddle_tpu.models.decoder import DecoderLM, param_shapes

    c = common.load_json("configs", CELL)
    cfg = decoder_config(c, init="zeros")
    shapes = param_shapes(cfg)
    count = lambda keep: sum(int(np.prod(s)) for n, s in shapes.items()
                             if keep(n))
    layer = lambda l: count(lambda n: n.startswith(f"layers.{l}."))
    assert shapes["layers.0.attn.w_in"] == (2688, 4096 + 6144 + 64)
    assert shapes["layers.0.attn.conv.weight"] == (6144, 4)
    assert shapes["layers.0.attn.conv.bias"] == (6144,)
    assert shapes["layers.0.attn.norm.weight"] == (4096,)
    assert shapes["layers.1.ffn.router"] == (2688, 128)      # all of them
    assert shapes["layers.1.ffn.router.bias"] == (128,)
    assert shapes["layers.1.ffn.w1"] == (64, 1856, 2688)     # the held
    assert shapes["layers.1.ffn.w2"] == (64, 1856, 2688)
    assert "layers.1.ffn.w3" not in shapes
    assert shapes["layers.1.ffn.shared.w1"] == (2688, 3712)
    assert shapes["layers.5.attn.wq"] == (2688, 32 * 128)
    assert shapes["layers.5.attn.wk"] == (2688, 2 * 128)
    # ONE norm a layer, and the part's own parameters only
    for l, ch in enumerate(c["hybrid_override_pattern"]):
        norms = [n for n in shapes if n.startswith(f"layers.{l}.")
                 and n.endswith(("attn_norm.weight", "ffn_norm.weight"))]
        assert norms == [f"layers.{l}." + ("ffn" if ch == "E" else "attn")
                         + "_norm.weight"]
    assert not any(n.endswith(".bias") and "conv" not in n
                   and "router" not in n for n in shapes)
    assert (layer(0), layer(1), layer(5)) == (38_744_896, 658_885_376,
                                              23_399_040)
    total = count(lambda n: True)
    assert total == 6 * 38_744_896 + 5 * 658_885_376 + 2 * 23_399_040 \
        + 2 * 65536 * 2688 + 2688 == 3_926_018_560
    assert abs(total * 2 / 2**30 - 7.31) < 0.005
    model = type("M", (), {"cfg": cfg,
                           "_pools": lambda s, w: DecoderLM._pools(s, w)})()
    state = DecoderLM.state_pools(model)
    M = (0, 2, 4, 7, 9, 11)
    assert [(s[0], s[1], s[2], s[3]) for s in state] == [
        ("ssm_state", (32, 128, 128), "float32", M),
        ("ssm_conv", (3, 6144), "bfloat16", M)]
    assert [(p[0], p[1], p[2], p[3]) for p in DecoderLM.cache_pools(model)] \
        == [("k", 2, 128, (5, 12)), ("v", 2, 128, (5, 12))]
    slot = 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    e = c["engine"]
    assert abs(e["max_batch_size"] * slot / 2**30 - 3.05) < 0.005
    assert abs(e["state_snapshots"] * slot / 2**30 - 0.29) < 0.005
    page = 16 * 2 * 128 * 2 * 2 * 2              # K and V, both layers
    assert page == 32 * 1024
    assert abs(e["kv_pages"] * page / 2**30 - 1.13) < 0.005
    assert reference_config(c)["experts_held"] == (64, 0)


def test_reference_layer_at_rows_is_those_rows_of_the_layer():
    import jax
    import jax.numpy as jnp

    from harness import mamba_weights
    from harness.run_serve_mamba import decoder_config, reference_config
    from paddle_tpu.models.decoder import param_shapes
    from reference import nemotron3_nano as ref

    c = TINY_MAMBA["configs/tiny-mamba.json"]
    shapes = param_shapes(decoder_config(c, init="zeros"))
    assert shapes["layers.1.ffn.w1"] == (8, 32, 64)      # the held, [out, in]
    assert shapes["layers.1.ffn.router"] == (64, 16)     # all of them
    w = mamba_weights.make(7, shapes, 0.02, "bfloat16")
    # the family's leaves are drawn by their own rules
    f32 = lambda n: np.asarray(w[n].astype(jnp.float32))
    assert (f32("layers.0.attn.D") == 1).all()
    assert (f32("layers.0.attn.conv.bias") == 0).all()
    assert 0 <= f32("layers.0.attn.A_log").min() \
        and f32("layers.0.attn.A_log").max() <= np.log(16) + 0.02
    assert 0 < np.abs(f32("layers.1.ffn.router.bias")).max() < 0.06
    assert np.abs(f32("layers.0.attn.conv.weight")).max() <= 0.5
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 64), jnp.float32)
    rc = reference_config(c)
    assert rc["experts_held"] == (8, 8)                  # chip 1 of 2
    rows = jnp.asarray([95, 3, 40, 41, 42, 17, 0, 64])
    for l, kind in ((0, "mamba"), (1, "experts"), (3, "attention")):
        assert rc["layer_types"][l] == kind
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        whole = ref.layer(x, p, kind, rc, q_block=16)
        some = ref.layer(x, p, kind, rc, q_block=4, rows=rows)
        np.testing.assert_allclose(np.asarray(some), np.asarray(whole)[rows],
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------ readers on a recorded run

def recorded_run(step_s=0.011, gmm_s=0.009, attn_s=0.003, other_s=0.004,
                 steps=4, slots=256, ctx=3400):
    """A run as the readers see one: a reduced trace of ``steps`` decode
    programs (each the recurrent step's kernel six times for ``step_s / 6``,
    the grouped matmul ten times for ``gmm_s / 10``, the paged attend twice
    and one other op) and one extend program that ALSO runs a grouped matmul
    (which no decode-program reader may count), with the ``serving/decode``
    spans the program would have put beside them. Numbers of a run recorded
    by hand at the cell's shapes, not a device measurement."""
    from harness import program_spans

    pat = "MEMEM*EMEMEM*"
    ops, mods, ring, steps_c, t = [], [], [], [], 100.0
    for i in range(steps):
        s = t
        for n, dur, name in ((6, step_s / 6, "fusion.3/mamba2_decode_step.1"),
                             (10, gmm_s / 10, "fusion.4/moe_grouped_matmul.2"),
                             (2, attn_s / 2, "fusion.5/paged_decode.3"),
                             (1, other_s, "fusion.9")):
            for _ in range(n):
                ops.append((t, t + dur, name, "bf16[256,2688]"))
                t += dur
        mods.append((s, t, "jit_paged_decode_fn"))
        ring.append((s, t, "serving/decode", {
            "ctx_tokens": slots * ctx, "running": slots,
            "ssm_slots_stepped": [slots if ch == "M" else 0 for ch in pat],
            "experts_touched": [64 if ch == "E" else 0 for ch in pat],
            "expert_max_load": [20 if ch == "E" else 0 for ch in pat],
            "local_rows": [760 + i if ch == "E" else 0 for ch in pat],
            "routed_rows": [slots * 6 if ch == "E" else 0 for ch in pat]}))
        steps_c.append((s, t, slots, slots * ctx))
        t += 0.001
    ops.append((t, t + 0.02, "fusion.4/moe_grouped_matmul.2",
                "bf16[3072,2688]"))
    mods.append((t, t + 0.05, "jit_extend_fn"))

    class Run:
        trace = {"devices": {0: ops}, "modules": {0: mods}}
        trace_host = (99.0, t + 1.0)
        config = common.load_json("configs", CELL)
        peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        counters = {"steps": steps_c}
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
    from harness import readers_mamba

    run = ring(recorded_run())
    share = readers_mamba.mamba_decode_roofline(run)
    # by hand: 4 steps x 6 layers x 256 slots x (a state of 64 x 64 x 128
    # float32 in and out + x and y 4,096 each, B and C 1,024 each, dt 64)
    # over the bandwidth, against 4 x 0.011 s of the kernel
    rows = 4 * 6 * 256
    want = rows * (2 * 64 * 64 * 128 + 2 * 4096 + 2 * 1024 + 64) * 4 \
        / 819e9 / 0.044
    assert abs(share - 100 * want) < 1e-6 and 0 < share < 100
    assert "memory-bound" in run.said[-1]
    # the recurrence's share of the decode programs' device seconds
    assert abs(readers_mamba.mamba_step_share(run)
               - 100 * 0.011 / 0.027) < 1e-6
    # two matrices of each touched held expert, five layers a step
    moe = readers_mamba.moe_experts_roofline(run)
    want = 4 * 5 * 64 * 2 * 2688 * 1856 * 2 / 819e9 / 0.036
    assert abs(moe - 100 * want) < 1e-6 and 0 < moe < 100
    # every held expert touched in the five EXPERT layers (over all
    # thirteen layers the same counts would read 5 / 13 of that)
    assert abs(readers_mamba.experts_touched_share(run) - 100.0) < 1e-9
    attn = readers_mamba.paged_decode_roofline(run)
    want = 4 * 2 * 256 * 3400 * 2 * 2 * 128 * 2 / 819e9 / 0.012
    assert abs(attn - 100 * want) < 1e-6 and 0 < attn < 100


def test_no_share_can_pass_100_and_why_the_gated_count_is_not_used(ring):
    """At the chip's best (each kernel as fast as the bandwidth allows for
    its required bytes) the shares read 100, no more. Read against the
    THREE-matrix function of the gated experts the same run would read
    150%: an impossible reading, which is why this cell has a roofline
    function of its own."""
    from harness import readers_mamba
    from roofline import mamba2_step, moe_experts, moe_experts_relu2

    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    w = mamba2_step.call(6 * 256, 64, 64, 8, 128)
    assert w["flops"] == 5.0 * 6 * 256 * 64 * 64 * 128
    assert w["bytes"] == 6 * 256 * (2 * 64 * 64 * 128 + 2 * 4096
                                    + 2 * 8 * 128 + 64) * 4
    t_step, bound = mamba2_step.min_seconds(w, peaks)
    assert bound == "memory"
    two = moe_experts_relu2.call(5 * 64, 5 * 763, 2688, 1856)
    three = moe_experts.call(5 * 64, 5 * 763, 2688, 1856)
    assert two["bytes"] == 5 * 64 * 2 * 2688 * 1856 * 2
    assert two["flops"] == 4.0 * 5 * 763 * 2688 * 1856
    assert three["bytes"] == 1.5 * two["bytes"]
    t_moe, bound = moe_experts_relu2.min_seconds(two, peaks)
    assert bound == "memory"
    run = ring(recorded_run(step_s=t_step, gmm_s=t_moe, steps=3))
    run.counters  # (the recorded local rows are 760..762 a layer)
    assert abs(readers_mamba.mamba_decode_roofline(run) - 100.0) < 1e-6
    assert abs(readers_mamba.moe_experts_roofline(run) - 100.0) < 1e-6
    t3, _ = moe_experts.min_seconds(three, peaks)
    assert abs(100.0 * t3 / t_moe - 150.0) < 1e-6     # impossible_reading


@pytest.mark.parametrize("reader", [
    "mamba_decode_roofline", "mamba_step_share", "moe_experts_roofline",
    "experts_touched_share", "paged_decode_roofline"])
def test_readers_give_none_where_there_is_nothing_to_read(reader, ring):
    """An untraced run, a configuration of another kind, and a program
    that puts no such counts on its spans or runs no such kernel (the
    parent commit's) read None."""
    from harness import readers_mamba

    class Run:
        trace = trace_host = None
        config = {"hidden_size": 64}
        counters = {}

    assert getattr(readers_mamba, reader)(Run()) is None
    run, spans, ps = recorded_run()
    bare = [(s, e, n, {k: v for k, v in a.items()
                       if k in ("ctx_tokens", "running")})
            for s, e, n, a in spans]
    run.trace = {"devices": {0: [o for o in run.trace["devices"][0]
                                 if o[2] == "fusion.9"]},
                 "modules": run.trace["modules"]}
    assert getattr(readers_mamba, reader)(ring((run, bare, ps))) is None


# ------------------------------------------- the file against the catalog

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_configuration_file_keeps_the_published_keys():
    """Every key of the published config under its name and value but the
    four in ``reduced``, whose published values stand beside them; no width
    among the reduced."""
    c = common.load_json("configs", CELL)
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern": pattern, "intermediate_size": 1856,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    if os.path.exists(CATALOG):     # the row itself, where the guide is
        row = [json.loads(l) for l in open(CATALOG)
               if '"name": "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in l][0]
        assert row["config"] == published
        assert row["source_url"] == c["source"]
    reduced = {"num_hidden_layers": 13,
               "hybrid_override_pattern": pattern[:13],
               "n_routed_experts": 64, "vocab_size": 65536}
    assert c["reduced"] == list(reduced)
    for k, v in published.items():
        if k in reduced:
            assert c[k] == reduced[k] and c[k + "_published"] == v, k
        else:
            assert c[k] == v, k
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in reduced)
    assert {ch: pattern[:13].count(ch) for ch in "ME*"} == {
        "M": 6, "E": 5, "*": 2}
    d = c["deployment_share"]
    assert d == {"chips_per_layer": 2, "chip": 0, "pipeline_stages": 4,
                 "layers_per_stage": 13}
    assert c["n_routed_experts"] * d["chips_per_layer"] == 128
    assert c["vocab_size"] * d["chips_per_layer"] == 131072
    assert d["pipeline_stages"] * d["layers_per_stage"] == 52
    assert len(c["assumed"]) >= 6
    for k in ("deployment", "assumed", "fit", "check", "_keys", "_reduced"):
        assert c[k], k
    assert c["fit"]["bytes"], "fit_mamba.py's bytes"
    manifest = json.load(open(os.path.join(common.ROOT, "BENCHMARK.json")))
    entry = [e for e in manifest["configs"] if e["name"] == c["name"]][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/" + CELL
    cell = [w for w in manifest["workloads"] if w["name"] == WORKLOAD][0]
    assert cell == {k: common.load_json("workloads", WORKLOAD + ".json")[k]
                    for k in ("name", "config", "traffic", "chips", "why")}
    lat = [m for m in manifest["end_to_end"]
           if m["name"] == "latency_per_tok_p50_ms"][0]
    assert lat["workloads"][-1] == WORKLOAD
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [WORKLOAD]]
    assert len(mine) == 17 and all(n.endswith(".reason") for n in mine)
    for n in mine:      # each has its reader's file
        assert os.path.exists(common.find("layer_metrics", n + ".py")), n
