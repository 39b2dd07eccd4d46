"""Drives the ``serve_solar`` runner (one chip's share of a layer: a decay a
key channel, gated grouped attention, some of the routed experts and a
shared one, a slice of the vocabulary) on the CPU at a tiny size, from an
overlay of new files: the whole run comes out correct against
``reference/solar_open2.py`` given the same share, the held-expert counts
come out of the program's ``serving/decode`` spans, and the reference's
last layer asked for some rows equals those rows of the whole layer."""

import json

import numpy as np
import pytest

from conftest import TINY_MANIFEST, drive_tiny

TINY_SOLAR = {
    "configs/tiny-solar.json": {
        "name": "tiny-solar", "runner": "serve_solar",
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                               "num_heads": 4, "num_kv_heads": None},
        "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
        "head_dim": 16, "num_key_value_heads": 2, "vocab_size": 128,
        "moe_intermediate_size": 32, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_layers": [0], "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_routed_experts": 4, "n_routed_experts_published": 16,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 4,
        "deployment_share": {"chips_per_layer": 4, "chip": 1},
        "assumed_sizes": {"kda_rank": 8},
        "initializer_range": 0.02, "dtype": "bfloat16",
        "program": {"query_chunk": 32, "gdn_chunk": 8},
        "engine": {"max_batch_size": 6, "max_seq_len": 128, "page_size": 16,
                   "kv_pages": 60, "state_snapshots": 6,
                   "prefix_cache": True, "speculative": None,
                   "prefill_buckets": [16, 32, 64]},
        "check": {"sample_requests": 3, "q_block": 16,
                  "limits": {"served_gap_mean": 0.02,
                             "served_gap_widest": 0.5}}},
    "traffic/tiny-agents.json": {
        "kind": "sessions", "live_sessions": 6, "turns": 1,
        "system_prompt_tokens": 32, "system_prompt_counts": [4, 2],
        "pairing_seed": 5, "page_size": 16, "run_in_completed": 9,
        "new_tokens": {"dist": "uniform", "min": 6, "max": 30},
        "answer": {"dist": "uniform", "min": 8, "max": 40}},
    "workloads/tiny-agents.json": {
        "name": "tiny-agents", "config": "tiny-solar",
        "traffic": "tiny-agents", "chips": 1, "why": "test"},
}


@pytest.fixture()
def solar_overlay(overlay):
    for rel, obj in TINY_SOLAR.items():
        (overlay / rel).write_text(json.dumps(obj))
    TINY_MANIFEST["workloads"].append({"name": "tiny-agents"})
    TINY_MANIFEST["end_to_end"][2]["workloads"].append("tiny-agents")
    yield overlay
    TINY_MANIFEST["workloads"].pop()
    TINY_MANIFEST["end_to_end"][2]["workloads"].pop()


def test_solar_cell_drives_and_is_correct(solar_overlay):
    from harness import program_spans
    from paddle_tpu import observability as obs

    obs.enable()       # the program's spans record, as under a traced run
    try:
        out, r = drive_tiny("tiny-agents", seconds=2.0)
        steps = [a for _, _, n, a in program_spans.ring()
                 if n == "serving/decode" and "local_rows" in a]
    finally:
        obs.disable()
        obs.reset()
    assert out["correct"] is True, r.compared
    assert out["metrics"]["latency_per_tok_p50_ms"]["value"] > 0
    assert out["attempted"] >= 1 and r.counters["steps"]
    assert r.counters["prompt_tokens_hit"] > 0
    # after run-in every admission restores a branch snapshot
    assert r.counters["admit_prompt_tokens"] > 0
    assert r.counters["admit_recomputed_tokens"] \
        < 0.2 * r.counters["admit_prompt_tokens"]
    # the share's counts: 4 of 16 experts held, a quarter of the rows or so
    assert steps and all(a["routed_rows"] == [6 * 4] * 4 for a in steps)
    local = sum(map(sum, (a["local_rows"] for a in steps)))
    routed = sum(map(sum, (a["routed_rows"] for a in steps)))
    assert 0.05 < local / routed < 0.6
    assert all(t <= 4 for a in steps for t in a["experts_touched"])
    assert not any("compile request" in f or "cache_full" in f
                   for f in r.failures), r.failures


def test_single_turn_buckets():
    from harness.run_serve_solar import program_buckets

    c, t = (TINY_SOLAR["configs/tiny-solar.json"],
            TINY_SOLAR["traffic/tiny-agents.json"])
    assert program_buckets(c, t) == ([32, 64], [16])
    big = {"engine": {"prefill_buckets": [16, 128, 256, 512, 1024]}}
    agents = {"turns": 1, "system_prompt_tokens": 512, "page_size": 16,
              "new_tokens": {"max": 512}}
    assert program_buckets(big, agents) == ([512, 1024], [16, 128, 256, 512])


def test_reference_layer_at_rows_is_those_rows_of_the_layer():
    import jax
    import jax.numpy as jnp

    from harness import solar_weights
    from harness.run_serve_solar import decoder_config, reference_config
    from paddle_tpu.models.decoder import param_shapes
    from reference import solar_open2 as ref

    c = TINY_SOLAR["configs/tiny-solar.json"]
    shapes = param_shapes(decoder_config(c, init="zeros"))
    assert shapes["layers.1.ffn.w1"] == (4, 64, 32)      # the held
    assert shapes["layers.1.ffn.router"] == (64, 16)     # all of them
    w = solar_weights.make(7, shapes, 0.02, "bfloat16")
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 64), jnp.float32)
    rc = reference_config(c)
    assert rc["experts_held"] == (4, 4)                  # chip 1 of 4
    rows = jnp.asarray([95, 3, 40, 41, 42, 17, 0, 64])
    for l, kind in ((0, "full_attention"), (1, "linear_attention")):
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        whole = ref.layer(x, p, kind, rc, q_block=16)
        some = ref.layer(x, p, kind, rc, q_block=4, rows=rows)
        np.testing.assert_allclose(np.asarray(some), np.asarray(whole)[rows],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("reader", [
    "experts_touched_share", "local_rows_share", "moe_experts_roofline",
    "kda_decode_roofline", "kda_chunk_roofline", "paged_decode_roofline"])
def test_readers_give_none_where_there_is_nothing_to_read(reader):
    """An untraced run, and a configuration of another kind, read None."""
    from harness import readers_solar

    class Run:
        trace = trace_host = None
        config = {"hidden_size": 64}
        counters = {}

    assert getattr(readers_solar, reader)(Run()) is None


def test_roofline_counts():
    from roofline import kda_chunk, kda_step, paged_decode_gqa

    # a slot's state once in and once out, its rows beside it
    w = kda_step.call(1, 64, 128, 128)
    assert w["bytes"] == (2 * 64 * 128 * 128 + 64 * (5 * 128 + 1)) * 4
    assert w["flops"] == 7.0 * 64 * 128 * 128
    # K and V at their stored width, FLOPs by the query heads
    g = paged_decode_gqa.call(1000, 64, 8, 128)
    assert g["bytes"] == 2 * 1000 * 8 * 128 * 2
    assert g["flops"] == 4.0 * 1000 * 64 * 128
    # more tokens cost more, a smaller chunk fewer score terms a token
    a, b = kda_chunk.call(512, 1, 64, 128, 128, 16), \
        kda_chunk.call(512, 1, 64, 128, 128, 32)
    assert a["flops"] < b["flops"] and a["bytes"] == b["bytes"]
