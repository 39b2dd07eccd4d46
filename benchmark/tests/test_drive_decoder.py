"""Drives the ``serve_decoder`` runner (the second architecture's) on the CPU
at a tiny size, from an overlay of new files: the whole run comes out
correct against ``reference/keye_vl2.py``, and the reference's last layer
asked for some rows equals those rows of the whole layer."""

import json

import numpy as np
import pytest

from conftest import TINY_MANIFEST, drive_tiny

TINY_DECODER = {
    "configs/tiny-decoder.json": {
        "name": "tiny-decoder", "runner": "serve_decoder",
        "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 16,
                      "topk": 16},
        "moe_intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": True,
        "tie_word_embeddings": False, "initializer_range": 0.02,
        "dtype": "bfloat16",
        "engine": {"max_batch_size": 5, "max_seq_len": 128, "page_size": 16,
                   "kv_pages": 60, "prefix_cache": True, "speculative": None,
                   "prefill_buckets": [16, 32, 64, 128]},
        "check": {"sample_requests": 3, "q_block": 16,
                  "limits": {"served_gap_mean": 0.02,
                             "served_gap_widest": 0.5}}},
    "traffic/tiny-docs.json": {
        "kind": "sessions", "live_sessions": 5, "turns": 2,
        "system_prompt_tokens": 32, "system_prompt_counts": [2, 1],
        "pairing_seed": 3, "page_size": 16, "run_in_completed": 6,
        "stagger_start": True,
        "new_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "answer": {"dist": "uniform", "min": 4, "max": 12}},
    "workloads/tiny-docs.json": {
        "name": "tiny-docs", "config": "tiny-decoder",
        "traffic": "tiny-docs", "chips": 1, "why": "test"},
}


@pytest.fixture()
def decoder_overlay(overlay):
    for rel, obj in TINY_DECODER.items():
        (overlay / rel).write_text(json.dumps(obj))
    TINY_MANIFEST["workloads"].append({"name": "tiny-docs"})
    TINY_MANIFEST["end_to_end"][2]["workloads"].append("tiny-docs")
    yield overlay
    TINY_MANIFEST["workloads"].pop()
    TINY_MANIFEST["end_to_end"][2]["workloads"].pop()


def test_decoder_cell_drives_and_is_correct(decoder_overlay):
    out, r = drive_tiny("tiny-docs", seconds=2.0)
    assert out["correct"] is True, r.compared
    assert out["metrics"]["latency_per_tok_p50_ms"]["value"] > 0
    assert out["attempted"] >= 1 and r.counters["steps"]
    assert r.counters["prompt_tokens_hit"] > 0
    assert r.counters["cold_admissions"] == 0
    # every program the traffic reached was compiled in set-up
    assert not any("compile request" in f for f in r.failures), r.failures


def test_reference_layer_at_rows_is_those_rows_of_the_layer():
    import jax
    import jax.numpy as jnp

    from harness import decoder_weights
    from harness.run_serve_decoder import decoder_config, reference_config
    from paddle_tpu.models.decoder import param_shapes
    from reference import keye_vl2 as ref

    c = TINY_DECODER["configs/tiny-decoder.json"]
    shapes = param_shapes(decoder_config(c, init="zeros"))
    w = decoder_weights.make(7, shapes, 0.02, "bfloat16")
    p = {k[len("layers.0."):]: v for k, v in w.items()
         if k.startswith("layers.0.")}
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 64), jnp.float32)
    rc = reference_config(c)
    whole = ref.layer(x, p, rc, q_block=16)
    rows = jnp.asarray([95, 3, 40, 41, 42, 17, 0, 64])
    some = ref.layer(x, p, rc, q_block=4, rows=rows)
    np.testing.assert_allclose(np.asarray(some), np.asarray(whole)[rows],
                               rtol=1e-5, atol=1e-6)
