"""Drives the ``serve_hybrid`` runner (gated delta-rule layers beside full
attention) on the CPU at a tiny size, from an overlay of new files: the whole
run comes out correct against ``reference/olmo_hybrid.py``, the snapshot
rule's counts come out of the program's spans, and the reference's last
layer asked for some rows equals those rows of the whole layer."""

import json

import numpy as np
import pytest

from conftest import TINY_MANIFEST, drive_tiny

KINDS = ["linear_attention", "linear_attention", "linear_attention",
         "full_attention"]
TINY_HYBRID = {
    "configs/tiny-hybrid.json": {
        "name": "tiny-hybrid", "runner": "serve_hybrid",
        "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "layer_types": KINDS,
        "linear_num_key_heads": 4, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 16,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
        "initializer_range": 0.02, "dtype": "bfloat16",
        "program": {"query_chunk": 32, "gdn_chunk": 16},
        "engine": {"max_batch_size": 5, "max_seq_len": 128, "page_size": 16,
                   "kv_pages": 60, "state_snapshots": 10,
                   "prefix_cache": True, "speculative": None,
                   "prefill_buckets": [16, 32, 64, 128]},
        "check": {"sample_requests": 3, "q_block": 16,
                  "limits": {"served_gap_mean": 0.02,
                             "served_gap_widest": 0.5}}},
    "traffic/tiny-sessions.json": {
        "kind": "sessions", "live_sessions": 5, "turns": 3,
        "system_prompt_tokens": 40, "system_prompt_counts": [3, 2],
        "pairing_seed": 3, "page_size": 16, "run_in_completed": 10,
        "stagger_start": True,
        "new_tokens": {"dist": "uniform", "min": 6, "max": 12},
        "answer": {"dist": "uniform", "min": 4, "max": 10}},
    "workloads/tiny-sessions.json": {
        "name": "tiny-sessions", "config": "tiny-hybrid",
        "traffic": "tiny-sessions", "chips": 1, "why": "test"},
}


@pytest.fixture()
def hybrid_overlay(overlay):
    for rel, obj in TINY_HYBRID.items():
        (overlay / rel).write_text(json.dumps(obj))
    TINY_MANIFEST["workloads"].append({"name": "tiny-sessions"})
    TINY_MANIFEST["end_to_end"][2]["workloads"].append("tiny-sessions")
    yield overlay
    TINY_MANIFEST["workloads"].pop()
    TINY_MANIFEST["end_to_end"][2]["workloads"].pop()


def test_hybrid_cell_drives_and_is_correct(hybrid_overlay):
    from paddle_tpu import observability as obs

    obs.enable()       # the program's spans record, as under a traced run
    try:
        out, r = drive_tiny("tiny-sessions", seconds=2.0)
    finally:
        obs.disable()
        obs.reset()
    assert out["correct"] is True, r.compared
    assert out["metrics"]["latency_per_tok_p50_ms"]["value"] > 0
    assert out["attempted"] >= 1 and r.counters["steps"]
    assert r.counters["prompt_tokens_hit"] > 0
    # after run-in every admission resumes from a snapshot, and what it ran
    # again for want of one is a small part of what it was sent
    assert r.counters["admit_prompt_tokens"] > 0
    assert r.counters["admit_recomputed_tokens"] \
        < 0.2 * r.counters["admit_prompt_tokens"]
    assert 0 < r.counters["snapshots_held"] <= r.counters["snapshots_capacity"]
    # every program the traffic reached was compiled in set-up
    assert not any("compile request" in f or "cache_full" in f
                   for f in r.failures), r.failures


def test_reference_layer_at_rows_is_those_rows_of_the_layer():
    import jax
    import jax.numpy as jnp

    from harness import hybrid_weights
    from harness.run_serve_hybrid import decoder_config, reference_config
    from paddle_tpu.models.decoder import param_shapes
    from reference import olmo_hybrid as ref

    c = TINY_HYBRID["configs/tiny-hybrid.json"]
    shapes = param_shapes(decoder_config(c, init="zeros"))
    w = hybrid_weights.make(7, shapes, 0.02, "bfloat16")
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 64), jnp.float32)
    rc = reference_config(c)
    rows = jnp.asarray([95, 3, 40, 41, 42, 17, 0, 64])
    for l, kind in ((0, "linear_attention"), (3, "full_attention")):
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}
        whole = ref.layer(x, p, kind, rc, q_block=16)
        some = ref.layer(x, p, kind, rc, q_block=4, rows=rows)
        np.testing.assert_allclose(np.asarray(some), np.asarray(whole)[rows],
                                   rtol=1e-5, atol=1e-6)


def test_weight_rule_knows_the_new_leaves():
    import jax.numpy as jnp

    from harness import hybrid_weights as hw

    shapes = {"layers.0.attn.A_log": (512,), "layers.0.attn.dt_bias": (512,),
              "layers.0.attn.conv.weight": (256, 4),
              "layers.0.attn.o_norm.weight": (512,),
              "layers.0.attn.wq": (64, 64)}
    assert [hw.kind(n) for n in sorted(shapes)] == [
        "a_log", "conv", "dt_bias", "scale", "normal"]
    w = {k: v.astype(jnp.float32)
         for k, v in hw.make(2**31 + 5, shapes, 0.02, "bfloat16").items()}
    a = jnp.exp(w["layers.0.attn.A_log"])
    assert 0 < float(a.min()) and 14 < float(a.max()) <= 16.1
    dt = jnp.log1p(jnp.exp(w["layers.0.attn.dt_bias"]))
    assert 0.9e-3 < float(dt.min()) and float(dt.max()) < 0.11
    assert 0.4 < float(jnp.abs(w["layers.0.attn.conv.weight"]).max()) <= 0.5
    assert abs(float(w["layers.0.attn.o_norm.weight"].mean()) - 1) < 0.05
    assert float(jnp.abs(w["layers.0.attn.wq"]).max()) < 0.12
    again = hw.make(2**31 + 5, shapes, 0.02, "bfloat16", ["layers.0.attn.wq"])
    assert bool((again["layers.0.attn.wq"].astype(jnp.float32)
                 == w["layers.0.attn.wq"]).all())
