"""CPU tests of the benchmark's own code (`python -m pytest benchmark/tests`).
Nothing here is a device measurement."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_MODEL = {"vocab_size": 128, "hidden_size": 64, "num_layers": 2,
              "num_heads": 4, "head_dim": 16, "intermediate_size": 256,
              "max_seq_len": 128, "layer_norm_eps": 1e-5,
              "initializer_range": 0.02, "tie_word_embeddings": True,
              "dtype": "bfloat16"}

TINY = {
    "configs/tiny-train.json": {
        "name": "tiny-train", "runner": "train", "model": TINY_MODEL,
        "parallel": {"dp_degree": 1, "mp_degree": 1},
        "optimizer": {"learning_rate": 2e-4, "beta1": 0.9, "beta2": 0.95,
                      "epsilon": 1e-8, "weight_decay": 0.1,
                      "moment_dtype": "bfloat16"},
        "runner_settings": {"use_recompute": True, "loss_chunk": 16},
        "check": {"program_steps": 3, "reference_steps": 2,
                  "rows_per_block": 2,
                  "limits": {"loss_abs": 0.02, "grad_norm_rel": 0.1,
                             "delta_norm_rel": 0.5}}},
    "configs/tiny-serve.json": {
        "name": "tiny-serve", "runner": "serve", "model": TINY_MODEL,
        "engine": {"max_batch_size": 4, "max_seq_len": 128, "page_size": 16,
                   "kv_pages": 40, "prefix_cache": True, "speculative": None,
                   "prefill_buckets": [32, 64, 128]},
        "check": {"sample_requests": 60, "limits": {"served_gap_mean": 1e-5, "served_gap_widest": 0.05}}},
    "traffic/tiny-stream.json": {"kind": "stream", "batch": 4, "seq_len": 64},
    "traffic/tiny-chat.json": {
        "kind": "open_loop", "rate_per_s": 40.0, "cycle_requests": 8,
        "pairing_seed": 3, "run_in_requests": 8,
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                   "min": 8, "max": 60},
        "answer": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 4, "max": 16}},
    "traffic/tiny-sessions.json": {
        "kind": "sessions", "live_sessions": 5, "turns": 2,
        "system_prompt_tokens": 32, "system_prompt_counts": [2, 1],
        "pairing_seed": 3, "page_size": 16, "run_in_completed": 6,
        "new_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "answer": {"dist": "uniform", "min": 4, "max": 12}},
    "workloads/tiny-train.json": {
        "name": "tiny-train", "config": "tiny-train",
        "traffic": "tiny-stream", "chips": 1, "why": "test"},
    "workloads/tiny-chat.json": {
        "name": "tiny-chat", "config": "tiny-serve", "traffic": "tiny-chat",
        "chips": 1, "why": "test"},
    "workloads/tiny-sessions.json": {
        "name": "tiny-sessions", "config": "tiny-serve",
        "traffic": "tiny-sessions", "chips": 1, "why": "test"},
}

TINY_MANIFEST = {
    "workloads": [{"name": n} for n in
                  ("tiny-train", "tiny-chat", "tiny-sessions")],
    "end_to_end": [
        {"name": "train_tok_s_chip", "unit": "tokens/s/chip",
         "workloads": ["tiny-train"]},
        {"name": "serve_out_tok_s", "unit": "tokens/s",
         "workloads": ["tiny-sessions"]},
        {"name": "latency_per_tok_p50_ms", "unit": "ms",
         "workloads": ["tiny-chat"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "tiny_steps.train", "unit": "steps",
         "workloads": ["tiny-train"]}],
}


def drive_tiny(cell, seed=5, seconds=1.0, trace=0, with_control=False):
    """Everything of a run but the look for a chip, on the CPU: (result
    object, the Run)."""
    import time

    import jax

    import run as bench_run
    from harness import common

    r = common.Run(cell, seed, seconds, trace, time.perf_counter())
    r.devices = jax.devices()[:1]
    r.dev_tag = "cpu test"
    r.peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    r.with_control = with_control
    out = bench_run.drive(r, TINY_MANIFEST)
    json.dumps(out)
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    return out, r


@pytest.fixture()
def overlay(tmp_path):
    """A directory of NEW files only (a configuration, traffic mixes, cells
    and a per-layer metric), found by name beside the committed ones: what
    a later PR adds, with no edit to a file that is there."""
    from harness import common

    for rel, obj in TINY.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))
    lm = tmp_path / "layer_metrics"
    lm.mkdir()
    (lm / "tiny_steps.train.py").write_text(
        "def read(run):\n    return float(run.counters['steps'])\n")
    common.SEARCH.append(str(tmp_path))
    yield tmp_path
    common.SEARCH.remove(str(tmp_path))
