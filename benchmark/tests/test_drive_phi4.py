"""Drives the ``serve_phi4`` runner (a decoder-hybrid-decoder: Mamba-1 layers
on slot state beside sliding differential attention on a page group of its
own, ONE full layer's K/V pool read by the cross layers, gated memory units,
the last-token cut) on the CPU at a tiny size, from an overlay of new files:
the whole run, multi-turn sessions that ``stagger_start`` opens part-way
included, comes out correct against ``reference/phi4_mini_flash.py``; the
new counts come out of the program's spans; the new readers and roofline
functions read a recorded run; and the configuration file is held to the
catalog's row."""

import importlib.util
import json
import os
import sys

import pytest


def _bench_conftest():
    """``benchmark/tests/conftest.py`` (``test_drive_mamba._bench_conftest``)."""
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

CELL = "phi4-mini-flash-serve.json"
WORKLOAD = "serve-phi4-flash-agentloop15k"
TINY_PHI4 = {
    "configs/tiny-phi4.json": {
        "name": "tiny-phi4", "runner": "serve_phi4", "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 96, "layer_norm_eps": 1e-5,
        "mb_per_layer": 2, "num_attention_heads": 8, "num_hidden_layers": 8,
        "num_key_value_heads": 4, "sliding_window": 24,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 128,
        "assumed_sizes": {"mamba_inner": 128, "mamba_state": 8,
                          "mamba_conv": 4, "mamba_dt_rank": 4},
        "initializer_range": 0.02, "dtype": "bfloat16",
        "program": {"query_chunk": 32},
        "engine": {"max_batch_size": 4, "max_seq_len": 256, "page_size": 16,
                   "kv_pages": 80, "group_pages": {"window": 60},
                   "state_snapshots": 10, "prefix_cache": True,
                   "speculative": None,
                   "prefill_buckets": [16, 32, 64, 128, 256]},
        "check": {"sample_requests": 3, "q_block": 32,
                  "limits": {"served_gap_mean": 0.02,
                             "served_gap_widest": 0.5}}},
    "traffic/tiny-loop.json": {
        "kind": "sessions", "live_sessions": 4, "turns": 3,
        "system_prompt_tokens": 32, "system_prompt_counts": [2, 2],
        "pairing_seed": 5, "page_size": 16, "stagger_start": True,
        "run_in_completed": 6,
        "new_tokens": {"dist": "uniform", "min": 6, "max": 24},
        "answer": {"dist": "uniform", "min": 8, "max": 30}},
    "workloads/tiny-loop.json": {
        "name": "tiny-loop", "config": "tiny-phi4", "traffic": "tiny-loop",
        "chips": 1, "why": "test"},
}


@pytest.fixture()
def phi4_overlay(overlay):
    for rel, obj in TINY_PHI4.items():
        (overlay / rel).write_text(json.dumps(obj))
    TINY_MANIFEST["workloads"].append({"name": "tiny-loop"})
    TINY_MANIFEST["end_to_end"][2]["workloads"].append("tiny-loop")
    yield overlay
    TINY_MANIFEST["workloads"].pop()
    TINY_MANIFEST["end_to_end"][2]["workloads"].pop()


def test_phi4_cell_drives_and_is_correct(phi4_overlay):
    from harness import program_spans
    from paddle_tpu import observability as obs

    obs.clear_spans()
    obs.enable()       # the program's spans record, as under a traced run
    try:
        out, r = drive_tiny("tiny-loop", seconds=3.0)
        ring = list(program_spans.ring())
    finally:
        obs.disable()
        obs.reset()
        obs.clear_spans()
    assert out["correct"] is True, r.compared
    assert out["metrics"]["latency_per_tok_p50_ms"]["value"] > 0
    assert out["attempted"] >= 1 and r.counters["steps"]
    assert r.counters["prompt_tokens_hit"] > 0
    # every admission of the window entered the cross-decoder with ONE row
    assert r.counters["admit_cross_rows"] >= 1
    assert r.counters["admit_cross_rows"] * 8 < r.counters["admit_prompt_rows"]
    # turns resume behind the last turn's snapshot AND its window
    assert r.counters["admit_recomputed_tokens"] \
        < 0.5 * r.counters["admit_prompt_tokens"]
    assert r.counters["window_pages"] == 59  # 60 less the trash page
    steps = [a for _, _, n, a in ring
             if n == "serving/decode" and "shared_read" in a]
    assert steps
    for a in steps:     # layers 5 (full) and 7 (cross) read the one pool
        assert [x > 0 for x in a["shared_read"]] == [
            l in (5, 7) for l in range(8)] or a["running"] == 0
        assert a["ssm_slots_stepped"] == [
            a["running"] if l in (0, 2, 4) else 0 for l in range(8)]
    assert not any("compile request" in f or "cache_full" in f
                   for f in r.failures), r.failures


def test_buckets_and_traffic_follow_the_issue():
    from harness.run_serve_phi4 import layer_plan, program_buckets

    c = common.load_json("configs", CELL)
    t = common.load_json("traffic", "reason-agent-loop-15k.json")
    assert (t["live_sessions"], t["turns"], t["system_prompt_tokens"]) \
        == (48, 8, 2048)
    assert t["system_prompt_counts"] == [16, 16, 8, 8]
    assert t["new_tokens"] == {"dist": "uniform", "min": 128, "max": 512}
    assert t["answer"] == {"dist": "uniform", "min": 384, "max": 1152}
    assert t["stagger_start"] and t["pairing_seed"] == 20261005
    assert t["run_in_completed"] == 48 and t["page_size"] == 16
    assert c["engine"]["max_batch_size"] == t["live_sessions"]
    longest = 2048 + 8 * (512 + 1152)
    assert longest == 15360 < c["engine"]["max_seq_len"]
    assert c["engine"]["max_seq_len"] % c["check"]["q_block"] == 0
    prefill, extend = program_buckets(c, t)
    assert prefill == [4096, 8192, 14336]
    assert extend == [16, 128, 256, 512, 1024, 1792, 4096, 8192, 14336]
    plan = layer_plan(c)
    assert [k for k, _ in plan[14:20]] == ["mamba", "sliding", "mamba",
                                           "full", "gmu", "cross"]
    assert plan[18][1] == 16 and plan[19][1] == 17 and plan[31] == ("cross", 17)


def test_built_description_is_the_counted_model():
    import numpy as np

    from harness.run_serve_phi4 import decoder_config
    from paddle_tpu.models.decoder import param_shapes

    c = common.load_json("configs", CELL)
    cfg = decoder_config(c, init="zeros")
    shapes = param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 3_852_562_944
    assert cfg.cut_layer == 17
    assert shapes["layers.16.attn.A_log"] == (16, 5120)
    assert "layers.19.attn.wk" not in shapes and "layers.19.attn.wq" in shapes


def test_roofline_counts():
    from roofline import mamba1_scan, mamba1_step, shared_kv_decode

    # a token of the shared pool: 5,120 B a reading layer
    w = shared_kv_decode.call(1000, 40, 10, 128)
    assert w["bytes"] == 1000 * 5120
    # a stepped slot: its 320 KiB state in and out, and its rows
    w = mamba1_step.call(1, 5120, 16)
    assert w["bytes"] == 2 * 320 * 1024 + (3 * 5120 + 32) * 4
    w = mamba1_scan.call(1792, 1, 5120, 16)
    assert w["bytes"] == 1792 * (3 * 5120 + 32) * 4 + 2 * 320 * 1024
    t, bound = mamba1_scan.min_seconds(
        w, {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "memory"


@pytest.mark.parametrize("reader", [
    "shared_kv_decode_roofline", "shared_kv_read_share",
    "window_decode_roofline", "mamba1_step_roofline", "mamba1_scan_roofline",
    "cross_rows_share"])
def test_readers_give_none_where_there_is_nothing_to_read(reader):
    """On a program without the spans and counters (the parent), and on a
    configuration of another kind, every new reader returns None."""
    from harness import readers_phi4

    class Run:
        trace = trace_host = None
        counters = {}
        config = {"hidden_size": 8}

    assert getattr(readers_phi4, reader)(Run()) is None
    import run as bench_run
    assert callable(bench_run.reader(reader + ".loop"))


def test_configuration_file_keeps_the_published_keys():
    c = common.load_json("configs", CELL)
    rows = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl") \
        else []
    row = [r for r in rows if r["name"] == "Phi-4-mini-flash-reasoning"]
    published = row[0]["config"] if row else {
        "hidden_size": 2560, "num_hidden_layers": 32,
        "num_attention_heads": 40, "num_key_value_heads": 20,
        "intermediate_size": 10240, "sliding_window": 512,
        "vocab_size": 200064, "mb_per_layer": 2}
    for k, v in published.items():
        assert c[k] == v, k
    if row:
        assert c["source"] == row[0]["source_url"]
    assert c["reduced"] == []
    assert set(c["assumed"]) >= {"positions", "mamba1", "differential",
                                 "attention_bias", "window", "lambda_init",
                                 "weights"}
    need = max(b["need"] for b in c["fit"]["bytes"].values())
    assert 0.70 < need / (15.75 * 2**30) < 0.92
