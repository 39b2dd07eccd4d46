"""Serving engine (paddle_tpu.serving): static-shape KV-cache decode +
continuous batching.

Covers: cached decode logits match the full-prefix causal forward (MHA and
GQA, fp32 tolerance), GPTForCausalLM.generate parity with the grown-prefix
reference loop plus the ONE-prefill/ONE-decode compile regression (the old
generate recompiled every emitted token), continuous-batching admission the
moment a slot frees mid-run, per-request eos / max_new_tokens / cache_full
termination, per-row batched sampling, and the flag-gated serving metrics
(present under FLAGS_observability, zero registry writes when off).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.serving import (Engine, SamplingParams, Scheduler,
                                decode_attend, write_kv)
from paddle_tpu.serving.sampling import sample_batched


@pytest.fixture
def telemetry():
    """Flag on + clean registry, restored to off+empty afterwards."""
    obs.enable()
    obs.reset()
    yield obs
    obs.disable()
    obs.reset()


def _tiny(**kw):
    m = gpt_tiny(dropout=0.0, num_layers=2, **kw)
    m.eval()
    return m


def _prompt(B, S, vocab=128, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, (B, S)).astype(np.int32)


# ---------------- decode core: parity with the full-prefix forward --------
class TestDecodeParity:
    @pytest.mark.parametrize("num_kv_heads", [None, 2],
                             ids=["mha", "gqa"])
    def test_decode_step_matches_full_forward(self, num_kv_heads):
        """Prefill [0, S0) then decode positions S0..S-1 one token at a
        time; every step's logits must match the causal forward over the
        grown prefix within fp32 tolerance."""
        kw = {} if num_kv_heads is None else {"num_kv_heads": num_kv_heads}
        m = _tiny(**kw)
        cfg = m.cfg
        B, S0, S = 2, 5, 9
        x = _prompt(B, S)
        full = np.asarray(m.forward(paddle.to_tensor(x))._value)  # [B, S, V]

        S_max = S + 1
        logits, kvs = m.prefill_with_cache(paddle.to_tensor(x[:, :S0]))
        np.testing.assert_allclose(np.asarray(logits._value),
                                   full[:, S0 - 1], rtol=1e-4, atol=1e-5)
        caches = []
        for k, v in kvs:
            kc = write_kv(jnp.zeros((B, cfg.num_kv_heads, S_max, cfg.head_dim),
                                    k._value.dtype), k._value, jnp.int32(0))
            vc = write_kv(jnp.zeros((B, cfg.num_kv_heads, S_max, cfg.head_dim),
                                    v._value.dtype), v._value, jnp.int32(0))
            caches.append((kc, vc))
        for t in range(S0, S):
            pos = jnp.full((B,), t, jnp.int32)
            logits, caches = m.decode_step(
                paddle.to_tensor(x[:, t]), caches, pos)
            caches = [(k._value, v._value) for k, v in caches]
            np.testing.assert_allclose(np.asarray(logits._value), full[:, t],
                                       rtol=1e-4, atol=1e-5)

    def test_decode_attend_masks_beyond_position(self):
        """Entries past each row's position must not leak into attention —
        the property that makes padded prefill buckets and freed-slot reuse
        safe."""
        B, H, S_max, D = 2, 2, 8, 4
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(B, H, 1, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, H, S_max, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, H, S_max, D)).astype(np.float32))
        pos = jnp.asarray([2, 5], jnp.int32)
        base = decode_attend(q, k, v, pos)
        poisoned_k = k.at[0, :, 3:].set(1e3).at[1, :, 6:].set(1e3)
        poisoned_v = v.at[0, :, 3:].set(1e3).at[1, :, 6:].set(1e3)
        np.testing.assert_allclose(
            np.asarray(decode_attend(q, poisoned_k, poisoned_v, pos)),
            np.asarray(base), rtol=1e-6)

    @pytest.mark.parametrize("x64", [True, False], ids=["x64_on", "x64_off"])
    def test_decode_attend_q_scale_stays_f32(self, x64):
        """The 1/sqrt(D) scale is a q-dtype scalar, never a strong f64:
        under x64 a bare `np.sqrt` scalar upcast the whole score tensor to
        f64 before the cast back (doubled decode flops and wire — caught by
        the analyzer's dtype-f64 rule, fixed by the jnp.asarray pin). Both
        x64 modes must trace an f64-free program with an f32 result."""
        with jax.enable_x64(x64):
            B, H, S_max, D = 2, 2, 8, 4
            q = jnp.ones((B, H, 1, D), jnp.float32)
            k = jnp.ones((B, H, S_max, D), jnp.float32)
            v = jnp.ones((B, H, S_max, D), jnp.float32)
            pos = jnp.asarray([2, 5], jnp.int32)
            out = decode_attend(q, k, v, pos)
            assert out.dtype == jnp.float32
            jaxpr = jax.make_jaxpr(decode_attend)(q, k, v, pos)
            assert "f64" not in str(jaxpr), str(jaxpr)


# ---------------- generate(): parity + the one-compile regression ---------
class TestGenerate:
    @pytest.mark.slow
    def test_generate_matches_grown_prefix_reference(self):
        """Greedy generate on the KV-cache core must reproduce the old
        grown-prefix loop token for token (it is exact, not approximate)."""
        m = _tiny(num_kv_heads=2)
        x = _prompt(2, 8)
        ref = jnp.asarray(x)
        for _ in range(5):
            logits = m.forward(paddle.to_tensor(np.asarray(ref)))._value[:, -1]
            nxt = jnp.argmax(logits, axis=-1).astype(ref.dtype)
            ref = jnp.concatenate([ref, nxt[:, None]], axis=1)
        out = m.generate(paddle.to_tensor(x), max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(out._value), np.asarray(ref))

    def test_generate_compiles_once_for_prefill_and_once_for_decode(
            self, telemetry):
        """THE regression the serving core exists for: N>4 generated tokens
        must cost exactly one prefill compile + one decode compile — the old
        implementation recompiled the forward at every grown prefix
        length."""
        m = _tiny()
        x = _prompt(2, 8)
        m.generate(paddle.to_tensor(x), max_new_tokens=6)
        c = obs.snapshot()["counters"]
        assert c["jit.compile.cache_miss{site=serving.prefill}"] == 1
        assert c["jit.compile.cache_miss{site=serving.decode}"] == 1
        # same shapes again: both executables come from the cache
        m.generate(paddle.to_tensor(x), max_new_tokens=6)
        c = obs.snapshot()["counters"]
        assert c["jit.compile.cache_miss{site=serving.prefill}"] == 1
        assert c["jit.compile.cache_miss{site=serving.decode}"] == 1
        assert c["jit.compile.cache_hit{site=serving.prefill}"] == 1
        assert c["jit.compile.cache_hit{site=serving.decode}"] == 1

    def test_generate_eos_fill_semantics(self):
        """A finished row keeps emitting eos (forced-eos fill), and the loop
        stops early once every row is finished — the old API contract."""
        m = _tiny()
        x = _prompt(2, 6, seed=3)
        free = m.generate(paddle.to_tensor(x), max_new_tokens=4)
        eos = int(np.asarray(free._value)[0, 6])  # row 0 finishes at step 1
        out = np.asarray(m.generate(paddle.to_tensor(x), max_new_tokens=4,
                                    eos_token_id=eos)._value)
        row0 = out[0, 6:]
        assert row0[0] == eos and (row0 == eos).all()


# ---------------- engine: continuous batching -----------------------------
class TestEngine:
    def test_offline_generate_matches_model_generate(self):
        m = _tiny(num_kv_heads=2)
        prompts = [[5, 17, 3], [9, 2, 11, 4]]
        eng = Engine(m, max_batch_size=2, max_seq_len=32)
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        for p, o in zip(prompts, outs):
            ids = paddle.to_tensor(np.asarray([p], np.int32))
            ref = np.asarray(m.generate(ids, max_new_tokens=6)._value)
            assert o == list(ref[0, len(p):])

    def test_admission_when_slot_frees_mid_run(self):
        """3 requests, 2 slots: the third stays queued until a short request
        finishes, then is admitted between decode steps — continuous
        batching, not drain-and-refill."""
        m = _tiny()
        eng = Engine(m, max_batch_size=2, max_seq_len=32)
        r1 = eng.add_request([5, 17, 3], SamplingParams(max_new_tokens=2))
        r2 = eng.add_request([9, 2, 4], SamplingParams(max_new_tokens=8))
        r3 = eng.add_request([7, 7, 7], SamplingParams(max_new_tokens=3))
        eng.step()  # admits r1+r2 (prefill = token 1), LAUNCHES decode step 1
        assert [r.num_generated for r in (r1, r2)] == [1, 1]
        assert r1.state == "running" and eng._flight is not None
        eng.step()  # launches step 2 (for r2 alone), settles step 1: r1 done
        assert r1.state == "finished" and r1.finish_reason == "length"
        assert [r.num_generated for r in (r1, r2)] == [2, 2]
        assert r3.state == "queued"
        eng.step()  # r1's slot is free -> r3 admitted this step
        assert r3.state == "running" and r3.slot == r1.slot
        while eng.has_unfinished:
            eng.step()
        assert [len(r.output_ids) for r in (r1, r2, r3)] == [2, 8, 3]
        assert {r.finish_reason for r in (r1, r2, r3)} == {"length"}

    def test_per_request_eos_and_length_termination(self):
        m = _tiny()
        eng = Engine(m, max_batch_size=2, max_seq_len=32)
        probe = eng.generate([[5, 17, 3]], SamplingParams(max_new_tokens=3))
        eos = probe[0][-1]  # appears somewhere in the greedy continuation
        stop = probe[0].index(eos) + 1  # first occurrence ends the request
        r_eos = eng.add_request([5, 17, 3],
                                SamplingParams(max_new_tokens=8,
                                               eos_token_id=eos))
        r_len = eng.add_request([9, 2, 4], SamplingParams(max_new_tokens=4))
        while eng.has_unfinished:
            eng.step()
        assert r_eos.finish_reason == "eos"
        assert r_eos.output_ids == probe[0][:stop]
        assert r_len.finish_reason == "length"
        assert len(r_len.output_ids) == 4

    def test_cache_full_termination_and_prompt_validation(self):
        m = _tiny()
        eng = Engine(m, max_batch_size=1, max_seq_len=12)
        r = eng.add_request(list(range(1, 9)), SamplingParams(max_new_tokens=50))
        while eng.has_unfinished:
            eng.step()
        assert r.finish_reason == "cache_full"
        assert len(r.prompt_ids) + len(r.output_ids) == 12
        with pytest.raises(ValueError):
            eng.add_request(list(range(12)))  # no room to generate

    def test_mixed_sampling_one_decode_compile(self, telemetry):
        """Greedy and sampled requests share the single decode executable:
        sampling params ride as arrays, not compile-time constants."""
        m = _tiny()
        eng = Engine(m, max_batch_size=2, max_seq_len=32)
        paddle.seed(7)
        outs = eng.generate(
            [[5, 17, 3], [9, 2, 4], [8, 1, 6]],
            [SamplingParams(max_new_tokens=4),
             SamplingParams(max_new_tokens=4, do_sample=True,
                            temperature=0.7, top_k=5),
             SamplingParams(max_new_tokens=4, do_sample=True)])
        assert all(len(o) == 4 for o in outs)
        c = obs.snapshot()["counters"]
        assert c["jit.compile.cache_miss{site=serving.decode}"] == 1
        assert c["jit.compile.cache_miss{site=serving.prefill}"] == 1

    def test_load_weights_hot_swap_from_training_layout(self):
        """Engine.load_weights reshards a live training-layout param tree
        onto the serving layout without rebuilding the engine: after the
        swap the engine reproduces the donor model's outputs exactly."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from paddle_tpu.distributed import resharding as _rs

        paddle.seed(11)
        m1 = _tiny()
        paddle.seed(23)
        m2 = _tiny()
        prompts = [[5, 17, 3], [9, 2, 11, 4]]
        sp = SamplingParams(max_new_tokens=5)
        ref2 = Engine(m2, max_batch_size=2, max_seq_len=32).generate(
            prompts, sp)

        eng = Engine(m1, max_batch_size=2, max_seq_len=32)
        out1 = eng.generate(prompts, sp)
        assert out1 != ref2  # different weights, different continuations

        # park m2's params on a "training" mesh (replicated there), then
        # hot-swap: each leaf reshards onto the engine's current layout
        mesh24 = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "mp"))
        params2, _ = m2.functional_state()
        train_params = {
            k: jax.device_put(v, NamedSharding(mesh24, P()))
            for k, v in params2.items()
        }
        _rs.clear_caches()
        assert eng.load_weights(train_params) is eng
        assert eng.generate(prompts, sp) == ref2

        # validation: shape mismatch and missing keys are rejected
        bad = dict(train_params)
        name = next(iter(bad))
        bad[name] = jnp.zeros((3, 3), jnp.float32)
        with pytest.raises(ValueError, match="engine compiled for"):
            eng.load_weights(bad)
        some = dict(train_params)
        some.pop(name)
        with pytest.raises(KeyError, match="missing params"):
            eng.load_weights(some)
        # allow_missing keeps the current (m2) leaf for the hole
        eng.load_weights(some, allow_missing=True)
        assert eng.generate(prompts, sp) == ref2

    def test_load_weights_with_target_shardings_recompiles(self, telemetry):
        """Passing shardings= relays the engine onto a serving mesh: the
        stale executables are dropped (recompile shows in telemetry) and
        outputs are unchanged — replicated-on-8 is numerically the same
        compute."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        m = _tiny()
        prompts = [[5, 17, 3]]
        sp = SamplingParams(max_new_tokens=4)
        eng = Engine(m, max_batch_size=2, max_seq_len=32)
        base = eng.generate(prompts, sp)
        c = obs.snapshot()["counters"]
        assert c["jit.compile.cache_miss{site=serving.decode}"] == 1

        mesh8 = Mesh(np.array(jax.devices()), ("serve",))
        params, _ = m.functional_state()
        shardings = {k: NamedSharding(mesh8, P()) for k in params}
        eng.load_weights(params, shardings=shardings)
        for v in eng.params.values():
            assert v.sharding == NamedSharding(mesh8, P())
        assert eng.generate(prompts, sp) == base
        c = obs.snapshot()["counters"]
        assert c["jit.compile.cache_miss{site=serving.decode}"] == 2

    def test_sample_batched_per_row_params(self):
        logits = jnp.asarray([[0.0, 1.0, 5.0, 2.0]] * 3)
        import jax

        out = sample_batched(
            logits, jax.random.PRNGKey(0),
            temperatures=jnp.asarray([1.0, 1.0, 1e-4], jnp.float32),
            top_ks=jnp.asarray([0, 1, 0], jnp.int32),
            greedy=jnp.asarray([True, False, False]))
        got = np.asarray(out)
        assert got[0] == 2   # greedy row: argmax
        assert got[1] == 2   # top_k=1 keeps only the argmax
        assert got[2] == 2   # T->0 concentrates the categorical on argmax


# ---------------- observability ------------------------------------------
class TestServingMetrics:
    def test_metrics_present_under_flag(self, telemetry):
        m = _tiny()
        eng = Engine(m, max_batch_size=2, max_seq_len=32)
        eng.generate([[5, 17, 3], [9, 2, 4]], SamplingParams(max_new_tokens=3))
        snap = obs.snapshot()
        c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
        assert c["serving.requests{event=added}"] == 2
        assert c["serving.requests{event=finished}"] == 2
        assert c["serving.tokens.generated"] == 6
        assert c["serving.finish_reason{reason=length}"] == 2
        assert g["serving.kv_cache.bytes"] > 0
        assert g["serving.queue.depth"] == 0
        assert g["serving.slots.active"] == 0
        assert g["serving.tokens_per_sec"] > 0
        for name in ("serving.ttft.seconds", "serving.tpot.seconds",
                     "serving.prefill.seconds", "serving.decode.step.seconds"):
            assert h[name]["count"] > 0

    def test_flag_off_writes_nothing(self):
        obs.disable()
        obs.reset()
        m = _tiny()
        eng = Engine(m, max_batch_size=2, max_seq_len=32)
        eng.generate([[5, 17, 3]], SamplingParams(max_new_tokens=3))
        snap = obs.snapshot()
        assert not snap["counters"] and not snap["gauges"] \
            and not snap["histograms"]

    def test_scheduler_gauges_track_queue_and_slots(self, telemetry):
        from paddle_tpu.serving.scheduler import Request

        s = Scheduler(num_slots=2)
        s.add(Request([1, 2]))
        s.add(Request([3]))
        s.add(Request([4]))
        assert obs.snapshot()["gauges"]["serving.queue.depth"] == 3
        r = s.next_waiting()
        g = obs.snapshot()["gauges"]
        assert g["serving.queue.depth"] == 2 and g["serving.slots.active"] == 1
        assert g["serving.slots.occupancy"] == 0.5
        # satellite: waiting + running in one gauge
        assert g["serving.requests.active"] == 3
        s.finish(r, "length")
        assert obs.snapshot()["gauges"]["serving.slots.active"] == 0

    def test_decode_token_latency_histogram(self, telemetry):
        """Satellite: the scheduler records per-step decode latency per
        running request — mid-request stall visibility, where the
        finish-time tpot histogram only sees completed requests."""
        m = _tiny()
        eng = Engine(m, max_batch_size=2, max_seq_len=32)
        eng.generate([[5, 17, 3], [9, 2, 4]],
                     SamplingParams(max_new_tokens=4))
        h = obs.snapshot()["histograms"]["serving.decode.token.seconds"]
        # 2 requests x 3 post-first decode steps
        assert h["count"] == 6
        assert h["avg"] > 0


# ---------------- per-request traces + SLO monitor -------------------------
class TestRequestTracer:
    def test_trace_file_spans_and_request_ids(self, tmp_path):
        from paddle_tpu.serving import (EngineConfig, read_request_traces,
                                        request_trace_path)

        m = _tiny()
        eng = Engine(m, EngineConfig(
            max_batch_size=2, max_seq_len=32,
            request_trace_dir=str(tmp_path)))
        reqs = [eng.add_request([5, 17, 3]), eng.add_request([9, 2])]
        while eng.has_unfinished:
            eng.step()
        path = request_trace_path(str(tmp_path), eng.tracer.host)
        records = read_request_traces(path)
        assert len(records) == 2
        # request_id propagates from the scheduler into the trace records
        assert {r["request_id"] for r in records} == \
            {rq.request_id for rq in reqs}
        for rec in records:
            assert rec["schema"] == "paddle_tpu.requests.v1"
            spans = rec["spans"]
            assert [s["name"] for s in spans] == \
                ["queue", "prefill", "decode", "finish"]
            # lifecycle order: each span starts at/after the previous
            starts = [s["start_s"] for s in spans]
            assert starts == sorted(starts) and starts[0] == 0.0
            assert all(s["dur_s"] >= 0 for s in spans)
            assert spans[2]["steps"] == rec["generated_tokens"] - 1
            assert rec["finish_reason"] == "length"
            assert rec["ttft_s"] > 0

    def test_slo_violations_and_flight_forensics(self, telemetry, tmp_path):
        """Absurdly tight targets make every phase violate: the counters
        carry per-phase counts and the violating request's full trace
        lands in the flight recorder."""
        from paddle_tpu.serving import EngineConfig, SLOConfig

        fdir = tmp_path / "flight"
        rec = obs.start_flight_recorder(str(fdir), flush_interval_s=3600)
        try:
            m = _tiny()
            eng = Engine(m, EngineConfig(
                max_batch_size=2, max_seq_len=32,
                slo=SLOConfig(ttft_target_s=1e-9, tpot_target_s=1e-9,
                              decode_step_target_s=1e-9)))
            eng.generate([[5, 17, 3]], SamplingParams(max_new_tokens=3))
            snap = obs.snapshot()
            c = snap["counters"]
            assert c["serving.slo.violations{phase=ttft}"] == 1
            assert c["serving.slo.violations{phase=tpot}"] == 1
            assert c["serving.slo.violations{phase=decode_step}"] >= 1
            assert snap["histograms"][
                "serving.slo.excess_seconds{phase=ttft}"]["count"] == 1
            assert eng.tracer.stats()["violations"] == {
                "ttft": 1, "tpot": 1, "decode_step": 2}
            # no trace dir configured: SLO accounting ran file-less
            assert eng.tracer.path is None
        finally:
            obs.stop_flight_recorder()
        flight = obs.read_flight(rec.path)
        viol = [e for e in flight["events"]
                if e.get("kind") == "slo_violation"]
        assert len(viol) == 1
        assert set(viol[0]["slo_violations"]) == \
            {"ttft", "tpot", "decode_step"}
        assert [s["name"] for s in viol[0]["spans"]][0] == "queue"

    def test_sampling_writes_every_nth(self, tmp_path):
        from paddle_tpu.serving import EngineConfig, read_request_traces

        m = _tiny()
        eng = Engine(m, EngineConfig(
            max_batch_size=2, max_seq_len=32,
            request_trace_dir=str(tmp_path), trace_sample_every=2))
        eng.generate([[1, 2], [3, 4], [5, 6], [7, 8]],
                     SamplingParams(max_new_tokens=2))
        st = eng.tracer.stats()
        assert st["finished"] == 4 and st["written"] == 2
        records = read_request_traces(st["path"])
        assert len(records) == 2  # 1st and 3rd finished requests

    def test_healthy_run_has_no_violations(self, telemetry):
        from paddle_tpu.serving import EngineConfig, SLOConfig

        m = _tiny()
        eng = Engine(m, EngineConfig(
            max_batch_size=2, max_seq_len=32,
            slo=SLOConfig(ttft_target_s=60.0, tpot_target_s=60.0)))
        eng.generate([[5, 17, 3]], SamplingParams(max_new_tokens=3))
        assert eng.tracer.stats()["violations"] == {}
        assert not any(k.startswith("serving.slo.violations")
                       for k in obs.snapshot()["counters"])
