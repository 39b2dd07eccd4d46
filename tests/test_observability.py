"""Runtime telemetry substrate (paddle_tpu.observability).

Covers: registry semantics (counters/gauges/histograms + labels), snapshot
and reset isolation, the zero-overhead flag-off contract, span tracing and
its chrome-trace/profiler merge seam, and the instrumentation wired into the
IR pass manager, the eager+traced collective faces, the jit compile caches,
and the per-step training telemetry — ending with the acceptance check that
ONE snapshot carries a pass timing, a collective byte counter, compile-cache
hit/miss counters, and an MFU gauge.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import tracing as obs_tracing


@pytest.fixture
def telemetry():
    """Flag on + clean registry/spans, restored to off+empty afterwards."""
    obs.enable()
    obs.reset()
    obs.clear_spans()
    yield obs
    obs.disable()
    obs.reset()
    obs.clear_spans()


@pytest.fixture
def _fresh_world():
    from paddle_tpu.distributed import collective, mesh, topology

    collective.destroy_process_group()
    mesh.reset_global_mesh()
    topology.set_hybrid_communicate_group(None)
    yield
    collective.destroy_process_group()
    mesh.reset_global_mesh()
    topology.set_hybrid_communicate_group(None)


# ---------------- registry semantics ----------------
class TestRegistry:
    def test_counter_accumulates_and_labels_split_series(self, telemetry):
        obs.counter("x.calls")
        obs.counter("x.calls", 2)
        obs.counter("x.calls", 1, op="a")
        snap = obs.snapshot()
        assert snap["counters"]["x.calls"] == 3
        assert snap["counters"]["x.calls{op=a}"] == 1

    def test_gauge_overwrites(self, telemetry):
        obs.gauge("g", 1.0)
        obs.gauge("g", 0.25)
        assert obs.snapshot()["gauges"]["g"] == 0.25

    def test_histogram_stats(self, telemetry):
        for v in (1.0, 2.0, 3.0):
            obs.histogram("h.seconds", v)
        h = obs.snapshot()["histograms"]["h.seconds"]
        assert h["count"] == 3 and h["sum"] == 6.0
        assert h["min"] == 1.0 and h["max"] == 3.0 and h["avg"] == 2.0

    def test_label_order_is_canonical(self, telemetry):
        obs.counter("k", 1, b=2, a=1)
        obs.counter("k", 1, a=1, b=2)
        assert obs.snapshot()["counters"]["k{a=1,b=2}"] == 2

    def test_snapshot_is_isolated_copy(self, telemetry):
        obs.counter("c")
        snap = obs.snapshot()
        snap["counters"]["c"] = 999
        assert obs.snapshot()["counters"]["c"] == 1

    def test_snapshot_reset_and_reset(self, telemetry):
        obs.counter("c")
        obs.histogram("h", 1.0)
        snap = obs.snapshot(reset=True)
        assert snap["counters"]["c"] == 1 and len(obs.get_registry()) == 0
        obs.counter("c", 5)
        obs.reset()
        assert obs.snapshot() == {"counters": {}, "gauges": {},
                                  "histograms": {}}

    def test_records_and_jsonl_roundtrip(self, telemetry, tmp_path):
        obs.counter("a.calls", 2, op="x")
        obs.gauge("train.mfu", 0.4)
        obs.histogram("a.seconds", 0.5)
        path = obs.dump_jsonl(str(tmp_path / "m.jsonl"))
        recs = [json.loads(l) for l in open(path)]
        by_name = {r["name"]: r for r in recs}
        assert by_name["a.calls"]["value"] == 2
        assert by_name["a.calls"]["labels"] == {"op": "x"}
        assert by_name["train.mfu"]["type"] == "gauge"
        assert by_name["a.seconds"]["count"] == 1

    def test_metrics_dump_tool_renders(self, telemetry, tmp_path):
        import importlib.util
        import pathlib

        obs.counter("a.calls", 2, op="x")
        obs.histogram("a.seconds", 0.5)
        path = obs.dump_jsonl(str(tmp_path / "m.jsonl"))
        tool = (pathlib.Path(__file__).resolve().parents[1]
                / "tools" / "metrics_dump.py")
        spec = importlib.util.spec_from_file_location("metrics_dump", tool)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        text = mod.render(mod.load(str(path)))
        assert "a.calls{op=x}" in text and "a.seconds" in text
        assert mod.render(mod.load(str(path)), grep="nomatch") \
            == "(no metrics matched)"


# ---------------- flag-off contract ----------------
class TestFlagOff:
    def test_disabled_calls_record_nothing(self):
        obs.disable()
        obs.reset()
        obs.clear_spans()
        obs.counter("x")
        obs.gauge("g", 1.0)
        obs.histogram("h", 1.0)
        with obs.span("region"):
            pass
        obs.record_collective("psum", nbytes=128)
        obs.record_compile("site", seconds=1.0)
        obs.record_step(seconds=0.1)
        assert len(obs.get_registry()) == 0
        assert obs.spans() == []
        assert obs.summary() == "(registry empty)"

    def test_disabled_instrumented_paths_stay_silent(self):
        obs.disable()
        obs.reset()
        from paddle_tpu.ir import Program
        from paddle_tpu.ir.pass_manager import PassManager

        prog = Program()
        t = prog.ctx.tensor_type("float32", (4,))
        x = prog.add_input(t)
        op = prog.create_op("pd.add", [x, x], [t])
        prog.set_outputs([op.result(0)])
        PassManager(["dce"]).run(prog)
        import paddle_tpu.distributed as dist

        dist.all_reduce(paddle.to_tensor(np.ones((4,), np.float32)))
        assert len(obs.get_registry()) == 0


# ---------------- span tracer ----------------
class TestSpans:
    def test_span_records_histogram_and_buffer(self, telemetry):
        with obs.span("ir.pass", **{"pass": "cse"}):
            pass
        snap = obs.snapshot()
        assert snap["histograms"]["ir.pass.seconds{pass=cse}"]["count"] == 1
        (ev,) = obs.spans()
        assert ev["name"] == "ir.pass{pass=cse}" and ev["dur"] >= 0

    def test_spans_merge_into_profiler_export(self, telemetry, tmp_path):
        """The unification seam: a span inside an active Profiler lands in
        profiler.export_chrome_tracing output alongside RecordEvent spans."""
        from paddle_tpu import profiler

        p = profiler.Profiler(
            targets=[profiler.ProfilerTarget.CPU],
            on_trace_ready=profiler.export_chrome_tracing(str(tmp_path)))
        p.start()
        with profiler.RecordEvent("native_event"):
            pass
        with obs.span("obs_event"):
            pass
        p.stop()
        out = p._last_export
        names = {e.get("name") for e in json.load(open(out))["traceEvents"]}
        assert "native_event" in names and "obs_event" in names


# ---------------- IR pass instrumentation ----------------
def _tiny_program():
    from paddle_tpu.ir import Program

    prog = Program()
    t = prog.ctx.tensor_type("float32", (4,))
    x = prog.add_input(t)
    live = prog.create_op("pd.add", [x, x], [t])
    prog.create_op("pd.exp", [x], [t])  # dead: gives dce a rewrite
    prog.set_outputs([live.result(0)])
    return prog


class TestPassInstrumentation:
    def test_pass_timing_and_rewrite_counters(self, telemetry):
        from paddle_tpu.ir.pass_manager import PassManager

        stats = PassManager(["cse", "dce"]).run(_tiny_program())
        assert stats["dce"] >= 1
        snap = obs.snapshot()
        assert snap["histograms"]["ir.pass.seconds{pass=dce}"]["count"] >= 1
        assert snap["counters"]["ir.pass.rewrites{pass=dce}"] >= 1
        assert snap["counters"]["ir.pass_manager.rounds"] >= 1
        # cse found nothing on the pruned program -> no_change series
        assert "ir.pass.no_change{pass=cse}" in snap["counters"]

    def test_oversized_causal_mask_skip_counter(self, telemetry):
        from paddle_tpu.ir import Program
        from paddle_tpu.ir.passes import _MASK_EVAL_LIMIT, _is_causal_mask

        prog = Program()
        side = int(np.sqrt(_MASK_EVAL_LIMIT)) + 1  # one past the proof limit
        t = prog.ctx.tensor_type("bool", (side, side))
        v = prog.add_input(t)
        assert _is_causal_mask(prog, v) is False
        assert obs.snapshot()["counters"][
            "ir.causal_mask.skipped_oversized"] == 1


# ---------------- collective instrumentation ----------------
class TestCollectiveInstrumentation:
    def test_eager_all_reduce_counts_and_bytes(self, telemetry, _fresh_world):
        import paddle_tpu.distributed as dist

        x = paddle.to_tensor(np.ones((8,), np.float32))
        dist.all_reduce(x)
        snap = obs.snapshot()
        key = "dist.collective.calls{face=eager,op=all_reduce}"
        assert snap["counters"][key] == 1
        assert snap["counters"][
            "dist.collective.bytes{face=eager,op=all_reduce}"] == 8 * 4
        assert snap["histograms"][
            "dist.collective.seconds{face=eager,op=all_reduce}"]["count"] == 1

    def test_traced_psum_records_at_trace_time(self, telemetry, _fresh_world):
        """Traced-face wrappers record shape*dtype bytes once per trace —
        re-executing the compiled fn adds nothing (zero runtime cost)."""
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.distributed.communication import psum

        n = 2
        mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
        f = jax.jit(jax.shard_map(
            lambda v: psum(v, "x"), mesh=mesh,
            in_specs=P("x"), out_specs=P()))
        arr = jnp.ones((n, 4), jnp.float32)
        # local shard keeps its leading microdim: psum of (1, 4) shards
        np.testing.assert_allclose(np.asarray(f(arr)),
                                   np.full((1, 4), float(n)))
        snap = obs.snapshot()
        key = "dist.collective.calls{face=traced,op=psum}"
        first = snap["counters"][key]
        assert first >= 1
        assert snap["counters"]["dist.collective.bytes{face=traced,op=psum}"] > 0
        f(arr)  # cached executable: no re-trace, no new records
        assert obs.snapshot()["counters"][key] == first

    def test_pipeline_schedule_records_ppermute_bytes(
            self, telemetry, _fresh_world):
        """A tiny pp=2 GPipe schedule must surface its boundary ppermutes in
        the registry — the per-collective byte attribution the issue asks
        for on the pipeline path."""
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu.distributed.fleet.meta_parallel import (
            pipeline_schedule)

        n, M, mbsz, d = 2, 2, 2, 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("pp",))
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(n, d, d).astype(np.float32) * 0.3)
        xs = jnp.asarray(rng.randn(M, mbsz, d).astype(np.float32))
        f = jax.jit(jax.shard_map(
            lambda w, xb: pipeline_schedule(
                lambda p, t: jnp.tanh(t @ p), w, xb, axis_name="pp")[None],
            mesh=mesh, in_specs=(P("pp"), P()), out_specs=P("pp"),
            check_vma=False))
        f(w, xs)
        snap = obs.snapshot()
        assert snap["counters"][
            "dist.collective.calls{face=traced,op=ppermute}"] >= 1
        assert snap["counters"][
            "dist.collective.bytes{face=traced,op=ppermute}"] > 0


# ---------------- compile cache + training telemetry ----------------
class TestCompileAndTraining:
    def test_to_static_cache_hit_miss(self, telemetry):
        from paddle_tpu import jit

        @jit.to_static
        def f(a):
            return a * 2

        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        f(x)
        f(x)
        snap = obs.snapshot()
        assert snap["counters"]["jit.compile.cache_miss{site=to_static}"] == 1
        assert snap["counters"]["jit.compile.cache_hit{site=to_static}"] >= 1
        assert snap["histograms"][
            "jit.compile.seconds{site=to_static}"]["count"] == 1

    def test_sharded_train_step_telemetry(self, telemetry, _fresh_world):
        from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
        from paddle_tpu.models import gpt_tiny

        paddle.seed(0)
        model = gpt_tiny(dropout=0.0, num_layers=2)
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-3, parameters=model.parameters())
        step = make_sharded_train_step(model, opt)
        rng = np.random.RandomState(0)
        x = rng.randint(0, 128, size=(4, 16))
        y = np.roll(x, -1, axis=1)
        float(step(x, y))
        float(step(x, y))
        snap = obs.snapshot()
        miss = "jit.compile.cache_miss{site=sharded_train_step}"
        hit = "jit.compile.cache_hit{site=sharded_train_step}"
        assert snap["counters"][miss] == 1 and snap["counters"][hit] == 1
        assert snap["histograms"][
            "jit.compile.seconds{site=sharded_train_step}"]["count"] == 1
        assert snap["counters"]["train.steps"] == 2
        assert snap["counters"]["train.samples"] == 8
        # warm dispatches (hits) feed the step-latency histogram
        assert snap["histograms"]["train.step.dispatch_seconds"]["count"] == 1


# ---------------- acceptance: one snapshot, all four families ----------------
def test_snapshot_contains_all_acceptance_families(telemetry, _fresh_world):
    """Issue acceptance: a single metrics snapshot holding >=1 pass-timing
    metric, >=1 collective byte counter, compile-cache hit/miss counters,
    and the per-step dispatch histogram the ``train/step`` span feeds."""
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.fleet.meta_parallel import pipeline_schedule
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.ir.pass_manager import PassManager
    from paddle_tpu.models import gpt_tiny

    # pass timing
    PassManager(["cse", "dce"]).run(_tiny_program())
    # pipeline-parallel collective bytes (traced ppermute)
    n, d = 2, 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("pp",))
    w = jnp.ones((n, d, d), jnp.float32) * 0.1
    xs = jnp.ones((2, 2, d), jnp.float32)
    jax.jit(jax.shard_map(
        lambda w, xb: pipeline_schedule(
            lambda p, t: jnp.tanh(t @ p), w, xb, axis_name="pp")[None],
        mesh=mesh, in_specs=(P("pp"), P()), out_specs=P("pp"),
        check_vma=False))(w, xs)
    # compile cache + per-step telemetry
    paddle.seed(0)
    model = gpt_tiny(dropout=0.0, num_layers=2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = make_sharded_train_step(model, opt)
    rng = np.random.RandomState(0)
    x = rng.randint(0, 128, size=(4, 16))
    y = np.roll(x, -1, axis=1)
    float(step(x, y))
    float(step(x, y))

    snap = obs.snapshot()
    assert any(k.startswith("ir.pass.seconds") for k in snap["histograms"])
    assert any(k.startswith("dist.collective.bytes{face=traced,op=ppermute")
               for k in snap["counters"])
    assert any(k.startswith("jit.compile.cache_miss") for k in snap["counters"])
    assert any(k.startswith("jit.compile.cache_hit") for k in snap["counters"])
    assert snap["histograms"]["train.step.dispatch_seconds"]["count"] == 1
    # and the human-readable faces render it
    text = obs.summary()
    assert "train.step.dispatch_seconds" in text and "Counter" in text
