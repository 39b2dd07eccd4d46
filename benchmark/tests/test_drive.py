"""Drives everything of a run but the look for a chip, on the CPU at a tiny
size, from an overlay of new files: the sound path comes out correct, and a
timed path broken underneath comes out NOT correct."""

import pytest

from conftest import drive_tiny


def test_train_cell_drives_and_is_correct(overlay):
    out, r = drive_tiny("tiny-train")
    assert out["correct"] is True, r.compared
    assert out["metrics"]["train_tok_s_chip"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["attempted"] >= 1
    # on a CPU the Mosaic kernels are absent: the run counts as failed
    assert out["failed"] == out["attempted"] and r.failures


def test_train_metric_from_overlay_reader(overlay):
    out, r = drive_tiny("tiny-train", trace=0)
    import run as bench_run
    from conftest import TINY_MANIFEST

    (m,) = bench_run.metrics_of(TINY_MANIFEST, "tiny-train", "per_layer")
    assert bench_run.reader(m["name"])(r) == r.counters["steps"]


def test_step_that_returns_its_state_unchanged_is_not_correct(overlay,
                                                               monkeypatch):
    from paddle_tpu.distributed.fleet.utils import ShardedTrainStep

    real = ShardedTrainStep.__call__

    def frozen(self, x, y, lr=None):
        params, opt_state = self.params, self.opt_state
        keep = lambda t: __import__("jax").tree_util.tree_map(
            lambda a: a + 0, t)
        p0, s0 = keep(params), keep(opt_state)
        loss = real(self, x, y, lr)
        self.params, self.opt_state = p0, s0
        return loss

    monkeypatch.setattr(ShardedTrainStep, "__call__", frozen)
    out, r = drive_tiny("tiny-train")
    assert out["correct"] is False, r.compared


def test_part_of_the_batch_left_out_is_not_correct(overlay, monkeypatch):
    from paddle_tpu.distributed.fleet.utils import ShardedTrainStep

    real = ShardedTrainStep.__call__

    def half(self, x, y, lr=None):
        x, y = x.copy(), y.copy()
        x[1:], y[1:] = x[0], y[0]   # every row is row 0
        return real(self, x, y, lr)

    monkeypatch.setattr(ShardedTrainStep, "__call__", half)
    out, r = drive_tiny("tiny-train")
    assert out["correct"] is False, r.compared


@pytest.mark.parametrize("cell", ["tiny-chat", "tiny-sessions"])
def test_serve_cells_drive_and_are_correct(overlay, cell):
    out, r = drive_tiny(cell, seconds=2.0)
    assert out["correct"] is True, r.compared
    key = ("latency_per_tok_p50_ms" if cell == "tiny-chat"
           else "serve_out_tok_s")
    assert out["metrics"][key]["value"] > 0
    assert out["attempted"] >= 1 and r.counters["steps"]
    if cell == "tiny-sessions":
        assert r.counters["prompt_tokens_hit"] > 0


def test_a_token_altered_where_it_is_produced_is_not_correct(overlay,
                                                              monkeypatch):
    from paddle_tpu.serving import engine as eng_mod

    real = eng_mod.Engine._maybe_finish

    def alter(self, req, tok):
        if req.num_generated % 3 == 0:
            req.output_ids[-1] = (req.output_ids[-1] + 1) % 128
        return real(self, req, tok)

    monkeypatch.setattr(eng_mod.Engine, "_maybe_finish", alter)
    out, r = drive_tiny("tiny-chat", seconds=2.0)
    assert out["correct"] is False, r.compared
