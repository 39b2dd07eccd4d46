"""``harness/shared_walk.py`` on recorded span lists: the share of the
running slots' context that a latent decode step scored in a shared walk.
Counts only: nothing here is a device measurement."""

import pytest

from harness import shared_walk


class FakeRun:
    """What the reader touches of a Run: the program's spans of the traced
    stretch as ``program_spans.view`` caches them."""

    def __init__(self, spans):
        self._program_spans = spans


def _steps(steps):
    """One ``serving/step`` > ``serving/decode`` pair a step, 50 ms apart;
    a step is (ctx_tokens, shared_walk_tokens a layer) or None: a decode
    span that ran nothing. ``walked`` None: a program that does not count."""
    out = []
    for i, step in enumerate(steps):
        s = 100.0 + 0.05 * i
        out.append((s, s + 0.049, "serving/step", {"step": i}))
        attrs = {"step": i, "running": 0}
        if step is not None:
            ctx, walked = step
            attrs.update(running=4, ctx_tokens=ctx,
                         latent_tokens_read=[ctx] * 5)
            if walked is not None:
                attrs["shared_walk_tokens"] = walked
        out.append((s + 0.001, s + 0.048, "serving/decode", attrs))
        out.append((s + 0.002, s + 0.003, "serving/decode/dispatch",
                    {"launch": i + 1}))
    return out


DOC = 32768


@pytest.mark.parametrize("steps, want", [
    # sixteen sessions a document, four documents, a mean context of 34,000
    ([(64 * 34000, [64 * DOC] * 5)] * 10, 100.0 * DOC / 34000),
    # nothing shared: the plan's tiles hold one member each
    ([(4000, [0] * 5)] * 7, 0.0),
    # a change of turn: one step a tile is short of two members
    ([(2000, [1024] * 5), (2100, [0] * 5), (2200, [1536] * 5)],
     100.0 * 2560 / 6300),
    # latent layers behind a layer of another kind: the step's value is
    # the latent layers'
    ([(1000, [0, 512, 512]), (1000, [0, 256, 256])], 100.0 * 768 / 2000),
    # steps that ran nothing do not count
    ([None, (500, [250] * 5), None], 50.0),
    # the parent: the attribute nowhere
    ([(500, None)] * 4, None),
    ([None] * 3, None),
    ([], None),
], ids=["sixteen_a_document", "disjoint", "a_tile_short_of_members",
        "mixed_layers", "with_empty_steps", "no_attribute", "nothing_ran",
        "no_spans"])
def test_shared_walk_share(steps, want):
    got = shared_walk.shared_walk_share(FakeRun(_steps(steps)))
    assert got == want if want is None else got == pytest.approx(want)


def test_no_view_gives_none():
    """An untraced run, or clocks that do not line up: ``view`` is None."""
    assert shared_walk.shared_walk_share(FakeRun(None)) is None


def test_the_metric_file_reads_through_the_reader():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "layer_metrics",
        "shared_walk_share.latent.py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read is shared_walk.shared_walk_share
