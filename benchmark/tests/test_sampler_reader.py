"""``harness/sampler.py`` on recorded span lists: the share of decode steps
that drew. Counts only: nothing here is a device measurement."""

import pytest

from harness import sampler


class FakeRun:
    """What the reader touches of a Run: the program's spans of the traced
    stretch as ``program_spans.view`` caches them."""

    def __init__(self, spans):
        self._program_spans = spans


def _steps(draws):
    """One ``serving/step`` > ``serving/decode`` pair a step, 10 ms apart;
    ``None``: a decode span without the attribute (no request running, or a
    program that does not count)."""
    out = []
    for i, d in enumerate(draws):
        s = 100.0 + 0.01 * i
        out.append((s, s + 0.009, "serving/step", {"step": i}))
        attrs = {"step": i, "running": 0 if d is None else 3}
        if d is not None:
            attrs["draws"] = d
        out.append((s + 0.001, s + 0.008, "serving/decode", attrs))
        out.append((s + 0.002, s + 0.003, "serving/decode/dispatch",
                    {"launch": i + 1}))
    return out


@pytest.mark.parametrize("draws, want", [
    ([0] * 40, 0.0),                                # every row greedy
    ([0, 0, 1, 0, 32, 0, 0, 2], 37.5),              # 3 of 8 steps drew
    ([5] * 7, 100.0),
    ([None, 0, None, 3], 50.0),                     # empty steps do not count
    ([None] * 6, None),                             # the attribute nowhere
    ([], None),
], ids=["all_greedy", "some", "all_draw", "with_empty_steps",
        "no_attribute", "no_spans"])
def test_sampler_draw_share(draws, want):
    assert sampler.sampler_draw_share(FakeRun(_steps(draws))) == want


def test_no_view_gives_none():
    """An untraced run, or clocks that do not line up: ``view`` is None."""
    assert sampler.sampler_draw_share(FakeRun(None)) is None
