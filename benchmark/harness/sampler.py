"""How often the decode program's sampler drew: the reader behind the
``program_span`` metrics ``sampler_draw_share.*``.

``serving.Engine`` puts ``draws`` on each ``serving/decode`` span that ran a
step: the live rows that are not greedy, which is what the program's sampler
sees in its ``greedy`` operand. With 0 the program runs its argmax and
nothing else; with any it scales, draws V random numbers a row and, for a
drawing row with a top-k, finds the row's k-th value
(``paddle_tpu/serving/sampling.py``). The share is of the decode steps of the
traced stretch, on the spans ``program_spans.view`` has moved onto the
trace's clock and checked. A program that puts no such attribute on its spans
(the parent of the PR that added it) gives None, never an error.
"""

from __future__ import annotations

from . import program_spans


def sampler_draw_share(run):
    """% of the stretch's decode steps with ``draws`` > 0."""
    draws = [a["draws"] for _, _, n, a in program_spans.view(run) or ()
             if n == "serving/decode" and "draws" in a]
    if not draws:
        return None
    return 100.0 * sum(d > 0 for d in draws) / len(draws)
