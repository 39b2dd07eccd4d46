"""How much of the context a latent decode step scored TOGETHER: the reader
behind the ``program_span`` metric ``shared_walk_share.latent``.

``serving.Engine`` puts ``shared_walk_tokens`` on each ``serving/decode``
span of a model with latent layers, a number a layer (what the model's step
counted, behind its tokens): the context tokens, summed over the running
slots, that the absorbed latent-attention kernel
(``paddle_tpu/kernels/latent_attention.py``) scored in a shared walk of two
members or more, each page fetched once for all of them and their heads the
rows of one matmul. It is the sum over the very plan the kernel ran on, and
every latent layer of a step runs on the same plan, so a step's value is its
layers' largest (a layer of another kind counts 0). The share is of
``ctx_tokens`` (the running slots' contexts), over the decode steps of the
traced stretch, on the spans ``program_spans.view`` has moved onto the
trace's clock and checked. A program that puts no such attribute on its
spans (the parent of the PR that added it), or a model without latent
layers, gives None, never an error.
"""

from __future__ import annotations

from . import program_spans


def shared_walk_share(run):
    """100 x sum of ``shared_walk_tokens`` / sum of ``ctx_tokens``."""
    steps = [a for _, _, n, a in program_spans.view(run) or ()
             if n == "serving/decode" and "shared_walk_tokens" in a
             and a.get("ctx_tokens")]
    if not steps:
        return None
    walked = sum(max(a["shared_walk_tokens"]) for a in steps)
    return 100.0 * walked / sum(a["ctx_tokens"] for a in steps)
