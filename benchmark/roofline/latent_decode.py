"""Required work of one absorbed latent-attention decode call (one layer,
one decode step; the named kernel ``latent_paged_decode``), the same
whatever implements it: every query head of every running slot scores each
of the slot's live tokens against the token's ONE latent row (``rank +
rope`` multiply-adds: the keys' up-projection is folded into the query) and
attends the row's first ``rank`` lanes (``rank`` more), so ``2 x heads x
(2 rank + rope)`` FLOPs a (slot, token); each DISTINCT live page has to
come in from HBM once (its ``rank + rope`` used lanes: idle lanes a pool pads
a row with are the program's choice), however many slots map it (sessions on one shared document: a kernel that fetches it for each of
them does more than is required, one that shares the read does not pass
100%), and each running slot's query goes in and its attended latents come
out once. Tokens of free slots and of unfilled page tails are not required
work; the absorb matmuls on either side are outside the call."""

from .flash import min_seconds  # noqa: F401


def call(context_tokens, distinct_pages, slot_steps, heads, rank, rope,
         page_size=16, itemsize=2):
    """``context_tokens``: live tokens summed over the running slots (and
    layer-steps); ``distinct_pages``: live pages counted once a layer-step;
    ``slot_steps``: running slots summed over layer-steps."""
    lanes = rank + rope
    return {"flops": 2.0 * context_tokens * heads * (2 * rank + rope),
            "bytes": (distinct_pages * page_size * lanes
                      + slot_steps * heads * (lanes + rank)) * float(itemsize)}
