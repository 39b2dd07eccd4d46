"""Seeded weights for a decoder of the Solar-Open2 kind
(``paddle_tpu.models.decoder``: ``gated_delta`` layers whose decay is a
vector over the key channels, gated GQA, routed experts and a shared one).
The rule is ``hybrid_weights``'s, by import: a leaf's values depend on the
seed, its name and its shape only, made on the device and rounded to the
serving type, by the name's ending:

    norm.weight   1 + N(0, 0.1)     (every RMSNorm scale, ``o_norm`` too)
    .A_log        log A,  A ~ U(0.001, 16)        (a head)
    .dt_bias      softplus^-1(dt), dt log-uniform in [0.001, 0.1]
                  (here one a key channel, ``[heads * dk]``)
    .conv.weight  U(-k^-1/2, k^-1/2), k the kernel's width
    anything else N(0, std): the projections, the low-rank pairs
                  ``wf_a / wf_b`` and ``wg_a / wg_b``, the GQA gate ``wg``,
                  the router, every expert and the shared expert

At N(0, 0.02) and rank 128 the low-rank pair moves the decay's step by a
factor e^(+-0.3) around its bias and the output gate around sigmoid(0): the
gates depend on the data without saturating. The runner and the reference
both call ``make``, so the two sides share the seed and nothing else."""

from .hybrid_weights import compile_makers, kind, make  # noqa: F401
