"""Seeded weights for a decoder of the DeepSeek-V3 kind
(``paddle_tpu.models.decoder``: latent attention, a dense layer before
routed experts chosen by sigmoid scores plus a bias). The rule is
``decoder_weights``'s, by import: a leaf's values depend on the seed, its
name and its shape only, made on the device and rounded to the serving
type, by the name's ending:

    norm.weight    1 + N(0, 0.1)    (the block's norms and the two latents')
    .router.bias   N(0, 0.1)        (the published weights' bias is trained,
                   not drawn; ZERO would make choosing and weighing the
                   same thing and let a weight taken from ``s + bias``
                   pass; at 0.1 beside scores in (0, 1) it changes the
                   choice for most tokens)
    anything else  N(0, std): the projections down to and up from the
                   latents, the router, every expert, the shared expert,
                   the dense layer, embedding and head

The runner and the reference both call ``make``, so the two sides share the
seed and nothing else."""

from __future__ import annotations

from . import decoder_weights

BIAS_STD = 0.1


def _parts(shapes: dict, names=None):
    """(the bias leaves, the rest) of ``names`` (default: all)."""
    names = list(shapes if names is None else names)
    bias = [n for n in names if n.endswith(".router.bias")]
    return bias, [n for n in names if not n.endswith(".router.bias")]


def compile_makers(shapes: dict, std: float, dtype):
    bias, rest = _parts(shapes)
    decoder_weights.compile_makers({n: shapes[n] for n in rest}, std, dtype)
    decoder_weights.compile_makers({n: shapes[n] for n in bias}, BIAS_STD,
                                   dtype)


def make(seed: int, shapes: dict, std: float, dtype, names=None):
    """{name: array} for ``shapes`` {name: shape}, or the subset ``names``."""
    bias, rest = _parts(shapes, names)
    out = decoder_weights.make(seed, shapes, std, dtype, rest)
    out.update(decoder_weights.make(seed, shapes, BIAS_STD, dtype, bias))
    return out
