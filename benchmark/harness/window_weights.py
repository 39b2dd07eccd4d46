"""Seeded weights for a decoder of the Command A+ kind
(``paddle_tpu.models.decoder``: sliding and full attention layers in a
parallel block, routed experts chosen by plain sigmoid scores, shared
experts). The rule is ``decoder_weights``'s, by import: a leaf's values
depend on the seed, its name and its shape only, made on the device and
rounded to the serving type; norm scales 1 + N(0, 0.1), everything else
N(0, std). (This family has no leaf of another kind: no bias, no router
bias, no convolution.)

The runner and the reference both call ``make``, so the two sides share the
seed and nothing else."""

from .decoder_weights import compile_makers, make  # noqa: F401
