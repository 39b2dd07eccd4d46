"""Seeded weights for a ``paddle_tpu.models.decoder`` model: the rule of
``weights.py`` (a leaf's values depend on the seed, its name and its shape
only; matrices, embeddings and biases N(0, std); norm scales 1 + N(0, 0.1);
rounded to the serving type; made on the device, one jitted call a shape)
with this family's names for the norm scales. The runner and the reference
both call ``make``, so the two sides share the seed and nothing else.

``compile_makers`` compiles the makers of all the distinct shapes at once, a
thread each: one after another, as a first ``make`` would, they took 42 s of
a cold run's set-up at this family's sizes (my chip run, PR 26)."""

from __future__ import annotations

import zlib

import jax.numpy as jnp
import numpy as np

from . import weights


def is_scale(name: str) -> bool:
    return name.endswith("norm.weight")


_COMPILED = {}   # (shape, scale, std, dtype) -> the maker, compiled


def _sig(name: str, shapes: dict, std: float, dtype):
    return (tuple(int(d) for d in shapes[name]), is_scale(name), float(std),
            jnp.dtype(dtype).name)


def compile_makers(shapes: dict, std: float, dtype):
    """Compile the maker of every distinct leaf shape, side by side."""
    from concurrent.futures import ThreadPoolExecutor

    word = np.uint32(0)
    sigs = sorted({_sig(n, shapes, std, dtype) for n in shapes}
                  - set(_COMPILED))
    lowered = [weights._leaf_fn(*sig, None).lower(word, word, word)
               for sig in sigs]
    if lowered:
        with ThreadPoolExecutor(len(lowered)) as pool:
            _COMPILED.update(zip(sigs, pool.map(lambda l: l.compile(),
                                                lowered)))


def make(seed: int, shapes: dict, std: float, dtype, names=None):
    """{name: array} for ``shapes`` {name: shape}, or the subset ``names``."""
    w0, w1 = weights.seed_words(seed)
    out = {}
    for n in sorted(shapes if names is None else names):
        sig = _sig(n, shapes, std, dtype)
        crc = np.uint32(zlib.crc32(n.encode()) % (2**31 - 1))
        out[n] = (_COMPILED.get(sig) or weights._leaf_fn(*sig, None))(
            w0, w1, crc)
    return out
