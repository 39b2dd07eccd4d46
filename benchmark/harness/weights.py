"""Seeded weights, made on the device.

The benchmark, not the program, owns the weights: the program is handed them
and the plain reference makes the same ones again from the seed, so the two
sides share a seed and nothing else. A leaf's values depend on the seed, its
name and its shape only, so any subset can be regenerated alone.

Matrices and embeddings are N(0, std); biases N(0, std) too (zeros would let
a dropped bias pass the check); LayerNorm scales 1 + N(0, 0.1). Values are
rounded to ``dtype``, the type they are trained / served in.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

_M31 = 2**31 - 1


def seed_words(seed: int):
    """Two uint32 words of any whole-number seed (seeds may pass 2**31)."""
    seed = int(seed)
    return np.uint32(seed % _M31), np.uint32((seed // _M31) % _M31)


def _is_scale(name: str) -> bool:
    return name.endswith(("ln1.weight", "ln2.weight", "final_ln.weight"))


_LEAF = {}


def _leaf_fn(shape, scale: bool, std: float, dtype: str, sharding):
    """The jitted maker of one leaf shape. Seed words and the leaf's name
    hash are runtime arguments, so the model's few distinct shapes compile
    to a dozen small programs (one program for all 290 leaves took the TPU
    compiler 85 s, and 7 s to load back from the cache on every run)."""
    sig = (shape, scale, std, dtype, sharding)
    if sig not in _LEAF:
        def fn(w0, w1, crc):
            key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0), w0), w1), crc)
            x = jax.random.normal(key, shape, jnp.float32)
            x = 1.0 + 0.1 * x if scale else std * x
            return x.astype(dtype)

        _LEAF[sig] = jax.jit(fn, out_shardings=sharding)
    return _LEAF[sig]


def make(seed: int, shapes: dict, std: float, dtype, shardings=None,
         names=None):
    """{name: array} for ``shapes`` {name: shape} (or the subset ``names``),
    laid out as ``shardings`` {name: Sharding} says (default: the default
    device). Made on the device, in the type asked for; nothing is drawn on
    the host."""
    w0, w1 = seed_words(seed)
    dt = jnp.dtype(dtype).name
    out = {}
    for n in sorted(shapes if names is None else names):
        shape = tuple(int(d) for d in shapes[n])
        sh = None if shardings is None else shardings[n]
        crc = np.uint32(zlib.crc32(n.encode()) % _M31)
        out[n] = _leaf_fn(shape, _is_scale(n), float(std), dt, sh)(w0, w1, crc)
    return out
