"""Seeded weights for a decoder with gated delta-rule layers
(``paddle_tpu.models.decoder``, ``gated_delta``): the rule of ``weights.py``
(a leaf's values depend on the seed, its name and its shape only; made on the
device, one jitted call a shape and kind; rounded to the serving type) with
this family's leaves, by the name's ending:

    norm.weight   1 + N(0, 0.1)     (every RMSNorm scale, ``o_norm`` too)
    .A_log        log A,  A ~ U(0.001, 16)        (the decay's rate, a head)
    .dt_bias      softplus^-1(dt), dt log-uniform in [0.001, 0.1]
    .conv.weight  U(-k^-1/2, k^-1/2), k the kernel's width (a depthwise
                  Conv1d's default in the public implementation)
    anything else N(0, std)

``A_log``, ``dt_bias`` and the convolution are neither norm scales nor
N(0, 0.02): at 0.02 the decay would be exp(-softplus(.) * 1.02) for every
head and the convolution's output a hundredth of its input. The runner and
the reference both call ``make``, so the two sides share the seed and
nothing else. ``compile_makers`` compiles the makers of all the distinct
shapes at once, a thread each (as ``decoder_weights``)."""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

_KINDS = (("norm.weight", "scale"), (".A_log", "a_log"),
          (".dt_bias", "dt_bias"), (".conv.weight", "conv"))


def kind(name: str) -> str:
    return next((k for end, k in _KINDS if name.endswith(end)), "normal")


def _draw(key, shape, what: str, std: float):
    f32 = jnp.float32
    if what == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    if what == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1e-3, 16.0))
    if what == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, np.log(1e-3),
                                        np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if what == "conv":
        r = shape[-1] ** -0.5
        return jax.random.uniform(key, shape, f32, -r, r)
    return std * jax.random.normal(key, shape, f32)


_LEAF = {}      # (shape, kind, std, dtype) -> the jitted maker
_COMPILED = {}  # the same -> the maker, compiled


def _leaf_fn(shape, what: str, std: float, dtype: str):
    sig = (shape, what, std, dtype)
    if sig not in _LEAF:
        def fn(w0, w1, crc):
            key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0), w0), w1), crc)
            return _draw(key, shape, what, std).astype(dtype)

        _LEAF[sig] = jax.jit(fn)
    return _LEAF[sig]


def _sig(name: str, shapes: dict, std: float, dtype):
    return (tuple(int(d) for d in shapes[name]), kind(name), float(std),
            jnp.dtype(dtype).name)


def compile_makers(shapes: dict, std: float, dtype):
    """Compile the maker of every distinct leaf shape, side by side."""
    from concurrent.futures import ThreadPoolExecutor

    word = np.uint32(0)
    sigs = sorted({_sig(n, shapes, std, dtype) for n in shapes}
                  - set(_COMPILED))
    lowered = [_leaf_fn(*sig).lower(word, word, word) for sig in sigs]
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
        out[n] = (_COMPILED.get(sig) or _leaf_fn(*sig))(w0, w1, crc)
    return out
