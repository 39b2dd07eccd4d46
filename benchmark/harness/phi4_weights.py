"""Seeded weights for a decoder-hybrid-decoder of the Phi-4-mini-flash kind
(``paddle_tpu.models.decoder``: ``mamba1`` layers, differential attention
with biases, gated memory units, LayerNorm with weight AND bias): the rule of
``weights.py`` (a leaf's values depend on the seed, its name and its shape
only; made on the device, one jitted call a shape and kind; rounded to the
serving type) with this family's leaves, by the name's ending:

    norm.weight    1 + N(0, 0.1)    (every LayerNorm scale, the pair norm's too)
    .A_log         log(1 .. N) along the state's lanes ([N, E]: Mamba-1's own
                   initialisation, the same for every channel)
    .dt_bias       softplus^-1(dt), dt log-uniform in [0.001, 0.1]
    .D             1                              (the skip, a channel)
    .conv.weight   U(-k^-1/2, k^-1/2), k the kernel's width (1/2 at width 4)
    .conv.bias     0
    .lambda_*      N(0, 0.1)        (the four vectors of a differential layer)
    anything else  N(0, std)        (matrices, projection and norm biases)

``A_log``, ``dt_bias``, ``D`` and the convolution are neither norm scales
nor N(0, 0.02): at 0.02 every channel would decay alike and the convolution
pass a hundredth of its input. The runner and the reference both call
``make``, so the two sides share the seed and nothing else.
``compile_makers`` compiles the makers of all the distinct shapes at once, a
thread each (as ``mamba_weights``, whose structure this file copies: its
kinds are a module constant that import cannot replace)."""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

_KINDS = (("norm.weight", "scale"), (".A_log", "a_log"),
          (".dt_bias", "dt_bias"), (".D", "ones"), (".conv.weight", "conv"),
          (".conv.bias", "zeros"), (".lambda_q1", "lam"), (".lambda_q2", "lam"),
          (".lambda_k1", "lam"), (".lambda_k2", "lam"))
#: the step's range (Mamba-1's defaults)
_DT = (1e-3, 1e-1)


def kind(name: str) -> str:
    return next((k for end, k in _KINDS if name.endswith(end)), "normal")


def _draw(key, shape, what: str, std: float):
    f32 = jnp.float32
    if what == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    if what == "a_log":     # [N, E]: log(1 .. N), every channel alike
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=f32))[:, None], shape)
    if what == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, f32, np.log(_DT[0]), np.log(_DT[1])))
        return dt + jnp.log(-jnp.expm1(-dt))
    if what == "ones":
        return jnp.ones(shape, f32)
    if what == "zeros":
        return jnp.zeros(shape, f32)
    if what == "conv":
        r = shape[-1] ** -0.5
        return jax.random.uniform(key, shape, f32, -r, r)
    if what == "lam":
        return 0.1 * jax.random.normal(key, shape, f32)
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
