"""Fused LayerNorm / RMSNorm Pallas kernels (phi/kernels/gpu/layer_norm_kernel.cu
and rms_norm fusion analogs): one HBM pass computes stats + normalizes +
applies affine. Backward recomputes stats from the saved input — on TPU the
stat recompute fuses into the dx elementwise pipeline, which is cheaper than
materializing (mean, rstd) through HBM with Mosaic's (8, 128)-tile layout."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ..core.place import pallas_interpret
from .mesh import shard_kernel


def _ln_kernel(x_ref, w_ref, b_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)  # [rows, H]
    mean = jnp.mean(x, axis=-1)
    var = jnp.mean(jnp.square(x - mean[:, None]), axis=-1)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean[:, None]) * rstd[:, None] * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)


def _rms_kernel(x_ref, w_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1) + eps)
    y_ref[:] = (x * rstd[:, None] * w_ref[:].astype(jnp.float32)).astype(y_ref.dtype)


def _rows_block(n_rows: int) -> int:
    """Mosaic tiling: the rows block must be a multiple of 8 or span all rows.
    Non-dividing blocks are fine (pl.cdiv grid pads the tail; padded rows are
    row-independent garbage the out-of-bounds write discards)."""
    if n_rows <= 256:
        return n_rows
    for b in (256, 128, 64, 32, 16, 8):
        if n_rows % b == 0:
            return b
    return 8  # non-dividing: grid pads the tail block


def _row_spec(x, spec) -> P:
    """Where the rows of ``x [..., H]`` live under a mesh: the caller's
    ``spec`` (a model that shards the sequence dim passes its residual-stream
    spec), else batch on the data axes. The normalized dim is never sharded."""
    if spec is None:
        from ..distributed.sharding_utils import data_axes

        spec = P(data_axes() or None)
    return P(*tuple(spec)[:x.ndim - 1])


def _on_rows(call, x, params, spec):
    """Run ``call(x_local, *params)`` (the row-wise pallas_call) on the row
    shards of ``x``; the affine params ride replicated."""
    return shard_kernel(call, (x, *params),
                        (_row_spec(x, spec),) + (P(),) * len(params),
                        lambda f: f[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layer_norm(x, weight, bias, eps: float = 1e-5, spec: P = None):
    return _ln_fwd(x, weight, bias, eps, spec)


def _ln_call(x, weight, bias, eps):
    H = x.shape[-1]
    x2 = x.reshape(-1, H)
    R = x2.shape[0]
    br = min(_rows_block(R), R)
    y = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(pl.cdiv(R, br),),
        in_specs=[
            pl.BlockSpec((br, H), lambda i: (i, 0)),
            pl.BlockSpec((H,), lambda i: (0,)),
            pl.BlockSpec((H,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, H), x.dtype),
        interpret=pallas_interpret(),
        name="layer_norm_fwd",
    )(x2, weight, bias)
    return y.reshape(x.shape)


def _ln_fwd(x, weight, bias, eps, spec):
    return _on_rows(functools.partial(_ln_call, eps=eps), x, (weight, bias),
                    spec)


def _ln_fwd_rule(x, weight, bias, eps, spec):
    return _ln_fwd(x, weight, bias, eps, spec), (x, weight)


def _ln_bwd_rule(eps, spec, res, g):
    x, weight = res
    orig_shape = x.shape
    H = orig_shape[-1]
    x2 = x.reshape(-1, H)
    g2 = g.reshape(-1, H).astype(jnp.float32)
    xf = x2.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True) + eps)
    xhat = (xf - mean) * rstd
    wg = g2 * weight.astype(jnp.float32)
    dx = (
        wg - jnp.mean(wg, axis=-1, keepdims=True) - xhat * jnp.mean(wg * xhat, axis=-1, keepdims=True)
    ) * rstd
    dw = jnp.sum(g2 * xhat, axis=0)
    db = jnp.sum(g2, axis=0)
    return dx.reshape(orig_shape).astype(x2.dtype), dw.astype(weight.dtype), db.astype(weight.dtype)


fused_layer_norm.defvjp(_ln_fwd_rule, _ln_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_rms_norm(x, weight, eps: float = 1e-6, spec: P = None):
    return _rms_fwd(x, weight, eps, spec)


def _rms_call(x, weight, eps):
    H = x.shape[-1]
    x2 = x.reshape(-1, H)
    R = x2.shape[0]
    br = min(_rows_block(R), R)
    y = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(pl.cdiv(R, br),),
        in_specs=[
            pl.BlockSpec((br, H), lambda i: (i, 0)),
            pl.BlockSpec((H,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, H), x.dtype),
        interpret=pallas_interpret(),
        name="rms_norm_fwd",
    )(x2, weight)
    return y.reshape(x.shape)


def _rms_fwd(x, weight, eps, spec):
    return _on_rows(functools.partial(_rms_call, eps=eps), x, (weight,), spec)


def _rms_fwd_rule(x, weight, eps, spec):
    return _rms_fwd(x, weight, eps, spec), (x, weight)


def _rms_bwd_rule(eps, spec, res, g):
    x, weight = res
    orig_shape = x.shape
    H = orig_shape[-1]
    x2 = x.reshape(-1, H)
    g2 = g.reshape(-1, H).astype(jnp.float32)
    xf = x2.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    xhat = xf * rstd
    wg = g2 * weight.astype(jnp.float32)
    dx = (wg - xhat * jnp.mean(wg * xhat, axis=-1, keepdims=True)) * rstd
    dw = jnp.sum(g2 * xhat, axis=0)
    return dx.reshape(orig_shape).astype(x2.dtype), dw.astype(weight.dtype)


fused_rms_norm.defvjp(_rms_fwd_rule, _rms_bwd_rule)
