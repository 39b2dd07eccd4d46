"""Fused AdamW Pallas kernel (phi/kernels/gpu/fused_adam_kernel.cu analog):
moment update + bias correction + decoupled decay + param update in one HBM
pass per tensor. XLA fuses most of this already; the kernel removes the
remaining intermediate materializations for the biggest params.

Layout: the flat tensor is padded to a (rows, 128)-lane grid and streamed
through VMEM in row blocks; hyperparameters ride in SMEM as scalars."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core.place import pallas_interpret
from .mesh import shard_kernel

_LANES = 128
_BLOCK_ROWS = 512  # 512*128*4B = 256KB per operand; 7 operands ≈ 1.8MB VMEM


def _adamw_kernel(hyp_ref, p_ref, g_ref, m_ref, v_ref, p_out, m_out, v_out):
    lr, b1, b2 = hyp_ref[0], hyp_ref[1], hyp_ref[2]
    eps, wd, b1p, b2p = hyp_ref[3], hyp_ref[4], hyp_ref[5], hyp_ref[6]
    # all casts happen HERE, in VMEM: operands stream in at their NATIVE
    # dtypes (bf16 grads/moments under moment_dtype='bfloat16') — a
    # pre-kernel astype would materialize full f32 copies in HBM (~20 GB of
    # traffic per step at 674M params), which this kernel exists to avoid
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:].astype(jnp.float32) + (1 - b1) * g
    v = b2 * v_ref[:].astype(jnp.float32) + (1 - b2) * g * g
    m_hat = m / (1 - b1p)
    v_hat = v / (1 - b2p)
    p = p * (1.0 - lr * wd) - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    p_out[:] = p.astype(p_out.dtype)
    m_out[:] = m.astype(m_out.dtype)
    v_out[:] = v.astype(v_out.dtype)


def fused_adamw_update(param, grad, m, v, *, lr, beta1, beta2, eps, weight_decay, beta1_pow, beta2_pow,
                       spec: P = P()):
    """One fused step for a single tensor; returns (new_param, new_m, new_v).
    beta*_pow are the *new* accumulated powers (beta^t). The update is
    elementwise, so under a mesh all four operands go in and out on one
    ``spec`` — the layout the optimizer state is stored in."""
    hyp = jnp.stack(
        [
            jnp.asarray(lr, jnp.float32).reshape(()),
            jnp.float32(beta1),
            jnp.float32(beta2),
            jnp.float32(eps),
            jnp.float32(weight_decay),
            jnp.asarray(beta1_pow, jnp.float32).reshape(()),
            jnp.asarray(beta2_pow, jnp.float32).reshape(()),
        ]
    )
    return shard_kernel(_adamw_call, (hyp, param, grad, m, v),
                        (P(),) + (spec,) * 4, lambda f: (f[1],) * 3)


def _adamw_call(hyp, param, grad, m, v):
    shape = param.shape
    n = param.size
    rows = -(-n // _LANES)
    pad = rows * _LANES - n

    def to2d(a, dtype):
        a = a.reshape(-1).astype(dtype)
        if pad:
            a = jnp.pad(a, (0, pad))
        return a.reshape(rows, _LANES)

    br = min(_BLOCK_ROWS, rows)
    blk = lambda: pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    new_p, new_m, new_v = pl.pallas_call(
        _adamw_kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            blk(),
            blk(),
            blk(),
            blk(),
        ],
        out_specs=[blk(), blk(), blk()],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), param.dtype),
            jax.ShapeDtypeStruct((rows, _LANES), m.dtype),
            jax.ShapeDtypeStruct((rows, _LANES), v.dtype),
        ],
        interpret=pallas_interpret(),
        name="fused_adamw",
    )(hyp, to2d(param, param.dtype), to2d(grad, grad.dtype), to2d(m, m.dtype), to2d(v, v.dtype))

    unflat = lambda a: a.reshape(-1)[:n].reshape(shape)
    return unflat(new_p), unflat(new_m), unflat(new_v)
