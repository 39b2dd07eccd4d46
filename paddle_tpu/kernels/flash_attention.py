"""Flash attention, Pallas TPU (phi/kernels/gpu/flash_attn_kernel.cu analog).

Blockwise-softmax attention with O(S) memory: forward keeps running
(max, sum, acc) per query block while streaming key blocks through VMEM;
backward is the standard two-kernel split (dq; dk+dv) recomputing P from the
saved logsumexp. Layout is paddle's flash layout [B, S, H, D]; heads fold
into the grid's leading axis so each program owns one (batch, head) pair and
the MXU sees [block_q, D] x [D, block_k] tiles.

Causal masking skips fully-masked key blocks via the loop bound (not just a
mask), halving causal FLOPs — same trick as the CUDA kernel's early exit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core.place import pallas_interpret
from .mesh import shard_kernel

NEG_INF = -1e30
LOG2E = 1.4426950408889634  # softmax runs in the exp2 domain (see _fwd_kernel)
# TPU vector lanes: scalar-per-row outputs (lse, delta) are broadcast across a
# 128-wide trailing dim so their blocks satisfy Mosaic's (8, 128) tiling rule —
# same layout as jax.experimental.pallas.ops.tpu.flash_attention (MIN_BLOCK_SIZE).
LANES = 128



# ------- one grid step of a flash kernel behind a cached context -------
def last_key_block(start, qi, block_q: int, block_k: int, num_kb: int):
    """Key blocks [0, this) hold a position some query of block ``qi``
    sees, where the first query stands at key position ``start``."""
    return jnp.minimum((start + (qi + 1) * block_q + block_k - 1) // block_k,
                       num_kb)


def online_softmax_init(m_scr, l_scr, acc_scr):
    """The running maximum, sum and accumulator before a row's first key."""
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def online_softmax_step(scores, values, m_scr, l_scr, acc_scr, *, rows: int,
                        chunks: int):
    """Fold ALL the keys of a grid step into the running maximum, sum and
    accumulator (float32 VMEM scratch, row for row) of its ``chunks * rows``
    score rows: the step of ``latent_attention.latent_flash`` and of
    ``paged_attention.extend_flash``, which differ in how a chunk's scores
    are made. ``scores(r)`` is chunk ``r``'s masked float32 tile ``[rows,
    keys]`` in the exp2 domain, ``values()`` the step's ``[keys, Dv]``. The
    chunks share nothing, and chunk r + 1's products are written down
    BEFORE chunk r's softmax so that the scheduler (one basic block: the
    loop is unrolled) runs the matrix unit's phase of one under the vector
    unit's phase of the other (PERF.md section 6, PR 44). A row whose keys
    so far are all masked carries the mask's value as its maximum and sums
    garbage; the first visible key rescales that to nothing, and every row
    a caller reads has one (its own position)."""

    def fold(r, s):
        qr, v = pl.ds(r * rows, rows), values()
        m, l = m_scr[qr, :1], l_scr[qr, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        acc_scr[qr] = acc_scr[qr] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[qr] = jnp.broadcast_to(m_new, (rows, m_scr.shape[1]))
        l_scr[qr] = jnp.broadcast_to(
            l * alpha + jnp.sum(p, axis=-1, keepdims=True),
            (rows, l_scr.shape[1]))

    s = scores(0)
    for r in range(chunks):
        ahead = scores(r + 1) if r + 1 < chunks else None
        fold(r, s)
        s = ahead


# ---------------- forward ----------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, num_kb: int, block_q: int, block_k: int, causal: bool, scale: float):
    """Grid (BH, num_q, num_k): K/V blocks STREAM through the trailing
    (sequential) grid dim, so VMEM holds only [block] tiles — never full-S
    K/V. Running (max, sum, acc) live in VMEM scratch across k iterations;
    the epilogue writes o/lse on the last relevant k block."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bq, d = q_ref.shape[1], q_ref.shape[2]
    # causal: key blocks strictly after the diagonal contribute nothing
    kb_hi = ((qi + 1) * bq + jnp.int32(block_k - 1)) // jnp.int32(block_k) if causal else num_kb

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki < kb_hi)
    def _compute():
        # MXU dots take the native (bf16) operands — fp32 inputs run the MXU
        # at a fraction of peak; fp32 lives only in accumulators/stats
        # (preferred_element_type pins the accumulation dtype). Softmax runs
        # in the exp2 domain: log2(e) folds into the dot's scale, saving a
        # full [bq, bk] multiply pass per block (stats/lse stay log2-domain;
        # the bwd kernels use the same domain).
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * jnp.float32(scale * LOG2E)  # [bq, bk], log2-domain
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m = m_scr[:, 0]
        l = l_scr[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jax.lax.broadcast_in_dim(m_new, m_scr.shape, (0,))
        l_scr[...] = jax.lax.broadcast_in_dim(l_new, l_scr.shape, (0,))

    @pl.when(ki == num_kb - 1)
    def _epilogue():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = jax.lax.broadcast_in_dim(
            m_scr[:, 0] + jnp.log2(l_safe), (bq, LANES), (0,))


def _fwd_call(qt, kt, vt, causal: bool, scale: float, block_q: int, block_k: int):
    """The forward kernel over folded ``[B*H, S, D]`` operands; returns
    ``(o [BH, S, D], lse [BH, S])`` (lse in the log2 domain)."""
    BH, S, D = qt.shape
    num_kb = S // block_k
    grid = (BH, S // block_q, num_kb)

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, num_kb=num_kb, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), qt.dtype),
            jax.ShapeDtypeStruct((BH, S, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # the 2048x1024 fp32 score tile + bf16 p + double-buffered K/V
            # brush past the 16 MiB default scoped-vmem cap; v5e has 128 MiB
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=pallas_interpret(),
        name="flash_fwd",
    )(qt, kt, vt)
    return o, lse[..., 0]


# ---------------- backward ----------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
               *, num_kb, block_k, causal, scale):
    """Grid (BH, num_q, num_k): K/V stream through the trailing dim, dq
    accumulates in VMEM scratch."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bq, d = q_ref.shape[1], q_ref.shape[2]
    kb_hi = ((qi + 1) * bq + jnp.int32(block_k - 1)) // jnp.int32(block_k) if causal else num_kb

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(ki < kb_hi)
    def _compute():
        # native-dtype MXU operands + log2-domain p — see _fwd_kernel
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]  # [bq, 1] (lanes-broadcast layout), log2-domain
        delta = delta_ref[0][:, :1]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * jnp.float32(scale * LOG2E)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp2(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * jnp.float32(scale)).astype(k.dtype)
        dq_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == num_kb - 1)
    def _epilogue():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, num_qb, block_q, causal, scale):
    """Grid (BH, num_k, num_q): Q/dO stream through the trailing dim, dk/dv
    accumulate in VMEM scratch."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    bk, d = k_ref.shape[1], k_ref.shape[2]
    # causal: query blocks before this key block contribute nothing
    qb_lo = (ki * bk) // block_q if causal else 0

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(qi >= qb_lo)
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]  # [bq, 1], log2-domain
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * jnp.float32(scale * LOG2E)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp2(s - lse)  # [bq, bk]
        dv_scr[...] += jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * jnp.float32(scale)).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _epilogue():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_call(causal, scale, block_q, block_k, qt, kt, vt, o, lse, do):
    """The two backward kernels over folded ``[B*H, S, D]`` operands
    (``lse [BH, S]``); returns ``(dq, dk, dv)`` in the same layout."""
    BH, S, D = qt.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [BH, S]
    # lanes-broadcast layout for the per-row scalars (see LANES above)
    lse = jnp.broadcast_to(lse[..., None], (BH, S, LANES))
    delta = jnp.broadcast_to(delta[..., None], (BH, S, LANES))
    num_kb = S // block_k
    num_qb = S // block_q
    seq_par = ("parallel", "parallel", "arbitrary")

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_kb=num_kb, block_k=block_k, causal=causal, scale=scale),
        grid=(BH, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), qt.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=seq_par),
        interpret=pallas_interpret(),
        name="flash_bwd_dq",
    )(qt, kt, vt, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_qb=num_qb, block_q=block_q, causal=causal, scale=scale),
        grid=(BH, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), kt.dtype),
            jax.ShapeDtypeStruct((BH, S, D), vt.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=seq_par),
        interpret=pallas_interpret(),
        name="flash_bwd_dkv",
    )(qt, kt, vt, do, lse, delta)
    return dq, dk, dv


def _pick_blocks(S: int, role: str = "fwd"):
    # measured on v5e (D=128): bigger blocks win — fewer grid steps amortize
    # the per-block epilogue. S=1024: (1024,1024) beats (512,512) by ~29%;
    # S=4096: fwd (2048,1024) beats (1024,1024) by ~18% (the fp32 score
    # tile 2048x1024x4B = 8 MiB still fits VMEM). The BACKWARD kernels hold
    # two score-sized tiles (p and the ds/dp chain), so bq caps at 1024
    # there — fwd/bwd block choices are independent (residuals are full
    # [BH, S, D] arrays; only the block-free lse layout is shared).
    bq_cap = 2048 if role == "fwd" else 1024
    bq = next((b for b in (bq_cap, 1024, 512, 256, 128, 64, 32, 16, 8)
               if b <= bq_cap and S % b == 0), None)
    bk = next((b for b in (1024, 512, 256, 128, 64, 32, 16, 8)
               if S % b == 0), None)
    if bq is None or bk is None:
        return None, None
    return min(bq, S), min(bk, S)


def _select_blocks(BH: int, S: int, D: int, dtype, causal: bool, role: str = "fwd"):
    """Heuristic default, upgraded by the autotune cache when tuning is on
    (phi/kernels/autotune AutoTuneBase::PickBestAlgorithm analog). Measured
    configs are keyed by (BH, S, D, dtype, causal, role); fwd and bwd pick
    independently."""
    from . import autotune

    default = _pick_blocks(S, role)
    if default[0] is None:
        return default
    bq_cap = 2048 if role == "fwd" else 1024
    candidates = [(bq, bk)
                  for bq in (2048, 1024, 512, 256, 128)
                  if bq <= bq_cap and S % bq == 0
                  for bk in (1024, 512, 256, 128) if S % bk == 0]
    if default not in candidates:
        # measurement must be able to pick (and so can only improve on) the
        # heuristic default, else enabling autotune could lock in a slower cfg
        candidates.insert(0, default)

    def make_run(cfg):
        bq, bk = cfg
        qt = jnp.zeros((BH, S, D), dtype)
        if role == "bwd":
            # measure the kernels the pick actually configures: dq + dkv
            lse = jnp.zeros((BH, S), jnp.float32)

            def bwd_fn(qt):
                dq, dk, dv = _bwd_call(causal, 1.0, bq, bk,
                                       qt, qt, qt, qt, lse, qt)
                # consume all three grads so neither pallas_call is DCE'd —
                # the pick must price dq AND dkv together
                return (dq[0, 0, 0].astype(jnp.float32)
                        + dk[0, 0, 0].astype(jnp.float32)
                        + dv[0, 0, 0].astype(jnp.float32))

            fn = jax.jit(bwd_fn)
            return lambda: fn(qt)
        fn = jax.jit(lambda qt: _fwd_call(qt, qt, qt, causal, 1.0, bq, bk)[0])
        return lambda: fn(qt)

    picked = autotune.pick_best(
        "flash_attention", (BH, S, D, str(jnp.dtype(dtype)), bool(causal), role),
        candidates, make_run, default=default)
    return tuple(picked)


# Under a mesh the kernels see local shards: batch on the data axes, heads on
# mp (the layout models/gpt.py head_spec pins q/k/v to), full S and D. The
# shard_map sits inside each custom_vjp rule; residuals cross it as
# [B, H, S, D] / [B, H, S] so their sharding is expressible (a merged B*H dim
# sharded on two axes is not).
def _specs():
    """Wished specs for [B, S, H, D], [B, H, S, D] and [B, H, S] operands."""
    from ..distributed.sharding_utils import data_axes

    b = data_axes() or None
    return P(b, None, "mp", None), P(b, "mp", None, None), P(b, "mp", None)


def _local_fwd(q, k, v, causal, scale):
    """[B, S, H, D] local shards -> (out [B, S, H, D], residuals)."""
    B, S, H, D = q.shape
    bq, bk = _select_blocks(B * H, S, D, q.dtype, causal)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))  # [B, H, S, D]
    o, lse = _fwd_call(qt.reshape(B * H, S, D), kt.reshape(B * H, S, D),
                       vt.reshape(B * H, S, D), causal, scale, bq, bk)
    o = o.reshape(B, H, S, D)
    return jnp.swapaxes(o, 1, 2), (qt, kt, vt, o, lse.reshape(B, H, S))


def _local_bwd(qt, kt, vt, o, lse, g, causal, scale):
    B, H, S, D = qt.shape
    bq, bk = _select_blocks(B * H, S, D, qt.dtype, causal, role="bwd")
    fold = lambda x: x.reshape(B * H, S, D)
    dq, dk, dv = _bwd_call(causal, scale, bq, bk, fold(qt), fold(kt),
                           fold(vt), fold(o), lse.reshape(B * H, S),
                           fold(jnp.swapaxes(g, 1, 2)))
    unfold = lambda x: jnp.swapaxes(x.reshape(B, H, S, D), 1, 2)
    return unfold(dq), unfold(dk), unfold(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, scale):
    bshd, _, _ = _specs()
    return shard_kernel(
        lambda q, k, v: _local_fwd(q, k, v, causal, scale)[0],
        (q, k, v), (bshd,) * 3, lambda f: f[0])


def _flash_fwd_rule(q, k, v, causal, scale):
    bshd, _, _ = _specs()

    def out_specs(f):
        b, _, h, _ = f[0]
        return f[0], (P(b, h, None, None),) * 4 + (P(b, h, None),)

    return shard_kernel(
        lambda q, k, v: _local_fwd(q, k, v, causal, scale),
        (q, k, v), (bshd,) * 3, out_specs)


def _flash_bwd_rule(causal, scale, res, g):
    bshd, bhsd, bhs = _specs()
    return shard_kernel(
        lambda *a: _local_bwd(*a, causal, scale), (*res, g),
        (bhsd,) * 4 + (bhs, bshd), lambda f: (f[5],) * 3)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_fwd(q, k, v, causal: bool = False, scale: float = None):
    """[B, S, H, D] flash attention; falls back to None-signal if unsupported
    (caller uses the jnp reference path)."""
    from jax.ad_checkpoint import checkpoint_name

    B, S, H, D = q.shape
    if _pick_blocks(S)[0] is None:
        raise ValueError(f"flash_attention: seq len {S} not divisible by a supported block")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    # named for the 'save_flash' remat policy (fleet/recompute.py): a
    # checkpointed block can keep THIS output resident so its backward
    # replays only the cheap projections/elementwise, not the flash kernel
    return checkpoint_name(_flash(q, k, v, causal, scale), "flash_out")
