"""The gated delta rule (Yang et al., arXiv:2412.06464), two forms of one
recurrence over a per-head float32 state ``S [dv, dk]``::

    S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T ,   o_t = S_t q_t

with ``a_t = exp(g_t)`` the decay and ``b_t`` the write strength of token
``t``. Neither form leaves a term out; a test holds both to the token-by-
token recurrence.

``gdn_chunked`` — prefill and extend: ``C`` tokens at a time (the chunkwise
form of arXiv:2406.06484 section 3 with the decay of 2412.06464 section 3.3).
Inside a chunk, with ``G_t = exp(sum_{s<=t} g_s)``, the rows ``u_t`` that
make ``S_t = G_t S_0 + sum_{s<=t} (G_t / G_s) u_s k_s^T`` solve the
unit-lower-triangular system ``(I + A) U = b V - (b G K) S_0^T``,
``A[t, s] = b_t (G_t / G_s) (k_t . k_s)`` for ``s < t``; then
``O = (G Q) S_0^T + tril((Q K^T) (G_t / G_s)) U`` and
``S_C = G_C S_0 + U^T ((G_C / G) K)``. Every ratio is the exponential of a
difference that is <= 0 where it is used, so nothing overflows. Float32
throughout, matmuls at precision "highest" (they are 3% of a layer's FLOPs
beside its projections). A token with ``b = 0, g = 0`` changes nothing:
that is how padding behind a prompt's last real token is passed. Plain XLA:
the work is small batched matmuls and one triangular solve.

``gdn_step`` — decode, one token a slot: the recurrence itself, in place on
the PACKED state the serving cache keeps (``pack_state``): ``[rows, H / hg,
dk, hg * dv]``, ``hg`` heads side by side in the lane dimension so that it
is a whole number of 128-lane rows (192-wide values: two heads, 384 lanes;
the natural ``[H, dv, dk]`` would pad its 96 lanes to 128 in memory, a third
more bytes held and moved). On the TPU a Pallas kernel (``gdn_decode_step``):
one grid step a (slot, block of head groups), the state block read once and
written once where it lies (``input_output_aliases``); elsewhere the same
arithmetic in ``jax.numpy`` (``_step_oracle``).
``tier.default_paged_impl`` says which, as for the paged attend.

Both forms take the decay either as ONE value a head (``g [..., H]``, the
gated delta rule as published) or as one a KEY CHANNEL (``g [..., H, dk]``,
Kimi Delta Attention, arXiv:2510.26692): ``S_t = S_{t-1} diag(a_t) + b_t
(v_t - S_{t-1} diag(a_t) k_t) k_t^T``, column ``c`` of the state decaying by
its own ``a_t[c]``. In the step that is one more column operand beside ``k``
and ``q`` (the packed state's ``dk`` rows each take their own factor). In
the chunked form the decay no longer factors out of ``k_t . k_s``: the
scores become ``sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])``, contracted
directly over ``[C, C, dk]`` (so ``C`` is kept small, 16-32), every exponent
still a difference <= 0; the rest of the algebra is the same with ``G`` a
vector.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.place import pallas_interpret
from .flash_attention import LANES
from .tier import default_paged_impl

_HI = lax.Precision.HIGHEST
#: bytes of one state block of the step kernel (it holds four: in and out,
#: double-buffered, beside a few block-sized temporaries)
_STEP_BLOCK_BYTES = 1 << 20


# ------------------------------------------------------------ packed state

def head_group(num_heads: int, dv: int) -> int:
    """Heads side by side in one packed row: the fewest that make the row a
    whole number of 128-lane rows, 1 where the heads do not divide."""
    hg = math.lcm(dv, LANES) // dv
    return hg if num_heads % hg == 0 else 1


def packed_shape(num_heads: int, dk: int, dv: int):
    hg = head_group(num_heads, dv)
    return (num_heads // hg, dk, hg * dv)


def pack_state(S):
    """``[..., H, dv, dk]`` -> ``[..., H / hg, dk, hg * dv]``."""
    *lead, H, dv, dk = S.shape
    hg = head_group(H, dv)
    n = len(lead)
    S = S.reshape(*lead, H // hg, hg, dv, dk)
    S = S.transpose(*range(n), n, n + 3, n + 1, n + 2)
    return S.reshape(*lead, H // hg, dk, hg * dv)


def unpack_state(P, num_heads: int):
    """The inverse of ``pack_state``."""
    *lead, G, dk, L = P.shape
    hg = num_heads // G
    n = len(lead)
    P = P.reshape(*lead, G, dk, hg, L // hg)
    return P.transpose(*range(n), n, n + 2, n + 3, n + 1) \
        .reshape(*lead, num_heads, L // hg, dk)


# ------------------------------------------------------------ chunked form

# jitted so that a model's layers share one trace and one lowering (as
# ``_step_call`` below: a program's text and the seconds to lower it would
# otherwise grow with every layer's copy of the scan's body)
@functools.partial(jax.jit, static_argnames="chunk")
def gdn_chunked(q, k, v, g, beta, S0, chunk: int = 64, cuts=None):
    """``q, k [T, H, dk]``, ``v [T, H, dv]``, ``beta [T, H]``, ``g [T, H]``
    (a decay a head) or ``[T, H, dk]`` (one a key channel), start state
    ``S0 [H, dv, dk]``, all float32: ``(o [T, H, dv], S_T)``.

    With ``cuts [n]`` (int32, run-time values in ``[0, T]``) also, third,
    the state after the last token before each cut, ``[n, H, dv, dk]``: the
    scan hands it out from inside the chunk that holds the cut. Nothing new
    is solved there: the rows ``U`` are causal, so the state after ``j``
    tokens of a chunk is the chunk-end formula over the rows before ``j``,
    ``S_j = G_j S_0 + U[:j]^T ((G_j / G[:j]) K[:j])`` (``j = 0``: ``S_0``;
    ``j = C``: the chunk's end state), one more ``[C, dv] x [C, dk]``
    product a cut and chunk."""
    T, H, dk = q.shape
    C = min(chunk, T)
    pad = -T % C
    if pad:     # b = 0, g = 0: the state passes the padding unchanged
        q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    n = (T + pad) // C
    split = lambda a: jnp.moveaxis(a.reshape((n, C) + a.shape[1:]), 2, 1)
    t = jnp.arange(C)
    incl = t[:, None] >= t[None, :]
    strict = t[:, None] > t[None, :]
    mm = functools.partial(jnp.einsum, precision=_HI)
    channel = g.ndim == 3
    wanted = cuts is not None
    cuts = jnp.clip(cuts, 0, T).astype(jnp.int32) if wanted \
        else jnp.zeros((0,), jnp.int32)
    # the chunk that holds each cut and the tokens of it before the cut
    # (in [1, C]; 0 only for a cut at the call's first token)
    cut_chunk = jnp.maximum(cuts - 1, 0) // C
    cut_local = cuts - cut_chunk * C
    over = lambda a, like: a.reshape(a.shape + (1,) * (like.ndim - a.ndim))

    def at_cuts(i, S, Sc, U, kc, gam):
        """``Sc [n, H, dv, dk]`` with the state at every cut that lies in
        chunk ``i`` (start state ``S``, solved rows ``U``, decays summed to
        ``gam [H, C]`` or ``[H, C, dk]``). Computed in every chunk and kept
        by a select: a ``lax.cond`` on "a cut lies in this chunk" read 1.6
        ms an admission SLOWER on the chip at ``C = 64`` (PERF.md section 6,
        PR 35), and under ``vmap`` it is a select anyway."""
        # G_j: the decay summed over the j tokens before the cut
        gj = jnp.moveaxis(jnp.take(gam, jnp.maximum(cut_local - 1, 0),
                                   axis=1), 1, 0)         # [n, H(, dk)]
        gj = jnp.where(over(cut_local > 0, gj), gj, 0.0)
        gj = gj[:, :, None] if channel else gj[:, :, None, None]
        w = jnp.exp(jnp.where(                  # G_j / G_s, rows s < j alone
            (t[None, :] < cut_local[:, None])[:, None, :, None],
            gj - (gam if channel else gam[..., None]), -jnp.inf))
        Sj = jnp.exp(gj) * S + mm("hcv,nhck->nhvk", U, kc * w)
        return jnp.where(over(i == cut_chunk, Sj), Sj, Sc)

    def step(carry, xs):
        S, Sc = carry
        i, qc, kc, vc, gc, bc = xs       # [H, C, dk|dv], [H, C]
        gam = jnp.cumsum(gc, axis=-1)
        D = jnp.exp(jnp.where(incl, gam[:, :, None] - gam[:, None, :],
                              -jnp.inf))                        # [H, t, s]
        A = jnp.where(strict, bc[:, :, None] * D * mm("hck,hsk->hcs", kc, kc),
                      0.0)
        rhs = jnp.concatenate([bc[..., None] * vc,
                               (bc * jnp.exp(gam))[..., None] * kc], axis=-1)
        sol = lax.linalg.triangular_solve(
            A + jnp.eye(C, dtype=A.dtype), rhs, left_side=True, lower=True,
            unit_diagonal=True)
        dv = vc.shape[-1]
        U = sol[..., :dv] - mm("hck,hvk->hcv", sol[..., dv:], S)
        O = mm("hck,hvk->hcv", qc * jnp.exp(gam)[..., None], S) \
            + mm("hcs,hsv->hcv", D * mm("hck,hsk->hcs", qc, kc), U)
        if wanted:
            Sc = at_cuts(i, S, Sc, U, kc, gam)
        gC = gam[:, -1]
        S = jnp.exp(gC)[:, None, None] * S + mm(
            "hcv,hck->hvk", U, kc * jnp.exp(gC[:, None] - gam)[..., None])
        return (S, Sc), O

    def step_channel(carry, xs):
        S, Sc = carry
        i, qc, kc, vc, gc, bc = xs       # gc [H, C, dk]: a decay a channel
        gam = jnp.cumsum(gc, axis=1)
        # R[t, s, c] = G_t[c] / G_s[c] for s <= t, 0 behind t
        R = jnp.exp(jnp.where(incl[None, :, :, None],
                              gam[:, :, None, :] - gam[:, None, :, :],
                              -jnp.inf))
        KK = jnp.sum(kc[:, :, None, :] * kc[:, None, :, :] * R, axis=-1)
        QK = jnp.sum(qc[:, :, None, :] * kc[:, None, :, :] * R, axis=-1)
        A = jnp.where(strict, bc[:, :, None] * KK, 0.0)
        eg = jnp.exp(gam)
        rhs = jnp.concatenate([bc[..., None] * vc,
                               bc[..., None] * eg * kc], axis=-1)
        sol = lax.linalg.triangular_solve(
            A + jnp.eye(C, dtype=A.dtype), rhs, left_side=True, lower=True,
            unit_diagonal=True)
        dv = vc.shape[-1]
        U = sol[..., :dv] - mm("hck,hvk->hcv", sol[..., dv:], S)
        O = mm("hck,hvk->hcv", qc * eg, S) + mm("hcs,hsv->hcv", QK, U)
        if wanted:
            Sc = at_cuts(i, S, Sc, U, kc, gam)
        S = eg[:, -1][:, None, :] * S + mm(
            "hcv,hck->hvk", U, kc * jnp.exp(gam[:, -1:] - gam))
        return (S, Sc), O

    Sc0 = jnp.zeros((cuts.shape[0],) + S0.shape, S0.dtype)
    (S, Sc), O = lax.scan(
        step_channel if channel else step, (S0, Sc0),
        (jnp.arange(n),) + tuple(map(split, (q, k, v, g, beta))))
    o = jnp.moveaxis(O, 1, 2).reshape(n * C, H, -1)[:T]
    return (o, S, Sc) if wanted else (o, S)


# ------------------------------------------------------- the recurrent step

def _step_oracle(q, k, v, g, beta, state):
    B, H, dk = q.shape
    S = unpack_state(state[:B], H)                         # [B, H, dv, dk]
    S = S * (jnp.exp(g)[:, :, None, :] if g.ndim == 3
             else jnp.exp(g)[:, :, None, None])
    u = beta[..., None] * (v - jnp.einsum("bhvk,bhk->bhv", S, k,
                                          precision=_HI))
    S = S + u[..., None] * k[:, :, None, :]
    o = jnp.einsum("bhvk,bhk->bhv", S, q, precision=_HI)
    return o, lax.dynamic_update_slice_in_dim(state, pack_state(S), 0, axis=0)


def _groups_per_block(G: int, dk: int, lanes: int) -> int:
    fit = max(1, _STEP_BLOCK_BYTES // (dk * lanes * 4))
    return max(d for d in range(1, G + 1) if G % d == 0 and d <= fit)


def _step_kernel(qT_ref, kT_ref, v_ref, a_ref, b_ref, s_ref, o_ref, so_ref,
                 *, gb: int, hg: int, dv: int, channel: bool):
    """Grid (slot, block of ``gb`` head groups). ``s_ref [1, gb, dk, hg*dv]``
    the packed state block; ``qT / kT [1, 1, dk, gb*hg]`` the block's heads'
    q and k as columns; ``v / a / b [1, 1, gb, hg*dv]`` the values, decays
    and write strengths with a head's scalar repeated over its ``dv``
    lanes; with ``channel`` the decays come as columns like q and k,
    ``a [1, 1, dk, gb*hg]``, one factor a state row."""
    dk, L = s_ref.shape[2], s_ref.shape[3]
    lane = lax.broadcasted_iota(jnp.int32, (1, L), 1)

    def columns(ref, grp):
        # [dk, L]: head j of the group in lanes [j * dv, (j + 1) * dv)
        x = jnp.broadcast_to(ref[0, 0, :, grp * hg:grp * hg + 1], (dk, L))
        for j in range(1, hg):
            x = jnp.where(lane >= j * dv,
                          ref[0, 0, :, grp * hg + j:grp * hg + j + 1], x)
        return x

    for grp in range(gb):
        K, Q = columns(kT_ref, grp), columns(qT_ref, grp)
        S = s_ref[0, grp] * (columns(a_ref, grp) if channel
                             else a_ref[0, 0, grp:grp + 1, :])
        u = b_ref[0, 0, grp:grp + 1, :] * (
            v_ref[0, 0, grp:grp + 1, :]
            - jnp.sum(S * K, axis=0, keepdims=True))
        S = S + K * u
        o_ref[0, 0, grp:grp + 1, :] = jnp.sum(S * Q, axis=0, keepdims=True)
        so_ref[0, grp] = S


# jitted so that a model's layers share one trace and one Mosaic lowering
# (as kernels/paged_attention._decode_call)
@functools.partial(jax.jit, static_argnames="interpret")
def _step_call(q, k, v, g, beta, state, *, interpret: bool):
    B, H, dk = q.shape
    dv = v.shape[-1]
    _, G, _, L = state.shape
    hg = H // G
    gb = _groups_per_block(G, dk, L)
    nb = G // gb
    cols = lambda a: a.reshape(B, nb, gb * hg, dk).transpose(0, 1, 3, 2)
    lanes = lambda a: jnp.broadcast_to(      # a head's scalar over its lanes
        a[..., None], (B, H, dv)).reshape(B, nb, gb, L)
    row = pl.BlockSpec((1, 1, gb, L), lambda b, i: (b, i, 0, 0))
    col = pl.BlockSpec((1, 1, dk, gb * hg), lambda b, i: (b, i, 0, 0))
    blk = pl.BlockSpec((1, gb, dk, L), lambda b, i: (b, i, 0, 0))
    channel = g.ndim == 3
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, gb=gb, hg=hg, dv=dv, channel=channel),
        grid=(B, nb),
        in_specs=[col, col, row, col if channel else row, row, blk],
        out_specs=[row, blk],
        out_shape=[jax.ShapeDtypeStruct((B, nb, gb, L), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="gdn_decode_step",
    )(cols(q), cols(k), v.reshape(B, nb, gb, L),
      cols(jnp.exp(g)) if channel else lanes(jnp.exp(g)), lanes(beta), state)
    return o.reshape(B, H, dv), state


def gdn_step(q, k, v, g, beta, state):
    """One token a slot: ``q, k [B, H, dk]``, ``v [B, H, dv]``, ``beta
    [B, H]``, ``g [B, H]`` or (a decay a key channel) ``[B, H, dk]``
    (float32) against rows ``[0, B)`` of the packed ``state [rows, H / hg,
    dk, hg * dv]``: ``(o [B, H, dv], state)`` with those rows advanced and
    every other row as it was."""
    if default_paged_impl() == "oracle":
        return _step_oracle(q, k, v, g, beta, state)
    return _step_call(q, k, v, g, beta, state, interpret=pallas_interpret())
