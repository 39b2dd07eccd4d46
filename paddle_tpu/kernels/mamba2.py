"""The Mamba-2 recurrence (state-space duality, Dao & Gu, arXiv:2405.21060),
two forms of one recurrence over a per-head float32 state ``S [P, N]`` (``P``
the head's width, ``N`` the state's)::

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t ,   y_t = S_t C_t + D x_t

with ``a_t = exp(dt_t A)`` ONE decay a head (``A < 0``), ``dt_t > 0`` the
step, ``x_t [P]`` the head's input and ``B_t, C_t [N]`` shared by the
``H / G`` heads of a group. It is the gated delta rule of ``gated_delta.py``
without its correction ``- a S k k^T``: no rank-one term, so the chunked
form has no triangular solve. A token with ``dt = 0`` changes nothing
(``a = 1``, nothing written): that is how padding behind a prompt's last
real token, and a slot that runs no request, are passed.

``mamba2_chunked`` -- prefill and extend: ``C`` tokens at a time in matrix
products (the SSD form). Inside a chunk, with ``gam_t = sum_{r <= t} dt_r
A``::

    Y = ((C B^T) * L) (dt x) + exp(gam) (S_0 C^T)^T ,
        L[t, s] = exp(gam_t - gam_s) = exp(sum_{s < r <= t} dt_r A),  s <= t
    S_C = exp(gam_C) S_0 + (dt x)^T (exp(gam_C - gam) B)

``C B^T`` is computed once a GROUP and masked a head. Every decay is the
exponential of a difference of cumulative sums that is <= 0 where it is
used (float32), so ``exp`` never sees a positive argument. Float32
throughout, matmuls at precision "highest" (a few percent of a layer's
FLOPs beside its projections). ``cuts`` hand out the state before a given
token from inside the scan (``S_j = exp(gam_{j-1}) S_0 + (dt x)[:j]^T
(exp(gam_{j-1} - gam[:j]) B[:j])``), as ``gdn_chunked`` does, so that an
admission that takes snapshots stays one program. Plain XLA.

``mamba2_step`` -- decode, one token a slot: the recurrence itself, in place
on the PACKED state the serving cache keeps (``gated_delta.pack_state`` with
``dv = P``, ``dk = N``: ``[rows, H / hg, N, hg * P]``, ``hg`` heads side by
side in the lanes). On the TPU a Pallas kernel (``mamba2_decode_step``): one
grid step a (slot, block of packed rows), the state block read once and
written once where it lies (``input_output_aliases``), a group's ``B`` and
``C`` brought in as columns and spread over the lanes once for the group's
heads, the skip ``D x`` added inside; elsewhere the same
arithmetic in ``jax.numpy`` (``_step_oracle``). ``tier.default_paged_impl``
says which.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.place import pallas_interpret
from .gated_delta import (_groups_per_block, pack_state,  # noqa: F401
                          packed_shape, unpack_state)
from .tier import default_paged_impl

_HI = lax.Precision.HIGHEST


# ------------------------------------------------------------ chunked form

# jitted so that a model's layers share one trace and one lowering (as
# ``gated_delta.gdn_chunked``)
@functools.partial(jax.jit, static_argnames="chunk")
def mamba2_chunked(x, dt, A, B, C, D, S0, chunk: int = 128, cuts=None):
    """``x [T, H, P]``, ``dt [T, H]`` (the step, after its softplus; 0 for a
    token that is padding), ``A [H]`` (negative), ``B, C [T, G, N]`` (head
    ``h`` reads group ``h // (H / G)``), ``D [H]``, start state ``S0 [H, P,
    N]``, all float32: ``(y [T, H, P], S_T)``.

    With ``cuts [n]`` (int32, run-time values in ``[0, T]``) also, third,
    the state after the last token before each cut, ``[n, H, P, N]``, handed
    out from inside the chunk that holds the cut (``j = 0``: ``S_0``; ``j =
    C``: the chunk's end state): one more ``[C, P] x [C, N]`` product a cut
    and chunk."""
    T, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    rep = H // G
    Cn = min(chunk, T)
    pad = -T % Cn
    if pad:     # dt = 0: the state passes the padding unchanged
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    n = (T + pad) // Cn
    split = lambda a: jnp.moveaxis(a.reshape((n, Cn) + a.shape[1:]), 2, 1)
    t = jnp.arange(Cn)
    incl = t[:, None] >= t[None, :]
    mm = functools.partial(jnp.einsum, precision=_HI)
    wanted = cuts is not None
    cuts = jnp.clip(cuts, 0, T).astype(jnp.int32) if wanted \
        else jnp.zeros((0,), jnp.int32)
    # the chunk that holds each cut and the tokens of it before the cut
    # (in [1, C]; 0 only for a cut at the call's first token)
    cut_chunk = jnp.maximum(cuts - 1, 0) // Cn
    cut_local = cuts - cut_chunk * Cn
    heads = lambda a: jnp.repeat(a, rep, axis=0)    # [G, ...] -> [H, ...]

    def at_cuts(i, S, Sc, U, Bh, gam):
        """``Sc [n, H, P, N]`` with the state at every cut that lies in
        chunk ``i`` (start state ``S``, written rows ``U = dt x [H, C, P]``,
        ``Bh [H, C, N]``, decays summed to ``gam [H, C]``); computed in
        every chunk and kept by a select (``gdn_chunked.at_cuts``)."""
        gj = jnp.moveaxis(jnp.take(gam, jnp.maximum(cut_local - 1, 0),
                                   axis=1), 1, 0)             # [n, H]
        gj = jnp.where((cut_local > 0)[:, None], gj, 0.0)[:, :, None]
        w = jnp.exp(jnp.where(                  # rows s < j alone
            (t[None, :] < cut_local[:, None])[:, None, :],
            gj - gam[None], -jnp.inf))                        # [n, H, C]
        Sj = jnp.exp(gj)[..., None] * S + mm(
            "hcp,nhcd->nhpd", U, Bh[None] * w[..., None])
        return jnp.where((i == cut_chunk)[:, None, None, None], Sj, Sc)

    def step(carry, xs):
        S, Sc = carry
        i, xc, dc, Bc, Cc = xs      # [H, C, P], [H, C], [G, C, N] x 2
        gam = jnp.cumsum(dc * A[:, None], axis=-1)            # [H, C] <= 0
        L = jnp.exp(jnp.where(incl, gam[:, :, None] - gam[:, None, :],
                              -jnp.inf))                      # [H, t, s]
        U = dc[..., None] * xc                                # dt x
        CB = mm("gtd,gsd->gts", Cc, Bc)                       # once a group
        Bh, Ch = heads(Bc), heads(Cc)
        Y = mm("hts,hsp->htp", heads(CB) * L, U) \
            + jnp.exp(gam)[..., None] * mm("hpd,htd->htp", S, Ch)
        if wanted:
            Sc = at_cuts(i, S, Sc, U, Bh, gam)
        gC = gam[:, -1]
        S = jnp.exp(gC)[:, None, None] * S + mm(
            "hcp,hcd->hpd", U, Bh * jnp.exp(gC[:, None] - gam)[..., None])
        return (S, Sc), Y

    Sc0 = jnp.zeros((cuts.shape[0],) + S0.shape, S0.dtype)
    (S, Sc), Y = lax.scan(step, (S0, Sc0),
                          (jnp.arange(n),) + tuple(map(split, (x, dt, B, C))))
    y = jnp.moveaxis(Y, 1, 2).reshape(n * Cn, H, P)[:T] \
        + D[None, :, None] * x[:T]
    return (y, S, Sc) if wanted else (y, S)


# ------------------------------------------------------- the recurrent step

def _step_oracle(x, dt, A, B, C, D, state):
    Bn, H, P = x.shape
    rep = H // B.shape[1]
    S = unpack_state(state[:Bn], H)                        # [B, H, P, N]
    Bh, Ch = jnp.repeat(B, rep, axis=1), jnp.repeat(C, rep, axis=1)
    S = S * jnp.exp(dt * A)[..., None, None] \
        + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    y = jnp.sum(S * Ch[:, :, None, :], axis=-1) + D[None, :, None] * x
    return y, lax.dynamic_update_slice_in_dim(state, pack_state(S), 0, axis=0)


def _step_kernel(bc_ref, a_ref, u_ref, d_ref, s_ref, y_ref, so_ref, *,
                 span: int, ng: int):
    """Grid (slot, block of ``ng`` groups' packed rows). ``s_ref [1, ng *
    span, N, L]`` the packed state block (``L = hg * P`` lanes: ``hg`` heads
    side by side; ``span`` packed rows in a run share a group); ``a / u / d
    [1, 1, ng * span, L]`` the decays ``exp(dt A)``, the written rows ``dt
    x`` and the skips ``D x``, a head's scalar repeated over its ``P``
    lanes; ``bc_ref [1, 1, N, 2 * ng]`` the block's groups' ``B`` then ``C``
    as columns."""
    N, L = s_ref.shape[2], s_ref.shape[3]
    for j in range(ng):
        # a group's B and C, spread over the lanes ONCE for its heads
        Bc = jnp.broadcast_to(bc_ref[0, 0, :, j:j + 1], (N, L))
        Cc = jnp.broadcast_to(bc_ref[0, 0, :, ng + j:ng + j + 1], (N, L))
        for grp in range(j * span, (j + 1) * span):
            S = s_ref[0, grp] * a_ref[0, 0, grp:grp + 1, :] \
                + Bc * u_ref[0, 0, grp:grp + 1, :]
            y_ref[0, 0, grp:grp + 1, :] = jnp.sum(
                S * Cc, axis=0, keepdims=True) + d_ref[0, 0, grp:grp + 1, :]
            so_ref[0, grp] = S


# jitted so that a model's layers share one trace and one Mosaic lowering
# (as kernels/gated_delta._step_call)
@functools.partial(jax.jit, static_argnames="interpret")
def _step_call(x, dt, A, B, C, D, state, *, interpret: bool):
    Bn, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    _, R, _, L = state.shape            # R packed rows of hg heads a slot
    hg = H // R
    span = H // G // hg                 # packed rows that share a group
    if span < 1 or R % span:
        raise ValueError(f"mamba2_step: {H} heads in {G} groups do not pack "
                         f"{hg} a row")
    # whole groups a block, as many as the block's bytes allow
    ng = _groups_per_block(R // span, N * span, L)
    gb, nb = ng * span, R // (ng * span)
    lanes = lambda a: jnp.broadcast_to(      # a head's values over its lanes
        a, (Bn, H, P)).reshape(Bn, nb, gb, L)
    # a block's groups' B, then C, as columns
    cols = lambda a: a.reshape(Bn, nb, ng, N).transpose(0, 1, 3, 2)
    row = pl.BlockSpec((1, 1, gb, L), lambda b, i: (b, i, 0, 0))
    blk = pl.BlockSpec((1, gb, N, L), lambda b, i: (b, i, 0, 0))
    col = pl.BlockSpec((1, 1, N, 2 * ng), lambda b, i: (b, i, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, span=span, ng=ng),
        grid=(Bn, nb),
        in_specs=[col, row, row, row, blk],
        out_specs=[row, blk],
        out_shape=[jax.ShapeDtypeStruct((Bn, nb, gb, L), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="mamba2_decode_step",
    )(jnp.concatenate([cols(B), cols(C)], axis=-1),
      lanes(jnp.exp(dt * A)[..., None]), lanes(dt[..., None] * x),
      lanes(D[None, :, None] * x), state)
    return y.reshape(Bn, H, P), state


def mamba2_step(x, dt, A, B, C, D, state):
    """One token a slot: ``x [B, H, P]``, ``dt [B, H]`` (0 for a slot that
    runs nothing: its state stays as it is), ``A, D [H]``, ``B, C [B, G,
    N]`` (float32) against rows ``[0, B)`` of the packed ``state [rows, H /
    hg, N, hg * P]``: ``(y [B, H, P], state)`` with those rows advanced and
    every other row as it was; ``y`` holds the skip ``D x``."""
    if default_paged_impl() == "oracle":
        return _step_oracle(x, dt, A, B, C, D, state)
    return _step_call(x, dt, A, B, C, D, state, interpret=pallas_interpret())
