"""The Mamba-1 recurrence (selective state spaces, Gu & Dao,
arXiv:2312.00752), two forms of one recurrence over a per-CHANNEL float32
state ``S [N, E]`` (``E`` the layer's inner width, ``N`` the state's; kept
``N``-major so that the channels lie along the lanes)::

    S_t[n, e] = exp(dt_t[e] A[n, e]) S_{t-1}[n, e] + dt_t[e] x_t[e] B_t[n]
    y_t[e]    = sum_n S_t[n, e] C_t[n] + D[e] x_t[e]

with ONE decay a channel AND state lane (``A [N, E]``, negative), ``dt_t
[E] > 0`` the step, ``x_t [E]`` the input and ``B_t, C_t [N]`` shared by all
channels. It is NOT Mamba-2's (``mamba2.py``: one decay a head, which gives
its chunked form matrix products): a decay a channel and lane leaves no
matmul shape, so both forms here walk the tokens in order. A token with
``dt = 0`` changes nothing (``exp(0) = 1``, nothing written): that is how
padding behind a prompt's last real token, and a slot that runs no request,
are passed.

``mamba1_scan`` -- prefill and extend: ``T`` tokens a row from a given
state. On the TPU a Pallas kernel: one grid step a (row, tile of channels,
chunk of tokens), the tile's state ``[N, Et]`` resident in registers / VMEM
while the chunk's tokens are walked eight at a time (``x`` and ``dt`` read as
whole sublane tiles, ``B`` and ``C`` as columns spread over the lanes), the
state BEFORE each of ``cuts``' tokens handed out from inside the walk, so
that an admission that takes snapshots stays one program. Elsewhere, and as
its oracle, a ``lax.scan`` over tokens (``scan_reference``).

``mamba1_step`` -- decode, one token a slot: the recurrence itself, in place
on the state buffer the serving cache keeps (``[rows, N, E]``, the slots'
rows first). On the TPU a Pallas kernel (``mamba1_decode_step``): one grid
step a slot, the slot's state read once and written once where it lies
(``input_output_aliases``); elsewhere the same arithmetic in ``jax.numpy``
(``step_reference``). ``tier.default_paged_impl`` says which.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.place import pallas_interpret
from .tier import default_paged_impl

#: tokens a grid step of the scan walks, and the channels of a tile (the
#: largest of these that divides the inner width; else the whole width)
_SCAN_TOKENS = 256
_SCAN_LANES = (512, 256, 128)
#: tokens read at a time inside a chunk: one float32 sublane tile
_ROWS = 8
#: channels the step kernel advances at a time
_STEP_LANES = 1024


# ---------------------------------------------------------------- the scan

def scan_reference(x, dt, A, B, C, D, S0, cuts=None):
    """One row, token by token: ``x, dt [T, E]`` (``dt`` the step after its
    softplus; 0 for a token that is padding), ``A [N, E]`` (negative), ``B,
    C [T, N]``, ``D [E]``, start state ``S0 [N, E]``, all float32: ``(y [T,
    E], S_T)``. With ``cuts [n]`` (int32, run-time values in ``[0, T]``)
    also, third, the state BEFORE each cut's token ``[n, N, E]`` (a cut at
    ``T``: the end state)."""
    T = x.shape[0]
    wanted = cuts is not None
    cuts = jnp.clip(cuts, 0, T).astype(jnp.int32) if wanted \
        else jnp.zeros((0,), jnp.int32)

    def step(carry, xs):
        S, Sc = carry
        t, xt, dtt, Bt, Ct = xs
        Sc = jnp.where((cuts == t)[:, None, None], S[None], Sc)
        S = jnp.exp(dtt[None, :] * A) * S + (dtt * xt)[None, :] * Bt[:, None]
        return (S, Sc), jnp.sum(S * Ct[:, None], axis=0) + D * xt

    Sc0 = jnp.zeros((cuts.shape[0],) + S0.shape, S0.dtype)
    (S, Sc), y = lax.scan(step, (S0, Sc0),
                          (jnp.arange(T, dtype=jnp.int32), x, dt, B, C))
    Sc = jnp.where((cuts >= T)[:, None, None], S[None], Sc)
    return (y, S, Sc) if wanted else (y, S)


def _scan_kernel(cuts_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
                 y_ref, s_ref, sc_ref, s_scr, *, tokens: int, n_cuts: int,
                 total: int):
    """Grid (row, tile of channels, chunk of ``tokens`` tokens; the chunks
    in order). ``x_ref / dt_ref [1, tokens, Et]``; ``b_ref / c_ref [1,
    tokens / 8, N, 8]``: eight tokens' ``B`` (``C``) as columns; ``a_ref [N,
    Et]``, ``d_ref [1, Et]``, ``s0_ref [1, N, Et]``; out ``y_ref [1, tokens,
    Et]``, ``s_ref [1, N, Et]`` (the end state) and ``sc_ref [1, n_cuts, N,
    Et]`` (the state before each cut's token, ``cuts_ref [rows, n_cuts]``);
    ``s_scr [N, Et]`` carries the state from chunk to chunk."""
    b, ti = pl.program_id(0), pl.program_id(2)
    N, Et = a_ref.shape

    @pl.when(ti == 0)
    def _start():
        s_scr[...] = s0_ref[0]
        for j in range(n_cuts):     # (a cut the walk never meets is written
            sc_ref[0, j] = s0_ref[0]    # at the end; this keeps it defined)

    A, Dv = a_ref[...], d_ref[...]

    def group(g, S):
        r = pl.multiple_of(g * _ROWS, _ROWS)
        xg, dg = x_ref[0, pl.ds(r, _ROWS), :], dt_ref[0, pl.ds(r, _ROWS), :]
        bg, cg = b_ref[0, g], c_ref[0, g]                      # [N, 8]
        ys = []
        for i in range(_ROWS):
            t = ti * tokens + g * _ROWS + i
            for j in range(n_cuts):
                @pl.when(cuts_ref[b, j] == t)
                def _hand_out(S=S, j=j):
                    sc_ref[0, j] = S
            xi, di = xg[i:i + 1, :], dg[i:i + 1, :]            # [1, Et]
            S = jnp.exp(di * A) * S \
                + jnp.broadcast_to(bg[:, i:i + 1], (N, Et)) * (di * xi)
            ys.append(jnp.sum(
                S * jnp.broadcast_to(cg[:, i:i + 1], (N, Et)), axis=0,
                keepdims=True) + Dv * xi)
        y_ref[0, pl.ds(r, _ROWS), :] = jnp.concatenate(ys, axis=0)
        return S

    S = lax.fori_loop(0, tokens // _ROWS, group, s_scr[...])
    s_scr[...] = S

    @pl.when(ti == pl.num_programs(2) - 1)
    def _end():
        s_ref[0] = S
        for j in range(n_cuts):
            @pl.when(cuts_ref[b, j] >= total)
            def _at_end(j=j):
                sc_ref[0, j] = S


def _scan_tile(E: int) -> int:
    return next((w for w in _SCAN_LANES if E % w == 0), E)


# jitted so that a model's layers share one trace and one Mosaic lowering
@functools.partial(jax.jit, static_argnames="interpret")
def _scan_call(x, dt, A, B, C, D, S0, cuts, *, interpret: bool):
    Bn, T, E = x.shape
    N, n = A.shape[0], cuts.shape[1]
    tokens = min(_SCAN_TOKENS, -(-T // _ROWS) * _ROWS)
    pad = -T % tokens
    if pad:     # dt = 0: the state passes the padding unchanged
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                       for a in (x, dt, B, C))
    Tp = T + pad
    Et = _scan_tile(E)
    # eight tokens' B (C) as columns: [rows, Tp / 8, N, 8]
    cols = lambda a: a.reshape(Bn, Tp // _ROWS, _ROWS, N).transpose(0, 1, 3, 2)
    tok = pl.BlockSpec((1, tokens, Et), lambda b, e, t, _c: (b, t, e))
    col = pl.BlockSpec((1, tokens // _ROWS, N, _ROWS),
                       lambda b, e, t, _c: (b, t, 0, 0))
    st = pl.BlockSpec((1, N, Et), lambda b, e, t, _c: (b, 0, e))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Bn, E // Et, Tp // tokens),
        in_specs=[tok, tok, col, col,
                  pl.BlockSpec((N, Et), lambda b, e, t, _c: (0, e)),
                  pl.BlockSpec((1, Et), lambda b, e, t, _c: (0, e)), st],
        out_specs=[tok, st,
                   pl.BlockSpec((1, n, N, Et),
                                lambda b, e, t, _c: (b, 0, 0, e))],
        scratch_shapes=[pltpu.VMEM((N, Et), jnp.float32)],
    )
    y, S, Sc = pl.pallas_call(
        functools.partial(_scan_kernel, tokens=tokens, n_cuts=n, total=Tp),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Bn, Tp, E), jnp.float32),
                   jax.ShapeDtypeStruct((Bn, N, E), jnp.float32),
                   jax.ShapeDtypeStruct((Bn, n, N, E), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mamba1_scan",
    )(jnp.clip(cuts, 0, T).astype(jnp.int32), x, dt, cols(B), cols(C), A,
      D[None, :], S0)
    return y[:, :T], S, Sc


def mamba1_scan(x, dt, A, B, C, D, S0, cuts=None):
    """``T`` tokens a row from a given state: ``x, dt [rows, T, E]`` (``dt``
    0 for a token that is padding), ``A [N, E]``, ``B, C [rows, T, N]``, ``D
    [E]``, ``S0 [rows, N, E]``, float32: ``(y [rows, T, E], S_T [rows, N,
    E], the state before each of cuts' tokens [rows, n, N, E])`` (``cuts
    [rows, n]``, run-time values in ``[0, T]``; None: ``n = 0``)."""
    rows = x.shape[0]
    if cuts is None:
        cuts = jnp.zeros((rows, 0), jnp.int32)
    if default_paged_impl() == "oracle":
        return jax.vmap(scan_reference, in_axes=(0, 0, None, 0, 0, None, 0, 0)
                        )(x, dt, A, B, C, D, S0, cuts)
    n = cuts.shape[1]   # (the kernel's blocks want one cut at least)
    y, S, Sc = _scan_call(x, dt, A, B, C, D, S0,
                          cuts if n else jnp.zeros((rows, 1), jnp.int32),
                          interpret=pallas_interpret())
    return y, S, Sc[:, :n]


# ------------------------------------------------------- the recurrent step

def step_reference(x, dt, A, B, C, D, state):
    Bn = x.shape[0]
    S = state[:Bn] * jnp.exp(dt[:, None, :] * A) \
        + (dt * x)[:, None, :] * B[:, :, None]
    y = jnp.sum(S * C[:, :, None], axis=1) + D * x
    return y, lax.dynamic_update_slice_in_dim(state, S, 0, axis=0)


def _step_kernel(x_ref, dt_ref, bc_ref, a_ref, d_ref, s_ref, y_ref, so_ref,
                 *, lanes: int):
    """Grid (slot,). ``x_ref / dt_ref [1, 1, E]``, ``bc_ref [1, N, 2]`` the
    slot's ``B`` then ``C`` as columns, ``a_ref [N, E]``, ``d_ref [1, E]``,
    ``s_ref [1, N, E]`` the slot's state, advanced ``lanes`` channels at a
    time."""
    N, E = a_ref.shape
    for lo in range(0, E, lanes):
        w = min(lanes, E - lo)
        cut = slice(lo, lo + w)
        x, dt = x_ref[0, :, cut], dt_ref[0, :, cut]            # [1, w]
        S = s_ref[0, :, cut] * jnp.exp(dt * a_ref[:, cut]) \
            + jnp.broadcast_to(bc_ref[0, :, 0:1], (N, w)) * (dt * x)
        y_ref[0, :, cut] = jnp.sum(
            S * jnp.broadcast_to(bc_ref[0, :, 1:2], (N, w)), axis=0,
            keepdims=True) + d_ref[:, cut] * x
        so_ref[0, :, cut] = S


@functools.partial(jax.jit, static_argnames="interpret")
def _step_call(x, dt, A, B, C, D, state, *, interpret: bool):
    Bn, E = x.shape
    N = A.shape[0]
    row = pl.BlockSpec((1, 1, E), lambda b: (b, 0, 0))
    blk = pl.BlockSpec((1, N, E), lambda b: (b, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, lanes=_STEP_LANES),
        grid=(Bn,),
        in_specs=[row, row, pl.BlockSpec((1, N, 2), lambda b: (b, 0, 0)),
                  pl.BlockSpec((N, E), lambda b: (0, 0)),
                  pl.BlockSpec((1, E), lambda b: (0, 0)), blk],
        out_specs=[row, blk],
        out_shape=[jax.ShapeDtypeStruct((Bn, 1, E), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="mamba1_decode_step",
    )(x[:, None, :], dt[:, None, :], jnp.stack([B, C], axis=-1), A,
      D[None, :], state)
    return y[:, 0], state


def mamba1_step(x, dt, A, B, C, D, state):
    """One token a slot: ``x, dt [B, E]`` (``dt`` 0 for a slot that runs
    nothing: its state stays as it is), ``A [N, E]``, ``B, C [B, N]``, ``D
    [E]`` (float32) against rows ``[0, B)`` of ``state [rows, N, E]``: ``(y
    [B, E], state)`` with those rows advanced and every other row as it
    was; ``y`` holds the skip ``D x``."""
    if default_paged_impl() == "oracle":
        return step_reference(x, dt, A, B, C, D, state)
    return _step_call(x, dt, A, B, C, D, state, interpret=pallas_interpret())
