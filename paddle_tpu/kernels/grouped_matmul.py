"""Drop-free expert matmuls: rows sorted by expert, each expert's run padded
to whole row tiles, one Pallas grouped matmul over the tiles.

``plan_groups`` lays ``M`` (token, expert) rows out by expert so that no row
tile straddles two experts: expert ``g``'s rows start at a multiple of the
tile ``tm`` and its run is padded to one. The layout is computed with sorts
and gathers only. ``grouped_matmul`` then walks the tiles; each reads its
expert's whole ``[K, N]`` matrix (consecutive tiles of one expert keep the
block, so an expert's weights are read once per call) and an expert no row
was routed to is never read. Tiles past the last routed row are skipped.

Nothing is dropped and nothing is capped: the padded layout has room for
every distribution of rows over experts (``M + G * (tm - 1)`` rows).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core.place import pallas_interpret
from .mesh import shard_kernel


def row_tile(M: int, G: int) -> int:
    """Rows per tile: about the mean run of an expert, a power of two in
    [16, 256] (16 rows fill a bfloat16 sublane tile; 256 x K x N keeps the
    matrix unit busy while the next expert's weights stream in)."""
    t = 16
    while t < 256 and t * G < M:
        t *= 2
    return t


def plan_groups(expert, G: int, tm: int):
    """Layout of rows ``expert [M]`` (each row's expert id) by expert.

    Returns ``(src, dest, tile_group, n_tiles, counts)``: ``src [M_pad]``
    the row that padded row ``r`` holds (0 for padding), ``dest [M]`` where
    row ``m`` went, ``tile_group [tiles]`` the expert of each tile (tiles
    past ``n_tiles`` repeat the last live tile's, so they fetch nothing
    new), ``counts [G]`` rows per expert.

    An id of ``G`` marks a row of an expert that is NOT among the ``G`` laid
    out (a program that holds some of a layer's experts): such rows sort
    behind every group, lie in no tile and in no count, and their ``dest``
    means nothing (the caller reads none)."""
    M = expert.shape[0]
    M_pad = -(-(M + G * (tm - 1)) // tm) * tm
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    e_sorted = expert[order]
    edges = jnp.searchsorted(e_sorted, jnp.arange(G + 1, dtype=expert.dtype))
    edges = edges.astype(jnp.int32)
    counts = edges[1:] - edges[:-1]
    starts = edges[:-1]
    padded = -(-counts // tm) * tm
    ends_p = jnp.cumsum(padded)
    starts_p = ends_p - padded
    r = jnp.arange(M_pad, dtype=jnp.int32)
    g = jnp.minimum(jnp.searchsorted(ends_p, r, side="right"), G - 1)
    g = g.astype(jnp.int32)
    within = r - starts_p[g]
    live = (within < counts[g]) & (r < ends_p[-1])
    src = jnp.where(live, order[jnp.clip(starts[g] + within, 0, M - 1)], 0)
    # where row m went: its rank in sorted order, moved to the padded run
    rank = jnp.argsort(order).astype(jnp.int32)
    dest = starts_p[expert] + rank - starts[expert]
    n_tiles = (ends_p[-1] // tm).astype(jnp.int32)
    tiles = M_pad // tm
    tg = g[::tm]
    last = tg[jnp.maximum(n_tiles - 1, 0)]
    tile_group = jnp.where(jnp.arange(tiles) < n_tiles, tg, last)
    return src, dest, tile_group.astype(jnp.int32), n_tiles, counts


def _gmm_kernel(grp_ref, nt_ref, x_ref, w_ref, o_ref, *, transposed: bool):
    del grp_ref

    @pl.when(pl.program_id(0) < nt_ref[0])
    def _():
        # ``w [K, N]``, or ``[N, K]`` contracted over its last dimension
        dims = (((1,), (1 if transposed else 0,)), ((), ()))
        o_ref[...] = lax.dot_general(x_ref[...], w_ref[0], dims,
                                     preferred_element_type=jnp.float32
                                     ).astype(o_ref.dtype)


def _gmm_call(tile_group, n_tiles, x, w, *, tm: int, transposed: bool):
    M_pad, K = x.shape
    N = w.shape[1 if transposed else 2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(M_pad // tm,),
        in_specs=[pl.BlockSpec((tm, K), lambda t, grp, nt: (t, 0)),
                  pl.BlockSpec((1,) + w.shape[1:],
                               lambda t, grp, nt: (grp[t], 0, 0))],
        out_specs=pl.BlockSpec((tm, N), lambda t, grp, nt: (t, 0)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M_pad, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=pallas_interpret(),
        name="moe_grouped_matmul",
    )(tile_group, n_tiles, x, w)


def grouped_matmul(x, w, tile_group, n_tiles, tm: int,
                   transposed: bool = False):
    """``x [M_pad, K]`` (rows laid out by ``plan_groups``) times each row
    tile's expert matrix ``w[tile_group[t]]`` of ``w [G, K, N]`` (with
    ``transposed``: of ``w [G, N, K]``, a matrix stored ``[out, in]``,
    contracted over its last dimension): ``[M_pad, N]`` in x's dtype
    (float32 accumulation). Rows of tiles past ``n_tiles`` are left
    unwritten."""
    return shard_kernel(
        functools.partial(_gmm_call, tm=tm, transposed=transposed),
        (tile_group, jnp.reshape(n_tiles, (1,)), x, w),
        (P(), P(), P(), P()), lambda f: P())
