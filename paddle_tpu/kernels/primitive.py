"""Kernel Primitive API — the KPS analog (phi/kernels/primitive/, kps/:
block-level device-portable primitives so one kernel source targets multiple
backends; SURVEY §2.2).

TPU re-design: the portability target is Mosaic's tiling rules rather than
CUDA/XPU-KP. These helpers encode the layout discipline every Pallas TPU
kernel here follows — 128-lane trailing dimension, (8,128) float32 tiles,
flatten-arbitrary-shape-to-padded-2D — plus factory functions that turn a
plain jnp expression into a tiled elementwise or row-reduction kernel.
kernels/fused_optim.py and norms.py are hand-rolled instances of the same
patterns; new kernels should build on these.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ..core.place import pallas_interpret
from .mesh import shard_kernel

LANES = 128        # vector lane width (trailing-dim tile)
SUBLANES = 8       # float32 sublane count -> (8, 128) native tile
DEFAULT_BLOCK_ROWS = 512


def pad_rows(n: int, lanes: int = LANES) -> int:
    """Rows of the [rows, lanes] 2D view holding n flat elements."""
    return -(-n // lanes)


def to_tiled_2d(a, lanes: int = LANES):
    """Flatten to [rows, lanes] with zero padding (ReadData analog: every
    kernel sees a lane-aligned 2D block regardless of logical shape)."""
    n = a.size
    rows = pad_rows(n, lanes)
    flat = a.reshape(-1)
    if rows * lanes != n:
        flat = jnp.pad(flat, (0, rows * lanes - n))
    return flat.reshape(rows, lanes)


def from_tiled_2d(a2d, shape: Sequence[int]):
    """Inverse of to_tiled_2d (WriteData analog)."""
    n = 1
    for s in shape:
        n *= int(s)
    return a2d.reshape(-1)[:n].reshape(shape)


def row_block_spec(block_rows: int, lanes: int = LANES) -> pl.BlockSpec:
    """1-D grid over row blocks of a [rows, lanes] view."""
    return pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))


def elementwise_kernel(fn: Callable, block_rows: int = DEFAULT_BLOCK_ROWS):
    """Lift ``fn(*blocks) -> block`` (pure jnp, fp32 math) into a tiled
    Pallas kernel over any same-shaped operands (ElementwiseUnary/Binary/
    Ternary analog in one factory).

        scaled_residual = elementwise_kernel(lambda x, y, a: x + a * y)
        out = scaled_residual(x, y, alpha)          # any shape, any dtype
    """

    def kernel(*refs):
        ins, out_ref = refs[:-1], refs[-1]
        vals = [r[...].astype(jnp.float32) for r in ins]
        out_ref[...] = fn(*vals).astype(out_ref.dtype)

    @functools.wraps(fn)
    def call(*arrays):
        arrays = [jnp.asarray(a) for a in arrays]
        shape, dtype = arrays[0].shape, arrays[0].dtype
        for a in arrays[1:]:
            if a.shape != shape:
                raise ValueError(f"elementwise operands must share a shape; "
                                 f"got {shape} vs {a.shape}")
        tiled = [to_tiled_2d(a) for a in arrays]
        rows = tiled[0].shape[0]
        br = min(block_rows, rows)
        run = pl.pallas_call(
            kernel,
            grid=(pl.cdiv(rows, br),),
            in_specs=[row_block_spec(br)] * len(tiled),
            out_specs=row_block_spec(br),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), dtype),
            interpret=pallas_interpret(),
            name="prim_elementwise",
        )
        # a generic primitive knows no layout: under a mesh it runs replicated
        out = shard_kernel(run, tiled, (P(),) * len(tiled), lambda f: P())
        return from_tiled_2d(out, shape)

    return call


def row_reduce_kernel(fn: Callable, init: float,
                      block_cols: int = 1024):
    """Lift a pairwise reduction ``fn(acc, block) -> acc`` over the LAST axis
    into a tiled kernel (Reduce<kps::AddFunctor> analog). The input is viewed
    as [rows, cols]; cols must be lane-aligned for the fast path, otherwise
    falls back to jnp.

        row_sum = row_reduce_kernel(lambda acc, x: acc + x.sum(-1), 0.0)
        out = row_sum(x)   # [..., cols] -> [...]
    """

    def kernel(x_ref, out_ref):
        # grid dim 1 walks col blocks sequentially (TPU grids iterate the
        # trailing dim innermost, in order), so the fp32 out block doubles as
        # the running accumulator across col blocks: VMEM holds only
        # (block_rows x block_cols) of x at a time, never the full row.
        ci = pl.program_id(1)

        @pl.when(ci == 0)
        def _init():
            out_ref[:, 0] = jnp.full((out_ref.shape[0],), init, jnp.float32)

        acc = out_ref[:, 0]
        out_ref[:, 0] = fn(acc, x_ref[...].astype(jnp.float32))

    def call(x):
        x = jnp.asarray(x)
        *lead, cols = x.shape
        rows = 1
        for s in lead:
            rows *= int(s)
        if cols % LANES or rows % SUBLANES:
            # layout-unfriendly shape: let XLA handle it
            acc = jnp.full(tuple(lead) or (), init, jnp.float32)
            return fn(acc.reshape(rows), x.reshape(rows, cols).astype(jnp.float32)) \
                .reshape(lead).astype(x.dtype)
        x2 = x.reshape(rows, cols)

        def divisor_block(limit, n, floor):
            b = min(limit, n)
            while n % b:  # n is a multiple of `floor`, so halving terminates
                b //= 2
            return max(b, floor)

        bc = divisor_block(block_cols, cols, LANES)
        br = divisor_block(DEFAULT_BLOCK_ROWS, rows, SUBLANES)
        run = pl.pallas_call(
            kernel,
            grid=(rows // br, cols // bc),
            in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            interpret=pallas_interpret(),
            name="prim_row_reduce",
        )
        out = shard_kernel(run, (x2,), (P(),), lambda f: P())
        return out.astype(x.dtype).reshape(lead)

    return call
