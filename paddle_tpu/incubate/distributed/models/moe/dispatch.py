"""Compressed MoE token dispatch: block-scaled int8 all-to-alls.

The dense GShard routing in moe_layer.py leaves the dispatch/combine
exchanges to GSPMD, which moves the [E, C, d] expert inputs and outputs
between ep ranks at full activation precision. This module is the
`moe_dispatch="quant"` path: the same routing math (gate logits, capacity
assignment and the aux loss stay full precision, so routing decisions are
bit-identical to dense), but the two cross-ep exchanges ride the
kernels/quant.py wire format — int8 payload with an f32 scale sidecar per
`block` trailing elements, ~3.9x fewer wire bytes at block 128.

Forward exchanges:
  dispatch: each rank contracts its LOCAL tokens against the (global,
    full-precision) dispatch one-hots into a partial [E, C, d] expert
    stack, reshapes E into [nep, E_loc], and all-to-alls the int8 payload
    over ep; summing the received per-source partials yields this rank's
    [E_loc, C, d] — a compressed reduce-scatter. Partials from the OTHER
    data axes (dp/sharding) are summed outside the manual region by GSPMD
    (same fp32 [E, C, d] reduction the dense path already pays).
  combine: each rank quantizes its local expert outputs and all-gathers
    them over ep; the combine einsum then runs on local tokens.

Backward is the transposed exchange, also compressed: the 0/0 all-to-all
permutation is its own transpose, and the all-gather transposes to the
quantized reduce-scatter above. The round/clip nonlinearity uses the
straight-through estimator — cotangents pass through the quantizer's wire
format but not its derivative (which is zero a.e.).

Context rules mirror comm_opt's reducer activation (see plan_quant_dispatch):
GSPMD-auto ambient opens a fully-manual shard_map island; a fully-manual
ambient (the flat explicit-grad-reduce step) runs the exchange body
directly with lax collectives; a PARTIAL-manual ambient (pipeline stages,
the hybrid reducer's region A) cannot host the all-to-all, so the layer
falls back to dense routing and records the `moe-dispatch-downgrade`
ambient finding — the analyzer-visible record that wire bytes silently
reverted to full precision.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .....kernels.quant import (dequantize_block_scaled, fit_block_size,
                                quantize_block_scaled)
from .....distributed.sharding_utils import DATA_AXES

EP_AXIS = "ep"

#: Below this block size the f32 scale sidecar eats the compression
#: (wire = 1 + 4/block bytes per value; block 8 is the 1.5x break-even
#: territory) — plan_quant_dispatch downgrades instead.
MIN_BLOCK = 8


# ---------------------------------------------------------------------------
# quantized exchange primitives (custom VJP, both directions compressed)
# ---------------------------------------------------------------------------

def _quant_a2a(x, axis_name: str, block_size: int):
    """dequant(all_to_all(quant(x))) over dim 0; x [n, ..., C] with n the
    axis size, C a block multiple. Returns f32 [n(source-major), ..., C]."""
    q, s = quantize_block_scaled(x, block_size)
    qr = lax.all_to_all(q, axis_name, 0, 0)
    sr = lax.all_to_all(s, axis_name, 0, 0)
    return dequantize_block_scaled(qr, sr, block_size)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def quant_all_to_all(x, axis_name: str, block_size: int):
    """Compressed all-to-all: int8 payload + f32 scales on the wire, f32
    out. Call inside a region manual over `axis_name`."""
    return _quant_a2a(x, axis_name, block_size)


def _qa2a_fwd(x, axis_name, block_size):
    return _quant_a2a(x, axis_name, block_size), None


def _qa2a_bwd(axis_name, block_size, _res, ct):
    # the (split=0, concat=0) all-to-all is a self-transpose permutation of
    # (rank, chunk) pairs; straight-through the quantizer and compress the
    # backward wire the same way as forward
    return (_quant_a2a(ct, axis_name, block_size),)


quant_all_to_all.defvjp(_qa2a_fwd, _qa2a_bwd)


def _quant_ag(x, axis_name: str, block_size: int):
    q, s = quantize_block_scaled(x, block_size)
    qg = lax.all_gather(q, axis_name, axis=0, tiled=True)
    sg = lax.all_gather(s, axis_name, axis=0, tiled=True)
    return dequantize_block_scaled(qg, sg, block_size)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def quant_all_gather(x, axis_name: str, block_size: int):
    """Compressed tiled all-gather over dim 0: local [m, ..., C] ->
    f32 [n*m, ..., C]. Transpose is the compressed reduce-scatter."""
    return _quant_ag(x, axis_name, block_size)


def _qag_fwd(x, axis_name, block_size):
    return _quant_ag(x, axis_name, block_size), None


def _qag_bwd(axis_name, block_size, _res, ct):
    # transpose of a tiled all-gather is a reduce-scatter; run it as the
    # compressed all-to-all + local sum over the source dim
    n = lax.psum(1, axis_name)
    cr = ct.reshape((n, ct.shape[0] // n) + ct.shape[1:])
    return (_quant_a2a(cr, axis_name, block_size).sum(axis=0),)


quant_all_gather.defvjp(_qag_fwd, _qag_bwd)


# ---------------------------------------------------------------------------
# plan: context resolution + static wire accounting
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DispatchPlan:
    """Resolved quant-dispatch schedule for one MoE layer call."""
    mesh: object                  # mesh hosting the island (None when direct)
    manual_direct: bool           # ambient already fully manual: no island
    axis_names: Tuple[str, ...]   # every mesh axis (the island's manual set)
    data_axes: Tuple[str, ...]    # batch-carrying axes, DATA_AXES order
    nep: int
    block: int
    # per-device RECEIVE-side bytes of the two forward exchanges (payload +
    # scale sidecar) and what the same exchanges move at fp32 — the
    # comm_opt/analysis convention (rules.wire_bytes), so the analyzer's
    # estimate reconciles against this accounting exactly
    bytes_wire: int
    bytes_raw: int

    @property
    def other_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.data_axes if a != EP_AXIS)

    @property
    def bytes_wire_train_step(self) -> int:
        """Fwd + transposed-bwd exchanges of one train-step MoE call: the
        backward all-to-alls mirror the forward ones byte-for-byte (the
        all-gather's transpose is the compressed reduce-scatter of the
        same buffer), so a step moves exactly twice the forward wire."""
        return 2 * self.bytes_wire

    @property
    def compression_ratio(self) -> float:
        return self.bytes_raw / self.bytes_wire if self.bytes_wire else 0.0


def _resolve_context():
    """(mesh, {axis: size}, manual_axes) of the ambient context. The
    abstract mesh a step is traced under (``jax.set_mesh``, and every
    shard_map region) carries axis types, so the manual set is exact.
    Outside any jax mesh context the process-global mesh (registered by
    topology's HybridCommunicateGroup / fleet.init) stands in, with
    nothing manual. No mesh at all -> (None, {}, ())."""
    m = jax.sharding.get_abstract_mesh()
    if not m.empty:
        sizes = {a: int(s) for a, s in m.shape.items()}
        manual = tuple(a for a, t in zip(m.axis_names, m.axis_types)
                       if t == jax.sharding.AxisType.Manual and sizes[a] > 1)
        return m, sizes, manual
    from .....distributed.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return None, {}, ()
    return mesh, {a: int(s) for a, s in mesh.shape.items()}, ()


def _downgrade(site: str, message: str, data: Tuple[str, ...]):
    from .....analysis.findings import Finding, record_ambient

    warnings.warn("moe_dispatch='quant' falling back to dense routing: "
                  + message, stacklevel=4)
    record_ambient(Finding(
        rule="moe-dispatch-downgrade", site=site, severity="warning",
        message=("moe_dispatch='quant' silently fell back to dense "
                 "routing (token exchanges move full-precision bytes): "
                 + message),
        data=data))
    _record_metrics(None)
    return None


def _record_metrics(plan: Optional[DispatchPlan]):
    from .....observability import metrics

    if plan is None:
        metrics.counter("moe.dispatch.downgraded")
        return
    metrics.gauge("moe.dispatch.block", plan.block)
    metrics.gauge("moe.dispatch.bytes_wire", plan.bytes_wire)
    metrics.gauge("moe.dispatch.bytes_raw", plan.bytes_raw)
    metrics.gauge("moe.dispatch.compression_ratio", plan.compression_ratio)


def plan_quant_dispatch(T: int, E: int, capacity: int, d: int,
                        block: int = 128, site: str = "moe.moe_route"
                        ) -> Optional[DispatchPlan]:
    """Resolve the ambient mesh context into a DispatchPlan, or None
    meaning "route dense".

    None is SILENT when there is nothing to compress (no ep axis, or ep
    degree 1 — no cross-rank exchange exists). It is a recorded DOWNGRADE
    (`moe-dispatch-downgrade` ambient finding + warning) when an exchange
    exists but cannot run compressed: a partial-manual ambient region
    (pipeline stage / hybrid reducer region A — the all-to-all cannot run
    under partial-auto shard_map), experts indivisible by the ep degree,
    or a model dim whose best block (gcd with `block`) is below MIN_BLOCK.
    """
    mesh, sizes, manual = _resolve_context()
    nep = sizes.get(EP_AXIS, 1)
    if mesh is None or nep <= 1:
        return None  # no exchange to compress; dense is exact, not a downgrade
    if E % nep:
        return _downgrade(site, f"{E} experts do not divide the ep degree "
                          f"{nep}", ("indivisible", str(E), str(nep)))
    blk = fit_block_size(d, block)
    if blk < MIN_BLOCK:
        return _downgrade(site, f"model dim {d} admits no quantization "
                          f"block >= {MIN_BLOCK} under block {block}",
                          ("block", str(d), str(block)))
    active = {a for a, s in sizes.items() if s > 1}
    manual = set(manual)
    if manual and manual != active:
        # partial-manual: the ep all-to-all cannot run while other
        # axes stay GSPMD-auto — same build constraint that forces
        # comm_opt's two-region schedule
        return _downgrade(site, "ambient region is manual over "
                          f"{sorted(manual)} with other mesh axes "
                          "GSPMD-auto; the compressed all-to-all needs "
                          "a fully-manual (or fully-auto) context",
                          ("partial-manual", ",".join(sorted(manual))))
    dax = tuple(a for a in DATA_AXES if a in active)
    world = int(np.prod([sizes[a] for a in dax], dtype=np.int64))
    if not manual and T % world:
        # the island shards the token dim over every data axis; an
        # indivisible global T cannot open it (manual contexts already
        # hold local shards, so no constraint there)
        return _downgrade(site, f"{T} tokens do not divide the data-axis "
                          f"world {world}", ("indivisible-tokens", str(T),
                                             str(world)))
    e_loc = E // nep
    # receive-side accounting per rules.wire_bytes: the dispatch all-to-all
    # moves the [nep, E_loc, C, d] partial ((nep-1)/nep of it lands on each
    # device's links), the combine all-gather receives every peer's local
    # [E_loc, C, d] — numerically identical per exchange since E = nep*E_loc
    def _recv_a2a(nbytes: int) -> int:
        return (nep - 1) * nbytes // nep

    disp_payload = E * capacity * d                 # int8: 1 byte/value
    disp_scales = 4 * E * capacity * (d // blk)     # f32 sidecar
    wire = (_recv_a2a(disp_payload) + _recv_a2a(disp_scales)
            + (nep - 1) * e_loc * capacity * (d + 4 * (d // blk)))
    raw = _recv_a2a(4 * disp_payload) + (nep - 1) * 4 * e_loc * capacity * d
    plan = DispatchPlan(
        mesh=None if manual else mesh, manual_direct=bool(manual),
        axis_names=tuple(sizes), data_axes=dax, nep=nep, block=blk,
        bytes_wire=wire, bytes_raw=raw)
    _record_metrics(plan)
    return plan


# ---------------------------------------------------------------------------
# the routed exchanges
# ---------------------------------------------------------------------------

def _dispatch_body(plan: DispatchPlan, dv, xv):
    """Local tokens -> this ep rank's [E_loc, C, d] partial (f32), summed
    over ep sources; partials over the other data axes remain."""
    part = jnp.einsum("tec,td->ecd", dv, xv.astype(jnp.float32))
    p4 = part.reshape((plan.nep, part.shape[0] // plan.nep) + part.shape[1:])
    return quant_all_to_all(p4, EP_AXIS, plan.block).sum(axis=0)


def quant_dispatch(plan: DispatchPlan, dv, xv):
    """dispatch one-hots [T, E, C] f32 + tokens [T, d] -> expert inputs
    [E, C, d] (ep-sharded logical view / local shard when manual)."""
    if plan.manual_direct:
        ein = _dispatch_body(plan, dv, xv)
        if plan.other_axes:
            ein = lax.psum(ein, plan.other_axes)
        return ein.astype(xv.dtype)

    bspec = P(plan.data_axes)

    def island(dv_l, xv_l):
        # [1, E_loc, C, d] — the leading stacked dim carries this rank's
        # dp/sharding partial out of the manual region (comm_opt's region-A
        # idiom), so the cross-data-axis sum runs under GSPMD auto and its
        # AD transpose is plain slicing, not a psum transpose
        return _dispatch_body(plan, dv_l, xv_l)[None]

    other = plan.other_axes
    stacked = jax.shard_map(
        island, mesh=plan.mesh, in_specs=(bspec, bspec),
        out_specs=P(other if other else None, EP_AXIS, None, None),
        axis_names=set(plan.axis_names), check_vma=False)(dv, xv)
    return stacked.sum(axis=0).astype(xv.dtype)


def _combine_body(plan: DispatchPlan, cv, ev):
    full = quant_all_gather(ev.astype(jnp.float32), EP_AXIS, plan.block)
    return jnp.einsum("tec,ecd->td", cv, full).astype(ev.dtype)


def quant_combine(plan: DispatchPlan, cv, ev):
    """combine weights [T, E, C] f32 + expert outputs [E, C, d] -> routed
    tokens [T, d]."""
    if plan.manual_direct:
        return _combine_body(plan, cv, ev)
    return jax.shard_map(
        _combine_body_island(plan), mesh=plan.mesh,
        in_specs=(P(plan.data_axes), P(EP_AXIS)),
        out_specs=P(plan.data_axes),
        axis_names=set(plan.axis_names), check_vma=False)(cv, ev)


def _combine_body_island(plan: DispatchPlan):
    def island(cv_l, ev_l):
        return _combine_body(plan, cv_l, ev_l)
    return island
