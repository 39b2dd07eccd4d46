"""Sharded train-step builder: where all the annotations become a program.

The reference's hybrid path assembles a training step at runtime — wrappers,
reducer hooks, pipeline schedulers, hybrid optimizer sync (SURVEY §3.4). Here
the step is one pjit-compiled pure function: parameters/optimizer state carry
NamedShardings derived from each Parameter's dist_spec (mp/sharding axes),
the batch is sharded over dp, and XLA emits + overlaps every collective. This
module is the single seam the GPT fixture, __graft_entry__ dry-run, bench.py
and the hapi/auto-parallel engines all compile through.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core import random as _random
from ...observability import goodput as _obs_goodput
from ...observability import instrument as _obs_instr
from ...observability import memory as _obs_memory
from ...observability import metrics as _obs_metrics
from ...observability.tracing import span as _span
from ...core.autograd import no_grad
from ...core.place import is_compile_only
from ...core.tensor import Tensor
from ...nn.clip import ClipGradByGlobalNorm
from ...nn.layer.layers import Layer
from ...optimizer.optimizer import Optimizer
from ..sharding_utils import ambient_axis_names
from .. import comm_opt as _comm_opt


def resolve_spec(spec: Optional[P], mesh: Mesh) -> P:
    """Drop spec axes the mesh doesn't have (mp spec on a dp-only mesh ->
    P()). UNCONSTRAINED entries become None: this resolver feeds
    NamedShardings (param/state placement), which must be fully specified."""
    if spec is None:
        return P()
    from ..sharding_utils import _resolve_ambient

    resolved = _resolve_ambient(spec, mesh.axis_names)
    out = [None if e is P.UNCONSTRAINED else e for e in resolved]
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_shardings(model: Layer, mesh: Mesh):
    """{name: NamedSharding} from each Parameter's dist_spec annotation."""
    out = {}
    for name, p in model.named_parameters():
        if p is None:
            continue
        out[name] = NamedSharding(mesh, resolve_spec(getattr(p, "dist_spec", None), mesh))
    return out


def _state_sharding_like(param_sharding: NamedSharding, leaf, mesh: Mesh, shard_axis: Optional[str]):
    """Optimizer-state placement for one leaf: inherit the param's spec
    (mp/pp/ep placement), then — under ZeRO — ALSO shard over the sharding
    axis on the first free divisible dim. This is what makes the sharded
    optimizer compose with pipeline parallelism (reference
    DygraphShardingOptimizer inside HybridParallelOptimizer): a stacked
    block state [pp, L/pp, d, ...] comes out P('pp', None, 'sharding', ...)
    rather than losing the ZeRO axis."""
    if leaf.ndim == 0:
        return NamedSharding(mesh, P())
    spec = param_sharding.spec if len(param_sharding.spec) <= leaf.ndim else P()
    entries = list(spec) + [None] * (leaf.ndim - len(spec))
    if shard_axis and shard_axis in mesh.axis_names:
        deg = mesh.shape[shard_axis]
        used = {a for e in entries if e is not None
                for a in (e if isinstance(e, tuple) else (e,))}
        if deg > 1 and shard_axis not in used:
            for i, e in enumerate(entries):
                if e is None and leaf.shape[i] % deg == 0 and leaf.shape[i] >= deg:
                    entries[i] = shard_axis
                    break
    while entries and entries[-1] is None:
        entries.pop()
    return NamedSharding(mesh, P(*entries))


class ShardedTrainStep:
    """Holds device state (params, opt state) and the compiled step.

    step(batch) -> loss. Batch = (x, y) numpy/jax arrays; x sharded over the
    data axes (dp AND sharding AND ep — the ZeRO axis is data parallelism
    with sharded optimizer states, reference GroupSharded semantics; the
    expert axis carries data for non-expert compute, DeepSpeed-MoE style)
    on dim 0. `sync_to_model()` writes params back into the Layer.
    """

    def __init__(
        self,
        model: Layer,
        optimizer: Optimizer,
        loss_fn: Optional[Callable] = None,
        mesh: Optional[Mesh] = None,
        batch_spec: P = P(("dp", "sharding", "ep")),
        donate: bool = True,
        seed: int = 0,
        accumulate_steps: Optional[int] = None,
        pp_remat: bool = True,
        virtual_pp_degree: int = 1,
        pp_schedule: str = "1f1b",
        scaler=None,
        grad_reduce=None,
        health_stats: Optional[bool] = None,
        param_specs: Optional[Dict[str, P]] = None,
    ):
        from ..topology import get_hybrid_communicate_group

        if mesh is None:
            hcg = get_hybrid_communicate_group()
            import numpy as _np

            mesh = hcg.get_mesh() if hcg is not None else Mesh(_np.array(jax.devices()[:1]), ("dp",))
        self.mesh = mesh
        self.model = model
        self.optimizer = optimizer
        # pp mode takes its loss from pipeline_spec().post_loss, so a model
        # without .loss (e.g. PipelineLayer with its own loss_fn) is fine
        self.loss_fn = loss_fn if loss_fn is not None else getattr(model, "loss", None)
        self._step_i = 0
        self._seed = seed
        self._donate = donate

        pp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pp", 1)
        self._pp = pp
        self._pspec = None

        params0, buffers0 = model.functional_state()

        if pp > 1:
            # compiled pipeline parallelism: block params restack to
            # [pp, L/pp, ...] leaves sharded over the pp axis; the step runs
            # the differentiable ppermute schedule (pipeline_schedule)
            if not hasattr(model, "pipeline_spec"):
                raise ValueError(
                    f"mesh has pp={pp} but {type(model).__name__} provides no "
                    "pipeline_spec(); implement the PipelineSpec protocol "
                    "(see meta_parallel.pipeline_parallel)")
            from .meta_parallel.pipeline_parallel import (
                block_param_name, stack_block_params)

            pspec = model.pipeline_spec()
            self._pspec = pspec
            self._accum = accumulate_steps if accumulate_steps else pp
            self._vpp = max(int(virtual_pp_degree), 1)
            if pp_schedule not in ("1f1b", "gpipe"):
                raise ValueError(
                    f"pp_schedule must be '1f1b' or 'gpipe', got {pp_schedule!r}")
            self._pp_schedule = pp_schedule
            stacked0, other0 = stack_block_params(params0, pspec, pp,
                                                  virtual_stages=self._vpp)
            self._stack_prefix = (f"{pspec.block_prefix}." if pspec.block_prefix
                                  else "") + "__stacked__."
            skey = lambda sfx: f"{self._stack_prefix}{sfx}"
            self._suffixes = sorted(stacked0)
            params0 = {**other0, **{skey(s): v for s, v in stacked0.items()}}

            named = dict(model.named_parameters())
            p_shard = {}
            for name in other0:
                p_shard[name] = NamedSharding(
                    mesh, resolve_spec(getattr(named[name], "dist_spec", None), mesh))
            lead = ("pp", None, None) if self._vpp > 1 else ("pp", None)
            for sfx in self._suffixes:
                ref = named[block_param_name(pspec.block_prefix, 0, sfx)]
                bspec = resolve_spec(getattr(ref, "dist_spec", None), mesh)
                entries = list(bspec) + [None] * (ref._value.ndim - len(bspec))
                p_shard[skey(sfx)] = NamedSharding(mesh, P(*lead, *entries))
        else:
            p_shard = param_shardings(model, mesh)
            if param_specs:
                # autoshard (or any caller) overrides the models' dist_spec
                # layout wholesale — partial tables keep the default for
                # params they don't name
                p_shard = {
                    name: (NamedSharding(mesh, param_specs[name])
                           if name in param_specs else sh)
                    for name, sh in p_shard.items()}
        if param_specs and pp > 1:
            raise ValueError("param_specs overrides are not supported with "
                             "pipeline parallelism (pp>1): block params are "
                             "restacked with a pp leading dim")

        opt_state0 = optimizer.init_state_pytree(params0)
        shard_axis = getattr(optimizer, "_shard_state_axis", None)
        s_shard = {
            name: jax.tree_util.tree_map(
                lambda leaf: _state_sharding_like(p_shard[name], leaf, mesh, shard_axis), opt_state0[name]
            )
            for name in opt_state0
        }
        # A mesh of compile-only devices (jax.experimental.topologies) can
        # hold no arrays: the step then keeps abstract state — it can be
        # lowered and compiled for that machine (lower_compiled), not run.
        if is_compile_only(mesh.devices.flat[0]):
            place = lambda v, s: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                                      sharding=s)
        else:
            place = jax.device_put
        self.params = jax.tree_util.tree_map(
            place, params0, {k: p_shard[k] for k in params0})
        self.opt_state = jax.tree_util.tree_map(place, opt_state0, s_shard)
        # the layout each parameter's update runs in: its optimizer state's
        # (ZeRO shards the moments finer than the stored param; the update
        # is elementwise, so the finest layout serves every operand)
        update_specs = {
            name: next((sh.spec for sh, leaf in zip(
                jax.tree_util.tree_leaves(s_shard[name]),
                jax.tree_util.tree_leaves(opt_state0[name]))
                if leaf.shape == params0[name].shape), p_shard[name].spec)
            for name in params0}

        batch_sharding = NamedSharding(mesh, resolve_spec(batch_spec, mesh))
        self._batch_sharding = batch_sharding

        # ---- in-graph numerics health (observability.health) ----
        # When on, the compiled step takes one extra [G] f32 input (the
        # grad-poison vector, all-ones in normal operation — the fault
        # injector bench/tests use) and returns one extra small replicated
        # pytree of per-param-group stats. Donation and the one-compile
        # contract are untouched: the poison vector is never donated and
        # its shape/dtype are fixed at build time.
        from ...observability import health as _obs_health
        self._health = (_obs_health.stats_enabled() if health_stats is None
                        else bool(health_stats))
        self._health_monitor = None
        self._health_pending = None
        self.health_state = None
        if self._health:
            import numpy as _np
            groups, gidx = _obs_health.group_index_map(list(params0))
            self._health_groups = groups
            self._health_poison = _np.ones(len(groups), _np.float32)
            _nG = len(groups)

            def _poison(grads, hp):
                return {k: g * hp[gidx[k]].astype(g.dtype)
                        for k, g in grads.items()}

            def _health_stats_of(params, grads, new_params):
                return _obs_health.in_graph_stats(gidx, _nG, params, grads,
                                                  new_params)
        else:
            self._health_groups = None
            self._health_poison = None
        health = self._health
        clip = optimizer._grad_clip if isinstance(optimizer._grad_clip, ClipGradByGlobalNorm) else None
        clip_norm = clip.clip_norm if clip is not None else None
        loss_fn_ = self.loss_fn
        mdl = model

        # a model-provided fused trunk->loss path (e.g. GPT's chunked CE that
        # never materializes full logits) wins over forward()+loss(), unless
        # the caller supplied an explicit loss_fn
        use_fwl = loss_fn is None and hasattr(model, "forward_with_loss")

        if pp > 1:
            pipe_loss = self._build_pipeline_loss(buffers0, pp_remat)

            def loss_impl(pvals, bufs, x, y, seed):
                # pipeline models are homogeneous transformer stacks (LN,
                # not BN) — buffers pass through unchanged
                return pipe_loss(pvals, x, y, seed), bufs
        else:
            if not use_fwl and loss_fn_ is None:
                raise ValueError(
                    f"{type(model).__name__} has no .loss/.forward_with_loss; "
                    "pass loss_fn= to make_sharded_train_step")
            self._accum = accumulate_steps if accumulate_steps else 1

            def loss_impl(pvals, bufs, x, y, seed):
                """Returns (loss, new_buffers): buffer updates (BatchNorm
                running stats etc.) are step STATE, not discarded — frozen
                buffers would silently leave eval statistics at init."""
                with no_grad(), _random.rng_scope(seed):
                    if use_fwl:
                        loss, new_bufs = mdl.functional_call(
                            pvals, bufs, Tensor(x), Tensor(y),
                            method="forward_with_loss")
                    else:
                        out, new_bufs = mdl.functional_call(pvals, bufs, Tensor(x))
                        loss = loss_fn_(out, Tensor(y))
                return loss._value.astype(jnp.float32), new_bufs

        M_acc = self._accum
        pp_mode = pp > 1

        # Grad compute sharding = param storage sharding minus the ZeRO axis:
        # under ZeRO-3 the stored param (hence, by propagation, its grad) is
        # sharded over `sharding`, and letting that reach the weight-grad dot
        # makes the partitioner reshard the ACTIVATION operand to match
        # (involuntary full rematerialization). Constraining the grad to the
        # compute spec keeps the dot local-partials + allreduce; the slice
        # down to the storage shard happens at the optimizer update, exactly
        # like ZeRO-1/2 grads (reference GroupShardedStage3's
        # reduce-then-keep-own-slice, group_sharded_stage3.py:486).
        zero_axis = getattr(optimizer, "_shard_state_axis", None) or "sharding"

        def _strip_axis(spec: P, axis: str) -> P:
            out = []
            for e in spec:
                if e == axis:
                    out.append(None)
                elif isinstance(e, tuple):
                    kept = tuple(a for a in e if a != axis)
                    out.append(kept if kept else None)
                else:
                    out.append(e)
            while out and out[-1] is None:
                out.pop()
            return P(*out)

        g_shard = {
            name: NamedSharding(mesh, _strip_axis(s.spec, zero_axis))
            for name, s in p_shard.items()
        }

        # ---- gradient-reduction strategy (distributed.comm_opt) ----
        # The explicit reducer replaces GSPMD's implicit grad all-reduce
        # with bucketed quantized/hierarchical collectives inside a
        # fully-manual shard_map over the data axes. On hybrid dp x mp
        # meshes reducer_for_step hands back a hybrid reducer instead:
        # fp32 reduces inline (flat psum in a partial-auto region manual
        # over reducer.manual_axes); quant runs the two-region schedule —
        # the partial-auto region emits stacked per-rank grads and
        # reducer.reduce_stacked compresses them per model shard (the
        # grad specs below localize its plan). reducer is None (implicit
        # reduction stays) for mode="off", a single-device data world, or
        # pp/sep meshes (those stages nest their own shard_maps; see
        # comm_opt.reduce).
        self._grad_reduce = _comm_opt.normalize_grad_reduce(grad_reduce)
        bspec0 = (batch_sharding.spec[0] if len(batch_sharding.spec)
                  else None)
        data_axes = (bspec0 if isinstance(bspec0, tuple)
                     else (bspec0,)) if bspec0 else ()
        reducer = _comm_opt.reducer_for_step(
            self._grad_reduce, mesh, data_axes,
            {k: (tuple(v.shape), v.dtype) for k, v in params0.items()},
            grad_specs={k: tuple(g_shard[k].spec) for k in params0})
        self._reducer = reducer
        self._ef_shard = reducer.ef_shardings() if reducer else {}
        self.ef_state = {} if reducer is None else {
            k: place(v, self._ef_shard[k])
            for k, v in reducer.init_ef().items()}
        # with overlap, every accumulation microbatch issues its own
        # bucket reductions (they hide under the next microbatch's
        # backward) — the per-step wire volume scales by M_acc. The
        # two-region hybrid cannot overlap: its reduce region sits
        # OUTSIDE the fwd/bwd region, after accumulation.
        self._reductions_per_step = (
            M_acc if (reducer is not None and self._grad_reduce.overlap
                      and M_acc > 1 and not reducer.two_region) else 1)
        overlap_reduce = reducer is not None and self._reductions_per_step > 1

        def grads_with_reduce(params, bufs, ef, x, y, seed, loss_scale=None):
            """value_and_grad_accum + the explicit reduction when active:
            returns ((loss, new_buffers), grads, new_ef). The whole
            fwd+bwd runs inside the manual region so per-microbatch
            reductions interleave with the remaining backward; the local
            loss is the LOCAL batch mean, pmean'd back to the global mean
            (ditto float buffer stats), which is exactly what the
            implicit path computes from the globally-sharded batch."""
            if reducer is None:
                (loss, new_bufs), grads = value_and_grad_accum(
                    params, bufs, x, y, seed, loss_scale=loss_scale)
                return (loss, new_bufs), grads, ef

            from jax import lax

            dax = reducer.data_axes
            scaled_in = loss_scale is not None

            if reducer.two_region:
                # Region A: partial-auto fwd/bwd (manual over the data
                # axes only; model axes stay GSPMD-auto), emitting each
                # data rank's local grads stacked on a leading data axis.
                # Region B (reduce_stacked, outside this shard_map) pins
                # the model-parallel layouts and runs the quantized
                # chain per model shard. Loss scaling composes the same
                # way as inline: grads leave region A scaled, region B
                # unscales before compression and rescales after, so EF
                # residuals stay in unscaled units.
                def local_a(params_l, bufs_l, x_l, y_l, seed_l, sc_l):
                    ls = sc_l if scaled_in else None
                    (l, new_bufs), g = value_and_grad_accum(
                        params_l, bufs_l, x_l, y_l, seed_l, loss_scale=ls)
                    l = jax.lax.pmean(l, dax)
                    new_bufs = jax.tree_util.tree_map(
                        lambda t: (jax.lax.pmean(t, dax)
                                   if jnp.issubdtype(t.dtype, jnp.floating)
                                   else t), new_bufs)
                    return l, new_bufs, {k: v[None] for k, v in g.items()}

                sc_in2 = (loss_scale if scaled_in else jnp.float32(1.0))
                loss, new_bufs, gstack = jax.shard_map(
                    local_a, mesh=mesh,
                    in_specs=(P(), P(), batch_sharding.spec,
                              batch_sharding.spec, P(), P()),
                    out_specs=(P(), P(), P(dax)),
                    axis_names=set(reducer.manual_axes), check_vma=False,
                )(params, bufs, x, y, seed, sc_in2)
                inv = (1.0 / sc_in2) if scaled_in else None
                grads, new_ef = reducer.reduce_stacked(gstack, ef,
                                                       inv_scale=inv)
                return (loss, new_bufs), grads, new_ef

            def local(params_l, bufs_l, ef_blk, x_l, y_l, seed_l, sc_l):
                ef_loc = {k: v[0] for k, v in ef_blk.items()}
                inv = (1.0 / sc_l) if scaled_in else None
                ls = sc_l if scaled_in else None
                if overlap_reduce:
                    B = x_l.shape[0]
                    if B % M_acc:
                        raise ValueError(
                            f"local batch {B} not divisible by "
                            f"accumulate_steps {M_acc}")
                    mb = B // M_acc
                    xs = jnp.swapaxes(
                        x_l.reshape((mb, M_acc) + x_l.shape[1:]), 0, 1)
                    ys = jnp.swapaxes(
                        y_l.reshape((mb, M_acc) + y_l.shape[1:]), 0, 1)
                    sc = sc_l if scaled_in else jnp.float32(1.0)

                    def body(carry, xsm):
                        acc_l, acc_g, bufs_c, ef_c = carry
                        xm, ym, m = xsm

                        def micro_loss(p):
                            with _random.key_salt(m):
                                l_, nb_ = loss_impl(p, bufs_c, xm, ym,
                                                    seed_l)
                            return l_ * sc, nb_

                        (l_, nb_), g_ = jax.value_and_grad(
                            micro_loss, has_aux=True)(params_l)
                        with jax.named_scope("comm/grad_reduce"):
                            g_, ef_c = reducer.reduce_local(
                                g_, ef_c, inv_scale=inv)
                        return (acc_l + l_,
                                jax.tree_util.tree_map(jnp.add, acc_g, g_),
                                nb_, ef_c), None

                    zeros = jax.tree_util.tree_map(jnp.zeros_like, params_l)
                    (l, g, new_bufs, ef_loc), _ = lax.scan(
                        body, (jnp.zeros((), jnp.float32), zeros, bufs_l,
                               ef_loc),
                        (xs, ys, jnp.arange(M_acc)))
                    invM = 1.0 / M_acc
                    l = l * invM
                    g = jax.tree_util.tree_map(lambda t: t * invM, g)
                else:
                    (l, new_bufs), g = value_and_grad_accum(
                        params_l, bufs_l, x_l, y_l, seed_l, loss_scale=ls)
                    with jax.named_scope("comm/grad_reduce"):
                        g, ef_loc = reducer.reduce_local(g, ef_loc,
                                                         inv_scale=inv)
                l = jax.lax.pmean(l, dax)
                new_bufs = jax.tree_util.tree_map(
                    lambda t: (jax.lax.pmean(t, dax)
                               if jnp.issubdtype(t.dtype, jnp.floating)
                               else t), new_bufs)
                return l, new_bufs, g, {k: v[None] for k, v in
                                        ef_loc.items()}

            sc_in = (loss_scale if scaled_in else jnp.float32(1.0))
            ef_specs = {k: P(dax) for k in ef}
            loss, new_bufs, grads, new_ef = jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(), P(), ef_specs, batch_sharding.spec,
                          batch_sharding.spec, P(), P()),
                out_specs=(P(), P(), P(), ef_specs),
                axis_names=set(reducer.manual_axes), check_vma=False,
            )(params, bufs, ef, x, y, seed, sc_in)
            return (loss, new_bufs), grads, new_ef

        def value_and_grad_accum(params, bufs, x, y, seed, loss_scale=None):
            """Gradient accumulation over M_acc microbatches (pipeline mode
            microbatches inside the schedule instead): fwd+bwd per microbatch
            inside a lax.scan, so only one microbatch's activations are live
            at a time — the memory profile accumulation exists to provide.
            loss_scale (traced scalar) multiplies the loss BEFORE autodiff —
            fp16 dynamic loss scaling; grads and the returned loss come back
            scaled. Applied outside the pipeline's custom_vjp, so it scales
            the 1F1B/GPipe/vpp backward streams identically.
            Returns ((loss, new_buffers), grads)."""
            sc = jnp.float32(1.0) if loss_scale is None else loss_scale

            if pp_mode or M_acc <= 1:
                def fn(p):
                    loss, new_bufs = loss_impl(p, bufs, x, y, seed)
                    return loss * sc, new_bufs

                return jax.value_and_grad(fn, has_aux=True)(params)
            B = x.shape[0]
            if B % M_acc:
                raise ValueError(f"batch {B} not divisible by accumulate_steps {M_acc}")
            mb = B // M_acc
            # microbatch m = rows m::M — strided split keeps dp shards local
            xs = jnp.swapaxes(x.reshape((mb, M_acc) + x.shape[1:]), 0, 1)
            ys = jnp.swapaxes(y.reshape((mb, M_acc) + y.shape[1:]), 0, 1)

            def body(carry, xsm):
                acc_l, acc_g, bufs_c = carry
                xm, ym, m = xsm

                def micro_loss(p):
                    with _random.key_salt(m):
                        loss, new_bufs = loss_impl(p, bufs_c, xm, ym, seed)
                    return loss * sc, new_bufs

                (l, new_bufs), g = jax.value_and_grad(
                    micro_loss, has_aux=True)(params)
                return (acc_l + l,
                        jax.tree_util.tree_map(jnp.add, acc_g, g),
                        new_bufs), None

            from jax import lax

            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            (l, g, new_bufs), _ = lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros, bufs),
                (xs, ys, jnp.arange(M_acc)))
            inv = 1.0 / M_acc
            return ((l * inv, new_bufs),
                    jax.tree_util.tree_map(lambda t: t * inv, g))

        @jax.named_scope("opt/update")
        def _clip_and_update(params, opt_state, grads, lr):
            grads = {
                k: jax.lax.with_sharding_constraint(g, g_shard[k])
                for k, g in grads.items()
            }
            if clip_norm is not None:
                gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree_util.tree_leaves(grads))
                scale = clip_norm / jnp.maximum(jnp.sqrt(gsq), clip_norm)
                grads = jax.tree_util.tree_map(lambda g: (g * scale).astype(g.dtype), grads)
            return optimizer.apply_gradients(params, grads, opt_state, lr=lr,
                                             update_specs=update_specs)

        self._scaler = scaler if (scaler is not None
                                  and scaler.is_enable()) else None
        if self._scaler is not None:
            # fp16 dynamic loss scaling inside the compiled step (reference
            # amp/grad_scaler.py:576 update_loss_scaling): loss scaled before
            # AD, grads unscaled in f32, non-finite grads skip the update
            # (jnp.where select — branchless, SPMD-uniform), and the
            # (scale, good, bad) automaton is device state carried by the
            # step exactly like optimizer state.
            sc = self._scaler
            dynamic = sc.is_use_dynamic_loss_scaling()
            incr_every, decr_every = sc._incr_every, sc._decr_every
            incr_ratio, decr_ratio = sc._incr_ratio, sc._decr_ratio

            def step(params, opt_state, bufs, sstate, ef, x, y, lr, seed,
                     hp=None):
                scale, good, bad = sstate
                (scaled_loss, new_bufs), grads, new_ef = grads_with_reduce(
                    params, bufs, ef, x, y, seed, loss_scale=scale)
                inv = 1.0 / scale
                dts = {k: g.dtype for k, g in grads.items()}
                grads = {k: g.astype(jnp.float32) * inv
                         for k, g in grads.items()}
                if health:
                    # fault injection BEFORE the overflow check, so poisoned
                    # grads flow through it exactly like a real overflow
                    grads = _poison(grads, hp)
                hgrads = grads  # unscaled f32 — what the stat pass reads
                found = jnp.zeros((), bool)
                for g in grads.values():
                    found = found | ~jnp.all(jnp.isfinite(g))
                grads = {k: g.astype(dts[k]) for k, g in grads.items()}
                new_params, new_state = _clip_and_update(
                    params, opt_state, grads, lr)
                keep = lambda old, new: jax.tree_util.tree_map(
                    lambda o, n: jnp.where(found, o, n.astype(o.dtype)),
                    old, new)
                new_params = keep(params, new_params)
                new_state = keep(opt_state, new_state)
                # overflow steps keep the PRE-STEP residuals too: the
                # non-finite grads poisoned this step's compression errors
                # (quant scales propagate NaN by design so `found` trips)
                new_ef = keep(ef, new_ef)
                if dynamic:
                    good2 = jnp.where(found, 0, good + 1)
                    bad2 = jnp.where(found, bad + 1, 0)
                    dec = found & (bad2 >= decr_every)
                    inc = (~found) & (good2 >= incr_every)
                    new_scale = jnp.where(
                        dec, jnp.maximum(scale * decr_ratio, 1.0),
                        jnp.where(inc, scale * incr_ratio, scale))
                    good2 = jnp.where(inc, 0, good2)
                    bad2 = jnp.where(dec, 0, bad2)
                else:
                    new_scale, good2, bad2 = scale, good, bad
                # loss reported unscaled (inf stays inf on overflow steps);
                # buffer updates (BN stats) keep even on skipped updates —
                # eager forward updates them before overflow is known
                out = (new_params, new_state, new_bufs, new_ef,
                       (new_scale, good2, bad2), scaled_loss * inv)
                if health:
                    # update_norm from the POST-keep params: truthfully
                    # zero on overflow-skipped steps
                    out = out + (_health_stats_of(params, hgrads,
                                                  new_params),)
                return out

            self.scaler_state = (jnp.float32(sc._scale),
                                 jnp.int32(sc._good_steps),
                                 jnp.int32(sc._bad_steps))
            donate_args = (0, 1, 2, 3, 4) if donate else ()
            hp_in = (None,) if health else ()
            h_out = (None,) if health else ()
            self._in_sh = (p_shard, s_shard, None, None, self._ef_shard,
                           batch_sharding, batch_sharding, None,
                           None) + hp_in
            self._out_sh = (p_shard, s_shard, None, self._ef_shard, None,
                            NamedSharding(mesh, P())) + h_out
            self._compiled = jax.jit(
                step,
                in_shardings=self._in_sh,
                out_shardings=self._out_sh,
                donate_argnums=donate_args,
            )
        else:
            self.scaler_state = None

            def step(params, opt_state, bufs, ef, x, y, lr, seed, hp=None):
                (loss, new_bufs), grads, new_ef = grads_with_reduce(
                    params, bufs, ef, x, y, seed)
                if health:
                    grads = _poison(grads, hp)
                new_params, new_state = _clip_and_update(
                    params, opt_state, grads, lr)
                out = (new_params, new_state, new_bufs, new_ef, loss)
                if health:
                    out = out + (_health_stats_of(params, grads,
                                                  new_params),)
                return out

            donate_args = (0, 1, 2, 3) if donate else ()
            hp_in = (None,) if health else ()
            h_out = (None,) if health else ()
            self._in_sh = (p_shard, s_shard, None, self._ef_shard,
                           batch_sharding, batch_sharding, None,
                           None) + hp_in
            self._out_sh = (p_shard, s_shard, None, self._ef_shard,
                            NamedSharding(mesh, P())) + h_out
            self._compiled = jax.jit(
                step,
                in_shardings=self._in_sh,
                out_shardings=self._out_sh,
                donate_argnums=donate_args,
            )
        # buffers are step STATE (device-resident like params/opt state).
        # COPIED, not aliased: functional_state returns the model's live
        # arrays, and donation would delete them out from under any eager
        # use of the model between compiled steps.
        self.buffers = jax.tree_util.tree_map(
            lambda v: jnp.array(v, copy=True), buffers0)
        # for run_steps (multi-step scan): the raw python step + shardings
        self._compiled_step_fn = step
        self._p_shard, self._s_shard = p_shard, s_shard
        self._multi = None
        # AOT executables keyed by (path, batch signature) — see _executable
        self._exe: Dict[Any, Any] = {}
        self._obs_nrecords = 0

    def sharding_contract(self):
        """Tier-2 analysis declaration: exactly the in/out shardings
        ``self._compiled`` is built with, so the sharding-flow rules judge
        the step against what the jit actually promises GSPMD and
        hlo_audit compiles the same partitioned program the step runs."""
        from ...analysis.sharding_flow import ShardingContract

        return ShardingContract(in_shardings=self._in_sh,
                                out_shardings=self._out_sh,
                                mesh=self._batch_sharding.mesh)

    def _executable(self, path: str, site: str, jitted, args, xg, yg):
        """The AOT-compiled executable of one dispatch path for this batch
        signature, compiled on first use and kept: dispatch goes through it,
        so there is exactly one compile per (path, batch signature), and its
        ``memory_analysis()`` (mem.exe.*{site=...}) and HLO
        (``kernel_sites``) stay readable. Returns (executable, whether this
        call compiled it)."""
        key = (path, xg.shape, str(xg.dtype), yg.shape, str(yg.dtype))
        exe = self._exe.get(key)
        if exe is not None:
            return exe, False
        with _span("compile", site=site, cache_hit=0):
            exe = self._exe[key] = jitted.lower(*args).compile()
        _obs_memory.record_executable(site, exe)
        return exe, True

    @property
    def kernel_sites(self) -> Dict[str, int]:
        """{kernel name: Mosaic calls} over the step programs compiled so
        far — which Pallas kernels actually made it into what runs (empty
        before the first dispatch, and on CPU where none are compiled)."""
        from ...kernels.mesh import kernel_sites

        out: Dict[str, int] = {}
        for exe in self._exe.values():
            for name, n in kernel_sites(exe).items():
                out[name] = out.get(name, 0) + n
        return out

    def _obs_record(self, site: str, first: bool, seconds: float,
                    samples: Optional[int], steps: int = 1):
        """Per-step training telemetry + compile-cache accounting (gated on
        the observability flag by the helpers). ``first``: this dispatch
        compiled its executable, so its wall time is the compile cost."""
        _obs_instr.record_compile(site, seconds=seconds if first else None,
                                  cache_hit=not first)
        _obs_metrics.counter("train.steps", steps)
        if samples:
            _obs_metrics.counter("train.samples", samples)
        if not first:
            _obs_metrics.histogram("train.step.dispatch_seconds",
                                   seconds / max(steps, 1))
            # goodput attribution only for warm steps: the first dispatch's
            # wall time is compile, not compute
            _obs_goodput.observe_step(seconds, steps=steps)
        self._obs_nrecords += 1
        if first or self._obs_nrecords % 32 == 0:
            _obs_memory.record_live_buffers()
            _obs_memory.record_device_memory()
        if self._reducer is not None:
            # static schedule -> exact byte accounting per dispatched step
            _comm_opt.record_reduce_metrics(
                self._reducer, steps=steps,
                reductions_per_step=self._reductions_per_step)

    def _build_pipeline_loss(self, buffers0, remat: bool):
        """loss_impl for pp>1: shard_map manual over the pp axis only (dp/mp/
        sharding stay under GSPMD auto partitioning), GPipe ppermute schedule
        with grads flowing through its transpose (the backward pipeline)."""
        from jax import lax, shard_map

        from .meta_parallel.pipeline_parallel import (
            pipeline_schedule, pipeline_schedule_1f1b,
            pipeline_schedule_interleaved,
            pipeline_schedule_interleaved_1f1b)

        pspec = self._pspec
        mesh = self.mesh
        M = self._accum
        vpp = self._vpp
        prefix = self._stack_prefix

        from ..sharding_utils import maybe_shard

        def pipe_loss(pvals, x, y, seed):
            stacked = {k[len(prefix):]: v for k, v in pvals.items() if k.startswith(prefix)}
            other = {k: v for k, v in pvals.items() if not k.startswith(prefix)}

            with no_grad(), _random.rng_scope(seed):
                # pre/post run under plain GSPMD over the full mesh — only the
                # homogeneous block schedule is manual over pp. The head is
                # re-sharded over (dp, pp) below, so non-last stages help with
                # the LM-head FLOPs instead of idling (the reference computes
                # the head on the last stage only).
                h0 = pspec.pre(other, buffers0, x)
                B = h0.shape[0]
                if B % M:
                    raise ValueError(f"batch {B} not divisible by accumulate_steps {M}")
                mb = B // M
                # microbatch m = rows m::M — the strided split keeps each
                # dp shard's rows local through the reshape
                mbs = jnp.swapaxes(h0.reshape((mb, M) + h0.shape[1:]), 0, 1)

                with_aux = pspec.block_with_aux is not None

                def body(stacked_loc, mbs_loc):
                    def stage(bp, h, chunk_idx=None):
                        Lps = jax.tree_util.tree_leaves(bp)[0].shape[0]
                        # global first-layer index of this stage's slice:
                        # contiguous stages own [s*Lps, ...); under
                        # interleaving device d's chunk r covers layers
                        # (r*pp+d)*Lpc, and the schedule hands us that
                        # global chunk index — so layer-salted dropout
                        # matches the non-pipelined layer order exactly
                        base = (lax.axis_index("pp") if chunk_idx is None
                                else chunk_idx) * Lps

                        def one(carry, xs):
                            bpi, li = xs
                            # salt with the global layer index so dropout
                            # masks differ per block (scan traces once)
                            h, aux = carry
                            with _random.key_salt(base + li):
                                if with_aux:
                                    h, a = pspec.block_with_aux(bpi, h)
                                    aux = aux + a
                                else:
                                    h = pspec.block(bpi, h)
                            return (h, aux), None

                        (h, aux), _ = lax.scan(
                            one, (h, jnp.zeros((), jnp.float32)),
                            (bp, jnp.arange(Lps)))
                        return (h, aux) if with_aux else h

                    if vpp > 1:
                        # default (1f1b) pairs the v-fold bubble shrink with
                        # the O(pp*v) in-flight memory cap; "gpipe" keeps the
                        # plain AD-transposed scan (O(M) activation memory).
                        # remat=False asks for NO recompute — the 1f1b
                        # schedule IS a recompute stream, so that request
                        # routes to the AD path (which honors the flag)
                        sched_i = (pipeline_schedule_interleaved_1f1b
                                   if self._pp_schedule == "1f1b" and remat
                                   else pipeline_schedule_interleaved)
                        outs = sched_i(
                            stage, stacked_loc, mbs_loc, axis_name="pp",
                            virtual_stages=vpp, remat=remat, with_aux=with_aux)
                    elif self._pp_schedule == "1f1b":
                        # activation memory bounded by the pp degree (1F1B
                        # in-flight cap) instead of accumulate_steps
                        outs = pipeline_schedule_1f1b(
                            stage, stacked_loc, mbs_loc, axis_name="pp",
                            remat=remat, with_aux=with_aux)
                    else:
                        outs = pipeline_schedule(stage, stacked_loc, mbs_loc,
                                                 axis_name="pp", remat=remat,
                                                 with_aux=with_aux)
                    # expose the per-stage outputs on a leading pp axis; the
                    # caller slices the last stage — no psum broadcast of
                    # microbatch activations. The aux total is already
                    # psummed over pp (identical across stages).
                    if with_aux:
                        return outs[0][None], outs[1]
                    return outs[None]

                # when the mesh carries a sep (context-parallel) axis, the
                # pipeline region goes manual over it too: the microbatch
                # stream enters as local seq shards and the blocks' ring
                # attention runs directly (nested shard_map trips Shardy)
                sep_deg = dict(zip(mesh.axis_names, mesh.devices.shape)).get("sep", 1)
                # only models whose blocks run context-parallel attention may
                # receive local seq shards
                use_sep = sep_deg > 1 and getattr(pspec, "context_parallel", False)
                sep_deg = sep_deg if use_sep else 1
                if with_aux and sep_deg > 1:
                    raise NotImplementedError(
                        "MoE gate aux under context parallelism needs "
                        "per-shard capacity semantics; use sep_degree=1 "
                        "with MoE pipelines")
                manual = {"pp"} | ({"sep"} if sep_deg > 1 else set())
                mbs_spec = P(None, None, "sep") if sep_deg > 1 else P()
                h_spec = P("pp", None, None, "sep") if sep_deg > 1 else P("pp")
                out_specs = (h_spec, P()) if with_aux else h_spec
                outs_g = shard_map(
                    body, mesh=mesh,
                    in_specs=(P("pp"), mbs_spec),
                    out_specs=out_specs,
                    axis_names=manual,
                    check_vma=False,
                )(stacked, mbs)
                if with_aux:
                    outs_g, aux_total = outs_g
                h_last = outs_g[-1]  # [M, mb, ...] — the last stage's stream
                # loss PER MICROBATCH, averaged — the reference's train_batch
                # semantics (matters for ratio losses like masked-LM, where a
                # full-batch loss is NOT the mean of microbatch losses; it is
                # also what plain gradient accumulation computes). vmap keeps
                # the M head matmuls batched (one MXU call, not M serial)
                ys = jnp.swapaxes(y.reshape((B // M, M) + y.shape[1:]), 0, 1)
                # spread the M per-microbatch head matmuls over pp (so
                # non-last stages help with LM-head FLOPs) and keep mb on dp,
                # each guarded by divisibility — an infeasible split forces
                # the partitioner into replicate-then-partition
                sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
                head_spec = [None, None]
                if sizes.get("pp", 1) > 1 and M % sizes["pp"] == 0:
                    head_spec[0] = "pp"
                if (B // M) % max(sizes.get("dp", 1), 1) == 0:
                    head_spec[1] = "dp"
                h_last = maybe_shard(h_last, P(*head_spec))
                post_one = lambda hm, ym: pspec.post_loss(other, buffers0, hm, ym)
                if M <= max(2 * sizes.get("pp", 1), 4):
                    # small stream: one batched MXU call for all M heads
                    per_mb = jax.vmap(post_one)(h_last, ys)
                else:
                    # large accumulation: sequential remat'd heads so the
                    # logits buffer is one microbatch's, not M stacked —
                    # the per-microbatch loss shape 1F1B's memory assumes
                    per_mb = lax.map(
                        jax.checkpoint(lambda hy: post_one(*hy)),
                        (h_last, ys))
                loss = jnp.mean(per_mb.astype(jnp.float32))
                if with_aux:
                    # mean-over-microbatch gate aux, weighted — matches the
                    # per-microbatch sequential objective
                    loss = loss + pspec.aux_weight * aux_total / M
            return loss.astype(jnp.float32)

        return pipe_loss

    def _to_global_batch(self, a):
        """Host array -> device batch. Single-controller: plain transfer.
        Multi-process (real multi-host): the caller's array is its LOCAL
        shard — each process loads its own slice of the global batch, the
        multi-host data-loading contract — and the global array is
        assembled across processes (hybrid_parallel_util broadcast analog,
        inverted: data stays where it was loaded)."""
        v = a._value if isinstance(a, Tensor) else a
        if jax.process_count() > 1:
            if isinstance(v, jax.Array) and not v.is_fully_addressable:
                return v  # already assembled over the global mesh
            # local numpy OR a process-local jax.Array (every eager Tensor
            # holds one) — both are this process's batch shard; passing the
            # array through directly lets on-device data assemble without a
            # host round-trip
            return jax.make_array_from_process_local_data(
                self._batch_sharding, v)
        return jnp.asarray(v)

    def run_steps(self, xs, ys, lr: Optional[float] = None):
        """K optimizer steps in ONE compiled dispatch: lax.scan over stacked
        [K, ...] batches. Amortizes per-dispatch host overhead (decisive for
        short-step models like convnets) — the multi-batch analog of the
        reference's C++ executor running the whole program per call. Returns
        the [K] per-step losses."""
        lr = self.optimizer.get_lr() if lr is None else lr
        scaled = self.scaler_state is not None
        if self._multi is None:
            base = self._compiled_step_fn
            health = self._health

            def multi(params, opt_state, bufs, sstate, ef, xs, ys, lr, seed,
                      hp=None):
                def body(carry, xy):
                    p, s, b, ss, e = carry
                    xk, yk, k = xy
                    extra = (hp,) if health else ()
                    if scaled:
                        out = base(p, s, b, ss, e, xk, yk, lr, seed + k,
                                   *extra)
                        p, s, b, e, ss = out[:5]
                    else:
                        out = base(p, s, b, e, xk, yk, lr, seed + k, *extra)
                        p, s, b, e = out[:4]
                    # per-step stream: (loss,) or (loss, health stats) —
                    # scan stacks the stats to [K, G] so every scanned
                    # step stays individually observable
                    return (p, s, b, ss, e), out[5 if scaled else 4:]

                (params, opt_state, bufs, sstate, ef), ys_out = jax.lax.scan(
                    body, (params, opt_state, bufs, sstate, ef),
                    (xs, ys, jnp.arange(xs.shape[0], dtype=jnp.uint32)))
                return (params, opt_state, bufs, sstate, ef) + tuple(ys_out)

            bspec = self._batch_sharding.spec
            stacked = NamedSharding(self.mesh, P(None, *bspec))
            hp_in = (None,) if health else ()
            h_out = (None,) if health else ()
            self._multi = jax.jit(
                multi,
                in_shardings=(self._p_shard, self._s_shard, None, None,
                              self._ef_shard, stacked, stacked, None,
                              None) + hp_in,
                out_shardings=(self._p_shard, self._s_shard, None, None,
                               self._ef_shard,
                               NamedSharding(self.mesh, P())) + h_out,
                donate_argnums=(0, 1, 2, 3, 4) if self._donate else (),
            )
        K = xs.shape[0] if hasattr(xs, "shape") else len(xs)
        self._step_i += K
        ss_in = self.scaler_state if scaled else jnp.zeros((), jnp.float32)
        with _span("train/step", step=self._step_i) as sp:
            xg, yg = jnp.asarray(xs), jnp.asarray(ys)
            if self._health:
                self.health_flush()
            args = (self.params, self.opt_state, self.buffers, ss_in,
                    self.ef_state, xg, yg,
                    # +1 so scanned step j draws seed (seed + prev_steps + 1
                    # + j) — identical to the seeds K sequential __call__s
                    # would use
                    jnp.float32(lr),
                    jnp.uint32(self._seed + self._step_i - K + 1))
            if self._health:
                args = args + (jnp.asarray(self._health_poison),)
            with jax.set_mesh(self.mesh):
                exe, first = self._executable(
                    "multi", "sharded_train_step.run_steps", self._multi,
                    args, xg, yg)
                out = exe(*args)
                (self.params, self.opt_state, self.buffers, ss_out,
                 self.ef_state, losses) = out[:6]
            sp.set(first=int(first), steps=K)
        if _obs_metrics.enabled():
            samples = None
            if hasattr(xs, "shape") and len(getattr(xs, "shape", ())) >= 2:
                samples = int(xs.shape[0]) * int(xs.shape[1])
            self._obs_record("sharded_train_step.run_steps", first,
                             sp.seconds, samples, steps=K)
        if scaled:
            self.scaler_state = ss_out
        if self._health:
            self._health_observe_multi(out[6], losses, K, scaled)
        return losses

    def __call__(self, x, y, lr: Optional[float] = None):
        """One optimizer step handed to the device, under one ``train/step``
        span: argument preparation and the executable call (the host's
        time; the device finishes later)."""
        lr = self.optimizer.get_lr() if lr is None else lr
        self._step_i += 1
        with _span("train/step", step=self._step_i) as sp:
            xg, yg = self._to_global_batch(x), self._to_global_batch(y)
            scaled = self.scaler_state is not None
            if self._health:
                # deliver the PREVIOUS step's stats first (they are already
                # computed on device — observing one step behind costs no
                # dispatch stall; detection latency is one step)
                self.health_flush()
            if scaled:
                args = (self.params, self.opt_state, self.buffers,
                        self.scaler_state, self.ef_state, xg, yg,
                        jnp.float32(lr),
                        jnp.uint32(self._seed + self._step_i))
            else:
                args = (self.params, self.opt_state, self.buffers,
                        self.ef_state, xg, yg,
                        jnp.float32(lr),
                        jnp.uint32(self._seed + self._step_i))
            if self._health:
                args = args + (jnp.asarray(self._health_poison),)
            with jax.set_mesh(self.mesh):
                exe, first = self._executable("step", "sharded_train_step",
                                              self._compiled, args, xg, yg)
                out = exe(*args)
                hstats = None
                if self._health:
                    out, hstats = out[:-1], out[-1]
                if scaled:
                    (self.params, self.opt_state, self.buffers,
                     self.ef_state, self.scaler_state, loss) = out
                else:
                    (self.params, self.opt_state, self.buffers,
                     self.ef_state, loss) = out
            if self._health:
                self._health_observe(loss, hstats)
            sp.set(first=int(first))
        if _obs_metrics.enabled():
            samples = None
            if hasattr(x, "shape") and len(getattr(x, "shape", ())) >= 1:
                samples = int(x.shape[0])
            self._obs_record("sharded_train_step", first, sp.seconds,
                             samples)
        return loss

    step = __call__

    @property
    def step_index(self) -> int:
        """Optimizer steps completed so far (checkpoint restore rewinds
        this; the elastic supervisor resumes its loop from it)."""
        return self._step_i

    def axis_sizes(self) -> Dict[str, int]:
        """{axis: size} of this step's mesh — the declared-parallelism
        view mesh re-formation plans against."""
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def loss_scaling(self) -> float:
        """Current dynamic loss scale (1.0 when no scaler is attached)."""
        if self.scaler_state is None:
            return 1.0
        return float(self.scaler_state[0])

    def sync_scaler(self):
        """Write the device scale automaton back into the attached
        GradScaler (for state_dict/checkpoint round trips)."""
        if self.scaler_state is None or self._scaler is None:
            return
        self._scaler._scale = float(self.scaler_state[0])
        self._scaler._good_steps = int(self.scaler_state[1])
        self._scaler._bad_steps = int(self.scaler_state[2])

    # ---------- training-numerics health (observability.health) ----------
    @property
    def health_groups(self):
        """Ordered param-group names of the in-graph stat pass ([] when
        health stats are off)."""
        return list(self._health_groups) if self._health else []

    def attach_health_monitor(self, monitor):
        """Bind a HealthMonitor: each step's in-graph stats reach
        ``monitor.observe()`` at the START of the next step (pipelined —
        the device values are ready by then, so observation never stalls
        a dispatch). Call ``health_flush()`` after the last step of a
        loop to deliver the final pending stats. Returns the monitor."""
        if not self._health:
            raise ValueError(
                "health stats are off for this step; build with "
                "health_stats=True (or FLAGS_health_stats=1 / "
                "set_flags({'health_stats': True}) before construction)")
        monitor.bind_groups(self._health_groups)
        self._health_monitor = monitor
        return monitor

    def health_flush(self):
        """Deliver any pending stats to the attached monitor (blocks on
        the device values). Returns the anomaly records raised."""
        pending, self._health_pending = self._health_pending, None
        if pending is None or self._health_monitor is None:
            return []
        return self._health_monitor.observe(**pending)

    def set_grad_poison(self, group=None, value=float("nan")):
        """Fault injector (tests/bench): from the next step on, multiply
        GROUP's gradients by VALUE inside the compiled step (the poison
        vector is a traced input — no recompile). ``group=None`` resets
        to the all-ones healthy vector."""
        if not self._health:
            raise ValueError("health stats are off for this step")
        import numpy as _np

        vec = _np.ones(len(self._health_groups), _np.float32)
        if group is not None:
            vec[self._health_groups.index(group)] = value
        self._health_poison = vec

    def _health_observe(self, loss, stats):
        """Stash one dispatched step's device stats for the next flush."""
        self.health_state = stats
        mon = self._health_monitor
        if mon is None:
            return
        self._health_pending = {
            "step": self._step_i, "loss": loss, "stats": stats,
            "loss_scale": (self.scaler_state[0]
                           if self.scaler_state is not None else None),
            "data_position": mon.data_position(),
        }

    def _health_observe_multi(self, hstack, losses, K, scaled):
        """run_steps: observe all K scanned steps from the stacked [K, G]
        stats. The scaler automaton is scan carry, so only the final
        scale is visible — passed with the last step's observation."""
        tm = jax.tree_util.tree_map
        self.health_state = tm(lambda v: v[-1], hstack)
        mon = self._health_monitor
        if mon is None:
            return
        pos = mon.data_position()
        ls = self.scaler_state[0] if scaled else None
        for k in range(K):
            mon.observe(step=self._step_i - K + k + 1, loss=losses[k],
                        stats=tm(lambda v, _k=k: v[_k], hstack),
                        loss_scale=ls if k == K - 1 else None,
                        data_position=pos)

    def sync_to_model(self):
        """Write the step's device state (params + buffers) back into the
        Layer. REQUIRED before any eager use of the model mid-training:
        with donate=True (default) each step consumes its input arrays —
        including, after the first sync, the model's own — so the Layer's
        tensors are stale/deleted until re-synced."""
        named_bufs = dict(self.model.named_buffers())
        for name, v in (self.buffers or {}).items():
            if name in named_bufs and named_bufs[name] is not None:
                named_bufs[name]._set_value_raw(v)
        named = dict(self.model.named_parameters())
        if self._pspec is not None:
            from .meta_parallel.pipeline_parallel import unstack_block_params

            prefix = self._stack_prefix
            stacked = {k[len(prefix):]: v for k, v in self.params.items()
                       if k.startswith(prefix)}
            flat = unstack_block_params(stacked, self._pspec, pp=self._pp,
                                        virtual_stages=self._vpp)
            for name, v in self.params.items():
                if not name.startswith(prefix):
                    named[name]._set_value_raw(v)
            for name, v in flat.items():
                named[name]._set_value_raw(v)
            return
        for name, v in self.params.items():
            named[name]._set_value_raw(v)

    # ---------- fault-tolerant checkpointing (paddle_tpu.checkpoint) ----------
    def state_for_checkpoint(self):
        """The step's full resume state as a composite TrainState: params,
        optimizer state, buffers, loss-scaler automaton, and the
        (seed, step) RNG position — one tree, so a CheckpointManager.save
        publishes it atomically and resume is bitwise-faithful (same
        parameter bits, same dropout streams, same scaler state).

        Snapshot before the next step(): donation consumes these arrays."""
        from ...checkpoint import TrainState

        extra = {}
        if self.scaler_state is not None:
            extra["scaler_state"] = list(self.scaler_state)
        if self.ef_state:
            # error-feedback residuals are convergence state: losing them
            # on resume would replay one step's compression error twice
            extra["grad_reduce_ef"] = dict(self.ef_state)
        extra = extra or None
        return TrainState(
            params=self.params,
            opt_state=self.opt_state,
            buffers=self.buffers or None,
            rng={"seed": int(self._seed)},
            step=self._step_i,
            extra=extra,
        )

    def checkpoint_shardings(self):
        """Shardings tree aligned with state_for_checkpoint().to_tree() —
        hand to CheckpointManager.restore so params/opt state come back
        device-resident in THIS step's layout (which may differ from the
        save-time mesh: restore-time resharding)."""
        return {"params": dict(self._p_shard), "opt_state": self._s_shard}

    def restore_from_checkpoint(self, tree):
        """Adopt a restored TrainState tree (from CheckpointManager.restore,
        ideally with checkpoint_shardings()). Leaves still resident on a
        mesh (e.g. state handed over across an elastic mesh re-form) move
        device-to-device through the resharding planner; host-numpy leaves
        are placed onto this step's mesh the ordinary way — either way a
        checkpoint saved under a different topology restores cleanly."""
        from ...checkpoint import TrainState
        from .. import resharding as _resharding

        ts = tree if isinstance(tree, TrainState) else TrainState.from_tree(tree)
        self.params = {k: _resharding.reshard(v, self._p_shard[k])
                       for k, v in ts.params.items()}
        self.opt_state = jax.tree_util.tree_map(
            lambda v, s: _resharding.reshard(v, s), ts.opt_state, self._s_shard)
        if ts.buffers is not None:
            self.buffers = jax.tree_util.tree_map(jnp.asarray, ts.buffers)
        if ts.extra and ts.extra.get("scaler_state") is not None:
            sc = ts.extra["scaler_state"]
            self.scaler_state = (jnp.float32(sc[0]), jnp.int32(sc[1]),
                                 jnp.int32(sc[2]))
        if self._reducer is not None and self._reducer.has_ef:
            ef_in = (ts.extra or {}).get("grad_reduce_ef")
            if ef_in is not None and self._reducer.ef_matches(ef_in):
                self.ef_state = {
                    k: jax.device_put(jnp.asarray(v, jnp.float32),
                                      self._ef_shard[k])
                    for k, v in dict(ef_in).items()}
            else:
                # topology or bucket-plan change (or a checkpoint saved
                # without the reducer): residuals don't transfer — reset
                self.ef_state = {
                    k: jax.device_put(v, self._ef_shard[k])
                    for k, v in self._reducer.init_ef().items()}
        self._step_i = int(ts.step)
        if ts.rng and "seed" in ts.rng:
            self._seed = int(ts.rng["seed"])
        return self

    def step_jaxpr(self, x, y):
        """Trace the raw (pre-pjit) step into a ClosedJaxpr — the input
        the step-anatomy tier's per-scope cost walker consumes
        (``observability/anatomy.scope_costs``). Trace-only: nothing is
        lowered or compiled."""
        hp = ((jnp.asarray(self._health_poison),) if self._health else ())
        if self.scaler_state is not None:
            args = (self.params, self.opt_state, self.buffers,
                    self.scaler_state, self.ef_state, jnp.asarray(x),
                    jnp.asarray(y), jnp.float32(1e-3), jnp.uint32(0), *hp)
        else:
            args = (self.params, self.opt_state, self.buffers,
                    self.ef_state, jnp.asarray(x), jnp.asarray(y),
                    jnp.float32(1e-3), jnp.uint32(0), *hp)
        return jax.make_jaxpr(self._compiled_step_fn)(*args)

    def lower_compiled(self, x, y):
        """AOT-lower the program ``__call__`` dispatches (traced under the
        step's mesh, like it) without executing — for compile checks, and
        for a step built on a compile-only mesh."""
        hp = ((jnp.asarray(self._health_poison),) if self._health else ())
        scaler = (() if self.scaler_state is None else (self.scaler_state,))
        args = (self.params, self.opt_state, self.buffers, *scaler,
                self.ef_state, jnp.asarray(x), jnp.asarray(y),
                jnp.float32(1e-3), jnp.uint32(0), *hp)
        with jax.set_mesh(self.mesh):
            return self._compiled.lower(*args)


def make_sharded_train_step(model, optimizer, loss_fn=None, mesh=None,
                            autoshard: bool = False,
                            autoshard_fixed_mesh: bool = False,
                            **kwargs) -> ShardedTrainStep:
    """Build a ShardedTrainStep; with ``autoshard=True`` the layout search
    (``paddle_tpu.autoshard``) runs first over a probe step under the
    hand-written seed layout, and the returned step is rebuilt on the
    winning mesh/param table (a seed win returns the probe itself). The
    search result is attached as ``step.autoshard_result``.
    ``autoshard_fixed_mesh=True`` keeps the given mesh and searches only
    the param layout (elastic re-formation: the supervisor owns the mesh)."""
    if not autoshard:
        return ShardedTrainStep(model, optimizer, loss_fn=loss_fn, mesh=mesh, **kwargs)

    from ...autoshard import search as _autoshard

    probe = ShardedTrainStep(model, optimizer, loss_fn=loss_fn, mesh=mesh, **kwargs)
    result = _autoshard.search_train_step(probe=probe,
                                          fixed_mesh=autoshard_fixed_mesh)
    win = result.winner
    if win is None or win.is_seed:
        probe.autoshard_result = result
        return probe
    step = ShardedTrainStep(
        model, optimizer, loss_fn=loss_fn,
        mesh=(probe.mesh if autoshard_fixed_mesh
              else _autoshard.winner_mesh(win.candidate)),
        param_specs=_autoshard.winner_param_specs(win.candidate),
        **kwargs)
    step.autoshard_result = result
    return step
