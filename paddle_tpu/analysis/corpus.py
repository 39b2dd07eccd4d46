"""The real-entry-point corpus the CI gate lints.

``build_corpus()`` constructs (without executing a single training or
serving step — everything is traced abstractly) the programs whose
invariants the last eight PRs only enforced dynamically:

- ``train_step``            ShardedTrainStep's compiled step body (dp mesh)
- ``train_step_grad_reduce`` same, with the int8 quantized GradReducer
  inlined — its contract carries the reducer plan's own wire-byte
  accounting for the analyzer to reconcile against
- ``train_step_moe``        GPT-MoE step on a dp x ep mesh with
  ``moe_dispatch="quant"`` — the token exchanges are explicit int8
  all-to-alls whose DispatchPlan accounting the analyzer reconciles
- ``serving_prefill`` / ``serving_decode`` / ``serving_verify``  the
  Engine's AOT programs (verify = the speculative [B, k+1] decode step),
  with the KV-cache donation contract the engine compiles with
- ``grad_reducer``          the standalone comm_opt tree reducer schedule
- ``reshard``               a resharding executor body ((2,2)->(4,) move)
- ``ir_optimized``          an ir.trace'd program after the default pass
  pipeline, re-traced through ``to_callable``

Entries that need more devices than the host has (or whose plan is empty)
are skipped with a recorded reason, never silently dropped: the gate tool
prints the skip list. Corpus construction is deterministic (fixed seeds)
so finding fingerprints are stable across runs and hosts with the same
device count.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .analyzer import ProgramSpec, SiteContract

__all__ = ["build_corpus"]

_STEP_ARGNAMES = ("params", "opt_state", "buffers", "ef", "x", "y",
                  "lr", "seed")


def _gpt_step(mesh, grad_reduce=None):
    import paddle_tpu as paddle
    from ..distributed.fleet.utils import make_sharded_train_step
    from ..models import gpt_tiny

    paddle.seed(0)
    model = gpt_tiny(dropout=0.0, num_layers=2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    return make_sharded_train_step(model, opt, mesh=mesh,
                                   grad_reduce=grad_reduce)


def _step_args(st, batch):
    rng = np.random.RandomState(0)
    x = rng.randint(0, 128, size=(batch, 16))
    y = np.roll(x, -1, axis=1)
    return (st.params, st.opt_state, st.buffers, st.ef_state,
            jnp.asarray(x), jnp.asarray(y), jnp.float32(1e-3),
            jnp.uint32(0))


def _train_step_spec() -> ProgramSpec:
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    st = _gpt_step(mesh)
    return ProgramSpec(
        "train_step", st._compiled_step_fn, _step_args(st, 2 * mesh.size),
        SiteContract(one_compile=True, donate_argnums=(0, 1, 2, 3)),
        argnames=_STEP_ARGNAMES, sharding=st.sharding_contract())


def _train_step_grad_reduce_spec() -> ProgramSpec:
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    st = _gpt_step(mesh, grad_reduce="int8")
    if st._reducer is None:
        raise RuntimeError("int8 reducer inactive on this topology")
    return ProgramSpec(
        "train_step_grad_reduce", st._compiled_step_fn,
        _step_args(st, 2 * mesh.size),
        SiteContract(
            one_compile=True, donate_argnums=(0, 1, 2, 3),
            # ReducePlan counts per-device receive-side bytes per step —
            # the analyzer's own convention, so no rescaling
            expected_wire_bytes=st._reducer.plan.bytes_wire_per_step),
        argnames=_STEP_ARGNAMES, sharding=st.sharding_contract())


def _train_step_moe_spec() -> ProgramSpec:
    """GPT-MoE train step on a dp x ep mesh with moe_dispatch='quant': the
    token dispatch/combine exchanges are explicit block-scaled int8
    all-to-alls (incubate .../moe/dispatch.py), so the site carries the
    DispatchPlan's own wire accounting for the analyzer to reconcile —
    the only jaxpr-level collectives in the program are the quantized
    exchanges (grads stay on GSPMD's implicit path)."""
    import paddle_tpu as paddle
    from ..distributed import mesh as _mesh
    from ..distributed.fleet.utils import make_sharded_train_step
    from ..incubate.distributed.models.moe.dispatch import plan_quant_dispatch
    from ..models import gpt_moe_tiny

    devs = jax.devices()
    mesh = Mesh(np.array(devs[:8]).reshape(2, 4), ("dp", "ep"))
    prev = _mesh.current_mesh()
    _mesh.set_global_mesh(mesh)  # moe_route resolves its plan from here
    try:
        paddle.seed(0)
        model = gpt_moe_tiny(dropout=0.0, moe_dispatch="quant")
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        st = make_sharded_train_step(model, opt, mesh=mesh)
        args = _step_args(st, 2 * mesh.size)
        # one MoE block (every_k=2 over 2 layers); T = batch * seq
        T = int(args[4].shape[0] * args[4].shape[1])
        E = model.cfg.moe_num_experts
        cap = max(1, int(model.cfg.moe_capacity_factor * T / E))
        plan = plan_quant_dispatch(T, E, cap, model.cfg.hidden_size)
        if plan is None:
            raise RuntimeError("quant dispatch plan inactive on this mesh")
    finally:
        if prev is not None:
            _mesh.set_global_mesh(prev)
        else:
            _mesh.reset_global_mesh()

    # mesh=: the analyzer traces lazily, after the builder restored the
    # global mesh — its tracers re-enter the mesh context so moe_route
    # resolves the quant plan exactly as the product step does (utils.py
    # traces under jax.set_mesh(self.mesh) too)
    return ProgramSpec(
        "train_step_moe", st._compiled_step_fn, args,
        SiteContract(one_compile=True, donate_argnums=(0, 1, 2, 3),
                     expected_wire_bytes=plan.bytes_wire_train_step),
        argnames=_STEP_ARGNAMES, sharding=st.sharding_contract(), mesh=mesh)


def _serving_specs() -> List[ProgramSpec]:
    import paddle_tpu as paddle
    from ..models import gpt_tiny
    from ..serving.engine import KV_DONATE_ARGNUMS, Engine

    paddle.seed(0)
    model = gpt_tiny(dropout=0.0, num_layers=2)
    eng = Engine(model, max_batch_size=2, max_seq_len=32)
    contract = SiteContract(one_compile=True,
                            donate_argnums=KV_DONATE_ARGNUMS,
                            donation_threshold=4096)
    # prefill scatters the prompt into the pages of the slot's table row
    # (page_row) and decode carries the [B, num_blocks] page table as
    # runtime data. k_pages / v_pages are TUPLES of per-layer pools (one
    # argname, one donated argnum, num_layers leaves each)
    pre_fn, pre_args = eng.prefill_program(8)
    dec_fn, dec_args = eng.decode_program()
    # speculative verify-k: the decode step widened to [B, k+1] — same
    # one-compile + donation contract; traced here WITHOUT enabling
    # speculation on the engine (verify_program takes k explicitly), so
    # building the corpus never compiles anything
    ver_fn, ver_args = eng.verify_program(k=2)
    return [
        ProgramSpec("serving_prefill", pre_fn, pre_args, contract,
                    argnames=("params", "k_pages", "v_pages", "ids",
                              "page_row", "length"),
                    sharding=eng.sharding_contract(len(pre_args))),
        ProgramSpec("serving_decode", dec_fn, dec_args,
                    # decode also donates the tokens and positions it puts
                    # out again for the next step
                    dataclasses.replace(
                        contract,
                        donate_argnums=eng.donate_argnums_of("decode")),
                    argnames=("params", "k_pages", "v_pages", "page_table",
                              "tokens", "positions", "temps", "top_ks",
                              "greedy", "host_tokens", "key"),
                    sharding=eng.sharding_contract(len(dec_args))),
        ProgramSpec("serving_verify", ver_fn, ver_args, contract,
                    argnames=("params", "k_pages", "v_pages", "page_table",
                              "tokens", "positions", "temps", "top_ks",
                              "greedy", "key"),
                    sharding=eng.sharding_contract(len(ver_args))),
    ]


def _grad_reducer_spec() -> ProgramSpec:
    from ..distributed.comm_opt import (GradReduceConfig, make_tree_reducer,
                                        reducer_for_step)

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    shapes = {"w1": (40, 33), "b1": (33,), "w2": (7, 5, 11)}
    templates = {k: (v, np.dtype(np.float32)) for k, v in shapes.items()}
    red = reducer_for_step(GradReduceConfig(mode="quant", dtype="int8"),
                           mesh, ("dp",), templates)
    if red is None:
        raise RuntimeError("quant reducer inactive on this topology")
    fn = make_tree_reducer(red)
    world = mesh.size
    gstack = {k: jnp.zeros((world,) + v, jnp.float32)
              for k, v in shapes.items()}
    ef = {k: jnp.asarray(v) for k, v in red.init_ef().items()}
    return ProgramSpec(
        "grad_reducer", fn, (gstack, ef),
        SiteContract(expected_wire_bytes=red.plan.bytes_wire_per_step),
        argnames=("grads", "ef"),
        sharding=red.sharding_contract(sorted(gstack), sorted(ef)))


def _reshard_spec() -> ProgramSpec:
    from ..distributed.resharding.executor import (_compiled_executor,
                                                   executor_contract,
                                                   plan_for)

    devs = jax.devices()
    src_mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("a", "b"))
    dst_mesh = Mesh(np.array(devs[:4]), ("c",))
    arr = jax.device_put(
        np.arange(64 * 8, dtype=np.float32).reshape(64, 8),
        NamedSharding(src_mesh, P("a", "b")))
    plan = plan_for(arr, NamedSharding(dst_mesh, P("c")))
    if not plan.steps:
        raise RuntimeError("reshard plan is an identity move")
    fn = _compiled_executor(plan, src_mesh)
    return ProgramSpec(
        "reshard", fn, (arr,),
        # ReshardPlan.bytes_wire totals receive bytes ACROSS all devices;
        # the analyzer estimates per device
        SiteContract(expected_wire_bytes=plan.bytes_wire // plan.world),
        argnames=("arr",), sharding=executor_contract(plan, src_mesh))


def _ir_optimized_spec() -> ProgramSpec:
    from .. import ir as _ir

    def net(x):
        w = jnp.ones((16, 16), jnp.float32)
        y = x @ w + jnp.float32(0.0)
        return jnp.tanh(y) * jnp.float32(1.0)

    x = jnp.ones((4, 16), jnp.float32)
    prog = _ir.trace(net, x)
    _ir.PassManager().run(prog)
    return ProgramSpec("ir_optimized", prog.to_callable(), (x,),
                       argnames=("x",))


def build_corpus() -> Tuple[List[ProgramSpec], List[Tuple[str, str]]]:
    """(specs, [(name, skip_reason)]). Construction failures are skips —
    the gate tool surfaces them — but never abort the whole corpus."""
    builders = [
        ("train_step", 1, _train_step_spec),
        ("train_step_grad_reduce", 2, _train_step_grad_reduce_spec),
        ("train_step_moe", 8, _train_step_moe_spec),
        ("serving", 1, _serving_specs),
        ("grad_reducer", 2, _grad_reducer_spec),
        ("reshard", 4, _reshard_spec),
        ("ir_optimized", 1, _ir_optimized_spec),
    ]
    ndev = jax.device_count()
    specs: List[ProgramSpec] = []
    skipped: List[Tuple[str, str]] = []
    for name, min_dev, build in builders:
        if ndev < min_dev:
            skipped.append((name, f"needs >= {min_dev} devices, have {ndev}"))
            continue
        try:
            out = build()
        except Exception as e:  # noqa: BLE001 - recorded, surfaced by gate
            skipped.append((name, f"{type(e).__name__}: {e}"))
            continue
        specs.extend(out if isinstance(out, list) else [out])
    return specs, skipped
