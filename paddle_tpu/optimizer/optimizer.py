"""Optimizer base + the SGD/Momentum/Adam family.

Analog of python/paddle/optimizer/optimizer.py + phi fused optimizer kernels
(fused_adam_kernel.cu etc). Each optimizer's math lives in a pure per-tensor
``_update(value, grad, state, lr) -> (new_value, new_state)`` so the SAME
kernel serves both regimes:
  * eager: ``step()`` walks params, applies clip/weight-decay, rebinds values;
  * jitted/pjit: ``apply_gradients(params, grads, state)`` maps the update
    over pytrees inside a traced train step (accumulator sharding specs ride
    along for ZeRO — see distributed/sharding.py).
Master weights: with multi_precision=True, bf16/fp16 params keep an fp32
master copy in state (the reference's master-weight path in adamw op).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.place import on_tpu
from ..core.tensor import Parameter, Tensor
from .lr import LRScheduler


class _NamedParamMeta:
    """Stand-in for a Parameter in the pure apply_gradients path, so
    name-keyed update rules (LARS exclude_from_weight_decay) and
    layout-keyed ones (the fused AdamW kernel's shard spec) see the same
    metadata as the eager step(): ``name`` and ``dist_spec``."""

    __slots__ = ("name", "dist_spec")

    def __init__(self, name, dist_spec=None):
        self.name = name
        self.dist_spec = dist_spec


class Optimizer:
    _state_names: List[str] = []

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None, grad_clip=None, name=None,
                 multi_precision=False):
        self._lr = learning_rate
        self._parameters = list(parameters) if parameters is not None else None
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators: Dict[int, dict] = {}
        self._step_count = 0
        self.regularization = weight_decay

    # ---- lr ----
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr.last_lr)
        return float(self._lr)

    def set_lr(self, value: float):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("Cannot set_lr when a LRScheduler is attached")
        self._lr = float(value)

    @property
    def _learning_rate(self):
        return self._lr

    # ---- state ----
    def _init_state(self, value) -> dict:
        """Per-parameter accumulator init; value is the (possibly master) array."""
        return {}

    def _get_state(self, p: Parameter) -> dict:
        state = self._accumulators.get(p._uid)
        if state is None:
            value = p._value
            state = self._init_state(value.astype(jnp.float32) if self._use_master(p) else value)
            if self._use_master(p):
                state["master_weight"] = value.astype(jnp.float32)
            self._accumulators[p._uid] = state
        return state

    def _use_master(self, p: Parameter) -> bool:
        return self._multi_precision and p._value.dtype in (jnp.bfloat16, jnp.float16)

    # ---- core pure update (override) ----
    def _update(self, value, grad, state: dict, lr: float, param_meta=None):
        raise NotImplementedError

    def _decoupled_wd(self) -> float:
        """AdamW-style decoupled weight decay coefficient (0 = off)."""
        return 0.0

    def _takes_native_grad(self, value) -> bool:
        """True when _update accepts grads at their native dtype (a fused
        kernel casting in VMEM); apply_gradients then skips the f32
        pre-convert that would materialize a full grad copy in HBM."""
        return False

    def _coupled_wd(self) -> float:
        """L2-regularization folded into the gradient (SGD/Momentum/Adam style)."""
        wd = self._weight_decay
        if wd is None:
            return 0.0
        if hasattr(wd, "coeff"):
            return float(wd.coeff)
        if isinstance(wd, (int, float)):
            return float(wd)
        return 0.0

    # ---- eager step ----
    @jax.named_scope("optimizer_step")
    def step(self):
        params = self._parameters
        if params is None:
            raise ValueError("Optimizer constructed without parameters; pass parameters=model.parameters()")
        params_grads = [(p, p.grad) for p in params if not p.stop_gradient and p.grad is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        self._step_count += 1
        lr = self.get_lr()
        for p, g in params_grads:
            if g is None:
                continue
            state = self._get_state(p)
            value = state.get("master_weight", p._value)
            gv = g._value
            reg = getattr(p, "regularizer", None)
            if reg is not None:
                # per-param regularizer overrides the optimizer-level decay
                gv = reg(gv.astype(value.dtype), value)
            else:
                cwd = self._coupled_wd()
                if cwd:
                    gv = gv.astype(value.dtype) + cwd * value
            # plain trainable Tensors (not Parameter) carry no optimize_attr
            plr = lr * getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)
            new_value, new_state = self._update(value, gv.astype(value.dtype), state, plr, param_meta=p)
            if "master_weight" in state:
                new_state["master_weight"] = new_value
                p._set_value_raw(new_value.astype(p._value.dtype))
            else:
                # eager dtype pin (see apply_gradients): trust-ratio math in
                # f32 must not promote bf16 params step over step
                p._set_value_raw(new_value.astype(p._value.dtype)
                                 if new_value.dtype != p._value.dtype else new_value)
            self._accumulators[p._uid] = new_state

    def clear_grad(self, set_to_zero: bool = False):
        if self._parameters:
            for p in self._parameters:
                p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from ..nn.layer.layers import in_dynamic_mode

        if not in_dynamic_mode():
            # static mode: append grad + update nodes to the default Program
            # (the analog of appending sgd/adam ops; fluid/backward.py:1865)
            from ..static.program import append_backward, append_optimizer

            params_grads = append_backward(loss, parameter_list=parameters, no_grad_set=no_grad_set)
            append_optimizer(self, params_grads)
            return None, params_grads
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    # ---- functional path (jit/pjit train steps) ----
    def init_state_pytree(self, params: dict):
        """{name: array} -> {name: {slot: array}} initial accumulators."""
        out = {}
        for name, v in params.items():
            use_master = self._multi_precision and v.dtype in (jnp.bfloat16, jnp.float16)
            base = v.astype(jnp.float32) if use_master else v
            s = self._init_state(base)
            if use_master:
                s["master_weight"] = base
            out[name] = s
        return out

    def apply_gradients(self, params: dict, grads: dict, state: dict, lr=None, step_count=None,
                        update_specs: Optional[dict] = None):
        """Pure: returns (new_params, new_state). Usable inside jit/pjit.
        ``update_specs`` ({name: PartitionSpec}) is the layout each
        parameter's update runs in under a mesh (its optimizer state's);
        kernels that must be sharded by hand read it."""
        lr = self.get_lr() if lr is None else lr
        update_specs = update_specs or {}
        new_params, new_state = {}, {}
        for name, v in params.items():
            g = grads.get(name)
            if g is None:
                new_params[name] = v
                new_state[name] = state[name]
                continue
            s = dict(state[name])
            value = s.get("master_weight", v)
            # optimizers whose update kernel casts internally (fused AdamW)
            # take the grad at its native dtype — a pre-convert here would
            # materialize a full f32 grad copy in HBM per parameter
            gv = g if self._takes_native_grad(value) else g.astype(value.dtype)
            cwd = self._coupled_wd()
            if cwd:
                gv = gv.astype(value.dtype) + cwd * value
            if step_count is not None:
                s = {**s, "_step_override": step_count}
            # name-only meta so name-keyed rules (LARS exclude lists) apply
            # identically in the compiled path and the eager step()
            nv, ns = self._update(
                value, gv, s, lr,
                param_meta=_NamedParamMeta(name, update_specs.get(name)))
            ns.pop("_step_override", None)
            # pin output dtypes to the input dtypes: a traced f32 lr (or a
            # trust-ratio norm) silently promotes bf16 params/states to
            # f32, which retraces the jitted step with f32 weights against
            # bf16 activations and breaks dtype-strict ops like conv
            ns = {k: (sv.astype(state[name][k].dtype)
                      if k in state[name] and hasattr(sv, "dtype")
                      and hasattr(state[name][k], "dtype")
                      and sv.dtype != state[name][k].dtype else sv)
                  for k, sv in ns.items()}
            if "master_weight" in s:
                ns["master_weight"] = nv
                new_params[name] = nv.astype(v.dtype)
            else:
                new_params[name] = nv.astype(v.dtype) if nv.dtype != v.dtype else nv
            new_state[name] = ns
        return new_params, new_state

    # ---- checkpointing ----
    def state_dict(self):
        out = {}
        if self._parameters:
            for p in self._parameters:
                state = self._accumulators.get(p._uid)
                if state:
                    for k, v in state.items():
                        out[f"{p.name}_{k}"] = Tensor(v) if not isinstance(v, Tensor) else v
        out["global_step"] = self._step_count
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def set_state_dict(self, state_dict):
        if "global_step" in state_dict:
            v = state_dict["global_step"]
            self._step_count = int(v.item() if isinstance(v, Tensor) else v)
        if "LR_Scheduler" in state_dict and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state_dict["LR_Scheduler"])
        if self._parameters:
            for p in self._parameters:
                state = self._get_state(p)
                for k in list(state.keys()):
                    key = f"{p.name}_{k}"
                    if key in state_dict:
                        v = state_dict[key]
                        state[k] = jnp.asarray(v.numpy() if isinstance(v, Tensor) else v)

    set_dict = set_state_dict

    def _step_value(self, state):
        return state.get("_step_override", self._step_count)


class SGD(Optimizer):
    def _update(self, value, grad, state, lr, param_meta=None):
        return value - lr * grad, state


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None, use_nesterov=False,
                 weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_state(self, value):
        return {"velocity": jnp.zeros_like(value)}

    def _update(self, value, grad, state, lr, param_meta=None):
        v = self._momentum * state["velocity"] + grad
        if self._nesterov:
            new = value - lr * (grad + self._momentum * v)
        else:
            new = value - lr * v
        return new, {**state, "velocity": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None, weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None, multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name, multi_precision)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_state(self, value):
        return {"moment": jnp.full_like(value, self._init_acc)}

    def _update(self, value, grad, state, lr, param_meta=None):
        m = state["moment"] + grad * grad
        new = value - lr * grad / (jnp.sqrt(m) + self._epsilon)
        return new, {**state, "moment": m}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name, multi_precision)
        self._epsilon, self._rho = epsilon, rho

    def _init_state(self, value):
        return {"avg_squared_grad": jnp.zeros_like(value), "avg_squared_update": jnp.zeros_like(value)}

    def _update(self, value, grad, state, lr, param_meta=None):
        g2 = self._rho * state["avg_squared_grad"] + (1 - self._rho) * grad * grad
        update = grad * jnp.sqrt(state["avg_squared_update"] + self._epsilon) / jnp.sqrt(g2 + self._epsilon)
        u2 = self._rho * state["avg_squared_update"] + (1 - self._rho) * update * update
        return value - lr * update, {**state, "avg_squared_grad": g2, "avg_squared_update": u2}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0, centered=False,
                 parameters=None, weight_decay=None, grad_clip=None, name=None, multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name, multi_precision)
        self._rho, self._epsilon, self._momentum, self._centered = rho, epsilon, momentum, centered

    def _init_state(self, value):
        s = {"mean_square": jnp.zeros_like(value), "momentum": jnp.zeros_like(value)}
        if self._centered:
            s["mean_grad"] = jnp.zeros_like(value)
        return s

    def _update(self, value, grad, state, lr, param_meta=None):
        ms = self._rho * state["mean_square"] + (1 - self._rho) * grad * grad
        out_state = {**state, "mean_square": ms}
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * grad
            denom = jnp.sqrt(ms - mg * mg + self._epsilon)
            out_state["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * state["momentum"] + lr * grad / denom
        out_state["momentum"] = mom
        return value - mom, out_state


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None,
                 weight_decay=None, grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, name=None, amsgrad=False, moment_dtype=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._amsgrad = amsgrad
        # moment_dtype='bfloat16' halves optimizer-state HBM (m+v) — the
        # memory freed buys a larger batch, which on TPU buys MFU; math still
        # runs in fp32 (moments are cast up per step, stored back down)
        self._moment_dtype = jnp.dtype(moment_dtype) if moment_dtype is not None else None

    def _init_state(self, value):
        mdt = self._moment_dtype or value.dtype
        s = {
            "moment1": jnp.zeros(value.shape, mdt),
            "moment2": jnp.zeros(value.shape, mdt),
            "beta1_pow": jnp.ones((), jnp.float32),
            "beta2_pow": jnp.ones((), jnp.float32),
        }
        if self._amsgrad:
            s["moment2_max"] = jnp.zeros(value.shape, mdt)
        return s

    def _update(self, value, grad, state, lr, param_meta=None):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        mdt = state["moment1"].dtype
        g32 = grad.astype(jnp.float32)
        m = b1 * state["moment1"].astype(jnp.float32) + (1 - b1) * g32
        v = b2 * state["moment2"].astype(jnp.float32) + (1 - b2) * g32 * g32
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        m_hat = m / (1 - b1p)
        if self._amsgrad:
            v_max = jnp.maximum(state["moment2_max"].astype(jnp.float32), v)
            v_hat = v_max / (1 - b2p)
            extra = {"moment2_max": v_max.astype(mdt)}
        else:
            v_hat = v / (1 - b2p)
            extra = {}
        new = (value.astype(jnp.float32) - lr * m_hat / (jnp.sqrt(v_hat) + eps)).astype(value.dtype)
        return new, {**state, "moment1": m.astype(mdt), "moment2": v.astype(mdt),
                     "beta1_pow": b1p, "beta2_pow": b2p, **extra}


class AdamW(Adam):
    """Decoupled weight decay (the reference's adamw op semantics)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None,
                 weight_decay=0.01, lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None, amsgrad=False, moment_dtype=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters, None, grad_clip,
                         lazy_mode, multi_precision, name=name, amsgrad=amsgrad,
                         moment_dtype=moment_dtype)
        self._wd_coeff = float(weight_decay) if not hasattr(weight_decay, "coeff") else float(weight_decay.coeff)
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _coupled_wd(self):
        return 0.0

    def _update(self, value, grad, state, lr, param_meta=None):
        decay = self._wd_coeff
        if param_meta is not None and self._apply_decay_param_fun is not None:
            if not self._apply_decay_param_fun(param_meta.name):
                decay = 0.0
        if self._use_fused_kernel(value):
            from ..kernels.fused_optim import fused_adamw_update

            b1p = state["beta1_pow"] * self._beta1
            b2p = state["beta2_pow"] * self._beta2
            # operands pass at their NATIVE dtypes: the kernel casts in VMEM
            # and writes moments back in the state dtype, so no full-tensor
            # f32 copies ever hit HBM (see _adamw_kernel)
            new, m, v = fused_adamw_update(
                value, grad, state["moment1"], state["moment2"],
                lr=lr, beta1=self._beta1, beta2=self._beta2, eps=self._epsilon,
                weight_decay=decay, beta1_pow=b1p, beta2_pow=b2p,
                spec=getattr(param_meta, "dist_spec", None) or P(),
            )
            return new, {**state, "moment1": m, "moment2": v, "beta1_pow": b1p, "beta2_pow": b2p}
        value = value * (1.0 - lr * decay)
        return super()._update(value, grad, state, lr, param_meta)

    def _use_fused_kernel(self, value) -> bool:
        # one fused HBM pass for big tensors on TPU (fused_adam_kernel.cu analog)
        from ..core.flags import flag_value

        if self._amsgrad or not flag_value("use_pallas_kernels"):
            return False
        return on_tpu() and value.size >= 1 << 16 and value.dtype in (jnp.float32, jnp.bfloat16)

    def _takes_native_grad(self, value) -> bool:
        return self._use_fused_kernel(value)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None,
                 weight_decay=None, grad_clip=None, name=None, multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_state(self, value):
        return {"moment": jnp.zeros_like(value), "inf_norm": jnp.zeros_like(value), "beta1_pow": jnp.ones((), jnp.float32)}

    def _update(self, value, grad, state, lr, param_meta=None):
        m = self._beta1 * state["moment"] + (1 - self._beta1) * grad
        u = jnp.maximum(self._beta2 * state["inf_norm"], jnp.abs(grad))
        b1p = state["beta1_pow"] * self._beta1
        new = value - lr / (1 - b1p) * m / (u + self._epsilon)
        return new, {**state, "moment": m, "inf_norm": u, "beta1_pow": b1p}


class NAdam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, momentum_decay=0.004,
                 parameters=None, weight_decay=None, grad_clip=None, name=None, multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name, multi_precision)
        self._beta1, self._beta2, self._epsilon, self._psi = beta1, beta2, epsilon, momentum_decay

    def _init_state(self, value):
        return {
            "moment1": jnp.zeros_like(value),
            "moment2": jnp.zeros_like(value),
            "mu_product": jnp.ones((), jnp.float32),
            "step": jnp.zeros((), jnp.float32),
        }

    def _update(self, value, grad, state, lr, param_meta=None):
        t = state["step"] + 1
        mu_t = self._beta1 * (1 - 0.5 * 0.96 ** (t * self._psi))
        mu_t1 = self._beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self._psi))
        mu_prod = state["mu_product"] * mu_t
        m = self._beta1 * state["moment1"] + (1 - self._beta1) * grad
        v = self._beta2 * state["moment2"] + (1 - self._beta2) * grad * grad
        m_hat = mu_t1 * m / (1 - mu_prod * mu_t1) + (1 - mu_t) * grad / (1 - mu_prod)
        v_hat = v / (1 - self._beta2**t)
        new = value - lr * m_hat / (jnp.sqrt(v_hat) + self._epsilon)
        return new, {**state, "moment1": m, "moment2": v, "mu_product": mu_prod, "step": t}


class RAdam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=None,
                 weight_decay=None, grad_clip=None, name=None, multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_state(self, value):
        return {"moment1": jnp.zeros_like(value), "moment2": jnp.zeros_like(value), "step": jnp.zeros((), jnp.float32)}

    def _update(self, value, grad, state, lr, param_meta=None):
        b1, b2 = self._beta1, self._beta2
        t = state["step"] + 1
        m = b1 * state["moment1"] + (1 - b1) * grad
        v = b2 * state["moment2"] + (1 - b2) * grad * grad
        m_hat = m / (1 - b1**t)
        rho_inf = 2.0 / (1 - b2) - 1
        rho_t = rho_inf - 2 * t * (b2**t) / (1 - b2**t)
        r = jnp.sqrt(jnp.maximum((rho_t - 4) * (rho_t - 2) * rho_inf / jnp.maximum((rho_inf - 4) * (rho_inf - 2) * rho_t, 1e-12), 0.0))
        v_hat = jnp.sqrt(v / (1 - b2**t)) + self._epsilon
        adapted = jnp.where(rho_t > 4, r * m_hat / v_hat, m_hat)
        return value - lr * adapted, {**state, "moment1": m, "moment2": v, "step": t}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9, beta2=0.999, epsilon=1e-6,
                 parameters=None, grad_clip=None, exclude_from_weight_decay_fn=None, name=None,
                 multi_precision=False):
        super().__init__(learning_rate, parameters, None, grad_clip, name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_state(self, value):
        return {
            "moment1": jnp.zeros_like(value),
            "moment2": jnp.zeros_like(value),
            "beta1_pow": jnp.ones((), jnp.float32),
            "beta2_pow": jnp.ones((), jnp.float32),
        }

    def _update(self, value, grad, state, lr, param_meta=None):
        b1, b2 = self._beta1, self._beta2
        m = b1 * state["moment1"] + (1 - b1) * grad
        v = b2 * state["moment2"] + (1 - b2) * grad * grad
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        m_hat = m / (1 - b1p)
        v_hat = v / (1 - b2p)
        wd = self._lamb_wd
        if param_meta is not None and self._exclude_fn is not None and self._exclude_fn(param_meta):
            wd = 0.0
        r = m_hat / (jnp.sqrt(v_hat) + self._epsilon) + wd * value
        w_norm = jnp.linalg.norm(value.astype(jnp.float32))
        r_norm = jnp.linalg.norm(r.astype(jnp.float32))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        new = value - lr * trust * r
        return new, {**state, "moment1": m, "moment2": v, "beta1_pow": b1p, "beta2_pow": b2p}


class Lars(Momentum):
    """LARS — Layer-wise Adaptive Rate Scaling (reference
    fluid/optimizer LarsMomentumOptimizer + the lars_momentum kernel,
    fleet/meta_optimizers/lars_optimizer.py): per-parameter trust ratio
    local_lr = lr * lars_coeff * ||w|| / (||g|| + lars_wd * ||w|| + eps),
    then momentum on local_lr * (g + lars_wd * w). The large-batch ResNet
    recipe (BASELINE config 4)."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 exclude_from_weight_decay=None, epsilon=1e-9,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, momentum, parameters,
                         use_nesterov=False, weight_decay=None,
                         grad_clip=grad_clip, multi_precision=multi_precision,
                         name=name)
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._lars_eps = epsilon
        self._exclude = list(exclude_from_weight_decay or [])

    def _update(self, value, grad, state, lr, param_meta=None):
        wd = self._lars_wd
        if param_meta is not None and self._exclude:
            pname = getattr(param_meta, "name", "") or ""
            if any(tok in pname for tok in self._exclude):
                wd = 0.0
        w_norm = jnp.linalg.norm(value.astype(jnp.float32))
        g_norm = jnp.linalg.norm(grad.astype(jnp.float32))
        trust = self._lars_coeff * w_norm / (g_norm + wd * w_norm + self._lars_eps)
        local_lr = jnp.where((w_norm > 0) & (g_norm > 0), lr * trust, lr)
        v = self._momentum * state["velocity"] + local_lr * (grad + wd * value)
        return value - v, {**state, "velocity": v}


LarsMomentum = Lars  # reference LarsMomentumOptimizer name


class DGCMomentum(Momentum):
    """Deep Gradient Compression momentum (reference
    fleet/meta_optimizers/dgc_optimizer.py + operators/dgc_op): before the
    gradient sync only the top `(1 - sparsity)` fraction of entries (by
    magnitude) of the momentum-corrected gradient is applied; the residual
    accumulates locally (error feedback) and re-enters next step. On TPU
    the allreduce itself is XLA's, so the compression runs as a pure
    per-parameter transform at the update seam — same math, no custom op."""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 sparsity=0.999, rampup_begin_step=0, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, momentum, parameters,
                         use_nesterov=False, weight_decay=weight_decay,
                         grad_clip=grad_clip, multi_precision=multi_precision,
                         name=name)
        self._sparsity = float(sparsity)
        self._rampup_begin = int(rampup_begin_step)

    def _init_state(self, value):
        return {"velocity": jnp.zeros_like(value),
                "residual": jnp.zeros_like(value),
                "dgc_step": jnp.zeros((), jnp.int32)}

    def _update(self, value, grad, state, lr, param_meta=None):
        u = self._momentum * state["velocity"] + grad
        acc = state["residual"] + u
        step = state["dgc_step"] + 1
        flat = acc.reshape(-1).astype(jnp.float32)
        k = max(1, int(round(flat.size * (1.0 - self._sparsity))))
        if k >= flat.size or self._sparsity <= 0.0:
            sparse = acc
            residual = jnp.zeros_like(acc)
        else:
            # k-th order statistic via top_k (k is tiny at 99.9% sparsity;
            # a full sort would dominate step time on the large tensors
            # DGC exists for)
            thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
            mask = (jnp.abs(acc) >= thresh.astype(acc.dtype))
            sparse = jnp.where(mask, acc, 0)
            residual = jnp.where(mask, jnp.zeros_like(acc), acc)
        # before rampup: plain dense momentum SGD (reference rampup_begin_step)
        dense = step <= self._rampup_begin
        applied = jnp.where(dense, acc, sparse)
        residual = jnp.where(dense, jnp.zeros_like(acc), residual)
        new = value - lr * applied
        return new, {**state, "velocity": u, "residual": residual,
                     "dgc_step": step}


class LBFGS(Optimizer):
    """Minimal L-BFGS (reference: python/paddle/optimizer/lbfgs.py); eager-only."""

    def __init__(self, learning_rate=1.0, max_iter=20, history_size=100, parameters=None,
                 weight_decay=None, grad_clip=None, name=None, line_search_fn=None, tolerance_grad=1e-7,
                 tolerance_change=1e-9, max_eval=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._max_iter = max_iter
        self._history = []

    def step(self, closure=None):
        if closure is None:
            raise ValueError("LBFGS.step requires a closure returning the loss")
        loss = closure()
        params = [p for p in self._parameters if not p.stop_gradient and p.grad is not None]
        flat_g = jnp.concatenate([p.grad._value.reshape(-1).astype(jnp.float32) for p in params])
        # two-loop recursion
        q = flat_g
        alphas = []
        for s, y, rho in reversed(self._history):
            a = rho * jnp.dot(s, q)
            alphas.append(a)
            q = q - a * y
        q = q  # H0 = I
        for (s, y, rho), a in zip(self._history, reversed(alphas)):
            b = rho * jnp.dot(y, q)
            q = q + s * (a - b)
        direction = -q
        lr = self.get_lr()
        offset = 0
        old_flat = jnp.concatenate([p._value.reshape(-1).astype(jnp.float32) for p in params])
        for p in params:
            n = int(np.prod(p.shape))
            upd = direction[offset : offset + n].reshape(p.shape)
            p._set_value_raw((p._value.astype(jnp.float32) + lr * upd).astype(p._value.dtype))
            offset += n
        new_loss = closure()
        new_flat_g = jnp.concatenate([p.grad._value.reshape(-1).astype(jnp.float32) for p in params])
        s = lr * direction
        y = new_flat_g - flat_g
        ys = jnp.dot(y, s)
        if float(ys) > 1e-10:
            self._history.append((s, y, 1.0 / ys))
            if len(self._history) > 100:
                self._history.pop(0)
        return new_loss
