"""Runtime flag system.

Analog of PADDLE_DEFINE_EXPORTED_* / paddle.set_flags (paddle/phi/core/flags.cc,
fluid/pybind global_value_getter_setter): a typed registry of FLAGS_* knobs with
env-var initialization (``FLAGS_xxx=...``), exposed via set_flags/get_flags.
XLA-specific tuning rides the separate XLA_FLAGS env var, passed through as-is.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Union


class _Flag:
    __slots__ = ("name", "default", "value", "type", "help")

    def __init__(self, name: str, default, help_: str = ""):
        self.name = name
        self.default = default
        self.type = type(default)
        self.help = help_
        env = os.environ.get(f"FLAGS_{name}")
        self.value = self._parse(env) if env is not None else default

    def _parse(self, text: str):
        if self.type is bool:
            return text.lower() in ("1", "true", "yes", "on")
        if self.type in (int, float):
            return self.type(text)
        return text


_REGISTRY: Dict[str, _Flag] = {}


def register_flag(name: str, default, help_: str = ""):
    if name not in _REGISTRY:
        _REGISTRY[name] = _Flag(name, default, help_)
    return _REGISTRY[name]


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags analog; accepts both 'FLAGS_x' and bare 'x' keys."""
    for key, value in flags.items():
        name = key[6:] if key.startswith("FLAGS_") else key
        if name not in _REGISTRY:
            register_flag(name, value)
        else:
            _REGISTRY[name].value = value


def get_flags(flags: Union[str, Iterable[str]]) -> Dict[str, Any]:
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for key in flags:
        name = key[6:] if key.startswith("FLAGS_") else key
        if name not in _REGISTRY:
            raise KeyError(f"Flag {key} not registered")
        out[key] = _REGISTRY[name].value
    return out


def flag_value(name: str):
    return _REGISTRY[name].value


# Core flags mirroring the reference's most load-bearing ones
# (phi/core/flags.cc): NaN checks, determinism, memory and logging knobs.
register_flag("check_nan_inf", False, "Check every op output for NaN/Inf (jax debug_nans analog)")
register_flag("deterministic", False, "Force deterministic lowering where available")
register_flag("use_pallas_kernels", True, "Use hand-written Pallas kernels on TPU where available")
register_flag("fraction_of_device_memory_to_use", 0.92, "Informational; XLA manages HBM")
register_flag("allocator_strategy", "xla", "Kept for parity; allocation is XLA/PJRT-managed")
register_flag("eager_delete_tensor_gb", 0.0, "Parity no-op; GC is host-side refcounting")
register_flag("benchmark", False, "Block on every op for timing")
register_flag("log_level", 0, "VLOG-style verbosity for framework logging")
register_flag("default_dtype", "float32", "Default floating dtype for creation ops")
register_flag("amp_dtype", "bfloat16", "Preferred autocast dtype on TPU")
register_flag("enable_async_checkpoint", True, "Write checkpoints from a background thread")
register_flag("max_inflight_microbatches", 2, "Pipeline schedule in-flight cap")
register_flag("observability", False,
              "Enable the runtime telemetry substrate (metrics registry + "
              "span tracer, paddle_tpu.observability). Off by default: "
              "instrumented sites reduce to one flag check and the registry "
              "stays empty, so tier-1 timing is unaffected")
register_flag("health_stats", False,
              "Compute in-graph per-param-group numerics stats (grad/param/"
              "update norms + nonfinite counts) inside the compiled train "
              "step and stream them to observability.health.HealthMonitor. "
              "Off by default: the step's traced program (and the analyzer "
              "corpus / HLO baselines) is unchanged unless enabled")
register_flag("eval_no_record", False,
              "Layers in eval() mode skip tape recording entirely: closes "
              "the chained-forward tape growth hazard (h = m(h) inference "
              "loops without no_grad) at the cost of input-gradients "
              "through eval-mode layers")
