"""paddle.amp namespace."""

from .auto_cast import amp_guard, auto_cast, decorate  # noqa: F401
from .grad_scaler import GradScaler  # noqa: F401


def is_float16_supported(device=None):
    """fp16 compute support (reference: amp/__init__ CUDA-arch probe). TPU MXU
    natively computes bf16; fp16 is emulated, so report False on TPU and True
    only where XLA has a native f16 path (GPU)."""
    from ..core.place import platform

    return platform() == "gpu"


def is_bfloat16_supported(device=None):
    from ..core.place import platform

    return platform() in ("tpu", "cpu", "gpu")
