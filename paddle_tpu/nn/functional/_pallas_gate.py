"""Single gate for routing ops to Pallas kernels (the PHI kernel-key
backend-selection analog — one bit instead of a registry lookup)."""

from ...core.flags import flag_value
from ...core.place import on_tpu


def use_pallas() -> bool:
    if not flag_value("use_pallas_kernels"):
        return False
    # prim/composite mode (reference fluid/prim composite grads): fused
    # custom_vjp kernels are only once-differentiable; with prim enabled
    # every op lowers through its primitive jnp composition so arbitrary-
    # order autodiff rules compose
    from ...incubate.autograd import prim_enabled

    if prim_enabled():
        return False
    return on_tpu()
