"""Required work of the Mamba-2 recurrent step of one layer in one decode
step (the named kernel ``mamba2_decode_step``), the same whatever implements
it: each RUNNING slot's float32 state (``H`` heads of ``P x N``) is read
once and written once, with the slot's rows beside it (``x`` and the output
``y``, ``H x P`` each; ``B`` and ``C``, ``G x N`` each, once for a group's
heads; the step ``dt``, ``H``; float32 as the kernel takes them); per state
element one multiply for the decay, a multiply-add for the update ``dt x (x)
B`` and a multiply-add for the read-out ``S C``: five FLOPs. A free slot's
state is not required work (the kernel's grid covers it all the same), so
the share cannot pass 100%."""

from .flash import min_seconds  # noqa: F401


def call(running_slots, H, P, G, N, itemsize=4):
    """``running_slots``: slots that emit a token, summed over the steps
    (and over the layers)."""
    state = H * P * N
    rows = 2 * H * P + 2 * G * N + H
    return {"flops": 5.0 * running_slots * state,
            "bytes": 1.0 * running_slots * (2 * state + rows) * itemsize}
