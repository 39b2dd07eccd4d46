"""Required work of the Mamba-1 recurrent step of one layer in one decode
step (the named kernel ``mamba1_decode_step``), the same whatever implements
it: each RUNNING slot's float32 state (``N x E``: a state lane a channel) is
read once and written once, with the slot's rows beside it (``x``, ``dt``
and the output ``y``, ``E`` each; ``B`` and ``C``, ``N`` each; float32 as
the kernel takes them); per state element one multiply for ``dt A``, one
exponential (counted as one), a multiply for the decay, a multiply-add for
the update ``dt x (x) B`` and a multiply-add for the read-out ``S C``: seven
FLOPs. A free slot's state is not required work (the kernel's grid covers
it all the same), so the share cannot pass 100%."""

from .flash import min_seconds  # noqa: F401


def call(running_slots, E, N, itemsize=4):
    """``running_slots``: slots that emit a token, summed over the steps
    (and over the layers)."""
    state = E * N
    rows = 3 * E + 2 * N
    return {"flops": 7.0 * running_slots * state,
            "bytes": 1.0 * running_slots * (2 * state + rows) * itemsize}
