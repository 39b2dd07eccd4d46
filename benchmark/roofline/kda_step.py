"""Required work of the per-channel-gated delta rule's recurrent step of one
layer in one decode step (the named kernel ``gdn_decode_step`` with a decay
a key channel): each RUNNING slot's float32 state (``H`` heads of
``dv x dk``) is read once and written once, with the slot's rows beside it
(q, k and the decay ``H x dk``, v and the output ``H x dv``, the write
strength ``H``, float32 as the kernel takes them); per state element one
multiply for the decay and a multiply-add each for ``S k``, the rank-one
update and ``S q``. A free slot's state is not required work (the kernel's
grid covers it all the same)."""

from .flash import min_seconds  # noqa: F401


def call(running_slots, H, dk, dv, itemsize=4):
    """``running_slots``: slots that emit a token, summed over the steps
    and the layers."""
    state = H * dk * dv
    rows = H * (3 * dk + 2 * dv + 1)
    return {"flops": 7.0 * running_slots * state,
            "bytes": 1.0 * running_slots * (2 * state + rows) * itemsize}
