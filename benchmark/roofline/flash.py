"""Required work of the causal flash-attention kernels, from shapes.

One forward call on q, k, v [B, H, S, D]: two matmuls (Q K^T and P V) over
the causal half of the S x S square -> 2 * (2 B H S^2 D / 2) FLOPs; it reads
q, k, v and writes o (plus one float32 log-sum-exp per row).
One backward (the dq kernel and the dkv kernel together): the algorithm needs
five matmuls over the causal half (recompute S, dV = P^T dO, dP = dO V^T,
dQ = dS K, dK = dS^T Q). The program's two kernels each recompute S and dP,
which is their cost, not required work, and is not counted here.
"""


def matmul_flops(B, H, S, D):
    """One [S, D] x [D, S]-sized matmul per head over the causal half."""
    return 2.0 * B * H * S * S * D / 2.0


def fwd(B, H, S, D, itemsize=2):
    return {"flops": 2 * matmul_flops(B, H, S, D),
            "bytes": 4.0 * B * H * S * D * itemsize + 4.0 * B * H * S}


def bwd(B, H, S, D, itemsize=2):
    # reads q, k, v, o, do, lse; writes dq, dk, dv
    return {"flops": 5 * matmul_flops(B, H, S, D),
            "bytes": 8.0 * B * H * S * D * itemsize + 4.0 * B * H * S}


def min_seconds(work, peaks):
    """(least seconds the chip could take, which bound binds)."""
    tf = work["flops"] / peaks["flops_per_s"]
    tb = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
