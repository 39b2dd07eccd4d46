"""GPT-MoE with expert parallelism over a device mesh (BASELINE config 5:
MoE + expert-parallel dispatch via all-to-all; runs on the 8-device virtual
CPU mesh for development, same code on a TPU pod).

Smoke: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python examples/moe_hybrid_parallel.py --smoke
"""

import argparse
import os

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # --smoke asks for the CPU: pin it before jax picks a backend
        import jax

        jax.config.update("jax_platforms", "cpu")
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    paddle.seed(0)
    d = args.hidden
    moe = MoELayer(d_model=d, experts=[
        paddle.nn.Sequential(paddle.nn.Linear(d, 2 * d), paddle.nn.GELU(),
                             paddle.nn.Linear(2 * d, d))
        for _ in range(args.experts)
    ], gate="gshard", top_k=2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=moe.parameters())

    rng = np.random.RandomState(0)
    target = rng.randn(8, 16, d).astype(np.float32)
    x = rng.randn(8, 16, d).astype(np.float32)
    for step in range(args.steps):
        out = moe(paddle.to_tensor(x))
        loss = ((out - paddle.to_tensor(target)) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        print(f"step {step}: loss {float(loss.numpy()):.4f} "
              f"(aux {float(moe.l_aux.numpy()):.4f})" if hasattr(moe, "l_aux")
              else f"step {step}: loss {float(loss.numpy()):.4f}", flush=True)
    print(f"devices: {len(jax.devices())}; done")


if __name__ == "__main__":
    main()
