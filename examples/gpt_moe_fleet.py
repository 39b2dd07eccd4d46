"""GPT-MoE through the PRODUCT fleet stack (BASELINE config 5, round-3
composition): ep_degree builds the expert mesh axis, experts live as
stacked ep-sharded parameters, ZeRO-3 shards the rest, the planner picks
the remaining degrees — and the same model pipelines (pp x ep) with the
gate aux loss riding the compiled schedule.

Smoke: python examples/gpt_moe_fleet.py --smoke
(8 virtual CPU devices; same code targets a TPU pod.)
"""

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " --xla_force_host_platform_device_count=8").strip()
        import jax

        # --smoke asks for the CPU: pin it before jax picks a backend
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.meta_parallel import group_sharded_parallel
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.models import gpt_moe_tiny

    # leg 1 — dp x ep x sharding with ZeRO-3, degrees via the planner
    # (auto_plan keeps the user-set ep_degree and factors the rest)
    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"ep_degree": 2}
    s.auto_plan = True
    s.auto_plan_configs = {
        "model": dict(hidden=64, layers=2, heads=4, vocab=128, seq=16),
        "batch": 32, "zero_stage": 3,
    }
    fleet.init(is_collective=True, strategy=s)
    print("planned hybrid_configs:", s.hybrid_configs, flush=True)

    paddle.seed(0)
    model = gpt_moe_tiny(dropout=0.0)
    opt = paddle.optimizer.AdamW(learning_rate=2e-3,
                                 parameters=model.parameters())
    model, opt, _ = group_sharded_parallel(model, opt, level="p_g_os")
    step = make_sharded_train_step(getattr(model, "_layers", model),
                                   getattr(opt, "_inner", opt))
    rng = np.random.RandomState(0)
    x = rng.randint(0, 128, size=(32, 16))
    y = np.roll(x, -1, axis=1)
    for i in range(args.steps):
        print(f"[ep x zero3] step {i}: loss {float(step(x, y)):.4f}", flush=True)

    # leg 2 — the SAME model family through the compiled pipeline: every
    # block MoE so the stack is homogeneous; the gate aux rides the
    # schedule (block_with_aux) and lands in the loss
    from paddle_tpu.distributed import collective, mesh, topology

    collective.destroy_process_group()
    mesh.reset_global_mesh()
    topology.set_hybrid_communicate_group(None)
    s2 = fleet.DistributedStrategy()
    s2.hybrid_configs = {"dp_degree": 2, "pp_degree": 2, "ep_degree": 2}
    fleet.init(is_collective=True, strategy=s2)
    paddle.seed(0)
    pmodel = gpt_moe_tiny(dropout=0.0, moe_every_k=1, moe_aux_weight=0.01)
    popt = paddle.optimizer.AdamW(learning_rate=2e-3,
                                  parameters=pmodel.parameters())
    pstep = make_sharded_train_step(pmodel, popt, accumulate_steps=2)
    for i in range(args.steps):
        print(f"[pp x ep]    step {i}: loss {float(pstep(x, y)):.4f}", flush=True)
    print("done")


if __name__ == "__main__":
    main()
