"""Train -> jit.save (StableHLO) -> inference.Predictor deployment (the
paddle.jit.save + AnalysisPredictor ZeroCopyRun workflow, SURVEY §3.5).

Smoke (CPU): python examples/deploy_inference.py --smoke
"""

import argparse
import os
import tempfile

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None, help="model path prefix")
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # --smoke asks for the CPU: pin it before jax picks a backend
        import jax

        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    from paddle_tpu import inference, jit, nn
    from paddle_tpu.static import InputSpec

    # 1. train a tiny regressor
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 32), nn.ReLU(), nn.Linear(32, 1))
    opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=net.parameters())
    rng = np.random.RandomState(0)
    w_true = rng.randn(4).astype(np.float32)
    for _ in range(200):
        x = rng.randn(32, 4).astype(np.float32)
        y = x @ w_true
        loss = ((net(paddle.to_tensor(x))[:, 0] - paddle.to_tensor(y)) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    print(f"train loss: {float(loss.numpy()):.5f}")

    # 2. export: StableHLO program + params, symbolic batch dim
    prefix = args.out or os.path.join(tempfile.mkdtemp(), "regressor")
    net.eval()
    jit.save(net, prefix, input_spec=[InputSpec([None, 4], "float32")])
    print(f"saved to {prefix}.*")

    # 3. deploy: AnalysisPredictor analog with the IR pass pipeline on
    cfg = inference.Config(prefix)
    cfg.switch_ir_optim(True)
    predictor = inference.create_predictor(cfg)
    x = rng.randn(5, 4).astype(np.float32)
    out, = predictor.run([x])
    ref = net(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    print("predictor output matches eager; max err",
          float(np.abs(out - ref).max()))
    print("done")


if __name__ == "__main__":
    main()
