"""Vision classification (ResNet/PP-YOLOE-style conv path — BASELINE config
4): vision.models + transforms + io.DataLoader + amp autocast + hapi-free
training loop.

Smoke (CPU): python examples/resnet_train.py --smoke
"""

import argparse
import os

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--classes", type=int, default=10)
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # --smoke asks for the CPU: pin it before jax picks a backend
        import jax

        jax.config.update("jax_platforms", "cpu")
        args.epochs, args.batch = 1, 8

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.io import DataLoader, TensorDataset
    from paddle_tpu.vision.models import resnet18

    paddle.seed(0)
    # synthetic CIFAR-shaped data (swap for vision.datasets.Cifar10 with a real corpus)
    rng = np.random.RandomState(0)
    n = args.batch * 4
    images = rng.randn(n, 3, 32, 32).astype(np.float32)
    labels = rng.randint(0, args.classes, size=(n,)).astype(np.int64)
    loader = DataLoader(TensorDataset([paddle.to_tensor(images), paddle.to_tensor(labels)]),
                        batch_size=args.batch, shuffle=True)

    model = resnet18(num_classes=args.classes)
    opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                    parameters=model.parameters())
    ce = nn.CrossEntropyLoss()

    for epoch in range(args.epochs):
        model.train()
        for i, (xb, yb) in enumerate(loader):
            with paddle.amp.auto_cast(level="O1"):
                logits = model(xb)
                loss = ce(logits, yb)
            loss.backward()
            opt.step()
            opt.clear_grad()
        print(f"epoch {epoch}: loss {float(loss.numpy()):.4f}", flush=True)
    print("done")


if __name__ == "__main__":
    main()
