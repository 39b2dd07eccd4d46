"""Recognize digits — the reference's canonical beginner book example
(test/book/test_recognize_digits.py): a LeNet-style convnet on MNIST-shaped
data through the hapi Model.fit path, then eval + single-image predict.

Smoke (CPU): python examples/recognize_digits.py --smoke
Real data: pass --mnist to pull paddle_tpu.vision.datasets.MNIST (needs the
downloaded corpus; the default uses synthetic digit-shaped tensors so the
example runs hermetically).
"""

import argparse
import os

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mnist", action="store_true")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch", type=int, default=64)
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # --smoke asks for the CPU: pin it before jax picks a backend
        import jax

        jax.config.update("jax_platforms", "cpu")
        args.epochs, args.batch = 1, 16

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.io import DataLoader, TensorDataset

    paddle.seed(0)

    # LeNet (reference: python/paddle/vision/models LeNet used by the book
    # chapter; conv/pool/fc exercise the conv PHI-kernel path)
    net = nn.Sequential(
        nn.Conv2D(1, 6, 5, padding=2), nn.ReLU(), nn.MaxPool2D(2),
        nn.Conv2D(6, 16, 5), nn.ReLU(), nn.MaxPool2D(2),
        nn.Flatten(),
        nn.Linear(16 * 5 * 5, 120), nn.ReLU(),
        nn.Linear(120, 84), nn.ReLU(),
        nn.Linear(84, 10),
    )

    if args.mnist:
        from paddle_tpu.vision.datasets import MNIST

        train_ds = MNIST(mode="train")
        val_ds = MNIST(mode="test")
    else:
        rng = np.random.RandomState(0)
        n = args.batch * (2 if args.smoke else 8)

        def synth(n):
            # digit-shaped blobs: class k gets a bright kxk corner patch, so
            # the task is learnable in one epoch
            x = rng.rand(n, 1, 28, 28).astype(np.float32) * 0.1
            y = rng.randint(0, 10, size=(n, 1)).astype(np.int64)
            for i in range(n):
                k = int(y[i, 0]) + 3
                x[i, 0, :k, :k] += 1.0
            return paddle.to_tensor(x), paddle.to_tensor(y)

        train_ds = TensorDataset(list(synth(n)))
        val_ds = TensorDataset(list(synth(args.batch)))

    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.Adam(learning_rate=1e-3, parameters=model.parameters()),
        nn.CrossEntropyLoss(),
        paddle.metric.Accuracy(),
    )
    model.fit(train_ds, epochs=args.epochs, batch_size=args.batch, verbose=0)
    eval_out = model.evaluate(val_ds, batch_size=args.batch, verbose=0)
    print("eval:", {k: float(np.asarray(v).reshape(-1)[0]) for k, v in eval_out.items()})

    # single-image predict through the same Model facade
    xb = val_ds[0][0]
    logits = model.predict_batch([paddle.to_tensor(np.asarray(xb._value)[None])])
    pred = int(np.asarray(logits[0]).argmax())
    print("predicted digit:", pred)
    print("done")


if __name__ == "__main__":
    main()
