"""BERT sequence-classification fine-tune (the BASELINE BERT-base SST-2
workflow): native WordPiece tokenization -> DataLoader-style batching ->
eager-or-jitted training -> evaluation.

Smoke (CPU): python examples/bert_finetune.py --smoke
"""

import argparse
import os

import numpy as np

# toy sentiment corpus stands in for SST-2 when no dataset path is given
_POS = ["a great movie", "truly wonderful acting", "great fun and wonderful"]
_NEG = ["a terrible movie", "truly awful acting", "terrible plot and awful"]
_VOCAB = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "a", "great", "movie", "truly",
          "wonderful", "acting", "fun", "and", "terrible", "awful", "plot"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--max-len", type=int, default=16)
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # --smoke asks for the CPU: pin it before jax picks a backend
        import jax

        jax.config.update("jax_platforms", "cpu")
        args.epochs = 12

    import paddle_tpu as paddle
    import paddle_tpu.native as native
    from paddle_tpu.models import bert_tiny

    tok = native.FastWordPieceTokenizer(_VOCAB)
    texts = _POS + _NEG
    labels = np.array([1] * len(_POS) + [0] * len(_NEG), np.int64)
    enc = tok(texts, max_len=args.max_len)
    ids = enc["input_ids"]
    mask = enc["attention_mask"]

    paddle.seed(0)
    model = bert_tiny(vocab_size=len(_VOCAB), num_labels=2)
    opt = paddle.optimizer.AdamW(learning_rate=args.lr, parameters=model.parameters())

    for epoch in range(args.epochs):
        model.train()
        logits = model(paddle.to_tensor(ids), attention_mask=paddle.to_tensor(mask))
        loss = model.loss(logits, paddle.to_tensor(labels))
        loss.backward()
        opt.step()
        opt.clear_grad()
        model.eval()
        pred = model(paddle.to_tensor(ids), attention_mask=paddle.to_tensor(mask))
        acc = float((np.argmax(pred.numpy(), -1) == labels).mean())
        print(f"epoch {epoch}: loss {float(loss.numpy()):.4f} acc {acc:.2f}", flush=True)
    assert acc == 1.0 or not args.smoke, "smoke run failed to fit the toy corpus"
    print("done")


if __name__ == "__main__":
    main()
