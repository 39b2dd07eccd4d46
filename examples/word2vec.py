"""Word2vec — the reference's book chapter 4 example
(test/book/test_word2vec.py): an N-gram language model over embeddings,
trained eagerly with the tape, then queried for nearest-neighbor words.

The reference book builds a 4-gram MLP over concatenated word embeddings
(not the skip-gram variant) — same here: predict word t from words
t-4..t-1 through shared nn.Embedding + two Linear layers.

Smoke (CPU): python examples/word2vec.py --smoke
"""

import argparse
import os

import numpy as np

N_GRAM = 4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--vocab", type=int, default=200)
    ap.add_argument("--emb", type=int, default=32)
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # --smoke asks for the CPU: pin it before jax picks a backend
        import jax

        jax.config.update("jax_platforms", "cpu")
        args.steps, args.vocab, args.emb = 30, 64, 16

    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    rng = np.random.RandomState(0)

    # synthetic corpus with real structure: a Markov chain where word w is
    # usually followed by (w + 1) % V, so the n-gram model has signal
    V = args.vocab
    corpus = [int(rng.randint(V))]
    for _ in range(5000 if not args.smoke else 800):
        corpus.append((corpus[-1] + 1) % V if rng.rand() < 0.8 else int(rng.randint(V)))
    corpus = np.asarray(corpus, np.int64)

    class NGramLM(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(V, args.emb)
            self.fc1 = nn.Linear(N_GRAM * args.emb, 64)
            self.fc2 = nn.Linear(64, V)

        def forward(self, ctx):  # ctx: [B, N_GRAM]
            e = self.emb(ctx)                      # [B, N_GRAM, E]
            h = paddle.reshape(e, [e.shape[0], -1])
            return self.fc2(paddle.tanh(self.fc1(h)))

    model = NGramLM()
    opt = paddle.optimizer.Adam(learning_rate=5e-3, parameters=model.parameters())
    ce = nn.CrossEntropyLoss()

    # n-gram windows
    ctxs = np.stack([corpus[i:i + N_GRAM] for i in range(len(corpus) - N_GRAM)])
    tgts = corpus[N_GRAM:]
    bsz = 64
    first = last = None
    for step in range(args.steps):
        idx = rng.randint(0, len(ctxs), size=bsz)
        loss = ce(model(paddle.to_tensor(ctxs[idx])), paddle.to_tensor(tgts[idx]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        last = float(loss)
        if first is None:
            first = last
    print(f"loss {first:.3f} -> {last:.3f}")
    assert last < first, "word2vec training did not reduce loss"

    # embedding-space query: the learned table should place w near w+1's
    # predictor context; report nearest neighbors by cosine
    W = np.asarray(model.emb.weight._value)
    w = 5 % V
    sims = (W @ W[w]) / (np.linalg.norm(W, axis=1) * np.linalg.norm(W[w]) + 1e-9)
    nearest = np.argsort(-sims)[1:4]
    print(f"nearest to word {w}: {nearest.tolist()}")
    print("done")


if __name__ == "__main__":
    main()
