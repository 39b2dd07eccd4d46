"""Plain reference of the GPT-3 decoder (Brown et al. 2020, table 2.1; GPT-2
block layout): forward, loss, gradients and AdamW in straightforward
``jax.numpy`` float32. No kernels, no cache, no batching tricks, nothing
imported from the program under test.

Layout (the names are the published model's parts, spelled as the program
spells its parameters so that one seeded weight maker serves both sides):

    h = wte[ids] + wpe[pos]
    per block:  h += proj(attn(qkv(ln1(h))))     causal, softmax in f32
                h += fc2(gelu_tanh(fc1(ln2(h))))
    logits = final_ln(h) @ wte.T                  (tied head)

Weights are ``[in, out]``; the fused qkv output is [q heads | k heads | v
heads], each head ``head_dim`` wide. Departure from the paper: none of the
sparse-attention layers (the program has none either; GPT-3's alternating
dense / locally banded pattern is listed in PERF.md as not modelled).

``mm`` is the matmul every contraction goes through. The default contracts in
float32 at precision "highest" (a TPU would otherwise run a float32 matmul in
one bf16 pass). The control of the correctness check passes a lower-precision
``mm`` instead (benchmark/harness/check.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mm_highest(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(h, p, prefix, cfg, mm):
    """One pre-LN decoder block over ``h`` [B, T, hidden] (float32)."""
    B, T, Hd = h.shape
    H, D = cfg["num_heads"], cfg["hidden_size"] // cfg["num_heads"]
    g = lambda n: p[prefix + n]
    x = layer_norm(h, g("ln1.weight"), g("ln1.bias"), cfg["layer_norm_eps"])
    qkv = mm(x, g("attn.qkv.weight")) + g("attn.qkv.bias").astype(jnp.float32)
    q, k, v = (qkv[..., i * H * D:(i + 1) * H * D].reshape(B, T, H, D)
               .transpose(0, 2, 1, 3) for i in range(3))
    s = mm(q, k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(D))
    causal = jnp.tril(jnp.ones((T, T), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm(a, v).transpose(0, 2, 1, 3).reshape(B, T, Hd)
    h = h + mm(o, g("attn.proj.weight")) + g("attn.proj.bias").astype(jnp.float32)
    x = layer_norm(h, g("ln2.weight"), g("ln2.bias"), cfg["layer_norm_eps"])
    x = gelu_tanh(mm(x, g("mlp.fc1.weight")) + g("mlp.fc1.bias").astype(jnp.float32))
    return h + mm(x, g("mlp.fc2.weight")) + g("mlp.fc2.bias").astype(jnp.float32)


def forward(params, ids, cfg, mm=mm_highest, remat=False):
    """Logits [B, T, vocab] (float32) of token ids [B, T]. ``remat``
    recomputes each block in the backward pass (memory, not mathematics)."""
    T = ids.shape[1]
    wte = params["gpt.embeddings.word_embeddings.weight"]
    wpe = params["gpt.embeddings.position_embeddings.weight"]
    h = wte[ids].astype(jnp.float32) + wpe[:T][None].astype(jnp.float32)
    for l in range(cfg["num_layers"]):
        f = lambda h_, p_, l=l: block(h_, p_, f"gpt.layers.{l}.", cfg, mm)
        h = (jax.checkpoint(f) if remat else f)(h, params)
    h = layer_norm(h, params["gpt.final_ln.weight"],
                   params["gpt.final_ln.bias"], cfg["layer_norm_eps"])
    return mm(h, wte.T)


def loss_sum(params, x, y, cfg, mm=mm_highest, remat=False):
    """Sum over tokens of the next-token cross entropy (labels ``y`` already
    shifted). The mean loss of a batch is the sum over its row blocks
    divided by the number of tokens."""
    logits = forward(params, x, cfg, mm, remat)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return (lse - gold).sum()


def adamw(p, g, m, v, t, *, lr, beta1, beta2, eps, weight_decay):
    """One decoupled-weight-decay Adam update of one leaf, all in float32
    (Loshchilov & Hutter 2019, algorithm 2; decay applied to every leaf, as
    the configuration states). ``t`` is the 1-based step."""
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    m_hat = m / (1 - beta1 ** t)
    v_hat = v / (1 - beta2 ** t)
    p = p * (1.0 - lr * weight_decay) - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    return p, m, v
