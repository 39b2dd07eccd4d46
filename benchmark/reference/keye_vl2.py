"""Plain reference of the language model of Keye-VL-2.0-30B-A3B
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
``model_type`` KeyeVL2): forward pass in straightforward ``jax.numpy``
float32. No cache, no kernels, no batching, no sorted dispatch, nothing
imported from the program under test.

One layer, for ``x [T, hidden]`` the residual stream::

    h = rms(x) * attn_norm
    q, k, v = h wq, h wk, h wv                 32 / 4 / 4 heads of 128
    q, k = rms over each head * q_norm / k_norm, then rotary (theta 1e7)
    qI = rope(h index.wq)   16 heads of 64;   kI = rope(layernorm(h index.wk))
    w = (h index.ww) * 64^-0.5 * 16^-0.5
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        for s <= t
    S_t = top_k(I[t, :], 2048) (every s <= t while t < 2048)
    a_t = softmax_{s in S_t}(q_t . k_s / sqrt(128)) v_s;   x += a wo
    g = rms(x) * ffn_norm;  p = softmax(g router) over all 128 experts
    E_t = top 8 of p, renormalised;  x += sum_e p_e (silu(g w1_e) * g w3_e) w2_e

then ``rms(x) * final_norm`` and the untied head. Experts are a plain loop
over all of them with a masked sum; selection is a plain ``lax.top_k`` over
the score row with positions after the query at -inf (``top_k`` breaks ties
towards the lower position). Attention runs in blocks of queries so that a
34k-token context fits a 16 GB chip, and the caller hands in one layer's
weights at a time (all six layers in float32 would be 17.5 GB).

Departures from the published description, and what the config does not
state (the configuration file's ``assumed`` list repeats them):

- text only: the vision tower is not part of the catalog's ``config``; with
  text, the three position ids of ``mrope_section`` [16, 24, 24] are all the
  token's position, which is plain 1-D rotary;
- QK-norm (RMSNorm over the 128 of each q and k head) as in the Qwen3
  family; the config has no key for it;
- the indexer's details are DeepSeek-V3.2-Exp's published indexer: inputs
  are the normed hidden state (this model has no query latent), LayerNorm
  (with bias) on ``kI``, rotary on all 64 of ``qI`` / ``kI`` in the same
  half-split form as q and k, the scales ``64^-0.5`` and ``16^-0.5``;
  ``q_chunk_size`` / ``kv_chunk_size`` 512 are the tiling of the score
  computation and change no result; selection is per query token and
  shared by all 32 heads;
- 6 of the 48 layers (the configuration's ``reduced``).

``mm`` is the matmul every contraction with a weight or a cached key goes
through. The default contracts in float32 at precision "highest"; the
control of the correctness check passes a lower-precision ``mm``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def mm_highest(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def rope(x, pos, theta):
    """``x [T, heads, D]`` at positions ``pos [T]``, half-split pairs."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) * 2.0 / D)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, cfg, mm, q_block, rows=None):
    """The attention half of a layer over ``x [T, hidden]`` (float32): its
    output at every position, or at the positions ``rows [R]`` alone (keys,
    values and indexer keys still come from every position of ``x``)."""
    T = x.shape[0]
    Hq, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    Hi, Di, topk = cfg["index_heads"], cfg["index_head_dim"], cfg["index_topk"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(T)
    h = rms(x, p["attn_norm.weight"], eps)
    q = rms(mm(h, p["attn.wq"]).reshape(T, Hq, D), p["attn.q_norm.weight"], eps)
    k = rms(mm(h, p["attn.wk"]).reshape(T, Hkv, D), p["attn.k_norm.weight"], eps)
    v = mm(h, p["attn.wv"]).reshape(T, Hkv, D)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    qi = rope(mm(h, p["attn.index.wq"]).reshape(T, Hi, Di), pos, theta)
    ki = layer_norm(mm(h, p["attn.index.wk"]), p["attn.index.k_norm.weight"],
                    p["attn.index.k_norm.bias"], eps)
    ki = rope(ki[:, None, :], pos, theta)[:, 0]
    w = mm(h, p["attn.index.ww"]) * (Di ** -0.5 * Hi ** -0.5)
    rep = Hq // Hkv
    K = min(topk, T)

    def one_block(args):
        qb, qib, wb, pb = args            # a block of Q queries
        causal = pos[None, :] <= pb[:, None]                  # [Q, T]
        s = jnp.maximum(mm(qib.transpose(1, 0, 2), ki.T), 0.0)   # [Hi, Q, T]
        score = jnp.where(causal, jnp.einsum("hqt,qh->qt", s, wb), -jnp.inf)
        top, idx = lax.top_k(score, K)                        # [Q, K]
        ks, vs = k[idx], v[idx]                               # [Q, K, Hkv, D]
        qg = qb.reshape(-1, Hkv, rep, D)
        a = mm(qg, ks.transpose(0, 2, 3, 1)) / jnp.sqrt(jnp.float32(D))
        # a query early in the text has fewer than K positions to choose
        # from: the rest of its top_k are positions after it, at -inf
        a = jnp.where((top > -jnp.inf)[:, None, None, :], a, -jnp.inf)
        a = jax.nn.softmax(a, axis=-1)                        # [Q, Hkv, rep, K]
        return mm(a, vs.transpose(0, 2, 1, 3)).reshape(-1, Hq, D)

    # the queries: every position, or the rows asked for
    at = (lambda t: t) if rows is None else (lambda t: t[rows])
    n = T if rows is None else rows.shape[0]
    Q = q_block if n % q_block == 0 else n
    split = lambda t: t.reshape((n // Q, Q) + t.shape[1:])
    o = lax.map(one_block, (split(at(q)), split(at(qi)), split(at(w)),
                            split(at(pos))))
    return at(x) + mm(o.reshape(n, Hq * D), p["attn.wo"])


def experts(x, p, cfg, mm):
    """The expert half of a layer over ``x [T, hidden]`` (float32)."""
    E, k = cfg["num_experts"], cfg["experts_per_token"]
    g = rms(x, p["ffn_norm.weight"], cfg["norm_eps"])
    prob = jax.nn.softmax(mm(g, p["ffn.router"]), axis=-1)    # [T, E]
    top, idx = lax.top_k(prob, k)
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    weight = jnp.zeros_like(prob).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)        # 0 if not chosen

    def one_expert(y, e):
        a = jax.nn.silu(mm(g, p["ffn.w1"][e])) * mm(g, p["ffn.w3"][e])
        return y + weight[:, e, None] * mm(a, p["ffn.w2"][e]), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(E))
    return x + y


def layer(x, p, cfg, mm=mm_highest, q_block=256, rows=None):
    """One decoder layer; ``p`` holds that layer's weights under their
    names without the ``layers.<l>.`` prefix. With ``rows [R]`` the result
    is the layer's output at those positions only, ``[R, hidden]``: what
    the LAST layer owes when only some positions' logits are wanted (every
    earlier layer feeds keys and values at every position to the next)."""
    return experts(attention(x, p, cfg, mm, q_block, rows), p, cfg, mm)


def embed(ids, table):
    return table[ids].astype(jnp.float32)


def logits(x, rows, final_norm, head, cfg, mm=mm_highest):
    """Logits ``[len(rows), vocab]`` at positions ``rows`` of the last
    layer's output ``x``."""
    return mm(rms(x[rows], final_norm, cfg["norm_eps"]), head)


def forward(params, ids, cfg, mm=mm_highest, q_block=256):
    """Logits ``[T, vocab]`` of token ids ``[T]`` with every weight in one
    dict (small sizes; a big one goes layer by layer, see harness/)."""
    x = embed(ids, params["embed.weight"])
    for l in range(cfg["num_layers"]):
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = layer(x, p, cfg, mm, q_block)
    return logits(x, jnp.arange(ids.shape[0]), params["final_norm.weight"],
                  params["head.weight"], cfg, mm)
