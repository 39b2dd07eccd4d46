"""Plain reference of Command A+
(https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json,
``model_type`` cohere2_moe, 218B-A25B; the language model alone): forward
pass in straightforward ``jax.numpy`` float32. No cache, no pages, no
kernels, no sorted dispatch: the window is a mask written as a mask, the
experts a plain loop with a masked sum. Nothing is imported from the
program under test.

One layer ``l``, for ``x [T, 4096]`` the residual stream; no biases
anywhere, no QK-norm::

    h = LayerNorm(x) = (x - mean(x)) / sqrt(var(x) + 1e-5) * gamma   (no bias)
    q = h wq (128 heads of 128);  k, v = h wk, h wv (8 heads of 128:
        16 query heads a key/value head)
    sliding_attention (l mod 4 in {0, 1, 2}): q, k turned by rotary
        positions, theta 50000, all 128 lanes, INTERLEAVED pairs
        (x[2i], x[2i + 1]); query t sees the keys s with
        t - 4096 < s <= t
    full_attention (l mod 4 = 3): no positions; s <= t
    a = softmax(q . k * 128^-1/2) v -> wo
    p = sigmoid(h router) over ALL 128 experts     (the SAME h: the block
        is parallel); E = the 8 of largest p; w_e = p_e / sum_{E} p
    f = sum_{e in E} w_e FFN_e(h) + (1/4) sum_{s=1..4} FFN'_s(h)
        FFN(h) = (silu(h w1) * (h w3)) w2, width 4096, routed and shared
    x = x + a + f
    logits = logit_scale * LayerNorm(x_final) . embed^T      (tied table)

The four shared experts' weights stand side by side in ``ffn.shared.w1 /
w3 [hidden, 4 x 4096]`` and ``ffn.shared.w2 [4 x 4096, hidden]``: expert
``s`` is columns (rows) ``[s x 4096, (s + 1) x 4096)``; they are computed
one at a time here and their outputs averaged.

THE SHARE. ``cfg["experts_held"] = (n, first)`` says which experts' weights
``ffn.w1 / w3 / w2 [n, ...]`` are: the routed sum runs over the chosen e in
``[first, first + n)`` alone, and what the others would add is left out (a
chip of a group that shares each layer; nothing stands in for the rest).
The vocabulary is whatever ``embed.weight`` holds: a slice is a smaller
vocabulary.

What the published config does not state, and is assumed (the
configuration file's ``assumed`` list repeats each): ``average`` is the
mean of the four shared experts' outputs, added to the routed sum; a shared
expert is ``intermediate_size`` wide; the window holds ``sliding_window``
keys, the query's own among them; LayerNorm has a scale and no bias.

``mm`` is the matmul every contraction with a weight (and the attention's
two) goes through. The default contracts in float32 at precision "highest";
the control of the correctness check passes a lower-precision ``mm``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def mm_highest(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def layer_norm(x, w, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def rotary_interleaved(x, pos, theta):
    """``x [T, heads, D]`` turned at ``pos [T]``: pair i is the lanes
    ``(2i, 2i + 1)``, at the frequency ``theta^(-2i / D)``."""
    T, H, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None, None] * inv          # [T, 1, D/2]
    pairs = x.reshape(T, H, D // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     axis=-1).reshape(T, H, D)


def attention(h, p, kind, cfg, mm, q_block, rows=None):
    """Output ``[T, hidden]`` of the GQA mixer over the normed input ``h``,
    or at the positions ``rows [R]`` alone. ``kind`` says whether the layer
    has rotary positions and a window."""
    T = h.shape[0]
    Hq, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    rep = Hq // Hkv
    pos = jnp.arange(T)
    q = mm(h, p["attn.wq"]).reshape(T, Hq, D)
    k = mm(h, p["attn.wk"]).reshape(T, Hkv, D)
    v = mm(h, p["attn.wv"]).reshape(T, Hkv, D)
    sliding = kind == "sliding_attention"
    if sliding:
        q = rotary_interleaved(q, pos, cfg["rope_theta"])
        k = rotary_interleaved(k, pos, cfg["rope_theta"])
    elif kind != "full_attention":
        raise ValueError(f"layer kind {kind!r}")
    q = q.reshape(T, Hkv, rep, D)
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 0, 2)   # [G,D,T], [G,T,D]

    def one_block(args):
        qb, pb = args                                 # [Q, G, rep, D], [Q]
        Q = qb.shape[0]
        qg = qb.transpose(1, 2, 0, 3).reshape(Hkv, rep * Q, D)
        s = mm(qg, kt) / jnp.sqrt(jnp.float32(D))     # [G, rep * Q, T]
        ok = pos[None, :] <= pb[:, None]
        if sliding:                                   # the mask, as a mask
            ok = ok & (pb[:, None] - pos[None, :] < cfg["sliding_window"])
        s = jnp.where(jnp.tile(ok, (rep, 1))[None], s, -jnp.inf)
        o = mm(jax.nn.softmax(s, axis=-1), vt)        # [G, rep * Q, D]
        return o.reshape(Hkv, rep, Q, D).transpose(2, 0, 1, 3)

    at = (lambda t: t) if rows is None else (lambda t: t[rows])
    n = T if rows is None else rows.shape[0]
    Q = q_block if n % q_block == 0 else n
    split = lambda t: t.reshape((n // Q, Q) + t.shape[1:])
    o = lax.map(one_block, (split(at(q)), split(at(pos))))
    return mm(o.reshape(n, Hq * D), p["attn.wo"])


def route(h, p, cfg, mm):
    """(weights [T, experts] float32, 0 where an expert is not chosen) by
    plain sigmoid scores: the ``experts_per_token`` largest, over their
    sum."""
    score = jax.nn.sigmoid(mm(h, p["ffn.router"]))            # [T, E]
    top, idx = lax.top_k(score, cfg["experts_per_token"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    return jnp.zeros_like(score).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)


def routed_experts(h, p, cfg, mm):
    """The routed experts' part over the normed input ``h [T, hidden]``:
    the sum over the chosen experts that ``cfg["experts_held"]`` holds."""
    n, first = cfg["experts_held"]
    weight = route(h, p, cfg, mm)

    def one_expert(y, e):                                     # e: held index
        a = jax.nn.silu(mm(h, p["ffn.w1"][e])) * mm(h, p["ffn.w3"][e])
        return y + weight[:, first + e, None] * mm(a, p["ffn.w2"][e]), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(n))
    return y


def shared_experts(h, p, cfg, mm):
    """The MEAN of the ``shared_experts`` shared experts' outputs, each a
    SwiGLU of its own columns of the side-by-side weights."""
    S = cfg["shared_experts"]
    F = p["ffn.shared.w1"].shape[1] // S
    y = jnp.zeros_like(h)
    for s in range(S):
        cols = slice(s * F, (s + 1) * F)
        a = jax.nn.silu(mm(h, p["ffn.shared.w1"][:, cols])) \
            * mm(h, p["ffn.shared.w3"][:, cols])
        y = y + mm(a, p["ffn.shared.w2"][cols])
    return y / S


def layer(x, p, kind, cfg, mm=mm_highest, q_block=256, rows=None):
    """One decoder layer of ``kind`` (``sliding_attention`` |
    ``full_attention``); ``p`` holds that layer's weights under their names
    without the ``layers.<l>.`` prefix. With ``rows [R]`` the result is the
    layer's output at those positions only, ``[R, hidden]``: what the LAST
    layer owes when only some positions' logits are wanted."""
    h = layer_norm(x, p["attn_norm.weight"], cfg["norm_eps"])
    a = attention(h, p, kind, cfg, mm, q_block, rows)
    if rows is not None:
        x, h = x[rows], h[rows]
    return x + a + routed_experts(h, p, cfg, mm) \
        + shared_experts(h, p, cfg, mm)


def embed(ids, table):
    return table[ids].astype(jnp.float32)


def logits(x, rows, final_norm, table, cfg, mm=mm_highest):
    """Logits ``[len(rows), vocab]`` at positions ``rows`` of the last
    layer's output ``x``, through the TIED table ``[vocab, hidden]``."""
    return cfg.get("logit_scale", 1.0) * mm(
        layer_norm(x[rows], final_norm, cfg["norm_eps"]), table.T)


def forward(params, ids, cfg, mm=mm_highest, q_block=256):
    """Logits ``[T, vocab]`` of token ids ``[T]`` with every weight in one
    dict (small sizes; a big one goes layer by layer, see harness/)."""
    x = embed(ids, params["embed.weight"])
    for l, kind in enumerate(cfg["layer_types"]):
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = layer(x, p, kind, cfg, mm, q_block)
    return logits(x, jnp.arange(ids.shape[0]), params["final_norm.weight"],
                  params["embed.weight"], cfg, mm)
