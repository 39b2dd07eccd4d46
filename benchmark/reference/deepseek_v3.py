"""Plain reference of DeepSeek-V3
(https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json,
``model_type`` deepseek_v3, 671B-A37B; arXiv:2412.19437 section 2.1 and the
model's own ``modeling_deepseek.py``): forward pass in straightforward
``jax.numpy`` float32. No cache, no kernels, no batching, no absorbed
weights, no sorted dispatch: keys and values are EXPANDED from the latents
for every head, the experts are a plain loop with a masked sum. Nothing is
imported from the program under test.

One layer, for ``x [T, 7168]`` the residual stream (pre-norm RMSNorm, eps
1e-6, no biases)::

    h = rms(x) * attn_norm;  x = x + attention(h)
    g = rms(x) * ffn_norm;   x = x + ffn(g)

attention (multi-head latent attention, 128 heads)::

    c_q = rms(h wq_a) * q_norm                            [1536]
    q   = c_q wq_b -> 128 heads x (128 nope | 64 rope);   q_pe = yarn(q_pe)
    [c | k_pe] = h wkv_a                                  [512 | 64]
    c   = rms(c) * kv_norm;  k_pe = yarn(k_pe)     (ONE k_pe for all heads)
    k_nope = c wk_b,  v = c wv_b          -> 128 heads x 128 each
    s   = (q_nope . k_nope + q_pe . k_pe) * 192^-1/2 * mscale^2,  causal
    out = (softmax(s) v  ->  [128 x 128]) wo

yarn: the half-split rotation (pair i is lanes (i, i + 32)) at the
frequencies ``inv_freq = inv_extra (1 - ramp) + inv_extra / factor * ramp``,
``inv_extra = theta^(-2i/64)``, ``ramp`` rising linearly from pair
``floor(d(beta_fast))`` to pair ``ceil(d(beta_slow))``, ``d(n) = 64
ln(original / (2 pi n)) / (2 ln theta)`` (10 and 23 at the published
values), clipped to [0, 1]; cos and sin times ``mscale / mscale_all_dim``
(1); the softmax scale times ``mscale^2``, ``mscale = 0.1 ln(factor) + 1``.

ffn: the first ``first_dense_layers`` layers a dense SwiGLU ``(silu(g w1) *
g w3) w2``; every later layer routed experts + one shared SwiGLU on every
token. Router (``MoEGate``, ``topk_method`` noaux_tc), float32: ``s =
sigmoid(g router)`` over ALL ``num_experts``; the choice is made on ``s +
router.bias``: a group's score is the sum of its two largest among its
experts, the ``topk_group`` best of ``n_group`` groups are kept, the top
``experts_per_token`` of ``s + bias`` inside them chosen; the weights are
``s`` (NOT ``s + bias``) of the chosen over their sum (+ 1e-20), times
``routed_scaling_factor``.

THE SHARE. ``cfg["experts_held"] = (n, first)`` says which experts' weights
``ffn.w1 / w3 / w2 [n, ...]`` are: the routed sum runs over the chosen e in
``[first, first + n)`` alone, and what the others would add is left out (a
chip of a group that shares each layer; nothing stands in for the rest).
The vocabulary is whatever ``embed.weight`` / ``head.weight`` hold.

Departures from the published code, each repeated in the configuration
file's ``assumed``: rotary pairs half-split where the published code
de-interleaves first (the same model under a permutation of ``wq_b``'s and
``wkv_a``'s rope columns, which seeded weights do not tell apart); the
up-projection ``kv_b_proj`` held as its two halves by column, ``wk_b`` and
``wv_b``; groups that are not kept masked with -inf (the published
inference code; the Hugging Face port fills 0.0, which differs only where a
kept expert's ``s + bias`` is negative); the multi-token-prediction module
is not held.

``mm`` is the matmul every contraction goes through. The default contracts
in float32 at precision "highest"; the control of the correctness check
passes a lower-precision ``mm``. Attention is computed a group of
``head_block`` heads and a block of ``q_block`` queries at a time, and the
queries in ``parts`` runs that each see only the keys up to their last
position (blocking alone: every number is the same sum).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def mm_highest(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg):
    """The rotary frequencies ``[qk_rope_head_dim / 2]``."""
    sc, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)
    extra = 1.0 / base ** (i / dim)
    inter = 1.0 / (sc["factor"] * base ** (i / dim))
    find = lambda n: dim * math.log(
        sc["original_max_position_embeddings"] / (n * 2 * math.pi)) \
        / (2 * math.log(base))
    low = max(math.floor(find(sc["beta_fast"])), 0)
    high = min(math.ceil(find(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg):
    sc = cfg["rope_scaling"]
    m = yarn_get_mscale(sc["factor"], sc["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rotate(x, pos, cfg):
    """``x [T, ..., dr]`` at positions ``pos [T]``, half-split pairs."""
    sc = cfg["rope_scaling"]
    m = yarn_get_mscale(sc["factor"], sc["mscale"]) \
        / yarn_get_mscale(sc["factor"], sc["mscale_all_dim"])
    ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, p, cfg, mm, q_block, rows=None):
    """Output ``[T, hidden]`` of the latent-attention mixer over the normed
    input ``h``, or at the positions ``rows [R]`` alone."""
    T = h.shape[0]
    H, rkv = cfg["num_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, scale = cfg["norm_eps"], softmax_scale(cfg)
    Hb = min(cfg.get("head_block", H), H)
    G = H // Hb
    cq = rms(mm(h, p["attn.wq_a"]), p["attn.q_norm.weight"], eps)
    ckv = mm(h, p["attn.wkv_a"])
    c = rms(ckv[:, :rkv], p["attn.kv_norm.weight"], eps)
    pos = jnp.arange(T)
    k_pe = rotate(ckv[:, rkv:], pos, cfg)                       # [T, dr]
    # a group of heads' columns (rows of wo) side by side, groups leading
    cols = lambda w, d: w.reshape(w.shape[0], G, Hb * d).transpose(1, 0, 2)
    wq, wk, wv = (cols(p["attn.wq_b"], dn + dr), cols(p["attn.wk_b"], dn),
                  cols(p["attn.wv_b"], dv))
    wo = p["attn.wo"].reshape(G, Hb * dv, -1)

    def run(q_at, n_keys):
        """The queries at positions ``q_at`` against keys ``[0, n_keys)``."""
        n = q_at.shape[0]
        Q = q_block if n % q_block == 0 else n

        def group(w):
            wq_g, wk_g, wv_g, wo_g = w
            q = mm(cq[q_at], wq_g).reshape(n, Hb, dn + dr)
            qn, qp = q[..., :dn], rotate(q[..., dn:], q_at, cfg)
            kn = mm(c[:n_keys], wk_g).reshape(n_keys, Hb, dn)
            v = mm(c[:n_keys], wv_g).reshape(n_keys, Hb, dv)
            knt, vt = kn.transpose(1, 2, 0), v.transpose(1, 0, 2)

            def block(args):
                qnb, qpb, pb = args               # [Q, Hb, dn], [Q, Hb, dr]
                s = (mm(qnb.transpose(1, 0, 2), knt)
                     + mm(qpb.transpose(1, 0, 2), k_pe[:n_keys].T)) * scale
                ok = pos[None, :n_keys] <= pb[:, None]
                s = jnp.where(ok[None], s, -jnp.inf)
                o = mm(jax.nn.softmax(s, axis=-1), vt)          # [Hb, Q, dv]
                return o.transpose(1, 0, 2)

            split = lambda t: t.reshape((n // Q, Q) + t.shape[1:])
            o = lax.map(block, (split(qn), split(qp), split(q_at)))
            return mm(o.reshape(n, Hb * dv), wo_g)              # [n, hidden]

        return jnp.sum(lax.map(group, (wq, wk, wv, wo)), axis=0)

    if rows is not None:
        return run(rows, T)
    parts = cfg.get("parts", 1)
    if T % parts:
        parts = 1
    step = T // parts
    return jnp.concatenate([run(pos[i * step:(i + 1) * step], (i + 1) * step)
                            for i in range(parts)], axis=0)


def route(g, p, cfg, mm):
    """``[T, E]`` float32: a token's weight on each expert, 0 where it was
    not chosen."""
    T = g.shape[0]
    E, k, G = cfg["num_experts"], cfg["experts_per_token"], cfg["n_group"]
    s = jax.nn.sigmoid(mm(g, p["ffn.router"]))                  # [T, E]
    choose = s + p["ffn.router.bias"].astype(jnp.float32)[None, :]
    grouped = choose.reshape(T, G, E // G)
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)   # [T, G]
    _, keep = lax.top_k(group_score, cfg["topk_group"])
    kept = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], keep].set(True)
    inside = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)
    _, idx = lax.top_k(inside, k)
    w = jnp.take_along_axis(s, idx, axis=1)          # s, not s + bias
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(T)[:, None], idx].set(w)


def routed_experts(g, p, cfg, mm):
    """The routed experts' part over the normed input ``g [T, hidden]``:
    the sum over the chosen experts that ``cfg["experts_held"]`` holds."""
    n, first = cfg["experts_held"]
    weight = route(g, p, cfg, mm)

    def one_expert(y, e):                                     # e: held index
        a = jax.nn.silu(mm(g, p["ffn.w1"][e])) * mm(g, p["ffn.w3"][e])
        w = lax.dynamic_index_in_dim(weight, first + e, axis=1)   # [T, 1]
        return y + w * mm(a, p["ffn.w2"][e]), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(g), jnp.arange(n))
    return y


def swiglu(g, p, pre, mm):
    return mm(jax.nn.silu(mm(g, p[pre + "w1"])) * mm(g, p[pre + "w3"]),
              p[pre + "w2"])


def layer(x, p, kind, cfg, mm=mm_highest, q_block=256, rows=None):
    """One decoder layer whose FFN is of ``kind`` (``dense`` | ``moe``);
    ``p`` holds that layer's weights under their names without the
    ``layers.<l>.`` prefix. With ``rows [R]`` the result is the layer's
    output at those positions only, ``[R, hidden]``: what the LAST layer
    owes when only some positions' logits are wanted."""
    eps = cfg["norm_eps"]
    h = rms(x, p["attn_norm.weight"], eps)
    x = (x if rows is None else x[rows]) + attention(h, p, cfg, mm, q_block,
                                                     rows)
    g = rms(x, p["ffn_norm.weight"], eps)
    if kind == "dense":
        return x + swiglu(g, p, "ffn.", mm)
    if kind != "moe":
        raise ValueError(f"ffn kind {kind!r}")
    return x + routed_experts(g, p, cfg, mm) + swiglu(g, p, "ffn.shared.", mm)


def ffn_kinds(cfg):
    d = cfg["first_dense_layers"]
    return ["dense"] * d + ["moe"] * (cfg["num_layers"] - d)


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
    for l, kind in enumerate(ffn_kinds(cfg)):
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = layer(x, p, kind, cfg, mm, q_block)
    return logits(x, jnp.arange(ids.shape[0]), params["final_norm.weight"],
                  params["head.weight"], cfg, mm)
