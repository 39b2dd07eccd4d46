"""Plain reference of Solar-Open2-250B
(https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json,
``model_type`` solar_open2, 250B-A15B): forward pass in straightforward
``jax.numpy`` float32. No cache, no kernels, no batching, no chunked form,
no sorted dispatch: the linear layers are the token-by-token recurrence
itself (``lax.scan``), the experts a plain loop with a masked sum. Nothing
is imported from the program under test.

One layer, for ``x [T, 4096]`` the residual stream (pre-norm, eps 1e-5, no
biases, no positions anywhere: ``use_rope`` false)::

    h = rms(x) * attn_norm;  x = x + mixer(h)
    g = rms(x) * ffn_norm;   x = x + experts(g)

``full_attention`` (layers 0, 4, 8, ...: ``gqa_layers``): q = h wq (64 heads
of 128), k, v = h wk, h wv (8 heads of 128: a group of 8 query heads a
key/value head); no rotation, no QK-norm; causal softmax, scale 128^-1/2;
out = (attn * sigmoid(h wg)) wo  (``use_gqa_gate``, element-wise).

``linear_attention`` (Kimi Delta Attention, arXiv:2510.26692: the gated
delta rule of arXiv:2412.06464 with a decay a KEY CHANNEL), 64 heads,
dk = dv = 128:

    q~, k~, v~ = h wq, h wk, h wv                        [T, 64 * 128] each
    c_t = silu(sum_{j=0..3} w[:, j] * c~_{t-3+j})   per channel of the three,
          zeros before the first token (causal, depthwise, no bias)
    per head:  q_t = q'_t / |q'_t| * 128^-1/2,   k_t = k'_t / |k'_t|
               (|.| = sqrt(sum of squares + 1e-6))
    b_t = 2 sigmoid(h wb)          (the 2: kda_allow_neg_eigval)
    g_t = -exp(A_log[head]) softplus((h wf_a) wf_b + dt_bias)   [64, 128]
    S' = S_{t-1} diag(exp(g_t));  S_t = S' + b_t (v_t - S' k_t) k_t^T
    o_t = S_t q_t                                        S in R^{128 x 128}
    y_t = rms_128(o_t) * o_norm * sigmoid((h wg_a) wg_b);   out = y wo

experts, every layer (``first_k_dense_replace`` 0): p = softmax(g router)
over ALL ``num_experts`` (320), the top 8 renormalised (``norm_topk_prob``),
times ``routed_scaling_factor``; x += sum over the chosen e of p_e
(silu(g w1_e) * g w3_e) w2_e  + the shared expert (silu(g s.w1) * g s.w3)
s.w2, ungated.

THE SHARE. ``cfg["experts_held"] = (n, first)`` says which experts' weights
``ffn.w1 / w3 / w2 [n, ...]`` are: the routed sum runs over the chosen e in
``[first, first + n)`` alone, and what the others would add is left out (a
chip of a group that shares each layer; nothing stands in for the rest).
The vocabulary is whatever ``embed.weight`` / ``head.weight`` hold: a slice
is a smaller vocabulary.

What the published config does not state, and is assumed (the configuration
file's ``assumed`` list repeats each): the low-rank pairs' rank (128, the
head width; ``kda_use_full_proj`` false), the convolution without bias and
before the L2 norm, the L2 norm's eps, ``A_log`` a head and ``dt_bias`` a
channel with their initial ranges, the output norm a head with one weight
vector, the GQA gate element-wise from its own full projection, softmax
scoring in the router, the shared expert's width (1,280 x
``n_shared_experts``).

``mm`` is the matmul every contraction with a weight goes through. The
default contracts in float32 at precision "highest"; the control of the
correctness check passes a lower-precision ``mm``. The recurrence's own
contractions (``S k``, ``S q``) are float32 multiply-and-sum.
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


def full_attention(x, h, p, cfg, mm, q_block, rows=None):
    """Output ``[T, hidden]`` of the gated GQA mixer over the normed input
    ``h``, or at the positions ``rows [R]`` alone."""
    T = h.shape[0]
    Hq, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    rep = Hq // Hkv
    q = mm(h, p["attn.wq"]).reshape(T, Hkv, rep, D)
    k = mm(h, p["attn.wk"]).reshape(T, Hkv, D)
    v = mm(h, p["attn.wv"]).reshape(T, Hkv, D)
    gate = jax.nn.sigmoid(mm(h, p["attn.wg"]))
    pos = jnp.arange(T)
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 0, 2)   # [G,D,T], [G,T,D]

    def one_block(args):
        qb, pb = args                                 # [Q, G, rep, D], [Q]
        Q = qb.shape[0]
        qg = qb.transpose(1, 2, 0, 3).reshape(Hkv, rep * Q, D)
        s = mm(qg, kt) / jnp.sqrt(jnp.float32(D))     # [G, rep * Q, T]
        ok = jnp.tile(pos[None, :] <= pb[:, None], (rep, 1))
        s = jnp.where(ok[None], s, -jnp.inf)
        o = mm(jax.nn.softmax(s, axis=-1), vt)        # [G, rep * Q, D]
        return o.reshape(Hkv, rep, Q, D).transpose(2, 0, 1, 3)

    at = (lambda t: t) if rows is None else (lambda t: t[rows])
    n = T if rows is None else rows.shape[0]
    Q = q_block if n % q_block == 0 else n
    split = lambda t: t.reshape((n // Q, Q) + t.shape[1:])
    o = lax.map(one_block, (split(at(q)), split(at(pos))))
    return mm(o.reshape(n, Hq * D) * at(gate), p["attn.wo"])


def linear_attention(h, p, cfg, mm):
    """Output ``[T, hidden]`` of the per-channel-gated delta-rule mixer over
    the normed input ``h``: the recurrence, one token at a time."""
    T = h.shape[0]
    H, dk, dv = (cfg["linear_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    K = cfg["conv_kernel"]
    c = jnp.concatenate([mm(h, p["attn.wq"]), mm(h, p["attn.wk"]),
                         mm(h, p["attn.wv"])], axis=-1)        # [T, C]
    w = p["attn.conv.weight"].astype(jnp.float32)               # [C, K]
    padded = jnp.concatenate([jnp.zeros((K - 1, c.shape[1]), c.dtype), c],
                             axis=0)
    c = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(K)))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = unit(c[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
    k = unit(c[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = c[:, 2 * H * dk:].reshape(T, H, dv)
    beta = jax.nn.sigmoid(mm(h, p["attn.wb"]))
    if cfg["allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(p["attn.A_log"].astype(jnp.float32))[:, None] \
        * jax.nn.softplus(mm(mm(h, p["attn.wf_a"]), p["attn.wf_b"])
                          + p["attn.dt_bias"].astype(jnp.float32)
                          ).reshape(T, H, dk)

    def token(S, t):                                      # S [H, dv, dk]
        qt, kt, vt, gt, bt = t
        S = S * jnp.exp(gt)[:, None, :]                   # a factor a column
        u = bt[:, None] * (vt - jnp.sum(S * kt[:, None, :], axis=-1))
        S = S + u[:, :, None] * kt[:, None, :]
        return S, jnp.sum(S * qt[:, None, :], axis=-1)    # o [H, dv]

    _, o = lax.scan(token, jnp.zeros((H, dv, dk), jnp.float32),
                    (q, k, v, g, beta))
    y = rms(o, p["attn.o_norm.weight"], cfg["norm_eps"]).reshape(T, H * dv) \
        * jax.nn.sigmoid(mm(mm(h, p["attn.wg_a"]), p["attn.wg_b"]))
    return mm(y, p["attn.wo"])


def routed_experts(g, p, cfg, mm):
    """The routed experts' part over the normed input ``g [T, hidden]``:
    the sum over the chosen experts that ``cfg["experts_held"]`` holds."""
    E, k = cfg["num_experts"], cfg["experts_per_token"]
    n, first = cfg["experts_held"]
    prob = jax.nn.softmax(mm(g, p["ffn.router"]), axis=-1)    # [T, E]
    top, idx = lax.top_k(prob, k)
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    weight = jnp.zeros_like(prob).at[
        jnp.arange(g.shape[0])[:, None], idx].set(top)        # 0 if not chosen

    def one_expert(y, e):                                     # e: held index
        a = jax.nn.silu(mm(g, p["ffn.w1"][e])) * mm(g, p["ffn.w3"][e])
        return y + weight[:, first + e, None] * mm(a, p["ffn.w2"][e]), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(g), jnp.arange(n))
    return y


def shared_expert(g, p, mm):
    return mm(jax.nn.silu(mm(g, p["ffn.shared.w1"]))
              * mm(g, p["ffn.shared.w3"]), p["ffn.shared.w2"])


def layer(x, p, kind, cfg, mm=mm_highest, q_block=256, rows=None):
    """One decoder layer of ``kind`` (``linear_attention`` |
    ``full_attention``); ``p`` holds that layer's weights under their names
    without the ``layers.<l>.`` prefix. With ``rows [R]`` the result is the
    layer's output at those positions only, ``[R, hidden]``: what the LAST
    layer owes when only some positions' logits are wanted."""
    eps = cfg["norm_eps"]
    h = rms(x, p["attn_norm.weight"], eps)
    if kind == "full_attention":
        a = full_attention(x, h, p, cfg, mm, q_block, rows)
    elif kind == "linear_attention":
        a = linear_attention(h, p, cfg, mm)
        a = a if rows is None else a[rows]
    else:
        raise ValueError(f"layer kind {kind!r}")
    x = (x if rows is None else x[rows]) + a
    g = rms(x, p["ffn_norm.weight"], eps)
    return x + routed_experts(g, p, cfg, mm) + shared_expert(g, p, mm)


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
    for l, kind in enumerate(cfg["layer_types"]):
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = layer(x, p, kind, cfg, mm, q_block)
    return logits(x, jnp.arange(ids.shape[0]), params["final_norm.weight"],
                  params["head.weight"], cfg, mm)
