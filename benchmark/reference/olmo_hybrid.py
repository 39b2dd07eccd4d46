"""Plain reference of Olmo-Hybrid-7B
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json,
``model_type`` olmo_hybrid): forward pass in straightforward ``jax.numpy``
float32. No cache, no kernels, no batching, no chunked form: the linear
layers are the token-by-token recurrence itself (``lax.scan``). Nothing is
imported from the program under test.

One layer, for ``x [T, 3840]`` the residual stream (the norm stands AFTER
the mixer and after the FFN, OLMo-2/3's placement)::

    x = x + rms(mixer(x)) * attn_norm
    x = x + rms(w2 (silu(x w1) * x w3)) * ffn_norm

``full_attention`` (every fourth layer): q, k, v = x wq, x wk, x wv; q and k
through RMSNorm over their WHOLE width (3,840), then 30 heads of 128; no
rotary; causal softmax attention, scale 128^-1/2; wo.

``linear_attention`` (the gated delta rule, Yang et al., arXiv:2412.06464),
with h = x:

    q~, k~, v~ = h wq, h wk, h wv          [T, 30*96], [T, 30*96], [T, 30*192]
    c_t = silu(sum_{j=0..3} w[:, j] * c~_{t-3+j})   per channel of the three,
          zeros before the first token (causal, depthwise, no bias)
    per head:  q_t = q'_t / |q'_t| * 96^-1/2,   k_t = k'_t / |k'_t|
               (|.| = sqrt(sum of squares + 1e-6))
    b_t = 2 sigmoid(h wb)          (the 2: linear_allow_neg_eigval)
    g_t = -exp(A_log) softplus(h wa + dt_bias),   a_t = exp(g_t)
    S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T      S in R^{192 x 96}
    o_t = S_t q_t
    y_t = rms_192(o_t) * o_norm * silu(h wg);   out = y wo

then ``rms(x) * final_norm`` and the untied head; eps 1e-6; no biases.

What the published config does not state, and is assumed (the configuration
file's ``assumed`` list repeats each): the norm placement; head_dim 128 =
3840 / 30; QK-norm over the whole width; ``rope_theta`` null read as no
rotation; the convolution without bias and before the L2 norm; ``A_log`` /
``dt_bias`` and their initial ranges; 16 of the 32 layers (``reduced``).

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


def full_attention(x, p, cfg, mm, q_block, rows=None):
    """Output ``[T, hidden]`` of the attention mixer (before its norm), or
    at the positions ``rows [R]`` alone."""
    T = x.shape[0]
    H, D, eps = cfg["num_heads"], cfg["head_dim"], cfg["norm_eps"]
    q = rms(mm(x, p["attn.wq"]), p["attn.q_norm.weight"], eps).reshape(T, H, D)
    k = rms(mm(x, p["attn.wk"]), p["attn.k_norm.weight"], eps).reshape(T, H, D)
    v = mm(x, p["attn.wv"]).reshape(T, H, D)
    pos = jnp.arange(T)
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 0, 2)   # [H,D,T], [H,T,D]

    def one_block(args):
        qb, pb = args                                     # [Q, H, D], [Q]
        s = mm(qb.transpose(1, 0, 2), kt) / jnp.sqrt(jnp.float32(D))
        s = jnp.where((pos[None, :] <= pb[:, None])[None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vt).transpose(1, 0, 2)

    at = (lambda t: t) if rows is None else (lambda t: t[rows])
    n = T if rows is None else rows.shape[0]
    Q = q_block if n % q_block == 0 else n
    split = lambda t: t.reshape((n // Q, Q) + t.shape[1:])
    o = lax.map(one_block, (split(at(q)), split(at(pos))))
    return mm(o.reshape(n, H * D), p["attn.wo"])


def linear_attention(x, p, cfg, mm):
    """Output ``[T, hidden]`` of the gated delta-rule mixer (before its
    norm): the recurrence, one token at a time."""
    T = x.shape[0]
    H, dk, dv = (cfg["linear_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    K = cfg["conv_kernel"]
    c = jnp.concatenate([mm(x, p["attn.wq"]), mm(x, p["attn.wk"]),
                         mm(x, p["attn.wv"])], axis=-1)        # [T, C]
    w = p["attn.conv.weight"].astype(jnp.float32)               # [C, K]
    padded = jnp.concatenate([jnp.zeros((K - 1, c.shape[1]), c.dtype), c],
                             axis=0)
    c = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(K)))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = unit(c[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
    k = unit(c[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = c[:, 2 * H * dk:].reshape(T, H, dv)
    beta = jax.nn.sigmoid(mm(x, p["attn.wb"]))
    if cfg["allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(p["attn.A_log"].astype(jnp.float32)) * jax.nn.softplus(
        mm(x, p["attn.wa"]) + p["attn.dt_bias"].astype(jnp.float32))

    def token(S, t):                                      # S [H, dv, dk]
        qt, kt, vt, gt, bt = t
        S = S * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - jnp.sum(S * kt[:, None, :], axis=-1))
        S = S + u[:, :, None] * kt[:, None, :]
        return S, jnp.sum(S * qt[:, None, :], axis=-1)    # o [H, dv]

    _, o = lax.scan(token, jnp.zeros((H, dv, dk), jnp.float32),
                    (q, k, v, g, beta))
    y = rms(o, p["attn.o_norm.weight"], cfg["norm_eps"]).reshape(T, H * dv) \
        * jax.nn.silu(mm(x, p["attn.wg"]))
    return mm(y, p["attn.wo"])


def layer(x, p, kind, cfg, mm=mm_highest, q_block=256, rows=None):
    """One decoder layer of ``kind`` (``linear_attention`` |
    ``full_attention``); ``p`` holds that layer's weights under their names
    without the ``layers.<l>.`` prefix. With ``rows [R]`` the result is the
    layer's output at those positions only, ``[R, hidden]``: what the LAST
    layer owes when only some positions' logits are wanted."""
    eps = cfg["norm_eps"]
    if kind == "full_attention":
        a = full_attention(x, p, cfg, mm, q_block, rows)
    elif kind == "linear_attention":
        a = linear_attention(x, p, cfg, mm)
        a = a if rows is None else a[rows]
    else:
        raise ValueError(f"layer kind {kind!r}")
    x = (x if rows is None else x[rows]) + rms(a, p["attn_norm.weight"], eps)
    f = mm(jax.nn.silu(mm(x, p["ffn.w1"])) * mm(x, p["ffn.w3"]), p["ffn.w2"])
    return x + rms(f, p["ffn_norm.weight"], eps)


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
