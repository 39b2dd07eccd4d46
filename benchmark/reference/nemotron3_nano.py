"""Plain reference of NVIDIA Nemotron 3 Nano 30B-A3B
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json,
``model_type`` nemotron_h, 31.6B-A3.2B): forward pass in straightforward
``jax.numpy`` float32. No cache, no kernels, no batching, no chunked form,
no sorted dispatch: the state-space layers are the recurrence itself, ONE
state update a token (``lax.scan`` over tokens), the experts a plain loop
with a masked sum, the router a plain top-6. Nothing is imported from the
program under test.

A layer has ONE part, by the ``l``-th character of
``hybrid_override_pattern`` (``M`` Mamba-2, ``E`` experts, ``*``
attention), for ``x [T, 2688]`` the residual stream (RMSNorm eps 1e-5, no
bias anywhere but the convolution's)::

    x = x + part(rms(x) * norm)

``M`` (Mamba-2, arXiv:2405.21060: 64 heads x 64 = 4096 inner, 8 groups,
state 128, convolution 4)::

    [z | xBC | dt] = h w_in                  widths 4096 | 6144 | 64
    xBC_t = silu(sum_{j=0..3} w[:, j] * xBC~_{t-3+j} + b)   zeros before the
            first token (causal, depthwise, over all 6,144 channels)
    x_h [64 x 64], B_g [8 x 128], C_g [8 x 128] = split(xBC); head h reads
            group h // 8
    dt_h = softplus(dt_h + dt_bias_h)        (no clamp)
    S_h  = exp(dt_h A_h) S_h + dt_h x_h (x) B_g,   A_h = -exp(A_log_h)
    y_h  = S_h C_g + D_h x_h                 (the state AFTER the update)
    y    = rms_groups(y * silu(z)) * norm    the gate FIRST, then a norm over
            each of the 8 groups of 512
    out  = y w_out

``*`` (attention): q = h wq (32 heads of 128), k, v = h wk, h wv (2 heads of
128: 16 query heads a key/value head); NO positions, no QK-norm; causal
softmax at scale 128^-1/2; out = a wo.

``E`` (experts): s = sigmoid(h router) over ALL ``num_experts`` (128); the
CHOICE is the 6 largest of ``s + router.bias`` (``n_group`` 1: no group
limit); the WEIGHTS are ``s`` of the chosen over their sum (+ 1e-20), times
``routed_scaling_factor`` 2.5; out = sum over the chosen e of w_e
relu(h w1_e)^2 w2_e  + the shared expert relu(h s.w1)^2 s.w2 (UNGATED FFNs
of two matrices; width 1,856 routed, 3,712 shared).

THE SHARE. ``cfg["experts_held"] = (n, first)`` says which experts' weights
``ffn.w1 [n, width, hidden]`` (a Linear's ``[out, in]``) and ``ffn.w2 [n,
width, hidden]`` are: the routed sum runs over the chosen e in
``[first, first + n)`` alone, and what the others would add is left out (a
chip of a group that shares each layer; nothing stands in for the rest).
The vocabulary is whatever ``embed.weight`` / ``head.weight`` hold.

What the published config does not state, and is assumed (the configuration
file's ``assumed`` list repeats each): no positions in the attention layers;
the gate before the grouped norm; the router's bias in the choice only.

``mm`` is the matmul every contraction with a weight goes through. The
default contracts in float32 at precision "highest"; the control of the
correctness check passes a lower-precision ``mm``. The recurrence's own
contractions (the outer product, ``S C``) are float32 multiply-and-sum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: the published pattern's characters, in this file's names
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def mm_highest(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def attention(h, p, cfg, mm, q_block, rows=None):
    """Output ``[T, hidden]`` of the GQA mixer over the normed input ``h``,
    or at the positions ``rows [R]`` alone."""
    T = h.shape[0]
    Hq, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    rep = Hq // Hkv
    q = mm(h, p["attn.wq"]).reshape(T, Hkv, rep, D)
    k = mm(h, p["attn.wk"]).reshape(T, Hkv, D)
    v = mm(h, p["attn.wv"]).reshape(T, Hkv, D)
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
    return mm(o.reshape(n, Hq * D), p["attn.wo"])


def mamba(h, p, cfg, mm):
    """Output ``[T, hidden]`` of the Mamba-2 mixer over the normed input
    ``h``: the recurrence, one token at a time."""
    T = h.shape[0]
    H, P, G, N = (cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_groups"],
                  cfg["ssm_state"])
    K, inner = cfg["conv_kernel"], H * P
    C = inner + 2 * G * N
    zxd = mm(h, p["attn.w_in"])
    z, c, dt = zxd[:, :inner], zxd[:, inner:inner + C], zxd[:, inner + C:]
    w = p["attn.conv.weight"].astype(jnp.float32)               # [C, K]
    padded = jnp.concatenate([jnp.zeros((K - 1, C), c.dtype), c], axis=0)
    c = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(K))
                    + p["attn.conv.bias"].astype(jnp.float32))
    x = c[:, :inner].reshape(T, H, P)
    Bm = c[:, inner:inner + G * N].reshape(T, G, N)
    Cm = c[:, inner + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + p["attn.dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["attn.A_log"].astype(jnp.float32))
    D = p["attn.D"].astype(jnp.float32)
    group = jnp.arange(H) // (H // G)                 # head h reads group

    def token(S, t):                                  # S [H, P, N]
        xt, dtt, Bt, Ct = t
        S = S * jnp.exp(dtt * A)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * Bt[group][:, None, :]
        return S, jnp.sum(S * Ct[group][:, None, :], axis=-1) \
            + D[:, None] * xt                         # y [H, P]

    _, y = lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                    (x, dt, Bm, Cm))
    y = y.reshape(T, inner)
    late = cfg.get("norm_before_gate", False)   # the family's flag: false
    if not late:
        y = y * jax.nn.silu(z)                        # the gate FIRST
    y = y.reshape(T, G, inner // G)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                      + cfg["norm_eps"])
    y = y.reshape(T, inner) * p["attn.norm.weight"].astype(jnp.float32)
    if late:
        y = y * jax.nn.silu(z)
    return mm(y, p["attn.w_out"])


def relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def route(g, p, cfg, mm):
    """The routing weights ``[T, num_experts]`` (0 where not chosen) of the
    normed input ``g``: the choice on ``s + bias``, the weights from ``s``."""
    E, k = cfg["num_experts"], cfg["experts_per_token"]
    s = jax.nn.sigmoid(mm(g, p["ffn.router"]))                # [T, E]
    _, idx = lax.top_k(s + p["ffn.router.bias"].astype(jnp.float32), k)
    top = jnp.take_along_axis(s, idx, axis=1)
    if cfg["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(g.shape[0])[:, None], idx].set(top)


def routed_experts(g, p, cfg, mm):
    """The routed experts' part over the normed input ``g [T, hidden]``:
    the sum over the chosen experts that ``cfg["experts_held"]`` holds."""
    n, first = cfg["experts_held"]
    weight = route(g, p, cfg, mm)

    def one_expert(y, e):                                     # e: held index
        a = relu2(mm(g, p["ffn.w1"][e].T))      # kept [width, hidden]
        return y + weight[:, first + e, None] * mm(a, p["ffn.w2"][e]), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(g), jnp.arange(n))
    return y


def shared_expert(g, p, mm):
    return mm(relu2(mm(g, p["ffn.shared.w1"])), p["ffn.shared.w2"])


def layer(x, p, kind, cfg, mm=mm_highest, q_block=256, rows=None):
    """One layer of ``kind`` (``mamba`` | ``experts`` | ``attention``);
    ``p`` holds that layer's weights under their names without the
    ``layers.<l>.`` prefix. With ``rows [R]`` the result is the layer's
    output at those positions only, ``[R, hidden]``: what the LAST layer
    owes when only some positions' logits are wanted."""
    eps = cfg["norm_eps"]
    at = (lambda t: t) if rows is None else (lambda t: t[rows])
    if kind == "experts":
        g = rms(at(x), p["ffn_norm.weight"], eps)
        return at(x) + routed_experts(g, p, cfg, mm) + shared_expert(g, p, mm)
    h = rms(x, p["attn_norm.weight"], eps)
    if kind == "attention":
        return at(x) + attention(h, p, cfg, mm, q_block, rows)
    if kind == "mamba":
        return at(x) + at(mamba(h, p, cfg, mm))
    raise ValueError(f"layer kind {kind!r}")


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
