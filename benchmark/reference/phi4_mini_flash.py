"""Plain reference of Microsoft Phi-4-mini-flash-reasoning
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json,
``model_type`` phi4flash, 3.85 B; arXiv:2507.06607, "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation", SambaY): forward
pass in straightforward ``jax.numpy`` float32. No cache, no kernels, no
batching, no last-token cut: EVERY layer runs on EVERY token, the Mamba-1
layers are the recurrence itself, ONE state update a token (``lax.scan``
over tokens), attention is a dense masked softmax a block of queries at a
time. Nothing is imported from the program under test.

Sizes from the published config: hidden d = 2,560, 32 layers, 40 query heads
and 20 key/value heads of 64, SwiGLU width 10,240, LayerNorm eps 1e-5,
``mb_per_layer`` 2, ``sliding_window`` 512, vocabulary 200,064, the head =
the embedding transposed, no bias in the SwiGLU or the head.

Every layer ``l`` (0-based), for ``x [T, d]`` the residual stream::

    x = x + mixer_l(LN(x))
    x = x + (silu(g) * u) w2,   g = LN'(x) w1,  u = LN'(x) w3

``LN`` is LayerNorm with weight AND bias; a final LayerNorm, then the head.
NO positions are applied anywhere (assumed (i)).

The mixer by layer (``mb_per_layer`` 2: even layers a Mamba-family mixer,
odd ones attention; the second half is the cross-decoder)::

    0, 2, .., 14     mamba     Mamba-1
    1, 3, .., 15     sliding   differential attention, window 512
    16               mamba     Mamba-1 that also hands on its memory m
    17               full      differential attention, every s <= t; its K
                               and V are THE cache of the cross-decoder
    18, 20, .., 30   gmu       gated memory unit over m
    19, 21, .., 31   cross     differential attention: own queries, layer
                               17's K and V

``mamba`` (Mamba-1, arXiv:2312.00752; assumed (ii): inner width E = 2 d =
5,120, state N = 16, convolution 4 with a bias, step rank R = ceil(d / 16) =
160, no bias on the in / out projections)::

    [x | z] = h w_in                                   d -> 2 E
    x_t = silu(sum_{j=0..3} w[:, j] x~_{t-3+j} + b)    zeros before the first
          token (causal, depthwise)
    [delta | B | C] = x w_x                            E -> R + N + N
    dt = softplus(delta w_dt + dt_bias)                R -> E
    A = -exp(A_log)                                    [N, E]
    S_t[n, e] = exp(dt_t[e] A[n, e]) S_{t-1}[n, e] + dt_t[e] x_t[e] B_t[n]
    y_t[e] = sum_n S_t[n, e] C_t[n] + D[e] x_t[e]
    out = (y * silu(z)) w_out                          E -> d
    m_t = y_t   (layer 16: the scan's output BEFORE the gate)

(Departure in LAYOUT only: ``A_log`` is kept ``[N, E]``, the state's lanes
first, as the program keeps it; the published ``[E, N]`` is its transpose.)

``gmu`` (arXiv:2507.06607 section 2): ``out = (silu(h w1) * m) w2``, ``m`` of
the SAME token; no state.

Differential attention (arXiv:2410.05258, in EVERY attention layer: assumed
(iii)): ``q = h wq + bq`` (40 heads of 64) and, in layers 1..17, ``k = h wk +
bk``, ``v = h wv + bv`` (20 heads of 64 each; the biases: assumed (iv)).
Query heads 2p, 2p + 1 form pair p (20 pairs); K heads 2r, 2r + 1 form K
pair r, ``v_r = [v_2r | v_2r+1]`` (128 lanes; 10 pairs); pair p reads pair r
= p // 2. With ``a_{p,j} = softmax_s(q_{p,j} . k_{r,j,s} / 8)`` over the
keys the layer sees (sliding: t - 512 < s <= t, assumed (v); full and
cross: s <= t)::

    o_p = sum_s (a_{p,1,s} - lambda a_{p,2,s}) v_{r,s}
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 l)                (assumed (vi))
    o_p = RMSNorm_128(o_p; gamma, eps 1e-5) (1 - lambda_init)
    out = [o_0 | .. | o_19] wo + bo

A cross layer has wq, bq, wo, bo, its own lambda vectors and gamma, and no
K/V projection.

``mm`` is the matmul every contraction with a weight goes through. The
default contracts in float32 at precision "highest"; the control of the
correctness check passes a lower-precision ``mm``. The recurrence's own
contractions and the attention's are float32.

``rows``: from layer ``CUT`` (17) on every token is a function of its own
row of the stream, of ``m`` and of layer 17's K and V alone, so a caller that
wants only some positions' logits may ask layer 17 for its output at
``rows`` only; the later layers then run on those rows. ``forward`` never
does: it runs every layer on every token.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_NEG = -1e30


def mm_highest(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=_HI)


def kinds(num_layers: int):
    """The mixer of each layer, from ``mb_per_layer`` 2 and the
    decoder-hybrid-decoder split at the middle."""
    half = num_layers // 2
    out = []
    for l in range(num_layers):
        if l % 2 == 0:
            out.append("mamba" if l <= half else "gmu")
        else:
            out.append("sliding" if l < half else
                       "full" if l == half + 1 else "cross")
    return out


def layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(((x - mu) ** 2).mean(-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32) + b.astype(jnp.float32)


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def mamba(h, p, cfg, mm):
    """(out [T, d], the scan's output before the gate [T, E])."""
    T = h.shape[0]
    E, N, R, K = cfg["inner"], cfg["state"], cfg["dt_rank"], cfg["conv_kernel"]
    f32 = jnp.float32
    xz = mm(h, p["attn.w_in"])
    x, z = xz[:, :E], xz[:, E:]
    pad = jnp.concatenate([jnp.zeros((K - 1, E), f32), x], axis=0)
    w = p["attn.conv.weight"].astype(f32)                     # [E, K]
    x = jax.nn.silu(sum(pad[j:j + T] * w[:, j] for j in range(K))
                    + p["attn.conv.bias"].astype(f32))
    dbc = mm(x, p["attn.w_x"])
    dt = jax.nn.softplus(mm(dbc[:, :R], p["attn.w_dt"])
                         + p["attn.dt_bias"].astype(f32))
    Bm, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(p["attn.A_log"].astype(f32))                 # [N, E]
    D = p["attn.D"].astype(f32)

    def step(S, xs):
        xt, dtt, Bt, Ct = xs
        S = jnp.exp(dtt[None, :] * A) * S + (dtt * xt)[None, :] * Bt[:, None]
        return S, jnp.sum(S * Ct[:, None], axis=0) + D * xt

    _, y = lax.scan(step, jnp.zeros((N, E), f32), (x, dt, Bm, Cm))
    return mm(y * jax.nn.silu(z), p["attn.w_out"]), y


def diff_attention(hq, qpos, k, v, p, lam0, cfg, mm, q_block, window=None):
    """Differential attention of the queries of ``hq [Tq, d]`` at positions
    ``qpos [Tq]`` against keys and values ``k, v [T, 20, 64]`` (position =
    row), a block of ``q_block`` queries at a time; ``lam0`` the layer's
    ``lambda_init``."""
    Hq, D = cfg["num_heads"], cfg["head_dim"]
    T, Hkv = k.shape[0], k.shape[1]
    P, rep = Hq // 2, (Hq // 2) // (Hkv // 2)
    f32 = jnp.float32
    Tq = hq.shape[0]
    q = (mm(hq, p["attn.wq"]) + p["attn.bq"].astype(f32)) \
        .reshape(Tq, P, 2, D)
    # pair p reads K/V pair p // rep
    kp = jnp.repeat(k.reshape(T, Hkv // 2, 2, D), rep, axis=1)   # [T, P, 2, D]
    vp = jnp.repeat(v.reshape(T, Hkv // 2, 2 * D), rep, axis=1)  # [T, P, 2D]
    lam = jnp.exp(jnp.sum(p["attn.lambda_q1"].astype(f32)
                          * p["attn.lambda_k1"].astype(f32))) \
        - jnp.exp(jnp.sum(p["attn.lambda_q2"].astype(f32)
                          * p["attn.lambda_k2"].astype(f32))) + lam0
    spos = jnp.arange(T)

    def block(qb, pb):
        s = jnp.einsum("tpjd,spjd->pjts", qb, kp, precision=_HI) / math.sqrt(D)
        seen = spos[None, :] <= pb[:, None]
        if window is not None:
            seen = seen & (spos[None, :] > pb[:, None] - window)
        a = jax.nn.softmax(jnp.where(seen[None, None], s, _NEG), axis=-1)
        o = jnp.einsum("pts,spd->tpd", a[:, 0] - lam * a[:, 1], vp,
                       precision=_HI)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg["norm_eps"])
        return o * p["attn.o_norm.weight"].astype(f32) * (1 - lam0)

    if Tq % q_block:
        o = block(q, qpos)
    else:
        o = lax.map(lambda a: block(*a),
                    (q.reshape(Tq // q_block, q_block, P, 2, D),
                     qpos.reshape(Tq // q_block, q_block)))
    return mm(o.reshape(Tq, Hq * D), p["attn.wo"]) + p["attn.bo"].astype(f32)


def keys_values(h, p, cfg, mm):
    f32 = jnp.float32
    T = h.shape[0]
    shape = (T, cfg["num_kv_heads"], cfg["head_dim"])
    return ((mm(h, p["attn.wk"]) + p["attn.bk"].astype(f32)).reshape(shape),
            (mm(h, p["attn.wv"]) + p["attn.bv"].astype(f32)).reshape(shape))


def layer(x, p, l, cfg, carry, mm=mm_highest, q_block=256, rows=None):
    """Layer ``l`` over the stream ``x``; ``p`` holds that layer's weights
    under their names without the ``layers.<l>.`` prefix; ``carry`` what the
    earlier layers hand on (``m``, ``k`` / ``v``, ``qpos``: the positions of
    the stream's rows). Returns (x, carry). ``rows [R]`` (layer ``CUT``
    alone): the output at those positions only (the module's docstring)."""
    return layer_of(x, p, cfg["layer_types"][l], cfg, carry, lambda_init(l),
                    l == cfg["memory_layer"], mm, q_block, rows)


def layer_of(x, p, kind, cfg, carry, lam0, keeps_memory, mm=mm_highest,
             q_block=256, rows=None):
    """``layer`` by what the layer's index decides: its ``kind``, its
    ``lambda_init`` ``lam0`` (a number, or a traced scalar: a caller that
    compiles one program a KIND and not a layer) and whether it is the
    Mamba-1 layer whose memory is handed on."""
    eps = cfg["norm_eps"]
    carry = dict(carry)
    h = layer_norm(x, p["attn_norm.weight"], p["attn_norm.bias"], eps)
    if kind == "mamba":
        a, m = mamba(h, p, cfg, mm)
        if keeps_memory:
            carry["m"] = m
    elif kind == "gmu":
        a = mm(jax.nn.silu(mm(h, p["attn.w1"])) * carry["m"], p["attn.w2"])
    elif kind == "cross":
        a = diff_attention(h, carry["qpos"], carry["k"], carry["v"], p, lam0,
                           cfg, mm, q_block)
    else:
        k, v = keys_values(h, p, cfg, mm)
        if kind == "full":
            carry["k"], carry["v"] = k, v
            if rows is not None:
                h, x = h[rows], x[rows]
                carry["qpos"], carry["m"] = rows, carry["m"][rows]
        a = diff_attention(h, carry["qpos"], k, v, p, lam0, cfg, mm, q_block,
                           cfg["sliding_window"] if kind == "sliding"
                           else None)
    x = x + a
    g = layer_norm(x, p["ffn_norm.weight"], p["ffn_norm.bias"], eps)
    return x + mm(jax.nn.silu(mm(g, p["ffn.w1"])) * mm(g, p["ffn.w3"]),
                  p["ffn.w2"]), carry


def embed(ids, table):
    return table[ids].astype(jnp.float32)


def logits(x, rows, final_norm, final_bias, table, cfg, mm=mm_highest):
    """Logits ``[len(rows), vocab]`` at rows ``rows`` of the last layer's
    output ``x``: the final LayerNorm, then the embedding transposed."""
    return mm(layer_norm(x[rows], final_norm, final_bias, cfg["norm_eps"]),
              table.T)


def forward(params, ids, cfg, mm=mm_highest, q_block=256):
    """Logits ``[T, vocab]`` of token ids ``[T]`` with every weight in one
    dict (small sizes; a big one goes layer by layer, see harness/): every
    layer on every token."""
    x = embed(ids, params["embed.weight"])
    carry = {"qpos": jnp.arange(ids.shape[0])}
    for l in range(len(cfg["layer_types"])):
        pre = f"layers.{l}."
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x, carry = layer(x, p, l, cfg, carry, mm, q_block)
    return logits(x, jnp.arange(ids.shape[0]), params["final_norm.weight"],
                  params["final_norm.bias"], params["embed.weight"], cfg, mm)
