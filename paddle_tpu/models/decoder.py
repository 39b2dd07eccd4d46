"""A decoder-only LM built from a DESCRIPTION of its block.

``DecoderConfig`` names the kind of each part of the block — norm
(``rms`` | ``layer`` | ``layer_nobias``) and where it stands (``pre`` |
``post`` | ``parallel``), positions (``rope`` | ``rope_gptj`` |
``rope_yarn`` | ``none``; ``position_by_kind`` where layers of one kind
have others), the token mixer (``dense`` | ``sliding`` | ``indexed_sparse``
| ``gated_delta`` | ``latent`` | ``mamba2`` | ``mamba1`` | ``gmu`` |
``cross`` | ``none``; one kind for all layers, or ``layer_types``, one a
layer; ``layer_sources`` where a layer reads an earlier one), FFN (``swiglu`` | ``moe_swiglu``
| ``relu2`` | ``moe_relu2`` | ``none``; the first ``first_dense_layers``
layers a dense swiglu of a width of their own, or ``ffn_types``, one kind
a layer), router (``softmax_topk`` | ``sigmoid_topk`` |
``sigmoid_group_topk``) — with
their widths and what varies within a kind (a dense layer's output gate,
a sliding layer's window, the width of a gated_delta layer's decay, which
of a layer's experts are held here, shared experts and how they are
combined, a scale on the logits); the parts are looked up by kind in
the tables at the bottom of each section, so the next architecture is a
description (and at most a new entry in one table), not a third class tree
beside ``models/gpt.py``. GPT-3's block (learned position table, LayerNorm
with biases, GELU, fused qkv) stays where it is; its programs do not pass
through here.

The block, for ``x`` the residual stream::

    h = norm(x);  q, k, v = h Wq, h Wk, h Wv        (no biases; GQA)
    q, k = per-head RMSNorm (qk_norm), then rotary positions
    indexed_sparse: I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
                    S_t = the index_topk positions of largest I[t, :]
    a_t = softmax over s in S_t (s <= t for dense) of q_t . k_s / sqrt(D)
    x += a Wo                 (attn_output_gate: (a * sigmoid(h Wg)) Wo)
    g = norm(x);  moe_swiglu: p = softmax_f32(g Wr), top-k experts,
                  renormalised; x += sum_e p_e (silu(g W1_e) * g W3_e) W2_e
                  (experts_held: the sum runs over the held e alone;
                  shared_experts: + one dense SwiGLU of g)

The PARALLEL block (``norm_placement`` "parallel": Command A+,
``use_parallel_block``) has ONE norm and feeds it to both parts, the
router included::

    h = LayerNorm(x) = (x - mean) / sqrt(var + eps) * gamma   (layer_nobias:
        float32 statistics, a scale, NO bias)
    x = x + attention(h) + ffn(h)
    sliding: q, k turned by rotary positions in INTERLEAVED pairs (x[2i],
        x[2i + 1]) (``rope_gptj``); query t sees the keys s with
        t - sliding_window < s <= t (its own among them)
    dense beside it (``position_by_kind`` {"dense": "none"}): no positions;
        every s <= t
    sigmoid_topk: p = sigmoid_f32(h Wr) over ALL experts, the top-k of it,
        w_e = p_e / sum of the chosen (no groups, no bias, no factor)
    shared_combine "mean": + (1 / n) sum_s FFN'_s(h), computed as ONE
        SwiGLU n times as wide, times 1 / n (the sum of n is the
        concatenation of their columns)
    logits = logit_scale * norm(x) . Emb^T

A ``sliding`` layer keeps pages of its window alone: its two pools stand in
a page GROUP of their own (``cache_pools``' fifth entry, ``("window",
sliding_window)``), with a page count, an allocator and a page table that
the engine slides (serving/README.md). Three forms of one layer: prefill a
causal BAND (``attend``'s ``band``: a chunk of queries is handed the keys
its band reaches, and turned a chunk at a time), extend over a view of the
window and the new tokens (``pools.window_blocks``), decode the paged
kernel with a ``window`` (``kernels/paged_attention``: the walk starts at
the window's first block).

A ``gated_delta`` layer (linear attention by the gated delta rule,
arXiv:2412.06464) mixes tokens through a recurrent state instead of a
cache of keys: with ``h`` the layer's input,

    q~, k~, v~ = h Wq, h Wk, h Wv;  each channel through a causal depthwise
        convolution of width ``linear_conv_kernel`` over time, then SiLU
    q = q' / |q'| * dk^-1/2,  k = k' / |k'|              (per head, eps 1e-6)
    b = 2 sigmoid(h Wb)  (the 2: ``linear_allow_neg_eigval``)
    g = -exp(A_log) softplus(h Wa + dt_bias),  a = exp(g)       (float32)
    S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T,  o_t = S_t q_t
    y = RMSNorm_dv(o) * silu(h Wg), then Wo
    linear_gate "channel" (Kimi Delta Attention, arXiv:2510.26692):
        g = -exp(A_log) softplus((h Wf_a) Wf_b + dt_bias), one a key channel:
        S_{t-1} diag(a_t) for a_t S_{t-1};  y = RMSNorm_dv(o) * sigmoid((h
        Wg_a) Wg_b)

It keeps per slot the float32 state ``S`` (packed, ``kernels/gated_delta``)
and the last ``linear_conv_kernel - 1`` inputs of the convolution; prefill
and extend run the chunked form from a given state and tail, decode the
recurrent step (``kernels/gated_delta``).

A ``latent`` layer (multi-head latent attention, arXiv:2412.19437 section
2.1; ``H`` heads, ranks ``rq`` / ``rkv``, a head's lanes ``dn`` without
positions + ``dr`` rotary, values ``dv``), with ``h`` the layer's input::

    c_q = RMSNorm(h wq_a)                                  [rq]
    q   = c_q wq_b -> H x (dn | dr);   q_pe = yarn(q_pe)
    [c | k_pe] = h wkv_a                                   [rkv | dr]
    c   = RMSNorm(c);  k_pe = yarn(k_pe)        (ONE k_pe for all heads)
    k_nope = c wk_b,  v = c wv_b                           H x dn, H x dv
    s   = (q_nope . k_nope + q_pe . k_pe) * (dn + dr)^-1/2 * mscale^2
    o   = softmax_f32(s) v -> wo       (causal; ``softmax_scale``)

``rope_yarn`` turns pair i at ``inv_freq = theta^(-2i/dr)`` blended with the
same ``/ factor`` by a linear ramp between the pairs that make
``beta_fast`` and ``beta_slow`` turns over the original context
(``yarn_inv_freq``); ``mscale = 0.1 ln(factor) + 1``. What the layer keeps
a token is ONE row ``[c | k_pe | idle lanes]`` (``latent_pool_width``), keys
and values the same bytes, in a pool of its own. Two forms of one layer:
prefill and extend EXPAND ``k_nope`` and ``v`` from the cached rows
(``latent_attend``: on the TPU a group of heads at a time into the Pallas
kernel ``kernels/latent_attention.latent_flash``, elsewhere a block of
keys at a time under a float32 online softmax in
``jax.numpy``); decode never does: ``q~_h = q_nope_h wk_b_h^T``,
``s_h = ([q~_h | q_pe_h] . [c | k_pe]) * scale``, ``o_h = (softmax(s_h) c)
wv_b_h`` (the absorbed form; on the TPU the Pallas kernel
``kernels/latent_attention.latent_paged_decode``, which reads a row once
for scores and values).

``sigmoid_group_topk`` (DeepSeek-V3's ``noaux_tc``), float32: ``s =
sigmoid(g Wr)`` over all experts; the CHOICE on ``s + bias``: a group's
score is the sum of its two largest, the ``topk_group`` best of ``n_group``
groups are kept, the top-k taken inside them; the WEIGHTS are ``s`` of the
chosen over their sum, times ``routed_scaling_factor``.

A layer may have ONE part (Nemotron-H, arXiv:2504.03624: the published
``hybrid_override_pattern`` ``MEMEM*EMEMEM*...`` gives each layer a Mamba-2
mixer ``M``, an expert layer ``E`` or an attention layer ``*`` ALONE):
``layer_types[l]`` or ``ffn_types[l]`` is ``"none"``, and the layer is ``x
= x + part(norm(x))`` with ONE norm. A part that is absent declares no
parameters and no norm and is not traced; such a layer hands back no pool
entries (an ``E`` layer reads no cache) or zero FFN statistics (an ``M``
layer routes nothing).

A ``mamba2`` layer (Mamba-2, arXiv:2405.21060; ``H`` heads of width ``P``,
``G`` groups, state ``N``, convolution ``K``), with ``h`` the layer's
input::

    [z | xBC | dt] = h W_in                widths H P | H P + 2 G N | H
    xBC = silu(conv(xBC) + b_conv)         causal, depthwise, width K, over
                                           ALL H P + 2 G N channels
    x_h [H x P], B_g [G x N], C_g [G x N] = split(xBC);  head h reads group
                                           h // (H / G)
    dt_h = softplus(dt_h + dt_bias_h)      float32; no clamp
    a_h  = exp(dt_h A_h),  A_h = -exp(A_log_h)     ONE decay a head
    S_h  <- a_h S_h + dt_h x_h (x) B_g(h)          S_h in R^{P x N}, float32
    y_h  = S_h C_g(h) + D_h x_h            (the state AFTER this token)
    y    = RMSNorm_groups(y * silu(z))     the gate FIRST, then a norm over
                                           each of G groups, weight [H P]
    out  = y W_out

It keeps per slot the float32 state ``S`` (packed as a gated_delta layer's,
``kernels/gated_delta.pack_state``) and the last ``K - 1`` inputs of the
convolution, and speaks ``gated_delta``'s cache protocol to the letter;
prefill and extend run the chunked form in matrix products from a given
state and tail, decode the recurrent step (``kernels/mamba2``).

``relu2`` / ``moe_relu2`` (Nemotron-H's ``mlp_hidden_act``): an UNGATED FFN
of two matrices, ``FFN(h) = relu(h W_up)^2 W_down``; the routed body
(``moe_routed``) then runs two grouped matmuls, not three, and a shared
expert may have a width of its own (``shared_intermediate_size``). With
``n_group`` 1 ``sigmoid_group_topk`` is a plain top-k of ``s + bias``.

A DECODER-HYBRID-DECODER (SambaY, arXiv:2507.06607; Phi-4-mini-flash) is
three more kinds, a flag and one per-layer field. ``mamba1`` (Mamba-1,
arXiv:2312.00752; inner width ``E``, state ``N``, step rank ``R``,
convolution ``K``), with ``h`` the layer's input::

    [x | z] = h W_in                              widths E | E
    x = silu(conv(x) + b_conv)                    causal, depthwise, width K
    [delta | B | C] = x W_x                       widths R | N | N
    dt = softplus(delta W_dt + dt_bias)           float32, a step a CHANNEL
    S[n, e] <- exp(dt[e] A[n, e]) S[n, e] + dt[e] x[e] B[n],  A = -exp(A_log)
                                  ONE decay a channel AND state lane: S in
                                  R^{N x E} float32, kept N-major
    y[e] = sum_n S[n, e] C[n] + D[e] x[e]         (the state AFTER this token)
    out = (y * silu(z)) W_out;   m = y            (the memory: BEFORE the gate)

It speaks ``gated_delta``'s cache protocol as ``mamba2`` does; both its forms
walk the tokens (``kernels/mamba1``: no chunked form in matrix products).
``gmu`` (a gated memory unit): ``out = (silu(h W1) * m) W2`` with ``m`` the
memory of the SAME token that the ``mamba1`` layer ``layer_sources[l]`` left;
no state, no cache. ``differential`` (arXiv:2410.05258) on every ``dense`` /
``sliding`` / ``cross`` layer: query heads 2p, 2p + 1 form pair p, K heads
2r, 2r + 1 K pair r with ``v_r = [v_2r | v_2r+1]``; pair p reads pair ``r = p
// (H_q / H_kv)``::

    a_{p,j} = softmax_s(q_{p,j} . k_{r,j,s} D^-1/2)   over the keys the layer
                                  sees (sliding: t - window < s <= t)
    o_p = sum_s (a_{p,1,s} - lambda a_{p,2,s}) v_{r,s}
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 l)           l the 0-based layer index
    o_p = RMSNorm_2D(o_p; gamma, eps) (1 - lambda_init);  out = [o_p] Wo + bo

(``attn_bias``: q, k, v and the output carry a bias.) The pools hold the
PAIRS (``[pages, H_kv / 2, page, 2 D]``), and the paged attends read them as
they are with the queries widened over a pair's lanes
(``kernels/paged_attention.diff_widen``). ``cross``: a differential layer
with its own queries and NO K/V projection, over the K and V of the ``dense``
layer ``layer_sources[l]``; it declares no pool: ``cache_pools()`` names that
layer's pool once, with every layer that READS it as a sixth entry, and
``_forward`` carries the written pool (and ``m``) from layer to layer. Where
every layer behind that dense layer is a ``gmu`` or a ``cross`` layer
(``cut_layer``), an ADMISSION's prefill or extend (one that is told
``lengths``) runs the layers before it and its K/V projection on all tokens,
its queries and every layer behind it on the LAST REAL token alone: nothing
else of them is ever read (they write no cache), an extend's matmul work is
halved and no ``[T, V]`` logits exist. A forward that is told no ``lengths``
(``forward``, a test's) runs every layer on every token.

It speaks the serving engine's whole protocol (serving/README.md):
``cache_pools()`` declares the paged pools of the layers that keep keys (K
and V token-major, one
"head" of ``H_kv * D``, so that a token's K is one run of bytes for the
sparse read, or head-major ``[pages, H_kv, page, D]`` for the paged-decode
kernel, ``kv_layout``; the indexer's keys, in whole 128-lane rows; a latent
layer's one row a token),
``state_pools()`` the slot-indexed state of the layers that keep a
recurrence (``gated_delta``, ``mamba2``, ``mamba1``); ``prefill_with_cache`` /
``extend_step`` / ``decode_step`` are pure functions of (parameters, pools,
page table).
What they do to a pool is below this module, in ``kernels/``: the write and
the view through a table (``pools.py``), the paged attends, each kernel with
its reference (``paged_attention.py``, ``latent_attention.py``,
``sparse_attention.py``, ``gated_delta.py``, ``mamba2.py``), and
``tier.py``, which says which of the two a trace bakes in. Nothing here
imports ``serving``.
ONE attention routine (``attend``) serves all three: queries at
``start .. start + T - 1`` against views of the pools, in chunks of queries
so that no ``[T, L]`` float32 array larger than a chunk exists; prefill is
the case ``start = 0`` on the keys just computed, decode the case ``T = 1``
(on the TPU its read of the selected rows is the Pallas kernel
``kernels/sparse_attention.sparse_paged_decode``). A head-major model's
extend and decode go through the paged attends instead
(``paged_extend_attend`` / ``paged_decode_attend``: kernel or reference, as
``kernels/tier`` says). The expert layer is
drop-free (``kernels/grouped_matmul``): rows sorted by expert, a grouped
matmul over the sorted rows, routing weights applied in float32 at the
combine.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.tensor import Parameter, Tensor
from ..kernels import latent_attention as _latent
from ..kernels import pools as _pools, tier as _tier
from ..kernels.paged_attention import (diff_combine, diff_decode_attend,
                                       diff_extend_attend, diff_widen,
                                       paged_decode_attend,
                                       paged_extend_attend)
from ..nn.layer.layers import Layer

_NEG_INF = -1e30


@dataclass
class DecoderConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16            # its own width, not hidden / heads
    max_context: int = 128        # longest sequence the positions serve
    norm: str = "rms"             # rms | layer | layer_nobias
    norm_eps: float = 1e-6
    # pre: x + f(norm(x)); post: x + norm(f(x)); parallel: ONE norm,
    # x + attn(norm(x)) + ffn(norm(x))
    norm_placement: str = "pre"
    position: str = "rope"        # rope | rope_gptj | rope_yarn | none
    # positions of the layers of some kinds, where they differ from
    # ``position``: {"dense": "none"} for full layers without positions
    # beside rotary ``sliding`` ones
    position_by_kind: Optional[dict] = None
    rope_theta: float = 1e7
    # rope_yarn's parameters, under the published names: factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim (``yarn_inv_freq``, ``softmax_scale``)
    rope_scaling: Optional[dict] = None
    # RMSNorm on q and k: True / "head" over each head, "full" over all of a
    # token's heads together, False none
    qk_norm: Union[bool, str] = True
    # dense | sliding | indexed_sparse | gated_delta | latent
    attention: str = "indexed_sparse"
    # a ``sliding`` layer's window: query t sees the keys s with
    # t - sliding_window < s <= t (its own among them), and keeps pages of
    # that many tokens only (a page group of its own: ``cache_pools``)
    sliding_window: Optional[int] = None
    # one kind a layer (keys of ATTENTIONS; "none": a layer WITHOUT a token
    # mixer, an FFN alone); None: ``attention`` for all
    layer_types: Optional[Tuple[str, ...]] = None
    # how a dense layer's K and V pages lie: "token" [pages, 1, page,
    # H_kv * D], "head" [pages, H_kv, page, D] (what kernels/paged_attention
    # reads: its decode goes through its paged_decode_attend)
    kv_layout: str = "token"
    # a dense layer's output gated element-wise by sigmoid(h Wg) before Wo
    attn_output_gate: bool = False
    # differential attention (arXiv:2410.05258) in every dense / sliding /
    # cross layer: query heads 2p, 2p + 1 form a pair that reads K/V pair
    # p // (num_heads / num_kv_heads) and gives softmax(q1 k1) - lambda
    # softmax(q2 k2) over the pair's two value heads side by side, then an
    # RMSNorm over those 2 D lanes; the pools hold PAIRS (num_kv_heads / 2
    # heads of 2 D lanes). ``attn_bias``: q, k, v and the output projection
    # carry a bias
    differential: bool = False
    attn_bias: bool = False
    # per layer, the EARLIER layer whose output a layer reads besides its
    # own input: a "gmu" layer the scan output (memory) of a "mamba1" layer,
    # a "cross" layer the K/V pool of a "dense" layer (it has no K/V
    # projection and declares no pool); None for every other layer
    layer_sources: Optional[Tuple[Optional[int], ...]] = None
    index_heads: int = 4
    index_head_dim: int = 8
    index_topk: int = 16
    # a gated_delta layer's widths (key and value heads are as many)
    linear_heads: int = 4
    linear_key_head_dim: int = 8
    linear_value_head_dim: int = 16
    linear_conv_kernel: int = 4
    linear_allow_neg_eigval: bool = True
    # the decay's width: "head" one value a head (arXiv:2412.06464, a dense
    # Wa, output gate silu(h Wg)); "channel" one a key channel (Kimi Delta
    # Attention, arXiv:2510.26692: decay and output gate each through a
    # low-rank pair of rank ``linear_gate_rank``, the output gate a sigmoid)
    linear_gate: str = "head"
    linear_gate_rank: int = 8
    gdn_chunk: int = 64           # tokens per chunk of the chunked form
    # a mamba2 layer's widths (arXiv:2405.21060): heads of ``ssm_head_dim``
    # (their product is the inner width), ``ssm_groups`` groups that share
    # B and C, the state's width, the convolution's, tokens a chunk
    ssm_heads: int = 4
    ssm_head_dim: int = 8
    ssm_groups: int = 2
    ssm_state: int = 16
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # a mamba1 layer's widths (arXiv:2312.00752; its state's width and
    # convolution are ``ssm_state`` / ``ssm_conv_kernel``): the inner width
    # (None: 2 x hidden) and the rank of the step's projection (None:
    # ceil(hidden / 16))
    ssm1_inner: Optional[int] = None
    ssm1_dt_rank: Optional[int] = None
    # a latent layer's widths (arXiv:2412.19437 section 2.1): the ranks of
    # the query's and the keys-and-values' latents, a head's query/key lanes
    # without and with positions, a head's value lanes
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    ffn: str = "moe_swiglu"       # swiglu | moe_swiglu | relu2 | moe_relu2
    intermediate_size: int = 128  # a dense FFN's width; an expert's in moe_*
    # the FFN's kind, one a layer (keys of FFNS; "none": a layer WITHOUT an
    # FFN, a token mixer alone); None: the rule below
    ffn_types: Optional[Tuple[str, ...]] = None
    # the FFN by layer: the first ``first_dense_layers`` layers are a dense
    # swiglu of width ``dense_intermediate_size`` whatever ``ffn`` says
    first_dense_layers: int = 0
    dense_intermediate_size: int = 256
    # softmax_topk | sigmoid_topk | sigmoid_group_topk
    router: str = "softmax_topk"
    # sigmoid_group_topk: the experts stand in ``n_group`` groups of which a
    # token keeps ``topk_group``; the chosen weights times the factor
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    num_experts: int = 8
    experts_per_token: int = 2
    norm_topk_prob: bool = True
    # (how many, from which index) of the ``num_experts`` experts this
    # program HOLDS, where a layer is shared by several chips: the router
    # keeps its width, the rows routed to the others are left out (no
    # exchange, nothing standing in for it). None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    # shared experts: one dense FFN (of the routed experts' activation) of
    # width ``intermediate_size`` x this beside the routed ones, ungated, on
    # every token; ``shared_intermediate_size`` where it has a width of its
    # own
    shared_experts: int = 0
    shared_intermediate_size: Optional[int] = None
    # how the shared experts' outputs join the routed sum: "sum", or "mean"
    # (their mean: the one wide SwiGLU times 1 / shared_experts)
    shared_combine: str = "sum"
    tie_word_embeddings: bool = False
    logit_scale: float = 1.0      # the head's logits times this
    initializer_range: float = 0.02
    dtype: str = "float32"
    # "normal": N(0, initializer_range), norm scales 1. "zeros": nothing is
    # drawn (a caller that installs its own weights, at a size whose float32
    # initial values would not fit beside them).
    init: str = "normal"
    query_chunk: int = 128        # queries per chunk of ``attend``
    # a dense layer's flash prefill one KEY/VALUE head at a time (its group
    # of query heads beside it): what stands in memory is one group's keys,
    # values and row statistics, not all heads' (at 128 query heads over 8
    # and 18k tokens: 0.3 GB where the whole is 2.3)
    flash_by_kv_head: bool = False

    def __post_init__(self):
        if self.qk_norm is True:
            self.qk_norm = "head"
        for field, table in (("norm", NORMS), ("position", POSITIONS),
                             ("attention", ATTENTIONS), ("ffn", FFNS),
                             ("router", ROUTERS),
                             ("norm_placement", NORM_PLACEMENTS),
                             ("kv_layout", KV_LAYOUTS),
                             ("qk_norm", QK_NORMS),
                             ("linear_gate", LINEAR_GATES),
                             ("shared_combine", SHARED_COMBINES)):
            if getattr(self, field) not in table:
                raise ValueError(f"{field} {getattr(self, field)!r}; "
                                 f"want one of {sorted(table, key=str)}")
        for field, table in (("layer_types", ATTENTIONS),
                             ("ffn_types", FFNS)):
            types = getattr(self, field)
            if types is None:
                continue
            setattr(self, field, tuple(types))
            unknown = sorted(set(types) - set(table))
            if unknown:
                raise ValueError(f"{field} has {unknown}; want entries "
                                 f"of {sorted(table)}")
            if len(types) != self.num_layers:
                raise ValueError(
                    f"{field} names {len(types)} layers, "
                    f"num_layers is {self.num_layers}")
        if any(k == "none" == f for k, (f, _) in zip(self.kinds, self.ffns)):
            raise ValueError("a layer of no mixer and no FFN")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        for kind, pos in (self.position_by_kind or {}).items():
            if kind not in ATTENTIONS or pos not in POSITIONS:
                raise ValueError(f"position_by_kind {kind!r}: {pos!r}")
        if "sliding" in self.kinds and not (
                self.sliding_window and self.sliding_window >= 1
                and self.kv_layout == "head"):
            raise ValueError("a sliding layer wants sliding_window >= 1 and "
                             "kv_layout 'head' (the paged-decode kernel's)")
        if self.position == "rope_yarn" and not (
                self.rope_scaling and set(self.kinds) == {"latent"}):
            # the other mixers' decode and flash paths scale by width^-1/2
            raise ValueError("position rope_yarn wants rope_scaling, and "
                             "serves latent layers alone")
        if self.num_experts % self.n_group or self.topk_group > self.n_group:
            raise ValueError(
                f"n_group {self.n_group} / topk_group {self.topk_group}: want "
                f"groups that divide {self.num_experts} experts")
        if not 0 <= self.first_dense_layers <= self.num_layers:
            raise ValueError(f"first_dense_layers {self.first_dense_layers} "
                             f"of {self.num_layers} layers")
        self._check_sources()
        if self.experts_held is not None:
            n, first = self.experts_held = tuple(self.experts_held)
            if not (n >= 1 and first >= 0 and first + n <= self.num_experts):
                raise ValueError(
                    f"experts_held {self.experts_held}: want (how many, "
                    f"from which index) within {self.num_experts} experts")

    def _check_sources(self):
        """``layer_sources`` against the kinds: a gmu layer names a mamba1
        layer before it, a cross layer a dense one (all cross layers the
        same), no other layer names any; what ``differential`` asks of the
        attention layers."""
        kinds, L = self.kinds, self.num_layers
        if self.layer_sources is not None:
            self.layer_sources = tuple(self.layer_sources)
        src = self.sources
        if len(src) != L:
            raise ValueError(f"layer_sources names {len(src)} layers, "
                             f"num_layers is {L}")
        wants = {"gmu": "mamba1", "cross": "dense"}
        for l, (kind, k) in enumerate(zip(kinds, src)):
            if kind not in wants:
                if k is not None:
                    raise ValueError(f"layer {l} ({kind}) reads no other "
                                     f"layer, layer_sources names {k}")
            elif not (isinstance(k, int) and 0 <= k < l
                      and kinds[k] == wants[kind]):
                raise ValueError(
                    f"layer {l} ({kind}) wants an EARLIER {wants[kind]} "
                    f"layer in layer_sources, got {k!r}")
        cross = {k for kind, k in zip(kinds, src) if kind == "cross"}
        if len(cross) > 1:
            raise ValueError(f"cross layers read ONE layer's pool, not "
                             f"{sorted(cross)}")
        attn = {"dense", "sliding", "cross"} & set(kinds)
        if (cross or self.attn_bias) and not self.differential:
            raise ValueError("cross layers and attn_bias are the "
                             "differential layers'")
        if cross and self.norm_placement != "pre":
            raise ValueError("cross layers want norm_placement 'pre'")
        if self.differential and attn and not (
                self.kv_layout == "head" and self.num_kv_heads % 2 == 0
                and not self.qk_norm and not self.attn_output_gate
                and "indexed_sparse" not in kinds
                and all(_position_of(self, k) is POSITIONS["none"]
                        for k in attn)):
            raise ValueError(
                "differential attention wants kv_layout 'head', an even "
                "num_kv_heads, no qk_norm, no output gate, no positions "
                "and no indexed_sparse layer")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The token mixer's kind, one a layer."""
        return self.layer_types or (self.attention,) * self.num_layers

    @property
    def sources(self) -> Tuple[Optional[int], ...]:
        """The earlier layer each layer reads (``layer_sources``), None
        where it reads none."""
        return self.layer_sources or (None,) * self.num_layers

    @property
    def cut_layer(self) -> Optional[int]:
        """The layer from whose QUERIES on an admission's prefill or extend
        runs on the last real token alone: the dense layer the cross layers
        read, where every layer behind it keeps neither pool nor state (gmu
        and cross layers: nothing else of them is ever read). None in a
        model without cross layers."""
        cross = [k for kind, k in zip(self.kinds, self.sources)
                 if kind == "cross"]
        if not cross or set(self.kinds[cross[0] + 1:]) - {"gmu", "cross"}:
            return None
        return cross[0]

    @property
    def ffns(self) -> Tuple[Tuple[str, int], ...]:
        """The FFN's (kind, width), one a layer."""
        if self.ffn_types is not None:
            return tuple((kind, self.intermediate_size)
                         for kind in self.ffn_types)
        d = self.first_dense_layers
        return (("swiglu", self.dense_intermediate_size),) * d \
            + ((self.ffn, self.intermediate_size),) * (self.num_layers - d)


# ------------------------------------------------------------------ norms

def rms_norm(x, p, pre, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p[pre + ".weight"].astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, p, pre, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(((xf - mu) ** 2).mean(-1, keepdims=True) + eps)
    y = y * p[pre + ".weight"].astype(jnp.float32)
    if pre + ".bias" in p:
        y = y + p[pre + ".bias"].astype(jnp.float32)
    return y.astype(x.dtype)


#: kind -> (fn, whether it has a bias)
NORMS = {"rms": (rms_norm, False), "layer": (layer_norm, True),
         "layer_nobias": (layer_norm, False)}
NORM_PLACEMENTS = ("pre", "post", "parallel")
SHARED_COMBINES = ("sum", "mean")
QK_NORMS = (False, "head", "full")
KV_LAYOUTS = ("token", "head")
LINEAR_GATES = ("head", "channel")


def _norm_shapes(cfg, pre, width):
    out = {pre + ".weight": (width,)}
    if NORMS[cfg.norm][1]:
        out[pre + ".bias"] = (width,)
    return out


def _norm(cfg, x, p, pre):
    return NORMS[cfg.norm][0](x, p, pre, cfg.norm_eps)


# -------------------------------------------------------------- positions

def _rotate(x, pos, inv):
    """``x [B, T, heads, D]`` turned by the angles ``pos [B, T]`` x ``inv
    [D/2]``: the half-split form (pair i is (x[i], x[i + D/2])), angles in
    float32."""
    D = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, :, None, None] * inv     # [B,T,1,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :D // 2].astype(jnp.float32), x[..., D // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rope(x, pos, theta):
    """Rotary positions on ``x [B, T, heads, D]`` at ``pos [B, T]``."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) * 2.0 / D)
    return _rotate(x, pos, inv)


def rope_gptj(x, pos, theta):
    """Rotary positions in INTERLEAVED pairs (pair i is (x[2i], x[2i + 1]),
    at ``theta^(-2i/D)``) on ``x [B, T, heads, D]`` at ``pos [B, T]``: ``x *
    cos + swap(x) * sin`` with ``swap(x)[2i] = -x[2i + 1]``, ``swap(x)[2i +
    1] = x[2i]``, made of two rotations along the lanes (no ``[.., D/2, 2]``
    reshape, which the chip would re-lay); angles in float32."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) * 2.0 / D)
    ang = jnp.repeat(pos.astype(jnp.float32)[:, :, None, None] * inv, 2,
                     axis=-1)                                  # [B,T,1,D]
    xf = x.astype(jnp.float32)
    even = (jnp.arange(D) % 2) == 0
    swap = jnp.where(even, -jnp.roll(xf, -1, axis=-1), jnp.roll(xf, 1, axis=-1))
    return (xf * jnp.cos(ang) + swap * jnp.sin(ang)).astype(x.dtype)


def yarn_inv_freq(D: int, theta: float, scaling: dict) -> np.ndarray:
    """YaRN's frequencies of the ``D / 2`` rotary pairs (arXiv:2309.00071, as
    DeepSeek-V3's rotary class computes them): pair i keeps its frequency
    ``theta^(-2i/D)`` below the ramp, turns ``factor`` times slower above
    it, and is blended linearly between; the ramp rises from
    ``floor(d(beta_fast))`` to ``ceil(d(beta_slow))``, ``d(n) = D ln(original
    / (2 pi n)) / (2 ln theta)`` the pair that makes n turns over the
    original context. float32."""
    half = np.arange(0, D, 2, dtype=np.float64) / D
    extra = theta ** -half
    inter = extra / scaling["factor"]
    orig = scaling["original_max_position_embeddings"]
    at = lambda n: D * np.log(orig / (n * 2 * np.pi)) / (2 * np.log(theta))
    low = max(np.floor(at(scaling["beta_fast"])), 0)
    high = min(np.ceil(at(scaling["beta_slow"])), D - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(D // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def rope_yarn(cfg, x, pos):
    """YaRN rotary positions; cos and sin carry ``mscale / mscale_all_dim``
    (1 where the two are equal)."""
    sc = cfg.rope_scaling
    inv = jnp.asarray(yarn_inv_freq(x.shape[-1], cfg.rope_theta, sc))
    m = _yarn_mscale(sc["factor"], sc.get("mscale", 1)) \
        / _yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0))
    y = _rotate(x.astype(jnp.float32), pos, inv)
    return (y if m == 1.0 else y * jnp.float32(m)).astype(x.dtype)


def softmax_scale(cfg, width: int) -> float:
    """A latent layer's softmax scale for queries and keys ``width`` wide:
    ``width^-1/2``, under rope_yarn times ``mscale(mscale_all_dim)^2``."""
    scale = float(width) ** -0.5
    if cfg.position == "rope_yarn":
        sc = cfg.rope_scaling
        scale *= _yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0)) ** 2
    return scale


#: kind -> positions on ``x [B, T, heads, D]`` at ``pos [B, T]``
POSITIONS = {"rope": lambda cfg, x, pos: rope(x, pos, cfg.rope_theta),
             "rope_gptj": lambda cfg, x, pos: rope_gptj(x, pos, cfg.rope_theta),
             "rope_yarn": rope_yarn,
             "none": lambda cfg, x, pos: x}


def _position_of(cfg, kind: str):
    """The positions of a layer of ``kind``."""
    return POSITIONS[(cfg.position_by_kind or {}).get(kind, cfg.position)]


# -------------------------------------------------------------- attention

def _mm(x, w):
    # in the operands' type: the matrix unit accumulates a bfloat16 product
    # in float32 either way, and an explicit float32 result would stand in
    # memory whole (0.57 GB for a 34k-token prompt's q) before its cast
    return jnp.dot(x, w)


def _attn_shapes(cfg, pre, sparse, cross=False):
    H, D = cfg.hidden_size, cfg.head_dim
    s = {pre + ".wq": (H, cfg.num_heads * D),
         pre + ".wk": (H, cfg.num_kv_heads * D),
         pre + ".wv": (H, cfg.num_kv_heads * D),
         pre + ".wo": (cfg.num_heads * D, H)}
    if cfg.attn_bias:
        s.update({pre + ".bq": (cfg.num_heads * D,),
                  pre + ".bk": (cfg.num_kv_heads * D,),
                  pre + ".bv": (cfg.num_kv_heads * D,), pre + ".bo": (H,)})
    if cross:       # a cross layer has no K/V projection
        for leaf in (".wk", ".wv", ".bk", ".bv"):
            s.pop(pre + leaf, None)
    if cfg.differential:
        s.update({pre + f".lambda_{a}{i}": (D,) for a in "qk" for i in "12"})
        s[pre + ".o_norm.weight"] = (2 * D,)
    if cfg.qk_norm:
        full = cfg.qk_norm == "full"
        s[pre + ".q_norm.weight"] = (cfg.num_heads * D if full else D,)
        s[pre + ".k_norm.weight"] = (cfg.num_kv_heads * D if full else D,)
    if cfg.attn_output_gate:
        s[pre + ".wg"] = (H, cfg.num_heads * D)
    if sparse:
        Hi, Di = cfg.index_heads, cfg.index_head_dim
        s.update({pre + ".index.wq": (H, Hi * Di),
                  pre + ".index.wk": (H, Di),
                  pre + ".index.ww": (H, Hi),
                  pre + ".index.k_norm.weight": (Di,),
                  pre + ".index.k_norm.bias": (Di,)})
    return s


def attend(cfg, q, k_view, v_view, qpos, index=None, window=None,
           first=None, band=False, turn=None, scale=None):
    """Queries ``q [B, T, Hq, D]`` at positions ``qpos [B, T]`` against key
    and value views ``[B, L, Hkv, D]`` (view position = sequence position,
    or ``first[b]`` + view position where ``first [B]`` is given: a view of
    a slot's window), ``[B, T, Hq, D]`` out. ``index`` (indexed_sparse) is
    ``(qi [B, T, Hi, Di], w [B, T, Hi] float32, ki_view [B, L, Di])``: a
    query attends to the ``index_topk`` positions ``s <= qpos`` of largest
    indexer score; without it, to every ``s <= qpos``, with ``window`` to
    those with ``qpos - window < s`` alone. ``band`` (a prefill under a
    window: the views are the sequence itself) hands a chunk of queries
    only the keys its band can reach, so the keys outside cost nothing;
    ``turn(q chunk, its positions)`` gives the queries their positions a
    chunk at a time (a 18k-token prompt's 128 query heads turned whole, in
    float32, stood in memory three times over: 3.4 GB).
    Computed in chunks of ``cfg.query_chunk`` queries. Numerics as
    ``kernels.paged_attention.extend_attend``: q pre-scaled in its own dtype,
    float32 scores, -1e30 mask, float32 softmax."""
    from ..kernels.sparse_attention import topk_mask

    B, T, Hq, D = q.shape
    L, Hkv = k_view.shape[1], k_view.shape[2]
    rep = Hq // Hkv
    kpos = jnp.arange(L, dtype=jnp.int32)
    scale = jnp.asarray(1.0 / np.sqrt(D) if scale is None else scale, q.dtype)
    qs = q * scale if turn is None else q

    def chunk(args, k_view=k_view, v_view=v_view, first=first):
        qc, pc, ic = args           # [B, C, Hq, D], [B, C], index
        C = qc.shape[1]
        if turn is not None:
            qc = turn(qc, pc) * scale
        L = k_view.shape[1]
        if window is None:
            valid = kpos[None, None, :] <= pc[:, :, None]         # [B, C, L]
        else:
            kp = jnp.arange(L, dtype=jnp.int32)[None, None, :]
            if first is not None:
                kp = kp + jnp.reshape(first, (-1, 1, 1))
            valid = (kp <= pc[:, :, None]) & (kp > pc[:, :, None] - window)
        if index is not None:
            qi, w = ic
            s = jnp.einsum("bthd,bld->bthl", qi, index[2],
                           preferred_element_type=jnp.float32)
            score = jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=2)
            valid = topk_mask(score, valid, cfg.index_topk)
        # a KV group's ``rep`` query heads stand side by side as rows of one
        # matmul against the group's keys (K and V are read at their stored
        # width); (rep, C) stay the two minor row dims, so nothing is padded
        qg = qc.reshape(B, C, Hkv, rep, D).transpose(0, 2, 3, 1, 4) \
            .reshape(B, Hkv, rep * C, D)
        s = jnp.einsum("bgqd,blgd->bgql", qg, k_view,
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid[:, None, None], s.reshape(B, Hkv, rep, C, L),
                      _NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(v_view.dtype)
        o = jnp.einsum("bgql,blgd->bgqd", probs.reshape(B, Hkv, rep * C, L),
                       v_view)
        return o.reshape(B, Hkv, rep, C, D).transpose(0, 3, 1, 2, 4) \
            .reshape(B, C, Hq, D)

    C = cfg.query_chunk if T % cfg.query_chunk == 0 else T
    idx = None if index is None else (index[0], index[1])
    if C == T:
        return chunk((qs, qpos, idx))
    # a band's chunk reaches back window - 1 keys from its first query
    Lb = min(L, -(-(C + window) // 128) * 128) if band else L

    # chunk by chunk, sliced out of (and written back into) the whole
    # arrays where they lie: stacked per-chunk copies of q and of the output
    # would each be another 0.3 GB at a 34k-token prompt
    def body(i, out):
        cut = lambda a: lax.dynamic_slice_in_dim(a, i * C, C, axis=1)
        args = (cut(qs), cut(qpos),
                None if idx is None else tuple(map(cut, idx)))
        if Lb < L:      # the keys [k0, k0 + Lb) hold the chunk's whole band
            k0 = jnp.clip(i * C + C - Lb, 0, L - Lb)
            keys = lambda a: lax.dynamic_slice_in_dim(a, k0, Lb, axis=1)
            o = chunk(args, keys(k_view), keys(v_view), k0)
        else:
            o = chunk(args)
        return lax.dynamic_update_slice_in_dim(out, o, i * C, axis=1)

    return lax.fori_loop(0, T // C, body, jnp.zeros((B, T, Hq, D), q.dtype))


def index_pool_width(cfg) -> int:
    """Lanes a token's indexer key takes in its pool: ``index_head_dim``
    rounded up to whole 128-lane rows. At 64 wide the chip keeps a
    ``[pages, 1, 16, 64]`` pool pages-minor at rest (no half-empty lanes)
    and every program that touches it re-lays the WHOLE pool in and out,
    every layer, every step (on the chip: ``copy_bf16_19201_1_16_64_``,
    0.95 s of a 20 s window, PERF.md section 6, PR 26); a 128-lane row is
    stored as declared and costs 64 idle lanes a token."""
    return -(-cfg.index_head_dim // 128) * 128


def _indexer(cfg, p, pre, h, pos):
    """(qi [B, T, Hi, Di], w [B, T, Hi] float32, ki [B, T, Di]) of the
    normed hidden state ``h``: LayerNorm on the shared key, rotary positions
    on both, the scales ``Di^-0.5`` (of the dot) and ``Hi^-0.5`` folded into
    the head weights."""
    B, T, _ = h.shape
    Hi, Di = cfg.index_heads, cfg.index_head_dim
    qi = _mm(h, p[pre + ".wq"]).reshape(B, T, Hi, Di)
    ki = layer_norm(_mm(h, p[pre + ".wk"]), p, pre + ".k_norm", cfg.norm_eps)
    qi = rope(qi, pos, cfg.rope_theta)
    ki = rope(ki[:, :, None, :], pos, cfg.rope_theta)[:, :, 0]
    w = jnp.dot(h, p[pre + ".ww"], preferred_element_type=jnp.float32)
    return qi, w * jnp.float32(Di ** -0.5 * Hi ** -0.5), ki


def attention(cfg, p, pre, h, start, cache=None, flash_ok=False,
              lengths=None, cuts=None, sparse=False, kind="dense", layer=None,
              carry=None):
    """The attention part of a block over ``h [B, T, hidden]`` whose
    tokens sit at ``start[b] .. start[b] + T - 1``. A layer of ``kind``
    "sliding" sees the last ``cfg.sliding_window`` keys alone: its prefill
    is a causal band (``attend``'s), its extend reads a view of the window
    and the new tokens (not of the whole table; ``paged_extend_attend``'s
    ``window``), its decode walks the window's pages
    (``paged_decode_attend``'s ``window``). Without ``cache``
    (prefill) the keys are the ones just computed; with ``cache`` (the
    layer's pools and the page table) they are written into the pools first
    and read back through the table. Returns (out [B, T, hidden], new):
    ``new`` the per-pool entries (``[B, 1, T, width]`` each, or ``[B, H_kv,
    T, D]`` where ``kv_layout`` is "head") without a cache, the updated
    pools with one. ``lengths`` and ``cuts`` are not its concern: a padded
    token's keys lie behind every real query, and its keys are a function of
    position."""
    if cfg.differential:
        return differential_attention(cfg, p, pre, h, start, cache, kind,
                                      layer, carry)
    B, T, _ = h.shape
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    window = cfg.sliding_window if kind == "sliding" else None

    def heads(w, n, norm=None):
        x = _mm(h, p[pre + w])
        if norm and cfg.qk_norm == "full":   # over all of a token's heads
            x = rms_norm(x, p, pre + norm, cfg.norm_eps)
        return x.reshape(B, T, n, D)

    q, k = heads(".wq", Hq, ".q_norm"), heads(".wk", Hkv, ".k_norm")
    v = heads(".wv", Hkv)
    if cfg.qk_norm == "head":
        q = rms_norm(q, p, pre + ".q_norm", cfg.norm_eps)
        k = rms_norm(k, p, pre + ".k_norm", cfg.norm_eps)
    turn = _position_of(cfg, kind)
    lazy = cache is None and window is not None   # q a chunk at a time
    q, k = q if lazy else turn(cfg, q, pos), turn(cfg, k, pos)
    index = _indexer(cfg, p, pre + ".index", h, pos) if sparse else None
    head_major = cfg.kv_layout == "head"
    if head_major:
        fresh = [k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)]
    else:
        fresh = [k.reshape(B, 1, T, Hkv * D), v.reshape(B, 1, T, Hkv * D)]
    Di = cfg.index_head_dim
    if sparse:
        fresh.append(jnp.pad(index[2][:, None], (
            (0, 0), (0, 0), (0, 0), (0, index_pool_width(cfg) - Di))))

    def out(o):
        o = o.reshape(B, T, Hq * D)
        if cfg.attn_output_gate:
            gate = jax.nn.sigmoid(_mm(h, p[pre + ".wg"]).astype(jnp.float32))
            o = (o.astype(jnp.float32) * gate).astype(h.dtype)
        return _mm(o, p[pre + ".wo"])

    if cache is None and window is not None:
        with jax.named_scope("attn/window"):
            o = attend(cfg, q, k, v, pos, window=window, band=True,
                       turn=lambda qc, pc: turn(cfg, qc, pc))
        return out(o), tuple(fresh)
    if cache is None:
        if flash_ok and (not sparse or T <= cfg.index_topk):
            # every position is selected: plain causal attention, through
            # the seam the flash kernel sits behind
            from ..nn import functional as F

            rep = Hq // Hkv
            flash = lambda q, k, v: F.scaled_dot_product_attention(
                Tensor(q), Tensor(k), Tensor(v), is_causal=True,
                training=False)._value
            if cfg.flash_by_kv_head:
                wide = lambda t: jnp.broadcast_to(t[:, :, None],
                                                  (B, T, rep, D))
                with _scope(cfg, "attn/full"):
                    o = lax.map(
                        lambda a: flash(a[0], wide(a[1]), wide(a[2])),
                        (q.reshape(B, T, Hkv, rep, D).transpose(2, 0, 1, 3, 4),
                         k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
                o = o.transpose(1, 2, 0, 3, 4).reshape(B, T, Hq, D)
                return out(o), tuple(fresh)
            expand = lambda t: jnp.broadcast_to(
                t[:, :, :, None], (B, T, Hkv, rep, D)).reshape(B, T, Hq, D)
            o = flash(q, expand(k), expand(v))
        else:
            o = attend(cfg, q, k, v, pos, index)
        return out(o), tuple(fresh)

    *pools, table = cache
    pools = [_pools.paged_write_kv(pool, new, table, start)
             for pool, new in zip(pools, fresh)]
    L = table.shape[1] * pools[0].shape[2]
    if window is not None:
        with jax.named_scope("attn/window"):
            if T == 1:
                o = paged_decode_attend(
                    q.transpose(0, 2, 1, 3), pools[0], pools[1], table, start,
                    window=window).transpose(0, 2, 1, 3)
            else:
                # the window before the first new token, and the new tokens
                first, sub = _pools.window_blocks(
                    table, start, pools[0].shape[2], window, T)
                o = paged_extend_attend(
                    q.transpose(0, 2, 1, 3), pools[0], pools[1], sub, start,
                    window=window, first=first).transpose(0, 2, 1, 3)
    elif head_major and T == 1:
        # the paged attend, kernel or oracle as kernels/tier says
        with _scope(cfg, "attn/full"):
            o = paged_decode_attend(q.transpose(0, 2, 1, 3), pools[0],
                                    pools[1], table, start)
        o = o.transpose(0, 2, 1, 3)
    elif sparse and T == 1 and _tier.default_paged_impl() == "pallas":
        from ..kernels.sparse_attention import (selected_rows,
                                                sparse_paged_decode)

        qi, w, _ = index
        ki_view = _pools.paged_gather(pools[2], table)[:, 0, :, :Di]  # B,L,Di
        s = jnp.einsum("bhd,bld->bhl", qi[:, 0], ki_view,
                       preferred_element_type=jnp.float32)
        score = jnp.sum(jnp.maximum(s, 0.0) * w[:, 0, :, None], axis=1)
        valid = jnp.arange(L, dtype=jnp.int32)[None, :] <= start[:, None]
        rows, n = selected_rows(score, valid, table, pools[0].shape[2],
                                cfg.index_topk)
        o = sparse_paged_decode(q[:, 0], pools[0], pools[1], rows, n)
        o = o[:, None]
    elif head_major and not sparse:
        # an extend behind the cached context, kernel or oracle likewise
        o = paged_extend_attend(q.transpose(0, 2, 1, 3), pools[0], pools[1],
                                table, start).transpose(0, 2, 1, 3)
    else:
        if head_major:
            view = lambda pool, heads: _pools.paged_gather(pool, table) \
                .transpose(0, 2, 1, 3)
        else:
            view = lambda pool, heads: _pools.paged_gather(pool, table)[:, 0] \
                .reshape(B, L, heads, -1)
        idx_view = None if not sparse else (
            index[0], index[1],
            _pools.paged_gather(pools[2], table)[:, 0, :, :Di])
        o = attend(cfg, q, view(pools[0], Hkv), view(pools[1], Hkv), pos,
                   idx_view)
    return out(o), tuple(pools)


def _scope(cfg, name):
    """``jax.named_scope(name)`` in a model with sliding layers (whose
    traces tell its window and full layers apart by it); nothing in any
    other, whose programs stay the ones they were."""
    return jax.named_scope(name) if "sliding" in cfg.kinds \
        else contextlib.nullcontext()


def _kv_pools(cfg, sparse, window=False):
    """[(name, heads, width)] of an attention layer's paged pools; a
    sliding layer's are named apart (they stand in a page group of their
    own, ``DecoderLM.cache_pools``)."""
    # (a differential layer keeps PAIRS: half the heads, twice the lanes)
    heads, width = (cfg.num_kv_heads // 2, 2 * cfg.head_dim) \
        if cfg.differential else (cfg.num_kv_heads, cfg.head_dim)
    if window:
        return [("k_window", heads, width), ("v_window", heads, width)]
    if cfg.kv_layout == "head":
        pools = [("k", heads, width), ("v", heads, width)]
    else:
        kv = cfg.num_kv_heads * cfg.head_dim
        pools = [("k", 1, kv), ("v", 1, kv)]
    if sparse:
        pools.append(("index_k", 1, index_pool_width(cfg)))
    return pools


# ------------------------------------------- differential attention layers

def diff_lambda_init(layer: int) -> float:
    """A differential layer's ``lambda_init`` by its 0-based index."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * layer))


def _last_rows(x, idx):
    """Row ``idx[b]`` of ``x [B, T, ...]``, kept as ``[B, 1, ...]``."""
    return jnp.take_along_axis(
        x, idx.reshape((-1,) + (1,) * (x.ndim - 1)), axis=1)


def differential_attention(cfg, p, pre, h, start, cache, kind, layer, carry):
    """A differential attention layer (the module's docstring has the
    equations) of ``kind`` "dense" (full), "sliding" (the window) or "cross"
    (its own queries, the K/V of layer ``cfg.sources[layer]``) over ``h [B,
    T, hidden]``. The pools hold PAIRS of K/V heads (``[pages, H_kv / 2,
    page, 2 D]``), and every read goes through the paged attends as they are
    with the queries widened over a pair's lanes
    (``kernels/paged_attention.diff_widen``).

    ``carry`` is what ``_forward`` hands from layer to layer: ``qpos [B,
    Tq]``, the positions of the rows the residual stream still holds;
    ``last [B]`` (an admission's prefill or extend, at ``cfg.cut_layer``):
    the row of the last real token, from whose queries on the batch is cut
    to that row (this layer still computes K and V of ALL rows; it hands
    back ``[B, 1, hidden]``, and ``block`` cuts the stream); ``("kv",
    layer)``: the (K, V, table) a source layer wrote, which its cross layers
    read (``table`` None in a prefill: the keys just computed)."""
    B, T, _ = h.shape
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hp = Hkv // 2
    window = cfg.sliding_window if kind == "sliding" else None
    scope = {"dense": "diff/self", "sliding": "diff/window",
             "cross": "diff/cross"}[kind]
    bias = (lambda x, b: x + p[pre + b]) if cfg.attn_bias else (lambda x, b: x)
    cut = carry.get("last") if layer == cfg.cut_layer else None
    hq = h if cut is None else _last_rows(h, cut)
    if cut is not None:
        carry["qpos"] = (start + cut)[:, None]
    qpos = carry["qpos"]                                        # [B, Tq]
    Tq = hq.shape[1]
    q = bias(_mm(hq, p[pre + ".wq"]), ".bq").reshape(B, Tq, Hq, D) \
        .transpose(0, 2, 1, 3)                                  # [B, Hq, Tq, D]
    new = ()
    if kind == "cross":
        k, v, table = carry[("kv", cfg.sources[layer])]
    else:
        # pairs: K/V heads 2r, 2r + 1 side by side in 2 D lanes
        pairs = lambda w, b: bias(_mm(h, p[pre + w]), b) \
            .reshape(B, T, Hp, 2 * D).transpose(0, 2, 1, 3)
        k, v = pairs(".wk", ".bk"), pairs(".wv", ".bv")
        table = None
        new = (k, v)
        if cache is not None:
            *pools, table = cache
            k, v = new = tuple(_pools.paged_write_kv(pool, fresh, table, start)
                               for pool, fresh in zip(pools, new))
        if layer in cfg.sources:
            carry[("kv", layer)] = (k, v, table)
    with jax.named_scope(scope):
        if table is None:
            # a prefill: the keys just computed, [B, Hp, T, 2 D]
            o = attend(cfg, diff_widen(q).transpose(0, 2, 1, 3),
                       k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                       qpos, window=window,
                       band=window is not None and Tq == T,
                       scale=float(D) ** -0.5).transpose(0, 2, 1, 3)
        elif Tq == 1:
            o = diff_decode_attend(q, k, v, table, qpos[:, 0], window)
        elif window is not None:
            # the window before the first new token, and the new tokens
            first, sub = _pools.window_blocks(table, start, k.shape[2],
                                              window, T)
            o = diff_extend_attend(q, k, v, sub, start, window, first)
        else:
            o = diff_extend_attend(q, k, v, table, qpos[:, 0])
    with jax.named_scope("diff/combine"):
        f32 = jnp.float32
        lam = lambda a: jnp.exp(jnp.sum(
            p[pre + f".lambda_q{a}"].astype(f32)
            * p[pre + f".lambda_k{a}"].astype(f32)))
        init = diff_lambda_init(layer)
        o = diff_combine(o, lam(1) - lam(2) + f32(init),
                             p[pre + ".o_norm.weight"], init, cfg.norm_eps)
        o = o.astype(h.dtype).transpose(0, 2, 1, 3).reshape(B, Tq, Hq * D)
    return bias(_mm(o, p[pre + ".wo"]), ".bo"), new


# --------------------------------------------- Mamba-1 and the memory unit

def _ssm1_widths(cfg):
    """(inner width, state width, rank of the step's projection, the
    convolution's width)."""
    return (cfg.ssm1_inner or 2 * cfg.hidden_size, cfg.ssm_state,
            cfg.ssm1_dt_rank or -(-cfg.hidden_size // 16),
            cfg.ssm_conv_kernel)


def _ssm1_shapes(cfg, pre):
    Hd = cfg.hidden_size
    E, N, R, K = _ssm1_widths(cfg)
    return {pre + ".w_in": (Hd, 2 * E),              # [x | z]
            pre + ".conv.weight": (E, K), pre + ".conv.bias": (E,),
            pre + ".w_x": (E, R + 2 * N),            # [delta | B | C]
            pre + ".w_dt": (R, E), pre + ".dt_bias": (E,),
            # kept N-major: the channels along the lanes, as the state is
            pre + ".A_log": (N, E), pre + ".D": (E,),
            pre + ".w_out": (E, Hd)}


def _ssm1_state_pools(cfg):
    """[(name, per-slot shape, dtype)] of a mamba1 layer's state: the
    float32 ``S [N, E]`` and the convolution's last inputs."""
    E, N, _, K = _ssm1_widths(cfg)
    return [("ssm1_state", (N, E), "float32"),
            ("ssm1_conv", (K - 1, E), cfg.dtype)]


def mamba1(cfg, p, pre, h, start, cache=None, flash_ok=False, lengths=None,
           cuts=None, layer=None, carry=None):
    """A Mamba-1 layer over ``h [B, T, hidden]`` (the module's docstring has
    the equations). It speaks ``gated_delta``'s cache protocol to the letter
    (``mamba2`` has it in words); where a gmu layer reads this one
    (``cfg.sources``) it leaves its memory, the scan's output BEFORE the
    gate ``[B, T, E]`` float32, in ``carry[("m", layer)]``."""
    from ..kernels import mamba1 as _m1

    B, T, _ = h.shape
    E, N, R, K = _ssm1_widths(cfg)
    f32 = jnp.float32
    state, conv, where, tail = _state_start("mamba1", cache, B, T, (K - 1, E),
                                            h.dtype)
    with jax.named_scope("ssm1/in_proj"):
        xz = _mm(h, p[pre + ".w_in"])
    with jax.named_scope("ssm1/conv"):
        x, tails, n = _conv_tails(
            xz[..., :E], tail, p[pre + ".conv.weight"], lengths, cuts,
            cache is not None and cuts is not None, p[pre + ".conv.bias"])
    with jax.named_scope("ssm1/x_proj"):
        dbc = jnp.dot(x.astype(h.dtype), p[pre + ".w_x"],
                      preferred_element_type=f32)
        dt = jax.nn.softplus(
            jnp.dot(dbc[..., :R].astype(h.dtype), p[pre + ".w_dt"],
                    preferred_element_type=f32)
            + p[pre + ".dt_bias"].astype(f32))
    Bm, Cm = dbc[..., R:R + N], dbc[..., R + N:]
    real = jnp.arange(T)[None, :] < n[:, None]
    if cache is not None and where is None:
        real = real & (start > 0)[:, None]      # a slot that runs a request
    dt = jnp.where(real[..., None], dt, 0.0)
    A = -jnp.exp(p[pre + ".A_log"].astype(f32))
    D = p[pre + ".D"].astype(f32)
    if cache is not None and where is None:
        with jax.named_scope("ssm1/step"):
            y, state = _m1.mamba1_step(x[:, 0], dt[:, 0], A, Bm[:, 0],
                                       Cm[:, 0], D, state)
        y = y[:, None]
        new = (state, lax.dynamic_update_slice_in_dim(
            conv, jnp.where(real[:, :, None], tails[:, 0],
                            conv[:B]).astype(conv.dtype), 0, axis=0))
    else:
        with jax.named_scope("ssm1/scan"):
            S0 = jnp.zeros((B, N, E), f32) if cache is None else \
                lax.dynamic_index_in_dim(state, where[0], keepdims=True)
            y, S, Sc = _m1.mamba1_scan(x, dt, A, Bm, Cm, D, S0, cuts)
        new = _state_new(cache, jnp.concatenate([Sc, S[:, None]], axis=1),
                         tails)
    if layer in cfg.sources:
        carry[("m", layer)] = y
    with jax.named_scope("ssm1/out_proj"):
        y = (y * jax.nn.silu(xz[..., E:].astype(f32))).astype(h.dtype)
        return _mm(y, p[pre + ".w_out"]), new


def _gmu_shapes(cfg, pre):
    E = _ssm1_widths(cfg)[0]
    return {pre + ".w1": (cfg.hidden_size, E), pre + ".w2": (E, cfg.hidden_size)}


@jax.named_scope("gmu")
def gmu(cfg, p, pre, h, start, cache=None, flash_ok=False, lengths=None,
        cuts=None, layer=None, carry=None):
    """A gated memory unit (arXiv:2507.06607 section 2): ``(silu(h W1) * m)
    W2`` with ``m`` the memory of the SAME token that mamba1 layer
    ``cfg.sources[layer]`` left in ``carry``. No state, no cache."""
    m = carry[("m", cfg.sources[layer])]
    g = jax.nn.silu(_mm(h, p[pre + ".w1"]).astype(jnp.float32))
    return _mm((g * m).astype(h.dtype), p[pre + ".w2"]), ()


# ------------------------------------------------- gated delta-rule layers

def _gdn_widths(cfg):
    """(heads, dk, dv, channels of the convolution)."""
    H, dk, dv = (cfg.linear_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    return H, dk, dv, H * (2 * dk + dv)


def _gdn_shapes(cfg, pre):
    Hd = cfg.hidden_size
    H, dk, dv, C = _gdn_widths(cfg)
    s = {pre + ".wq": (Hd, H * dk), pre + ".wk": (Hd, H * dk),
         pre + ".wv": (Hd, H * dv), pre + ".wb": (Hd, H),
         pre + ".A_log": (H,),
         pre + ".conv.weight": (C, cfg.linear_conv_kernel),
         pre + ".o_norm.weight": (dv,),
         pre + ".wo": (H * dv, Hd)}
    if cfg.linear_gate == "channel":
        r = cfg.linear_gate_rank
        s.update({pre + ".wf_a": (Hd, r), pre + ".wf_b": (r, H * dk),
                  pre + ".wg_a": (Hd, r), pre + ".wg_b": (r, H * dv),
                  pre + ".dt_bias": (H * dk,)})
    else:
        s.update({pre + ".wg": (Hd, H * dv), pre + ".wa": (Hd, H),
                  pre + ".dt_bias": (H,)})
    return s


def _gdn_state_pools(cfg):
    """[(name, per-slot shape, dtype)] of a gated_delta layer's state: the
    packed float32 ``S`` and the convolution's last inputs."""
    from ..kernels.gated_delta import packed_shape

    H, dk, dv, C = _gdn_widths(cfg)
    return [("gdn_state", packed_shape(H, dk, dv), "float32"),
            ("gdn_conv", (cfg.linear_conv_kernel - 1, C), cfg.dtype)]


def _state_start(name, cache, B, T, shape, dtype):
    """What a layer with slot state starts from, of its ``cache`` entry
    ``(state, conv, where)`` (None: a prefill, from zero): (state, conv,
    where, the convolution's tail ``[B, *shape]`` this call starts with).
    ``where`` None means rows ``[0, B)``, one token each."""
    if cache is None:
        return None, None, None, jnp.zeros((B,) + shape, dtype)
    state, conv, where = cache
    if where is None and T != 1:
        raise NotImplementedError(
            f"{name}: several tokens a slot over every slot (the "
            "speculative verify step) would need the state of each "
            "position kept to roll a rejected draft back")
    tail = conv[:B] if where is None else \
        lax.dynamic_index_in_dim(conv, where[0], keepdims=True)
    return state, conv, where, tail


def _conv_tails(x, tail, w, lengths, cuts, barrier, bias=None):
    """The causal depthwise convolution of a layer with slot state over ``x
    [B, T, C]`` behind ``tail [B, K - 1, C]`` (weights ``w [C, K]``), then
    SiLU, float32; the tails ``[B, cuts + 1, K - 1, C]``: the last ``K - 1``
    real inputs before each cut and before the end; and ``n [B]``, the real
    tokens a row (``lengths``, or all ``T``)."""
    (B, T, _), Kc = x.shape, w.shape[1]
    f32 = jnp.float32
    win = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B, T+Kc-1, C]
    n = jnp.full((B,), T, jnp.int32) if lengths is None else lengths
    # real token t is row t + Kc - 1 of ``win``
    ends = n[:, None] if cuts is None else jnp.concatenate(
        [jnp.minimum(cuts, n[:, None]), n[:, None]], axis=1)
    tails = jnp.stack([jax.vmap(
        lambda a, i: lax.dynamic_slice_in_dim(a, i, Kc - 1))(win, ends[:, j])
        for j in range(ends.shape[1])], axis=1)
    w = w.astype(f32)
    y = sum(win[:, j:j + T].astype(f32) * w[:, j] for j in range(Kc))
    y = jax.nn.silu(y if bias is None else y + bias.astype(f32))
    if barrier:
        # an extend's tails are ready when its convolution is, and ``win``
        # (T x C) dies there: left to itself the compiler takes the cuts'
        # tails with the row writes at the program's end and keeps every
        # layer's ``win`` until then (0.34 GiB more of extend/3328's
        # temporaries at the hybrid cell's widths; TPU compiler, PR 35)
        y, tails = lax.optimization_barrier((y, tails))
    return y, tails, n


def _state_new(cache, S, tails):
    """What a prefill or a one-slot extend hands back of the packed states
    ``S [B, cuts + 1, ...]`` and tails: without a cache the rows themselves
    ``[B * (cuts + 1), ...]`` for the engine to install, else (``B = 1``)
    the buffers with the cuts' rows, then the end's, written."""
    if cache is None:
        return (S.reshape((-1,) + S.shape[2:]),
                tails.reshape((-1,) + tails.shape[2:]))
    state, conv, where = cache
    return (_pools.write_state_rows(state, S[0], where[1]),
            _pools.write_state_rows(conv, tails[0], where[1]))


def gated_delta(cfg, p, pre, h, start, cache=None, flash_ok=False,
                lengths=None, cuts=None):
    """A gated delta-rule layer over ``h [B, T, hidden]`` (the module's
    docstring has the equations). Only the first ``lengths[b]`` tokens of a
    row are real: the rest neither move the state nor enter the tail.
    ``cuts [B, n]`` (tokens from the call's first) asks besides for the
    state and tail as they stand BEFORE each cut's token: the chunked form
    hands them out from inside its scan (a snapshot needs no program to end
    where it is taken). ``cache`` is ``(state, conv, where)``, the layer's
    two state buffers ``[rows, ...]`` and where this call's state lives in
    them: ``None`` for rows ``[0, B)`` (decode, ``T = 1``), else ``(source,
    rows)`` of a ``B = 1`` extend: it starts from row ``source`` and writes
    what each cut asked for, then the end state and tail, to ``rows [n +
    1]`` in that order (a later write wins: a cut that is not wanted names
    the end's row). Without ``cache`` the state starts at zero (prefill).
    Returns (out [B, T, hidden], new): the updated buffers with a cache, else
    the same states and tails ``[B * (n + 1), ...]``, a row's cuts before
    its end, for the engine to install."""
    from ..kernels import gated_delta as _gdn

    B, T, _ = h.shape
    H, dk, dv, C = _gdn_widths(cfg)
    f32 = jnp.float32
    state, conv, where, tail = _state_start(
        "gated_delta", cache, B, T, (cfg.linear_conv_kernel - 1, C), h.dtype)
    x = jnp.concatenate([_mm(h, p[pre + w]) for w in (".wq", ".wk", ".wv")],
                        axis=-1)
    y, tails, n = _conv_tails(x, tail, p[pre + ".conv.weight"], lengths,
                              cuts, cache is not None and cuts is not None)
    unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = unit(y[..., :H * dk].reshape(B, T, H, dk)) * f32(dk ** -0.5)
    k = unit(y[..., H * dk:2 * H * dk].reshape(B, T, H, dk))
    v = y[..., 2 * H * dk:].reshape(B, T, H, dv)
    beta = jax.nn.sigmoid(jnp.dot(h, p[pre + ".wb"],
                                  preferred_element_type=f32))
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    channel = cfg.linear_gate == "channel"
    if channel:     # a decay a key channel, through the low-rank pair
        g = -jnp.exp(p[pre + ".A_log"].astype(f32))[:, None] * jax.nn.softplus(
            jnp.dot(_mm(h, p[pre + ".wf_a"]), p[pre + ".wf_b"],
                    preferred_element_type=f32)
            + p[pre + ".dt_bias"].astype(f32)).reshape(B, T, H, dk)
    else:
        g = -jnp.exp(p[pre + ".A_log"].astype(f32)) * jax.nn.softplus(
            jnp.dot(h, p[pre + ".wa"], preferred_element_type=f32)
            + p[pre + ".dt_bias"].astype(f32))
    real = (jnp.arange(T)[None, :] < n[:, None])[..., None]
    beta = jnp.where(real, beta, 0.0)
    g = jnp.where(real[..., None] if channel else real, g, 0.0)

    if cache is not None and where is None:
        o, state = _gdn.gdn_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], state)
        o = o[:, None]
        new = (state, lax.dynamic_update_slice_in_dim(
            conv, tails[:, 0].astype(conv.dtype), 0, axis=0))
    else:
        S0 = jnp.zeros((B, H, dv, dk), f32) if cache is None else \
            _gdn.unpack_state(
                lax.dynamic_index_in_dim(state, where[0], keepdims=True), H)
        o, S, *at_cuts = jax.vmap(functools.partial(
            _gdn.gdn_chunked, chunk=cfg.gdn_chunk))(q, k, v, g, beta, S0,
                                                    cuts=cuts)
        new = _state_new(cache, _gdn.pack_state(
            jnp.concatenate(at_cuts + [S[:, None]], axis=1)), tails)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps) \
        * p[pre + ".o_norm.weight"].astype(f32)
    if channel:
        gate = jax.nn.sigmoid(_mm(_mm(h, p[pre + ".wg_a"]),
                                  p[pre + ".wg_b"]).astype(f32))
    else:
        gate = jax.nn.silu(_mm(h, p[pre + ".wg"]).astype(f32))
    y = (o.reshape(B, T, H * dv) * gate).astype(h.dtype)
    return _mm(y, p[pre + ".wo"]), new


# ------------------------------------------------- Mamba-2 (state space)

def _ssm_widths(cfg):
    """(heads, a head's width, groups, state width, inner width, channels
    of the convolution)."""
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    return H, P, G, N, H * P, H * P + 2 * G * N


def _ssm_shapes(cfg, pre):
    Hd = cfg.hidden_size
    H, P, G, N, inner, C = _ssm_widths(cfg)
    return {pre + ".w_in": (Hd, inner + C + H),      # [z | x B C | dt]
            pre + ".conv.weight": (C, cfg.ssm_conv_kernel),
            pre + ".conv.bias": (C,),
            pre + ".A_log": (H,), pre + ".dt_bias": (H,), pre + ".D": (H,),
            pre + ".norm.weight": (inner,),
            pre + ".w_out": (inner, Hd)}


def _ssm_state_pools(cfg):
    """[(name, per-slot shape, dtype)] of a mamba2 layer's state: the packed
    float32 ``S`` and the convolution's last inputs."""
    from ..kernels.mamba2 import packed_shape

    H, P, G, N, _, C = _ssm_widths(cfg)
    return [("ssm_state", packed_shape(H, N, P), "float32"),
            ("ssm_conv", (cfg.ssm_conv_kernel - 1, C), cfg.dtype)]


def mamba2(cfg, p, pre, h, start, cache=None, flash_ok=False, lengths=None,
           cuts=None):
    """A Mamba-2 layer over ``h [B, T, hidden]`` (the module's docstring has
    the equations). It speaks ``gated_delta``'s cache protocol to the
    letter: only the first ``lengths[b]`` tokens of a row are real, ``cuts
    [B, n]`` asks for the state and tail BEFORE each cut's token (from
    inside the chunked form's scan), ``cache`` is ``(state, conv, where)``
    with ``where`` ``None`` for rows ``[0, B)`` (decode, ``T = 1``: a slot at
    position 0 runs no request, and its state stays as it is) or ``(source,
    rows)`` of a ``B = 1`` extend; without ``cache`` the state starts at
    zero (prefill). Returns (out [B, T, hidden], new) as ``gated_delta``
    does."""
    from ..kernels import mamba2 as _ssm

    B, T, _ = h.shape
    H, P, G, N, inner, C = _ssm_widths(cfg)
    f32 = jnp.float32
    state, conv, where, tail = _state_start(
        "mamba2", cache, B, T, (cfg.ssm_conv_kernel - 1, C), h.dtype)
    with jax.named_scope("ssm/in_proj"):
        zxd = _mm(h, p[pre + ".w_in"])
    z, x = zxd[..., :inner], zxd[..., inner:inner + C]
    dt = zxd[..., inner + C:]
    with jax.named_scope("ssm/conv"):
        y, tails, n = _conv_tails(
            x, tail, p[pre + ".conv.weight"], lengths, cuts,
            cache is not None and cuts is not None, p[pre + ".conv.bias"])
    xs = y[..., :inner].reshape(B, T, H, P)
    Bm = y[..., inner:inner + G * N].reshape(B, T, G, N)
    Cm = y[..., inner + G * N:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt.astype(f32) + p[pre + ".dt_bias"].astype(f32))
    real = jnp.arange(T)[None, :] < n[:, None]
    if cache is not None and where is None:
        real = real & (start > 0)[:, None]      # a slot that runs a request
    dt = jnp.where(real[..., None], dt, 0.0)
    A = -jnp.exp(p[pre + ".A_log"].astype(f32))
    D = p[pre + ".D"].astype(f32)

    if cache is not None and where is None:
        with jax.named_scope("ssm/step"):
            o, state = _ssm.mamba2_step(xs[:, 0], dt[:, 0], A, Bm[:, 0],
                                        Cm[:, 0], D, state)
        o = o[:, None]
        new = (state, lax.dynamic_update_slice_in_dim(
            conv, jnp.where(real[:, :, None], tails[:, 0],
                            conv[:B]).astype(conv.dtype), 0, axis=0))
    else:
        with jax.named_scope("ssm/chunk"):
            S0 = jnp.zeros((B, H, P, N), f32) if cache is None else \
                _ssm.unpack_state(
                    lax.dynamic_index_in_dim(state, where[0], keepdims=True),
                    H)
            o, S, *at_cuts = jax.vmap(
                functools.partial(_ssm.mamba2_chunked, chunk=cfg.ssm_chunk),
                in_axes=(0, 0, None, 0, 0, None, 0))(
                    xs, dt, A, Bm, Cm, D, S0, cuts=cuts)
        new = _state_new(cache, _ssm.pack_state(
            jnp.concatenate(at_cuts + [S[:, None]], axis=1)), tails)
    with jax.named_scope("ssm/gated_norm"):
        # the gate FIRST, then a norm over each group's share of the inner
        # width
        o = o.reshape(B, T, inner) * jax.nn.silu(z.astype(f32))
        o = o.reshape(B, T, G, inner // G)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps)
        o = (o.reshape(B, T, inner)
             * p[pre + ".norm.weight"].astype(f32)).astype(h.dtype)
    return _mm(o, p[pre + ".w_out"]), new


# ------------------------------------------------ latent (MLA) attention

def latent_pool_width(cfg) -> int:
    """Lanes a token's latent row takes in its pool: ``[c (kv_lora_rank) |
    k_pe (qk_rope_head_dim)]`` rounded up to whole 128-lane rows (576 -> 640
    at the published widths), for ``index_pool_width``'s reason: the chip
    stores a minor dimension in whole 128-lane tiles either way, and a pool
    that is not declared so is re-laid whole by every program that touches
    it. The idle lanes are written as zeros and read as such."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def _latent_shapes(cfg, pre):
    Hd, H = cfg.hidden_size, cfg.num_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {pre + ".wq_a": (Hd, rq), pre + ".q_norm.weight": (rq,),
            pre + ".wq_b": (rq, H * (dn + dr)),
            pre + ".wkv_a": (Hd, rkv + dr), pre + ".kv_norm.weight": (rkv,),
            pre + ".wk_b": (rkv, H * dn), pre + ".wv_b": (rkv, H * dv),
            pre + ".wo": (H * dv, Hd)}


def _latent_queries(cfg, p, pre, cq, pos):
    """(q_nope [B, T, H, dn], q_pe [B, T, H, dr], rotated) of the query's
    normed latent ``cq [B, T, rq]``."""
    B, T, _ = cq.shape
    dn = cfg.qk_nope_head_dim
    q = _mm(cq, p[pre + ".wq_b"]).reshape(B, T, cfg.num_heads, -1)
    return q[..., :dn], POSITIONS[cfg.position](cfg, q[..., dn:], pos)


#: heads whose keys and values the kernel path expands at a time
_LATENT_HEAD_GROUP = 8
#: keys the ``jax.numpy`` path expands at a time
_LATENT_KEY_BLOCK = 512


def _latent_attend_flash(cfg, p, pre, cq, c_view, kpe_view, qpos):
    """``latent_attend`` on the TPU: a group of heads at a time, the group's
    queries projected and its keys and values expanded from ALL the cached
    rows once (``mla/expand``; ``[L, 8 heads, 192 + 128]`` is 0.18 GB at
    35k tokens where all 128 heads' would be 2.9), then the Pallas kernel
    ``kernels/latent_attention.latent_flash`` (causal behind the cached
    context, the one rotary key shared by the group's heads, a value width
    of its own), whose score tiles never leave VMEM."""
    B, T, _ = cq.shape
    L = c_view.shape[1]
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    G = np.gcd(H, _LATENT_HEAD_GROUP)
    by_head = lambda w, d: p[pre + w].reshape(-1, H, d)
    wq, wk, wv = by_head(".wq_b", dn + dr), by_head(".wk_b", dn), \
        by_head(".wv_b", dv)
    scale = jnp.asarray(softmax_scale(cfg, dn + dr), cq.dtype)
    # [B, G, rows, d] -> the kernel's [B * G, rows, d]
    fold = lambda a: a.reshape((B * G,) + a.shape[2:])

    def group(i, out):
        cut = lambda w: lax.dynamic_slice_in_dim(w, i * G, G, axis=1)
        q = jnp.einsum("btr,rhd->bthd", cq, cut(wq))
        qn = (q[..., :dn] * scale).transpose(0, 2, 1, 3)
        qp = (POSITIONS[cfg.position](cfg, q[..., dn:], qpos)
              * scale).transpose(0, 2, 1, 3)
        with jax.named_scope("mla/expand"):
            kn = jnp.einsum("blr,rhd->bhld", c_view, cut(wk))
            v = jnp.einsum("blr,rhd->bhld", c_view, cut(wv))
        o = _latent.latent_flash(fold(qn), fold(qp), fold(kn), kpe_view,
                                 fold(v), qpos[:, 0], G)
        o = o.reshape(B, G, T, dv).transpose(0, 2, 1, 3).reshape(B, T, G * dv)
        return lax.dynamic_update_slice_in_dim(out, o, i * G * dv, axis=2)

    o = lax.fori_loop(0, H // G, group, jnp.zeros((B, T, H * dv), cq.dtype))
    return _mm(o, p[pre + ".wo"])


def latent_attend(cfg, p, pre, cq, c_view, kpe_view, qpos):
    """The expanded form of latent attention, for prefill and extend:
    queries from the normed latents ``cq [B, T, rq]`` at ``qpos [B, T]``
    against the views ``c_view [B, L, rkv]`` / ``kpe_view [B, L, dr]`` of
    the cached latents (view position = sequence position), through ``wo``:
    ``[B, T, hidden]`` out. On the TPU ``_latent_attend_flash``
    (``kernels/tier.default_paged_impl`` says which); elsewhere, and as
    its oracle, plain ``jax.numpy``: queries go in chunks of
    ``cfg.query_chunk`` (projected from ``cq``, attended and put through
    ``wo`` chunk by chunk: a 34k-token prompt's q alone would be 1.7 GB),
    and each chunk walks the keys in blocks of ``_LATENT_KEY_BLOCK``, as far as
    its last query sees: a block's keys and values are expanded from its
    latents (``mla/expand``), scored, and folded into a float32 online
    softmax, so no expanded key outlives its block. Numerics as ``attend``:
    q pre-scaled in its own dtype, float32 scores, -1e30 mask."""
    if _tier.default_paged_impl() == "pallas":
        return _latent_attend_flash(cfg, p, pre, cq, c_view, kpe_view, qpos)
    B, T, _ = cq.shape
    L = c_view.shape[1]
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    rkv = cfg.kv_lora_rank
    wk = p[pre + ".wk_b"].reshape(rkv, H, dn)
    wv = p[pre + ".wv_b"].reshape(rkv, H, dv)
    Kb = _LATENT_KEY_BLOCK if L % _LATENT_KEY_BLOCK == 0 else L
    f32 = jnp.float32

    def chunk(cqc, pc):
        C = cqc.shape[1]
        qn, qp = _latent_queries(cfg, p, pre, cqc, pc)
        scale = jnp.asarray(softmax_scale(cfg, dn + cfg.qk_rope_head_dim),
                            cqc.dtype)
        qn, qp = qn * scale, qp * scale

        def block(j, carry):
            m, l, acc = carry
            cut = lambda a: lax.dynamic_slice_in_dim(a, j * Kb, Kb, axis=1)
            cb, kp = cut(c_view), cut(kpe_view)
            with jax.named_scope("mla/expand"):
                kn = jnp.einsum("bkr,rhd->bkhd", cb, wk)
                v = jnp.einsum("bkr,rhd->bkhd", cb, wv)
            s = jnp.einsum("bqhd,bkhd->bhqk", qn, kn,
                           preferred_element_type=f32) \
                + jnp.einsum("bqhd,bkd->bhqk", qp, kp,
                             preferred_element_type=f32)
            kpos = j * Kb + jnp.arange(Kb, dtype=jnp.int32)
            s = jnp.where((kpos[None, None, :] <= pc[:, :, None])[:, None],
                          s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            w = jnp.exp(s - m_new[..., None])
            a = jnp.exp(m - m_new)
            pv = jnp.einsum("bhqk,bkhd->bhqd", w.astype(v.dtype), v,
                            preferred_element_type=f32)
            return m_new, l * a + w.sum(-1), acc * a[..., None] + pv

        # key 0 is behind every query, so no row of a walked block's
        # running maximum stays at the mask's value
        blocks = jnp.minimum(jnp.max(pc) // Kb + 1, L // Kb)
        _, l, acc = lax.fori_loop(
            0, blocks, block,
            (jnp.full((B, H, C), _NEG_INF, f32), jnp.zeros((B, H, C), f32),
             jnp.zeros((B, H, C, dv), f32)))
        o = (acc / l[..., None]).astype(cqc.dtype)
        return _mm(o.transpose(0, 2, 1, 3).reshape(B, C, H * dv),
                   p[pre + ".wo"])

    C = cfg.query_chunk if T % cfg.query_chunk == 0 else T
    if C == T:
        return chunk(cq, qpos)

    def body(i, out):
        cut = lambda a: lax.dynamic_slice_in_dim(a, i * C, C, axis=1)
        return lax.dynamic_update_slice_in_dim(
            out, chunk(cut(cq), cut(qpos)), i * C, axis=1)

    return lax.fori_loop(0, T // C, body,
                         jnp.zeros((B, T, cfg.hidden_size), cq.dtype))


def latent_attention(cfg, p, pre, h, start, cache=None, flash_ok=False,
                     lengths=None, cuts=None):
    """A latent-attention layer (MLA; the module's docstring has the
    equations) over ``h [B, T, hidden]`` whose tokens sit at ``start[b] ..
    start[b] + T - 1``. What it keeps a token is ONE row, ``[c | k_pe]``
    (``latent_pool_width`` lanes), keys and values alike. Prefill and
    extend run the expanded form over the rows (``latent_attend``); decode
    (``T = 1`` over a cache) never expands: the absorbed form, ``wk_b``
    folded into the query and ``wv_b`` applied to the attended latents
    (``kernels/latent_attention.latent_decode_attend``: the Pallas kernel
    ``latent_paged_decode`` or its reference).
    Returns (out, new) as ``attention`` does."""
    B, T, _ = h.shape
    H, rkv = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    W = latent_pool_width(cfg)
    pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    cq = rms_norm(_mm(h, p[pre + ".wq_a"]), p, pre + ".q_norm", cfg.norm_eps)
    ckv = _mm(h, p[pre + ".wkv_a"])
    c = rms_norm(ckv[..., :rkv], p, pre + ".kv_norm", cfg.norm_eps)
    kpe = POSITIONS[cfg.position](cfg, ckv[..., None, rkv:], pos)[:, :, 0]
    fresh = jnp.concatenate(
        [c, kpe, jnp.zeros((B, T, W - rkv - dr), c.dtype)], axis=-1)[:, None]
    if cache is None:
        return latent_attend(cfg, p, pre, cq, c, kpe, pos), (fresh,)

    pool, table, *plan = cache      # a decode step's shared-walk plan
    pool = _pools.paged_write_kv(pool, fresh, table, start)
    if T > 1:
        rows = _pools.paged_gather(pool, table)[:, 0]          # [B, L, W]
        return latent_attend(cfg, p, pre, cq, rows[..., :rkv],
                             rows[..., rkv:rkv + dr], pos), (pool,)
    qn, qp = _latent_queries(cfg, p, pre, cq, pos)
    with jax.named_scope("mla/absorb"):
        ql = jnp.einsum("bhd,rhd->bhr", qn[:, 0],
                        p[pre + ".wk_b"].reshape(rkv, H, dn))
    q = jnp.concatenate(
        [ql, qp[:, 0], jnp.zeros((B, H, W - rkv - dr), ql.dtype)], axis=-1)
    with jax.named_scope("mla/decode"):
        ol = _latent.latent_decode_attend(
            q * jnp.asarray(softmax_scale(cfg, dn + dr), q.dtype), pool,
            table, start, rkv, *plan)                          # [B, H, rkv]
    with jax.named_scope("mla/absorb"):
        o = jnp.einsum("bhr,rhd->bhd", ol,
                       p[pre + ".wv_b"].reshape(rkv, H, dv))
    return _mm(o.reshape(B, 1, H * dv), p[pre + ".wo"]), (pool,)


#: kind -> (the layer's function, its parameters' shapes, its paged pools
#: [(name, heads, width)], its slot state [(name, shape, dtype)])
ATTENTIONS = {
    "dense": (attention, functools.partial(_attn_shapes, sparse=False),
              functools.partial(_kv_pools, sparse=False), lambda c: []),
    "sliding": (functools.partial(attention, kind="sliding"),
                functools.partial(_attn_shapes, sparse=False),
                functools.partial(_kv_pools, sparse=False, window=True),
                lambda c: []),
    "indexed_sparse": (functools.partial(attention, sparse=True,
                                         kind="indexed_sparse"),
                       functools.partial(_attn_shapes, sparse=True),
                       functools.partial(_kv_pools, sparse=True),
                       lambda c: []),
    "gated_delta": (gated_delta, _gdn_shapes, lambda c: [], _gdn_state_pools),
    "latent": (latent_attention, _latent_shapes,
               lambda c: [("latent", 1, latent_pool_width(c))], lambda c: []),
    "mamba2": (mamba2, _ssm_shapes, lambda c: [], _ssm_state_pools),
    "mamba1": (mamba1, _ssm1_shapes, lambda c: [], _ssm1_state_pools),
    # a gated memory unit over an earlier mamba1 layer's scan output, and
    # an attention layer over an earlier dense layer's K/V: no pool, no state
    "gmu": (gmu, _gmu_shapes, lambda c: [], lambda c: []),
    "cross": (functools.partial(attention, kind="cross"),
              functools.partial(_attn_shapes, sparse=False, cross=True),
              lambda c: [], lambda c: []),
    # a layer without a token mixer: nothing declared, nothing traced
    "none": (None, lambda c, pre: {}, lambda c: [], lambda c: []),
}


# -------------------------------------------------------------------- FFN

def softmax_topk(cfg, g, wr):
    """Router: softmax in float32 over ALL experts, the top-k of it,
    renormalised over the chosen (``norm_topk_prob``). Returns (weights
    [N, k] float32, experts [N, k] int32)."""
    logits = jnp.dot(g, wr, preferred_element_type=jnp.float32)
    pw, e = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.experts_per_token)
    if cfg.norm_topk_prob:
        pw = pw / jnp.sum(pw, axis=-1, keepdims=True)
    return pw, e.astype(jnp.int32)


@jax.named_scope("router/group_topk")
def sigmoid_group_topk(cfg, g, wr, bias):
    """Router of DeepSeek-V3 (``noaux_tc``), float32: scores ``s =
    sigmoid(g Wr)`` over ALL experts; the CHOICE is made on ``s + bias``
    (the bias balances the load and weighs nothing): a group's score is the
    sum of its two largest, the ``topk_group`` best of ``n_group`` groups
    are kept, and the top-k taken inside them; the WEIGHTS are ``s`` of the
    chosen, over their sum (``norm_topk_prob``), times
    ``routed_scaling_factor``."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(g, wr, preferred_element_type=f32))
    N, E = s.shape
    G = cfg.n_group
    if G == 1:      # one group keeps every expert: the plain top-k
        _, e = lax.top_k(s + bias.astype(f32), cfg.experts_per_token)
    else:
        choose = (s + bias.astype(f32)).reshape(N, G, E // G)
        _, keep = lax.top_k(lax.top_k(choose, 2)[0].sum(-1), cfg.topk_group)
        kept = jnp.any(keep[:, :, None] == jnp.arange(G)[None, None, :],
                       axis=1)
        _, e = lax.top_k(jnp.where(kept[:, :, None], choose, _NEG_INF)
                         .reshape(N, E), cfg.experts_per_token)
    pw = jnp.take_along_axis(s, e, axis=1)
    if cfg.norm_topk_prob:
        pw = pw / (jnp.sum(pw, axis=-1, keepdims=True) + 1e-20)
    return pw * f32(cfg.routed_scaling_factor), e.astype(jnp.int32)


@jax.named_scope("router/sigmoid_topk")
def sigmoid_topk(cfg, g, wr):
    """Router by plain sigmoid scores, float32: ``s = sigmoid(g Wr)`` over
    ALL experts, the top-k of it (no groups, no bias, no factor), the
    weights ``s`` of the chosen over their sum (``norm_topk_prob``)."""
    s = jax.nn.sigmoid(jnp.dot(g, wr, preferred_element_type=jnp.float32))
    pw, e = lax.top_k(s, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        pw = pw / jnp.sum(pw, axis=-1, keepdims=True)
    return pw, e.astype(jnp.int32)


#: kind -> (the router's function of (cfg, g, the ``[hidden, experts]``
#: matrix, *its other leaves), those leaves' names behind ``.router``)
ROUTERS = {"softmax_topk": (softmax_topk, ()),
           "sigmoid_topk": (sigmoid_topk, ()),
           "sigmoid_group_topk": (sigmoid_group_topk, (".bias",))}


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


#: an FFN's activation by name -> (the function; whether a third matrix
#: ``w3`` gates it: ``act(g w1) * g w3``, else ``act(g w1)`` alone; whether a
#: ROUTED expert's ``w1`` is kept ``[G, out, in]``, as the public checkpoints
#: keep a Linear, and contracted over its last dimension). The layout is a
#: fact of its own, not the gate's: what asks for it is a width that is no
#: multiple of 128 lanes (relu2's 1,856 = 14.5 x 128: the chip stores a
#: ``[.., hidden, width]`` array width-major at rest, and a kernel that
#: wants it otherwise re-lays ALL the experts every step, 609 MiB a layer;
#: TPU compiler, PR 46). A gated expert of such a width still pays that.
ACTIVATIONS = {"swiglu": (jax.nn.silu, True, False),
               "relu2": (_relu2, False, True)}


def _dense_shapes(cfg, pre, F, act="swiglu"):
    H = cfg.hidden_size
    s = {pre + ".w1": (H, F), pre + ".w3": (H, F), pre + ".w2": (F, H)}
    if not ACTIVATIONS[act][1]:
        del s[pre + ".w3"]
    return s


def _dense_ffn(p, pre, g, act="swiglu"):
    fn, gated, _ = ACTIVATIONS[act]
    a = fn(_mm(g, p[pre + ".w1"]))
    if gated:
        a = a * _mm(g, p[pre + ".w3"])
    return _mm(a, p[pre + ".w2"])


def dense_ffn(cfg, p, pre, g, act="swiglu"):
    """Dense FFN over ``g [N, hidden]`` (``swiglu``: gated, three matrices;
    ``relu2``: ``relu(g w1)^2 w2``, two); no routing statistics."""
    return _dense_ffn(p, pre, g, act), jnp.zeros((len(ffn_stats(cfg)),),
                                                 jnp.int32)


def _moe_shapes(cfg, pre, F, act="swiglu"):
    H, E = cfg.hidden_size, cfg.num_experts
    G = E if cfg.experts_held is None else cfg.experts_held[0]
    s = {pre + ".router": (H, E), pre + ".w1": (G, H, F),
         pre + ".w3": (G, H, F), pre + ".w2": (G, F, H)}
    _, gated, out_in = ACTIVATIONS[act]
    if not gated:
        del s[pre + ".w3"]
    if out_in:
        s[pre + ".w1"] = (G, F, H)
    s.update({pre + ".router" + leaf: (E,)          # one value an expert
              for leaf in ROUTERS[cfg.router][1]})
    if cfg.shared_experts:
        Fs = cfg.shared_intermediate_size or F * cfg.shared_experts
        s.update(_dense_shapes(cfg, pre + ".shared", Fs, act))
    return s


def ffn_stats(cfg) -> Tuple[str, ...]:
    """What a layer's FFN counts in a step (``moe_routed``'s statistics): a
    program told which experts it holds also counts the rows that landed on
    them, beside all the rows it routed."""
    names = ("experts_touched", "expert_max_load")
    if cfg.experts_held is not None:
        names += ("local_rows", "routed_rows")
    return names


def step_stats(cfg) -> Tuple[str, ...]:
    """What a layer counts in a step: its FFN's statistics and, of a model
    with latent layers, ``latent_tokens_read``: the cached tokens the step's
    attention read, summed over the live slots, and ``shared_walk_tokens``:
    those of them that slots on one document scored TOGETHER, each page
    fetched once for all of them (the sum over the step's plan,
    ``kernels/latent_attention.latent_decode_plan``: 0 in the oracle tier,
    which has none); both 0 in a layer of another kind. Of a model with sliding
    layers, ``window_tokens_read`` / ``full_tokens_read``: the cached tokens
    a sliding layer (``min(context, sliding_window)`` a slot) and a full
    one (the context) attended, summed over the live slots, each 0 in a
    layer of the other kind. Of a model with mamba2 layers,
    ``ssm_slots_stepped``: the running slots a mamba2 layer's recurrent
    step advanced (0 in a layer of another kind; a mamba1 layer's likewise).
    Of a model with cross layers, ``shared_read``: the cached tokens a layer
    read from the ONE pool the cross layers share (the layer that writes it
    and each cross layer: the context a live slot; 0 in any other layer). A
    layer without an FFN counts 0 in the FFN's columns."""
    return ffn_stats(cfg) + (("latent_tokens_read", "shared_walk_tokens")
                             if "latent" in cfg.kinds else ()) \
        + (("window_tokens_read", "full_tokens_read")
           if "sliding" in cfg.kinds else ()) \
        + (("ssm_slots_stepped",)
           if {"mamba2", "mamba1"} & set(cfg.kinds) else ()) \
        + (("shared_read",) if "cross" in cfg.kinds else ())


def moe_routed(cfg, p, pre, g, act="swiglu"):
    """The routed experts' part of the layer over ``g [N, hidden]`` (an
    expert ``act``'s FFN: three matrices and three grouped matmuls where it
    is gated, two where not), drop-free: every (token, chosen expert) row of
    an expert held here is computed, whatever the distribution. With ``experts_held`` the router
    still chooses among ALL experts; a row routed to an expert that is not
    held sorts behind the held groups, in no tile of the grouped matmul (no
    matmul, no weight read), and adds nothing: what the chips that hold it
    would add is left out. Returns (y [N, hidden] float32, [held experts
    with a row, largest expert's rows(, rows on held experts, rows
    routed)])."""
    from ..kernels.grouped_matmul import grouped_matmul, plan_groups, row_tile

    N, H = g.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    route, leaves = ROUTERS[cfg.router]
    pw, e = route(cfg, g, p[pre + ".router"],
                  *(p[pre + ".router" + leaf] for leaf in leaves))
    G, local = E, None
    if cfg.experts_held is not None:
        G, first = cfg.experts_held
        local = (e >= first) & (e < first + G)
        e = jnp.where(local, e - first, G)
    tm = row_tile(N * k * G // E, G)     # by the rows expected HERE
    src, dest, tile_group, n_tiles, counts = plan_groups(e.reshape(-1), G, tm)
    x = g[src // k]                                        # [M_pad, H]
    gmm = lambda a, w, **kw: grouped_matmul(a, w, tile_group, n_tiles, tm,
                                            **kw)
    fn, gated, out_in = ACTIVATIONS[act]
    a = fn(gmm(x, p[pre + ".w1"], transposed=out_in))
    if gated:
        a = a * gmm(x, p[pre + ".w3"])
    if local is not None:   # an absent expert's row has no place in the
        dest = jnp.where(local.reshape(-1), dest, 0)    # layout: read none
    y = gmm(a, p[pre + ".w2"])[dest].reshape(N, k, H)
    if local is not None:
        y = jnp.where(local[:, :, None], y, 0)
    y = jnp.sum(y.astype(jnp.float32) * pw[:, :, None], axis=1)
    stats = [jnp.sum(counts > 0), jnp.max(counts)]
    if local is not None:
        stats += [jnp.sum(counts), jnp.asarray(N * k)]
    return y, jnp.stack(stats).astype(jnp.int32)


def moe_ffn(cfg, p, pre, g, act="swiglu"):
    """The expert layer over ``g [N, hidden]``: the routed experts'
    part (``moe_routed``) plus, with ``shared_experts``, one dense FFN of
    the same activation over every token. Returns (y [N, hidden], the
    routing statistics)."""
    # (the gated kind's programs carry no scope of this: they stay the
    # ones they were)
    scope = lambda name: contextlib.nullcontext() if act == "swiglu" \
        else jax.named_scope(f"ffn/{act}_{name}")
    with scope("experts"):
        y, stats = moe_routed(cfg, p, pre, g, act)
    if cfg.shared_experts and cfg.shared_combine == "mean":
        # the mean of the shared experts: their sum is the ONE wide FFN
        with jax.named_scope("ffn/shared_mean"):
            y = y + _dense_ffn(p, pre + ".shared", g, act) \
                .astype(jnp.float32) * jnp.float32(1.0 / cfg.shared_experts)
    elif cfg.shared_experts:
        with scope("shared"):
            y = y + _dense_ffn(p, pre + ".shared", g, act) \
                .astype(jnp.float32)
    return y.astype(g.dtype), stats


def _ffn_kind(fn, shapes, act):
    return (functools.partial(fn, act=act), functools.partial(shapes, act=act))


#: kind -> (the FFN's function of (cfg, p, pre, g [N, hidden]), its
#: parameters' shapes of (cfg, pre, width))
FFNS = {"swiglu": _ffn_kind(dense_ffn, _dense_shapes, "swiglu"),
        "moe_swiglu": _ffn_kind(moe_ffn, _moe_shapes, "swiglu"),
        "relu2": _ffn_kind(dense_ffn, _dense_shapes, "relu2"),
        "moe_relu2": _ffn_kind(moe_ffn, _moe_shapes, "relu2"),
        # a layer without an FFN: nothing declared, nothing traced
        "none": (None, lambda cfg, pre, F: {})}
swiglu, moe_swiglu = FFNS["swiglu"][0], FFNS["moe_swiglu"][0]


#: tokens per pass of the FFN over a long sequence
_FFN_TOKEN_CHUNK = 2048


def ffn(cfg, p, pre, g, kind):
    """``g [B, T, hidden]`` through a block's FFN of ``kind``,
    ``_FFN_TOKEN_CHUNK`` tokens at a time (a long prefill's sorted rows,
    eight a token, would otherwise stand in memory whole). Statistics are
    the last chunk's."""
    B, T, H = g.shape
    fn = FFNS[kind][0]
    flat = g.reshape(B * T, H)
    C = _FFN_TOKEN_CHUNK
    if B * T <= C or (B * T) % C:
        y, stats = fn(cfg, p, pre, flat)
        return y.reshape(B, T, H), stats

    def body(i, carry):
        y, _ = carry
        yc, stats = fn(cfg, p, pre, lax.dynamic_slice_in_dim(flat, i * C, C))
        return lax.dynamic_update_slice_in_dim(y, yc, i * C, axis=0), stats

    y, stats = lax.fori_loop(
        0, B * T // C, body,
        (jnp.zeros_like(flat), jnp.zeros((len(ffn_stats(cfg)),), jnp.int32)))
    return y.reshape(B, T, H), stats


# ------------------------------------------------------------------ model

def param_shapes(cfg: DecoderConfig) -> dict:
    """{name: shape} of every parameter, in the names the model uses."""
    H = cfg.hidden_size
    s = {"embed.weight": (cfg.vocab_size, H)}
    for l, kind in enumerate(cfg.kinds):
        pre = f"layers.{l}"
        parallel = cfg.norm_placement == "parallel"  # ONE norm a block
        fkind, width = cfg.ffns[l]
        # a part that is absent declares nothing, its norm neither
        if kind != "none" or parallel:
            s.update(_norm_shapes(cfg, pre + ".attn_norm", H))
        s.update(ATTENTIONS[kind][1](cfg, pre + ".attn"))
        if fkind != "none" and not parallel:
            s.update(_norm_shapes(cfg, pre + ".ffn_norm", H))
        s.update(FFNS[fkind][1](cfg, pre + ".ffn", width))
    s.update(_norm_shapes(cfg, "final_norm", H))
    if not cfg.tie_word_embeddings:
        s["head.weight"] = (H, cfg.vocab_size)
    return s


def is_norm_scale(name: str) -> bool:
    return name.endswith("norm.weight")


def initial_value(name: str, shape, key, std: float):
    """A parameter's initial float32 value by the kind its name states:
    norm scales 1, biases 0, a mamba2 layer's skip ``D`` 1, a gated_delta or
    mamba2 layer's ``A_log`` = log U(0, 16),
    ``dt_bias`` = softplus^-1 of a step drawn log-uniform in [0.001, 0.1]
    and convolution U(-k^-1/2, k^-1/2) (the public implementation's), every
    other leaf N(0, std)."""
    if is_norm_scale(name):
        return jnp.ones(shape, jnp.float32)
    if name.endswith(".dt_bias"):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name.endswith(".bias"):
        return jnp.zeros(shape, jnp.float32)
    if name.endswith(".A_log") and len(shape) == 2:
        # a mamba1 layer's [N, E]: log(1 .. N) along the state's lanes
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
    if name.endswith(".A_log"):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0))
    if name.endswith(".D"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith(".conv.weight"):
        r = shape[-1] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -r, r)
    return std * jax.random.normal(key, shape, jnp.float32)


#: the kinds whose function is told its layer and handed ``_forward``'s carry
_CARRIED = ("dense", "sliding", "cross", "mamba1", "gmu")


def block(cfg, p, l, x, start, cache=None, flash_ok=False, lengths=None,
          cuts=None, carry=None):
    """One block over the residual stream ``x [B, T, hidden]``: returns
    (x, the layer's new pool entries, its routing statistics). ``carry``:
    what ``_forward`` hands from layer to layer (``differential_attention``
    has its entries); where the mixer cut the batch to the last real token
    (``cfg.cut_layer``), the stream and the carried memories are cut with
    it."""
    pre = f"layers.{l}"
    kind = cfg.ffns[l][0]
    more = {"layer": l, "carry": carry} if cfg.kinds[l] in _CARRIED else {}
    # a part that is absent (kind "none") is not traced: no identity pass,
    # no norm; it hands back no pool entries / zero statistics
    mix = None if cfg.kinds[l] == "none" else functools.partial(
        ATTENTIONS[cfg.kinds[l]][0], cfg, p, pre + ".attn", start=start,
        cache=cache, flash_ok=flash_ok, lengths=lengths, cuts=cuts, **more)
    feed = None if kind == "none" else functools.partial(
        ffn, cfg, p, pre + ".ffn", kind=kind)
    new, stats = (), jnp.zeros((len(ffn_stats(cfg)),), jnp.int32)
    if cfg.norm_placement == "parallel":
        h = _norm(cfg, x, p, pre + ".attn_norm")
        parts = []
        if mix:
            a, new = mix(h=h)
            parts.append(a)
        if feed:
            y, stats = feed(g=h)
            parts.append(y)
        for part in parts:      # both from the ONE norm, then the sums
            x = x + part
    elif cfg.norm_placement == "post":
        if mix:
            a, new = mix(h=x)
            x = x + _norm(cfg, a, p, pre + ".attn_norm")
        if feed:
            y, stats = feed(g=x)
            x = x + _norm(cfg, y, p, pre + ".ffn_norm")
    else:
        if mix:
            a, new = mix(h=_norm(cfg, x, p, pre + ".attn_norm"))
            if a.shape[1] != x.shape[1]:    # cut to the last real token
                x = _last_rows(x, carry["last"])
                for key in [k for k in carry if k[0] == "m"]:
                    carry[key] = _last_rows(carry[key], carry["last"])
            x = x + a
        if feed:
            y, stats = feed(g=_norm(cfg, x, p, pre + ".ffn_norm"))
            x = x + y
    if "latent" in cfg.kinds:
        read = walk = jnp.zeros((), jnp.int32)
        if cfg.kinds[l] == "latent" and cache is not None:
            # a live slot (its first block is mapped) attends every cached
            # token up to the last it wrote
            pool, table, *plan = cache
            live = table[:, 0] >= 0
            read = jnp.sum(jnp.where(live, start + x.shape[1], 0))
            if plan:
                walk = _latent.shared_walk_tokens(plan[0], pool.shape[2])
        stats = jnp.concatenate(
            [stats, jnp.stack([read, walk]).astype(jnp.int32)])
    if "sliding" in cfg.kinds:
        win = full = jnp.zeros((), jnp.int32)
        if cache is not None and cfg.kinds[l] in ("sliding", "dense"):
            # a live slot (the block of its last token is mapped: a sliding
            # layer's first blocks are not) attends up to its window
            table, ps = cache[-1], cache[0].shape[2]
            last = start + x.shape[1] - 1
            live = jnp.take_along_axis(
                table, jnp.minimum(last // ps, table.shape[1] - 1)[:, None],
                axis=1)[:, 0] >= 0
            ctx = jnp.where(live, last + 1, 0)
            if cfg.kinds[l] == "sliding":
                win = jnp.sum(jnp.minimum(ctx, cfg.sliding_window))
            else:
                full = jnp.sum(ctx)
        stats = jnp.concatenate(
            [stats, jnp.stack([win, full]).astype(jnp.int32)])
    if {"mamba2", "mamba1"} & set(cfg.kinds):
        stepped = jnp.zeros((), jnp.int32)
        if cfg.kinds[l] in ("mamba2", "mamba1") and cache is not None \
                and cache[2] is None:
            # the slots whose state the recurrent step advanced: those that
            # run a request (a dead slot sits at position 0)
            stepped = jnp.sum(start > 0)
        stats = jnp.concatenate([stats, stepped[None].astype(jnp.int32)])
    if "cross" in cfg.kinds:
        read = jnp.zeros((), jnp.int32)
        src = l if cfg.kinds[l] == "dense" else cfg.sources[l]
        if cache is not None and ("kv", src) in carry:
            # a live slot (its first block is mapped) reads its context
            table = carry[("kv", src)][2]
            read = jnp.sum(jnp.where(table[:, 0] >= 0,
                                     carry["qpos"][:, -1] + 1, 0))
        stats = jnp.concatenate([stats, read[None].astype(jnp.int32)])
    return x, new, stats


class DecoderLM(Layer):
    """The decoder of a ``DecoderConfig`` with the serving engine's model
    protocol. Parameters are flat, named as ``param_shapes`` names them."""

    def __init__(self, cfg: DecoderConfig):
        super().__init__()
        self.cfg = cfg
        dt = jnp.dtype(cfg.dtype)
        key = jax.random.PRNGKey(0)
        for i, (name, shape) in enumerate(param_shapes(cfg).items()):
            if cfg.init == "zeros":
                v = jnp.zeros(shape, dt)
            else:
                v = initial_value(name, shape, jax.random.fold_in(key, i),
                                  cfg.initializer_range).astype(dt)
            self.add_parameter(name, Parameter(v))

    def _p(self):
        return {n: p._value for n, p in self._parameters.items()}

    # ---- the serving engine's protocol ----
    @property
    def max_context(self) -> int:
        return self.cfg.max_context

    def _pools(self, which: int):
        """The pools of every layer kind present, each with the layers that
        hold it (layers of one kind declare the same pools)."""
        kinds, out = self.cfg.kinds, []
        for kind in dict.fromkeys(kinds):
            layers = tuple(l for l, k in enumerate(kinds) if k == kind)
            out += [spec + (layers,)
                    for spec in ATTENTIONS[kind][which](self.cfg)]
        return out

    def cache_pools(self):
        """[(name, heads, width)] of the paged pools: K and V (a token's
        heads side by side, or head-major), and the indexer's keys; with
        the layers that hold them as a fourth entry where not every layer
        does."""
        cfg, L = self.cfg, self.cfg.num_layers
        # a sliding layer's pools stand in a page GROUP of their own: (its
        # name, the window it keeps), the declaration's fifth entry
        group = ("window", cfg.sliding_window)
        pools = [spec + (group,) if spec[0].endswith("_window")
                 else spec[:3] if len(spec[3]) == L else spec
                 for spec in self._pools(2)]
        if "cross" not in cfg.kinds:
            return pools
        # the ONE pool the cross layers share is declared once, held by the
        # layer that writes it; the sixth entry names every layer that
        # READS it (the holder and its cross layers): a token is charged
        # one page entry, whatever the number of readers
        readers = lambda held: tuple(
            l for l, (k, src) in enumerate(zip(cfg.kinds, cfg.sources))
            if l in held or (k == "cross" and src in held))
        return [spec if len(spec) > 4
                else spec + (("global", None), readers(spec[3]))
                for spec in pools]

    def state_pools(self):
        """[(name, per-slot shape, dtype, layers)] of the slot-indexed
        state: a gated_delta or mamba2 layer's packed ``S`` and convolution
        tail."""
        return self._pools(3)

    def selected_tokens(self, ctx):
        """Cached positions a decode step's attention reads for contexts
        ``ctx`` (array of live tokens per slot)."""
        if "indexed_sparse" not in self.cfg.kinds:
            return ctx
        return np.minimum(ctx, self.cfg.index_topk)

    @property
    def step_stats(self) -> Tuple[str, ...]:
        """Names of what a decode step counts, per layer."""
        return step_stats(self.cfg)

    def _forward(self, ids, start, caches=None, flash_ok=False, lengths=None,
                 cuts=None):
        cfg, p = self.cfg, self._p()
        x = p["embed.weight"][ids]
        news, stats = [], []
        latent = [l for l, k in enumerate(cfg.kinds) if k == "latent"]
        if caches is not None and latent and ids.shape[1] == 1:
            # a decode step over latent pools: which slots walk which pages
            # together is read from the table ONCE, for every layer
            pool, table = caches[latent[0]]
            plan = _latent.latent_decode_plan(table, start, pool.shape[2])
            if plan is not None:
                caches = [e + (plan,) if l in latent else e
                          for l, e in enumerate(caches)]
        # what a layer hands to later ones: the positions of the rows the
        # stream holds, a mamba1 layer's memory, a dense layer's written
        # K/V, and for an admission's prefill or extend the row of the last
        # real token, to which ``cfg.cut_layer`` cuts the batch
        T = ids.shape[1]
        carry = {"qpos": start[:, None] + jnp.arange(T, dtype=jnp.int32)}
        if lengths is not None and T > 1 and cfg.cut_layer is not None:
            carry["last"] = jnp.clip(lengths - 1, 0, T - 1)
        for l in range(cfg.num_layers):
            x, new, st = block(cfg, p, l, x, start,
                               None if caches is None else caches[l], flash_ok,
                               lengths, cuts, carry)
            if T > 1 and cfg.cut_layer is not None:
                # the stream stands in memory ONCE: left to itself the
                # compiler folds the chain of residual adds into each
                # consumer and keeps every layer's branch outputs to the
                # program's end (17 x 70 MB at 14,336 tokens, and the
                # program past the chip's memory; TPU compiler, PR 49)
                x = lax.optimization_barrier(x)
            news.append(tuple(Tensor(a) for a in new))
            stats.append(st)
        return x, news, jnp.stack(stats)

    def _logits(self, h):
        p = self._p()
        h = _norm(self.cfg, h, p, "final_norm")
        w = p["embed.weight"].T if self.cfg.tie_word_embeddings \
            else p["head.weight"]
        logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
        if self.cfg.logit_scale != 1.0:
            logits = logits * jnp.float32(self.cfg.logit_scale)
        return logits

    def forward(self, input_ids):
        """Logits ``[B, T, vocab]`` (float32) of a full causal pass."""
        ids = _ids(input_ids)
        x, _, _ = self._forward(ids, jnp.zeros((ids.shape[0],), jnp.int32))
        return Tensor(self._logits(x))

    def prefill_with_cache(self, input_ids, lengths=None, cuts=None):
        """(last real token's logits ``[B, V]``, per layer the pool entries
        ``[B, 1, T, width]`` for the engine to install; of a layer with
        recurrent state its state and tail before each of ``cuts [B, n]``,
        then at the end, ``[B * (n + 1), ...]``)."""
        ids = _ids(input_ids)
        B, T = ids.shape
        lengths = None if lengths is None else _ids(lengths)
        x, news, _ = self._forward(ids, jnp.zeros((B,), jnp.int32),
                                   flash_ok=True, lengths=lengths,
                                   cuts=None if cuts is None else _ids(cuts))
        if x.shape[1] == 1:     # the model cut the batch itself
            last = x[:, 0]
        elif lengths is None:
            last = x[:, T - 1]
        else:
            idx = jnp.clip(lengths - 1, 0, T - 1)
            last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        return Tensor(self._logits(last)), news

    def extend_step(self, tokens, caches, positions, lengths=None, cuts=None):
        """``tokens [B, T]`` at ``positions[b] + t`` over the paged pools:
        (logits ``[B, T, V]``, per layer the updated pools). ``lengths``
        says how many of a row's tokens are real, which a layer with
        recurrent state has to know; ``cuts [B, n]`` before which tokens it
        writes its state to the rows its cache entry names besides. A model
        with a ``cut_layer`` that is told ``lengths`` (an admission) hands
        back the last real token's logits alone, ``[B, 1, V]``."""
        ids = _ids(tokens)
        ids = ids[:, None] if ids.ndim == 1 else ids
        start = jnp.broadcast_to(_ids(positions), (ids.shape[0],))
        entries = [tuple(map(_raw, e)) for e in caches]
        x, news, stats = self._forward(
            ids, start, entries,
            lengths=None if lengths is None else _ids(lengths),
            cuts=None if cuts is None else _ids(cuts))
        return Tensor(self._logits(x)), news, Tensor(stats)

    def decode_step(self, tokens, caches, positions):
        """One token per slot: (logits ``[B, V]``, per layer the updated
        pools, routing statistics ``[layers, len(step_stats)]``)."""
        logits, news, stats = self.extend_step(tokens, caches, positions)
        return Tensor(logits._value[:, -1]), news, stats


def _raw(t):
    return t._value if isinstance(t, Tensor) else t


def _ids(t):
    return jnp.asarray(_raw(t)).astype(jnp.int32)
