"""Fused transformer layers (fluid/operators/fused/fused_attention_op.cu,
fused_feedforward analogs). "Fused" on TPU means: route through the flash
attention Pallas kernel + let XLA fuse the elementwise chain; the API carries
the reference's pre/post-LN contract."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import nn
from ...nn import functional as F
from ...nn.layer.layers import Layer


class FusedMultiHeadAttention(Layer):
    def __init__(
        self,
        embed_dim: int,
        num_heads: int,
        dropout_rate: float = 0.0,
        attn_dropout_rate: float = 0.0,
        normalize_before: bool = False,
        need_weights: bool = False,
        qkv_weight_attr=None,
        epsilon: float = 1e-5,
        name=None,
    ):
        super().__init__()
        if embed_dim % num_heads:
            raise ValueError(f"num_heads ({num_heads}) must evenly divide embed_dim ({embed_dim})")
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.head_dim = embed_dim // num_heads
        self.normalize_before = normalize_before
        self.attn_dropout_rate = attn_dropout_rate
        self.qkv = nn.Linear(embed_dim, 3 * embed_dim)
        self.proj = nn.Linear(embed_dim, embed_dim)
        self.ln = nn.LayerNorm(embed_dim, epsilon=epsilon)
        self.dropout = nn.Dropout(dropout_rate)

    def forward(self, x, attn_mask=None):
        residual = x
        if self.normalize_before:
            x = self.ln(x)
        B, S = x.shape[0], x.shape[1]
        qkv = self.qkv(x).reshape([B, S, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.attn_dropout_rate, training=self.training
        )
        out = self.dropout(self.proj(out.reshape([B, S, self.embed_dim])))
        out = residual + out
        if not self.normalize_before:
            out = self.ln(out)
        return out


class FusedFeedForward(Layer):
    def __init__(
        self,
        d_model: int,
        dim_feedforward: int,
        dropout_rate: float = 0.1,
        activation: str = "relu",
        act_dropout_rate=None,
        epsilon: float = 1e-5,
        normalize_before: bool = False,
        name=None,
    ):
        super().__init__()
        self.fc1 = nn.Linear(d_model, dim_feedforward)
        self.fc2 = nn.Linear(dim_feedforward, d_model)
        self.ln = nn.LayerNorm(d_model, epsilon=epsilon)
        self.dropout = nn.Dropout(dropout_rate)
        self.act_dropout = nn.Dropout(dropout_rate if act_dropout_rate is None else act_dropout_rate)
        self.act = getattr(F, activation)
        self.normalize_before = normalize_before

    def forward(self, x):
        residual = x
        if self.normalize_before:
            x = self.ln(x)
        out = self.fc2(self.act_dropout(self.act(self.fc1(x))))
        out = residual + self.dropout(out)
        if not self.normalize_before:
            out = self.ln(out)
        return out


class FusedTransformerEncoderLayer(Layer):
    def __init__(
        self,
        d_model: int,
        nhead: int,
        dim_feedforward: int,
        dropout_rate: float = 0.1,
        activation: str = "relu",
        attn_dropout_rate=None,
        act_dropout_rate=None,
        normalize_before: bool = False,
        name=None,
    ):
        super().__init__()
        self.attn = FusedMultiHeadAttention(
            d_model,
            nhead,
            dropout_rate=dropout_rate,
            attn_dropout_rate=attn_dropout_rate if attn_dropout_rate is not None else dropout_rate,
            normalize_before=normalize_before,
        )
        self.ffn = FusedFeedForward(
            d_model,
            dim_feedforward,
            dropout_rate=dropout_rate,
            activation=activation,
            act_dropout_rate=act_dropout_rate,
            normalize_before=normalize_before,
        )

    def forward(self, src, src_mask=None):
        return self.ffn(self.attn(src, attn_mask=src_mask))


class FusedLinear(Layer):
    """Linear whose matmul+bias fuses into one dot (reference FusedLinear /
    fused_gemm_epilogue). On TPU, XLA fuses the epilogue already — the class
    exists so checkpoints and code port unchanged."""

    def __init__(self, in_features, out_features, weight_attr=None, bias_attr=None, transpose_weight=False, name=None):
        super().__init__()
        self.transpose_weight = transpose_weight
        shape = [out_features, in_features] if transpose_weight else [in_features, out_features]
        self.weight = self.create_parameter(shape, attr=weight_attr)
        if bias_attr is False:
            self.bias = None
            self.add_parameter("bias", None)
        else:
            self.bias = self.create_parameter([out_features], attr=None if bias_attr is True else bias_attr, is_bias=True)

    def forward(self, x):
        w = self.weight
        if self.transpose_weight:
            from ...ops.linalg import t as _t

            w = _t(w)
        return F.linear(x, w, self.bias)


class FusedBiasDropoutResidualLayerNorm(Layer):
    """out = LayerNorm(residual + dropout(x + bias)) in one fused chain
    (reference FusedBiasDropoutResidualLayerNorm)."""

    def __init__(self, embed_dim, dropout_rate=0.5, weight_attr=None, bias_attr=None, epsilon=1e-5, name=None):
        super().__init__()
        self.linear_bias = self.create_parameter([embed_dim], is_bias=True)
        self.dropout = nn.Dropout(dropout_rate)
        self.ln = nn.LayerNorm(embed_dim, epsilon=epsilon)

    def forward(self, x, residual):
        return self.ln(residual + self.dropout(x + self.linear_bias))


class FusedDropoutAdd(Layer):
    """out = dropout(x) + y (reference FusedDropoutAdd)."""

    def __init__(self, p=0.5, mode="upscale_in_train", name=None):
        super().__init__()
        self.dropout = nn.Dropout(p, mode=mode)

    def forward(self, x, y):
        return self.dropout(x) + y


class FusedEcMoe(Layer):
    """Expert-choice MoE layer (reference FusedEcMoe): gate scores route each
    token to top experts; expert FFNs run as one batched einsum over the
    expert dim (MXU-batched, the TPU-native layout)."""

    def __init__(self, hidden_size, inter_size, num_experts, act_type="gelu", weight_attr=None, bias_attr=None):
        super().__init__()
        self.num_experts = num_experts
        self.act_type = act_type
        self.gate = nn.Linear(hidden_size, num_experts)
        self.w1 = self.create_parameter([num_experts, hidden_size, inter_size])
        self.b1 = self.create_parameter([num_experts, 1, inter_size], is_bias=True)
        self.w2 = self.create_parameter([num_experts, inter_size, hidden_size])
        self.b2 = self.create_parameter([num_experts, 1, hidden_size], is_bias=True)

    def forward(self, x, gate_logits=None):
        from ...ops._dispatch import apply, as_tensor

        if gate_logits is None:
            gate_logits = self.gate(x)
        act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu}[self.act_type]
        num_experts = self.num_experts

        def f(xv, gv, w1, b1, w2, b2):
            B, S, H = xv.shape
            T = B * S
            probs = jax.nn.softmax(gv.reshape(T, num_experts), -1)  # [T, E]
            flat = xv.reshape(T, H)
            # expert-choice routing: each expert picks its top-capacity tokens
            # (Zhou et al.; the reference kernel's contract) — capacity 2T/E
            capacity = max(1, min(T, (2 * T) // num_experts))
            expert_scores = probs.T  # [E, T]
            top_p, top_idx = jax.lax.top_k(expert_scores, capacity)  # [E, C]
            chosen = flat[top_idx]  # [E, C, H] gathered per expert
            h = act(jnp.einsum("ech,ehi->eci", chosen, w1) + b1)
            out = jnp.einsum("eci,eih->ech", h, w2) + b2  # [E, C, H]
            # combine: scatter-add each expert's outputs back, weighted by prob
            weighted = out * top_p[..., None]
            mixed = jnp.zeros((T, H), xv.dtype)
            for e in range(num_experts):  # E is small and static; unrolled adds fuse
                mixed = mixed.at[top_idx[e]].add(weighted[e])
            return mixed.reshape(B, S, H)

        return apply(
            "fused_ec_moe", f, as_tensor(x), as_tensor(gate_logits),
            self.w1, self.b1, self.w2, self.b2,
        )


class FusedMultiTransformer(Layer):
    """Stacked pre-LN decoder layers in ONE op (reference
    incubate/nn/layer/fused_transformer.py:1021 FusedMultiTransformer, the
    inference fast path of fused_multi_transformer_op.cu).

    TPU re-design: all layers' weights live STACKED on a leading [L, ...]
    dim and the block chain runs as a lax.scan — one traced block regardless
    of depth (compile time O(1) in L), with XLA fusing the intra-block
    chain. KV caches are [L, B, H, S_max, D] pairs; ``time_step`` selects
    the single-token decode path (write K/V at the position, attend over the
    valid prefix) — the generation loop the CUDA kernel serves.
    """

    def __init__(self, embed_dim, num_heads, dim_feedforward, dropout_rate=0.0,
                 activation="gelu", normalize_before=True, num_layers=1,
                 epsilon=1e-5, kv_num_heads=None, name=None):
        super().__init__()
        if embed_dim % num_heads:
            raise ValueError(f"embed_dim {embed_dim} % num_heads {num_heads}")
        if not normalize_before:
            raise NotImplementedError("FusedMultiTransformer is pre-LN only "
                                      "(reference normalize_before=True path)")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        # GQA serving: K/V carry kv_num_heads (< num_heads) — the KV cache
        # shrinks by num_heads/kv_num_heads, the binding memory at long S
        self.kv_num_heads = kv_num_heads if kv_num_heads is not None else num_heads
        if num_heads % self.kv_num_heads:
            raise ValueError(
                f"num_heads {num_heads} % kv_num_heads {self.kv_num_heads}")
        self.dim_feedforward = dim_feedforward
        self.num_layers = num_layers
        self.epsilon = epsilon
        self._act = activation
        L, H, F_ = num_layers, embed_dim, dim_feedforward
        qkv_out = (num_heads + 2 * self.kv_num_heads) * self.head_dim
        mk = self.create_parameter
        from ...nn import initializer as I

        ones, zeros = I.Constant(1.0), I.Constant(0.0)
        self.ln1_w = mk([L, H], default_initializer=ones)
        self.ln1_b = mk([L, H], default_initializer=zeros, is_bias=True)
        self.qkv_w = mk([L, H, qkv_out])
        self.qkv_b = mk([L, qkv_out], default_initializer=zeros, is_bias=True)
        self.proj_w = mk([L, H, H])
        self.proj_b = mk([L, H], default_initializer=zeros, is_bias=True)
        self.ln2_w = mk([L, H], default_initializer=ones)
        self.ln2_b = mk([L, H], default_initializer=zeros, is_bias=True)
        self.ffn1_w = mk([L, H, F_])
        self.ffn1_b = mk([L, F_], default_initializer=zeros, is_bias=True)
        self.ffn2_w = mk([L, F_, H])
        self.ffn2_b = mk([L, H], default_initializer=zeros, is_bias=True)

    def gen_cache(self, batch_size: int, max_seq_len: int, dtype="float32"):
        """Empty [L, B, heads, S_max, D] K and V caches (reference
        gen_cache contract for the generation loop)."""
        import jax.numpy as jnp

        from ...core.tensor import Tensor

        shape = (self.num_layers, batch_size, self.kv_num_heads, max_seq_len, self.head_dim)
        return Tensor(jnp.zeros(shape, dtype)), Tensor(jnp.zeros(shape, dtype))

    def forward(self, x, attn_mask=None, caches=None, time_step=None):
        """Prefill: x [B, S, H] -> [B, S, H] (causal); filling caches when
        given. Decode: x [B, 1, H] + time_step -> one-token output with the
        caches advanced. Returns (out, (k_cache, v_cache)) when caches are
        passed, else out — the reference's cache_kvs contract."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ...kernels.paged_attention import decode_attend
        from ...kernels.pools import write_kv
        from ...ops._dispatch import apply, as_tensor

        if attn_mask is not None:
            raise NotImplementedError(
                "FusedMultiTransformer is causal-only (the generation fast "
                "path); custom attn_mask is unsupported")
        x = as_tensor(x)
        nh, hd, eps, act_name = self.num_heads, self.head_dim, self.epsilon, self._act
        nkv = self.kv_num_heads
        rep = nh // nkv  # query heads per shared K/V head (1 = MHA)

        def ln(v, w, b):
            mu = v.mean(-1, keepdims=True)
            var = ((v - mu) ** 2).mean(-1, keepdims=True)
            return (v - mu) * jax.lax.rsqrt(var + eps) * w + b

        def act(v):
            return jax.nn.gelu(v, approximate=False) if act_name == "gelu" else jax.nn.relu(v)

        def block(h, p, k_layer, v_layer, step):
            """One decoder block on [B, T, H]; k_layer/v_layer are this
            layer's cache slices or None."""
            (l1w, l1b, qkvw, qkvb, pw, pb, l2w, l2b, f1w, f1b, f2w, f2b) = p
            B, T = h.shape[0], h.shape[1]
            z = ln(h, l1w, l1b)
            qkv = z @ qkvw + qkvb  # [B, T, (nh + 2*nkv)*hd]
            q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
            q = q.reshape(B, T, nh, hd).transpose(0, 2, 1, 3)  # [B, nh, T, hd]
            k = k.reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)  # [B, nkv, T, hd]
            v = v.reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
            # caches store nkv heads (the GQA memory win); queries see the
            # shared heads via a broadcast XLA keeps fused into the einsum
            expand = (lambda t: jnp.repeat(t, rep, axis=1)) if rep > 1 else (lambda t: t)
            if k_layer is not None:
                if step is not None:
                    # decode: shared static-cache write/attend
                    # (kernels/pools, paged_attention) — what the GPT serving
                    # engine runs, so the two cached decode implementations
                    # cannot drift
                    k_layer = write_kv(k_layer, k, step)
                    v_layer = write_kv(v_layer, v, step)
                    o = decode_attend(q, k_layer, v_layer, step)
                else:
                    # prefill: causal attention; caches filled with the prefix
                    k_layer = lax.dynamic_update_slice(k_layer, k, (0, 0, 0, 0))
                    v_layer = lax.dynamic_update_slice(v_layer, v, (0, 0, 0, 0))
                    o = _causal_attn(q, expand(k), expand(v))
            else:
                o = _causal_attn(q, expand(k), expand(v))
            o = o.transpose(0, 2, 1, 3).reshape(B, T, nh * hd)
            h = h + (o @ pw + pb)
            z = ln(h, l2w, l2b)
            h = h + (act(z @ f1w + f1b) @ f2w + f2b)
            return h, k_layer, v_layer

        def _causal_attn(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=jnp.float32) / jnp.sqrt(float(hd)).astype(jnp.float32)
            T = q.shape[2]
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1).astype(v.dtype), v)

        params = (self.ln1_w, self.ln1_b, self.qkv_w, self.qkv_b,
                  self.proj_w, self.proj_b, self.ln2_w, self.ln2_b,
                  self.ffn1_w, self.ffn1_b, self.ffn2_w, self.ffn2_b)

        if caches is None:
            def fn(xv, *pv):
                def body(h, layer_p):
                    h2, _, _ = block(h, layer_p, None, None, None)
                    return h2, None
                out, _ = lax.scan(body, xv, tuple(pv))
                return out

            return apply("fused_multi_transformer", fn, x, *params)

        k_cache, v_cache = caches
        k_cache, v_cache = as_tensor(k_cache), as_tensor(v_cache)
        step_t = as_tensor(time_step) if time_step is not None else None
        has_step = step_t is not None

        def fn(xv, kc, vc, *rest):
            if has_step:
                step = rest[0].astype(jnp.int32).reshape(())
                pv = rest[1:]
            else:
                step, pv = None, rest

            def body(h, layer_in):
                layer_p, kl, vl = layer_in[:-2], layer_in[-2], layer_in[-1]
                h2, kl2, vl2 = block(h, layer_p, kl, vl, step)
                return h2, (kl2, vl2)

            out, (nk, nv) = lax.scan(body, xv, tuple(pv) + (kc, vc))
            return out, nk, nv

        args = (x, k_cache, v_cache) + ((step_t,) if has_step else ()) + params
        out, nk, nv = apply("fused_multi_transformer_cached", fn, *args)
        return out, (nk, nv)
