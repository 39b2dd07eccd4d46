"""Attention functionals.

Reference: python/paddle/nn/functional/flash_attention.py backed by
phi/kernels/gpu/flash_attn_kernel.cu (FlashAttention v1, SURVEY.md §5.7).
TPU-native design: the public API is identical, but the hot path dispatches to
a Pallas flash-attention kernel (paddle_tpu/kernels/flash_attention.py) on TPU
and to this fused jnp/XLA lowering elsewhere. Inputs are [batch, seq, heads,
head_dim] like the reference.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.flags import flag_value
from ...core.op_registry import register_op
from ...core.place import on_tpu
from ...ops._dispatch import apply, as_tensor


def _sdpa_ref(q, k, v, mask=None, dropout_p=0.0, causal=False, scale=None, dropout_key=None):
    """Reference lowering: [B, S, H, D] in, [B, S, H, D] out, f32 softmax."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = (q * s).astype(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k, preferred_element_type=jnp.float32)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(causal_mask, logits, jnp.float32(-1e30))
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.float32(-1e30))
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def _use_pallas(q_dtype) -> bool:
    return flag_value("use_pallas_kernels") and on_tpu()


@register_op("nn.scaled_dot_product_attention")
def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True, name=None
):
    query, key, value = as_tensor(query), as_tensor(key), as_tensor(value)
    tensors = [query, key, value] + ([as_tensor(attn_mask)] if attn_mask is not None else [])
    dropout_key = None
    if dropout_p > 0.0 and training:
        from ...core import random as _random

        dropout_key = _random.next_key()

    if _use_pallas(query._jdtype()) and attn_mask is None and dropout_p == 0.0:
        from ...kernels.flash_attention import _pick_blocks, flash_attention_fwd

        if _pick_blocks(query.shape[1])[0] is not None:

            def fn(q, k, v):
                return flash_attention_fwd(q, k, v, causal=is_causal)

            return apply("sdpa_pallas", fn, query, key, value)

    def fn(q, k, v, *rest):
        mask = rest[0] if rest else None
        return _sdpa_ref(q, k, v, mask=mask, dropout_p=dropout_p if training else 0.0, causal=is_causal, dropout_key=dropout_key)

    return apply("sdpa", fn, *tensors)


import functools as _functools


def _cp_body(mode, is_causal, scale, axis_name):
    from ...distributed.fleet.meta_parallel.sequence_parallel import (
        ring_attention, ulysses_attention)

    def body(ql, kl, vl):
        if mode == "ulysses":
            return ulysses_attention(ql, kl, vl, axis_name, causal=is_causal, scale=scale)
        return ring_attention(ql, kl, vl, axis_name, causal=is_causal, scale=scale)

    return body


@_functools.lru_cache(maxsize=64)
def _cp_sharded(mesh, mode, is_causal, scale, axis_name):
    """Cached jitted shard_map for context-parallel attention: one compile
    per (mesh, mode, causal, scale, axis, shape) instead of per call."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name)
    return jax.jit(shard_map(
        _cp_body(mode, is_causal, scale, axis_name), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset({axis_name}), check_vma=False,
    ))


@register_op("nn.context_parallel_attention")
def context_parallel_attention(query, key, value, mode: str = "ring",
                               is_causal: bool = False, scale=None,
                               axis_name: str = "sep", name=None):
    """Attention over a sequence-sharded residual stream (SURVEY §5.7 —
    absent in the reference; this is where the TPU build exceeds it).

    query/key/value: [B, S, H, D] GLOBAL arrays whose seq dim is sharded
    over the `axis_name` mesh axis. Runs ring attention (ppermute K/V ring,
    blockwise-softmax accumulation) or Ulysses (all_to_all head<->seq
    reshard) inside a shard_map manual over that axis only; dp/mp stay under
    GSPMD auto. Differentiable (the tape records the whole shard_map vjp).
    """
    from ...distributed.topology import get_hybrid_communicate_group

    query, key, value = as_tensor(query), as_tensor(key), as_tensor(value)
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        raise RuntimeError("context_parallel_attention needs fleet.init with sep_degree set")
    mesh = hcg.get_mesh()
    if mode not in ("ring", "ulysses"):
        raise ValueError(f"mode must be 'ring' or 'ulysses', got {mode!r}")

    def fn(q, k, v):
        # already inside a region manual over this axis (the pp pipeline's
        # shard_map includes 'sep' in its manual set): values are local seq
        # shards, so run the ring directly — nesting another shard_map here
        # trips Shardy's manual-axis bounding
        ctx = jax.sharding.get_abstract_mesh()
        types = dict(zip(getattr(ctx, "axis_names", ()), getattr(ctx, "axis_types", ())))
        if types.get(axis_name) == jax.sharding.AxisType.Manual:
            return _cp_body(mode, is_causal, scale, axis_name)(q, k, v)
        use_mesh = ctx if axis_name in types else mesh
        # _cp_sharded returns a CACHED jitted callable (one compile per
        # distinct shape); under an outer trace the jit inlines
        return _cp_sharded(use_mesh, mode, is_causal, scale, axis_name)(q, k, v)

    return apply("cp_attention", fn, query, key, value)


@register_op("nn.flash_attention")
def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False, fixed_seed_offset=None, training=True, name=None):
    """paddle.nn.functional.flash_attention API (flash_attention.py in reference)."""
    out = scaled_dot_product_attention(
        query, key, value, attn_mask=None, dropout_p=dropout, is_causal=causal, training=training
    )
    if return_softmax:
        return out, None
    return out, None


@register_op("nn.flash_attn_unpadded")
def flash_attn_unpadded(
    query, key, value, cu_seqlens_q, cu_seqlens_k, max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0, causal=False, return_softmax=False, training=True, name=None
):
    """Varlen API parity: runs dense SDPA with a segment mask built from cu_seqlens."""
    query, key, value = as_tensor(query), as_tensor(key), as_tensor(value)
    cu_q = as_tensor(cu_seqlens_q)

    def fn(q, k, v, cq):
        # inputs are packed [total_tokens, heads, dim]; reconstruct batch mask
        total, h, d = q.shape
        b = cq.shape[0] - 1
        seg_ids = jnp.cumsum(jnp.zeros(total, jnp.int32).at[cq[1:-1]].add(1))
        qb = q[None]  # treat packed dim as one batch of length total
        kb = k[None]
        mask = (seg_ids[:, None] == seg_ids[None, :])[None, None]
        out = _sdpa_ref(qb, kb, v[None], mask=mask, causal=causal, scale=scale)
        return out[0]

    return apply("flash_attn_unpadded", fn, query, key, value, cu_q), None
