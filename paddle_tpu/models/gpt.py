"""GPT: the flagship decoder-only LM (PaddleNLP gpt-3 / test fixture
auto_parallel_gpt_model.py analog — SURVEY.md §4, §6 north-star configs).

TPU-first design choices:
- Every projection is a fleet mp layer (ColumnParallel qkv+fc1, RowParallel
  proj+fc2, VocabParallelEmbedding): on one chip they are plain dense layers;
  under a mesh the P(*, 'mp') annotations make GSPMD emit Megatron TP with
  exactly two collectives per block.
- Attention runs through nn.functional.scaled_dot_product_attention, the seam
  where the Pallas flash kernel plugs in on TPU ([B, S, H, D] layout).
- `sequence_parallel=True` re-shards the residual stream P(dp, mp, None)
  between blocks, sharding LayerNorm/dropout work along seq over the mp axis
  (Megatron-SP — absent in the reference, SURVEY §5.7; the allgather/
  reduce-scatter seams fall out of the GSPMD annotations).
- bf16-friendly: params stay f32 (master copy lives in the optimizer),
  activations cast by amp or the caller.
"""

from __future__ import annotations

from dataclasses import dataclass

from jax.sharding import PartitionSpec as P

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.sharding_utils import annotate_parameter, maybe_shard
from ..nn import functional as F
from ..nn.layer.layers import Layer


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = None  # grouped-query attention: K/V heads shared by
    #                           num_heads/num_kv_heads query heads each
    #                           (1 = MQA, None = full MHA). Shrinks the
    #                           serving KV cache by the same ratio — a
    #                           capability the reference snapshot lacks.
    max_seq_len: int = 1024
    intermediate_size: int = None
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    sequence_parallel: bool = False
    context_parallel: str = "ring"  # attention scheme under a sep axis:
    #                                 'ring' (ppermute K/V) | 'ulysses' (a2a)
    use_recompute: bool = False
    recompute_policy: str = None  # None/'full' | 'dots_saveable' (keep MXU
    #                               outputs resident, replay elementwise only)
    recompute_interval: int = 1   # remat every k-th block (k=2 halves the
    #                               replay FLOPs at ~half the memory saving)
    loss_chunk: int = 0           # CE in seq chunks of this size (0 = off):
    #                               avoids materializing [B, S, V] fp32 logits
    initializer_range: float = 0.02
    # ---- GPT-MoE (reference incubate/distributed/models/moe) ----
    moe_num_experts: int = 0      # 0 = dense FFN everywhere
    moe_every_k: int = 2          # MoE FFN replaces the dense FFN in every
    #                               k-th block (blocks k-1, 2k-1, ...)
    moe_top_k: int = 2            # 2 = GShard gate, 1 = Switch gate
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01  # load-balance aux-loss weight
    moe_dispatch: str = "dense"   # 'quant' = block-scaled int8 token
    #                               exchanges over ep (incubate .../moe/
    #                               dispatch.py); routing stays fp32

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must divide num_heads")
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


# GPT-3 1.3B — the BASELINE.json pretrain config
GPT3_1p3B = dict(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16, max_seq_len=2048)
GPT_TINY = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=64)


def _batch_axes():
    """Mesh axes carrying the batch dim: dp, the ZeRO `sharding` axis (a
    sharded optimizer is still data parallelism for activations — dropping it
    here forced a replicate-over-sharding reshard every block), and ep
    (expert parallelism rides the data axes for non-expert compute,
    DeepSpeed-MoE style). Resolved against the ambient mesh at constraint
    time; order matches ShardedTrainStep's batch_spec."""
    from ..distributed.sharding_utils import data_axes

    return data_axes()


def _seq_spec(cfg: GPTConfig) -> P:
    """Residual-stream sharding between blocks: batch over dp (+ep); seq
    over the sep (context-parallel) axis when the ambient mesh has one, and
    over mp when Megatron-SP is on."""
    from ..distributed.sharding_utils import ambient_axis_names

    seq_axes = []
    if "sep" in ambient_axis_names():
        seq_axes.append("sep")
    if cfg.sequence_parallel:
        seq_axes.append("mp")
    return P(_batch_axes(), tuple(seq_axes) if seq_axes else None, None)


class GPTAttention(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        # GQA: the fused projection emits H query heads + 2*H_kv K/V heads
        # (H_kv == H is plain MHA, the 3H layout)
        qkv_out = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
        self.qkv = ColumnParallelLinear(cfg.hidden_size, qkv_out, gather_output=False)
        self.proj = RowParallelLinear(cfg.hidden_size, cfg.hidden_size, input_is_parallel=True)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x, kv_cache=None, cache_positions=None, return_kv=False):
        B, S = x.shape[0], x.shape[1]
        cfg = self.cfg
        from ..distributed.sharding_utils import ambient_axis_names
        from ..distributed.topology import get_hybrid_communicate_group

        qkv = self.qkv(x)  # [B, S, (H + 2*Hkv)*D/mp] sharded on last dim
        Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        if return_kv or kv_cache is not None:
            return self._serving_forward(qkv, B, S, kv_cache, cache_positions,
                                         return_kv)
        # heads over mp; seq stays sharded over sep when the axis is active
        # (gathering full-S here would defeat context parallelism's memory)
        seq_axis = "sep" if "sep" in ambient_axis_names() else None
        head_spec = P(_batch_axes(), seq_axis, "mp", None)
        q = maybe_shard(qkv[:, :, :Hq * D].reshape([B, S, Hq, D]), head_spec)
        k = qkv[:, :, Hq * D:(Hq + Hkv) * D].reshape([B, S, Hkv, D])
        v = qkv[:, :, (Hq + Hkv) * D:].reshape([B, S, Hkv, D])
        if Hkv != Hq:
            # expand shared K/V heads to the query-head count — exact GQA
            # semantics. A true broadcast (insert group dim, broadcast,
            # merge), NOT repeat_interleave: jnp.repeat lowers to
            # gather/concat which materializes K/V at full query-head
            # width; broadcast_in_dim XLA fuses into the attention matmuls
            rep = Hq // Hkv

            def _expand(tv):
                tv = jnp.broadcast_to(tv[:, :, :, None, :],
                                      (B, S, Hkv, rep, D))
                return tv.reshape(B, S, Hq, D)

            from ..ops._dispatch import apply

            k = apply("gqa_expand", _expand, k)
            v = apply("gqa_expand", _expand, v)
        k = maybe_shard(k, head_spec)
        v = maybe_shard(v, head_spec)
        hcg = get_hybrid_communicate_group()
        sep = hcg.get_sep_parallel_world_size() if hcg is not None else 1
        # inside a region already manual over sep (the pipeline), x is a
        # LOCAL seq shard and the ring MUST run (falling through to plain
        # attention would silently drop cross-chunk attention)
        import jax as _jax

        ctx_types = {}
        try:
            _m = _jax.sharding.get_abstract_mesh()
            ctx_types = dict(zip(_m.axis_names, _m.axis_types))
        except Exception:
            pass
        in_manual_sep = ctx_types.get("sep") == _jax.sharding.AxisType.Manual
        if sep > 1 and (in_manual_sep or S % sep == 0):
            # context parallelism: seq stays sharded over the sep axis and
            # attention runs as a ring (or Ulysses a2a) over it — the
            # long-context path (SURVEY §5.7). Indivisible GLOBAL S outside
            # a manual region (e.g. generation growing the prefix) falls
            # through to plain attention below, which is then exact.
            if cfg.dropout > 0 and self.training:
                raise NotImplementedError(
                    "attention dropout is unsupported under context "
                    "parallelism (sep_degree > 1); set dropout=0 or sep=1")
            out = F.context_parallel_attention(
                q, k, v, mode=cfg.context_parallel, is_causal=True)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, dropout_p=cfg.dropout, is_causal=True, training=self.training
            )
        out = out.reshape([B, S, cfg.hidden_size])
        return self.dropout(self.proj(out))

    def _serving_forward(self, qkv, B, S, kv_cache, cache_positions,
                         return_kv):
        """KV-cache serving paths over the same mp-sharded projections.

        Prefill (``return_kv=True``): ordinary causal attention over the
        (padded) prompt, plus this layer's K/V in cache layout
        ``[B, H_kv, S, D]`` for the engine to install in its static cache.
        Decode (``kv_cache=(k, v)`` each ``[B, H_kv, S_max, D]``): write the
        incoming token's K/V at ``cache_positions`` and attend the valid
        prefix through kernels/paged_attention's shared decode helpers (the
        same math FusedMultiTransformer's time_step path uses)."""
        from ..kernels.paged_attention import (
            decode_attend, paged_decode_attend, paged_extend_attend)
        from ..kernels.pools import paged_write_kv, write_kv
        from ..ops._dispatch import apply, as_tensor

        cfg = self.cfg
        Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = qkv[:, :, :Hq * D].reshape([B, S, Hq, D])
        k = qkv[:, :, Hq * D:(Hq + Hkv) * D].reshape([B, S, Hkv, D])
        v = qkv[:, :, (Hq + Hkv) * D:].reshape([B, S, Hkv, D])
        if return_kv:
            rep = Hq // Hkv

            def _expand(tv):
                tv = jnp.broadcast_to(tv[:, :, :, None, :],
                                      (B, S, Hkv, rep, D))
                return tv.reshape(B, S, Hq, D)

            k_att = apply("gqa_expand", _expand, k) if rep > 1 else k
            v_att = apply("gqa_expand", _expand, v) if rep > 1 else v
            out = F.scaled_dot_product_attention(
                q, k_att, v_att, is_causal=True, training=False)
            kv = apply("serving_kv_layout",
                       lambda kv_, vv: (kv_.transpose(0, 2, 1, 3),
                                        vv.transpose(0, 2, 1, 3)), k, v)
            out = out.reshape([B, S, cfg.hidden_size])
            return self.dropout(self.proj(out)), tuple(kv)

        if len(kv_cache) == 3:
            # block-paged cache: (k_pool, v_pool, page_table) — the table
            # routes this slot's token(s) to pages; the paged attend reads
            # only live pages (paged_decode_attend's dispatch: oracle einsum
            # on CPU, Pallas ragged kernel on TPU). S is static: S=1 is the
            # plain decode step, S>1 the multi-token extend (suffix prefill
            # after a prefix-cache splice / speculative verify-k), where
            # query t of row b sits at cache_positions[b] + t.
            kc, vc, table = kv_cache

            def _decode_paged(qv, kv_, vv, kcv, vcv, tblv, posv):
                qT = qv.transpose(0, 2, 1, 3)   # [B, Hq, S, D]
                kc2 = paged_write_kv(kcv, kv_.transpose(0, 2, 1, 3), tblv,
                                     posv)
                vc2 = paged_write_kv(vcv, vv.transpose(0, 2, 1, 3), tblv,
                                     posv)
                if S == 1:
                    o = paged_decode_attend(qT, kc2, vc2, tblv, posv)
                else:
                    o = paged_extend_attend(qT, kc2, vc2, tblv, posv)
                return o.transpose(0, 2, 1, 3), kc2, vc2

            o, kc2, vc2 = apply("serving_decode_attn", _decode_paged, q, k,
                                v, as_tensor(kc), as_tensor(vc),
                                as_tensor(table), as_tensor(cache_positions))
            out = o.reshape([B, S, cfg.hidden_size])
            return self.dropout(self.proj(out)), (kc2, vc2)

        if S > 1:
            raise NotImplementedError(
                "multi-token cached decode (extend_step / speculative "
                "verify) requires the paged KV layout; the dense cache "
                "only decodes one token per step")
        kc, vc = kv_cache

        def _decode(qv, kv_, vv, kcv, vcv, posv):
            qT = qv.transpose(0, 2, 1, 3)   # [B, Hq, 1, D]
            kc2 = write_kv(kcv, kv_.transpose(0, 2, 1, 3), posv)
            vc2 = write_kv(vcv, vv.transpose(0, 2, 1, 3), posv)
            o = decode_attend(qT, kc2, vc2, posv)
            return o.transpose(0, 2, 1, 3), kc2, vc2

        o, kc2, vc2 = apply("serving_decode_attn", _decode, q, k, v,
                            as_tensor(kc), as_tensor(vc),
                            as_tensor(cache_positions))
        out = o.reshape([B, S, cfg.hidden_size])
        return self.dropout(self.proj(out)), (kc2, vc2)


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.fc1 = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size, gather_output=False)
        self.fc2 = RowParallelLinear(cfg.intermediate_size, cfg.hidden_size, input_is_parallel=True)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x):
        return self.dropout(self.fc2(F.gelu(self.fc1(x), approximate=True)))


class GPTMoEMLP(Layer):
    """Expert-parallel MoE FFN — the GPT-MoE block's dense-FFN replacement
    (reference incubate/distributed/models/moe/moe_layer.py:261 MoELayer with
    global_scatter/global_gather index routing :117/:188).

    TPU-native: experts are first-class STACKED parameters [E, ...] whose
    dist_spec shards the expert dim over the `ep` mesh axis, and routing is
    the dense GShard/Switch capacity dispatch — two einsums against one-hot
    dispatch/combine tensors. Under an ep mesh GSPMD emits exactly the
    all-to-all pair the reference wrote by hand (asserted by
    tests/test_hlo_collectives.py), and the batched expert einsum stays on
    the owning devices. `aux_loss` carries the load-balancing gate term,
    folded into the LM loss with cfg.moe_aux_weight."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        E, d, f = cfg.moe_num_experts, cfg.hidden_size, cfg.intermediate_size
        self.cfg = cfg
        self.gate_weight = self.create_parameter([d, E])
        self.w1 = self.create_parameter([E, d, f])
        self.b1 = self.create_parameter([E, f], is_bias=True)
        self.w2 = self.create_parameter([E, f, d])
        self.b2 = self.create_parameter([E, d], is_bias=True)
        annotate_parameter(self.w1, P("ep", None, None))
        annotate_parameter(self.b1, P("ep", None))
        annotate_parameter(self.w2, P("ep", None, None))
        annotate_parameter(self.b2, P("ep", None))
        self.dropout = nn.Dropout(cfg.dropout)
        self.aux_loss = None

    def forward(self, x):
        from ..incubate.distributed.models.moe.moe_layer import moe_route
        from ..ops._dispatch import apply

        cfg = self.cfg
        B, S, d = x.shape[0], x.shape[1], x.shape[2]
        xt = x.reshape([-1, d])  # [T, d]
        T = xt.shape[0]
        capacity = max(1, int(cfg.moe_capacity_factor * T / cfg.moe_num_experts))

        import jax as _jax

        def run_experts(ein):
            def experts_fn(ei, w1, b1, w2, b2):
                # batched per-expert FFN in the activation dtype (bf16 on
                # the MXU); the expert dim stays sharded over ep end to end
                h = jnp.einsum("ecd,edf->ecf", ei, w1.astype(ei.dtype))
                h = _jax.nn.gelu(h + b1[:, None, :].astype(ei.dtype), approximate=True)
                o = jnp.einsum("ecf,efd->ecd", h, w2.astype(ei.dtype))
                return o + b2[:, None, :].astype(ei.dtype)

            return apply("moe_experts_fused", experts_fn, ein,
                         self.w1, self.b1, self.w2, self.b2)

        out, aux = moe_route(
            xt, self.gate_weight, "gshard" if cfg.moe_top_k == 2 else "switch",
            capacity, run_experts, dispatch_mode=cfg.moe_dispatch)
        self.aux_loss = aux
        return self.dropout(out.reshape([B, S, d]))


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig, use_moe: bool = False):
        super().__init__()
        self.cfg = cfg
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.mlp = GPTMoEMLP(cfg) if use_moe else GPTMLP(cfg)

    def forward(self, x, kv_cache=None, cache_positions=None, return_kv=False):
        # anatomy scope convention: attn / mlp / moe nest under the
        # enclosing block_NN scope (observability/anatomy.py)
        mlp_scope = "moe" if isinstance(self.mlp, GPTMoEMLP) else "mlp"
        # the residual-stream layout doubles as the fused LN kernel's row
        # spec: under sep / Megatron-SP the rows are sequence-sharded too
        spec = _seq_spec(self.cfg)
        x = maybe_shard(x, spec)
        if return_kv or kv_cache is not None:
            with jax.named_scope("attn"):
                a, kv = self.attn(self.ln1(x, spec=spec), kv_cache=kv_cache,
                                  cache_positions=cache_positions,
                                  return_kv=return_kv)
                x = x + a
            with jax.named_scope(mlp_scope):
                x = x + self.mlp(self.ln2(x, spec=spec))
            return maybe_shard(x, spec), kv
        with jax.named_scope("attn"):
            x = x + self.attn(self.ln1(x, spec=spec))
        with jax.named_scope(mlp_scope):
            x = x + self.mlp(self.ln2(x, spec=spec))
        return maybe_shard(x, spec)


class GPTEmbeddings(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.word_embeddings = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embeddings = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, input_ids, position_ids=None):
        import paddle_tpu as paddle

        if position_ids is None:
            position_ids = paddle.arange(input_ids.shape[1]).unsqueeze(0)
        h = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
        return self.dropout(h)


class GPTModel(Layer):
    """Transformer trunk: embeddings -> blocks -> final LN."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        k = max(cfg.moe_every_k, 1)
        self.layers = nn.LayerList([
            GPTBlock(cfg, use_moe=cfg.moe_num_experts > 0 and i % k == k - 1)
            for i in range(cfg.num_layers)])
        self.final_ln = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.moe_aux_loss = None
        self._init_weights()

    def _init_weights(self):
        import jax.numpy as jnp

        from ..core import random as _random

        std = self.cfg.initializer_range
        import jax

        for name, p in self.named_parameters():
            if p is None:
                continue
            if p._value.ndim >= 2:
                key = _random.default_generator.next_key()
                p._set_value_raw(std * jax.random.normal(key, p._value.shape, p._value.dtype))
            elif "bias" in name:
                p._set_value_raw(jnp.zeros_like(p._value))

    def forward(self, input_ids, position_ids=None, kv_caches=None,
                cache_positions=None, return_kv=False):
        if return_kv or kv_caches is not None:
            # serving paths: thread per-layer KV through the block stack
            # (prefill returns the prompt's K/V; decode updates the static
            # cache). Inference-only — recompute/MoE-aux machinery is the
            # training loop's concern.
            with jax.named_scope("embed"):
                h = self.embeddings(input_ids, position_ids)
            kvs = []
            for i, block in enumerate(self.layers):
                cache_i = kv_caches[i] if kv_caches is not None else None
                with jax.named_scope("block_%02d" % i):
                    h, kv = block(h, kv_cache=cache_i,
                                  cache_positions=cache_positions,
                                  return_kv=return_kv)
                kvs.append(kv)
            with jax.named_scope("final_ln"):
                h = self.final_ln(h, spec=_seq_spec(self.cfg))
            return h, kvs
        with jax.named_scope("embed"):
            h = self.embeddings(input_ids, position_ids)
        aux = None
        for i, block in enumerate(self.layers):
            # MoE blocks run outside recompute: their aux_loss is read by
            # the loss path this trace, and smuggling it out of a
            # jax.checkpoint region would leak tracers
            with jax.named_scope("block_%02d" % i):
                if self.cfg.use_recompute and self.training \
                        and i % max(self.cfg.recompute_interval, 1) == 0 \
                        and not isinstance(block.mlp, GPTMoEMLP):
                    from ..distributed.fleet.recompute import recompute

                    h = recompute(block, h, policy=self.cfg.recompute_policy)
                else:
                    h = block(h)
            if isinstance(block.mlp, GPTMoEMLP) and block.mlp.aux_loss is not None:
                aux = block.mlp.aux_loss if aux is None else aux + block.mlp.aux_loss
        self.moe_aux_loss = aux
        with jax.named_scope("final_ln"):
            return self.final_ln(h, spec=_seq_spec(self.cfg))


class GPTForCausalLM(Layer):
    """Trunk + (tied) LM head + causal-LM loss."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(cfg.hidden_size, cfg.vocab_size, has_bias=False, gather_output=False)

    def _logits(self, h):
        """LM head over final hidden states (tied or separate). The head
        matmul attributes to the ``loss`` anatomy scope — the chunked CE
        path fuses it with the loss, so both paths agree."""
        with jax.named_scope("loss"):
            if self.cfg.tie_word_embeddings:
                logits = h.matmul(self.gpt.embeddings.word_embeddings.weight, transpose_y=True)
                return maybe_shard(logits, P(_batch_axes(), None, "mp"))
            return self.lm_head(h)

    def forward(self, input_ids, position_ids=None):
        return self._logits(self.gpt(input_ids, position_ids))

    def _moe_aux(self):
        """Weighted MoE load-balance aux term from the LAST trunk forward
        (None for dense models). Callers inside the same trace only."""
        aux = getattr(self.gpt, "moe_aux_loss", None)
        if aux is None:
            return None
        return aux * self.cfg.moe_aux_weight

    def loss(self, logits, labels):
        """Next-token CE, labels already shifted by the data pipeline.
        For MoE configs the gate aux loss is added by forward_with_loss
        (this method sees only logits)."""
        V = logits.shape[-1]
        with jax.named_scope("loss"):
            return F.cross_entropy(
                logits.reshape([-1, V]), labels.reshape([-1])).mean()

    def forward_with_loss(self, input_ids, labels):
        """Fused trunk->loss path. With cfg.loss_chunk set, the LM-head matmul
        and fp32 cross-entropy run per sequence chunk under jax.checkpoint, so
        the full [B, S, V] fp32 logits tensor (2.7 GB at B=20, V=32k) never
        materializes — HBM saved buys batch, and batch buys MFU. Falls back to
        forward()+loss() when chunking is off or doesn't divide S."""
        import jax

        cfg = self.cfg
        chunk = getattr(cfg, "loss_chunk", 0)
        S = input_ids.shape[1]
        from ..distributed.topology import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
        mp = hcg.get_model_parallel_world_size() if hcg is not None else 1
        if not chunk or S % chunk or mp > 1:
            # vocab-parallel logits go through ParallelCrossEntropy instead
            loss = self.loss(self.forward(input_ids), labels)
            aux = self._moe_aux()
            return loss if aux is None else loss + aux
        h = self.gpt(input_ids)
        if cfg.tie_word_embeddings:
            W = self.gpt.embeddings.word_embeddings.weight  # [V, Hd]
            logits_of = lambda hc, Wv: hc @ Wv.T
        else:
            W = self.lm_head.weight  # [Hd, V]
            logits_of = lambda hc, Wv: hc @ Wv
        hv = h._value
        yv = labels._value if isinstance(labels, Tensor) else jnp.asarray(labels)
        Wv = W._value
        B, _, Hd = hv.shape
        n = S // chunk
        hs = hv.reshape(B, n, chunk, Hd).swapaxes(0, 1)   # [n, B, c, Hd]
        ys = yv.reshape(B, n, chunk).swapaxes(0, 1)

        def chunk_ce(h_c, y_c, Wv):
            logits = logits_of(h_c, Wv).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, y_c[..., None].astype(jnp.int32), axis=-1)[..., 0]
            return (lse - gold).sum()

        ckpt_ce = jax.checkpoint(chunk_ce)

        def body(acc, xy):
            h_c, y_c = xy
            return acc + ckpt_ce(h_c, y_c, Wv), None

        with jax.named_scope("loss"):
            total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                    (hs, ys))
        loss = Tensor(total / (B * S))
        aux = self._moe_aux()
        return loss if aux is None else loss + aux


    # ---- compiled pipeline-parallel protocol (PipelineSpec) ----
    def embed(self, input_ids):
        """Pre-stage for pipeline parallelism: embeddings only."""
        return self.gpt.embeddings(input_ids)

    def head_loss(self, h, labels):
        """Post-stage for pipeline parallelism: final LN + LM head + CE."""
        return self.loss(self._logits(
            self.gpt.final_ln(h, spec=_seq_spec(self.cfg))), labels)

    def pipeline_spec(self):
        """PipelineSpec protocol consumed by make_sharded_train_step when the
        mesh carries a pp axis (the PipelineLayer/LayerDesc partition role,
        reference pp_layers.py:56: embeddings = pre, the homogeneous GPTBlock
        stack = stages, final LN + head + loss = post)."""
        from ..distributed.fleet.meta_parallel.pipeline_parallel import (
            make_layer_stack_pipeline_spec)

        if self.cfg.moe_num_experts > 0:
            if self.cfg.moe_every_k != 1:
                raise NotImplementedError(
                    "pipelined GPT-MoE needs a homogeneous stack: set "
                    "moe_every_k=1 (every block MoE) so the scanned stage "
                    "params stack; mixed dense/MoE stacks compose with "
                    "dp x ep x sharding x mp instead")
            # every block is MoE: the gate aux rides the schedule via the
            # block_with_aux protocol (an attribute write can't leave the
            # scan), weighted into the loss like the unpipelined objective
            return make_layer_stack_pipeline_spec(
                self, self.gpt.layers[0], "gpt.layers", self.cfg.num_layers,
                context_parallel=True, aux_attr="mlp.aux_loss",
                aux_weight=self.cfg.moe_aux_weight)
        return make_layer_stack_pipeline_spec(
            self, self.gpt.layers[0], "gpt.layers", self.cfg.num_layers,
            context_parallel=True)  # GPTAttention handles manual-sep shards

    # ---- serving decode protocol (paddle_tpu/serving engine) ----
    def prefill_with_cache(self, input_ids, lengths=None, position_ids=None):
        """Serving prefill: one causal forward over the (right-padded)
        prompt that also returns each layer's K/V in cache layout
        ``[B, H_kv, T, D]``. ``lengths`` (``[B]`` ints, or None for the full
        width) selects each row's LAST REAL token; returns
        ``(last_logits [B, V], kvs)``. Padding rows beyond a row's length
        produce garbage K/V, but the decode mask (``key_pos <= position``)
        never reads a padded position before a real token overwrites it."""
        from ..ops._dispatch import as_tensor

        ids = as_tensor(input_ids)
        B, T = ids.shape[0], ids.shape[1]
        h, kvs = self.gpt(ids, position_ids=position_ids, return_kv=True)
        hv = h._value
        if lengths is None:
            h_last = hv[:, T - 1:T]
        else:
            idx = jnp.clip(
                as_tensor(lengths)._value.astype(jnp.int32) - 1, 0, T - 1)
            h_last = jnp.take_along_axis(hv, idx[:, None, None], axis=1)
        logits = self._logits(Tensor(h_last))  # [B, 1, V]
        return Tensor(logits._value[:, 0]), kvs

    def decode_step(self, tokens, kv_caches, positions):
        """One static-shape cached decode step: ``tokens`` ``[B]`` (or
        ``[B, 1]``) int ids, ``kv_caches`` a per-layer list of either
        dense ``(k, v)`` entries (each ``[B, H_kv, S_max, D]``) or paged
        ``(k_pool, v_pool, page_table)`` triples (pools
        ``[P, H_kv, ps, D]``, table ``[B, num_blocks]`` int32),
        ``positions`` ``[B]`` — the sequence index each row's token is
        written at. Returns ``(logits [B, V], new_caches)`` (new ``(k, v)``
        per layer; a paged table is host-managed and passes through
        unchanged); functionally pure, so the serving engine jit-compiles
        it once and reuses the executable every token."""
        from ..ops._dispatch import as_tensor

        idv = as_tensor(tokens)._value
        if idv.ndim == 1:
            idv = idv[:, None]
        pos = as_tensor(positions)._value.astype(jnp.int32)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, (idv.shape[0],))
        # position embedding indices clamp at the table edge, matching
        # jnp's clamping gather the grown-prefix path relied on implicitly
        position_ids = Tensor(jnp.clip(pos, 0, self.cfg.max_seq_len - 1)[:, None])
        caches = [tuple(as_tensor(c) for c in entry) for entry in kv_caches]
        h, new = self.gpt(Tensor(idv), position_ids=position_ids,
                          kv_caches=caches, cache_positions=Tensor(pos))
        logits = self._logits(h)  # [B, 1, V]
        return Tensor(logits._value[:, -1]), new

    def extend_step(self, tokens, kv_caches, positions):
        """Multi-token cached decode: ``tokens`` ``[B, T]`` int ids where
        row ``b``'s token ``t`` extends the cache at sequence position
        ``positions[b] + t`` (``T`` is static — the speculative verify
        width ``k+1``, or a suffix-prefill bucket after a prefix-cache
        splice). Requires the paged cache layout. Returns
        ``(logits [B, T, V], new_caches)`` — logits at EVERY position, so
        the caller can read the model's next-token choice after each draft
        token. Functionally pure like ``decode_step``; the engine compiles
        one executable per static ``T``."""
        from ..ops._dispatch import as_tensor

        idv = as_tensor(tokens)._value
        if idv.ndim == 1:
            idv = idv[:, None]
        B, T = idv.shape
        pos = as_tensor(positions)._value.astype(jnp.int32)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, (B,))
        qpos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        position_ids = Tensor(jnp.clip(qpos, 0, self.cfg.max_seq_len - 1))
        caches = [tuple(as_tensor(c) for c in entry) for entry in kv_caches]
        h, new = self.gpt(Tensor(idv), position_ids=position_ids,
                          kv_caches=caches, cache_positions=Tensor(pos))
        return self._logits(h), new  # [B, T, V]

    def generate(self, input_ids, max_new_tokens: int = 32, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, eos_token_id=None):
        """Autoregressive decoding (PaddleNLP GenerationMixin.generate's
        greedy/sampling core). Runs on the serving decode core
        (paddle_tpu/serving): one bucketed prefill + a single-token decode
        step over a static KV cache — one prefill compile + one decode
        compile total, instead of the old grown-prefix forward that
        re-compiled every emitted token. API and greedy/temperature/top-k/
        forced-eos semantics are unchanged."""
        from ..serving.engine import cached_generate

        return cached_generate(
            self, input_ids, max_new_tokens=max_new_tokens,
            do_sample=do_sample, temperature=temperature, top_k=top_k,
            eos_token_id=eos_token_id)


def gpt_tiny(**overrides) -> GPTForCausalLM:
    cfg = {**GPT_TINY, **overrides}
    return GPTForCausalLM(GPTConfig(**cfg))


def gpt_moe_tiny(**overrides) -> GPTForCausalLM:
    """Tiny GPT-MoE fixture: 4 experts, MoE FFN every 2nd block."""
    cfg = {**GPT_TINY, "num_layers": 2, "moe_num_experts": 4,
           "moe_every_k": 2, **overrides}
    return GPTForCausalLM(GPTConfig(**cfg))
