"""A decoder of the DeepSeek-V3 kind — latent (MLA) attention with YaRN
positions in every layer, a leading dense SwiGLU layer, then routed experts
chosen by sigmoid scores under a group limit, of which THIS program holds
some, plus a shared one — under serving.Engine, against its plain reference
(benchmark/reference/deepseek_v3.py: keys and values expanded for every
head, no cache, a masked sum over the held experts) at a small size on the
CPU: hidden 32, 3 layers (1 dense of 48 + 2 expert layers), 4 heads of 8
(no positions) + 8 (rotary) on a query latent of 24 and a key/value latent
of 16, values of 8; 32 experts of width 16 in 4 groups of which 2 are kept,
4 a token, 8 held from index 8 (share 1 of 4); pages of 8 tokens.

Tolerances. Program and reference both compute in float32 here, in
different orders (key blocks with an online softmax against whole rows, the
absorbed form against expanded keys, sorted rows against a masked loop,
pages), so logits (|logit| up to about 5 with these weights) agree to about
1e-5; the limit 1e-4 leaves ten times of room and is far under what a lower
precision or any fault moves a logit by: the reference with its matmuls'
operands rounded to bfloat16 reads 1e-2 or more, as do a dropped
``mscale^2``, a weight taken from ``s + bias``, a missing group limit, a
missing shared expert and another share
(``test_the_comparison_can_fail``). The two kernels' own comparisons with
their oracles (``latent_paged_decode``: the absorbed form over pages;
``latent_flash``: the expanded form behind a cached context) are held to
2e-5 (the same float32 sums in another order).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.latent_attention import latent_decode_attend
from paddle_tpu.kernels.tier import use_paged_attention_impl
from paddle_tpu.models import decoder as dec
from paddle_tpu.models.decoder import (DecoderConfig, DecoderLM,
                                       is_norm_scale, param_shapes)
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import deepseek_v3 as ref  # noqa: E402

TOL = 1e-4
V, E, PS = 97, 32, 8
YARN = dict(type="yarn", factor=4.0, original_max_position_embeddings=32,
            beta_fast=4, beta_slow=1, mscale=1.0, mscale_all_dim=1.0)
PUBLISHED = dict(type="yarn", factor=40, original_max_position_embeddings=4096,
                 beta_fast=32, beta_slow=1, mscale=1.0, mscale_all_dim=1.0)
SIZES = dict(vocab_size=V, hidden_size=32, num_layers=3, num_heads=4,
             num_kv_heads=4, max_context=128, norm_eps=1e-6,
             position="rope_yarn", rope_theta=100.0, rope_scaling=YARN,
             qk_norm=False, attention="latent", q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
             v_head_dim=8, query_chunk=16, ffn="moe_swiglu",
             intermediate_size=16, first_dense_layers=1,
             dense_intermediate_size=48, router="sigmoid_group_topk",
             n_group=4, topk_group=2, routed_scaling_factor=2.5,
             num_experts=E, experts_per_token=4, experts_held=(8, 8),
             shared_experts=1)
RCFG = dict(num_layers=3, first_dense_layers=1, num_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=8, norm_eps=1e-6, rope_theta=100.0, rope_scaling=YARN,
            num_experts=E, experts_per_token=4, n_group=4, topk_group=2,
            norm_topk_prob=True, routed_scaling_factor=2.5,
            experts_held=(8, 8), head_block=2, parts=2)


def _model(**over):
    """Seeded weights that make every part matter: matrices at ten times
    the initializer's 0.02, norm scales 1 + N(0, 0.1), the router's bias
    N(0, 0.1) (NOT zero: choosing and weighing then differ)."""
    m = DecoderLM(DecoderConfig(**{**SIZES, **over}))
    m.eval()
    key = jax.random.PRNGKey(1)
    for i, (n, p) in enumerate(m.named_parameters()):
        k = jax.random.fold_in(key, i)
        if is_norm_scale(n):
            p._set_value_raw(1 + 0.1 * jax.random.normal(
                k, p._value.shape, jnp.float32))
        elif n.endswith("router.bias"):
            p._set_value_raw(0.1 * jax.random.normal(k, p._value.shape,
                                                     jnp.float32))
        elif p._value.ndim >= 2:
            p._set_value_raw(p._value * 10)
    return m


def _params(m):
    return {n: p._value for n, p in m.named_parameters()}


def _layer_params(p, l):
    pre = f"layers.{l}."
    return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, V, size=(n,)).tolist()


def _ref_rows(m, text, first, mm=ref.mm_highest, **over):
    """Reference logits at positions first.. of ``text``."""
    lg = ref.forward(_params(m), jnp.asarray(text), {**RCFG, **over}, mm,
                     q_block=16)
    return np.asarray(lg[first:])


def _forward(m, text):
    return np.asarray(jax.jit(lambda ids: m(ids)._value)(
        jnp.asarray(text)[None])[0])


def _engine(m, **over):
    return Engine(m, EngineConfig(**{**dict(
        max_batch_size=3, max_seq_len=96, page_size=PS, prefix_cache=True,
        prefill_buckets=(8, 16, 32, 64, 96)), **over}))


def _serve_logits(eng, prompt, follow):
    """Admit ``prompt`` through the engine's own admission (its prefill /
    extend programs, its pools), then feed ``follow`` one token a decode
    step through ``decode_step`` over the engine's pools: (the request,
    logits [1 + len(follow), V] at the prompt's last position and at each
    fed token's, the per-step ``latent_tokens_read`` a layer)."""
    rows, reads = [], []
    run = eng._run_prompt

    def keep(*a):
        out = run(*a)
        rows.append(np.asarray(out[0]))
        return out

    eng._run_prompt = keep
    req = eng.add_request(prompt, SamplingParams(max_new_tokens=64))
    assert eng._admit() == 1
    eng._run_prompt = run
    rows = rows[-1:]                # the last piece's are the prompt's
    B, slot, m = eng.config.max_batch_size, req.slot, eng.model

    @jax.jit    # traced once: the interpreted kernel is slow step by step
    def step(tokens, pools, table, pos):
        logits, new, stats = m.decode_step(
            tokens, eng.cache.layer_entries(pools, table), pos)
        return logits._value, [tuple(t._value for t in layer)
                               for layer in new], stats._value

    for j, tok in enumerate(follow):
        tokens = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tokens[slot], pos[slot] = tok, len(prompt) + j
        eng._positions[slot] = pos[slot]
        eng._grow_pages()
        logits, new, stats = step(jnp.asarray(tokens), eng.cache.pools,
                                  eng.cache.table_device(), jnp.asarray(pos))
        eng.cache.pools = eng.cache.pools_from_layers(new)
        rows.append(np.asarray(logits[slot]))
        reads.append(np.asarray(stats)[:, m.step_stats.index(
            "latent_tokens_read")].tolist())
    return req, np.stack(rows), reads


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(autouse=True)
def small_key_blocks(monkeypatch):
    """16 keys a block in the ``jax.numpy`` form, so that these small
    contexts walk several."""
    monkeypatch.setattr(dec, "_LATENT_KEY_BLOCK", 16)


def mm_bf16(a, b):
    """The reference's matmul with its operands rounded to bfloat16."""
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


# ----------------------------------------------------- (a) model vs reference

class TestAgainstReference:
    def test_full_forward(self, model):
        text = _ids(64)
        np.testing.assert_allclose(_forward(model, text),
                                   _ref_rows(model, text, 0), atol=TOL)

    def test_latent_layer_alone(self, model):
        """The mixer of an expert layer against the reference's, from the
        same normed input; the pool entry it hands out is ``[c | k_pe |
        zeros]`` in whole 128-lane rows."""
        cfg, p = model.cfg, _params(model)
        h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 32), jnp.float32)
        got, (fresh,) = dec.latent_attention(cfg, p, "layers.1.attn", h,
                                             jnp.zeros((1,), jnp.int32))
        want = ref.attention(h[0], _layer_params(p, 1), RCFG, ref.mm_highest,
                             16)
        np.testing.assert_allclose(got[0], want, atol=1e-5)
        assert fresh.shape == (1, 1, 48, 128) == (
            1, 1, 48, dec.latent_pool_width(cfg))
        assert not np.asarray(fresh[..., 24:]).any()
        assert model.cache_pools() == [("latent", 1, 128)]

    @pytest.mark.parametrize("what", [
        "bfloat16", "no_mscale", "weight_from_biased_score", "no_group_limit",
        "no_shared_expert", "another_share", "plain_rope"])
    def test_the_comparison_can_fail(self, model, what):
        """bfloat16 in float32's place, and a model that differs in one
        part of a layer, are each far outside TOL."""
        text = _ids(64)
        got = _forward(model, text)
        if what == "bfloat16":
            want = _ref_rows(model, text, 0, mm_bf16)
        elif what == "no_mscale":           # the softmax scale without it
            want = _ref_rows(model, text, 0, rope_scaling={
                **YARN, "mscale_all_dim": 0.0, "mscale": 0.0})
        elif what == "weight_from_biased_score":
            # the bias folded into the router's scores: sigmoid(logit) + b
            # both chooses and weighs
            route = ref.route

            def biased(g, p, cfg, mm):
                b = p["ffn.router.bias"].astype(jnp.float32)
                w = route(g, p, {**cfg, "norm_topk_prob": False,
                                 "routed_scaling_factor": 1.0}, mm)
                w = jnp.where(w > 0, w + b[None, :], 0.0)
                return w / (w.sum(-1, keepdims=True) + 1e-20) * 2.5

            ref.route = biased
            try:
                want = _ref_rows(model, text, 0)
            finally:
                ref.route = route
        elif what == "no_group_limit":
            want = _ref_rows(model, text, 0, n_group=1, topk_group=1)
        elif what == "plain_rope":
            want = _ref_rows(model, text, 0, rope_scaling={
                **YARN, "factor": 1.0})
        else:
            over = {"no_shared_expert": dict(shared_experts=0),
                    "another_share": dict(experts_held=(8, 16))}[what]
            other = _model(**over)
            mine = _params(model)
            for n, p in other.named_parameters():   # every weight they share
                if n in mine and mine[n].shape == p._value.shape:
                    p._set_value_raw(mine[n])
            want, got = got, _forward(other, text)
        assert np.abs(got - want).max() > 50 * TOL

    # --------------------- (b) prefill, then decode through the latent pool
    @pytest.mark.parametrize("impl", ["oracle", "pallas"])
    def test_prefill_then_decode_through_the_engines_pools(self, model, impl):
        """A 45-token prompt admitted by the engine (the expanded form),
        then 20 decode steps over the engine's latent pool (the absorbed
        form; under ``pallas`` the kernel, interpreted): every position's
        logits are the reference's ONE forward, and every layer counts the
        context it read."""
        text = _ids(65, seed=2)
        with use_paged_attention_impl(impl):
            eng = _engine(model)
            _, got, reads = _serve_logits(eng, text[:45], text[45:])
        np.testing.assert_allclose(got, _ref_rows(model, text, 44), atol=TOL)
        assert reads == [[46 + j] * 3 for j in range(20)]

    # ------------------------------- (c) an extend over cached latents
    @pytest.mark.parametrize("impl", ["oracle", "pallas"])
    def test_extend_over_cached_latents_is_the_cold_prompt(self, model, impl):
        """A prompt served after a prefix hit (the shared pages' latents
        read back, the rest extended over them) gives the logits of the
        same prompt served cold, at the prompt's end and through 10 decode
        steps; both are the reference's. Under ``pallas`` the expanded
        form is the ``latent_flash`` kernel (interpreted), a group of heads
        at a time."""
        shared, tail = _ids(40, seed=5), _ids(10, seed=8)
        text = shared + _ids(9, seed=7) + tail
        with use_paged_attention_impl(impl):
            warm = _engine(model)
            warm.generate([shared + _ids(17, seed=6)],
                          SamplingParams(max_new_tokens=3))
            req, hit, _ = _serve_logits(warm, text[:49], text[49:])
            assert req.prefix_hit_blocks == 5
            if impl == "oracle":    # (interpreted, a second engine is slow)
                _, cold, _ = _serve_logits(_engine(model), text[:49],
                                           text[49:])
                np.testing.assert_allclose(hit, cold, atol=TOL)
        np.testing.assert_allclose(hit, _ref_rows(model, text, 48), atol=TOL)

    # --------------------------------- (i) through the engine, prefix cache on
    def test_two_sessions_on_one_document_emit_the_references_tokens(
            self, model):
        """Two sessions over one shared document, through the engine's
        own steps: the second session and the first's second turn hit the
        prefix cache; every greedy token is the reference's."""
        eng = _engine(model)
        doc = _ids(32, seed=11)
        hist = [doc + _ids(5, seed=20), doc + _ids(7, seed=21)]
        hits = []
        for turn, s in ((0, 0), (0, 1), (1, 0)):
            prompt = hist[s]
            req = eng.add_request(prompt, SamplingParams(max_new_tokens=6))
            while eng.has_unfinished:
                eng.step()
            out = list(req.output_ids)
            hits.append(req.prefix_hit_blocks)
            rows = _ref_rows(model, prompt + out[:-1], len(prompt) - 1)
            assert rows.argmax(-1).tolist() == out
            hist[s] = prompt + out + _ids(4, seed=30 + turn + s)
        assert hits == [0, 4, 4]      # the document's four pages


# ------------------------------------------- (d) the kernel and its oracle

def _pool_case(seed=0, B=5, H=4, W=128, value=64, pages=24, nb=6):
    """Ragged contexts over a pool of 8-token pages: slot 0 ends mid-page,
    slot 1 fills its table, slot 2 is EMPTY, slots 3 and 4 share their first
    two pages (one document) and differ behind them."""
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    pool = jax.random.normal(k[0], (pages, 1, PS, W), jnp.float32)
    q = 0.3 * jax.random.normal(k[1], (B, H, W), jnp.float32)
    table = np.full((B, nb), -1, np.int32)
    table[0, :3] = [3, 4, 5]
    table[1, :6] = [6, 7, 8, 9, 10, 11]
    table[3, :3] = [12, 13, 14]
    table[4, :4] = [12, 13, 15, 16]
    pos = np.array([18, 47, 0, 23, 24], np.int32)
    return q, pool, jnp.asarray(table), jnp.asarray(pos), value


class TestLatentPagedDecode:
    def _both(self, *case):
        with use_paged_attention_impl("oracle"):
            want = latent_decode_attend(*case)
        with use_paged_attention_impl("pallas"):
            got = latent_decode_attend(*case)
        return np.asarray(got), np.asarray(want)

    def test_kernel_is_the_oracle_on_ragged_slots(self):
        got, want = self._both(*_pool_case())
        live = [0, 1, 3, 4]
        np.testing.assert_allclose(got[live], want[live], atol=2e-5)
        assert not got[2].any()              # the empty slot: zeros

    def test_oracle_is_plain_attention_over_the_rows(self):
        """Slot 0 by hand: 19 tokens, the last page's tail masked."""
        q, pool, table, pos, value = _pool_case()
        rows = np.concatenate([np.asarray(pool[p, 0]) for p in (3, 4, 5)])[:19]
        s = np.asarray(q[0]) @ rows.T
        w = np.exp(s - s.max(-1, keepdims=True))
        want = (w / w.sum(-1, keepdims=True)) @ rows[:, :value]
        with use_paged_attention_impl("oracle"):
            got = latent_decode_attend(q, pool, table, pos, value)
        np.testing.assert_allclose(got[0], want, atol=2e-5)

    def test_several_chunks_and_a_page_past_the_table(self, monkeypatch):
        """Chunks of two pages: slot 1 walks three of them, slot 0 ends
        inside its second; a position past the table's width reads the
        table and no further."""
        from paddle_tpu.kernels import latent_attention as la

        monkeypatch.setattr(la, "_CHUNK_TOKENS", 2 * PS)
        la._decode_call.clear_cache()
        q, pool, table, pos, value = _pool_case(seed=3)
        got, want = self._both(q, pool, table, pos.at[1].set(60), value)
        la._decode_call.clear_cache()
        np.testing.assert_allclose(got[[0, 1, 3, 4]], want[[0, 1, 3, 4]],
                                   atol=2e-5)

    def test_shared_pages_read_alike(self):
        """Two slots whose contexts are the same pages and position give
        the same rows for the same query."""
        q, pool, table, pos, value = _pool_case()
        q = q.at[4].set(q[3])
        table = table.at[4].set(table[3])
        got, _ = self._both(q, pool, table, pos.at[4].set(23), value)
        np.testing.assert_array_equal(got[3], got[4])


# --------------------------------------- the shared walk and its plan

def _walk_case(name):
    """(q, pool, table, positions, value, the plan's (members, shared pages)
    of every tile of two or more, by its first slot) for one shape of
    sharing, at pages of 8 tokens, chunks of 2 pages and tiles of at most 4
    (``small_walk``). A slot's pages behind what it shares are its own."""
    B, nb = {"r_plus_one_and_one": 7}.get(name, 8), 10
    table = np.full((B, nb), -1, np.int32)
    pos = np.zeros((B,), np.int32)
    own = iter(range(40, 200))

    def slot(b, shared, total, last):
        """``shared`` leading page ids, then pages of its own up to
        ``total``; its position ``last`` tokens into its last page."""
        table[b, :total] = list(shared) + [next(own) for _ in
                                           range(total - len(shared))]
        pos[b] = (total - 1) * PS + last

    doc_a, doc_b = list(range(1, 7)), list(range(10, 14))
    if name == "two_groups_scattered":
        for b, total, last in ((6, 8, 3), (0, 7, 0), (3, 10, 7)):
            slot(b, doc_a, total, last)
        for b, total, last in ((5, 5, 2), (1, 6, 5)):
            slot(b, doc_b, total, last)
        slot(2, [], 3, 4)
        slot(7, [], 1, 0)
        want = {0: (3, 6), 1: (2, 4)}
    elif name == "r_plus_one_and_one":
        for b, total in ((1, 7), (2, 8), (3, 9), (5, 10), (6, 7)):
            slot(b, doc_a, total, b)
        slot(0, [], 4, 6)
        want = {1: (4, 6)}        # the fifth walks alone: a tile of one
    elif name == "dead_slot_and_sentinel":
        for b, total in ((0, 7), (2, 8), (5, 9)):
            slot(b, doc_b, total, 1)
        table[2, 5] = -1          # inside slot 2's live range, behind the
        slot(3, [], 5, 5)         # shared pages: the trash page's rows
        table[3, 1] = -1
        want = {0: (3, 4)}        # slots 1, 4, 6, 7 are dead
    elif name == "shared_not_a_whole_chunk":
        for b, total in ((1, 6), (4, 9), (7, 7)):
            slot(b, doc_a[:5], total, 4)
        want = {1: (3, 4)}        # the fifth page each walks for itself
    elif name == "member_inside_the_shared_pages":
        slot(0, doc_a, 8, 2)
        slot(3, doc_a, 9, 7)
        slot(5, doc_a[:4], 4, 1)  # 26 tokens into what the others share
        want = {0: (3, 2)}        # 3 whole pages under it: one chunk
    elif name == "copy_on_write":
        slot(2, doc_a, 8, 3)
        slot(4, doc_a[:4], 8, 3)  # the same document up to a copied page
        slot(6, doc_a[:4], 7, 0)
        want = {2: (3, 4)}
    elif name == "nothing_shared":
        for b in range(B):
            slot(b, [], 1 + b, b)
        want = {}
    else:
        raise KeyError(name)
    k = jax.random.split(jax.random.PRNGKey(len(name)), 2)
    pool = jax.random.normal(k[0], (200, 1, PS, 128), jnp.float32)
    q = 0.3 * jax.random.normal(k[1], (B, 4, 128), jnp.float32)
    return q, pool, jnp.asarray(table), jnp.asarray(pos), 64, want


WALKS = ["two_groups_scattered", "r_plus_one_and_one",
         "dead_slot_and_sentinel", "shared_not_a_whole_chunk",
         "member_inside_the_shared_pages", "copy_on_write", "nothing_shared"]


@pytest.fixture
def small_walk(monkeypatch):
    """Chunks of two pages, tiles of at most four members, two members a
    matmul: the shapes of sharing fit a pool of 8-token pages."""
    from paddle_tpu.kernels import latent_attention as la

    monkeypatch.setattr(la, "_CHUNK_TOKENS", 2 * PS)
    monkeypatch.setattr(la, "_TILE_MEMBERS", 4)
    monkeypatch.setattr(la, "_BLOCK_MEMBERS", 2)
    la._decode_call.clear_cache()
    yield la
    la._decode_call.clear_cache()


class TestSharedWalk:
    @pytest.mark.parametrize("name", WALKS)
    def test_kernel_is_the_oracle(self, small_walk, name):
        q, pool, table, pos, value, _ = _walk_case(name)
        with use_paged_attention_impl("oracle"):
            want = np.asarray(latent_decode_attend(q, pool, table, pos,
                                                   value))
        with use_paged_attention_impl("pallas"):
            got = np.asarray(latent_decode_attend(q, pool, table, pos,
                                                  value))
        live = np.asarray(table[:, 0]) >= 0
        np.testing.assert_allclose(got[live], want[live], atol=2e-5)
        assert not got[~live].any()

    @pytest.mark.parametrize("name", WALKS)
    def test_plan_is_read_from_the_table_and_the_positions(self, small_walk,
                                                           name):
        la = small_walk
        _, _, table, pos, _, want = _walk_case(name)
        order, tile, count, shared = np.asarray(
            la.shared_walk_plan(table, pos, PS))
        B = table.shape[0]
        live = np.asarray(table[:, 0]) >= 0
        assert sorted(order) == list(range(B))      # every slot, once
        assert (count[~live[order]] == 0).all()
        assert (count[live[order]] > 0).all()
        tiles = {}
        for r in range(B):
            if count[r] > 1:
                tiles.setdefault(int(tile[r]), []).append(r)
        got = {}
        for first, places in tiles.items():
            assert places == list(range(first, first + count[first]))
            assert len({int(shared[r]) for r in places}) == 1
            assert count[first] <= la._TILE_MEMBERS
            slots = order[places]
            n = int(shared[first])
            # what is walked together is the same pages, wholly under
            # every member's position
            assert (np.asarray(table)[slots, :n]
                    == np.asarray(table)[slots[0], :n]).all()
            assert (n * PS <= np.asarray(pos)[slots] + 1).all()
            got[int(slots.min())] = (len(places), n)
        assert got == want
        assert (shared[count <= 1] == 0).all()
        assert int(la.shared_walk_tokens(jnp.asarray(
            [order, tile, count, shared]), PS)) \
            == sum(m * n * PS for m, n in want.values())

    def test_nothing_shared_is_a_tile_of_one_bit_for_bit(self, small_walk,
                                                         monkeypatch):
        """With disjoint tables every tile has one member and no shared
        page: the rows are those of a kernel whose tiles cannot hold two,
        and those of slots that DO share are their own walks' to 2e-5."""
        la = small_walk

        def run(name):
            q, pool, table, pos, value, _ = _walk_case(name)
            with use_paged_attention_impl("pallas"):
                return np.asarray(latent_decode_attend(q, pool, table,
                                                       pos, value))

        apart, together = run("nothing_shared"), run("two_groups_scattered")
        monkeypatch.setattr(la, "_TILE_MEMBERS", 1)
        monkeypatch.setattr(la, "_BLOCK_MEMBERS", 1)
        la._decode_call.clear_cache()
        np.testing.assert_array_equal(run("nothing_shared"), apart)
        np.testing.assert_allclose(run("two_groups_scattered"), together,
                                   atol=2e-5)


class TestSharedWalkThroughTheEngine:
    """Two documents of four pages, three sessions on each, all six in the
    batch at once with the prefix cache on."""

    def _serve(self, m, impl, docs=2):
        """(the six requests, the decode spans' attributes, pallas_calls of
        the kernel's name in the decode program as traced)."""
        from paddle_tpu import observability as obs

        obs.enable()
        obs.reset()
        obs.clear_spans()
        try:
            with use_paged_attention_impl(impl):
                eng = _engine(m, max_batch_size=6)
                reqs = []
                for s in range(6):
                    prompt = _ids(32, seed=50 + s % docs) \
                        + _ids(5 + s, seed=60 + s)
                    reqs.append(eng.add_request(
                        prompt, SamplingParams(max_new_tokens=7)))
                    eng.step()          # admitted: its pages are in the trie
                while eng.has_unfinished:
                    eng.step()
                fn, args = eng.decode_program()
                calls = str(jax.make_jaxpr(fn)(*args)).count(
                    "name=latent_paged_decode")
            steps = [e["attrs"] for e in obs.spans()
                     if e["name"] == "serving/decode"
                     and "ctx_tokens" in e["attrs"]]
            compiles = obs.snapshot()["counters"][
                "jit.compile.cache_miss{site=serving.decode}"]
        finally:
            obs.disable()
            obs.reset()
            obs.clear_spans()
        assert compiles == 1
        assert [k for k in eng._exe if k[0] == "decode"] == [("decode",)]
        return reqs, steps, calls

    def test_tokens_are_the_oracles_and_the_span_counts_the_walk(
            self, model, small_walk, monkeypatch):
        plans = []
        plan = small_walk.shared_walk_plan
        monkeypatch.setattr(
            small_walk, "shared_walk_plan",
            lambda *a: plans.append(None) or plan(*a))
        want, plain, none = self._serve(model, "oracle")
        assert not plans and none == 0
        got, steps, calls = self._serve(model, "pallas")
        assert [r.output_ids for r in got] == [r.output_ids for r in want]
        assert [r.prefix_hit_blocks for r in got] == [0, 0, 4, 4, 4, 4]
        # ONE kernel does the absorbed attention, the layers share its
        # trace, and the plan is made once a TRACE of the step, not once a
        # layer (the engine's compile and ``_serve``'s look at the program)
        assert calls == 1 and len(plans) == 2
        layers = model.cfg.num_layers
        walked = [a["shared_walk_tokens"] for a in steps]
        # all six running: three members x 32 shared tokens x two documents
        assert max(walked) == [192] * layers
        for a in steps:
            assert a["shared_walk_tokens"][0] <= a["ctx_tokens"]
            assert a["latent_tokens_read"] == [a["ctx_tokens"]] * layers
        # the oracle tier gathers every slot's own view: nothing walked
        assert {tuple(a["shared_walk_tokens"]) for a in plain} \
            == {(0,) * layers}

    def test_documents_of_their_own_walk_nothing_together(self, model,
                                                          small_walk):
        reqs, steps, _ = self._serve(model, "pallas", docs=6)
        assert [r.prefix_hit_blocks for r in reqs] == [0] * 6
        assert {tuple(a["shared_walk_tokens"]) for a in steps} \
            == {(0,) * model.cfg.num_layers}

    def test_a_model_without_latent_layers_has_no_such_count(self):
        from paddle_tpu import observability as obs

        obs.enable()
        obs.clear_spans()
        try:
            eng = Engine(DecoderLM(DecoderConfig()), EngineConfig(
                max_batch_size=2, max_seq_len=64, page_size=PS))
            eng.generate([_ids(9)], SamplingParams(max_new_tokens=3))
            steps = [e["attrs"] for e in obs.spans()
                     if e["name"] == "serving/decode"
                     and "ctx_tokens" in e["attrs"]]
        finally:
            obs.disable()
            obs.reset()
            obs.clear_spans()
        assert steps and not any("shared_walk_tokens" in a for a in steps)
        assert "shared_walk_tokens" not in eng.model.step_stats


class TestLatentFlash:
    """The expanded form's kernel: queries behind a cached context, a value
    width of its own."""

    @staticmethod
    def _plain(qn, qp, kn, kp, v, starts, heads):
        G, T, _ = qn.shape
        s = jnp.einsum("gtd,gld->gtl", qn, kn) \
            + jnp.einsum("gtd,gld->gtl", qp, jnp.repeat(kp, heads, axis=0))
        k = kn
        qpos = starts[jnp.arange(G) // heads][:, None, None] \
            + jnp.arange(T)[None, :, None]
        s = jnp.where(jnp.arange(k.shape[1])[None, None, :] <= qpos, s, -1e30)
        return jnp.einsum("gtl,gld->gtd", jax.nn.softmax(s, -1), v)

    @staticmethod
    def _operands(T, L, dtype=jnp.float32):
        k = jax.random.split(jax.random.PRNGKey(T), 5)
        draw = lambda key, shape, by=1.0: (
            by * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
        return (draw(k[0], (4, T, 16), 0.3), draw(k[1], (4, T, 8), 0.3),
                draw(k[2], (4, L, 16)), draw(k[3], (2, L, 8)),  # one a sequence
                draw(k[4], (4, L, 16)))

    @pytest.mark.parametrize("T,L,starts,blocks,rows", [
        (16, 48, (20, 7), (8, 16), 8),  # one block of each
        (32, 64, (0, 0), (8, 16), 8),   # a prefill: the keys are the queries' own
        (32, 96, (64, 3), (8, 16), 8),  # the last page, and almost nothing cached
        # wholly visible blocks, then a crossing one, then unread ones, the
        # starts no multiple of the key block and not the same: sequence 0's
        # first query block sees blocks 0-1 whole, crosses 2, leaves 3-5;
        # sequence 1's second one sees 0-3 whole and crosses 4 and 5
        (16, 96, (37, 70), (8, 16), 8),
        # several crossing blocks a query block (16 queries over keys by 8),
        # its queries in two chunks, then one query block in four chunks
        (32, 96, (37, 61), (16, 8), 8),
        (32, 96, (5, 64), (32, 32), 8),
        # a query block the chunk does not divide goes whole: its last
        # 24 % 16 rows are scored like the others
        (48, 96, (37, 5), (24, 16), 16),
        (24, 96, (70, 0), (24, 32), 16),
    ])
    def test_is_plain_attention_over_blocks(self, monkeypatch, T, L, starts,
                                            blocks, rows):
        """Small blocks, so that a query block walks several key blocks,
        those wholly behind its first query and those that cross a position,
        and leaves the ones behind its last position unread; a step's
        queries go ``rows`` at a time, each chunk's products written down
        ahead of the softmax of the one before."""
        from paddle_tpu.kernels import latent_attention as la

        monkeypatch.setattr(la, "_blocks", lambda T, L: blocks)
        monkeypatch.setattr(la, "_FLASH_ROWS", rows)
        la._flash_call.clear_cache()
        qn, qp, kn, kp, v = self._operands(T, L)
        # what lies behind a query's position must not matter: poison it
        dead = jnp.arange(L)[None, :, None] > (max(starts) + T - 1)
        got = la.latent_flash(qn, qp, jnp.where(dead, 1e4, kn), kp,
                              jnp.where(dead, 1e4, v),
                              jnp.asarray(starts), 2)
        la._flash_call.clear_cache()
        want = self._plain(qn, qp, kn, kp, v, jnp.asarray(starts), 2)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_block_sizes_divide_the_lengths(self):
        from paddle_tpu.kernels.latent_attention import _blocks

        assert _blocks(34816, 34816) == (1024, 1024)
        assert _blocks(128, 35840) == (128, 1024)
        assert _blocks(512, 35840) == (512, 1024)
        assert _blocks(2048, 35840) == (1024, 1024)
        assert _blocks(256, 512 * 3) == (256, 512)
        assert _blocks(48, 96) == (48, 96)
        assert _blocks(3000, 3000) == (3000, 3000)

    @pytest.mark.parametrize("T,L,start,blocks", [
        (16, 96, 37, (8, 16)), (16, 96, 70, (8, 16)), (32, 96, 61, (16, 8)),
        (32, 64, 0, (8, 16)), (32, 96, 64, (8, 16)), (32, 96, 5, (32, 32)),
        (48, 96, 48, (48, 96)), (16, 48, 15, (8, 16)), (16, 48, 16, (8, 16)),
    ])
    def test_last_block_bounds_what_a_query_block_sees(self, T, L, start,
                                                       blocks):
        """Against a count over every (query, key) pair: the key blocks a
        query block walks are those of which some query of it sees some
        key, and they are the leading ones."""
        from paddle_tpu.kernels.flash_attention import last_key_block

        bq, bk = blocks
        sees = (start + np.arange(T))[:, None] >= np.arange(L)[None, :]
        some = sees.reshape(T // bq, bq, L // bk, bk).any((1, 3))
        last = np.asarray(last_key_block(start, jnp.arange(T // bq), bq, bk,
                                         L // bk))
        np.testing.assert_array_equal(
            some, np.arange(L // bk)[None, :] < last[:, None])


# ---------------------------------------------------------------- (e) YaRN

class TestYarn:
    def test_published_parameters(self):
        """DeepSeek-V3's ``rope_scaling`` over the 32 pairs of 64 rotary
        lanes: the ramp rises from pair 10 to pair 23, the fastest pair
        keeps its frequency, the slowest turns forty times slower; the
        softmax scale is 192^-1/2 x (0.1 ln 40 + 1)^2."""
        inv = dec.yarn_inv_freq(64, 10000.0, PUBLISHED)
        extra = 10000.0 ** (-np.arange(32) / 32.0)
        assert inv.dtype == np.float32 and inv.shape == (32,)
        np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
        np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
        assert inv[0] == 1.0
        np.testing.assert_allclose(inv[31], extra[31] / 40, rtol=1e-6)
        ramp = (extra - inv) / (extra - extra / 40)
        np.testing.assert_allclose(ramp[10:24], (np.arange(10, 24) - 10) / 13,
                                   atol=1e-5)
        cfg = DecoderConfig(**{**SIZES, "rope_scaling": PUBLISHED,
                               "qk_nope_head_dim": 128,
                               "qk_rope_head_dim": 64})
        assert abs(dec.softmax_scale(cfg, 192) - 0.135234) < 5e-7
        np.testing.assert_allclose(
            np.asarray(ref.yarn_inv_freq({
                "rope_scaling": PUBLISHED, "qk_rope_head_dim": 64,
                "rope_theta": 10000.0})), inv, rtol=1e-6)
        assert abs(ref.softmax_scale({
            "rope_scaling": PUBLISHED, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64}) - 0.135234) < 5e-7

    def test_rotation_is_the_references(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 3, 8),
                              jnp.float32)
        pos = jnp.arange(40, dtype=jnp.int32)[None] + 17
        got = dec.rope_yarn(DecoderConfig(**SIZES), x, pos)
        want = ref.rotate(x[0], pos[0], RCFG)
        np.testing.assert_allclose(got[0], want, atol=1e-5)

    def test_yarn_serves_latent_layers_alone(self):
        with pytest.raises(ValueError, match="rope_yarn"):
            DecoderConfig(**{**SIZES, "attention": "dense"})
        with pytest.raises(ValueError, match="rope_yarn"):
            DecoderConfig(**{**SIZES, "rope_scaling": None})


# ------------------------------------------------------------- (f) the router

def _route(model, g, bias, l=1):
    """(program's weights [N, k] and experts [N, k], the reference's dense
    [N, E]) of layer ``l``'s router over ``g`` with ``bias`` installed."""
    p = dict(_params(model))
    pre = f"layers.{l}.ffn"
    p[pre + ".router.bias"] = jnp.asarray(bias, jnp.float32)
    pw, e = dec.sigmoid_group_topk(model.cfg, g, p[pre + ".router"],
                                   p[pre + ".router.bias"])
    dense = ref.route(g, _layer_params(p, l), RCFG, ref.mm_highest)
    return np.asarray(pw), np.asarray(e), np.asarray(dense)


class TestRouter:
    def test_is_the_references(self, model):
        g = jax.random.normal(jax.random.PRNGKey(2), (64, 32), jnp.float32)
        bias = 0.1 * np.random.RandomState(0).randn(E)
        pw, e, dense = _route(model, g, bias)
        got = np.zeros_like(dense)
        np.put_along_axis(got, e, pw, axis=1)
        np.testing.assert_allclose(got, dense, atol=1e-6)
        np.testing.assert_allclose(pw.sum(-1), 2.5, rtol=1e-5)

    def test_bias_chooses_and_does_not_weigh(self, model):
        """A bias that lifts one expert into the choice: its weight is
        still its own sigmoid score over the chosen scores' sum."""
        g = jax.random.normal(jax.random.PRNGKey(4), (16, 32), jnp.float32)
        _, e0, _ = _route(model, g, np.zeros(E))
        s = np.asarray(jax.nn.sigmoid(
            g @ _params(model)["layers.1.ffn.router"]))
        lifted = int(np.argmin(s[0]))       # token 0's LEAST likely expert
        assert lifted not in e0[0]
        bias = np.zeros(E)
        bias[lifted] = 5.0
        pw, e, _ = _route(model, g, bias)
        assert lifted in e[0] and set(e[0]) != set(e0[0])
        want = s[0, e[0]] / s[0, e[0]].sum() * 2.5
        np.testing.assert_allclose(pw[0], want, rtol=1e-5)
        assert pw[0, list(e[0]).index(lifted)] == pw[0].min()

    def test_largest_score_in_a_group_not_kept_is_not_chosen(self, model):
        """Where a token's largest score stands alone in a weak group, the
        group falls to the limit (2 of 4 kept, by the sum of the two
        largest) and the expert with it."""
        g = jax.random.normal(jax.random.PRNGKey(6), (256, 32), jnp.float32)
        pw, e, dense = _route(model, g, np.zeros(E))
        s = np.asarray(jax.nn.sigmoid(
            g @ _params(model)["layers.1.ffn.router"]))
        dropped = [t for t in range(256) if s[t].argmax() not in e[t]]
        assert dropped, "no token's best expert lies in a dropped group"
        for t in dropped:
            groups = set(e[t] // 8)
            assert len(groups) <= 2 and s[t].argmax() // 8 not in groups
            assert dense[t, s[t].argmax()] == 0


# ------------------------------------------------------ (g) the FFN by layer

def test_ffn_kind_and_width_by_layer(model):
    """A dense layer of its own width before the expert layers; a dense
    layer counts no routing."""
    shapes = param_shapes(model.cfg)
    assert shapes["layers.0.ffn.w1"] == (32, 48)
    assert "layers.0.ffn.router" not in shapes
    assert shapes["layers.1.ffn.w1"] == (8, 32, 16)          # the held
    assert shapes["layers.1.ffn.router"] == (32, E)          # all of them
    assert shapes["layers.1.ffn.router.bias"] == (E,)
    assert shapes["layers.2.ffn.shared.w2"] == (16, 32)
    assert model.cfg.ffns == (("swiglu", 48), ("moe_swiglu", 16),
                              ("moe_swiglu", 16))
    assert model.step_stats == ("experts_touched", "expert_max_load",
                                "local_rows", "routed_rows",
                                "latent_tokens_read", "shared_walk_tokens")
    p = _params(model)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 24, 32), jnp.float32)
    zero = jnp.zeros((1,), jnp.int32)
    for l, kind in enumerate(ref.ffn_kinds(RCFG)):
        got, _, stats = jax.jit(
            lambda p, x, l=l: dec.block(model.cfg, p, l, x, zero))(p, x)
        want = jax.jit(lambda p, x, kind=kind: ref.layer(
            x, p, kind, RCFG, q_block=8))(_layer_params(p, l), x[0])
        np.testing.assert_allclose(got[0], want, atol=2e-5)
        if kind == "dense":
            assert not np.asarray(stats).any()
        else:
            assert int(stats[3]) == 24 * 4 and 0 < int(stats[2]) < 24 * 4


# --------------------------------------------------- (h) the sum of shares

def test_shares_add_up_to_the_uncut_layer():
    """32 experts in 4 groups over 4 chips: the routed parts of the four
    shares of 8 and the shared expert counted ONCE add up to what the
    uncut reference gives for the whole expert layer."""
    whole = _model(experts_held=None)
    pw = _params(whole)
    g = jax.random.normal(jax.random.PRNGKey(9), (40, 32), jnp.float32)
    lp = _layer_params(pw, 1)
    want = ref.routed_experts(g, lp, {**RCFG, "experts_held": (E, 0)},
                              ref.mm_highest) \
        + ref.swiglu(g, lp, "ffn.shared.", ref.mm_highest)
    total = dec._dense_ffn(pw, "layers.1.ffn.shared", g)
    rows = 0
    for chip in range(4):
        cfg = DecoderConfig(**{**SIZES, "experts_held": (8, 8 * chip)})
        p = dict(pw)
        for w in ("w1", "w3", "w2"):
            p[f"layers.1.ffn.{w}"] = pw[f"layers.1.ffn.{w}"][8 * chip:
                                                             8 * chip + 8]
        y, stats = dec.moe_routed(cfg, p, "layers.1.ffn", g)
        part = ref.routed_experts(
            g, {**lp, **{f"ffn.{w}": p[f"layers.1.ffn.{w}"]
                         for w in ("w1", "w3", "w2")}},
            {**RCFG, "experts_held": (8, 8 * chip)}, ref.mm_highest)
        np.testing.assert_allclose(y, part, atol=1e-5)
        total, rows = total + y, rows + int(stats[2])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert rows == 40 * 4                  # every routed row on one chip


# ----------------------------------- (j) the older descriptions, unchanged

OLDER = {
    "indexed_sparse": (dict(), {
        "layers.0.attn.index.wq": (64, 32), "layers.1.ffn.router": (64, 8),
        "layers.1.ffn.w1": (8, 64, 128)}, (37, 3316694807)),
    "hybrid": (dict(layer_types=("gated_delta", "dense"), ffn="swiglu",
                    kv_layout="head", position="none", qk_norm=False), {
        "layers.0.attn.A_log": (4,), "layers.1.attn.wq": (64, 64),
        "layers.1.ffn.w1": (64, 128)}, (28, 4035424780)),
    "share": (dict(layer_types=("dense", "gated_delta"), position="none",
                   linear_gate="channel", attn_output_gate=True,
                   experts_held=(2, 4), shared_experts=1, qk_norm=False), {
        "layers.0.attn.wg": (64, 64), "layers.1.attn.wf_a": (64, 8),
        "layers.1.ffn.w1": (2, 64, 128), "layers.1.ffn.router": (64, 8),
        "layers.0.ffn.shared.w1": (64, 128)}, (39, 2590006625)),
}


@pytest.mark.parametrize("name", OLDER)
def test_older_descriptions_keep_their_parameters(name):
    """The three descriptions the benchmark already runs name the
    parameters they named, in the shapes and the order they had at the
    commit before the latent layer (count and CRC-32 of the list, taken
    there): nothing of a latent layer, a router's bias or a by-layer FFN
    reaches them."""
    import zlib

    over, some, pinned = OLDER[name]
    cfg = DecoderConfig(**over)
    shapes = param_shapes(cfg)
    for n, shape in some.items():
        assert shapes[n] == shape, n
    assert (len(shapes), zlib.crc32(
        repr(list(shapes.items())).encode())) == pinned
    assert not any(k in n for n in shapes for k in
                   ("router.bias", "wq_a", "wkv_a", "wk_b", "kv_norm"))
    assert "latent_tokens_read" not in dec.step_stats(cfg)
    assert cfg.ffns == ((cfg.ffn, cfg.intermediate_size),) * cfg.num_layers
