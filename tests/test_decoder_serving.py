"""The description-built decoder (models/decoder.py) under serving.Engine,
against its plain reference (benchmark/reference/keye_vl2.py) at a small
size on the CPU: hidden 64, 2 layers, 8 experts top-2, ``index_topk`` 16,
contexts to 96 so that selection is active.

Tolerances. Program and reference both compute in float32 here, in
different orders (chunks of queries, rows sorted by expert, pages), so
logits (|logit| up to about 7 with these weights) agree to a few 1e-6; the
limit 2e-4 leaves room for a platform's own summation order and is forty
times under the smallest gap between a row's two best logits seen here, so
a wrongly selected position, a dropped expert row or a stale page (each
moves a logit by 1e-2 or more) fails it. Where tokens are compared, the
reference's greedy token at each position is what the engine must emit.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu import observability as obs
from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import sparse_attention as sa
from paddle_tpu.kernels.pools import (PAGE_SENTINEL, paged_gather,
                                      paged_write_kv)
from paddle_tpu.kernels.tier import use_paged_attention_impl
from paddle_tpu.models.decoder import (DecoderConfig, DecoderLM, moe_swiglu,
                                       param_shapes)
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
from paddle_tpu.serving.kv_cache import PagedKVCache

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import keye_vl2 as ref  # noqa: E402

TOL = 2e-4
SIZES = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
             num_kv_heads=2, head_dim=16, max_context=128, index_heads=4,
             index_head_dim=8, index_topk=16, intermediate_size=32,
             num_experts=8, experts_per_token=2, query_chunk=32)
RCFG = dict(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            index_heads=4, index_head_dim=8, index_topk=16, norm_eps=1e-6,
            rope_theta=1e7, num_experts=8, experts_per_token=2,
            norm_topk_prob=True)


def _model(**over):
    """Seeded weights that make every part matter: matrices at ten times
    the initializer's 0.02, norm scales 1 + N(0, 0.1), biases N(0, 0.1)."""
    m = DecoderLM(DecoderConfig(**{**SIZES, **over}))
    m.eval()
    key = jax.random.PRNGKey(1)
    for i, (n, p) in enumerate(m.named_parameters()):
        k = jax.random.fold_in(key, i)
        if n.endswith("norm.weight"):
            p._set_value_raw(1 + 0.1 * jax.random.normal(k, p._value.shape))
        elif n.endswith(".bias"):
            p._set_value_raw(0.1 * jax.random.normal(k, p._value.shape))
        else:
            p._set_value_raw(p._value * 10)
    return m


def _params(m):
    return {n: p._value for n, p in m.named_parameters()}


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, size=(n,)).tolist()


def _ref_rows(m, text, first):
    """Reference logits at positions first.. of ``text``."""
    lg = ref.forward(_params(m), jnp.asarray(text), RCFG, q_block=len(text))
    return np.asarray(lg[first:])


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture
def telemetry():
    obs.enable()
    obs.reset()
    yield obs
    obs.disable()
    obs.reset()


# ----------------------------------------------------------- selection

class TestSelection:
    @pytest.mark.parametrize("k", [1, 5, 16, 64])
    @pytest.mark.parametrize("ties", [False, True])
    def test_topk_mask_is_lax_top_k(self, k, ties):
        """The bisection's set is ``lax.top_k``'s on the masked row, ties
        at the threshold included (lower position first)."""
        rs = np.random.RandomState(k)
        s = rs.randn(6, 200).astype(np.float32)
        if ties:
            s = np.round(s * 2) / 2          # many equal scores, some -0.0
        valid = np.arange(200)[None, :] <= rs.randint(0, 200, (6, 1))
        got = np.asarray(sa.topk_mask(jnp.asarray(s), jnp.asarray(valid), k))
        _, idx = lax.top_k(jnp.where(valid, s, -jnp.inf), k)
        want = np.zeros_like(valid)
        np.put_along_axis(want, np.asarray(idx), True, axis=1)
        np.testing.assert_array_equal(got, want & valid)

    # (L, page, k, ties, first slot's last position, table entries past it)
    CASES = {
        "L96": (96, 8, 32, False, None, "mapped"),
        "L200_not_a_multiple_of_128": (200, 8, 32, False, None, "mapped"),
        "L384": (384, 8, 32, False, None, "mapped"),
        "L100_ends_inside_a_page": (100, 8, 32, False, None, "mapped"),
        "ties_at_the_threshold": (200, 8, 32, True, None, "mapped"),
        "context_shorter_than_k": (24, 8, 32, False, None, "mapped"),
        "slot_with_n_under_k": (200, 8, 32, False, 5, "mapped"),
        "unallocated_table_entries": (200, 8, 32, True, 40, "unmapped"),
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_selected_rows_are_lax_top_k_through_the_table(self, case):
        """``selected_rows`` against an independent reference: the
        ascending positions of ``lax.top_k``'s set on the masked row,
        looked up in the page table with numpy (sentinels clamp to the
        trash page); past ``n`` the slot's position 0."""
        L, ps, k, ties, first, rest = self.CASES[case]
        rs = np.random.RandomState(L + k)
        B, nb = 5, -(-L // ps)
        s = rs.randn(B, L).astype(np.float32)
        if ties:
            s = np.round(s * 2) / 2
        last = rs.randint(0, L, (B,))
        if first is not None:
            last[0] = first
        table = (rs.permutation(B * nb).reshape(B, nb) + 1).astype(np.int32)
        if rest == "unmapped":
            table[np.arange(nb)[None, :] > last[:, None] // ps] = -1
        valid = np.arange(L)[None, :] <= last[:, None]
        rows, n = sa.selected_rows(jnp.asarray(s), jnp.asarray(valid),
                                   jnp.asarray(table), ps, k)
        rows, n = np.asarray(rows), np.asarray(n)
        assert rows.shape == (B, min(k, L)) and rows.dtype == np.int32
        _, top = lax.top_k(jnp.where(valid, s, -jnp.inf), min(k, L))
        for b in range(B):
            pos = np.sort([t for t in np.asarray(top[b]) if valid[b, t]])
            assert int(n[b]) == len(pos) == min(k, last[b] + 1)
            want = np.maximum(table[b, pos // ps], 0) * ps + pos % ps
            np.testing.assert_array_equal(rows[b, :len(pos)], want)
            assert (rows[b, len(pos):] == max(table[b, 0], 0) * ps).all()

    def test_decode_lowers_to_three_row_gathers_a_sparse_layer(self):
        """The tiny model's decode program (StableHLO text, every call site
        counted): what a sparse layer gathers by the ``[B, K]`` selection
        is K rows, V rows and the compaction's address rows — nothing of
        one element a slice, which costs this chip what a 1 KB row costs
        (PERF.md section 7)."""
        cfg = DecoderConfig(**SIZES)
        with use_paged_attention_impl("pallas"):
            eng = Engine(DecoderLM(cfg), EngineConfig(
                max_batch_size=2, max_seq_len=96, page_size=8))
            fn, args = eng.decode_program()
            text = jax.jit(fn).lower(*args).as_text()
        funcs = {}
        for body in re.split(r"\n  func\.func ", text)[1:]:
            name = re.match(r"(?:public |private )?@([\w.]+)", body).group(1)
            funcs[name] = (
                re.findall(r'"stablehlo\.gather".*slice_sizes = array<i64: '
                           r'([\d, ]+)>.*-> tensor<([\w]+)>', body),
                re.findall(r"call @([\w.]+)\(", body))

        def gathers(name):
            own, calls = funcs[name]
            return own + [g for c in calls for g in gathers(c)]

        B, K, W = 2, cfg.index_topk, cfg.num_kv_heads * cfg.head_dim
        # every gather indexed by the selection, an element gather's
        # ``BxKxi32`` result included
        by_selection = sorted(g for g in gathers("main")
                              if g[1].startswith(f"{B}x{K}x"))
        want = [("1, 1, 128", f"{B}x{K}x128xi32"),
                ("1, %d" % W, f"{B}x{K}x{W}xf32"),
                ("1, %d" % W, f"{B}x{K}x{W}xf32")] * cfg.num_layers
        assert by_selection == sorted(want)

    def test_sparse_decode_kernel_matches_masked_softmax(self):
        """``sparse_paged_decode`` (Pallas, interpreted here) over rows
        gathered through a page table = softmax over the selected
        positions of the dense view."""
        rs = np.random.RandomState(3)
        B, Hq, Hkv, D, ps, nb, P = 3, 4, 2, 16, 8, 12, 40
        kp = jnp.asarray(rs.randn(P, 1, ps, Hkv * D).astype(np.float32))
        vp = jnp.asarray(rs.randn(P, 1, ps, Hkv * D).astype(np.float32))
        table = jnp.asarray(rs.permutation(P - 1)[:B * nb].reshape(B, nb) + 1,
                            jnp.int32)
        q = jnp.asarray(rs.randn(B, Hq, D).astype(np.float32))
        pos = jnp.asarray([95, 40, 7])
        score = jnp.asarray(rs.randn(B, nb * ps).astype(np.float32))
        valid = jnp.arange(nb * ps)[None, :] <= pos[:, None]
        rows, n = sa.selected_rows(score, valid, table, ps, 16)
        got = sa.sparse_paged_decode(q, kp, vp, rows, n)
        mask = sa.topk_mask(score, valid, 16)
        view = lambda pool: paged_gather(pool, table)[:, 0].reshape(
            B, nb * ps, Hkv, D)
        s = jnp.einsum("bgrd,blgd->bgrl", q.reshape(B, Hkv, 2, D) / 4.0,
                       view(kp))
        p = jax.nn.softmax(jnp.where(mask[:, None, None], s, -1e30), -1)
        want = jnp.einsum("bgrl,blgd->bgrd", p, view(vp)).reshape(B, Hq, D)
        np.testing.assert_allclose(got, want, atol=1e-5)


# --------------------------------------------------------- expert layer

class TestExpertLayer:
    @pytest.mark.parametrize("tm", [8, 16])
    @pytest.mark.parametrize("skew", ["uniform", "one_expert", "two_empty"])
    def test_grouped_matmul_is_the_per_row_product(self, tm, skew):
        rs = np.random.RandomState(tm)
        M, G, K, N = 40, 8, 32, 24
        e = {"uniform": rs.randint(0, G, M),
             "one_expert": np.full(M, 5),
             "two_empty": rs.choice([0, 2, 3, 4, 6, 7], M)}[skew]
        x = jnp.asarray(rs.randn(M, K).astype(np.float32))
        w = jnp.asarray(rs.randn(G, K, N).astype(np.float32))
        src, dest, tile_group, n_tiles, counts = gm.plan_groups(
            jnp.asarray(e, jnp.int32), G, tm)
        y = gm.grouped_matmul(x[src], w, tile_group, n_tiles, tm)[dest]
        want = jnp.einsum("mk,mkn->mn", x, w[e])
        np.testing.assert_allclose(y, want, atol=1e-4)
        np.testing.assert_array_equal(counts, np.bincount(e, minlength=G))

    @pytest.mark.parametrize("route", ["learned", "all_to_one_pair"])
    def test_drop_free_against_the_per_token_sum(self, model, route):
        """Every (token, chosen expert) row is computed: the layer equals
        the per-token sum over its chosen experts, also when the router
        sends EVERY token to the same two experts (a capacity-padded layer
        would drop all but a capacity's worth)."""
        cfg, p = model.cfg, dict(_params(model))
        pre = "layers.0.ffn"
        if route == "all_to_one_pair":
            r = np.zeros((64, 8), np.float32)
            r[:, 3], r[:, 6] = 1.0, 0.5     # experts 3 and 6, always
            p[pre + ".router"] = jnp.asarray(r)
        g = jnp.abs(jnp.asarray(np.random.RandomState(0).randn(48, 64),
                                jnp.float32))
        y, stats = moe_swiglu(cfg, p, pre, g)
        prob = jax.nn.softmax(g @ p[pre + ".router"], -1)
        top, idx = lax.top_k(prob, 2)
        top = top / top.sum(-1, keepdims=True)
        want = np.zeros((48, 64), np.float32)
        for t in range(48):
            for j in range(2):
                e = int(idx[t, j])
                a = jax.nn.silu(g[t] @ p[pre + ".w1"][e]) \
                    * (g[t] @ p[pre + ".w3"][e])
                want[t] += float(top[t, j]) * np.asarray(a @ p[pre + ".w2"][e])
        np.testing.assert_allclose(y, want, atol=TOL)
        if route == "all_to_one_pair":
            assert stats.tolist() == [2, 48]    # 2 experts, 48 rows each


# ------------------------------------------------- model vs reference

class TestAgainstReference:
    def test_full_forward(self, model):
        text = _ids(96)
        got = model(jnp.asarray(text)[None])._value[0]
        np.testing.assert_allclose(got, _ref_rows(model, text, 0), atol=TOL)

    def test_long_sequence_goes_through_the_ffn_in_chunks(self, model,
                                                          monkeypatch):
        """Past ``_FFN_TOKEN_CHUNK`` tokens the expert layer runs a chunk at
        a time (a 34k-token prompt's sorted rows would not fit whole): same
        logits."""
        from paddle_tpu.models import decoder

        monkeypatch.setattr(decoder, "_FFN_TOKEN_CHUNK", 32)
        text = _ids(96, seed=4)
        got = model(jnp.asarray(text)[None])._value[0]
        np.testing.assert_allclose(got, _ref_rows(model, text, 0), atol=TOL)

    @pytest.mark.parametrize("impl", ["oracle", "pallas"])
    def test_prefill_then_decode_through_the_pools(self, model, impl):
        """``prefill_with_cache`` of 70 tokens, its entries installed in the
        paged pools, then 20 ``decode_step``s: every step's logits are the
        reference's full forward at that position (selection is active from
        position 16 on)."""
        text = _ids(90, seed=2)
        n0, ps = 70, 8
        cache = PagedKVCache(2, 1, 1, 128, 32, "float32", page_size=ps,
                             pools=model.cache_pools())
        cache.assign_pages(0, list(range(1, 17)))
        table = cache.table_device()
        want = _ref_rows(model, text, n0 - 1)
        logits, kvs = model.prefill_with_cache(jnp.asarray(text[:n0])[None])
        np.testing.assert_allclose(logits._value[0], want[0], atol=TOL)
        pools = [list(pool) for pool in cache.pools]
        for l, entry in enumerate(kvs):
            for i, t in enumerate(entry):
                pools[i][l] = paged_write_kv(
                    pools[i][l], t._value, table, jnp.zeros((1,), jnp.int32))
        with use_paged_attention_impl(impl):
            for j, tok in enumerate(text[n0:]):
                entries = [tuple(pool[l] for pool in pools) + (table,)
                           for l in range(2)]
                logits, new, stats = model.decode_step(
                    jnp.asarray([tok]), entries, jnp.asarray([n0 + j]))
                for l, entry in enumerate(new):
                    for i, t in enumerate(entry):
                        pools[i][l] = t._value
                np.testing.assert_allclose(logits._value[0], want[1 + j],
                                           atol=TOL)
        assert stats._value.shape == (2, 2)

    @pytest.mark.parametrize("impl", ["oracle", "pallas"])
    def test_engine_with_splice_and_extend(self, model, impl):
        """Through ``serving.Engine`` with the prefix cache on: cold
        prefills, then a prompt that shares 40 tokens (5 pages) and goes
        through the splice and ``extend_step``. Every emitted token is the
        reference's greedy token of the full text."""
        shared = _ids(40, seed=5)
        prompts = [shared + _ids(17, seed=6), _ids(70, seed=7)]
        p3 = shared + _ids(9, seed=8)
        # the tier is baked in as each program is traced, on first use
        with use_paged_attention_impl(impl):
            eng = Engine(model, EngineConfig(
                max_batch_size=3, max_seq_len=128, page_size=8,
                prefill_buckets=(32, 64, 128), prefix_cache=True))
            outs = eng.generate(prompts, SamplingParams(max_new_tokens=12))
            r3 = eng.add_request(p3, SamplingParams(max_new_tokens=12))
            while eng.has_unfinished:
                eng.step()
        assert r3.prefix_hit_blocks == 5
        assert ("extend", 32) in eng._exe
        for prompt, out in zip(prompts + [p3], outs + [r3.output_ids]):
            rows = _ref_rows(model, prompt + out[:-1], len(prompt) - 1)
            assert rows.argmax(-1).tolist() == out

    def test_context_under_topk_is_dense_causal(self, model):
        """With ``index_topk`` no smaller than the context every position is
        selected: the sparse model's logits are those of the SAME weights
        under ``attention='dense'`` (which has no indexer at all)."""
        sparse = _model(index_topk=128)
        dense = DecoderLM(DecoderConfig(**{**SIZES, "attention": "dense"}))
        dense.eval()
        src = _params(sparse)
        for n, p in dense.named_parameters():
            p._set_value_raw(src[n])
        text = jnp.asarray(_ids(96, seed=9))[None]
        np.testing.assert_allclose(sparse(text)._value, dense(text)._value,
                                   atol=TOL)
        # and the selecting model differs, so the test can fail
        assert np.abs(np.asarray(model(text)._value)
                      - np.asarray(dense(text)._value)).max() > 10 * TOL

    def test_description_rejects_an_unknown_kind(self):
        with pytest.raises(ValueError, match="attention"):
            DecoderConfig(attention="banded")
        assert "head.weight" in param_shapes(DecoderConfig())
        assert "head.weight" not in param_shapes(
            DecoderConfig(tie_word_embeddings=True))


# ------------------------------------------------ the pools' lifecycle

class TestThreePools:
    def test_engine_sizes_pools_from_the_declaration(self, model):
        eng = Engine(model, EngineConfig(max_batch_size=2, max_seq_len=64,
                                         page_size=8, kv_pages=9))
        assert [s[0] for s in eng.cache.pool_specs] == ["k", "v", "index_k"]
        assert [p[0].shape for p in eng.cache.pools] == [
            (9, 1, 8, 32), (9, 1, 8, 32), (9, 1, 8, 128)]  # 8 of 128 lanes
        assert eng.donate_argnums == (1, 2, 3)
        assert eng.cache.k is eng.cache.pools[0]

    def test_engine_refuses_a_context_past_the_declared_one(self, model):
        with pytest.raises(ValueError, match="declared context"):
            Engine(model, EngineConfig(max_seq_len=256))

    def test_copy_on_write_copies_every_pool(self, model):
        eng = Engine(model, EngineConfig(max_batch_size=2, max_seq_len=64,
                                         page_size=8, prefix_cache=True))
        eng.generate([_ids(20, seed=1)], SamplingParams(max_new_tokens=2))
        src = eng.prefix_cache.match(_ids(20, seed=1))[1][0]
        before = [[np.asarray(l[src]) for l in pool] for pool in eng.cache.pools]
        assert all(np.abs(b).max() > 0 for pool in before for b in pool)
        dst = eng.page_alloc.alloc(1, owner="test")[0]
        eng.cache.copy_page(src, dst)
        for pool, was in zip(eng.cache.pools, before):
            for layer, b in zip(pool, was):
                np.testing.assert_array_equal(layer[dst], b)
                np.testing.assert_array_equal(layer[src], b)

    def test_shared_page_in_the_write_path_gets_a_private_copy(self, model):
        """``_ensure_writable`` on a page the trie shares: the slot's table
        row moves to a fresh page holding the same bytes in all three
        pools."""
        eng = Engine(model, EngineConfig(max_batch_size=2, max_seq_len=64,
                                         page_size=8, prefix_cache=True))
        prompt = _ids(24, seed=2)
        eng.generate([prompt], SamplingParams(max_new_tokens=1))
        req = eng.add_request(prompt + _ids(5, seed=3),
                              SamplingParams(max_new_tokens=3))
        eng.step()
        shared = int(eng.cache.page_table[req.slot, 0])
        assert eng.page_alloc.is_shared(shared)
        assert eng._ensure_writable(req.slot, 0, f"req{req.request_id}")
        fresh = int(eng.cache.page_table[req.slot, 0])
        assert fresh != shared and eng._cow_copies == 1
        for pool in eng.cache.pools:
            for layer in pool:
                np.testing.assert_array_equal(layer[fresh], layer[shared])

    def test_eviction_frees_pages_and_later_requests_stay_right(self, model):
        """A pool too small for every finished prompt's pages: the trie's
        cold leaves are evicted (their pages, in all three pools, go back
        to the allocator and are written again), and what is served after
        is still the reference's greedy text."""
        eng = Engine(model, EngineConfig(
            max_batch_size=1, max_seq_len=128, page_size=8, kv_pages=20,
            prefill_buckets=(64, 128), prefix_cache=True))
        evicted = 0
        for seed in range(4):
            prompt = _ids(60, seed=20 + seed)
            before = eng.prefix_cache.num_nodes
            out = eng.generate([prompt], SamplingParams(max_new_tokens=6))[0]
            evicted += max(0, before + 7 - eng.prefix_cache.num_nodes)
            rows = _ref_rows(model, prompt + out[:-1], len(prompt) - 1)
            assert rows.argmax(-1).tolist() == out
        assert evicted > 0
        assert eng.page_alloc.num_free + eng.prefix_cache.num_nodes == 19


# ------------------------------------------------------- engine protocol

class TestEngineProtocol:
    def test_mixed_engines_compile_decode_once_each(self, model, telemetry):
        """A GPT-3-tiny engine and a decoder engine stepped in turn: one
        decode compile each, whatever the order and mix of requests."""
        gpt = gpt_tiny(dropout=0.0, num_layers=2)
        gpt.eval()
        a = Engine(gpt, EngineConfig(max_batch_size=2, max_seq_len=64))
        b = Engine(model, EngineConfig(max_batch_size=2, max_seq_len=64,
                                       page_size=8))
        for i in range(3):
            a.add_request([5 + i, 17, 3], SamplingParams(max_new_tokens=5))
            b.add_request(_ids(9 + i, seed=i), SamplingParams(max_new_tokens=5))
        while a.has_unfinished or b.has_unfinished:
            a.step()
            b.step()
        c = telemetry.snapshot()["counters"]
        assert c["jit.compile.cache_miss{site=serving.decode}"] == 2
        assert [k for k in a._exe if k[0] == "decode"] == [("decode",)]
        assert [k for k in b._exe if k[0] == "decode"] == [("decode",)]

    @pytest.mark.parametrize("family", ["gpt", "decoder"])
    def test_programs_compiled_side_by_side_serve_the_same_tokens(
            self, model, telemetry, family):
        """``compile_programs`` (traces in turn, backend compiles in
        threads) leaves the executables the lazy path would have made: same
        keys, one miss a program, none afterwards, same tokens."""
        if family == "gpt":
            m = gpt_tiny(dropout=0.0, num_layers=2)
            m.eval()
        else:
            m = model
        cfg = dict(max_batch_size=2, max_seq_len=64, page_size=8,
                   prefill_buckets=(16, 64), prefix_cache=True)
        prompts = [_ids(20, seed=3), _ids(20, seed=3)[:16] + _ids(7, seed=4)]
        sp = SamplingParams(max_new_tokens=5)
        want = Engine(m, EngineConfig(**cfg)).generate(prompts, sp)
        before = telemetry.snapshot()["counters"]
        eng = Engine(m, EngineConfig(**cfg))
        keys = eng.compile_programs(prefill=[16, 64], extend=[16])
        assert keys == [("decode",), ("prefill", 16), ("prefill", 64),
                        ("extend", 16)]
        assert set(keys) <= set(eng._exe)
        assert eng.compile_programs(prefill=[64], extend=[16]) == []
        assert eng.generate(prompts, sp) == want
        c = telemetry.snapshot()["counters"]
        miss = lambda site: (c[f"jit.compile.cache_miss{{site={site}}}"]
                             - before[f"jit.compile.cache_miss{{site={site}}}"])
        assert miss("serving.decode") == 1 and miss("serving.prefill") == 3

    def test_decode_span_counts_context_selection_and_experts(
            self, model, telemetry):
        from paddle_tpu.observability import tracing

        eng = Engine(model, EngineConfig(max_batch_size=2, max_seq_len=64,
                                         page_size=8))
        tracing.clear_spans()
        eng.generate([_ids(30, seed=1), _ids(10, seed=2)],
                     SamplingParams(max_new_tokens=4))
        dec = [e["attrs"] for e in tracing.spans()
               if e["name"] == "serving/decode" and "ctx_tokens" in e["attrs"]]
        first = dec[0]
        assert first["running"] == 2
        assert first["ctx_tokens"] == 31 + 11
        assert first["selected_tokens"] == 16 + 11   # topk 16 caps the first
        assert len(first["experts_touched"]) == 2     # one count per layer
        assert all(1 <= x <= 4 for x in first["experts_touched"])
        assert all(1 <= x <= 2 for x in first["expert_max_load"])

    @pytest.mark.parametrize("speculative", [None, 2],
                             ids=["plain", "speculative"])
    def test_gpt_decode_span_has_context_and_no_expert_counts(
            self, telemetry, speculative):
        """The engine has one host decode step: a verify step's span
        carries the counts a plain step's does."""
        from paddle_tpu.observability import tracing

        gpt = gpt_tiny(dropout=0.0, num_layers=2)
        gpt.eval()
        tracing.clear_spans()
        Engine(gpt, EngineConfig(max_batch_size=2, max_seq_len=64,
                                 speculative=speculative)).generate(
            [[5, 17, 3]], SamplingParams(max_new_tokens=3))
        dec = [e["attrs"] for e in tracing.spans()
               if e["name"] == "serving/decode" and "running" in e["attrs"]
               and e["attrs"]["running"]]
        assert dec[0]["ctx_tokens"] == dec[0]["selected_tokens"] == 4
        assert "experts_touched" not in dec[0]
        # page 16: the one running slot's 4 tokens sit in 1 page of a
        # 2-slot x 4-block table; at the 17th token a second page is live
        steps = 2 if speculative is None else len(dec)  # drafts may land
        assert 1 <= steps <= 2
        assert [d["live_pages"] for d in dec] == [1] * steps
        assert dec[0]["table_pages"] == 2 * 4
        proposed = [e for e in tracing.spans()
                    if e["name"] == "serving/decode/propose"]
        assert len(proposed) == (0 if speculative is None else steps)

    def test_decode_span_counts_live_pages_across_a_page_boundary(
            self, telemetry):
        from paddle_tpu.observability import tracing

        gpt = gpt_tiny(dropout=0.0, num_layers=2)
        gpt.eval()
        tracing.clear_spans()
        Engine(gpt, EngineConfig(max_batch_size=2, max_seq_len=64,
                                 page_size=8)).generate(
            [_ids(14, seed=1), _ids(3, seed=2)],
            SamplingParams(max_new_tokens=4))
        dec = [e["attrs"] for e in tracing.spans()
               if e["name"] == "serving/decode" and "live_pages" in e["attrs"]]
        # contexts 15 + 4, 16 + 5, 17 + 6 tokens at page 8: 2 + 1, 2 + 1,
        # then 3 + 1 pages
        assert [d["live_pages"] for d in dec] == [3, 3, 4]
        assert {d["table_pages"] for d in dec} == {2 * 8}


# ------------------------------------- the decode step's operands stay put
FAMILIES = ["gpt", "decoder", "speculative"]


def _toks(n, seed):
    """Ids both families' vocabularies hold (gpt_tiny's is 128)."""
    return np.random.RandomState(seed).randint(1, 100, size=(n,)).tolist()


def _family_model(family, model):
    import paddle_tpu as paddle

    if family == "decoder":
        return model
    paddle.seed(0)
    m = gpt_tiny(dropout=0.0, num_layers=2)
    m.eval()
    return m


def _family_engine(family, m, **kw):
    return Engine(m, EngineConfig(
        max_batch_size=3, max_seq_len=64, page_size=8,
        prefill_buckets=(16, 64),
        speculative=2 if family == "speculative" else None, **kw))


def _marked(eng):
    """The operands the host is the authority for: all five under
    speculation (every step is settled before the next), the last four of a
    plain engine, whose ``tokens`` mirror lags the device by the step in
    flight."""
    from paddle_tpu.serving.engine import _HOST_OPERANDS, _OPERANDS

    return _OPERANDS if eng.spec is not None else _HOST_OPERANDS


def _kept_against_mirrors(eng):
    """What is wrong with the invariant after a ``step()``: a kept device
    array differs from its host mirror although the mirror is not marked
    changed, a dead slot's position is not 0, or a dead slot whose token
    the host has not decided since carries a token other than 0."""
    wrong = []
    for name in _marked(eng):
        if name not in eng._stale and not np.array_equal(
                np.asarray(eng._dev[name]), getattr(eng, "_" + name)):
            wrong.append(name)
    if not eng.cache.table_changed and not np.array_equal(
            np.asarray(eng.cache.table_device()), eng.cache.page_table):
        wrong.append("table")
    dead = np.array([s.request is None for s in eng._slots])
    if eng._positions[dead].any() or (
            "positions" not in eng._stale
            and np.asarray(eng._dev["positions"])[dead].any()):
        wrong.append("dead slot's position")
    if eng.spec is None and np.asarray(eng._dev["tokens"])[
            dead & ~eng._from_host].any():
        wrong.append("dead slot's token")
    return wrong


def _carried(eng):
    """[(request, tokens it had, the token the device carries for it)] of
    the step in flight: what the next settle has to append."""
    if eng._flight is None:
        return []
    tokens = np.asarray(eng._dev["tokens"])
    return [(req, req.num_generated, int(tokens[slot]))
            for req, slot in eng._flight.rows if req.state != "finished"]


def _drive(family, m, second, eos, mark_all=False):
    """One seeded run that admits (a prefix hit and a sampled request among
    them, later than the first three: three slots), finishes by length and
    by eos, crosses page boundaries (page 8), copies a shared page on
    write, and ends one slot ``cache_full`` at the sequence budget.
    ``mark_all`` marks every mirror the host is the authority for changed
    before each step, which is what the engine did before it kept its
    operands. Returns what the tests below read."""
    import paddle_tpu as paddle
    from paddle_tpu.observability import tracing

    paddle.seed(7)
    eng = _family_engine(family, m, prefix_cache=True)
    calls = []
    name = "verify_program" if family == "speculative" else "decode_program"
    program = getattr(eng, name)
    setattr(eng, name, lambda *a, **k: calls.append(1) or program(*a, **k))
    first = _toks(20, seed=3)
    reqs = [eng.add_request(p, sp) for p, sp in [
        (first, SamplingParams(max_new_tokens=30)),
        (second, SamplingParams(max_new_tokens=25, eos_token_id=eos)),
        (_toks(50, seed=6), SamplingParams(max_new_tokens=40)),
        (first[:16] + _toks(5, seed=4), SamplingParams(max_new_tokens=10)),
        (_toks(9, seed=8), SamplingParams(max_new_tokens=12, do_sample=True,
                                          temperature=0.8, top_k=5))]]
    before = obs.snapshot()["counters"]
    tracing.clear_spans()
    wrong, shared, carried = [], None, []
    while eng.has_unfinished:
        if mark_all:
            eng._stale.update(_marked(eng))
            eng.cache._table_devs = [None] * len(eng.cache.groups)
        slot = reqs[3].slot
        if shared is None and slot is not None:
            # someone else takes a reference on the page the prefix-hit
            # request writes next, once it stands inside a mapped page
            # (21 prompt tokens: its third page): its next step has to
            # copy on write
            page = int(eng.cache.page_table[
                slot, eng._positions[slot] // eng.cache.page_size])
            if page != PAGE_SENTINEL:
                shared = page
                eng.page_alloc.retain([shared], owner="test")
        eng.step()
        wrong += [(eng._step_i, w) for w in _kept_against_mirrors(eng)]
        # the tokens the device carried into the step just settled are the
        # ones the host appended: the device's copy was the authority
        wrong += [(eng._step_i, "carried token") for req, n, tok in carried
                  if req.output_ids[n:n + 1] != [tok]]
        carried = _carried(eng)
    assert eng._flight is None or all(
        req.state == "finished" for req, _ in eng._flight.rows)
    eng.page_alloc.free([shared], owner="test")
    after = obs.snapshot()["counters"]
    site = "{site=serving.decode}"
    return dict(
        outputs=[r.output_ids for r in reqs],
        reasons=[r.finish_reason for r in reqs], wrong=wrong,
        spans=tracing.spans(), program_calls=len(calls),
        steps=eng._step_i, cow_copies=eng._cow_copies,
        compiles=after.get("jit.compile.cache_miss" + site, 0)
        - before.get("jit.compile.cache_miss" + site, 0),
        hits=after.get("jit.compile.cache_hit" + site, 0)
        - before.get("jit.compile.cache_hit" + site, 0))


@pytest.fixture(scope="module", params=FAMILIES)
def kept_run(request, model):
    """(family, the run, the same run with every mirror marked changed
    before each step): driven once a family, read by the four tests."""
    family = request.param
    m = _family_model(family, model)
    # the eos request: the first of a few prompts whose greedy answer
    # holds, from its third token on, a token it has not held before; that
    # token is its eos, so that it ends there and not earlier
    prompts = [_toks(11, seed=s) for s in range(5, 13)]
    outs = _family_engine("plain", m).generate(
        prompts, SamplingParams(max_new_tokens=12))
    second, eos = next(
        (p, t) for p, out in zip(prompts, outs)
        for j, t in enumerate(out) if j >= 2 and t not in out[:j])
    obs.enable()
    obs.reset()
    try:
        return family, _drive(family, m, second, eos), _drive(
            family, m, second, eos, mark_all=True)
    finally:
        obs.disable()
        obs.reset()


class TestOperandsStayOnTheDevice:
    """ISSUE 29: between decode steps the page table, tokens, positions and
    the three sampling rows stay on the device; the host puts one again
    only when something other than the step changed its host mirror.
    ISSUE 43: a plain engine's ``tokens`` mirror lags by the step in flight,
    so the device's copy is the authority and the host's own tokens (an
    admission's first, a finish's 0) travel as the ``host_tokens`` row."""

    def test_kept_arrays_equal_the_host_mirrors_after_every_step(
            self, kept_run):
        family, run, _ = kept_run
        assert run["wrong"] == []
        # the run was what it set out to be
        assert run["reasons"][:4] == ["length", "eos", "cache_full", "length"]
        assert run["reasons"][4] == "length" and run["cow_copies"] == 1
        admits = [e["attrs"] for e in run["spans"]
                  if e["name"] == "serving/admit"
                  and "blocked" not in e["attrs"]]
        assert [a["hit_blocks"] for a in admits] == [0, 0, 0, 2, 0]
        grown = sum(e["attrs"]["allocated"] for e in run["spans"]
                    if e["name"] == "serving/decode/grow_pages")
        assert grown >= 6                     # page boundaries were crossed

    def test_tokens_are_those_of_putting_everything_every_step(
            self, kept_run):
        _, run, old = kept_run
        assert run["outputs"] == old["outputs"]
        assert run["reasons"] == old["reasons"]
        assert run["steps"] == old["steps"]

    def test_puts_only_what_an_admission_finish_or_new_page_changed(
            self, kept_run):
        """Told from the OTHER spans' attributes, not from the marks: the
        table travels exactly on the steps after something wrote it, five
        rows on those after an admission, a release or a finish (a plain
        step: positions, the three sampling rows and ``host_tokens``; a
        verify step: tokens and positions always), and nothing else ever
        but, on a plain engine's first step, the first carried ``tokens``."""
        family, run, old = kept_run
        quiet = uploads = released = 0
        # spans are recorded as they END: an upload's record comes after
        # the admissions and the grow_pages of its own step and before its
        # step's settle, so one pass in order sees each upload with exactly
        # what was written since the upload before it
        rows = table = False
        for e in run["spans"]:
            a = e["attrs"]
            if e["name"] == "serving/admit" and "blocked" not in a:
                rows = table = True
            elif e["name"] == "serving/decode/grow_pages":
                # a request whose last token is in flight gives its slot
                # and pages back here, before the launch
                released = a.get("released", 0)
                table |= bool(a["allocated"] or a["cow_copies"]
                              or a["cache_full"] or released)
                rows |= bool(a["cache_full"] or released)
            elif e["name"] == "serving/decode/settle":
                # ... so its finish, at this settle, changes nothing more
                if a["finished"] > released:
                    rows = table = True
                released = 0
            elif e["name"] == "serving/decode/upload":
                uploads += 1
                least = 2 if family == "speculative" else 0
                once = 1 if uploads == 1 and family != "speculative" else 0
                assert a["table_put"] == int(table), (e, rows, table)
                assert a["puts"] == int(table) + once + (
                    5 if rows else least), e
                quiet += a["puts"] == least
                rows = table = False
        assert uploads >= 12 and quiet >= uploads // 3
        # the old behaviour put its rows and the table on every step
        assert all(e["attrs"]["puts"] >= 5 for e in old["spans"]
                   if e["name"] == "serving/decode/upload")

    def test_program_is_built_once_and_found_afterwards(self, kept_run):
        """``decode_program()`` is called for the first step's compile and
        never again (the verify program was compiled with the engine, so
        not at all): every later step is a dictionary hit, which still
        counts."""
        family, run, _ = kept_run
        first = 0 if family == "speculative" else 1
        assert run["program_calls"] == first
        assert run["compiles"] == first
        uploads = sum(e["name"] == "serving/decode/upload"
                      for e in run["spans"])
        assert run["hits"] == uploads - first
