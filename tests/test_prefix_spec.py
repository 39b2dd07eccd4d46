"""Prefix cache + speculative decoding (ISSUE 19).

Covers: refcounted PageAllocator sharing (retain/free lifecycle, exact
re-cover of the pool after every sharer drops, double-free errors naming
the offending pages and owners), the radix trie (match cap, LRU leaf
eviction, trie-vs-live-request reference split), copy-on-write page
duplication preserving the sharer's bytes, engine-level prefix-hit output
parity with a cold engine (oracle AND interpret attend tiers), shared-page
lifetime across concurrent sharers, greedy speculative decode emitting a
token-identical stream to plain decode (including the cache_full
boundary), the one-decode-compile guarantee with speculation on, the
n-gram proposer / greedy acceptance host halves, the serving.prefix.* /
serving.spec.* metric series, and the request-trace records' new
attribution fields.
"""

import json

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.serving import (Engine, EngineConfig, PrefixCache,
                                SamplingParams, SpeculativeConfig,
                                accept_greedy, propose_ngram,
                                read_request_traces,
                                use_paged_attention_impl)
from paddle_tpu.serving.kv_cache import PAGE_SENTINEL, PagedKVCache
from paddle_tpu.serving.scheduler import FINISHED, PageAllocator


@pytest.fixture
def telemetry():
    obs.enable()
    obs.reset()
    yield obs
    obs.disable()
    obs.reset()


def _tiny(**kw):
    m = gpt_tiny(dropout=0.0, num_layers=2, **kw)
    m.eval()
    return m


def _toks(n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, 50, (n,))]


def _page(pools, page):
    """One page's bytes in every layer of a pool tuple: [L, H_kv, ps, D]."""
    return np.stack([np.asarray(pool[page]) for pool in pools])


def _run(eng, prompt, **sp):
    """Queue one request, drain the engine, return the Request."""
    req = eng.add_request(prompt, SamplingParams(**sp))
    while eng.has_unfinished:
        eng.step()
    return req


# ---------------- host halves of speculative decoding ----------------------
class TestSpeculativeHost:
    def test_propose_ngram_continuation(self):
        # suffix [2, 3] recurs at index 1; its continuation is proposed
        assert propose_ngram([1, 2, 3, 4, 2, 3], k=2, ngram=2) == [4, 2]

    def test_propose_ngram_pads_short_continuation(self):
        # the recurrence sits near the context start: the 2-token
        # continuation is padded to k by repeating its last token
        assert propose_ngram([1, 2, 1, 2], k=3, ngram=1) == [1, 2, 2]

    def test_propose_ngram_fallback_repeats_last(self):
        # nothing recurs: the always-valid draft is the last token, k times
        assert propose_ngram([5, 6, 7], k=3, ngram=2) == [7, 7, 7]
        assert propose_ngram([], k=2, ngram=3) == [0, 0]

    def test_propose_ngram_always_exactly_k(self):
        rng = np.random.default_rng(3)
        for n in (1, 2, 5, 30):
            ctx = [int(t) for t in rng.integers(0, 4, (n,))]
            for k in (1, 3, 5):
                assert len(propose_ngram(ctx, k, 3)) == k

    def test_accept_greedy_full_and_partial_and_none(self):
        # all k drafts agree -> k accepted + the bonus token
        assert accept_greedy([5, 6, 7], [5, 6, 7, 9]) == (3, [5, 6, 7, 9])
        # divergence at j=1 -> accepted prefix + model's own token there
        assert accept_greedy([5, 8, 7], [5, 6, 7, 9]) == (1, [5, 6])
        # immediate rejection still emits the guaranteed position-0 token
        assert accept_greedy([4, 8], [5, 6, 7]) == (0, [5])

    def test_config_validation(self):
        with pytest.raises(ValueError):
            SpeculativeConfig(k=0)
        with pytest.raises(ValueError):
            SpeculativeConfig(ngram=0)
        # EngineConfig coercion: True -> default config, int -> k
        assert EngineConfig(speculative=True).speculative == SpeculativeConfig()
        assert EngineConfig(speculative=5).speculative.k == 5
        assert EngineConfig(speculative=None).speculative is None


# ---------------- refcounted allocator -------------------------------------
class TestRefcountedAllocator:
    def test_shared_page_survives_first_free_pool_recovers_after_last(self):
        a = PageAllocator(9)
        pages = a.alloc(3, owner="reqA")
        a.retain(pages, owner="reqB")
        for p in pages:
            assert a.refcount(p) == 2 and a.is_shared(p)
        assert a.num_shared == 3
        a.free(pages, owner="reqA")          # first sharer drops
        for p in pages:
            assert a.refcount(p) == 1        # still allocated
        assert a.num_free == a.num_allocatable - 3
        a.free(pages, owner="reqB")          # last sharer drops
        assert a.num_allocated == 0
        assert a.num_free == a.num_allocatable  # exact re-cover

    def test_double_free_names_pages_and_owners(self):
        a = PageAllocator(5)
        pages = a.alloc(2, owner="req7")
        a.free(pages, owner="req7")
        with pytest.raises(ValueError) as ei:
            a.free(pages, owner="req9")
        msg = str(ei.value)
        for p in pages:
            assert str(p) in msg             # every offending page id
        assert "req9" in msg                 # who issued the bad free

    def test_partial_double_free_is_all_or_nothing(self):
        a = PageAllocator(5)
        live = a.alloc(1, owner="reqA")
        dead = a.alloc(1, owner="reqB")
        a.free(dead, owner="reqB")
        with pytest.raises(ValueError) as ei:
            a.free(live + dead, owner="reqA")
        assert str(dead[0]) in str(ei.value)
        assert str(live[0]) not in str(ei.value)
        assert a.refcount(live[0]) == 1      # the good page was not freed

    def test_retain_unallocated_raises(self):
        a = PageAllocator(4)
        with pytest.raises(ValueError, match="not allocated"):
            a.retain([2], owner="prefix-cache")


# ---------------- radix trie -----------------------------------------------
class TestPrefixCacheTrie:
    def _cache(self, pool=12, ps=4):
        a = PageAllocator(pool)
        return a, PrefixCache(ps, a)

    def test_insert_then_match_returns_block_pages(self):
        a, pc = self._cache()
        prompt = _toks(12)                   # 3 full blocks of 4
        pages = a.alloc(3, owner="req0")
        assert pc.insert(prompt, pages) == 3
        for p in pages:                      # trie holds one ref per node
            assert a.refcount(p) == 2
        # a 13-token prompt with the same first 12 tokens hits all 3 blocks
        hit, got = pc.match(prompt + [7])
        assert (hit, got) == (3, pages)

    def test_match_cap_leaves_last_aligned_block_to_suffix_prefill(self):
        a, pc = self._cache()
        prompt = _toks(12)
        pages = a.alloc(3, owner="req0")
        pc.insert(prompt, pages)
        # the exact prompt is fully cached, but matching is capped at
        # (12-1)//4 = 2 blocks so the suffix prefill always has >= 1 token
        hit, got = pc.match(prompt)
        assert (hit, got) == (2, pages[:2])

    def test_partial_block_never_matches(self):
        a, pc = self._cache()
        prompt = _toks(12)
        pages = a.alloc(3, owner="req0")
        pc.insert(prompt, pages)
        # same first 6 tokens = 1 full block + half a block -> 1 block hit
        hit, _ = pc.match(prompt[:6] + _toks(6, seed=9))
        assert hit == 1

    def test_insert_existing_blocks_keeps_first_pages(self):
        a, pc = self._cache()
        prompt = _toks(8)
        first = a.alloc(2, owner="req0")
        second = a.alloc(2, owner="req1")
        pc.insert(prompt, first)
        assert pc.insert(prompt, second) == 0   # no new nodes
        assert pc.match(prompt + [1])[1] == first
        for p in second:                        # duplicate stays private
            assert a.refcount(p) == 1

    def test_evict_lru_frees_cold_leaves_first(self):
        a, pc = self._cache(pool=12)
        cold, warm = _toks(4, seed=1), _toks(4, seed=2)
        p_cold = a.alloc(1, owner="r0")
        p_warm = a.alloc(1, owner="r1")
        pc.insert(cold, p_cold)
        pc.insert(warm, p_warm)
        a.free(p_cold, "r0")
        a.free(p_warm, "r1")                 # only trie refs remain
        pc.match(warm + [3])                 # touch warm -> cold is LRU
        assert pc.evict_lru(a.num_free + 1) == 1
        assert pc.num_nodes == 1
        assert a.refcount(p_cold[0]) == 0    # cold page returned
        assert a.refcount(p_warm[0]) == 1    # warm survives

    def test_evicting_spliced_page_defers_to_live_sharer(self):
        a, pc = self._cache(pool=6)
        prompt = _toks(4)
        pages = a.alloc(1, owner="req0")
        pc.insert(prompt, pages)
        a.free(pages, "req0")
        a.retain(pages, owner="req1")        # a live request still maps it
        pc.clear()                           # trie drops its reference...
        assert pc.num_nodes == 0
        assert a.refcount(pages[0]) == 1     # ...but the sharer keeps it
        a.free(pages, "req1")
        assert a.num_free == a.num_allocatable


# ---------------- copy-on-write + slot bookkeeping -------------------------
class TestCopyOnWrite:
    def test_copy_page_duplicates_bytes_and_isolates_writes(self):
        c = PagedKVCache(2, 1, 1, 16, 4, page_size=8, num_pages=6)
        rng = np.random.default_rng(0)
        src_bytes = rng.normal(size=(2, 1, 8, 4)).astype(np.float32)
        c.k = tuple(k.at[3].set(b) for k, b in zip(c.k, src_bytes))
        c.copy_page(3, 4)
        np.testing.assert_array_equal(_page(c.k, 4), src_bytes)
        c.k = tuple(k.at[4].set(0.0) for k in c.k)   # write the copy...
        np.testing.assert_array_equal(_page(c.k, 3), src_bytes)

    def test_clear_slot_idempotent(self):
        c = PagedKVCache(1, 2, 1, 16, 4, page_size=8)
        c.assign_pages(0, [3, 4])
        assert c.clear_slot(0) == [3, 4]
        assert c.clear_slot(0) == []         # second call frees nothing
        assert all(p == PAGE_SENTINEL for p in c.page_table[0])

    def test_engine_cow_preserves_sharers_bytes(self):
        """_ensure_writable on a shared page gives the writer a private
        byte-copy and leaves the trie's page untouched."""
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=64,
                                     page_size=8, prefix_cache=True))
        warm = _toks(20, seed=5)
        _run(eng, warm, max_new_tokens=2)    # trie now holds 2 blocks
        # admit a sharer and keep it running
        req = eng.add_request(warm[:16] + _toks(4, seed=6),
                              SamplingParams(max_new_tokens=30))
        eng.step()
        slot = req.slot
        shared = int(eng.cache.page_table[slot, 0])
        assert eng.page_alloc.is_shared(shared)
        before = _page(eng.cache.k, shared)
        assert eng._ensure_writable(slot, 0, owner="cow-test")
        fresh = int(eng.cache.page_table[slot, 0])
        assert fresh != shared
        np.testing.assert_array_equal(_page(eng.cache.k, fresh), before)
        assert eng.page_alloc.refcount(shared) == 1  # trie's ref only
        # unshared pages are left alone
        assert eng._ensure_writable(slot, 0, owner="cow-test")
        assert int(eng.cache.page_table[slot, 0]) == fresh


# ---------------- engine-level prefix cache --------------------------------
class TestEnginePrefixCache:
    def test_hit_output_matches_cold_engine(self):
        m = _tiny()
        cold = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=64,
                                      page_size=8))
        hot = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=64,
                                     page_size=8, prefix_cache=True))
        warm = _toks(20, seed=1)
        _run(hot, warm, max_new_tokens=4)    # populate the trie
        prompt = warm[:16] + _toks(4, seed=2)
        req = _run(hot, prompt, max_new_tokens=6)
        assert req.prefix_hit_blocks == 2    # 16 shared tokens / ps=8
        want = _run(cold, prompt, max_new_tokens=6)
        assert req.output_ids == want.output_ids

    def test_hit_output_matches_under_interpret_tier(self):
        """The spliced-page decode path agrees across attend tiers: the
        interpret-mode Pallas kernel reads the same shared pages the
        oracle gather does."""
        m = _tiny()
        outs = []
        for impl in ("oracle", "pallas"):
            with use_paged_attention_impl(impl):
                eng = Engine(m, EngineConfig(max_batch_size=1, max_seq_len=64,
                                             page_size=8, prefix_cache=True))
                warm = _toks(20, seed=1)
                _run(eng, warm, max_new_tokens=3)
                req = _run(eng, warm[:16] + _toks(4, seed=2),
                           max_new_tokens=5)
            assert req.prefix_hit_blocks == 2
            outs.append(req.output_ids)
        assert outs[0] == outs[1]

    def test_shared_pages_survive_first_finisher_exact_recover_after(self):
        """Two concurrent sharers of the same cached prefix: the first
        finish drops only its own references; the pool is exactly
        re-covered once both finish and the trie is cleared."""
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=64,
                                     page_size=8, prefix_cache=True))
        warm = _toks(20, seed=3)
        _run(eng, warm, max_new_tokens=2)
        shared = eng.prefix_cache.match(warm)[1]
        assert len(shared) == 2
        r1 = eng.add_request(warm[:16] + _toks(4, seed=4),
                             SamplingParams(max_new_tokens=3))
        r2 = eng.add_request(warm[:16] + _toks(4, seed=5),
                             SamplingParams(max_new_tokens=12))
        eng.step()                           # both admitted, both splice
        for p in shared:
            assert eng.page_alloc.refcount(p) == 3   # trie + r1 + r2
        observed = False
        while eng.has_unfinished:
            eng.step()
            if r1.state == FINISHED and r2.state != FINISHED:
                observed = True
                for p in shared:             # r1's finish dropped ONLY r1
                    assert eng.page_alloc.refcount(p) == 2
        assert observed
        # both sharers gone: only trie references remain...
        assert eng.page_alloc.num_allocated == eng.prefix_cache.num_nodes
        # ...and dropping the trie re-covers the pool exactly
        eng.prefix_cache.clear()
        assert eng.page_alloc.num_allocated == 0
        assert eng.page_alloc.num_free == eng.page_alloc.num_allocatable

    def test_prefix_metrics_under_flag(self, telemetry):
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=1, max_seq_len=64,
                                     page_size=8, prefix_cache=True))
        warm = _toks(20, seed=1)
        _run(eng, warm, max_new_tokens=2)
        _run(eng, warm[:16] + _toks(4, seed=2), max_new_tokens=2)
        snap = obs.snapshot()
        assert snap["counters"]["serving.prefix.misses"] == 1
        assert snap["counters"]["serving.prefix.hits"] == 1
        assert snap["gauges"]["serving.prefix.pages_shared"] >= 0
        assert snap["histograms"]["serving.prefix.splice_seconds"]["count"] == 1


# ---------------- engine-level speculative decoding ------------------------
class TestEngineSpeculative:
    def test_greedy_output_token_identical_to_plain_decode(self):
        """The acceptance invariant: with speculation on, the greedy token
        stream is EXACTLY what one-at-a-time decode produces — including a
        request that runs into the max_seq_len cache_full boundary, where
        the verify step drafts past S_max (trash-routed writes)."""
        m = _tiny()
        plain = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=32,
                                       page_size=8))
        spec = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=32,
                                      page_size=8, speculative=2))
        prompts = [_toks(12, seed=1), _toks(6, seed=2)]
        sp = SamplingParams(max_new_tokens=25)   # 12+25 > 32: hits the cap
        want = [_run(plain, p, max_new_tokens=25) for p in prompts]
        got = [_run(spec, p, max_new_tokens=25) for p in prompts]
        for w, g in zip(want, got):
            assert g.output_ids == w.output_ids
            assert g.finish_reason == w.finish_reason
        assert want[0].finish_reason == "cache_full"
        assert got[0].draft_tokens > 0
        assert 0 <= got[0].accepted_tokens <= got[0].draft_tokens

    def test_one_decode_compile_for_engine_lifetime(self, telemetry):
        """With speculation on, the verify-k program IS the decode step:
        compiled once at construction, never again — the same
        serving.decode counter contract the plain engine pins."""
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=64,
                                     page_size=8, speculative=3))
        c = obs.snapshot()["counters"]
        assert c["jit.compile.cache_miss{site=serving.decode}"] == 1
        eng.generate([_toks(10, seed=1), _toks(7, seed=2)],
                     SamplingParams(max_new_tokens=12))
        eng.generate([_toks(9, seed=3)], SamplingParams(max_new_tokens=8))
        c = obs.snapshot()["counters"]
        assert c["jit.compile.cache_miss{site=serving.decode}"] == 1
        assert c["jit.compile.cache_hit{site=serving.decode}"] > 0

    def test_sampled_rows_emit_one_token_per_step(self):
        """Non-greedy rows ignore drafts (one sampled token from position 0
        per verify step) and coexist with greedy rows in the same batch."""
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=64,
                                     page_size=8, speculative=2))
        r_greedy = eng.add_request(_toks(8, seed=1),
                                   SamplingParams(max_new_tokens=6))
        r_samp = eng.add_request(_toks(8, seed=2),
                                 SamplingParams(max_new_tokens=6,
                                                do_sample=True,
                                                temperature=0.8, top_k=5))
        while eng.has_unfinished:
            eng.step()
        assert len(r_greedy.output_ids) == 6
        assert len(r_samp.output_ids) == 6
        assert r_samp.draft_tokens == 0      # sampled rows never drafted
        assert r_greedy.draft_tokens > 0

    def test_spec_metrics_under_flag(self, telemetry):
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=1, max_seq_len=64,
                                     page_size=8, speculative=2))
        eng.generate([_toks(10)], SamplingParams(max_new_tokens=10))
        snap = obs.snapshot()
        c, g = snap["counters"], snap["gauges"]
        assert c["serving.spec.draft_tokens"] > 0
        assert 0 <= c["serving.spec.accepted_tokens"] \
            <= c["serving.spec.draft_tokens"]
        # emitted/verify-slots: >= 1/(k+1) by the guaranteed bonus token
        assert 0.0 < g["serving.spec.accept_rate"] <= 1.0
        # tokens generated == what the request actually received
        assert c["serving.tokens.generated"] == 10


# ---------------- request-trace attribution fields -------------------------
class TestTraceAttribution:
    def test_records_carry_prefix_and_spec_fields(self, tmp_path):
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=1, max_seq_len=64,
                                     page_size=8, prefix_cache=True,
                                     speculative=2,
                                     request_trace_dir=str(tmp_path)))
        warm = _toks(20, seed=1)
        _run(eng, warm, max_new_tokens=4)
        _run(eng, warm[:16] + _toks(4, seed=2), max_new_tokens=4)
        path = eng.tracer.path
        # torn tail: a crashed writer's partial line must not break readers
        with open(path, "a") as f:
            f.write('{"schema": "paddle_tpu.requ')
        records = read_request_traces(path)
        assert len(records) == 2
        miss, hit = records
        assert miss["prefix_hit_blocks"] == 0
        assert hit["prefix_hit_blocks"] == 2
        for rec in records:
            assert rec["draft_tokens"] >= rec["accepted_tokens"] >= 0
            assert rec["draft_tokens"] > 0   # greedy + speculation on
            assert [s["name"] for s in rec["spans"]] == \
                ["queue", "prefill", "decode", "finish"]

    def test_old_schema_lines_tolerated(self, tmp_path):
        # a reader-side default: pre-ISSUE-19 lines have no attribution
        # fields and must still parse
        p = tmp_path / "requests-host00000.jsonl"
        p.write_text(json.dumps({"schema": "paddle_tpu.requests.v1",
                                 "request_id": 1, "spans": []}) + "\n")
        recs = read_request_traces(str(p))
        assert len(recs) == 1
        assert recs[0].get("prefix_hit_blocks", 0) == 0
