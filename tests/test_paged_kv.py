"""Block-paged KV cache + ragged paged-decode kernel (ISSUE 13).

Covers: PageAllocator exact-cover invariants (every page free XOR
allocated, all-or-nothing allocation, double-free raises, trash page never
handed out), paged write/gather parity with the dense cache primitives,
paged-vs-oracle decode-attend parity across ragged lengths / GQA / empty
slots (the Pallas kernel under ``interpret=True`` so CPU exercises its
numerics), engine-level parity (the engine vs ``model.generate``'s dense
lockstep loop, oracle vs interpret tier, mid-run admission), page-pool
admission backpressure and
decode-growth ``cache_full``, the one-compile decode guarantee with the
page table riding as runtime data, and the new page-occupancy gauges.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.kernels.paged_attention import (_pages_per_chunk,
                                                decode_attend,
                                                paged_attention,
                                                paged_decode_attend)
from paddle_tpu.kernels.pools import (PAGE_SENTINEL, paged_gather,
                                      paged_write_kv, write_kv)
from paddle_tpu.kernels.tier import (default_paged_impl,
                                     use_paged_attention_impl)
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
from paddle_tpu.serving.scheduler import PageAllocator


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


def _prompt(b, t, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 50, (b, t)).astype(np.int32)


def _oracle_attend(q, k_pool, v_pool, table, positions):
    """The paged attend's oracle, by name: the dense attend over the
    gathered pools."""
    return decode_attend(q, paged_gather(k_pool, table),
                         paged_gather(v_pool, table), positions)


# ---------------- allocator invariants ------------------------------------
class TestPageAllocator:
    def test_exact_cover_of_pool(self):
        """Every allocatable page is handed out exactly once, page 0 (the
        trash page) never, and freeing returns the pool to full."""
        a = PageAllocator(9)
        assert a.num_allocatable == 8
        seen = []
        while True:
            got = a.alloc(1)
            if got is None:
                break
            seen += got
        assert sorted(seen) == list(range(1, 9))  # all pages, 0 excluded
        assert len(set(seen)) == len(seen)        # no double-allocation
        assert a.num_free == 0 and a.num_allocated == 8
        a.free(seen)
        assert a.num_free == 8 and a.num_allocated == 0
        # pool is whole again: the same exact cover is available
        assert sorted(a.alloc(8)) == list(range(1, 9))

    def test_alloc_is_all_or_nothing(self):
        a = PageAllocator(5)  # 4 allocatable
        first = a.alloc(3)
        assert len(first) == 3
        assert a.alloc(2) is None        # only 1 free: nothing handed out
        assert a.num_free == 1           # pool untouched by the failure
        assert len(a.alloc(1)) == 1

    def test_double_free_and_foreign_free_raise(self):
        a = PageAllocator(4)
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(ValueError, match="not allocated"):
            a.free(pages[:1])            # double-free
        with pytest.raises(ValueError, match="not allocated"):
            a.free([3])                  # never handed out
        with pytest.raises(ValueError):
            PageAllocator(1)             # no room for trash + 1


# ---------------- paged primitives ----------------------------------------
class TestPagedPrimitives:
    def _pool_and_dense(self, B=3, L=1, Hkv=2, ps=4, nb=3, D=8, seed=0):
        """A random page pool + table and the dense cache holding the SAME
        bytes at the table's mapping (sentinels clamp to the trash page in
        both, so even unallocated blocks agree)."""
        rng = np.random.RandomState(seed)
        P = B * nb + 1
        kp = jnp.asarray(rng.randn(P, Hkv, ps, D).astype(np.float32))
        vp = jnp.asarray(rng.randn(P, Hkv, ps, D).astype(np.float32))
        table = np.full((B, nb), PAGE_SENTINEL, np.int32)
        table[0, :2] = [1, 2]      # 2 live pages
        table[1, :1] = [5]         # 1 live page
        # row 2 stays all-sentinel: an empty slot
        tbl = jnp.asarray(table)
        kd = paged_gather(kp, tbl)
        vd = paged_gather(vp, tbl)
        return kp, vp, tbl, kd, vd

    def test_paged_gather_reconstructs_dense_layout(self):
        kp, _, tbl, kd, _ = self._pool_and_dense()
        B, nb, ps = tbl.shape[0], tbl.shape[1], kp.shape[2]
        assert kd.shape == (B, kp.shape[1], nb * ps, kp.shape[3])
        # dense position j holds page table[b, j//ps] offset j%ps
        assert np.allclose(np.asarray(kd)[0, :, 5, :],
                           np.asarray(kp)[2, :, 1, :])
        # sentinel blocks clamp to the trash page
        assert np.allclose(np.asarray(kd)[2, :, 0, :],
                           np.asarray(kp)[0, :, 0, :])

    def test_paged_write_matches_dense_write(self):
        kp, _, tbl, kd, _ = self._pool_and_dense()
        B, Hkv, ps, D = tbl.shape[0], kp.shape[1], kp.shape[2], kp.shape[3]
        rng = np.random.RandomState(7)
        new = jnp.asarray(rng.randn(B, Hkv, 1, D).astype(np.float32))
        pos = jnp.asarray([5, 2, 0], jnp.int32)  # ragged, row 2 empty slot
        kp2 = paged_write_kv(kp, new, tbl, pos)
        kd2 = write_kv(kd, new, pos)
        got = np.asarray(paged_gather(kp2, tbl))
        want = np.asarray(kd2)
        # compare the LIVE prefix of each row (row 0 has 2 pages, row 1 has
        # 1): past it the paged view re-gathers the shared trash page, which
        # row 2's clamped write just touched — exactly the bytes the decode
        # mask never admits
        assert np.allclose(got[0, :, :2 * ps], want[0, :, :2 * ps])
        assert np.allclose(got[1, :, :ps], want[1, :, :ps])
        # row 2 (empty slot) really did clamp to the trash page at offset 0
        assert np.allclose(got[2, :, 0, :], want[2, :, 0, :])

    @pytest.mark.parametrize("T", [1, 3, 6])
    def test_paged_write_multi_token_matches_per_token_loop(self, T):
        """The page-at-a-time write lands every token where the per-token
        scatter (one ``pool[page, :, offset] = row`` per token: the
        reference, kept here) puts it — an unaligned start that crosses a
        page boundary and an empty slot included — and leaves every other
        live page as it was. Tokens past the row's pages (a sentinel) or
        past the table's capacity go to the trash page."""
        kp, _, tbl, _, _ = self._pool_and_dense()
        B, nb = tbl.shape
        Hkv, ps, D = kp.shape[1:]
        new = jnp.asarray(np.random.RandomState(3).randn(B, Hkv, T, D)
                          .astype(np.float32))

        def per_token(pos):
            want = np.asarray(kp).copy()
            for b in range(B):
                for t in range(T):
                    p = pos[b] + t
                    page = max(int(tbl[b, min(p // ps, nb - 1)]), 0)
                    if p >= nb * ps:
                        page = 0
                    want[page, :, p % ps, :] = np.asarray(new)[b, :, t, :]
            return want

        for pos in ([3, 1, 0], [9, 2, 0]):  # row 0: pages 1->2, then past
            got = paged_write_kv(kp, new, tbl,
                                 jnp.asarray(pos, jnp.int32))
            # page 0 is the trash page: several rows race there, by design
            np.testing.assert_array_equal(np.asarray(got)[1:],
                                          per_token(pos)[1:])

    @pytest.mark.parametrize("rep", [1, 2])
    def test_kernel_matches_oracle_ragged_gqa_empty(self, rep):
        """interpret-mode Pallas kernel vs the gather+einsum oracle on the
        identical pool bytes: ragged positions, GQA head grouping, a
        full slot, and an all-sentinel empty slot."""
        kp, vp, tbl, kd, vd = self._pool_and_dense()
        B, Hkv, ps, D = tbl.shape[0], kp.shape[1], kp.shape[2], kp.shape[3]
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(B, Hkv * rep, 1, D).astype(np.float32))
        pos = jnp.asarray([6, 3, 0], jnp.int32)  # mid-page, page-0-only, empty
        want = _oracle_attend(q, kp, vp, tbl, pos)
        got = paged_attention(q, kp, vp, tbl, pos)
        assert got.shape == want.shape == (B, Hkv * rep, 1, D)
        assert np.allclose(np.asarray(got)[:2], np.asarray(want)[:2],
                           atol=1e-5)
        # the empty slot: the oracle attends the trash page's first row,
        # the kernel walks no page and writes zeros; the engine reads
        # neither
        assert not np.asarray(got)[2].any()
        # oracle == the dense decode_attend it wraps
        ref = decode_attend(q, kd, vd, pos)
        assert np.allclose(np.asarray(want), np.asarray(ref), atol=1e-6)

    # positions in units of the kernel's chunk (``ct`` tokens); None = a
    # dead slot (all-sentinel row, position 0)
    _WALKS = {
        "one_chunk": lambda ct, cap: [ct - 1],
        "chunk_plus_one_token": lambda ct, cap: [ct],
        "partial_last_chunk": lambda ct, cap: [2 * ct + ct // 2 + 1, 3],
        "full_table_width": lambda ct, cap: [cap - 1, ct // 2],
        "dead_between_live": lambda ct, cap: [ct + 5, None, 2 * ct - 1],
    }

    @staticmethod
    def _walk_case(positions, *, Hkv, rep, ps, nb, D, dtype, seed=0,
                   poison=False):
        """Pools, a shuffled page table covering each slot's live pages,
        and q for ``positions``. With ``poison`` every page outside the
        live sets (the trash page too) is NaN in the pools handed to the
        kernel; the returned clean pools are what the oracle reads."""
        rng = np.random.RandomState(seed)
        B = len(positions)
        P = B * nb + 1
        kp = rng.randn(P, Hkv, ps, D).astype(np.float32)
        vp = rng.randn(P, Hkv, ps, D).astype(np.float32)
        ids = 1 + rng.permutation(P - 1)
        table = np.full((B, nb), PAGE_SENTINEL, np.int32)
        pos = np.zeros(B, np.int32)
        used = 0
        for b, p in enumerate(positions):
            if p is None:
                continue
            n = p // ps + 1
            table[b, :n] = ids[used:used + n]
            used += n
            pos[b] = p
        live = [b for b, p in enumerate(positions) if p is not None]
        dead_pages = np.setdiff1d(np.arange(P), table[table >= 0])
        kq, vq = kp.copy(), vp.copy()
        if poison:
            kq[dead_pages] = vq[dead_pages] = np.nan
        q = jnp.asarray(rng.randn(B, Hkv * rep, 1, D), dtype)
        as_ = lambda a: jnp.asarray(a, dtype)
        return (q, as_(kq), as_(vq), as_(kp), as_(vp), jnp.asarray(table),
                jnp.asarray(pos), live)

    def _assert_walk_matches_oracle(self, case, tol):
        q, kq, vq, kp, vp, tbl, pos, live = case
        want = np.asarray(_oracle_attend(q, kp, vp, tbl, pos), np.float32)
        got = np.asarray(paged_attention(q, kq, vq, tbl, pos), np.float32)
        assert np.isfinite(got).all()
        err = np.abs(got[live] - want[live]).max() / np.abs(want[live]).max()
        assert err <= tol, err
        dead = [b for b in range(q.shape[0]) if b not in live]
        assert not got[dead].any()

    @pytest.mark.parametrize("rep", [1, 2])
    @pytest.mark.parametrize("walk", sorted(_WALKS))
    def test_kernel_walk_matches_oracle(self, walk, rep):
        """The in-kernel page walk against the oracle where its loop and
        its copies can go wrong: a context of exactly one chunk, one chunk
        plus a token, several chunks with a partial last one, the table's
        full width, and a dead slot between two live ones."""
        Hkv, ps, D = 2, 16, 8
        ct = _pages_per_chunk(Hkv, Hkv * rep, ps, D, 4) * ps
        nb = 3 * ct // ps
        positions = self._WALKS[walk](ct, nb * ps)
        self._assert_walk_matches_oracle(self._walk_case(
            positions, Hkv=Hkv, rep=rep, ps=ps, nb=nb, D=D,
            dtype=jnp.float32), tol=1e-5)

    @pytest.mark.parametrize("Hkv,rep", [(2, 16), (8, 8), (8, 16), (16, 1)])
    def test_kernel_walk_at_serving_tile_shapes(self, Hkv, rep):
        """Page 16, D 128, bf16 pools at the cells' head counts (reasoning,
        agents, rag, chat), each walked at ITS chunk width: under one
        chunk, exactly one, one plus a token, three and a half, the
        table's full width, and a dead slot between live ones; tolerance
        as chip_smoke's for bf16."""
        ps, D = 16, 128
        ct = _pages_per_chunk(Hkv, Hkv * rep, ps, D, 2) * ps
        nb = 4 * ct // ps
        self._assert_walk_matches_oracle(self._walk_case(
            [ct // 2, ct - 1, ct, None, 3 * ct + ct // 2, nb * ps - 1, 15],
            Hkv=Hkv, rep=rep, ps=ps, nb=nb, D=D, dtype=jnp.bfloat16),
            tol=5e-2)

    @pytest.mark.parametrize("shape", ["tiny_pages", "two_kv_heads"])
    def test_kernel_reads_only_live_pages(self, shape):
        """Every pool page outside the slots' live sets is NaN (tails of
        live pages stay finite, as the allocator leaves them): the output
        is finite and the oracle's on the live rows, so no dead page, no
        trash page and no stale buffer reached the sum. At 2 K/V heads
        the contexts end in a partial chunk behind whole ones, so both of
        the walk's bodies read poisoned pools."""
        if shape == "tiny_pages":
            case = self._walk_case(
                [140, None, 3, 400, None, 256], Hkv=2, rep=2, ps=8, nb=56,
                D=8, dtype=jnp.float32, poison=True)
            tol = 1e-5
        else:
            ct = _pages_per_chunk(2, 32, 16, 128, 2) * 16
            case = self._walk_case(
                [ct + ct // 2 + 3, None, 3, 2 * ct + 17, None, ct - 1],
                Hkv=2, rep=16, ps=16, nb=3 * ct // 16, D=128,
                dtype=jnp.bfloat16, poison=True)
            tol = 5e-2
        self._assert_walk_matches_oracle(case, tol)

    # (H_kv, H_q) of the six serving configurations (chat, Keye, hybrid,
    # agents, rag, reasoning; page 16, D 128, bf16) -> pages a chunk
    @pytest.mark.parametrize("Hkv,Hq,pages", [
        (16, 16, 8), (4, 32, 32), (30, 30, 8), (8, 64, 16), (8, 128, 16),
        (2, 32, 64)])
    def test_chunk_width_follows_the_shapes(self, Hkv, Hq, pages):
        """The chunk's width is a function of the operands' shapes: as
        many pages as fill a buffer of about half a MiB, at most 1,024
        tokens and a [H_q, chunk] float32 score tile of 128 KiB, a whole
        number of lane widths of tokens, four buffers inside the VMEM
        budget."""
        from paddle_tpu.kernels.flash_attention import LANES
        from paddle_tpu.kernels.paged_attention import _CHUNK_VMEM_BYTES

        ps, D, itemsize = 16, 128, 2
        got = _pages_per_chunk(Hkv, Hq, ps, D, itemsize)
        assert got == pages
        assert got * ps % LANES == 0 and got * ps <= 1024
        assert 4 * got * Hkv * ps * D * itemsize <= _CHUNK_VMEM_BYTES
        # the tests' tiny pages do not make a chunk of thousands of pages
        assert _pages_per_chunk(2, 4, 4, 8, 4) * 4 <= 1024

    @pytest.mark.parametrize("nb", [8, 128])
    def test_kernel_grid_does_not_depend_on_table_width(self, nb):
        """One grid step a slot, whatever the table's width: the page walk
        is the kernel's own loop."""
        import jax

        B, Hkv, ps, D = 4, 2, 16, 128
        sds = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(paged_attention)(
            sds((B, Hkv, 1, D), jnp.bfloat16),
            sds((9, Hkv, ps, D), jnp.bfloat16),
            sds((9, Hkv, ps, D), jnp.bfloat16),
            sds((B, nb), jnp.int32), sds((B,), jnp.int32))

        def pallas_calls(jaxpr):
            for e in jaxpr.eqns:
                if e.primitive.name == "pallas_call":
                    yield e
                for sub in jax.core.jaxprs_in_params(e.params):
                    yield from pallas_calls(sub)

        calls = list(pallas_calls(jaxpr.jaxpr))
        assert len(calls) == 1
        assert "paged_decode" in str(calls[0].params["name"])
        assert tuple(calls[0].params["grid_mapping"].grid) == (B,)

    def test_impl_dispatch_and_override(self):
        """One function says kernel or oracle: the platform (a CPU here, so
        the oracle) unless the context manager pinned a tier, and
        ``paged_decode_attend`` traces what it says."""
        import jax

        kp, vp, tbl, _, _ = self._pool_and_dense()
        q = jnp.zeros((tbl.shape[0], kp.shape[1], 1, kp.shape[3]))
        pos = jnp.zeros((tbl.shape[0],), jnp.int32)

        def traces_kernel():
            # a new function object per call: traces are cached by function
            # identity, and the tier is chosen while tracing
            return "pallas_call" in str(jax.make_jaxpr(
                lambda *a: paged_decode_attend(*a))(q, kp, vp, tbl, pos))

        assert default_paged_impl() == "oracle" and not traces_kernel()
        with use_paged_attention_impl("pallas"):
            assert default_paged_impl() == "pallas" and traces_kernel()
            with use_paged_attention_impl("oracle"):
                assert not traces_kernel()
            assert default_paged_impl() == "pallas"
        assert default_paged_impl() == "oracle"
        with pytest.raises(ValueError):
            use_paged_attention_impl("nope").__enter__()


# ---------------- the programs update the pools in place ------------------
def _program(eng, name):
    return {"decode": eng.decode_program,
            "verify": lambda: eng.verify_program(k=2),
            "prefill": lambda: eng.prefill_program(16),
            "extend": lambda: eng.extend_program(16)}[name]()


class TestPoolsUpdatedInPlace:
    """The KV cache is a tuple of per-layer donated buffers (ISSUE 25): every
    serving program writes each layer where it lies and holds no second
    copy — of one layer, let alone of the stack the parent rebuilt."""

    @pytest.mark.parametrize("layout,name", [
        ("paged", "decode"), ("paged", "prefill"), ("paged", "extend"),
        ("paged", "verify")])
    def test_every_pool_leaf_aliased_and_no_pool_sized_temp(self, layout,
                                                            name):
        import re

        import jax
        from paddle_tpu.serving.engine import KV_DONATE_ARGNUMS

        # pools far larger than anything else the tiny model holds, so a
        # temporary the size of one layer's pool cannot hide
        eng = Engine(_tiny(), EngineConfig(
            max_batch_size=2, max_seq_len=64, kv_pages=2048, page_size=8))
        fn, args = _program(eng, name)
        exe = jax.jit(fn, donate_argnums=KV_DONATE_ARGNUMS) \
            .lower(*args).compile()
        L = eng.cache.num_layers
        assert len(eng.cache.k) == len(eng.cache.v) == L
        assert args[1] is eng.cache.k and args[2] is eng.cache.v
        first = len(jax.tree_util.tree_leaves(args[0]))  # params come first
        header = exe.as_text().split("\n", 1)[0]
        aliased = {int(p) for p in re.findall(
            r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
        assert aliased == set(range(first, first + 2 * L)), header[:400]
        leaf = eng.cache.k[0]
        leaf_bytes = leaf.size * leaf.dtype.itemsize
        ma = exe.memory_analysis()
        assert ma.alias_size_in_bytes == 2 * L * leaf_bytes
        assert ma.temp_size_in_bytes < leaf_bytes, (
            ma.temp_size_in_bytes, leaf_bytes)

    def test_decode_jaxpr_rebuilds_no_stacked_cache(self):
        """No concatenate / dynamic_update_slice in the decode program
        puts out an array the size of all layers' pools (the parent's
        ``jnp.stack`` of the per-layer pools did)."""
        import jax

        eng = Engine(_tiny(), EngineConfig(max_batch_size=2, max_seq_len=64,
                                           page_size=8))
        fn, args = eng.decode_program()
        stack = sum(k.size for k in eng.cache.k)
        seen = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                seen.append(eqn.primitive.name)
                if eqn.primitive.name in ("concatenate",
                                          "dynamic_update_slice"):
                    assert all(v.aval.size < stack for v in eqn.outvars), eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        assert "scatter" in seen  # the walk did reach the cache writes

    def test_prefix_hit_cow_and_speculation_match_plain_greedy(self):
        """Ragged prompts through ONE engine with the prefix cache and
        speculation on — a cold prompt, a prefix hit (suffix prefill
        through the extend program) and a request whose shared page is
        copied on write in mid-run — emit token for token what a plain
        engine emits."""
        m = _tiny()
        warm = [int(t) for t in _prompt(1, 20, seed=5)[0]]
        prompts = [warm, warm[:16] + [7, 9, 11], [3, 1, 4, 1, 5, 9, 2, 6]]
        sp = SamplingParams(max_new_tokens=10)
        want = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=64,
                                      page_size=8)).generate(prompts, sp)
        eng = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=64,
                                     page_size=8, prefix_cache=True,
                                     speculative=2))
        # pages can be shared, so the copy program was compiled with the
        # engine: a copy on write never compiles between two decode steps
        copy_exe = eng.cache._copy_exes.get(0)
        assert copy_exe is not None
        got = eng.generate(prompts[:1], sp)
        reqs = [eng.add_request(p, sp) for p in prompts[1:]]
        eng.step()                       # both admitted, first tokens out
        assert reqs[0].prefix_hit_blocks == 2
        slot = reqs[0].slot
        shared = int(eng.cache.page_table[slot, 0])
        assert eng.page_alloc.is_shared(shared)
        assert eng._ensure_writable(slot, 0,
                                    owner=f"req{reqs[0].request_id}")
        assert int(eng.cache.page_table[slot, 0]) != shared
        while eng.has_unfinished:
            eng.step()
        got += [r.output_ids for r in reqs]
        assert got == want
        assert eng._cow_copies == 1 and reqs[0].draft_tokens > 0
        assert eng.cache.copy_page_exe() is copy_exe


# ---------------- engine: paged layout ------------------------------------
class TestPagedEngine:
    def test_paged_matches_dense_layout_with_midrun_admission(self):
        """3 ragged greedy requests through 2 slots (so the third is
        admitted mid-run) emit what ``model.generate`` — the lockstep loop
        over dense ``[B, H_kv, S_max, D]`` buffers, the engine's
        independent reference — emits a prompt at a time. GQA model, page
        smaller than the prefill bucket so prefill exercises
        partial/multi-page scatter."""
        prompts = [[5, 17, 3], [9, 2, 11, 4, 8, 1, 7, 12, 6], [7, 7, 7]]
        sp = SamplingParams(max_new_tokens=5)
        paddle.seed(0)
        m = _tiny(num_kv_heads=2)
        dense = [np.asarray(m.generate(
            paddle.to_tensor(np.asarray([p], np.int32)),
            max_new_tokens=5)._value)[0, len(p):].tolist() for p in prompts]
        paged = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=32,
                                       page_size=4)).generate(prompts, sp)
        assert paged == dense

    def test_interpret_kernel_engine_matches_oracle_engine(self):
        """End-to-end decode through the Pallas kernel (interpret tier)
        equals the oracle tier — including the empty slot the 1-request
        batch leaves in the B=2 decode."""
        paddle.seed(0)
        m = _tiny()
        prompts = [[5, 17, 3, 9, 2]]
        sp = SamplingParams(max_new_tokens=4)
        cfg = EngineConfig(max_batch_size=2, max_seq_len=32)
        with use_paged_attention_impl("oracle"):
            oracle = Engine(m, cfg).generate(prompts, sp)
        with use_paged_attention_impl("pallas"):
            eng = Engine(m, cfg)
            kern = eng.generate(prompts, sp)
        assert kern == oracle
        # the tier was baked in at trace time: the kernel engine's programs
        # go on running the kernel outside the context
        assert eng.generate(prompts, sp) == oracle

    def test_interpret_kernel_extend_matches_oracle_engine(self):
        """A prefix hit's suffix goes through the extend program: with 4
        MHA heads (one query head a K/V head) its attention is
        ``extend_flash`` in the ``pallas`` tier, the einsum reference over
        the gathered view in the ``oracle`` tier, and the served tokens are
        the same. The decode program names the decode kernel alone, the
        prefill program no paged kernel."""
        from paddle_tpu.kernels.mesh import traced_kernels

        paddle.seed(0)
        m = _tiny()
        warm = [int(t) for t in _prompt(1, 20, seed=5)[0]]
        prompts = [warm, warm[:16] + [7, 9, 11, 2, 4]]
        sp = SamplingParams(max_new_tokens=6)
        cfg = EngineConfig(max_batch_size=2, max_seq_len=64, page_size=8,
                           prefix_cache=True)
        outs, names = {}, {}
        for impl in ("oracle", "pallas"):
            with use_paged_attention_impl(impl):
                eng = Engine(m, cfg)
                outs[impl] = [eng.generate([p], sp)[0] for p in prompts]
                assert [k for k in eng._exe if k[0] == "extend"]
                for kind, a in (("extend", (8,)), ("decode", ()),
                                ("prefill", (32,))):
                    fn, args = getattr(eng, kind + "_program")(*a)
                    names[impl, kind] = set(traced_kernels(fn, *args))
        assert outs["pallas"] == outs["oracle"]
        assert names["pallas", "extend"] == {"extend_flash"}
        assert names["pallas", "decode"] == {"paged_decode"}
        assert "extend_flash" not in names["pallas", "prefill"]
        assert not any(names["oracle", k] for k in ("extend", "decode"))

    def test_paged_decode_compiles_once(self, telemetry):
        """The page table is runtime data: admissions, finishes, and table
        rewrites between steps never change the decode signature — ONE
        decode compile for the engine lifetime (two prompt lengths share
        one bucket here, so prefill is one compile too)."""
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=32,
                                     page_size=8))
        outs = eng.generate([[5, 17, 3], [9, 2, 4, 1, 6], [8, 3]],
                            SamplingParams(max_new_tokens=6))
        assert all(len(o) == 6 for o in outs)
        c = obs.snapshot()["counters"]
        assert c["jit.compile.cache_miss{site=serving.decode}"] == 1
        assert c["jit.compile.cache_miss{site=serving.prefill}"] == 1

    def test_admission_backpressure_then_midrun_admit(self, telemetry):
        """kv_pages below the envelope: the second request backpressures in
        the queue (slots are free — PAGES are not), gets admitted when the
        first finishes and frees its pages, and the pool ends exactly
        covered (everything back on the free list)."""
        m = _tiny()
        # 1 allocatable page of 8 tokens: exactly one request in flight
        eng = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=32,
                                     page_size=8, kv_pages=2))
        r1 = eng.add_request([5, 17, 3], SamplingParams(max_new_tokens=2))
        r2 = eng.add_request([9, 2, 4], SamplingParams(max_new_tokens=2))
        eng.step()  # r1 admitted; r2 must wait for pages, not slots
        assert r1.state == "running" and r2.state == "queued"
        eng.step()  # r1's one decode step, launched by the call before, settles
        assert r1.state == "finished" and r1.finish_reason == "length"
        assert r2.state == "queued"
        assert eng.cache.free_slots == 2  # both slots idle: pages were the
        assert eng.page_alloc.num_allocated == 0     # binding constraint
        eng.step()  # r1's pages are back -> r2 admitted
        while eng.has_unfinished:
            eng.step()
        assert r2.finish_reason == "length" and len(r2.output_ids) == 2
        # exact cover restored
        assert eng.page_alloc.num_allocated == 0
        assert eng.page_alloc.num_free == eng.page_alloc.num_allocatable
        assert (eng.cache.page_table == PAGE_SENTINEL).all()
        g = obs.snapshot()["gauges"]
        assert g["serving.kv.pages.allocated"] == 0
        assert g["serving.kv.pages.free"] == 1
        assert g["serving.kv.page_utilization"] == 0.0

    def test_decode_growth_exhaustion_finishes_cache_full(self):
        """A generation that outgrows the pool finishes ``cache_full`` at
        the step whose page can't be mapped; its generated prefix is
        intact and every page returns to the allocator."""
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=1, max_seq_len=32,
                                     page_size=4, kv_pages=2))
        r = eng.add_request([5, 17, 3], SamplingParams(max_new_tokens=10))
        while eng.has_unfinished:
            eng.step()
        # admission mapped page 0 (positions 0..3); position 4 needed a
        # second page the pool doesn't have
        assert r.finish_reason == "cache_full"
        assert len(r.output_ids) == 2
        assert eng.page_alloc.num_allocated == 0

    def test_kv_gauges_and_pool_bytes(self, telemetry):
        """Paged gauges ride next to mem.kv_cache.bytes, and a half-size
        pool really is half the dense HBM for the same envelope."""
        m = _tiny()
        eng = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=32,
                                     page_size=8))
        eng.generate([[5, 17, 3]], SamplingParams(max_new_tokens=2))
        g = obs.snapshot()["gauges"]
        assert g["mem.kv_cache.bytes"] == eng.cache.nbytes
        assert g["serving.kv_cache.bytes"] == eng.cache.nbytes
        for name in ("serving.kv.pages.allocated", "serving.kv.pages.free",
                     "serving.kv.page_utilization"):
            assert name in g
        # same envelope at kv_pages = half the budget -> ~half the bytes
        full = eng.cache.nbytes
        half = Engine(m, EngineConfig(max_batch_size=2, max_seq_len=32,
                                      page_size=8, kv_pages=5))
        assert half.cache.nbytes < full * 0.6

    def test_config_validation(self):
        m = _tiny()
        # page_size shrinks to divide S_max instead of failing
        eng = Engine(m, EngineConfig(max_batch_size=1, max_seq_len=24,
                                     page_size=16))
        assert eng.cache.page_size == 8
        assert 24 % eng.cache.page_size == 0
