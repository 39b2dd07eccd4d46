"""Sliding-window layers beside full ones, on page groups of their own:
``models/decoder`` (the ``sliding`` kind, positions by layer kind,
interleaved rotary pairs, a LayerNorm without a bias, the parallel block,
plain sigmoid top-k routing, shared experts combined by their mean), the
paged-decode kernel's ``window``, ``PagedKVCache``'s groups, the prefix
trie's resumable depths and the engine's sliding rule, held to the plain
reference ``benchmark/reference/command_a_plus.py`` on seeded weights.

float32 on the CPU, tiny widths, a window of 16 tokens over pages of 4 (so
a resume looks back on four blocks). Tolerances: logits of magnitude ~1
agree with the float32 "highest" reference within 2e-4 (sums of a few
hundred float32 products in another order); every WRONG reading of the
block (a window off by one, rotary on the full layer, a sequential block,
softmax weights, a summed shared part) moves them by more than 1e-2
(``test_a_wrong_reading_fails``)."""

import hashlib
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.paged_attention import (decode_attend,
                                                paged_decode_attend)
from paddle_tpu.kernels.pools import paged_gather
from paddle_tpu.kernels.tier import use_paged_attention_impl
from paddle_tpu.models import decoder as dec
from paddle_tpu.models.decoder import DecoderConfig, DecoderLM, param_shapes
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
from paddle_tpu.serving.kv_cache import PAGE_SENTINEL, PagedKVCache
from paddle_tpu.serving.prefix_cache import PrefixCache
from paddle_tpu.serving.scheduler import PageAllocator

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))
from reference import command_a_plus as ref  # noqa: E402

W, PS = 16, 4            # window, page: a resume looks back on 4 blocks
TOL = 2e-4
KINDS = ("sliding",) * 3 + ("dense",)
RCFG = {"num_heads": 4, "num_kv_heads": 2, "head_dim": 8,
        "rope_theta": 50000.0, "sliding_window": W, "norm_eps": 1e-5,
        "experts_per_token": 2, "norm_topk_prob": True,
        "experts_held": (8, 0), "shared_experts": 4,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}


def _cfg(**kw):
    base = dict(
        vocab_size=64, hidden_size=32, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=8, max_context=320, norm="layer_nobias",
        norm_eps=1e-5, norm_placement="parallel", position="rope_gptj",
        position_by_kind={"dense": "none"}, rope_theta=50000.0,
        qk_norm=False, layer_types=KINDS, sliding_window=W,
        kv_layout="head", ffn="moe_swiglu", intermediate_size=16,
        router="sigmoid_topk", num_experts=8, experts_per_token=2,
        shared_experts=4, shared_combine="mean", tie_word_embeddings=True,
        query_chunk=8, initializer_range=0.4)
    base.update(kw)
    return DecoderConfig(**base)


def _model(cfg, like=None):
    """The model of ``cfg`` with norm scales 1 + N(0, 0.1) (a scale of 1
    would hide a missing one); with ``like``, that model's weights where
    the names and shapes agree."""
    m = DecoderLM(cfg)
    key = jax.random.PRNGKey(3)
    have = {} if like is None else _params(like)
    for i, (n, p) in enumerate(m.named_parameters()):
        v = p._value
        if n in have and have[n].shape == v.shape:
            v = have[n]
        elif n.endswith("norm.weight"):
            v = v + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                            v.shape, jnp.float32)
        p._set_value_raw(v.astype(jnp.float32))
    return m


def _params(m):
    return {n: p._value for n, p in m.named_parameters()}


def _ids(n, seed):
    return np.random.RandomState(seed).randint(0, 64, size=(n,)).tolist()


@pytest.fixture(scope="module")
def model():
    return _model(_cfg())


def _ref_logits(m, text, rc=RCFG):
    return np.asarray(ref.forward(_params(m), jnp.asarray(text), rc,
                                  q_block=len(text)))


def _greedy_gap(m, prompt, out):
    """Widest gap of a served token's reference logit below the reference's
    best, over ONE forward of the reference on prompt + served tokens."""
    lg = _ref_logits(m, list(prompt) + list(out[:-1]))[len(prompt) - 1:]
    return float(np.max(lg.max(-1) - lg[np.arange(len(out)), out]))


def _engine(m, impl="oracle", **kw):
    conf = dict(max_batch_size=3, max_seq_len=128, page_size=PS,
                prefix_cache=True, prefill_buckets=(8, 16, 32, 64, 128),
                group_pages={"window": 40})
    conf.update(kw)
    with use_paged_attention_impl(impl):
        return Engine(m, EngineConfig(**conf))


def _generate(eng, prompts, n, impl="oracle", each_step=None):
    with use_paged_attention_impl(impl):
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p in prompts]
        while eng.has_unfinished:
            eng.step()
            if each_step is not None:
                each_step(eng)
    return [r.output_ids for r in reqs]


# -------------------------------- (a) layers and model against the reference

@pytest.mark.parametrize("T", [8, 16, 17, 40, 256])
def test_model_matches_the_reference(model, T):
    """Under, at and over the window; at 256 a chunk of queries is handed
    a slice of the keys (``attend``'s band), not all of them."""
    ids = _ids(T, seed=T)
    got = model.forward(jnp.asarray(ids)[None])._value[0]
    np.testing.assert_allclose(got, _ref_logits(model, ids), atol=TOL)


@pytest.mark.parametrize("l,kind", [(0, "sliding_attention"),
                                    (3, "full_attention")])
@pytest.mark.parametrize("T", [12, 16, 40])
def test_one_layer_matches_the_reference(model, l, kind, T):
    p = _params(model)
    x = jax.random.normal(jax.random.PRNGKey(T), (1, T, 32), jnp.float32)
    got, _, _ = dec.block(model.cfg, p, l, x, jnp.zeros((1,), jnp.int32))
    pre = f"layers.{l}."
    want = ref.layer(x[0], {k[len(pre):]: v for k, v in p.items()
                            if k.startswith(pre)}, kind, RCFG, q_block=T)
    np.testing.assert_allclose(got[0], want, atol=TOL)


@pytest.mark.parametrize("by_head", [False, True])
def test_prefill_programs_form_matches_the_reference(model, by_head):
    """``prefill_with_cache``: the flash seam in the full layer (all heads
    at once, or one key/value head with its query heads at a time), the
    band in the sliding ones; the last real token's logits, and the keys
    handed out for both page groups."""
    m = _model(_cfg(flash_by_kv_head=by_head), like=model)
    ids = _ids(48, seed=2) + [0] * 16
    logits, news = m.prefill_with_cache(jnp.asarray(ids)[None],
                                        lengths=jnp.asarray([48]))
    np.testing.assert_allclose(logits._value[0],
                               _ref_logits(model, ids[:48])[-1], atol=TOL)
    assert [tuple(t._value.shape for t in layer) for layer in news] \
        == [((1, 2, 64, 8),) * 2] * 4


@pytest.mark.parametrize("wrong", [
    dict(sliding_window=W + 1), dict(sliding_window=W - 1),
    dict(position_by_kind=None), dict(router="softmax_topk"),
    dict(shared_combine="sum"), dict(position="rope")])
def test_a_wrong_reading_fails(model, wrong):
    """A window off by one either way, rotary positions on the full layer,
    softmax weights, a summed shared part, half-split pairs on unpermuted
    weights: each is far outside the tolerance."""
    m = _model(_cfg(**wrong), like=model)
    ids = _ids(40, seed=40)
    got = m.forward(jnp.asarray(ids)[None])._value[0]
    assert np.abs(got - _ref_logits(model, ids)).max() > 1e-2


# ------------------------------------------ (f) the router, the shared mean

def test_sigmoid_router_and_shared_mean(model):
    cfg, p = model.cfg, _params(model)
    g = jax.random.normal(jax.random.PRNGKey(5), (24, 32), jnp.float32)
    pw, e = dec.sigmoid_topk(cfg, g, p["layers.1.ffn.router"])
    lp = {k[len("layers.1."):]: v for k, v in p.items()
          if k.startswith("layers.1.")}
    weight = np.asarray(ref.route(g, lp, RCFG, ref.mm_highest))
    np.testing.assert_allclose(
        np.take_along_axis(weight, np.asarray(e), 1), pw, atol=1e-6)
    assert np.count_nonzero(weight) == 24 * 2
    # softmax makes the same choice (both rise with the logit) and weighs
    # it otherwise
    sw, se = dec.softmax_topk(cfg, g, p["layers.1.ffn.router"])
    assert np.array_equal(np.sort(se, 1), np.sort(e, 1))
    assert np.abs(np.sort(sw, 1) - np.sort(pw, 1)).max() > 1e-2
    # the mean of the four shared experts, not their sum
    y, _ = dec.moe_swiglu(cfg, p, "layers.1.ffn", g)
    want = ref.routed_experts(g, lp, RCFG, ref.mm_highest) \
        + ref.shared_experts(g, lp, RCFG, ref.mm_highest)
    np.testing.assert_allclose(y, want, atol=TOL)
    summed, _ = dec.moe_swiglu(_cfg(shared_combine="sum"), p,
                               "layers.1.ffn", g)
    np.testing.assert_allclose(
        summed - y, 3 * ref.shared_experts(g, lp, RCFG, ref.mm_highest),
        atol=TOL)


# ---------------------------- (g) the parallel block, the rotary pairing

def test_parallel_block_is_not_the_sequential_one(model):
    p = dict(_params(model))
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 20, 32), jnp.float32)
    start = jnp.zeros((1,), jnp.int32)
    par, _, _ = dec.block(model.cfg, p, 3, x, start)
    p["layers.3.ffn_norm.weight"] = p["layers.3.attn_norm.weight"]
    seq, _, _ = dec.block(_cfg(norm_placement="pre"), p, 3, x, start)
    assert np.abs(par - seq).max() > 1e-2
    lp = {k[len("layers.3."):]: v for k, v in p.items()
          if k.startswith("layers.3.")}
    want = ref.layer(x[0], lp, "full_attention", RCFG, q_block=20)
    np.testing.assert_allclose(par[0], want, atol=TOL)
    assert "layers.3.ffn_norm.weight" not in param_shapes(model.cfg)
    assert "layers.3.attn_norm.bias" not in param_shapes(model.cfg)


def test_interleaved_pairs_are_half_split_ones_under_a_permutation():
    """``rope_gptj`` on x = ``rope`` on x's lanes in the order (0, 2, 4,
    ..., 1, 3, 5, ...): the same model under that permutation of the
    columns of wq and wk (scores are dot products: a permutation of both
    sides' lanes changes none)."""
    D = 8
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 3, D), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 7, 3, D), jnp.float32)
    pos = jnp.asarray(np.random.RandomState(0).randint(0, 300, (2, 7)))
    perm = np.concatenate([np.arange(0, D, 2), np.arange(1, D, 2)])
    np.testing.assert_allclose(dec.rope_gptj(x, pos, 5e4)[..., perm],
                               dec.rope(x[..., perm], pos, 5e4), atol=1e-5)
    np.testing.assert_allclose(
        ref.rotary_interleaved(x[0], pos[0], 5e4),
        dec.rope_gptj(x, pos, 5e4)[0], atol=1e-5)
    dots = lambda f, a, b: jnp.einsum("bthd,bshd->bhts", f(a), f(b))
    np.testing.assert_allclose(
        dots(lambda a: dec.rope_gptj(a, pos, 5e4), x, y),
        dots(lambda a: dec.rope(a[..., perm], pos, 5e4), x, y), atol=1e-4)


# ----------------------------------------------------- (h) the sum of shares

def test_four_shares_add_up_to_the_uncut_layer():
    """32 experts, four chips of 8: the routed parts of the four shares,
    and the shared experts counted ONCE, add up to the uncut layer's."""
    whole = _model(_cfg(num_experts=32, experts_per_token=4))
    pw = _params(whole)
    g = jax.random.normal(jax.random.PRNGKey(11), (40, 32), jnp.float32)
    rc = {**RCFG, "experts_per_token": 4, "experts_held": (32, 0)}
    lp = {k[len("layers.1."):]: v for k, v in pw.items()
          if k.startswith("layers.1.")}
    want = ref.routed_experts(g, lp, rc, ref.mm_highest)
    total, rows = 0.0, 0
    for chip in range(4):
        cfg = _cfg(num_experts=32, experts_per_token=4,
                   experts_held=(8, 8 * chip))
        p = dict(pw)
        for w in ("w1", "w3", "w2"):
            p[f"layers.1.ffn.{w}"] = pw[f"layers.1.ffn.{w}"][8 * chip:
                                                             8 * chip + 8]
        y, stats = dec.moe_routed(cfg, p, "layers.1.ffn", g)
        total, rows = total + y, rows + int(stats[2])
    np.testing.assert_allclose(total, want, atol=TOL)
    assert rows == 40 * 4
    full, _ = dec.moe_swiglu(_cfg(num_experts=32, experts_per_token=4), pw,
                             "layers.1.ffn", g)
    np.testing.assert_allclose(
        full, total + ref.shared_experts(g, lp, rc, ref.mm_highest), atol=TOL)


# ------------------------------------------------ (d) the kernel's window

def _pools(B, nb, Hkv=2, D=8, seed=0):
    rs = np.random.RandomState(seed)
    P = B * nb + 1
    k = jnp.asarray(rs.randn(P, Hkv, PS, D), jnp.float32)
    v = jnp.asarray(rs.randn(P, Hkv, PS, D), jnp.float32)
    table = np.arange(1, P).reshape(B, nb).astype(np.int32)
    return k, v, table


@pytest.mark.parametrize("window", [6, 16, 17, "three_chunks"])
def test_window_kernel_matches_the_oracle(window):
    """Ragged lengths, an empty slot, a context shorter than the window,
    windows that start mid-page (6 and 17 over pages of 4), and the pages
    behind the window unmapped, as the engine leaves them. The last case
    is a window of three chunks of the kernel's walk."""
    if window == "three_chunks":
        # two and a half chunks and two tokens: from a position that ends a
        # page the walk starts mid-page and ends in a partial chunk
        from paddle_tpu.kernels.paged_attention import _pages_per_chunk

        ct = _pages_per_chunk(2, 4, PS, 8, 4) * PS
        window = 2 * ct + ct // 2 + 2
        B, nb = 5, (window + window // 2) // PS
        pos = np.array([3, 0, window + 37, nb * PS - 1, window - 9], np.int32)
    else:
        B, nb = 5, 16
        pos = np.array([3, 0, 37, 63, 21], np.int32)
    k, v, table = _pools(B, nb)
    table[1] = PAGE_SENTINEL                          # an empty slot
    for b in (2, 3, 4):                               # freed behind the window
        table[b, :max(0, pos[b] - window + 1) // PS] = PAGE_SENTINEL
    q = jnp.asarray(np.random.RandomState(1).randn(B, 4, 1, 8), jnp.float32)
    with use_paged_attention_impl("pallas"):
        got = paged_decode_attend(q, k, v, jnp.asarray(table),
                                  jnp.asarray(pos), window=window)
    with use_paged_attention_impl("oracle"):
        want = paged_decode_attend(q, k, v, jnp.asarray(table),
                                   jnp.asarray(pos), window=window)
    live = np.array([0, 2, 3, 4])
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got[live], want[live], atol=1e-5)
    assert not got[1].any()                           # zeros, read by no one
    # the oracle's lower bound is the mask of a dense softmax
    kd, vd = paged_gather(k, jnp.asarray(table)), \
        paged_gather(v, jnp.asarray(table))
    s = jnp.einsum("bhd,bhkd->bhk", q[:, :, 0] / np.sqrt(8.0),
                   jnp.repeat(kd, 2, axis=1))
    kp = np.arange(nb * PS)[None, None, :]
    ok = (kp <= pos[:, None, None]) & (kp > pos[:, None, None] - window)
    dense = jnp.einsum("bhk,bhkd->bhd",
                       jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1),
                       jnp.repeat(vd, 2, axis=1))
    np.testing.assert_allclose(want[live, :, 0], np.asarray(dense)[live],
                               atol=1e-5)


def test_no_window_is_the_kernel_it_was():
    """``window=None`` traces the kernel under its old name with nothing
    added (the decode programs' lowered text is pinned below and in
    tests/test_hybrid_serving.py); its output is the oracle's, and bit for
    bit that of a window wider than any context."""
    import importlib

    pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
    B, nb = 3, 8
    k, v, table = _pools(B, nb, seed=4)
    pos = jnp.asarray(np.array([5, 30, 17], np.int32))
    q = jnp.asarray(np.random.RandomState(2).randn(B, 4, 1, 8), jnp.float32)
    with use_paged_attention_impl("pallas"):
        none = pa.paged_attention(q, k, v, jnp.asarray(table), pos)
        wide = pa.paged_attention(q, k, v, jnp.asarray(table), pos, 10 ** 6)
    np.testing.assert_allclose(
        none, decode_attend(q, paged_gather(k, jnp.asarray(table)),
                            paged_gather(v, jnp.asarray(table)), pos),
        atol=1e-5)
    assert np.array_equal(np.asarray(none), np.asarray(wide))
    text = lambda w: str(jax.make_jaxpr(
        lambda *a: pa.paged_attention(*a, window=w))(
            q, k, v, jnp.asarray(table), pos))
    assert "paged_decode" in text(None) and "window_decode" not in text(None)
    assert "window_decode" in text(16) and "paged_decode" not in text(16)


def _kernels_of(eng, kind, *a):
    """Names of the Pallas calls in a program of ``eng`` as traced (on the
    CPU a kernel lowers to plain HLO, which ``kernel_sites`` cannot read)."""
    from paddle_tpu.kernels.mesh import traced_kernels

    fn, args = getattr(eng, kind + "_program")(*a)
    return set(traced_kernels(fn, *args))


def test_extends_behind_a_prefix_hit_serve_the_same_tokens_in_both_tiers(
        model):
    """Three prompts on one document, one after the other: the third
    resumes at the document's end and its suffix goes through an extend
    program, whose sliding layers attend a view of the window and its full
    layer the whole table's. In the ``pallas`` tier those are
    ``window_extend_flash`` and ``extend_flash`` and nothing else of the
    extend is a kernel's; the decode program names the decode kernels and
    the prefill program none. Greedy tokens are the oracle tier's."""
    doc = _ids(44, seed=100)
    prompts = [doc + _ids(n, seed=n) for n in (5, 9, 13)]
    outs = {}
    for impl in ("oracle", "pallas"):
        eng = _engine(model, impl)
        outs[impl] = [_generate(eng, [p], 8, impl)[0] for p in prompts]
        assert [k for k in eng._exe if k[0] == "extend"], "no extend ran"
        with use_paged_attention_impl(impl):
            ext = _kernels_of(eng, "extend", 16)
            dec = _kernels_of(eng, "decode")
            pre = _kernels_of(eng, "prefill", 64)
        attends = {"extend_flash", "window_extend_flash", "paged_decode",
                   "window_decode"}
        if impl == "oracle":
            assert not attends & (ext | dec | pre)
            continue
        assert attends & ext == {"extend_flash", "window_extend_flash"}
        assert attends & dec == {"paged_decode", "window_decode"}
        assert not attends & pre
    assert outs["pallas"] == outs["oracle"]
    for p, o in zip(prompts, outs["pallas"]):
        assert _greedy_gap(model, p, o) < TOL


# -------------------------------------- the cache's groups, the trie's depths

def test_cache_groups_and_their_tables(model):
    pools = model.cache_pools()
    assert [p[0] for p in pools] == ["k_window", "v_window", "k", "v"]
    assert pools[0][3:] == ((0, 1, 2), ("window", W)) and pools[2][3] == (3,)
    c = PagedKVCache(4, 2, 2, 32, 8, page_size=PS, num_pages=17, pools=pools,
                     group_pages={"window": 9})
    assert c.groups == [("global", None, 17), ("window", W, 9)]
    assert c.pool_group == [1, 1, 0, 0]
    assert [p[0].shape[0] for p in c.pools] == [9, 9, 17, 17]
    c.assign_pages(0, [3, 4, 5])
    c.assign_at(0, [2, 5], [7, 8], group=1)
    assert c.slot_pages(0) == [3, 4, 5] and c.slot_pages(0, 1) == [7, 8]
    assert c.table_changed == 2
    g, w = c.tables_device()
    assert c.table_changed == 0 and int(w[0, 5]) == 8 and int(g[0, 2]) == 5
    # each layer is handed its own group's table
    entries = c.layer_entries(c.pools, ("G", "W"))
    assert [e[-1] for e in entries] == ["W", "W", "W", "G"]
    assert c.unmap_before(0, 3, group=1) == [7] and c.table_changed == 1
    assert c.clear_slot(0, 1) == [8] and c.clear_slot(0) == [3, 4, 5]
    # a model without a group builds what it built
    one = PagedKVCache(2, 2, 2, 32, 8, page_size=PS)
    assert one.groups == [("global", None, 17)] and one.pool_group == [0, 0]


def test_a_match_is_cut_back_to_a_resumable_depth():
    """A depth can be resumed only if the blocks that hold the window - 1
    tokens before it (four, here) still have their window pages."""
    ga, wa = PageAllocator(40), PageAllocator(40, "window")
    trie = PrefixCache(PS, ga, more=[(wa, W)])
    prompt = _ids(12 * PS + 1, seed=1)
    gp, wp = ga.alloc(12), wa.alloc(12)
    holes = [p if j in (0, 1, 2, 3, 4, 5, 9, 10, 11) else -1
             for j, p in enumerate(wp)]
    assert trie.insert(prompt, gp, [holes]) == 12
    hit, resume, pages = trie.match_groups(prompt)
    assert (hit, resume) == (12, 6)         # 12 wants 8..11, 6 has 2..5
    assert pages[0] == gp and pages[1] == wp[:6]
    assert trie.match(prompt) == (12, gp)
    # a later insert hands a node the page it lacks; then 12 can
    trie.insert(prompt, gp, [[-1] * 6 + wp[6:9]])
    assert trie.match_groups(prompt)[:2] == (12, 12)
    assert trie.match_groups(prompt[:3 * PS + 1])[:2] == (3, 3)   # from 0
    # eviction frees both groups' pages (the callers' own references stay)
    wa.free(wp), ga.free(gp)
    trie.clear()
    assert ga.num_allocated == wa.num_allocated == 0


# ---------------------------------- (b, c, e, i) through the engine's programs

def _cover(eng):
    """The allocators' exact cover, for BOTH groups: every page is free or
    referenced, as often as slots map it and trie nodes hold it; no window
    page is mapped behind a slot's window."""
    nodes, stack = [], [eng.prefix_cache._root]
    while stack:
        node = stack.pop()
        stack += node.children.values()
        nodes.append(node)
    for g, alloc in enumerate(eng.page_allocs):
        refs = {}
        for row in eng.cache.page_tables[g]:
            for page in row[row != PAGE_SENTINEL]:
                refs[int(page)] = refs.get(int(page), 0) + 1
        for node in nodes[1:]:
            page = node.page if g == 0 else node.more[g - 1]
            if page is not None:
                refs[page] = refs.get(page, 0) + 1
        assert refs == alloc._refs, (g, refs, alloc._refs)
        assert alloc.num_free + alloc.num_allocated == alloc.num_allocatable
    live = 0
    for slot, st in enumerate(eng._slots):
        if st.request is None:
            assert not eng.cache.slot_pages(slot, 1)
            continue
        first = max(0, int(eng._positions[slot]) - W) // PS
        row = eng.cache.page_tables[1][slot]
        assert (row[:first] == PAGE_SENTINEL).all(), (slot, first, row)
        live = max(live, int((row != PAGE_SENTINEL).sum()))
    return live


@pytest.mark.parametrize("impl", ["oracle", "pallas"])
def test_decode_slides_the_window_and_reuses_its_pages(model, impl):
    """Prefill, then 60 decode steps a slot = the reference's forward of 60
    more tokens: the window slides over fifteen pages, the window group
    (14 pages for 3 slots) is too small for any slot's context, and pages
    one slot freed are mapped by another meanwhile."""
    eng = _engine(model, impl, prefix_cache=False,
                  group_pages={"window": 3 * 6 + 1})
    handed = []
    alloc = eng.page_allocs[1].alloc
    eng.page_allocs[1].alloc = lambda n, owner=None: (
        lambda got: handed.extend((p, owner) for p in got or ()) or got)(
            alloc(n, owner=owner))
    prompts = [_ids(n, seed=n) for n in (5, 23, 41)]
    most = []
    outs = _generate(
        eng, prompts, 60, impl, each_step=lambda e: most.append(max(
            len(e.cache.slot_pages(s, 1)) for s in range(3))))
    for p, o in zip(prompts, outs):
        assert len(o) == 60 and _greedy_gap(model, p, o) < TOL
    assert max(most) <= W // PS + 1       # a window and the page it enters
    owners = {}
    for page, owner in handed:
        owners.setdefault(page, set()).add(owner)
    assert max(map(len, owners.values())) >= 2
    assert eng.window_pages_freed >= 3 * 10
    assert all(a.num_allocated == 0 for a in eng.page_allocs)


def test_sessions_on_one_document(model):
    """(c), (i): two sessions on one document, three turns each, a third
    that opens on the document's tail; greedy tokens are the reference's.
    The SECOND prompt on a document cannot resume at its end (the first
    left the window before ITS end alone): it is cut back to 0, runs
    again, shares the full layer's pages all the same, and leaves the
    window before the document's end to the trie; from then on every
    prompt resumes where its match ends."""
    eng = _engine(model)
    doc = _ids(44, seed=100)                    # 11 blocks
    text = {s: doc + _ids(5 + 2 * s, seed=s) for s in (1, 2)}
    cuts, recomputed = [], 0
    for turn in range(3):
        before = eng.resume_cut_tokens
        outs = _generate(eng, [text[1], text[2]], 9, each_step=_cover)
        cuts.append(eng.resume_cut_tokens - before)
        for s, out in zip((1, 2), outs):
            assert _greedy_gap(model, text[s], out) < TOL
            text[s] = text[s] + out + _ids(6, seed=10 * s + turn)
    assert cuts == [44, 0, 0]
    third = doc + _ids(9, seed=3)
    hit, resume, _ = eng.prefix_cache.match_groups(third)
    assert (hit, resume) == (11, 11)            # opens on the document's tail
    out = _generate(eng, [third], 9, each_step=_cover)[0]
    assert _greedy_gap(model, third, out) < TOL
    assert eng.resume_cut_tokens == 44
    # the full layer's pages of the document are ONE copy
    path = eng.prefix_cache._path(doc, 11)
    assert [n.more[0] is not None for n in path] == [False] * 7 + [True] * 4
    assert eng.page_allocs[0].num_allocated == eng.prefix_cache.num_nodes


def test_a_match_past_a_hole_is_cut_back_and_recomputed(model):
    """A cached path whose window pages are gone in the middle (the trie
    was never handed them, or gave them up): the match is cut back to the
    deepest depth that still has its window, the blocks behind it run
    again into pages of the request's own, and the logits are the same."""
    eng = _engine(model)
    first = _ids(24, seed=7)
    long = first + _ids(40, seed=8)             # 16 blocks
    _generate(eng, [first], 2)
    _generate(eng, [long], 2)
    path = eng.prefix_cache._path(long, 16)
    assert all(n.more[0] is not None for n in path[2:])
    eng.page_allocs[1].free([path[12].more[0]], owner="prefix-cache")
    path[12].more[0] = None                     # a hole at block 12
    probe = long[:60] + _ids(7, seed=9)         # matches 15 blocks
    assert eng.prefix_cache.match_groups(probe)[:2] == (15, 12)
    out = _generate(eng, [probe], 8, each_step=_cover)[0]
    assert _greedy_gap(model, probe, out) < TOL
    assert eng.resume_cut_tokens == 3 * PS


def test_pools_too_small_evict_and_keep_the_cover(model):
    """(e): admissions, slides, finishes and evictions under pools that
    cannot hold every finished prompt: the exact cover holds after every
    step, in both groups, and every request still decodes the reference's
    tokens."""
    eng = _engine(model, kv_pages=50, group_pages={"window": 24})
    rs = np.random.RandomState(5)
    docs = [_ids(28, seed=200 + d) for d in range(3)]
    prompts = [docs[i % 3] + _ids(3 + int(rs.randint(8)), seed=300 + i)
               for i in range(9)]
    before = eng.prefix_cache.num_nodes
    outs = _generate(eng, prompts, 12, each_step=_cover)
    for p, o in zip(prompts, outs):
        assert len(o) == 12 and _greedy_gap(model, p, o) < TOL
    assert eng.prefix_cache.num_nodes < before + sum(len(p) // PS
                                                     for p in prompts)
    eng.prefix_cache.clear()
    assert all(a.num_allocated == 0 for a in eng.page_allocs)


def test_spans_counters_and_step_statistics(model):
    """The decode span carries ``window_tokens_read`` / ``full_tokens_read``
    a layer and the window group's live pages; the slide has a span and a
    counter; an engine with windows refuses what it cannot serve."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import tracing

    assert model.step_stats[-2:] == ("window_tokens_read", "full_tokens_read")
    obs.enable()
    try:
        tracing.clear_spans()
        eng = _engine(model)
        _generate(eng, [_ids(30, seed=1), _ids(9, seed=2)], 8)
        spans = tracing.spans()
        steps = [s["attrs"] for s in spans if s["name"] == "serving/decode"
                 and "window_tokens_read" in s["attrs"]]
        last = steps[-1]
        assert last["window_tokens_read"][:3] == [W + 9 + 7] * 3
        assert last["window_tokens_read"][3] == 0
        assert last["full_tokens_read"] == [0, 0, 0, 30 + 7 + 9 + 7]
        assert 0 < last["window_pages_live"] <= last["window_pages"] == 39
        slides = [s["attrs"] for s in spans
                  if s["name"] == "serving/window/slide"]
        assert slides and sum(a["freed"] for a in slides) \
            == eng.window_pages_freed > 0
        snap = obs.snapshot()
        assert snap["counters"]["serving.window.pages_freed"] \
            == eng.window_pages_freed
        assert "serving.window.pages_live" in snap["gauges"]
        assert "serving.kv.pages.allocated{group=window}" in snap["gauges"]
    finally:
        obs.disable()
        obs.reset()
    with pytest.raises(ValueError, match="sliding-window"):
        Engine(model, EngineConfig(max_batch_size=2, max_seq_len=64,
                                   page_size=PS, speculative=2))


# ----------------------------------- (j) the older descriptions, unchanged

OLDER = {
    "hybrid": dict(layer_types=("gated_delta", "dense"), ffn="swiglu",
                   kv_layout="head", position="none", qk_norm=False),
    "share": dict(layer_types=("dense", "gated_delta"), position="none",
                  linear_gate="channel", attn_output_gate=True,
                  experts_held=(2, 4), shared_experts=1, qk_norm=False),
    "latent": dict(attention="latent", position="rope_yarn", qk_norm=False,
                   rope_scaling={"factor": 4,
                                 "original_max_position_embeddings": 32,
                                 "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                                 "mscale_all_dim": 1},
                   router="sigmoid_group_topk", n_group=2, topk_group=1,
                   first_dense_layers=1, experts_held=(2, 4),
                   shared_experts=1),
}

#: (arguments, sha256 of the lowered text, first 16 hex digits) of the
#: programs of three tiny engines of the kinds the benchmark already runs
#: (the fourth, the default sparse decoder, and the GPT are pinned in
#: tests/test_hybrid_serving.py), and (count, CRC-32) of their parameter
#: lists, recorded at the parent of the PR that gave the cache its page
#: groups (f50b1e0; jax 0.9.0, x64 on as in these tests). A PR that means
#: to change these programs records them again: print ``_lowered(...)``.
#: The six decode programs were recorded again at PR 43 (the ``host_tokens``
#: operand and its select: one argument more).
#: ``hybrid/decode/pallas`` was recorded again at PR 47 (the paged-decode
#: kernel's page walk; 534176be6752a5b1 before it), ``hybrid/extend/oracle``
#: at PR 48 (a head-major extend's attention is ``paged_extend_attend``, whose
#: oracle is ``extend_attend`` over the gathered view, where it was
#: ``decoder.attend``; 0650b351e53e3636 before it).
PARENTS = {
    "hybrid/params": (28, 4035424780),
    "share/params": (39, 2590006625),
    "latent/params": (34, 1586234722),
    "hybrid/prefill/oracle": (9, "b0e020062e23733c"),
    "hybrid/extend/oracle": (10, "0ef1fa47175c5fb2"),
    "hybrid/decode/oracle": (13, "68db039800b0e874"),
    "hybrid/decode/pallas": (13, "4a5157101a4727ff"),
    "share/prefill/oracle": (9, "8b4e2f98c16d6298"),
    "share/extend/oracle": (10, "e71bfd3db5f77058"),
    "share/decode/oracle": (13, "2d02876efc7303a0"),
    "share/decode/pallas": (13, "4824ba6592a7af51"),
    "latent/prefill/oracle": (5, "f73b6dbaab2847b5"),
    "latent/extend/oracle": (6, "0c206328ccf7ea76"),
    "latent/decode/oracle": (10, "6a66f53c8cc512bd"),
    "latent/decode/pallas": (10, "66b22e8cca63fa42"),
}


def _lowered(name):
    which, kind, impl = (name.split("/") + [None])[:3]
    cfg = DecoderConfig(**OLDER[which])
    if kind == "params":
        shapes = param_shapes(cfg)
        return len(shapes), zlib.crc32(repr(list(shapes.items())).encode())
    with use_paged_attention_impl(impl):
        eng = Engine(DecoderLM(cfg), EngineConfig(
            max_batch_size=2, max_seq_len=64, page_size=8, prefix_cache=True))
        assert len(eng.page_allocs) == 1 and not eng._windows
        fn, args = {"prefill": lambda: eng.prefill_program(16),
                    "extend": lambda: eng.extend_program(16),
                    "decode": eng.decode_program}[kind]()
        text = jax.jit(fn, donate_argnums=eng.donate_argnums_of(kind)) \
            .lower(*args).as_text()
    return len(args), hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", PARENTS)
def test_older_descriptions_build_and_lower_as_on_the_parent(name):
    """A model that declares no page group has one group, one allocator
    and one table, names the parameters it named, and its prefill, extend
    and decode programs take the operands they took and lower to the text
    they lowered to."""
    assert _lowered(name) == PARENTS[name]


def test_train_step_is_the_parents():
    """The GPT train step (flash attention behind its seam, which this PR
    leaves alone) lowers to the parent's text."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step
    from paddle_tpu.models.gpt import gpt_tiny

    paddle.seed(0)
    m = gpt_tiny(dropout=0.0, num_layers=2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    x = np.zeros((2, 16), np.int32)
    text = make_sharded_train_step(m, opt).lower_compiled(x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "0a335ee13ecde668"
