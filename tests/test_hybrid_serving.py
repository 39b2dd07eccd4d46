"""A decoder whose layers are of two kinds — gated delta-rule linear
attention in three layers of four, full attention in the fourth, the norm
after the mixer — under serving.Engine, against its plain reference
(benchmark/reference/olmo_hybrid.py, the token-by-token recurrence) at a
small size on the CPU: hidden 32, 4 layers, 4 heads; linear keys 8 wide,
values 16; pages of 8 tokens, chunks of 8.

Tolerances. Program and reference both compute in float32 here, in
different orders (chunks against single tokens, pages, the packed state), so
logits (|logit| up to about 3 with these weights) agree to about 1e-5 at
most positions and to 3e-4 at one in a hundred: where a mixer's output is
small, the norm that FOLLOWS it (this family's placement) divides by its
RMS and carries float32 rounding up with it. The limit 1e-3 leaves three
times of room over that and is far under what any fault moves a logit by:
a dropped decay, a stale state row, a snapshot restored at the wrong block
or a missing convolution tail each move logits by 1e-1 or more
(``test_the_comparison_can_fail``: 7). The kernels' own comparison with
the recurrence is held to 2e-5: there both sides are a few hundred float32
operations from the same inputs and nothing is normalised.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu import observability as obs
from paddle_tpu.kernels import gated_delta as gd
from paddle_tpu.kernels.tier import use_paged_attention_impl
from paddle_tpu.models.decoder import (ATTENTIONS, DecoderConfig, DecoderLM,
                                       gated_delta, initial_value,
                                       is_norm_scale, param_shapes)
from paddle_tpu.observability import tracing
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
from paddle_tpu.serving.prefix_cache import PrefixCache
from paddle_tpu.serving.scheduler import PageAllocator

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import olmo_hybrid as ref  # noqa: E402

TOL = 1e-3
V = 97
KINDS = ("gated_delta", "gated_delta", "gated_delta", "dense")
SIZES = dict(vocab_size=V, hidden_size=32, num_layers=4, num_heads=4,
             num_kv_heads=4, head_dim=8, max_context=128,
             norm_placement="post", position="none", qk_norm="full",
             layer_types=KINDS, kv_layout="head", linear_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=16, gdn_chunk=8,
             ffn="swiglu", intermediate_size=64, query_chunk=32)
RCFG = dict(layer_types=["linear_attention"] * 3 + ["full_attention"],
            num_heads=4, head_dim=8, linear_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16, conv_kernel=4, allow_neg_eigval=True,
            norm_eps=1e-6)
PS = 8


def _model(**over):
    """Seeded weights that make every part matter: matrices at ten times
    the initializer's 0.02, norm scales 1 + N(0, 0.1)."""
    m = DecoderLM(DecoderConfig(**{**SIZES, **over}))
    m.eval()
    key = jax.random.PRNGKey(1)
    for i, (n, p) in enumerate(m.named_parameters()):
        if is_norm_scale(n):
            p._set_value_raw(1 + 0.1 * jax.random.normal(
                jax.random.fold_in(key, i), p._value.shape, jnp.float32))
        elif p._value.ndim == 2 and not n.endswith("conv.weight"):
            p._set_value_raw(p._value * 10)
    return m


def _params(m):
    return {n: p._value for n, p in m.named_parameters()}


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, V, size=(n,)).tolist()


def _ref_rows(m, text, first):
    """Reference logits at positions first.. of ``text``."""
    lg = ref.forward(_params(m), jnp.asarray(text), RCFG, q_block=len(text))
    return np.asarray(lg[first:])


def _engine(m, **over):
    return Engine(m, EngineConfig(**{**dict(
        max_batch_size=3, max_seq_len=96, page_size=PS, prefix_cache=True,
        prefill_buckets=(8, 16, 32, 64, 96)), **over}))


def _serve_logits(eng, prompt, follow):
    """Admit ``prompt`` through the engine's own admission (its prefill /
    restore / extend programs, its pools), then feed ``follow`` one token a
    decode step through ``decode_step`` over the engine's pools: (the
    request, logits [1 + len(follow), V] at the prompt's last position and
    at each fed token's)."""
    rows = []
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
    for j, tok in enumerate(follow):
        tokens = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tokens[slot], pos[slot] = tok, len(prompt) + j
        eng._positions[slot] = pos[slot]
        eng._grow_pages()
        logits, new, _ = m.decode_step(
            jnp.asarray(tokens), eng.cache.layer_entries(
                eng.cache.pools, eng.cache.table_device()), jnp.asarray(pos))
        eng.cache.pools = eng.cache.pools_from_layers(
            [tuple(t._value for t in layer) for layer in new])
        rows.append(np.asarray(logits._value[slot]))
    return req, np.stack(rows)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture
def telemetry():
    obs.enable()
    obs.reset()
    tracing.clear_spans()
    yield obs
    obs.disable()
    obs.reset()


def _admits():
    return [e["attrs"] for e in tracing.spans() if e["name"] == "serving/admit"]


# ------------------------------------------ the two forms of the recurrence

def _recurrence(q, k, v, g, beta, S0):
    def token(S, t):
        qt, kt, vt, gt, bt = t
        S = S * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - jnp.sum(S * kt[:, None, :], axis=-1))
        S = S + u[:, :, None] * kt[:, None, :]
        return S, jnp.sum(S * qt[:, None, :], axis=-1)

    S, o = lax.scan(token, S0, (q, k, v, g, beta))
    return o, S


def _inputs(T, H, dk, dv, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    f32 = jnp.float32
    return (unit(jax.random.normal(ks[0], (T, H, dk), f32)) * f32(dk ** -0.5),
            unit(jax.random.normal(ks[1], (T, H, dk), f32)),
            jax.random.normal(ks[2], (T, H, dv), f32),
            -2.0 * jax.random.uniform(ks[3], (T, H), f32),
            2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H), f32)),
            jax.random.normal(ks[5], (H, dv, dk), f32))


#: where a state is asked for among 100 tokens in chunks of 16
CUTS = {"at_0": (0,), "at_a_chunks_edge": (32,), "inside_a_chunk": (37,),
        "inside_the_last_chunk": (98,), "at_T": (100,),
        "two_cuts": (37, 96), "two_in_one_chunk": (33, 47)}


class TestTwoFormsOfOneRecurrence:
    @pytest.mark.parametrize("T,chunk", [(1, 8), (7, 4), (64, 16), (100, 64),
                                         (130, 64)])
    @pytest.mark.parametrize("start", ["zero", "given"])
    def test_chunked_form_is_the_recurrence(self, T, chunk, start):
        """Also from a non-zero start state and where T is no multiple of
        the chunk; widths 24 / 48 (keys / values)."""
        *x, S0 = _inputs(T, 4, 24, 48, T)
        S0 = S0 if start == "given" else jnp.zeros_like(S0)
        o, S = gd.gdn_chunked(*x, S0, chunk)
        want_o, want_S = _recurrence(*x, S0)
        np.testing.assert_allclose(o, want_o, atol=2e-5)
        np.testing.assert_allclose(S, want_S, atol=2e-5)

    def test_padding_behind_the_last_token_moves_nothing(self):
        q, k, v, g, beta, S0 = _inputs(40, 4, 8, 16, 3)
        real = (jnp.arange(40) < 29)[:, None]
        _, S = gd.gdn_chunked(q, k, v, jnp.where(real, g, 0.0),
                              jnp.where(real, beta, 0.0), S0, 8)
        _, want = _recurrence(q[:29], k[:29], v[:29], g[:29], beta[:29], S0)
        np.testing.assert_allclose(S, want, atol=2e-5)

    @pytest.mark.parametrize("cuts", CUTS.values(), ids=CUTS)
    def test_state_at_a_cut_is_the_recurrence_stopped_there(self, cuts):
        """100 tokens in chunks of 16 (the last holds 4 real ones), the cuts
        run-time values: the state handed out at each is what the token-by-
        token recurrence holds after the tokens before it; the outputs and
        the end state are what they are without cuts."""
        *x, S0 = _inputs(100, 4, 24, 48, 7)
        o, S, at = jax.jit(lambda *a: gd.gdn_chunked(*a[:6], 16, a[6]))(
            *x, S0, jnp.asarray(cuts, jnp.int32))
        want_o, want_S = _recurrence(*x, S0)
        np.testing.assert_allclose(o, want_o, atol=2e-5)
        np.testing.assert_allclose(S, want_S, atol=2e-5)
        assert at.shape == (len(cuts),) + S0.shape
        for c, got in zip(cuts, at):
            _, want = _recurrence(*(a[:c] for a in x), S0)
            np.testing.assert_allclose(got, want, atol=2e-5)
        # the comparison can fail: a token further on the state is another
        _, near = _recurrence(*(a[:cuts[-1] - 1] for a in x), S0)
        assert np.abs(at[-1] - near).max() > 1e-2 or cuts[-1] == 0

    def test_a_cut_in_the_padding_is_the_end(self):
        """Padding behind ``lengths`` (b = 0, g = 0) moves nothing: a cut
        inside it, or past it, hands out the state after the last real
        token; one before it the state there."""
        q, k, v, g, beta, S0 = _inputs(40, 4, 8, 16, 3)
        real = (jnp.arange(40) < 29)[:, None]
        _, S, at = gd.gdn_chunked(q, k, v, jnp.where(real, g, 0.0),
                                  jnp.where(real, beta, 0.0), S0, 8,
                                  jnp.asarray([21, 29, 35, 40], jnp.int32))
        for c, got in zip((21, 29, 29, 29), at):
            _, want = _recurrence(q[:c], k[:c], v[:c], g[:c], beta[:c], S0)
            np.testing.assert_allclose(got, want, atol=2e-5)
        np.testing.assert_allclose(S, at[-1], atol=2e-5)

    @pytest.mark.parametrize("cuts", [(8, 24), (0, 29), (13, 35)])
    def test_layer_hands_out_state_and_tail_at_the_cuts(self, model, cuts):
        """``decoder.gated_delta`` over 40 tokens of which 29 are real: at
        each cut the packed state and the convolution's tail are those the
        same layer ends with over the tokens before the cut alone (a cut in
        the padding: over the real ones), and the tail is the last ``Kc -
        1`` inputs of the convolution before it, zeros before the first."""
        p, pre = _params(model), "layers.1.attn"
        h = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 32), jnp.float32)
        layer = lambda h, **kw: gated_delta(
            model.cfg, p, pre, h, jnp.zeros((1,), jnp.int32), **kw)[1]
        S, tails = layer(h, lengths=jnp.asarray([29]),
                         cuts=jnp.asarray([cuts]))
        assert S.shape[0] == tails.shape[0] == len(cuts) + 1
        x = jnp.concatenate([h[0] @ p[pre + w] for w in (".wq", ".wk", ".wv")],
                            axis=-1)
        x = jnp.concatenate([jnp.zeros((3, x.shape[1])), x])
        for i, c in enumerate(cuts + (29,)):
            c = min(c, 29)
            np.testing.assert_allclose(tails[i], x[c:c + 3], atol=1e-6)
            if c:
                want_S, want_tail = layer(h[:, :c])
                np.testing.assert_allclose(S[i], want_S[0], atol=2e-5)
                np.testing.assert_allclose(tails[i], want_tail[0], atol=1e-6)
            else:
                assert not np.asarray(S[i]).any()

    @pytest.mark.parametrize("impl", ["oracle", "pallas"])
    @pytest.mark.parametrize("H,dk,dv", [(4, 8, 16), (2, 96, 192), (3, 8, 16)])
    def test_recurrent_step_in_place_on_the_packed_state(self, impl, H, dk, dv):
        """One token a slot on rows [0, B) of the packed buffer (the Pallas
        kernel interpreted here): the recurrence's step, the rows behind
        (snapshots) untouched. (3, 8, 16): heads that do not pack."""
        B = 3
        q, k, v, g, beta, S0 = _inputs(B, H, dk, dv, H)
        S0 = jnp.stack([S0 * (i + 1) for i in range(B)])
        state = jnp.concatenate([gd.pack_state(S0), jnp.full(
            (2,) + gd.packed_shape(H, dk, dv), 7.0)])
        assert gd.packed_shape(2, 96, 192) == (1, 96, 384)   # whole lane rows
        np.testing.assert_array_equal(
            gd.unpack_state(gd.pack_state(S0), H), S0)
        with use_paged_attention_impl(impl):
            o, new = gd.gdn_step(q, k, v, g, beta, state)
        for b in range(B):
            want_o, want_S = _recurrence(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                         g[b:b + 1], beta[b:b + 1], S0[b])
            np.testing.assert_allclose(o[b], want_o[0], atol=2e-5)
            np.testing.assert_allclose(gd.unpack_state(new[b], H), want_S,
                                       atol=2e-5)
        np.testing.assert_array_equal(new[B:], state[B:])


# -------------------------------------------------- model vs reference

class TestAgainstReference:
    def test_full_forward(self, model):
        text = _ids(90)
        got = model(jnp.asarray(text)[None])._value[0]
        np.testing.assert_allclose(got, _ref_rows(model, text, 0), atol=TOL)

    def test_the_comparison_can_fail(self, model):
        """A model that differs in one small part of the layer (no negative
        eigenvalues: b = sigmoid, not 2 sigmoid) is far outside TOL."""
        other = _model(linear_allow_neg_eigval=False)
        text = _ids(90)
        got = other(jnp.asarray(text)[None])._value[0]
        assert np.abs(got - _ref_rows(model, text, 0)).max() > 50 * TOL

    @pytest.mark.parametrize("impl", ["oracle", "pallas"])
    def test_prefill_then_decode_through_the_engines_pools(self, model, impl):
        """A 45-token prompt admitted by the engine (prefill in two pieces,
        the snapshot between), then 20 decode steps over the engine's
        pools: every position's logits are the reference's full forward."""
        text = _ids(65, seed=2)
        with use_paged_attention_impl(impl):
            eng = _engine(model)
            _, got = _serve_logits(eng, text[:45], text[45:])
        np.testing.assert_allclose(got, _ref_rows(model, text, 44), atol=TOL)

    def test_restore_and_extend_is_the_cold_prompt(self, model):
        """A prompt served after a prefix hit (snapshot restored, the rest
        extended) gives the logits of the same prompt served cold, at the
        prompt's end and through 12 decode steps; both are the
        reference's."""
        shared, tail = _ids(40, seed=5), _ids(21, seed=8)
        text = shared + _ids(9, seed=7) + tail
        warm = _engine(model)
        warm.generate([shared + _ids(17, seed=6), shared + _ids(5, seed=9)],
                      SamplingParams(max_new_tokens=3))
        req, hit = _serve_logits(warm, text[:49], text[49:])
        assert req.prefix_hit_blocks == 5
        _, cold = _serve_logits(_engine(model), text[:49], text[49:])
        np.testing.assert_allclose(hit, cold, atol=TOL)
        np.testing.assert_allclose(hit, _ref_rows(model, text, 48), atol=TOL)

    def test_engine_emits_the_references_greedy_tokens(self, model):
        """Through ``generate``: four turns of a session over a shared
        prefix, each turn the whole history plus new tokens."""
        eng = _engine(model)
        hist = _ids(24, seed=11)
        for turn in range(4):
            prompt = hist + _ids(5 + turn, seed=20 + turn)
            out = eng.generate([prompt], SamplingParams(max_new_tokens=7))[0]
            rows = _ref_rows(model, prompt + out[:-1], len(prompt) - 1)
            assert rows.argmax(-1).tolist() == out
            hist = prompt + out


# ------------------------------------------------ snapshots in the trie

class TestSnapshots:
    def test_third_prompt_restores_at_the_branch(self, model, telemetry):
        """The first prompt over a shared prefix snapshots its end; the
        second leaves the cached path at the prefix's last whole block and
        snapshots there (and at its end); the third restores at the
        branch: nothing is computed twice, whatever its length. A later
        turn restores at the previous prompt's end."""
        eng = _engine(model)
        shared = _ids(20, seed=1)                     # 2 whole blocks + 4
        p = [shared + _ids(n, seed=n) for n in (9, 13, 30)]
        outs = [eng.generate([x], SamplingParams(max_new_tokens=4))[0]
                for x in p]
        a = _admits()
        assert [(x["hit_blocks"], x["snapshot_blocks"],
                 x["recomputed_tokens"]) for x in a] == [
            (0, 0, 0), (2, 0, 2 * PS), (2, 2, 0)]
        taken = [(e["attrs"]["blocks"], e["attrs"]["reason"])
                 for e in tracing.spans()
                 if e["name"].startswith("serving/snapshot{")]
        assert taken == [(3, "prompt_end"), (2, "branch"), (4, "prompt_end"),
                         (6, "prompt_end")]
        restores = [e["attrs"]["blocks"] for e in tracing.spans()
                    if e["name"] == "serving/admit/restore"]
        assert restores == [2]
        turn2 = p[0] + outs[0] + _ids(6, seed=3)
        eng.generate([turn2], SamplingParams(max_new_tokens=2))
        assert (_admits()[-1]["hit_blocks"],
                _admits()[-1]["snapshot_blocks"]) == (3, 3)
        # the later prompt end superseded the earlier one on its unbranched
        # chain (one snapshot taken, one given up); the branch, where three
        # prompts part, keeps its own
        assert eng.snapshot_alloc.num_allocated == 4
        assert eng.prefix_cache.snapshots_dropped == 0
        assert eng.prefix_cache.deepest_snapshot(p[0] + [0], 3)[0] == 2
        assert eng.prefix_cache.deepest_snapshot(turn2 + [0], 4)[0] == 4

    def test_hit_deeper_than_any_snapshot_falls_back(self, model, telemetry):
        """Pages match three blocks, the deepest snapshot on that path lies
        at two: the engine restores there, splices two blocks only and runs
        the third again into a page of the request's own. Same tokens as
        served cold."""
        eng = _engine(model)
        shared = _ids(20, seed=1)
        second = shared + _ids(13, seed=13)
        eng.generate([shared + _ids(9, seed=9), second],
                     SamplingParams(max_new_tokens=2))
        deep = second[:30] + _ids(8, seed=4)
        req = eng.add_request(deep, SamplingParams(max_new_tokens=6))
        while eng.has_unfinished:
            eng.step()
        adm = _admits()[-1]
        assert (adm["hit_blocks"], adm["snapshot_blocks"],
                adm["recomputed_tokens"]) == (3, 2, PS)
        assert req.prefix_hit_blocks == 3
        cold = _engine(model).generate([deep], SamplingParams(max_new_tokens=6))
        assert req.output_ids == cold[0]

    def test_refcounts_return_to_zero(self, model):
        eng = _engine(model)
        shared = _ids(20, seed=1)
        eng.generate([shared + _ids(n, seed=n) for n in (9, 13, 7)],
                     SamplingParams(max_new_tokens=3))
        snaps = eng.snapshot_alloc
        assert snaps.num_allocated == 4     # three ends and the branch
        assert all(snaps.refcount(s) == 1 for s in range(1, 5))
        eng.prefix_cache.clear()
        assert snaps.num_allocated == 0 and eng.page_alloc.num_allocated == 0

    def test_lru_eviction_frees_snapshot_and_page_together(self):
        pages, snaps = PageAllocator(8), PageAllocator(4)
        trie = PrefixCache(4, pages, snaps)
        old, new = list(range(8)), list(range(100, 108))
        for prompt in (old, new):
            got = pages.alloc(2, owner="r")
            trie.insert(prompt, got)
            pages.free(got, owner="r")
            sid = trie.reserve_snapshots(1, "r")[0]
            assert trie.attach_snapshot(prompt, 2, sid, "r")
        trie.match(new + [0])                     # ``old`` is the LRU chain
        assert (pages.num_allocated, snaps.num_allocated) == (4, 2)
        assert trie.evict_lru(pages.num_free + 1) == 1
        # the leaf of ``old`` went: its page and its snapshot with it
        assert (pages.num_allocated, snaps.num_allocated) == (3, 1)
        assert trie.deepest_snapshot(old + [0], 2) == (0, None)
        assert trie.deepest_snapshot(new + [0], 2)[0] == 2

    def test_short_snapshot_pool_takes_from_the_lru_node(self):
        pages, snaps = PageAllocator(16), PageAllocator(3)   # two snapshots
        trie = PrefixCache(4, pages, snaps)
        prompts = [list(range(b, b + 4)) for b in (0, 10, 20)]
        for prompt in prompts:
            trie.insert(prompt, pages.alloc(1, owner="r"))
            sid = trie.reserve_snapshots(1, "r")
            assert trie.attach_snapshot(prompt, 1, sid[0], "r")
        assert trie.snapshots_dropped == 1 and trie.num_nodes == 3
        assert [trie.deepest_snapshot(p + [0], 1)[0] for p in prompts] == [
            0, 1, 1]                       # the node stays, its snapshot went
        # a snapshot a third party still holds is not freed by leaving its
        # node: the pool stays short and the caller is told so
        held = trie.deepest_snapshot(prompts[1] + [0], 1)[1]
        snaps.retain([held], owner="admission")
        trie.match(prompts[2] + [0])
        assert trie.reserve_snapshots(2, "r") is None

    def test_admission_backpressures_when_snapshots_run_out(self, model,
                                                            telemetry):
        """One snapshot in the pool, and the head request resumes from it:
        it cannot both keep it and take the one it owes, so it stays queued
        (``blocked``) and nothing it was given is kept; the snapshot it held
        on to is free on the next try, which then serves it cold. Same
        tokens as an engine with room."""
        eng = _engine(model, state_snapshots=1)
        first = _ids(29, seed=1)
        out = eng.generate([first], SamplingParams(max_new_tokens=3))[0]
        turn2 = first + out + _ids(6, seed=2)
        req = eng.add_request(turn2, SamplingParams(max_new_tokens=4))
        free = eng.page_alloc.num_free
        eng.step()
        assert req.slot is None and _admits()[-1].get("blocked") == 1
        assert eng.page_alloc.num_free == free
        while eng.has_unfinished:
            eng.step()
        assert _admits()[-1]["snapshot_blocks"] == 0
        roomy = _engine(model).generate(
            [turn2], SamplingParams(max_new_tokens=4))[0]
        assert req.output_ids == roomy


# ------------------------------------------- one program an admission

def _admit_watched(eng, prompt):
    """Admit ``prompt`` (telemetry on): (the request, its ``serving/admit``
    span, the programs looked up to be run while it was admitted)."""
    keys, held = [], eng._held
    eng._held = lambda *key: keys.append(key) or held(*key)
    req = eng.add_request(prompt, SamplingParams(max_new_tokens=4))
    assert eng._admit() == 1
    eng._held = held
    adm = [e for e in tracing.spans() if e["name"] == "serving/admit"][-1]
    return req, adm, keys


def _snapshots_of(adm):
    """Blocks of the snapshots admission span ``adm`` took."""
    return [e["attrs"]["blocks"] for e in tracing.spans()
            if e["name"].startswith("serving/snapshot{")
            and e["attrs"]["request_id"] == adm["attrs"]["request_id"]]


def _state_rows(eng, row):
    """Row ``row`` of every state buffer, over the layers that keep one."""
    return [np.asarray(buf[row]) for pool in
            eng.cache.pools[len(eng.cache.pool_specs):] for buf in pool]


def _scenario(eng, name):
    """Serve what comes before, then return the prompt whose admission is
    under test and what its span has to say: (prompt, kind of program,
    hit_blocks, snapshot_blocks, blocks snapshotted)."""
    shared = _ids(20, seed=1)                       # 2 whole blocks + 4
    serve = lambda ps: [eng.generate([x], SamplingParams(max_new_tokens=4))[0]
                        for x in ps]
    if name == "cold":
        return shared + _ids(9, seed=9), "prefill", 0, 0, [3]
    if name == "cold_two_cuts":         # leaves the cached path at a block
        serve([shared + _ids(9, seed=9)])           # no snapshot lies at
        return shared + _ids(13, seed=13), "prefill", 2, 0, [2, 4]
    if name == "at_a_branch":
        serve([shared + _ids(9, seed=9), shared + _ids(13, seed=13)])
        return shared + _ids(30, seed=30), "extend", 2, 2, [6]
    if name == "behind_a_branch":       # pages match deeper than a snapshot
        second = shared + _ids(13, seed=13)
        serve([shared + _ids(9, seed=9), second])
        return second[:30] + _ids(13, seed=4), "extend", 3, 2, [3, 5]
    if name == "at_a_prompts_end":
        first = shared + _ids(9, seed=9)
        out = serve([first])[0]
        return first + out + _ids(6, seed=3), "extend", 3, 3, [4]
    assert name == "at_a_pages_edge"    # a prompt of three whole pages
    first = _ids(3 * PS, seed=2)
    out = serve([first])[0]
    return first + out + _ids(7, seed=3), "extend", 3, 3, [4]


SCENARIOS = ["cold", "cold_two_cuts", "at_a_branch", "behind_a_branch",
             "at_a_prompts_end", "at_a_pages_edge"]


def _programs(adm) -> int:
    """Compiled executables launched under one ``serving/admit`` span (the
    sampler's eager stretch is none)."""
    return sum(e["attrs"].get("launches", 1) for e in tracing.spans()
               if e["parent"] == adm["id"] and "launch" in e["attrs"]
               and not e["attrs"].get("eager"))


class TestOneProgramAnAdmission:
    @pytest.mark.parametrize("name", SCENARIOS)
    def test_every_admission_is_one_program(self, model, telemetry, name):
        """Cold, resumed at a branch, behind one, at a prompt's end and at
        one that lies on a page's edge: the admission looks up ONE program
        (the one compiled ``launch`` under its span), on one
        ``serving/admit/extend`` or ``/prefill`` span over all the tokens
        behind the snapshot; the
        snapshots it owes are taken, the tokens are a cold engine's."""
        eng = _engine(model)
        prompt, kind, hit, resumed, snaps = _scenario(eng, name)
        before = [_programs(e) for e in tracing.spans()
                  if e["name"] == "serving/admit"]
        assert before == [1] * len(before)
        req, adm, keys = _admit_watched(eng, prompt)
        n = len(prompt)
        assert keys == [(kind, eng._bucket(n - resumed * PS))]
        a = adm["attrs"]
        assert (_programs(adm), a["hit_blocks"], a["snapshot_blocks"]) == (
            1, hit, resumed)
        runs = [e for e in tracing.spans() if e["parent"] == adm["id"]
                and e["name"] in ("serving/admit/prefill",
                                  "serving/admit/extend")]
        assert [(e["name"], e["attrs"]["tokens"]) for e in runs] == [
            ("serving/admit/" + kind, n - resumed * PS)]
        assert _snapshots_of(adm) == snaps
        while eng.has_unfinished:
            eng.step()
        cold = _engine(model, prefix_cache=False).generate(
            [prompt], SamplingParams(max_new_tokens=4))[0]
        assert req.output_ids == cold

    @pytest.mark.parametrize("name", SCENARIOS[1:4])
    def test_snapshot_rows_are_those_of_a_program_that_ends_there(
            self, model, telemetry, name):
        """What the one program wrote to each snapshot's row from inside
        its scan (state and tail, every layer) is what lies in the slot's
        row of an engine whose program ENDED at that block (the same prompt
        cut off there, served cold): the rows the path in pieces copied."""
        eng = _engine(model)
        prompt, _, _, _, snaps = _scenario(eng, name)
        _admit_watched(eng, prompt)
        ends = {}
        for block in snaps:
            at, snap = eng.prefix_cache.deepest_snapshot(prompt + [0], block)
            assert at == block
            cut = _engine(model, prefix_cache=False)
            req = cut.add_request(prompt[:block * PS],
                                  SamplingParams(max_new_tokens=4))
            assert cut._admit() == 1
            got = _state_rows(eng, eng.cache.snapshot_row(snap))
            ends[block] = _state_rows(cut, req.slot)
            assert len(got) == 6                    # S and tail, 3 layers
            for a, b in zip(got, ends[block]):
                np.testing.assert_allclose(a, b, atol=1e-4)
        # the comparison can fail: another block's rows are far from these
        if len(snaps) == 2:
            assert max(np.abs(a - b).max() for a, b in
                       zip(*ends.values())) > 1e-2


# ------------------------------------- a model without state is left alone

#: (arguments, sha256 of the lowered text, first 16 hex digits) of the
#: programs of two tiny engines WITHOUT recurrent state, recorded at the
#: parent of the PR that gave the programs of a model WITH state their
#: state operand (f556f11; jax 0.9.0, x64 on as in these tests). A PR that
#: means to change these programs records them again: print
#: ``_lowered(...)`` of each below. The four decode programs were recorded
#: again at PR 39 (the sampler's conditional in place of its sort) and at
#: PR 43 (the ``host_tokens`` operand and its select: one argument more).
#: ``gpt/decode/pallas`` was recorded again at PR 47 (the paged-decode
#: kernel's page walk; 694c5b446e7a6e60 before it).
WITHOUT_STATE = {
    "gpt/prefill/oracle": (6, "3baaa1868e5f7615"),
    "gpt/extend/oracle": (7, "24143a35f6d0155c"),
    "gpt/decode/oracle": (11, "3298d34fd1edf2bd"),
    "gpt/decode/pallas": (11, "efa21588867f80f4"),
    "decoder/prefill/oracle": (7, "0024ab6d3c6af9d9"),
    "decoder/extend/oracle": (8, "539dd0b7ee3df441"),
    "decoder/decode/oracle": (12, "1524d1bb051655c3"),
    "decoder/decode/pallas": (12, "88177ec91bd8cd68"),
}


def _lowered(name):
    """(arguments, hash of the lowered text) of program ``model/kind/tier``
    of a tiny engine: ``gpt_tiny`` or the default ``DecoderLM`` (sparse
    attention, routed experts; three paged pools, no state)."""
    import hashlib

    from paddle_tpu.models.gpt import gpt_tiny

    which, kind, impl = name.split("/")
    with use_paged_attention_impl(impl):
        model = gpt_tiny(dropout=0.0, num_layers=2) if which == "gpt" \
            else DecoderLM(DecoderConfig())
        eng = Engine(model, EngineConfig(max_batch_size=2, max_seq_len=64,
                                         page_size=8, prefix_cache=True))
        assert not eng._stateful and eng._state_arg(1) == ()
        fn, args = {"prefill": lambda: eng.prefill_program(16),
                    "extend": lambda: eng.extend_program(16),
                    "decode": eng.decode_program}[kind]()
        text = jax.jit(fn, donate_argnums=eng.donate_argnums_of(kind)) \
            .lower(*args).as_text()
    return len(args), hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", WITHOUT_STATE)
def test_programs_of_a_model_without_state_are_the_parents(name):
    """The prefill, extend and decode programs of a model that declares no
    state take the operands they took and lower to the text they lowered
    to: the state operand, the cuts and the rows exist only where
    ``state_pools()`` declares something."""
    assert _lowered(name) == WITHOUT_STATE[name]


# -------------------------------------- the description and the engine

class TestDescription:
    def test_pools_are_declared_by_layer_kind(self, model):
        assert model.cache_pools() == [("k", 4, 8, (3,)), ("v", 4, 8, (3,))]
        assert [(n, s, l) for n, s, _, l in model.state_pools()] == [
            ("gdn_state", (4, 8, 16), (0, 1, 2)),
            ("gdn_conv", (3, 128), (0, 1, 2))]
        eng = _engine(model, kv_pages=9, state_snapshots=5)
        assert [len(p) for p in eng.cache.pools] == [1, 1, 3, 3]
        assert eng.cache.pools[0][0].shape == (9, 4, PS, 8)
        assert eng.cache.pools[2][0].shape == (3 + 5, 4, 8, 16)
        assert eng.cache.pools[2][0].dtype == jnp.float32
        assert eng.donate_argnums == (1, 2, 3, 4)
        entries = eng.cache.layer_entries(eng.cache.pools, "table", "row")
        assert [len(e) for e in entries] == [3, 3, 3, 3]
        assert entries[0][2] == "row" and entries[3][2] == "table"

    def test_speculative_with_recurrent_state_is_refused(self, model):
        with pytest.raises(ValueError, match="recurrent state.*gdn_state"):
            Engine(model, EngineConfig(max_seq_len=64, speculative=2))

    @pytest.mark.parametrize("bad,match", [
        (dict(layer_types=KINDS[:3]), "names 3 layers, num_layers is 4"),
        (dict(layer_types=KINDS[:3] + ("banded",)), "banded"),
        (dict(position="alibi"), "position"),
        (dict(norm_placement="sandwich"), "norm_placement"),
        (dict(kv_layout="ragged"), "kv_layout"),
        (dict(qk_norm="group"), "qk_norm"),
    ])
    def test_description_rejects_what_no_table_knows(self, bad, match):
        with pytest.raises(ValueError, match=match):
            DecoderConfig(**{**SIZES, **bad})

    def test_new_leaves_are_neither_norm_scales_nor_normal(self):
        shapes = param_shapes(DecoderConfig(**SIZES))
        pre = "layers.0.attn."
        assert shapes[pre + "conv.weight"] == (4 * (8 + 8 + 16), 4)
        assert shapes[pre + "A_log"] == shapes[pre + "dt_bias"] == (4,)
        assert pre + "A_log" not in {n for n in shapes if is_norm_scale(n)}
        assert is_norm_scale(pre + "o_norm.weight")
        assert "layers.3.attn.conv.weight" not in shapes
        assert shapes["layers.3.attn.q_norm.weight"] == (32,)   # whole width
        key = jax.random.PRNGKey(0)
        a = initial_value(pre + "A_log", (4096,), key, 0.02)
        assert float(jnp.exp(a).max()) > 15 and float(jnp.exp(a).min()) > 0
        dt = jax.nn.softplus(initial_value(pre + "dt_bias", (4096,), key, 0.02))
        assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 0.1 + 1e-6
        c = initial_value(pre + "conv.weight", (64, 4), key, 0.02)
        assert 0.4 < float(jnp.abs(c).max()) <= 0.5
        assert set(ATTENTIONS) == {"dense", "sliding", "indexed_sparse",
                                   "gated_delta", "latent", "mamba2", "mamba1",
                                   "gmu", "cross", "none"}
