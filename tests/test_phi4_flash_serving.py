"""A decoder-hybrid-decoder of the Phi-4-mini-flash kind (SambaY,
arXiv:2507.06607): Mamba-1 layers on slot state beside sliding-window
differential attention on a page group of its own, ONE full differential
layer whose K/V pool the cross layers of the second half read, gated memory
units over the last Mamba-1 layer's scan output, and an admission whose
second half runs on the last real token alone -- under serving.Engine,
against its plain reference (benchmark/reference/phi4_mini_flash.py: every
layer on every token, the recurrence one state update a token, dense masked
softmax) at a small size on the CPU: hidden 32; 8 / 4 heads of 8 (4 query
pairs on 2 K/V pairs); Mamba-1 inner 64, state 4, step rank 4; window 12;
pages of 4 tokens.

Tolerances. Program and reference both compute in float32 here, in
different orders (pages, widened queries over pair-head pools, the state's
lanes), so logits (|logit| up to about 1 with these weights) agree to about
1e-6; the limit 1e-4 leaves room and is far under what any fault moves a
logit by: the same program with lambda 0, a cross layer on K/V of its own
input or a state rounded to bfloat16 reads 1e-3 or more
(``test_the_comparison_can_fail``). ONE module-scoped engine serves the
engine cases.
"""

import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.kernels import mamba1 as m1
from paddle_tpu.kernels.tier import use_paged_attention_impl
from paddle_tpu.models import decoder as dec
from paddle_tpu.models.decoder import (DecoderConfig, DecoderLM,
                                       is_norm_scale, param_shapes)
from paddle_tpu.observability import metrics, tracing
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import phi4_mini_flash as ref  # noqa: E402

TOL = 1e-4
V = 96
PS = 4
WINDOW = 12
#: this file's names for the six kinds of layer -> the program's
KIND = {"mamba": "mamba1", "sliding": "sliding", "full": "dense",
        "gmu": "gmu", "cross": "cross"}
#: small patterns: [(kind, the earlier layer it reads)]
PATTERNS = {
    "mamba": [("mamba", None)],
    "sliding": [("sliding", None)],
    "full": [("full", None)],
    "memory": [("mamba", None), ("gmu", 0)],
    "cross": [("full", None), ("cross", 0)],
    "whole": [("mamba", None), ("sliding", None), ("mamba", None),
              ("sliding", None), ("mamba", None), ("full", None),
              ("gmu", 4), ("cross", 5)],
}


def _sizes(pattern):
    return dict(
        vocab_size=V, hidden_size=32, num_layers=len(pattern), num_heads=8,
        num_kv_heads=4, head_dim=8, max_context=128, norm="layer",
        norm_eps=1e-5, position="none", qk_norm=False, kv_layout="head",
        layer_types=tuple(KIND[k] for k, _ in pattern),
        layer_sources=tuple(s for _, s in pattern), sliding_window=WINDOW,
        differential=True, attn_bias=True, ssm_state=4, ssm1_dt_rank=4,
        ffn="swiglu", intermediate_size=48, tie_word_embeddings=True,
        query_chunk=32)


def _rcfg(pattern):
    memory = [s for k, s in pattern if k == "gmu"]
    return dict(layer_types=[k for k, _ in pattern], num_heads=8,
                num_kv_heads=4, head_dim=8, inner=64, state=4, dt_rank=4,
                conv_kernel=4, norm_eps=1e-5, sliding_window=WINDOW,
                memory_layer=memory[0] if memory else None)


def _model(name="whole", **over):
    """Seeded weights that make every part matter: matrices at ten times the
    initializer's 0.02, norm scales 1 + N(0, 0.1), biases and lambda vectors
    well off 0, the skip ``D`` off 1."""
    m = DecoderLM(DecoderConfig(**{**_sizes(PATTERNS[name]), **over}))
    m.eval()
    key = jax.random.PRNGKey(1)
    for n, p in m.named_parameters():
        k = jax.random.fold_in(key, zlib.crc32(n.encode()) % (2**31 - 1))
        draw = lambda s: s * jax.random.normal(k, p._value.shape, jnp.float32)
        if is_norm_scale(n):
            p._set_value_raw(1 + draw(0.1))
        elif n.endswith((".bias", ".D")) and "dt_bias" not in n:
            p._set_value_raw(p._value + draw(0.3))
        elif ".lambda_" in n:
            p._set_value_raw(draw(0.3))
        elif n.endswith((".bq", ".bk", ".bv", ".bo")):
            p._set_value_raw(draw(0.2))
        elif p._value.ndim >= 2 and not n.endswith(("conv.weight", "A_log")):
            p._set_value_raw(p._value * 10)
    return m


def _params(m):
    return {n: p._value for n, p in m.named_parameters()}


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(1, V, size=(n,)).tolist()


def _ref_logits(m, text, name="whole"):
    return np.asarray(ref.forward(_params(m), jnp.asarray(text),
                                  _rcfg(PATTERNS[name]), q_block=len(text)))


def _forward(m, text):
    return np.asarray(jax.jit(lambda ids: m(ids)._value)(
        jnp.asarray(text)[None])[0])


def _engine(m, **over):
    return Engine(m, EngineConfig(**{**dict(
        max_batch_size=3, max_seq_len=96, page_size=PS, prefix_cache=True,
        state_snapshots=8, group_pages={"window": 48},
        prefill_buckets=(8, 16, 32, 64, 96)), **over}))


def _serve_logits(eng, prompt, follow):
    """Admit ``prompt`` through the engine's own admission (its prefill /
    restore / splice / extend programs, its pools), then feed ``follow`` one
    token a decode step through ``decode_step`` over the engine's pools:
    (the request, logits [1 + len(follow), V] at the prompt's last position
    and at each fed token's). (``tests/test_mamba_serving.py``'s, with a
    table a page group.)"""
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
    rows = rows[-1:]
    B, slot, m = eng.config.max_batch_size, req.slot, eng.model

    @jax.jit
    def step(tokens, pools, tables, pos):
        logits, new, _ = m.decode_step(
            tokens, eng.cache.layer_entries(pools, tables), pos)
        return logits._value, [tuple(t._value for t in layer)
                               for layer in new]

    for j, tok in enumerate(follow):
        tokens = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tokens[slot], pos[slot] = tok, len(prompt) + j
        eng._positions[slot] = pos[slot]
        eng._grow_pages()
        logits, new = step(jnp.asarray(tokens), eng.cache.pools,
                           eng.cache.tables_device(), jnp.asarray(pos))
        eng.cache.pools = eng.cache.pools_from_layers(new)
        rows.append(np.asarray(logits[slot]))
    return req, np.stack(rows)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def served(model):
    """ONE engine shared by the engine cases. Three requests on one system
    prompt (six whole pages), one after the other: the first prefills cold,
    the second starts over and leaves a snapshot and the window's pages at
    the branch, the third RESUMES there: a restored snapshot AND a spliced
    window in one admission. Then a follow-up turn of the third, which
    resumes at its prompt's end. Logits of every admission and of four
    decode steps behind it, and what the spans said."""
    obs.enable()
    obs.reset()
    tracing.clear_spans()
    eng = _engine(model)
    shared = _ids(24, seed=1)
    prompts = [shared + _ids(n, seed=n) for n in (9, 14, 7)]
    runs = []
    for p in prompts:
        follow = _ids(4, seed=len(p))
        req, rows = _serve_logits(eng, p, follow)
        runs.append((p + follow, len(p), rows, req))
        eng._finish(req, "length")  # its slot and its own pages go back
    turn = runs[2][0] + _ids(6, seed=77)
    follow = _ids(4, seed=78)
    req, rows = _serve_logits(eng, turn, follow)
    runs.append((turn + follow, len(turn), rows, req))
    eng._finish(req, "length")
    gauges = metrics.snapshot()["gauges"]
    spans = list(tracing.spans())
    obs.disable()
    obs.reset()
    tracing.clear_spans()
    return eng, runs, spans, gauges


# ------------------------------------------------ (a) layers and the model

@pytest.mark.parametrize("name", list(PATTERNS))
def test_mixers_against_the_reference(name):
    """Each kind of layer (a Mamba-1 layer, a sliding and a full
    differential layer, a gated memory unit over a Mamba-1 layer's memory, a
    cross layer over a full layer's K/V) and the whole pattern: logits of a
    full causal pass, every layer on every token on both sides."""
    m = _model(name)
    text = _ids(29)
    assert np.abs(_forward(m, text) - _ref_logits(m, text, name)).max() < TOL


def _plant_lambda_zero(monkeypatch):
    combine = dec.diff_combine
    monkeypatch.setattr(dec, "diff_combine",
                        lambda o, lam, *a: combine(o, lam * 0, *a))


def _plant_cross_reads_its_own(monkeypatch):
    """A cross layer as a standard decoder layer would be: K and V of its
    OWN input (through the source layer's projections)."""
    real = dec.differential_attention

    def planted(cfg, p, pre, h, start, cache, kind, layer, carry):
        if kind != "cross":
            return real(cfg, p, pre, h, start, cache, kind, layer, carry)
        src = f"layers.{cfg.sources[layer]}.attn"
        p = {**p, **{pre + leaf: p[src + leaf]
                     for leaf in (".wk", ".wv", ".bk", ".bv")}}
        return real(cfg, p, pre, h, start, None, "dense", layer, carry)[0], ()

    monkeypatch.setattr(dec, "differential_attention", planted)


def _plant_bf16_state(monkeypatch):
    bf = lambda S: S.astype(jnp.bfloat16).astype(jnp.float32)
    scan, step = m1.mamba1_scan, m1.mamba1_step

    def scan16(*a):
        y, S, Sc = scan(*a)
        return y, bf(S), bf(Sc)

    def step16(*a):
        y, state = step(*a)
        return y, bf(state)

    monkeypatch.setattr(m1, "mamba1_scan", scan16)
    monkeypatch.setattr(m1, "mamba1_step", step16)


PLANTS = {"lambda_zero": _plant_lambda_zero,
          "cross_reads_its_own": _plant_cross_reads_its_own,
          "bf16_state": _plant_bf16_state}


@pytest.mark.parametrize("plant", list(PLANTS))
def test_the_comparison_can_fail(plant, monkeypatch):
    """Three planted faults each move the logits past the limit: the
    comparison is one that can fail. (The state's rounding shows only
    through the cache: prefill, then decode steps from the kept state.)"""
    PLANTS[plant](monkeypatch)
    m = _model()
    text = _ids(29)
    if plant == "bf16_state":
        _, got = _serve_logits(_engine(m, prefix_cache=False), text[:20],
                               text[20:])
        want = _ref_logits(m, text)[19:]
    else:
        got, want = _forward(m, text), _ref_logits(m, text)
    assert np.abs(got - want).max() > 10 * TOL


# ----------------------------------------------- (b) the Mamba-1 kernels

def _scan_inputs(T, E=64, N=4, seed=0, rows=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    f = jnp.float32
    x = jax.random.normal(k[0], (rows, T, E), f)
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, T, E), f) - 2)
    A = -jnp.exp(0.5 * jax.random.normal(k[2], (N, E), f))
    B, C = (jax.random.normal(k[i], (rows, T, N), f) for i in (3, 4))
    return x, dt, A, B, C, jnp.full((E,), 0.7, f), \
        jax.random.normal(k[5], (rows, N, E), f)


def _token_scan(x, dt, A, B, C, D, S0):
    """The reference's own recurrence, a python loop over tokens."""
    S, ys = np.asarray(S0, np.float64), []
    A, D = np.asarray(A, np.float64), np.asarray(D, np.float64)
    before = [S.copy()]
    for t in range(x.shape[0]):
        xt, dtt = np.asarray(x[t], np.float64), np.asarray(dt[t], np.float64)
        S = np.exp(dtt[None] * A) * S + (dtt * xt)[None] \
            * np.asarray(B[t], np.float64)[:, None]
        ys.append((S * np.asarray(C[t], np.float64)[:, None]).sum(0) + D * xt)
        before.append(S.copy())
    return np.stack(ys), before


@pytest.mark.parametrize("tier", ["oracle", "pallas"])
@pytest.mark.parametrize("T", [5, 16, 37])
def test_scan_is_the_token_scan(T, tier):
    """Both tiers of ``mamba1_scan`` (the oracle's ``lax.scan``; the Pallas
    kernel under the interpreter, whose chunk of tokens ``T`` need not
    fill) against a loop over tokens, from a state that is not zero."""
    x, dt, A, B, C, D, S0 = _scan_inputs(T, rows=2)
    with use_paged_attention_impl(tier):
        y, S, _ = m1.mamba1_scan(x, dt, A, B, C, D, S0)
    for r in range(2):
        want, states = _token_scan(x[r], dt[r], A, B[r], C[r], D, S0[r])
        assert np.abs(np.asarray(y[r]) - want).max() < 2e-5
        assert np.abs(np.asarray(S[r]) - states[-1]).max() < 2e-5


@pytest.mark.parametrize("tier", ["oracle", "pallas"])
@pytest.mark.parametrize("cuts", [(0, 21), (8, 9), (20, 21)],
                         ids=["first", "middle", "end"])
def test_scan_hands_out_the_state_before_a_cut(cuts, tier):
    T = 21
    x, dt, A, B, C, D, S0 = _scan_inputs(T, seed=3)
    with use_paged_attention_impl(tier):
        _, _, Sc = m1.mamba1_scan(x, dt, A, B, C, D, S0,
                                  jnp.asarray([cuts], jnp.int32))
    _, states = _token_scan(x[0], dt[0], A, B[0], C[0], D, S0[0])
    for j, c in enumerate(cuts):
        assert np.abs(np.asarray(Sc[0, j]) - states[c]).max() < 2e-5


@pytest.mark.parametrize("tier", ["oracle", "pallas"])
def test_padding_moves_no_state(tier):
    """Tokens with ``dt = 0`` (padding behind the last real one) leave the
    state where the last real token put it, and a cut behind them reads
    it."""
    T, n = 24, 17
    x, dt, A, B, C, D, S0 = _scan_inputs(T, seed=5)
    dt = dt.at[:, n:].set(0.0)
    with use_paged_attention_impl(tier):
        _, S, Sc = m1.mamba1_scan(x, dt, A, B, C, D, S0,
                                  jnp.asarray([[n, T]], jnp.int32))
    _, states = _token_scan(x[0, :n], dt[0, :n], A, B[0, :n], C[0, :n], D,
                            S0[0])
    for got in (S[0], Sc[0, 0], Sc[0, 1]):
        assert np.abs(np.asarray(got) - states[-1]).max() < 2e-5


def test_step_kernel_oracle_and_scan():
    """The decode step: kernel (interpreted) and oracle against one token of
    the scan, in place on rows [0, B) of a buffer whose other rows (the
    snapshots) stay; a slot with ``dt = 0`` (dead) keeps its state."""
    x, dt, A, B, C, D, S0 = _scan_inputs(1, rows=3, seed=7)
    dt = dt.at[2].set(0.0)
    state = jnp.concatenate([S0, S0 + 1.0], axis=0)
    want_y, want_S = [], []
    for r in range(3):
        y, states = _token_scan(x[r], dt[r], A, B[r], C[r], D, S0[r])
        want_y.append(y[0]), want_S.append(states[-1])
    for tier in ("oracle", "pallas"):
        with use_paged_attention_impl(tier):
            y, new = m1.mamba1_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D,
                                    state)
        assert np.abs(np.asarray(y) - np.stack(want_y)).max() < 2e-5
        assert np.abs(np.asarray(new[:3]) - np.stack(want_S)).max() < 2e-5
        assert np.array_equal(np.asarray(new[3:]), np.asarray(state[3:]))
        assert np.array_equal(np.asarray(new[2]), np.asarray(state[2]))


# ------------------------------------------ (c) the last-token cut

@pytest.mark.parametrize("n", [13, 32])
def test_prefills_cut_gives_the_last_tokens_logits(model, n):
    """``prefill_with_cache`` told ``lengths`` runs the layers from the cut
    on the last real token alone; every layer on every token gives the same
    logits there (padding behind the last real token included)."""
    text = _ids(n)
    ids = np.zeros((1, 32), np.int32)
    ids[0, :n] = text
    def prefill(ids, lengths):
        logits, news = model.prefill_with_cache(ids, lengths=lengths)
        return logits._value, [tuple(t._value for t in e) for e in news]

    logits, news = jax.jit(prefill)(jnp.asarray(ids),
                                    jnp.asarray([n], jnp.int32))
    assert np.abs(np.asarray(logits[0])
                  - _forward(model, text)[-1]).max() < TOL
    # the layers behind the cut hand back nothing to install
    assert [len(e) for e in news] == [2, 2, 2, 2, 2, 2, 0, 0]


def test_cut_layer_is_where_the_cross_decoder_begins():
    cfg = DecoderConfig(**_sizes(PATTERNS["whole"]))
    assert cfg.cut_layer == 5
    assert DecoderConfig(**_sizes(PATTERNS["memory"])).cut_layer is None
    # a layer with state behind the full layer: no cut is sound
    late = PATTERNS["whole"] + [("mamba", None)]
    assert DecoderConfig(**_sizes(late)).cut_layer is None


# ------------------------------------- (d) through the engine's cache

@pytest.mark.parametrize("which", range(4),
                         ids=["cold", "starts_over", "resumes_at_branch",
                              "next_turn"])
def test_admission_then_decode_is_the_references_forward(served, model,
                                                         which):
    """Prefill (or a restored snapshot + a spliced window + an extend), then
    decode through the cache, equals the reference's full forward: the
    admission's last-token logits and four decode steps behind it."""
    _, runs, _, _ = served
    text, n, rows, _ = runs[which]
    want = _ref_logits(model, text)[n - 1:]
    assert np.abs(rows - want[:len(rows)]).max() < TOL


def test_the_third_admission_restores_and_splices(served):
    """The case the engine refused before: ONE admission restores a
    snapshot row and splices the window group's pages before the resume
    point; the follow-up turn resumes at its prompt's end."""
    eng, runs, spans, _ = served
    adm = [s for s in spans if s["name"] == "serving/admit"]
    at = lambda i, k: adm[i]["attrs"].get(k)
    assert [at(i, "snapshot_blocks") for i in range(4)] == [0, 0, 6, 7]
    assert [at(i, "resume_blocks") for i in range(4)] == [0, 0, 6, 7]
    # the second matched the system prompt's pages and ran them again
    assert at(1, "hit_blocks") == 6 and at(1, "recomputed_tokens") == 24
    assert at(2, "recomputed_tokens") == 0
    ext = [s for s in spans if s["name"] == "serving/admit/extend"]
    assert [s["attrs"]["start"] for s in ext] == [24, 28]
    assert eng.resume_cut_tokens == 24


def test_spans_and_counters(served):
    """The admission span says how many rows entered the cross-decoder
    (one: the cut) beside the prompt's tokens; the decode program's
    statistics name the shared pool's reads; the gauge says how many layers
    read the one pool."""
    eng, runs, spans, gauges = served
    adm = [s for s in spans if s["name"] == "serving/admit"]
    assert all(s["attrs"]["cross_rows"] == 1 for s in adm)
    assert all(s["attrs"]["prompt_tokens"] > 24 for s in adm)
    assert eng.model.step_stats[-4:] == (
        "window_tokens_read", "full_tokens_read", "ssm_slots_stepped",
        "shared_read")
    assert eng._shared_readers == 2
    assert gauges["serving.shared_pool.readers"] == 2


def test_step_statistics_count_the_shared_pools_reads(model):
    """A decode step over two live slots: each of the layers that read the
    one pool (the full layer and the cross layer) counts the slots'
    contexts, the sliding layers their windows, the Mamba-1 layers the
    slots they stepped."""
    eng = _engine(model, prefix_cache=False)
    obs.enable()
    tracing.clear_spans()
    eng.generate([_ids(20, seed=2), _ids(9, seed=3)],
                 SamplingParams(max_new_tokens=3))
    dec_spans = [s["attrs"] for s in tracing.spans()
                 if s["name"] == "serving/decode" and "shared_read" in s["attrs"]]
    obs.disable()
    obs.reset()
    tracing.clear_spans()
    first = dec_spans[0]
    assert list(first["shared_read"]) == [0, 0, 0, 0, 0, 31, 0, 31]
    assert list(first["window_tokens_read"]) == [0, 12 + 10, 0, 22, 0, 0, 0, 0]
    assert list(first["ssm_slots_stepped"]) == [2, 0, 2, 0, 2, 0, 0, 0]


def test_a_token_is_charged_once_for_every_reading_layer(model):
    """The one shared pool is ONE buffer a pool (K, V) whatever the number
    of layers that read it: its bytes are a page's bytes times the pages,
    the allocator hands out one page a block of tokens, and the cross layer
    is handed an empty cache entry."""
    eng = _engine(model, prefix_cache=False)
    c = eng.cache
    k = c.pool_specs.index(("k", 2, 16))
    assert c.pool_layers[k] == (5,) and c.pool_readers[k] == (5, 7)
    assert len(c.pools[k]) == 1
    pages = c.groups[0][2]
    assert c.pools[k][0].nbytes == pages * 2 * PS * 16 * 4
    entries = c.layer_entries(c.pools, c.tables_device())
    assert entries[7] == () and entries[6] == () and len(entries[5]) == 3
    before = eng.page_alloc.num_allocated
    req = eng.add_request(_ids(21, seed=4), SamplingParams(max_new_tokens=4))
    eng._admit()
    # 21 tokens and the next one: six blocks of four, charged once
    assert eng.page_alloc.num_allocated - before == 6
    assert len(c.slot_pages(req.slot, 0)) == 6


def test_allocators_are_covered_exactly(served):
    """After every request has finished the pages and snapshots that are
    still held are the trie's, group by group: nothing leaked, the windows
    that a superseded snapshot looked back on are gone."""
    eng, _, _, _ = served
    trie = eng.prefix_cache
    nodes, stack = [], [trie._root]
    while stack:
        node = stack.pop()
        stack.extend(node.children.values())
        if node is not trie._root:
            nodes.append(node)
    assert eng.page_allocs[0].num_allocated == len(nodes)
    held = [n.more[0] for n in nodes if n.more[0] is not None]
    assert eng.page_allocs[1].num_allocated == len(held)
    snaps = [n for n in nodes if n.snapshot is not None]
    assert eng.snapshot_alloc.num_allocated == len(snaps)
    # every snapshot still has the window before it
    back = (WINDOW + PS - 2) // PS
    assert len(held) <= len(snaps) * back
    assert eng.window_pages_freed > 0


def test_kernels_in_the_engine_agree_with_the_oracle(model):
    """The engine's programs with every kernel in (interpreted: the paged
    decode and window decode over the pair-head pools, the window extend's
    flash, the Mamba-1 scan and step) give the oracle tier's tokens."""
    shared = _ids(24, seed=1)
    prompts = [shared + _ids(n, seed=n) for n in (9, 14, 7)]
    outs = {}
    for tier in ("oracle", "pallas"):
        with use_paged_attention_impl(tier):
            eng = _engine(model)
            outs[tier] = [eng.generate([p], SamplingParams(max_new_tokens=3))[0]
                          for p in prompts]
            if tier == "pallas":
                fn, args = eng.decode_program()
                from paddle_tpu.kernels.mesh import traced_kernels
                names = traced_kernels(fn, *args)
    assert outs["oracle"] == outs["pallas"]
    assert {"mamba1_decode_step", "paged_decode", "window_decode"} <= set(names)


# ------------------------------------------------- (e) what the engine refuses

def test_engine_builds_over_state_and_windows(model):
    eng = _engine(model)
    assert eng._stateful and eng._windows == [(1, WINDOW)]
    assert [g[0] for g in eng.cache.groups] == ["global", "window"]


def test_speculation_is_refused(model):
    with pytest.raises(ValueError, match="speculative decoding is refused"):
        _engine(model, speculative=2)


def test_a_first_group_with_a_window_refuses_the_prefix_cache():
    """A model whose ONLY attention is sliding (its first page group keeps
    a window) is still refused the prefix cache; without it it builds."""
    m = _model("sliding")
    with pytest.raises(ValueError, match="sliding-window pools"):
        Engine(m, max_batch_size=2, max_seq_len=32, page_size=PS,
               prefix_cache=True)
    Engine(m, max_batch_size=2, max_seq_len=32, page_size=PS)


@pytest.mark.parametrize("pattern, match", [
    ([("gmu", None)], "EARLIER mamba1"),
    ([("full", None), ("gmu", 0)], "EARLIER mamba1"),
    ([("cross", 0), ("full", None)], "EARLIER dense"),
    ([("mamba", 0)], "reads no other layer"),
    ([("full", None), ("full", None), ("cross", 0), ("cross", 1)],
     "ONE layer's pool"),
])
def test_layer_sources_are_validated(pattern, match):
    with pytest.raises(ValueError, match=match):
        DecoderConfig(**_sizes(pattern))


def test_the_published_description_counts_its_parameters():
    """The description the benchmark's configuration builds: 3,852,562,944
    parameters (shapes only; nothing is allocated)."""
    L = 32
    kinds = ["mamba1" if l % 2 == 0 else "sliding" for l in range(16)] \
        + ["mamba1", "dense"] \
        + ["gmu" if l % 2 == 0 else "cross" for l in range(18, L)]
    src = [None] * 18 + [16 if l % 2 == 0 else 17 for l in range(18, L)]
    cfg = DecoderConfig(
        vocab_size=200064, hidden_size=2560, num_layers=L, num_heads=40,
        num_kv_heads=20, head_dim=64, max_context=16384, norm="layer",
        norm_eps=1e-5, position="none", qk_norm=False, kv_layout="head",
        layer_types=tuple(kinds), layer_sources=tuple(src),
        sliding_window=512, differential=True, attn_bias=True, ssm_state=16,
        ffn="swiglu", intermediate_size=10240, tie_word_embeddings=True,
        init="zeros")
    assert cfg.cut_layer == 17
    assert sum(int(np.prod(s)) for s in param_shapes(cfg).values()) \
        == 3_852_562_944
