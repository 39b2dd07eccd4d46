"""A decoder of the Nemotron-H kind: layers of ONE part each (a Mamba-2
state-space mixer, ungated relu² experts behind a sigmoid router with a
bias, of which THIS program holds some, plus a shared expert of a width of
its own, or NoPE GQA) under serving.Engine, against its plain reference
(benchmark/reference/nemotron3_nano.py: the recurrence one state update a
token, a masked sum over the held experts, a plain top-k) at a small size
on the CPU: hidden 32; Mamba-2 4 heads x 8 on 2 groups, state 16, chunk 8;
4 / 2 attention heads of 8; 16 experts of width 24, 3 a token, 8 held
(share 0 of 2), a shared expert 48 wide; pages of 8 tokens.

Tolerances. Program and reference both compute in float32 here, in
different orders (chunks against single tokens, sorted rows against a
masked loop, pages, the packed state), so logits (|logit| up to about 1
with these weights) agree to about 1e-6; the limit 1e-4 leaves room and is
far under what a lower precision or any fault moves a logit by: the same
program with a gated expert, the norm before the gate, the bias in the
weights, a missing ``D`` or a bfloat16 state reads 1e-3 or more
(``test_the_comparison_can_fail``). The kernels' own comparison with the
recurrence is held to 2e-5 (a few hundred float32 operations from the same
inputs).
"""

import hashlib
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu import observability as obs
from paddle_tpu.kernels import mamba2 as ssm
from paddle_tpu.kernels.gated_delta import pack_state, unpack_state
from paddle_tpu.kernels.tier import use_paged_attention_impl
from paddle_tpu.models import decoder as dec
from paddle_tpu.models.decoder import (DecoderConfig, DecoderLM,
                                       is_norm_scale, param_shapes)
from paddle_tpu.observability import tracing
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
from paddle_tpu.serving.kv_cache import PagedKVCache

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import nemotron3_nano as ref  # noqa: E402

TOL = 1e-4
V = 96
PATTERN = "MEM*EME"
E, HELD = 16, 8
PS = 8
#: the published pattern's characters: (the program's mixer, its FFN)
PARTS = {"M": ("mamba2", "none"), "E": ("none", "moe_relu2"),
         "*": ("dense", "none")}


def _sizes(pattern=PATTERN, held=(HELD, 0)):
    return dict(
        vocab_size=V, hidden_size=32, num_layers=len(pattern), num_heads=4,
        num_kv_heads=2, head_dim=8, max_context=128, norm_eps=1e-5,
        position="none", qk_norm=False, kv_layout="head",
        layer_types=tuple(PARTS[c][0] for c in pattern),
        ffn_types=tuple(PARTS[c][1] for c in pattern),
        ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
        ssm_conv_kernel=4, ssm_chunk=8, intermediate_size=24,
        shared_experts=1, shared_intermediate_size=48,
        router="sigmoid_group_topk", n_group=1, topk_group=1,
        routed_scaling_factor=2.5, num_experts=E, experts_per_token=3,
        experts_held=held, query_chunk=32)


def _rcfg(pattern=PATTERN, held=(HELD, 0)):
    return dict(layer_types=[ref.KINDS[c] for c in pattern], num_heads=4,
                num_kv_heads=2, head_dim=8, ssm_heads=4, ssm_head_dim=8,
                ssm_groups=2, ssm_state=16, conv_kernel=4, norm_eps=1e-5,
                num_experts=E, experts_per_token=3, norm_topk_prob=True,
                routed_scaling_factor=2.5, experts_held=held)


def _model(pattern=PATTERN, held=(HELD, 0), **over):
    """Seeded weights that make every part matter: matrices at ten times
    the initializer's 0.02, norm scales 1 + N(0, 0.1), the convolution's and
    the router's biases and the skip ``D`` moved off 0 and 1."""
    m = DecoderLM(DecoderConfig(**{**_sizes(pattern, held), **over}))
    m.eval()
    key = jax.random.PRNGKey(1)
    for i, (n, p) in enumerate(m.named_parameters()):
        k = jax.random.fold_in(key, zlib.crc32(n.encode()) % (2**31 - 1))
        draw = lambda s: s * jax.random.normal(k, p._value.shape, jnp.float32)
        if is_norm_scale(n):
            p._set_value_raw(1 + draw(0.1))
        elif n.endswith((".conv.bias", ".D")):
            p._set_value_raw(p._value + draw(0.3))
        elif n.endswith(".router.bias"):
            p._set_value_raw(draw(0.05))
        elif p._value.ndim >= 2 and not n.endswith("conv.weight"):
            p._set_value_raw(p._value * 10)
    return m


def _params(m):
    return {n: p._value for n, p in m.named_parameters()}


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, V, size=(n,)).tolist()


def _ref_logits(m, text, rcfg=None, mm=ref.mm_highest):
    return np.asarray(ref.forward(_params(m), jnp.asarray(text),
                                  rcfg or _rcfg(), mm, q_block=len(text)))


def _forward(m, text):
    return np.asarray(jax.jit(lambda ids: m(ids)._value)(
        jnp.asarray(text)[None])[0])


def _engine(m, **over):
    return Engine(m, EngineConfig(**{**dict(
        max_batch_size=3, max_seq_len=96, page_size=PS, prefix_cache=True,
        state_snapshots=6, prefill_buckets=(8, 16, 32, 64, 96)), **over}))


def _serve_logits(eng, prompt, follow):
    """Admit ``prompt`` through the engine's own admission (its prefill /
    restore / extend programs, its pools), then feed ``follow`` one token a
    decode step through ``decode_step`` over the engine's pools: (the
    request, logits [1 + len(follow), V] at the prompt's last position and
    at each fed token's). (``tests/test_solar_serving.py``'s.)"""
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

    @jax.jit
    def step(tokens, pools, table, pos):
        logits, new, _ = m.decode_step(
            tokens, eng.cache.layer_entries(pools, table), pos)
        return logits._value, [tuple(t._value for t in layer)
                               for layer in new]

    for j, tok in enumerate(follow):
        tokens = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tokens[slot], pos[slot] = tok, len(prompt) + j
        eng._positions[slot] = pos[slot]
        eng._grow_pages()
        logits, new = step(jnp.asarray(tokens), eng.cache.pools,
                           eng.cache.table_device(), jnp.asarray(pos))
        eng.cache.pools = eng.cache.pools_from_layers(new)
        rows.append(np.asarray(logits[slot]))
    return req, np.stack(rows)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def served(model):
    """ONE engine run shared by the engine cases: three requests on one
    system prompt (two whole pages and a half), then what the spans said."""
    obs.enable()
    obs.reset()
    tracing.clear_spans()
    eng = _engine(model)
    shared = _ids(20, seed=1)
    prompts = [shared + _ids(n, seed=n) for n in (9, 13, 7)]
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
    spans = list(tracing.spans())
    obs.disable()
    obs.reset()
    # leave none in the ring for a later test of a cell in this process
    tracing.clear_spans()
    return eng, prompts, outs, spans


# ------------------------------------------------ (a) layers and the model

@pytest.mark.parametrize("pattern", ["M", "E", "*", PATTERN])
def test_layers_against_the_reference(pattern):
    """An ``M`` layer, an ``E`` layer, a ``*`` layer and the whole model on
    a pattern of all three: logits of a full causal pass."""
    m = _model(pattern)
    text = _ids(21)
    got = _forward(m, text)
    want = _ref_logits(m, text, _rcfg(pattern))
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 0.05


def test_the_comparison_can_fail(model, monkeypatch):
    """Each of these moves a logit by far more than the tolerance: a SwiGLU
    in the relu²'s place, the norm before the gate, the bias in the
    weights, a missing ``D``, a bfloat16 state."""
    text = _ids(40, seed=2)
    got = _forward(model, text)
    params = _params(model)

    def gap(p=params, **patch):
        for name, fn in patch.items():
            monkeypatch.setattr(ref, name, fn)
        out = np.asarray(ref.forward(p, jnp.asarray(text), _rcfg(),
                                     q_block=len(text)))
        monkeypatch.undo()
        return np.abs(got - out).max()

    assert gap() < TOL
    # a gated (silu) expert in the ungated relu²'s place
    assert gap(relu2=jax.nn.silu) > 10 * TOL
    # the bias in the weights as well as in the choice
    route = ref.route

    def biased(g, p, cfg, mm):
        s = dict(p)
        w = route(g, s, cfg, mm)
        return jnp.where(w > 0, w + s["ffn.router.bias"][None].astype(
            jnp.float32), 0.0)

    assert gap(route=biased) > 10 * TOL
    # no skip
    assert gap({n: jnp.zeros_like(v) if n.endswith(".D") else v
                for n, v in params.items()}) > 10 * TOL
    # the norm before the gate
    late = np.asarray(ref.forward(params, jnp.asarray(text),
                                  dict(_rcfg(), norm_before_gate=True),
                                  q_block=len(text)))
    assert np.abs(got - late).max() > 10 * TOL
    # the state rounded to bfloat16 between tokens

    def bf16_state(x, dt, A, B, C, D, S0):
        def tok(S, t):
            xt, dtt, Bt, Ct = t
            Bh, Ch = jnp.repeat(Bt, 2, 0), jnp.repeat(Ct, 2, 0)
            S = S * jnp.exp(dtt * A)[:, None, None] \
                + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :]
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
            return S, jnp.sum(S * Ch[:, None, :], -1) + D[:, None] * xt
        S, y = lax.scan(tok, S0, (x, dt, B, C))
        return y, S

    a = _inputs(64, seed=5)
    want, _ = _recurrence(*a)
    low, _ = bf16_state(*a)
    assert float(jnp.abs(want - low).max()) > 100 * 2e-5


# --------------------------------------- (b) the two forms of the recurrence

def _recurrence(x, dt, A, B, C, D, S0):
    """Token by token: ``(y [T, H, P], S_T)``."""
    rep = x.shape[1] // B.shape[1]

    def token(S, t):
        xt, dtt, Bt, Ct = t
        Bh, Ch = jnp.repeat(Bt, rep, 0), jnp.repeat(Ct, rep, 0)
        S = S * jnp.exp(dtt * A)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :]
        return S, jnp.sum(S * Ch[:, None, :], -1) + D[:, None] * xt

    S, y = lax.scan(token, S0, (x, dt, B, C))
    return y, S


def _inputs(T, H=4, P=8, G=2, N=16, seed=0, zero_state=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    f32 = jnp.float32
    S0 = jax.random.normal(ks[6], (H, P, N), f32)
    return (jax.random.normal(ks[0], (T, H, P), f32),
            jax.nn.softplus(jax.random.normal(ks[1], (T, H), f32)),
            -jnp.exp(jax.random.normal(ks[2], (H,), f32)),
            jax.random.normal(ks[3], (T, G, N), f32),
            jax.random.normal(ks[4], (T, G, N), f32),
            jax.random.normal(ks[5], (H,), f32),
            jnp.zeros_like(S0) if zero_state else S0)


#: lengths under, at and over a chunk of 8, and not a multiple of it
@pytest.mark.parametrize("T", [5, 8, 13, 24])
@pytest.mark.parametrize("zero_state", [True, False],
                         ids=["from_zero", "from_a_state"])
def test_chunked_form_is_the_token_scan(T, zero_state):
    a = _inputs(T, seed=T, zero_state=zero_state)
    want_y, want_S = _recurrence(*a)
    y, S = ssm.mamba2_chunked(*a, chunk=8)
    assert float(jnp.abs(y - want_y).max()) < 2e-5
    assert float(jnp.abs(S - want_S).max()) < 2e-5


#: where a state is asked for among 21 tokens in chunks of 8
CUTS = {"at_0": (0,), "at_a_chunks_edge": (8, 16), "inside_a_chunk": (3, 13),
        "at_the_end": (21,), "edge_and_inside": (16, 19)}


@pytest.mark.parametrize("name", CUTS)
def test_cuts_hand_out_the_state_before_a_token(name):
    a = _inputs(21, seed=3)
    cuts = CUTS[name]
    y0, S0 = ssm.mamba2_chunked(*a, chunk=8)
    y, S, Sc = ssm.mamba2_chunked(*a, chunk=8,
                                  cuts=jnp.asarray(cuts, jnp.int32))
    assert float(jnp.abs(y - y0).max()) == 0.0      # cuts move nothing
    assert float(jnp.abs(S - S0).max()) == 0.0
    for j, c in enumerate(cuts):
        part = tuple(v[:c] if i in (0, 1, 3, 4) else v
                     for i, v in enumerate(a))
        want = _recurrence(*part)[1] if c else a[6]
        assert float(jnp.abs(Sc[j] - want).max()) < 2e-5


def test_padding_moves_neither_state_nor_tail():
    """Ragged ``lengths``: a row's tokens behind its length are padding.
    The layer's end state and tail are those of the real tokens alone."""
    m = _model("M")
    cfg, p = m.cfg, _params(m)
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32), jnp.float32)
    lengths = jnp.asarray([16, 11], jnp.int32)
    start = jnp.zeros((2,), jnp.int32)
    _, (S, tail) = dec.mamba2(cfg, p, "layers.0.attn", h, start,
                              lengths=lengths)
    _, (S1, tail1) = dec.mamba2(cfg, p, "layers.0.attn", h[1:, :11],
                                start[:1])
    assert float(jnp.abs(S[1] - S1[0]).max()) < 2e-5
    assert float(jnp.abs(tail[1] - tail1[0]).max()) == 0.0
    _, (S0, tail0) = dec.mamba2(cfg, p, "layers.0.attn", h[:1], start[:1])
    assert float(jnp.abs(S[0] - S0[0]).max()) < 2e-5
    assert float(jnp.abs(tail[0] - tail0[0]).max()) == 0.0


# ------------------------------------------------------ (c) the step kernel

@pytest.mark.parametrize("heads", [(4, 8, 2, 16), (8, 64, 2, 128)],
                         ids=["tiny", "two_heads_a_row"])
def test_recurrent_step_kernel_oracle_and_scan(heads):
    """The Pallas kernel under the interpreter = its oracle = one token of
    the scan; a slot with ``dt = 0`` (it runs nothing) and the rows behind
    the slots stay as they were."""
    H, P, G, N = heads
    Bn = 3
    x, dt, A, B, C, D, _ = _inputs(Bn, H, P, G, N, seed=7)
    S = jax.random.normal(jax.random.PRNGKey(9), (Bn + 2, H, P, N),
                          jnp.float32)
    state = pack_state(S)
    dt = dt.at[1].set(0.0)
    yo, so = ssm._step_oracle(x, dt, A, B, C, D, state)
    with use_paged_attention_impl("pallas"):
        yk, sk = ssm.mamba2_step(x, dt, A, B, C, D, state)
    assert float(jnp.abs(yo - yk).max()) < 2e-5
    assert float(jnp.abs(so - sk).max()) < 2e-5
    for s in (so, sk):
        assert float(jnp.abs(s[1] - state[1]).max()) == 0.0   # empty slot
        assert float(jnp.abs(s[Bn:] - state[Bn:]).max()) == 0.0
    for b in range(Bn):
        y1, S1 = _recurrence(x[b:b + 1], dt[b:b + 1], A, B[b:b + 1],
                             C[b:b + 1], D, S[b])
        assert float(jnp.abs(y1[0] - yk[b]).max()) < 2e-5
        assert float(jnp.abs(S1 - unpack_state(sk[:Bn], H)[b]).max()) < 2e-5


# ------------------------------------- (d) through the engine's pools

def test_prefill_then_decode_is_one_forward(model):
    """Prefill, then N decode steps through the engine's pools = one
    forward of N more tokens."""
    eng = _engine(model, prefix_cache=False)
    prompt, follow = _ids(19, seed=3), _ids(9, seed=4)
    _, rows = _serve_logits(eng, prompt, follow)
    want = _ref_logits(model, prompt + follow)[len(prompt) - 1:]
    assert np.abs(rows - want).max() < TOL


def test_extend_behind_a_restored_snapshot_is_the_same(model):
    """The third prompt on a shared prefix restores the branch snapshot and
    extends: the same logits as one forward."""
    eng = _engine(model)
    shared = _ids(20, seed=1)
    eng.generate([shared + _ids(9, seed=9), shared + _ids(13, seed=13)],
                 SamplingParams(max_new_tokens=2))
    prompt, follow = shared + _ids(7, seed=7), _ids(6, seed=6)
    before = eng.prefix_cache.deepest_snapshot(prompt, 2)
    assert before[0] == 2 and before[1] is not None
    req, rows = _serve_logits(eng, prompt, follow)
    assert req.prefix_hit_blocks == 2
    want = _ref_logits(model, prompt + follow)[len(prompt) - 1:]
    assert np.abs(rows - want).max() < TOL


# ----------------------------------------- (e) through serving.Engine

def test_engine_greedy_tokens_are_the_references(served, model):
    _, prompts, outs, _ = served
    for prompt, out in zip(prompts, outs):
        full = _ref_logits(model, prompt + out)
        assert out == [int(np.argmax(full[len(prompt) - 1 + i]))
                       for i in range(len(out))]


def test_engine_second_request_takes_the_branch_snapshot(served):
    _, _, _, spans = served
    adm = [e["attrs"] for e in spans if e["name"] == "serving/admit"]
    assert [(a["hit_blocks"], a["snapshot_blocks"], a["recomputed_tokens"])
            for a in adm] == [(0, 0, 0), (2, 0, 2 * PS), (2, 2, 0)]
    taken = [(e["attrs"]["blocks"], e["attrs"]["reason"]) for e in spans
             if e["name"].startswith("serving/snapshot{")]
    assert (2, "branch") in taken
    assert [e["attrs"]["blocks"] for e in spans
            if e["name"] == "serving/admit/restore"] == [2]


def test_engine_spans_and_counters(served):
    """The engine's spans come for this model as for the hybrid one, and
    the decode span carries the new counter: a Mamba-2 layer counts the
    running slots it stepped, any other layer 0; an ``M`` layer routes
    nothing."""
    eng, _, _, spans = served
    names = {e["name"].split("{")[0] for e in spans}
    assert {"serving/step", "serving/admit", "serving/admit/prefill",
            "serving/admit/extend", "serving/decode",
            "serving/decode/dispatch", "serving/snapshot"} <= names
    assert eng.model.step_stats == (
        "experts_touched", "expert_max_load", "local_rows", "routed_rows",
        "ssm_slots_stepped")
    steps = [e["attrs"] for e in spans if e["name"] == "serving/decode"
             and "ssm_slots_stepped" in e["attrs"]]
    assert steps
    for a in steps:
        for l, ch in enumerate(PATTERN):
            assert a["ssm_slots_stepped"][l] == (a["running"] if ch == "M"
                                                 else 0)
            assert (a["routed_rows"][l] > 0) == (ch == "E")
            if ch == "E":
                assert a["routed_rows"][l] == 3 * 3     # slots x top-k
                assert 0 <= a["local_rows"][l] <= a["routed_rows"][l]


def test_snapshot_allocator_is_covered_exactly(model):
    """After admissions, finishes and evictions every snapshot id is either
    free or held by exactly one trie node; the same for pages and their
    holders; cleared, nothing is left."""
    eng = _engine(model, state_snapshots=3, kv_pages=20)
    shared = _ids(20, seed=1)
    for round_ in range(3):
        eng.generate([shared + _ids(5 + n, seed=10 * round_ + n)
                      for n in range(3)], SamplingParams(max_new_tokens=3))
        eng.generate([_ids(3 * PS + 1, seed=50 + round_)],
                     SamplingParams(max_new_tokens=2))
    snaps, trie = eng.snapshot_alloc, eng.prefix_cache
    assert trie.snapshots_dropped > 0           # the pool of 3 was short
    held = [n.snapshot for n in trie._with_snapshot]
    assert sorted(held) == sorted(snaps._refs)  # each id once, no other
    assert all(snaps.refcount(s) == 1 for s in held)
    assert snaps.num_allocated + snaps.num_free == snaps.num_allocatable
    assert eng.cache.free_slots == eng.config.max_batch_size
    assert eng.page_alloc.num_allocated == trie.num_nodes
    trie.clear()
    assert snaps.num_allocated == 0 and eng.page_alloc.num_allocated == 0


def test_kernels_in_the_engine_agree_with_the_oracle(model):
    """The engine's programs with the kernels in (the interpreter) serve the
    tokens the oracle tier serves."""
    prompts = [_ids(11, seed=21), _ids(6, seed=22)]
    plain = _engine(model, prefix_cache=False).generate(
        prompts, SamplingParams(max_new_tokens=4))
    with use_paged_attention_impl("pallas"):
        eng = _engine(model, prefix_cache=False)
        got = eng.generate(prompts, SamplingParams(max_new_tokens=4))
    assert got == plain


def test_speculation_is_refused(model):
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(model, speculative=2)


# ------------------------------------------------------------ (f) the router

def test_router_bias_changes_the_choice_not_the_weights():
    m = _model("E")
    cfg, p = m.cfg, _params(m)
    g = jax.random.normal(jax.random.PRNGKey(3), (8, 32), jnp.float32)
    wr, bias = p["layers.0.ffn.router"], p["layers.0.ffn.router.bias"]
    s = np.asarray(jax.nn.sigmoid(g @ wr))
    pw0, e0 = dec.sigmoid_group_topk(cfg, g, wr, jnp.zeros_like(bias))
    # a bias that lifts the LEAST likely expert of token 0 into its choice
    low = int(np.argmin(s[0]))
    lift = jnp.zeros_like(bias).at[low].set(10.0)
    pw, e = dec.sigmoid_group_topk(cfg, g, wr, lift)
    assert low in np.asarray(e[0]) and low not in np.asarray(e0[0])
    chosen = s[np.arange(8)[:, None], np.asarray(e)]
    want = chosen / chosen.sum(-1, keepdims=True) * 2.5
    assert np.abs(np.asarray(pw) - want).max() < 1e-6   # s / sum x 2.5
    assert np.abs(np.asarray(pw).sum(-1) - 2.5).max() < 1e-5
    # the reference's plain top-k says the same
    w = np.asarray(ref.route(g, {"ffn.router": wr, "ffn.router.bias": lift},
                             _rcfg("E"), ref.mm_highest))
    for t in range(8):
        assert sorted(np.nonzero(w[t])[0]) == sorted(np.asarray(e[t]))


def test_relu2_differs_from_swiglu():
    """The ungated kind has two matrices an expert and another result."""
    relu2 = param_shapes(DecoderConfig(**_sizes("E")))
    gated = param_shapes(DecoderConfig(**{
        **_sizes("E"), "ffn_types": ("moe_swiglu",)}))
    assert "layers.0.ffn.w3" in gated and "layers.0.ffn.w3" not in relu2
    assert "layers.0.ffn.shared.w3" not in relu2
    assert relu2["layers.0.ffn.w1"] == (HELD, 24, 32)   # kept [out, in]
    assert relu2["layers.0.ffn.shared.w1"] == (32, 48)  # a width of its own
    g = jax.random.normal(jax.random.PRNGKey(0), (5, 32), jnp.float32)
    p = {"f.w1": jnp.ones((32, 8)) * 0.1, "f.w3": jnp.ones((32, 8)) * 0.1,
         "f.w2": jnp.ones((8, 32)) * 0.1}
    a = dec._dense_ffn(p, "f", g, "relu2")
    b = dec._dense_ffn(p, "f", g, "swiglu")
    assert float(jnp.abs(a - b).max()) > 1e-3
    want = jnp.square(jnp.maximum(g @ p["f.w1"], 0)) @ p["f.w2"]
    assert float(jnp.abs(a - want).max()) < 1e-6


# ------------------------------------------------------ (g) the sum of shares

def test_the_two_shares_add_up_to_the_uncut_layer():
    """At 16 experts, the two shares of 8, the shared expert counted once,
    add up to the uncut reference layer."""
    whole = _model("E", held=(E, 0))
    pw = _params(whole)
    g = jax.random.normal(jax.random.PRNGKey(5), (12, 32), jnp.float32)
    rc = _rcfg("E", held=(E, 0))
    pre = "layers.0."
    p = {n[len(pre):]: v for n, v in pw.items() if n.startswith(pre)}
    want = ref.routed_experts(g, p, rc, ref.mm_highest) \
        + ref.shared_expert(g, p, ref.mm_highest)
    total = dec._dense_ffn(pw, "layers.0.ffn.shared", g, "relu2")
    for first in (0, HELD):
        cfg = DecoderConfig(**_sizes("E", held=(HELD, first)))
        share = dict(pw)
        for w in ("w1", "w2"):
            share[f"layers.0.ffn.{w}"] = \
                pw[f"layers.0.ffn.{w}"][first:first + HELD]
        y, stats = dec.moe_routed(cfg, share, "layers.0.ffn", g, "relu2")
        total = total + y
        assert int(stats[3]) == 12 * 3 and 0 < int(stats[2]) < 12 * 3
    assert float(jnp.abs(total - want).max()) < TOL


# ------------------------------------------- (h) a layer of ONE part; parents

def test_a_layer_of_one_part_declares_its_own_parameters_only():
    shapes = param_shapes(DecoderConfig(**_sizes()))
    by_layer = {}
    for n in shapes:
        if n.startswith("layers."):
            by_layer.setdefault(int(n.split(".")[1]), []).append(
                n.split(".", 2)[2])
    for l, ch in enumerate(PATTERN):
        names = by_layer[l]
        norms = [n for n in names if n in ("attn_norm.weight",
                                           "ffn_norm.weight")]
        assert norms == (["ffn_norm.weight"] if ch == "E"
                         else ["attn_norm.weight"])       # ONE norm
        assert all(n.startswith("ffn") for n in names) == (ch == "E")
        assert all(n.startswith("attn") for n in names) == (ch != "E")
    m = DecoderLM(DecoderConfig(**_sizes()))
    state = m.state_pools()
    assert [(s[0], s[3]) for s in state] == [
        ("ssm_state", (0, 2, 5)), ("ssm_conv", (0, 2, 5))]
    assert state[0][1] == ssm.packed_shape(4, 16, 8) and state[1][1] == (3, 96)
    assert [(s[0], s[3]) for s in m.cache_pools()] == [("k", (3,)),
                                                       ("v", (3,))]
    with pytest.raises(ValueError, match="no mixer and no FFN"):
        DecoderConfig(num_layers=1, layer_types=("none",),
                      ffn_types=("none",))


def test_a_layer_without_pools_is_handed_an_empty_entry():
    """``PagedKVCache.layer_entries`` for a model whose FIRST pool belongs
    to some layers and whose other layers hold state or nothing."""
    cache = PagedKVCache(
        4, 2, 2, 32, 8, page_size=8,
        pools=[("k", 2, 8, (2,)), ("v", 2, 8, (2,))],
        state_pools=[("s", (3, 4), "float32", (0,))], num_snapshots=1)
    entries = cache.layer_entries(cache.pools, "table", rows="rows")
    assert [len(e) for e in entries] == [2, 0, 3, 0]
    assert entries[0][1] == "rows" and entries[2][2] == "table"
    assert entries[1] == () and entries[3] == ()
    back = cache.pools_from_layers([e[:-1] if e else () for e in entries])
    assert [len(p) for p in back] == [1, 1, 1]


def test_engine_builds_over_a_first_pool_of_some_layers(model):
    """``Engine.__init__`` sizes the snapshot pool from the configuration
    and builds its cache from a first pool that two of seven layers hold."""
    eng = _engine(model, state_snapshots=5)
    assert eng.cache.num_snapshots == 5
    assert eng.cache.pool_layers == [(3,), (3,), (0, 2, 5), (0, 2, 5)]
    assert [b.shape[0] for b in eng.cache.pools[2]] == [3 + 5] * 3
    assert len(eng.donate_argnums) == 4


#: the window description (Command A+'s kind: a parallel block, ONE norm
#: feeding both parts), the one older description no other file pins:
#: (arguments, sha256 of the lowered text, first 16 hex digits) and (count,
#: CRC-32) of its parameter list, recorded at the parent of this PR
#: (9a13f70; jax 0.9.0, x64 on as in these tests). The other four
#: descriptions and the GPT's programs and train step are pinned in
#: tests/test_window_serving.py and tests/test_hybrid_serving.py, which this
#: PR leaves as they were.
#: ``window/decode/pallas`` was recorded again at PR 47 (the paged-decode
#: kernel's page walk; 86fdb634458456b2 before it), ``window/extend/oracle``
#: at PR 48 (a head-major extend's attention is ``paged_extend_attend``, whose
#: oracle is ``extend_attend`` over the gathered view, where it was
#: ``decoder.attend``; b5e0fbca39d3f079 before it).
WINDOW = dict(layer_types=("sliding",) * 3 + ("dense",), sliding_window=16,
              kv_layout="head", norm="layer_nobias",
              norm_placement="parallel", position="rope_gptj",
              position_by_kind={"dense": "none"}, router="sigmoid_topk",
              shared_experts=4, shared_combine="mean", qk_norm=False,
              tie_word_embeddings=True, num_layers=4, experts_held=(2, 4))
PARENT = {
    "window/params": (50, 4142470600),
    "window/prefill/oracle": (9, "b71831046b432b1b"),
    "window/extend/oracle": (10, "c58614a66b366087"),
    "window/decode/oracle": (14, "b81bda4484256679"),
    "window/decode/pallas": (14, "451242c0bd563bf7"),
}


def _lowered(name):
    _, kind, impl = (name.split("/") + [None])[:3]
    cfg = DecoderConfig(**WINDOW)
    if kind == "params":
        shapes = param_shapes(cfg)
        return len(shapes), zlib.crc32(repr(list(shapes.items())).encode())
    with use_paged_attention_impl(impl):
        eng = Engine(DecoderLM(cfg), EngineConfig(
            max_batch_size=2, max_seq_len=64, page_size=4, prefix_cache=True,
            group_pages={"window": 40}))
        fn, args = {"prefill": lambda: eng.prefill_program(16),
                    "extend": lambda: eng.extend_program(16),
                    "decode": eng.decode_program}[kind]()
        text = jax.jit(fn, donate_argnums=eng.donate_argnums_of(kind)) \
            .lower(*args).as_text()
    return len(args), hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", PARENT)
def test_the_parallel_block_builds_and_lowers_as_on_the_parent(name):
    assert _lowered(name) == PARENT[name]


@pytest.mark.parametrize("over", [
    {}, {"norm_placement": "post"}, {"norm_placement": "parallel"},
    {"ffn": "swiglu"}, {"first_dense_layers": 1},
    {"layer_types": ("gated_delta", "dense"), "position": "none"}])
def test_older_descriptions_have_two_parts_a_layer(over):
    """Without ``ffn_types`` or a "none" every layer is a mixer AND an FFN,
    with the norms it had."""
    cfg = DecoderConfig(**over)
    shapes = param_shapes(cfg)
    assert "none" not in cfg.kinds and all(k != "none" for k, _ in cfg.ffns)
    for l in range(cfg.num_layers):
        assert f"layers.{l}.attn_norm.weight" in shapes
        assert (f"layers.{l}.ffn_norm.weight" in shapes) == (
            cfg.norm_placement != "parallel")
    assert "ssm_slots_stepped" not in dec.step_stats(cfg)
