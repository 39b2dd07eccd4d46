"""A decoder of the Solar-Open2 kind — a per-channel-gated delta rule (Kimi
Delta Attention) in three layers of four, output-gated NoPE GQA in the
first, routed experts of which THIS program holds some, plus a shared one,
in every layer — under serving.Engine, against its plain reference
(benchmark/reference/solar_open2.py: the token-by-token recurrence, a masked
sum over the held experts) at a small size on the CPU: hidden 32, 4 layers,
4 / 2 heads of 8; linear heads 4 of 8 x 8; 32 experts of width 16, 4 a
token, 4 held from index 4 (share 1 of 8); pages of 8 tokens, chunks of 4.

Tolerances. Program and reference both compute in float32 here, in
different orders (chunks against single tokens, sorted rows against a
masked loop, pages, the packed state), so logits (|logit| up to about 2
with these weights) agree to about 3e-6; the limit 1e-4 leaves thirty times
of room and is far under what a lower precision or any fault moves a logit
by: the same program with its matmuls' operands rounded to bfloat16 reads
1e-2 or more (``test_the_comparison_can_fail``), as do a scalar decay in
the channel gate's place, a missing shared expert, and an absent expert's
rows let in. The kernels' own comparison with the recurrence is held to
2e-5 (a few hundred float32 operations from the same inputs).
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
from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels.tier import use_paged_attention_impl
from paddle_tpu.models import decoder as dec
from paddle_tpu.models.decoder import (DecoderConfig, DecoderLM, initial_value,
                                       is_norm_scale, param_shapes)
from paddle_tpu.observability import tracing
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import solar_open2 as ref  # noqa: E402

TOL = 1e-4
V = 97
KINDS = ("dense", "gated_delta", "gated_delta", "gated_delta")
E, HELD, SHARES = 32, 4, 8          # experts, held a share, shares
SIZES = dict(vocab_size=V, hidden_size=32, num_layers=4, num_heads=4,
             num_kv_heads=2, head_dim=8, max_context=128, norm_eps=1e-5,
             position="none", qk_norm=False, layer_types=KINDS,
             kv_layout="head", attn_output_gate=True, linear_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=8,
             linear_gate="channel", linear_gate_rank=8, gdn_chunk=4,
             ffn="moe_swiglu", intermediate_size=16, num_experts=E,
             experts_per_token=4, experts_held=(HELD, 4), shared_experts=1,
             query_chunk=32)
RCFG = dict(layer_types=["full_attention"] + ["linear_attention"] * 3,
            num_heads=4, num_kv_heads=2, head_dim=8, linear_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8, conv_kernel=4,
            allow_neg_eigval=True, norm_eps=1e-5, num_experts=E,
            experts_per_token=4, norm_topk_prob=True,
            routed_scaling_factor=1, experts_held=(HELD, 4))
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
        elif p._value.ndim >= 2 and not n.endswith("conv.weight"):
            p._set_value_raw(p._value * 10)
    return m


def _params(m):
    return {n: p._value for n, p in m.named_parameters()}


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, V, size=(n,)).tolist()


def _ref_rows(m, text, first, mm=ref.mm_highest):
    """Reference logits at positions first.. of ``text``."""
    lg = ref.forward(_params(m), jnp.asarray(text), RCFG, mm,
                     q_block=len(text))
    return np.asarray(lg[first:])


def _forward(m, text):
    """The program's logits of a full causal pass, traced once (step by
    step the interpreted kernels are slow)."""
    return np.asarray(jax.jit(lambda ids: m(ids)._value)(
        jnp.asarray(text)[None])[0])


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

    @jax.jit    # traced once: the interpreted kernels are slow step by step
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


@pytest.fixture()
def telemetry():
    obs.enable()
    obs.reset()
    tracing.clear_spans()
    yield
    obs.disable()
    obs.reset()


# ------------------------------------------ the two forms of the recurrence

GATES = ["head", "channel"]


def _recurrence(q, k, v, g, beta, S0):
    """Token by token; ``g [T, H]`` a decay a head, ``[T, H, dk]`` one a key
    channel (a factor a column of ``S [H, dv, dk]``)."""
    def token(S, t):
        qt, kt, vt, gt, bt = t
        S = S * (jnp.exp(gt)[:, None, :] if gt.ndim == 2
                 else jnp.exp(gt)[:, None, None])
        u = bt[:, None] * (vt - jnp.sum(S * kt[:, None, :], axis=-1))
        S = S + u[:, :, None] * kt[:, None, :]
        return S, jnp.sum(S * qt[:, None, :], axis=-1)

    S, o = lax.scan(token, S0, (q, k, v, g, beta))
    return o, S


def _inputs(T, H, dk, dv, seed, gate):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    f32 = jnp.float32
    gshape = (T, H, dk) if gate == "channel" else (T, H)
    return (unit(jax.random.normal(ks[0], (T, H, dk), f32)) * f32(dk ** -0.5),
            unit(jax.random.normal(ks[1], (T, H, dk), f32)),
            jax.random.normal(ks[2], (T, H, dv), f32),
            -2.0 * jax.random.uniform(ks[3], gshape, f32),
            2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H), f32)),
            jax.random.normal(ks[5], (H, dv, dk), f32))


#: where a state is asked for among 100 tokens in chunks of 16
CUTS = {"at_0": (0,), "at_a_chunks_edge": (32,), "inside_a_chunk": (37,),
        "inside_the_last_chunk": (98,), "at_T": (100,),
        "two_cuts": (37, 96), "two_in_one_chunk": (33, 47)}


@pytest.mark.parametrize("gate", GATES)
class TestTwoFormsOfOneRecurrence:
    @pytest.mark.parametrize("T,chunk", [(1, 8), (7, 4), (64, 16), (100, 32),
                                         (130, 16)])
    @pytest.mark.parametrize("start", ["zero", "given"])
    def test_chunked_form_is_the_recurrence(self, gate, T, chunk, start):
        *x, S0 = _inputs(T, 4, 24, 48, T, gate)
        S0 = S0 if start == "given" else jnp.zeros_like(S0)
        o, S = gd.gdn_chunked(*x, S0, chunk)
        want_o, want_S = _recurrence(*x, S0)
        np.testing.assert_allclose(o, want_o, atol=2e-5)
        np.testing.assert_allclose(S, want_S, atol=2e-5)

    def test_a_steep_decay_overflows_nothing(self, gate):
        """30 nats a token: ``exp(-G_s)`` alone would leave float32 inside
        three tokens; every ratio taken is of a difference <= 0."""
        q, k, v, g, beta, S0 = _inputs(48, 2, 16, 16, 9, gate)
        g = 15.0 * g
        o, S = gd.gdn_chunked(q, k, v, g, beta, S0, 16)
        want_o, want_S = _recurrence(q, k, v, g, beta, S0)
        assert np.isfinite(np.asarray(o)).all()
        np.testing.assert_allclose(o, want_o, atol=2e-5)
        np.testing.assert_allclose(S, want_S, atol=2e-5)

    def test_padding_behind_the_last_token_moves_nothing(self, gate):
        """``b = 0, g = 0`` is how padding is passed."""
        q, k, v, g, beta, S0 = _inputs(40, 4, 8, 16, 3, gate)
        real = jnp.arange(40) < 29
        _, S = gd.gdn_chunked(
            q, k, v, jnp.where(real.reshape((40,) + (1,) * (g.ndim - 1)), g,
                               0.0),
            jnp.where(real[:, None], beta, 0.0), S0, 8)
        _, want = _recurrence(q[:29], k[:29], v[:29], g[:29], beta[:29], S0)
        np.testing.assert_allclose(S, want, atol=2e-5)

    @pytest.mark.parametrize("cuts", CUTS.values(), ids=CUTS)
    def test_state_at_a_cut_is_the_recurrence_stopped_there(self, gate, cuts):
        """100 tokens in chunks of 16 (the last holds 4 real ones), the cuts
        run-time values: the state handed out at each is what the token-by-
        token recurrence holds after the tokens before it; the outputs and
        the end state are what they are without cuts."""
        *x, S0 = _inputs(100, 4, 24, 48, 7, gate)
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

    def test_a_cut_in_the_padding_is_the_end(self, gate):
        """Padding behind ``lengths`` (b = 0, g = 0) moves nothing: a cut
        inside it, or past it, hands out the state after the last real
        token; one before it the state there. Under a steep decay too (30
        nats a token): every exponent at a cut is a difference <= 0."""
        q, k, v, g, beta, S0 = _inputs(40, 4, 8, 16, 3, gate)
        g = 15.0 * g
        real = jnp.arange(40) < 29
        _, S, at = gd.gdn_chunked(
            q, k, v, jnp.where(real.reshape((40,) + (1,) * (g.ndim - 1)), g,
                               0.0),
            jnp.where(real[:, None], beta, 0.0), S0, 8,
            jnp.asarray([21, 29, 35, 40], jnp.int32))
        assert np.isfinite(np.asarray(at)).all()
        for c, got in zip((21, 29, 29, 29), at):
            _, want = _recurrence(q[:c], k[:c], v[:c], g[:c], beta[:c], S0)
            np.testing.assert_allclose(got, want, atol=2e-5)
        np.testing.assert_allclose(S, at[-1], atol=2e-5)

    @pytest.mark.parametrize("cuts", [(8, 24), (0, 29), (13, 35)])
    def test_layer_hands_out_state_and_tail_at_the_cuts(self, gate, cuts):
        """``decoder.gated_delta`` over 40 tokens of which 29 are real: at
        each cut the packed state and the convolution's tail are those the
        same layer ends with over the tokens before the cut alone (a cut in
        the padding: over the real ones), and the tail is the last ``Kc -
        1`` inputs of the convolution before it, zeros before the first."""
        model = _model(linear_gate=gate)
        p, pre = _params(model), "layers.1.attn"
        h = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 32), jnp.float32)
        layer = lambda h, **kw: dec.gated_delta(
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
    @pytest.mark.parametrize("H,dk,dv", [(4, 8, 16), (2, 128, 128),
                                         (3, 8, 16)])
    def test_recurrent_step_in_place_on_the_packed_state(self, gate, impl, H,
                                                         dk, dv):
        """One token a slot on rows [0, B) of the packed buffer (the Pallas
        kernel interpreted here), the rows behind (snapshots) untouched.
        (2, 128, 128): this model's head, whole lane rows unpacked."""
        B = 3
        q, k, v, g, beta, S0 = _inputs(B, H, dk, dv, H, gate)
        S0 = jnp.stack([S0 * (i + 1) for i in range(B)])
        state = jnp.concatenate([gd.pack_state(S0), jnp.full(
            (2,) + gd.packed_shape(H, dk, dv), 7.0)])
        assert gd.packed_shape(64, 128, 128) == (64, 128, 128)
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

def mm_bf16(a, b):
    """The reference's matmul with its operands rounded to bfloat16."""
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


class TestAgainstReference:
    def test_full_forward(self, model):
        text = _ids(90)
        np.testing.assert_allclose(_forward(model, text),
                                   _ref_rows(model, text, 0), atol=TOL)

    @pytest.mark.parametrize("what", [
        "bfloat16", "scalar_decay", "no_shared_expert", "another_share",
        "no_output_gate"])
    def test_the_comparison_can_fail(self, model, what):
        """bfloat16 in float32's place, and a model that differs in one
        part of a layer, are each far outside TOL."""
        text = _ids(90)
        want = _ref_rows(model, text, 0)
        if what == "bfloat16":
            got = _ref_rows(model, text, 0, mm_bf16)
        else:
            over = {"scalar_decay": dict(linear_gate="head"),
                    "no_shared_expert": dict(shared_experts=0),
                    "another_share": dict(experts_held=(HELD, 8)),
                    "no_output_gate": dict(attn_output_gate=False)}[what]
            other = _model(**over)
            mine = _params(model)
            for n, p in other.named_parameters():   # every weight they share
                if n in mine and mine[n].shape == p._value.shape:
                    p._set_value_raw(mine[n])
            got = _forward(other, text)
        assert np.abs(got - want).max() > 50 * TOL

    @pytest.mark.parametrize("impl", ["oracle", "pallas"])
    def test_prefill_then_decode_through_the_engines_pools(self, model, impl):
        """A 45-token prompt admitted by the engine (prefill, the snapshot,
        the tail), then 20 decode steps over the engine's pools: every
        position's logits are the reference's full forward."""
        text = _ids(65, seed=2)
        with use_paged_attention_impl(impl):
            eng = _engine(model)
            _, got = _serve_logits(eng, text[:45], text[45:])
        np.testing.assert_allclose(got, _ref_rows(model, text, 44), atol=TOL)

    def test_restore_and_extend_is_the_cold_prompt(self, model, telemetry):
        """A prompt served after a prefix hit (the branch snapshot restored,
        the rest extended) gives the logits of the same prompt served cold,
        at the prompt's end and through 12 decode steps; both are the
        reference's."""
        shared, tail = _ids(40, seed=5), _ids(21, seed=8)
        text = shared + _ids(9, seed=7) + tail
        warm = _engine(model)
        warm.generate([shared + _ids(17, seed=6), shared + _ids(5, seed=9)],
                      SamplingParams(max_new_tokens=3))
        req, hit = _serve_logits(warm, text[:49], text[49:])
        assert req.prefix_hit_blocks == 5
        restores = [e for e in tracing.spans()
                    if e["name"].startswith("serving/admit/restore")]
        assert restores and restores[-1]["attrs"]["blocks"] == 5
        _, cold = _serve_logits(_engine(model), text[:49], text[49:])
        np.testing.assert_allclose(hit, cold, atol=TOL)
        np.testing.assert_allclose(hit, _ref_rows(model, text, 48), atol=TOL)

    def test_engine_emits_the_references_greedy_tokens(self, model):
        """Through ``generate``, single-turn requests over one shared
        prefix (this model's traffic)."""
        eng = _engine(model)
        shared = _ids(24, seed=11)
        for turn in range(3):
            prompt = shared + _ids(5 + 4 * turn, seed=20 + turn)
            out = eng.generate([prompt], SamplingParams(max_new_tokens=7))[0]
            rows = _ref_rows(model, prompt + out[:-1], len(prompt) - 1)
            assert rows.argmax(-1).tolist() == out

    def test_gated_gqa_layer_alone(self, model):
        """The full layer's mixer (64 -> here 4 query heads on 2 key/value
        heads, no positions, no QK-norm, sigmoid output gate) against the
        reference's, from the same normed input."""
        cfg, p = model.cfg, _params(model)
        h = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 32), jnp.float32)
        got, _ = dec.attention(cfg, p, "layers.0.attn", h,
                               jnp.zeros((1,), jnp.int32))
        lp = {k[len("layers.0."):]: v for k, v in p.items()
              if k.startswith("layers.0.")}
        want = ref.full_attention(None, h[0], lp, RCFG, ref.mm_highest, 40)
        np.testing.assert_allclose(got[0], want, atol=1e-5)
        assert "layers.0.attn.wg" in p and "layers.0.attn.q_norm.weight" not in p


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


def _state_rows(eng, row):
    """Row ``row`` of every state buffer, over the layers that keep one."""
    return [np.asarray(buf[row]) for pool in
            eng.cache.pools[len(eng.cache.pool_specs):] for buf in pool]


def _scenario(eng, name):
    """Serve what comes before, then return the prompt whose admission is
    under test and what its span has to say: (prompt, kind of program,
    hit_blocks, snapshot_blocks, blocks snapshotted). This model's traffic:
    single-turn requests over one shared prefix."""
    shared = _ids(20, seed=1)                       # 2 whole blocks + 4
    serve = lambda ps: eng.generate(ps, SamplingParams(max_new_tokens=4))
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
        taken = [e["attrs"]["blocks"] for e in tracing.spans()
                 if e["name"].startswith("serving/snapshot{")
                 and e["attrs"]["request_id"] == a["request_id"]]
        assert taken == snaps
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


# ------------------------------------------------ the share of the experts

def _layer_inputs(N=40, seed=4):
    return jax.random.normal(jax.random.PRNGKey(seed), (N, 32), jnp.float32)


def _whole_layer_weights(seed=6):
    """The uncut layer: all E experts, the router, the shared expert."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda k, *s: 0.2 * jax.random.normal(k, s, jnp.float32)
    return {"ffn.router": n(ks[0], 32, E), "ffn.w1": n(ks[1], E, 32, 16),
            "ffn.w3": n(ks[2], E, 32, 16), "ffn.w2": n(ks[3], E, 16, 32),
            "ffn.shared.w1": n(ks[4], 32, 16),
            "ffn.shared.w3": n(ks[5], 32, 16),
            "ffn.shared.w2": n(ks[6], 16, 32)}


def _share_of(w, i):
    """Share ``i``'s weights: its experts' slices, everything else whole."""
    sl = slice(i * HELD, (i + 1) * HELD)
    return {k: (v[sl] if k in ("ffn.w1", "ffn.w3", "ffn.w2") else v)
            for k, v in w.items()}


def _share_cfg(i):
    return DecoderConfig(**{**SIZES, "experts_held": (HELD, i * HELD)})


def _routed(i, g, w):
    """Share ``i``'s routed part of the layer ``w`` over ``g``, its counts."""
    return dec.moe_routed(
        _share_cfg(i), {"l." + k: v for k, v in _share_of(w, i).items()},
        "l.ffn", g)


class TestSumOfShares:
    @pytest.mark.parametrize("share", range(SHARES))
    def test_a_shares_routed_part_is_the_references(self, share):
        """Each of the shares: the program's routed part (sorted rows, the
        grouped matmul over the held experts) is the reference's masked sum
        over the same experts, and counts what it did."""
        g, w = _layer_inputs(), _whole_layer_weights()
        y, stats = _routed(share, g, w)
        rc = {**RCFG, "experts_held": (HELD, share * HELD)}
        want = ref.routed_experts(g, _share_of(w, share), rc, ref.mm_highest)
        np.testing.assert_allclose(y, want, atol=1e-5)
        _, e = dec.softmax_topk(_share_cfg(share), g, w["ffn.router"])
        mine = (e >= share * HELD) & (e < (share + 1) * HELD)
        per = np.bincount(np.asarray(e[mine]) - share * HELD, minlength=HELD)
        assert stats.tolist() == [int((per > 0).sum()), int(per.max()),
                                  int(mine.sum()), g.shape[0] * 4]

    def test_shares_and_the_shared_expert_once_are_the_uncut_layer(self):
        """The routed parts that all the shares give, plus the shared
        expert counted ONCE, are what the uncut layer (all E experts held
        by one program, and by the reference) gives."""
        g, w = _layer_inputs(), _whole_layer_weights()
        parts = [_routed(i, g, w)[0] for i in range(SHARES)]
        routed = sum(parts)
        shared = ref.shared_expert(g, w, ref.mm_highest)
        whole_cfg = DecoderConfig(**{**SIZES, "experts_held": None})
        whole, stats = dec.moe_swiglu(
            whole_cfg, {"l." + k: v for k, v in w.items()}, "l.ffn", g)
        np.testing.assert_allclose(routed + shared, whole, atol=1e-5)
        rc = {**RCFG, "experts_held": (E, 0)}
        want = ref.routed_experts(g, w, rc, ref.mm_highest) + shared
        np.testing.assert_allclose(whole, want, atol=1e-5)
        assert stats.shape == (2,)      # an uncut layer counts as it did
        # and no share's part is nothing
        assert all(float(jnp.abs(y).max()) > 1e-3 for y in parts)

    @pytest.mark.parametrize("share", [0, SHARES - 1])
    def test_rows_of_absent_experts_cost_no_tile(self, share):
        """Counted: the tiles the grouped matmul visits are those of the
        held experts' own rows, each run padded to whole tiles; the rows
        routed elsewhere (seven eighths of them) are in none."""
        g, w = _layer_inputs(64), _whole_layer_weights()
        _, e = dec.softmax_topk(_share_cfg(share), g, w["ffn.router"])
        mine = (e >= share * HELD) & (e < (share + 1) * HELD)
        ids = jnp.where(mine, e - share * HELD, HELD).reshape(-1)
        tm = 8
        src, dest, tile_group, n_tiles, counts = gm.plan_groups(ids, HELD, tm)
        per = np.bincount(np.asarray(e[mine]) - share * HELD, minlength=HELD)
        assert counts.tolist() == per.tolist()
        assert int(n_tiles) == int(np.ceil(per / tm).sum())
        assert int(n_tiles) * tm < ids.shape[0]     # fewer than the rows routed
        # every live padded row holds a row of a held expert, each once
        live = np.asarray(src)[:int(n_tiles) * tm]
        held_rows = np.flatnonzero(np.asarray(mine).reshape(-1))
        assert sorted(set(live.tolist()) - {0}) == sorted(
            set(held_rows.tolist()) - {0})
        d = np.asarray(dest)[held_rows]
        assert len(set(d.tolist())) == len(held_rows) and d.max() < int(
            n_tiles) * tm
        assert (np.asarray(tile_group)[:int(n_tiles)] < HELD).all()

    def test_description_of_a_share(self):
        shapes = param_shapes(DecoderConfig(**SIZES))
        assert shapes["layers.0.ffn.router"] == (32, E)       # all of them
        assert shapes["layers.0.ffn.w1"] == (HELD, 32, 16)    # the held
        assert shapes["layers.3.ffn.shared.w2"] == (16, 32)
        for bad in ((0, 0), (5, 28), (4, -1)):
            with pytest.raises(ValueError, match="experts_held"):
                DecoderConfig(**{**SIZES, "experts_held": bad})


# -------------------------------------- the description and the engine

class TestDescription:
    def test_pools_and_leaves(self, model):
        assert model.cache_pools() == [("k", 2, 8, (0,)), ("v", 2, 8, (0,))]
        assert [(n, s, l) for n, s, _, l in model.state_pools()] == [
            ("gdn_state", (4, 8, 8), (1, 2, 3)),
            ("gdn_conv", (3, 96), (1, 2, 3))]
        shapes = param_shapes(model.cfg)
        pre = "layers.1.attn."
        assert shapes[pre + "wf_a"] == shapes[pre + "wg_a"] == (32, 8)
        assert shapes[pre + "wf_b"] == shapes[pre + "wg_b"] == (8, 32)
        assert shapes[pre + "A_log"] == (4,)              # a head
        assert shapes[pre + "dt_bias"] == (32,)           # a key channel
        assert pre + "wa" not in shapes and pre + "wg" not in shapes
        dt = jax.nn.softplus(initial_value(pre + "dt_bias", (4096,),
                                           jax.random.PRNGKey(0), 0.02))
        assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 0.1 + 1e-6
        with pytest.raises(ValueError, match="linear_gate"):
            DecoderConfig(**{**SIZES, "linear_gate": "matrix"})

    def test_decode_span_counts_the_rows_on_held_experts(self, model,
                                                         telemetry):
        """``serving/decode`` carries, a layer, the held experts with a
        row, the busiest one's rows, the rows on held experts and the rows
        routed; eight slots are served at once with snapshots on the
        trie."""
        eng = _engine(model, max_batch_size=8, state_snapshots=6)
        shared = _ids(16, seed=1)
        eng.generate([shared + _ids(3 + i, seed=30 + i) for i in range(8)],
                     SamplingParams(max_new_tokens=5))
        assert model.step_stats == ("experts_touched", "expert_max_load",
                                    "local_rows", "routed_rows")
        steps = [e["attrs"] for e in tracing.spans()
                 if e["name"] == "serving/decode"
                 and "local_rows" in e["attrs"]]
        assert steps
        for a in steps:
            assert len(a["local_rows"]) == 4
            assert a["routed_rows"] == [8 * 4] * 4
            for t, m, loc in zip(a["experts_touched"], a["expert_max_load"],
                                 a["local_rows"]):
                assert 0 <= t <= HELD and m <= loc <= 8 * 4
                assert (loc == 0) == (t == 0) and loc <= t * m
        assert eng.snapshot_alloc.num_allocated > 0
