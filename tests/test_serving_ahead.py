"""The plain decode step is launched one step AHEAD of its fetch (ISSUE 43):
``Engine.step()`` k launches decode program D_k and only then fetches and
settles D_{k-1}.

What is held here, on the CPU at tiny sizes, for the GPT engine and one
``DecoderLM`` engine each of the stateful (gated delta-rule layers), latent
(MLA) and window (sliding layers on a page group of their own) kinds:

(a) tokens: under staggered admissions and finishes every stream is, token
    for token, the one the request gets when it is served ALONE, and it is
    the model's own: ``cached_generate``'s for the GPT, within the family's
    tolerance of the best logit of the model's plain forward (no cache, no
    pages, no engine) for a ``DecoderLM``;
(b) the order itself, from the spans;
(c) an ``eos`` in mid-batch: the row runs once more and is dropped, and the
    request admitted into the same slot and pages right behind it is served
    as if nothing had been there;
(d) a pool with no free page at a request's last token: ``length``, not
    ``cache_full``; and a pool that is short while a step is in flight
    settles that step first;
(e) the engine empties by count: no program is launched with no live row;
(f) under speculation, and after ``generate()``, nothing is in flight;
(g) a sampled request draws from the key sequence it drew from alone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import test_hybrid_serving as stateful_kind
import test_latent_serving as latent_kind
import test_window_serving as window_kind
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

FAMILIES = ["gpt", "stateful", "latent", "window"]


def _gpt():
    paddle.seed(0)
    m = gpt_tiny(dropout=0.0, num_layers=2)
    m.eval()
    return m


#: family -> (the model, the engine's envelope, the widest gap allowed of a
#: served token's logit below the plain forward's best: the family's own
#: tolerance, its tests' docstrings say why; None: token equality with
#: ``cached_generate``)
KINDS = {
    "gpt": (_gpt, dict(max_seq_len=64, page_size=8,
                       prefill_buckets=(16, 64)), None),
    "stateful": (stateful_kind._model, dict(
        max_seq_len=96, page_size=stateful_kind.PS,
        prefill_buckets=(8, 16, 32, 64, 96)), stateful_kind.TOL),
    "latent": (latent_kind._model, dict(
        max_seq_len=96, page_size=latent_kind.PS,
        prefill_buckets=(8, 16, 32, 64, 96)), latent_kind.TOL),
    "window": (lambda: window_kind._model(window_kind._cfg()), dict(
        max_seq_len=128, page_size=window_kind.PS,
        prefill_buckets=(8, 16, 32, 64, 128),
        group_pages={"window": 40}), window_kind.TOL),
}
_MODELS = {}


def _model(family):
    if family not in _MODELS:
        _MODELS[family] = KINDS[family][0]()
    return _MODELS[family]


def _engine(family, **over):
    conf = dict(max_batch_size=3, prefix_cache=True)
    conf.update(KINDS[family][1])
    conf.update(over)
    return Engine(_model(family), EngineConfig(**conf))


def _ids(n, seed):
    return np.random.RandomState(seed).randint(1, 60, size=(n,)).tolist()


def _alone(family, prompts, params):
    """Each request served by itself, one after the other, in one engine:
    no other row beside it, no admission or finish while it runs."""
    eng = _engine(family)
    return [eng.generate([p], sp)[0] for p, sp in zip(prompts, params)]


def _is_the_models_own(family, prompt, out):
    """The stream against the model WITHOUT the engine."""
    m, tol = _model(family), KINDS[family][2]
    if tol is None:
        ref = np.asarray(m.generate(paddle.to_tensor(
            np.asarray([prompt], np.int32)), max_new_tokens=len(out))._value)
        return out == ref[0, len(prompt):].tolist()
    text = jnp.asarray(list(prompt) + list(out[:-1]))[None]
    lg = np.asarray(jax.jit(lambda ids: m(ids)._value)(text))[
        0, len(prompt) - 1:]
    return float(np.max(lg.max(-1) - lg[np.arange(len(out)), out])) <= tol


@pytest.fixture
def telemetry():
    obs.enable()
    obs.reset()
    obs.clear_spans()
    yield obs
    obs.disable()
    obs.reset()
    obs.clear_spans()


def _spans(name):
    return [e for e in obs.spans() if e["name"] == name]


# ------------------------------------------------------------- (a) tokens

@pytest.mark.parametrize("family", FAMILIES)
def test_streams_under_staggered_admissions_are_those_of_serving_alone(
        family):
    """Six requests through three slots: two more arrive in mid-run, answers
    of 3 to 14 tokens, so slots free and fill at different steps and most
    launches go out with rows that another request held a step before."""
    first = _ids(21, seed=1)
    prompts = [first, _ids(9, seed=2), _ids(30, seed=3),
               first[:16] + _ids(5, seed=4),    # a prefix hit
               _ids(12, seed=5), _ids(17, seed=6)]
    params = [SamplingParams(max_new_tokens=n) for n in (9, 3, 14, 6, 4, 11)]
    want = _alone(family, prompts, params)
    eng = _engine(family)
    reqs = [eng.add_request(p, sp) for p, sp in zip(prompts[:4], params)]
    for _ in range(4):
        eng.step()
    reqs += [eng.add_request(p, sp)
             for p, sp in zip(prompts[4:], params[4:])]
    while eng.has_unfinished:
        eng.step()
    assert [r.output_ids for r in reqs] == want
    assert [r.finish_reason for r in reqs] == ["length"] * 6
    assert [len(r.output_ids) for r in reqs] == [9, 3, 14, 6, 4, 11]
    for prompt, out in zip(prompts, want):
        assert _is_the_models_own(family, prompt, out)
    # the mechanism engaged, no row was run for nothing (every finish was
    # one the host could count), and nothing is left behind
    assert eng.steps_ahead > eng.steps_drained >= 1
    assert eng.dropped_rows == 0 and eng._flight is None
    assert eng.cache.free_slots == 3


# -------------------------------------------------------------- (b) order

def test_a_step_is_launched_before_the_step_before_is_fetched(telemetry):
    eng = _engine("gpt", max_batch_size=2)
    for seed, n in ((1, 7), (2, 4), (3, 5)):
        eng.add_request(_ids(10, seed), SamplingParams(max_new_tokens=n))
    while eng.has_unfinished:
        eng.step()
    disp, fetch = (_spans("serving/decode/" + leaf)
                   for leaf in ("dispatch", "fetch"))
    # every launched step is fetched once, in the order of the launches
    launches = [e["attrs"]["launch"] for e in disp]
    assert [e["attrs"]["waits_for"] for e in fetch] == launches
    numbered = sorted(e["attrs"]["launch"] for e in obs.spans()
                      if "launch" in e["attrs"])
    assert numbered == list(range(1, eng._launch_i + 1))    # contiguous
    # ``dispatch launch=n+1`` opens, and ends, before ``fetch waits_for=n``
    # opens, wherever a step was in flight at the launch: ``ahead`` = 1
    end = {e["attrs"]["waits_for"]: e["ts"] for e in fetch}
    ahead = 0
    for before, e in zip([None] + disp, disp):
        went_first = before is not None and \
            e["ts"] + e["dur"] <= end[before["attrs"]["launch"]]
        assert e["attrs"]["ahead"] == int(went_first)
        ahead += went_first
    assert ahead == eng.steps_ahead >= len(disp) - 2
    assert eng.steps_ahead + eng.steps_drained == len(disp)
    # what describes ONE step is on the span of the call that settles it:
    # a call that settles nothing says nothing about a step
    for dec in _spans("serving/decode"):
        kids = {e["name"] for e in obs.spans() if e["parent"] == dec["id"]}
        assert ("running" in dec["attrs"]) == (
            "serving/decode/settle" in kids) == ("draws" in dec["attrs"])


# ---------------------------------------------------------------- (c) eos

@pytest.mark.parametrize("family", FAMILIES)
def test_an_eos_in_mid_batch_is_dropped_and_its_slot_and_pages_reused(
        family, telemetry):
    prompts = [_ids(11, seed=s) for s in range(5, 13)]
    long = SamplingParams(max_new_tokens=12)
    outs = _alone(family, prompts, [long] * len(prompts))
    # the eos request: the first whose answer holds, from its third token
    # on, a token it has not held before: it ends THERE, which nobody can
    # count beforehand
    i, cut = next((i, j) for i, out in enumerate(outs)
                  for j, t in enumerate(out) if j >= 2 and t not in out[:j])
    a, b, c = prompts[i], prompts[(i + 1) % 8], prompts[(i + 2) % 8]
    want_b, want_c = outs[(i + 1) % 8], outs[(i + 2) % 8][:7]
    eng = _engine(family, max_batch_size=2)
    obs.reset()
    obs.clear_spans()
    ra = eng.add_request(a, SamplingParams(max_new_tokens=12,
                                           eos_token_id=outs[i][cut]))
    rb = eng.add_request(b, long)
    rc = eng.add_request(c, SamplingParams(max_new_tokens=7))
    eng.step()
    slot, pages = ra.slot, set(eng.cache.slot_pages(ra.slot))
    held = None
    while eng.has_unfinished:
        eng.step()
        if held is None and rc.slot is not None:
            held = set(eng.cache.slot_pages(rc.slot))
    assert (ra.output_ids, ra.finish_reason) == (outs[i][:cut + 1], "eos")
    assert (rb.output_ids, rc.output_ids) == (want_b, want_c)
    assert _is_the_models_own(family, c, rc.output_ids)
    # the new request stands where the ended one stood
    assert rc.slot == slot and held & pages
    # one row ran for a request that had finished: counted where it says
    assert eng.dropped_rows == 1
    assert sum(e["attrs"]["dropped"]
               for e in _spans("serving/decode/settle")) == 1
    assert obs.snapshot()["counters"]["serving.decode.dropped_rows"] == 1
    (drop,) = [e for e in _spans("serving/decode/settle")
               if e["attrs"]["dropped"]]
    by_id = {e["id"]: e for e in obs.spans()}
    # the dropped row was one of the two the step ran, and its step was
    # launched while the eos was still in flight
    assert by_id[drop["parent"]]["attrs"]["running"] == 2


# ----------------------------------------------------- (d) the pool's end

def test_no_free_page_at_the_last_token_ends_length():
    """One allocatable page of 8 tokens: the prompt takes positions 0-6,
    the second and last token's step writes position 7. The step after it
    would write position 8, on a page the pool does not have: it is never
    grown for, because the host counts that the request ends before."""
    eng = _engine("gpt", max_batch_size=2, kv_pages=2, prefix_cache=False)
    req = eng.add_request(_ids(7, seed=1), SamplingParams(max_new_tokens=2))
    while eng.has_unfinished:
        eng.step()
    assert (req.finish_reason, len(req.output_ids)) == ("length", 2)
    assert eng.page_alloc.num_allocated == 0


def test_an_ending_request_leaves_its_page_to_its_neighbour():
    """Two allocatable pages: each request's prompt takes one. The short
    one's last token is in flight when the long one crosses its page's end;
    the short one's page is released BEFORE that launch, and the long one
    runs on to its ``length``."""
    want = _alone("gpt", [_ids(6, seed=2)], [SamplingParams(max_new_tokens=8)])
    eng = _engine("gpt", max_batch_size=2, kv_pages=3, prefix_cache=False)
    short = eng.add_request(_ids(6, seed=1), SamplingParams(max_new_tokens=3))
    long = eng.add_request(_ids(6, seed=2), SamplingParams(max_new_tokens=8))
    while eng.has_unfinished:
        eng.step()
    assert (short.finish_reason, long.finish_reason) == ("length", "length")
    assert long.output_ids == want[0]
    assert eng.steps_drained == 1       # no launch had to wait for a settle


def test_a_short_pool_settles_the_step_in_flight_first(telemetry):
    """As above, but the short request ends on an ``eos`` the host cannot
    count: at the launch that needs the page, both rows want one and the
    pool has none. The step in flight is settled first (old order), the
    ``eos`` frees the page, and nobody ends ``cache_full``."""
    prompts = [_ids(6, seed=s) for s in range(1, 9)]
    alone = _alone("gpt", prompts, [SamplingParams(max_new_tokens=8)] * 8)
    # the eos request: one whose third token is new to its answer
    i = next(i for i, out in enumerate(alone) if out[2] not in out[:2])
    j = (i + 1) % 8
    eng = _engine("gpt", max_batch_size=2, kv_pages=3, prefix_cache=False)
    obs.clear_spans()
    short = eng.add_request(prompts[i], SamplingParams(
        max_new_tokens=8, eos_token_id=alone[i][2]))
    long = eng.add_request(prompts[j], SamplingParams(max_new_tokens=8))
    while eng.has_unfinished:
        eng.step()
    assert (short.finish_reason, long.finish_reason) == ("eos", "length")
    assert (short.output_ids, long.output_ids) == (alone[i][:3], alone[j])
    grow = [e["attrs"] for e in _spans("serving/decode/grow_pages")]
    assert sum(a.get("short", 0) for a in grow) == 1
    assert sum(a["cache_full"] for a in grow) == 0
    # the launch behind the early settle went out with nothing in flight
    assert eng.steps_drained == 2 and eng.dropped_rows == 0
    assert [e["attrs"]["ahead"]
            for e in _spans("serving/decode/dispatch")].count(0) == 2


# ------------------------------------------------- (e) emptying by count

def test_the_engine_empties_by_count_without_a_dead_step(telemetry):
    """An answer of n tokens is one prefill and n - 1 decode steps, in n
    calls: the last call launches nothing, because the host counted that
    the step in flight makes the last token."""
    eng = _engine("gpt")
    n = 6
    req = eng.add_request(_ids(10, seed=1), SamplingParams(max_new_tokens=n))
    calls = 0
    while eng.has_unfinished:
        eng.step()
        calls += 1
        assert req.num_generated == calls
    assert calls == n and req.finish_reason == "length"
    assert len(_spans("serving/decode/dispatch")) == n - 1
    assert eng._launch_i == 2 + (n - 1)     # prefill, its sample, the steps
    assert eng._flight is None and eng.dropped_rows == 0
    last = _spans("serving/decode")[-1]["attrs"]
    (done,) = [e["attrs"] for e in _spans("serving/decode/settle")][-1:]
    # what the harness's reader asks of the last step of a busy stretch
    assert last["running"] - done["finished"] == 0
    # a step on an engine that holds nothing launches nothing either
    eng.step()
    assert eng._launch_i == 2 + (n - 1)
    assert _spans("serving/decode")[-1]["attrs"]["running"] == 0


# ---------------------------------------------- (f) nothing left in flight

def test_a_speculative_engine_has_nothing_in_flight_between_calls():
    eng = _engine("gpt", speculative=2)
    plain = _engine("gpt")
    prompts = [_ids(12, seed=1), _ids(20, seed=2)]
    sp = SamplingParams(max_new_tokens=9)
    reqs = [eng.add_request(p, sp) for p in prompts]
    while eng.has_unfinished:
        eng.step()
        assert eng._flight is None
    assert (eng.steps_ahead, eng.dropped_rows) == (0, 0)
    assert eng.steps_drained > 0
    assert [r.output_ids for r in reqs] == plain.generate(prompts, sp)


def test_generate_leaves_nothing_in_flight_behind_an_eos():
    eng = _engine("gpt")
    prompt = _ids(10, seed=3)
    (out,) = eng.generate([prompt], SamplingParams(max_new_tokens=8))
    cut = next(j for j, t in enumerate(out) if j >= 1 and t not in out[:j])
    steps = eng._step_i
    (got,) = eng.generate([prompt], SamplingParams(
        max_new_tokens=8, eos_token_id=out[cut]))
    assert got == out[:cut + 1]
    # the step launched beside the eos was fetched and dropped by
    # ``generate`` itself: one call more than the tokens asked for
    assert eng._flight is None and eng.dropped_rows == 1
    assert eng._step_i - steps == cut + 2
    # driven by hand, the next call drops it
    req = eng.add_request(prompt, SamplingParams(
        max_new_tokens=8, eos_token_id=out[cut]))
    while eng.has_unfinished:
        eng.step()
    assert eng._flight is not None and req.finish_reason == "eos"
    eng.step()
    assert eng._flight is None and eng.dropped_rows == 2


# ------------------------------------------------------- (g) sampled rows

def test_a_sampled_request_draws_what_it_drew_alone():
    """One key a step with a sampled row, as before: a row released by
    count is no draw, so the keys a request's draws take do not depend on
    who ends beside it. Same slot, same batch width: same stream."""
    sampled = SamplingParams(max_new_tokens=10, do_sample=True,
                             temperature=0.9, top_k=8)
    prompt = _ids(9, seed=8)
    paddle.seed(11)
    (want,) = _engine("gpt").generate([prompt], sampled)
    paddle.seed(11)
    eng = _engine("gpt")
    req = eng.add_request(prompt, sampled)
    others = [eng.add_request(_ids(8, seed=s), SamplingParams(
        max_new_tokens=n)) for s, n in ((1, 3), (2, 6))]
    while eng.has_unfinished:
        eng.step()
    assert req.slot == 0 and req.output_ids == want
    assert [r.finish_reason for r in others] == ["length", "length"]
    assert eng.sampler_steps_draw == 9 and eng.dropped_rows == 0
