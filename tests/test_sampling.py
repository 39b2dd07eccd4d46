"""The serving sampler does only the work its rows asked for (ISSUE 39).

``sample_batched`` and ``_top_k_filter`` against the bodies they replaced
(kept here as the oracles: a whole-vocabulary sort, every piece run whatever
the rows say), token for token on the same keys; the lowered decode and
verify programs hold a conditional and no sort; the engine says on its
``serving/decode`` span and in its counters which steps drew.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams, sampling

_NEG_INF = -1e30


# ------------------------------------------------- the parent's bodies

def _old_top_k_filter(logits, k):
    V = logits.shape[-1]
    k_eff = min(int(k), V)
    if k_eff <= 0 or k_eff >= V:
        return logits
    kth = jnp.sort(logits, axis=-1)[..., -k_eff][..., None]
    return jnp.where(logits < kth, _NEG_INF, logits)


def _old_sample_batched(logits, key, temperatures, top_ks, greedy):
    V = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    scaled = lf / jnp.maximum(temperatures.astype(jnp.float32), 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_ks.astype(jnp.int32) - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    filter_on = (top_ks > 0) & (top_ks < V)
    filtered = jnp.where(filter_on[:, None] & (scaled < kth), _NEG_INF, scaled)
    sampled = jax.random.categorical(key, filtered, axis=-1)
    return jnp.where(greedy, jnp.argmax(lf, axis=-1), sampled)


# ------------------------------------------------------------ inputs

def _logits(B, V, dtype, seed):
    """Rows with ties (at every threshold the grid's k values cut), both
    zeros, and entries at the filter's own -1e30."""
    rng = np.random.default_rng(seed)
    x = (3.0 * rng.standard_normal((B, V))).astype(np.float32)
    x[:, :64] = np.round(x[:, :64])       # many equal values near the top
    x[:, 64:72] = 0.0
    x[:, 72:80] = -0.0
    x[:, 80:88] = _NEG_INF
    x[:, 88:96] = x.max(axis=-1, keepdims=True)   # the top itself is tied
    x = np.take_along_axis(x, rng.permuted(
        np.broadcast_to(np.arange(V), (B, V)), axis=-1), axis=-1)
    return jnp.asarray(x).astype(dtype)


def _ks(V):
    return [0, 1, 5, V - 1, V, V + 3]


GREEDY = {"all_greedy": lambda rng, B: np.ones(B, bool),
          "none_greedy": lambda rng, B: np.zeros(B, bool),
          "mixed": lambda rng, B: np.arange(B) % 3 == 0}


@pytest.mark.parametrize("V", [1000, 1024])
@pytest.mark.parametrize("B", [1, 32])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", list(GREEDY))
def test_sample_batched_equals_the_sorting_body(mode, dtype, B, V):
    rng = np.random.default_rng(B * V)
    logits = _logits(B, V, dtype, seed=V + B)
    temps = jnp.asarray(rng.uniform(0.3, 1.6, B).astype(np.float32))
    greedy = jnp.asarray(GREEDY[mode](rng, B))
    new, old = jax.jit(sampling.sample_batched), jax.jit(_old_sample_batched)
    ks = _ks(V)
    # every k of the grid in ONE batch (B = 32), the batch's rows rotated so
    # that each k meets greedy and drawing rows; one k a call at B = 1
    for shift in range(len(ks)):
        top_ks = jnp.asarray([ks[(b + shift) % len(ks)] for b in range(B)],
                             jnp.int32)
        for s in range(3):
            key = jax.random.PRNGKey(100 * shift + s)
            got = np.asarray(new(logits, key, temps, top_ks, greedy))
            want = np.asarray(old(logits, key, temps, top_ks, greedy))
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("V", [1000, 1024])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("i", range(6), ids=["0", "1", "5", "V-1", "V", "V+3"])
def test_top_k_filter_equals_the_sorting_body(i, dtype, V):
    k = _ks(V)[i]
    logits = _logits(4, V, dtype, seed=7)
    got = sampling._top_k_filter(logits, k)
    want = _old_top_k_filter(logits, k)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


def test_kth_value_keeps_a_zero_of_either_sign_a_threshold():
    """The count finds a key; the filter compares FLOATS, to which -0.0 and
    +0.0 are one value as they were to the sort's comparator."""
    x = jnp.asarray([[3.0, -0.0, 0.0, -1.0], [0.0, -0.0, -2.0, 5.0]])
    for k in (2, 3):
        kth = sampling._kth_value(x, k)
        assert np.asarray(kth == 0.0).all()
        np.testing.assert_array_equal(
            np.asarray(sampling._top_k_filter(x, k)),
            np.asarray(_old_top_k_filter(x, k)))


def test_no_sort_left_in_the_sampler():
    import inspect

    source = inspect.getsource(sampling)
    assert "jnp.sort" not in source and "lax.sort" not in source


# ------------------------------------------------- the engine's programs

def _tiny():
    paddle.seed(0)
    m = gpt_tiny(dropout=0.0, num_layers=2)
    m.eval()
    return m


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_program_holds_the_conditional_and_no_sort(kind):
    eng = Engine(_tiny(), EngineConfig(max_batch_size=2, max_seq_len=32))
    fn, args = eng.decode_program() if kind == "decode" \
        else eng.verify_program(k=3)
    text = jax.jit(fn).lower(*args).as_text()
    assert ".sort" not in text and "top_k" not in text   # as ops
    assert "stablehlo.case" in text or "stablehlo.if" in text


@pytest.fixture
def telemetry():
    obs.enable()
    obs.reset()
    obs.clear_spans()
    yield obs
    obs.disable()
    obs.reset()
    obs.clear_spans()


def _decode_draws():
    return [e["attrs"]["draws"] for e in obs.spans()
            if e["name"] == "serving/decode" and "draws" in e["attrs"]]


def test_greedy_run_never_draws_and_a_mixed_run_does(telemetry):
    eng = Engine(_tiny(), EngineConfig(max_batch_size=4, max_seq_len=32))
    prompts = [[5, 17, 3], [9, 2, 4], [8, 1, 6]]
    eng.generate(prompts, SamplingParams(max_new_tokens=5))
    draws = _decode_draws()
    assert draws and set(draws) == {0}
    assert eng.sampler_steps_draw == 0
    assert eng.sampler_steps_argmax == len(draws)

    obs.clear_spans()
    paddle.seed(7)
    # the sampled request ends first: the steps after it are argmax steps
    # again, although its slot stays in the batch (a dead slot reads greedy)
    eng.generate(prompts, [
        SamplingParams(max_new_tokens=6),
        SamplingParams(max_new_tokens=3, do_sample=True, temperature=0.7,
                       top_k=5),
        SamplingParams(max_new_tokens=6)])
    mixed = _decode_draws()
    assert set(mixed) == {0, 1}
    assert mixed[-1] == 0
    assert eng.sampler_steps_draw == sum(d > 0 for d in mixed)
    assert eng.sampler_steps_argmax == len(draws) + sum(d == 0 for d in mixed)
    c = obs.snapshot()["counters"]
    assert c["jit.compile.cache_miss{site=serving.decode}"] == 1


def test_counters_run_with_tracing_off():
    obs.disable()
    eng = Engine(_tiny(), EngineConfig(max_batch_size=2, max_seq_len=32))
    eng.generate([[5, 17, 3]], SamplingParams(max_new_tokens=4,
                                              do_sample=True, top_k=3))
    assert eng.sampler_steps_draw == 3 and eng.sampler_steps_argmax == 0
    assert eng._greedy.all()     # the finished slot went back to greedy
