"""Per-request sampling for the serving engine.

Two faces over the same math (temperature scale -> top-k filter ->
categorical draw, or plain argmax):

- ``sample_static``: scalar parameters baked into the compiled generate()
  decode step — replicates GPTForCausalLM.generate's original greedy /
  temperature / top-k semantics exactly.
- ``sample_batched``: fully vectorized over the batch with PER-ROW
  parameter arrays, so one compiled decode step serves a continuously
  batched slot set where every request carries its own SamplingParams —
  no recompile when the request mix changes.

What a step of ``sample_batched`` costs follows what its rows asked for,
read on the device from the operands: a step whose rows are all greedy runs
one argmax over ``[B, V]`` and nothing else; a step with a sampling row adds
the temperature divide and the categorical draw (V random numbers a row),
and only where a sampling row has a top-k does it add that row's k-th value,
32 compare-and-count passes over ``[B, V]`` (``kernels/order_stat.py``),
never a sort. The tokens are the same for the same key whichever branch
runs.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.order_stat import bits_to_float, kth_largest, ordered_bits

_NEG_INF = -1e30  # a plain float: a jnp constant here would initialize the backend at import


@dataclass
class SamplingParams:
    """Per-request decoding controls (vLLM SamplingParams analog, reduced to
    the knobs GPTForCausalLM.generate already exposed)."""

    max_new_tokens: int = 16
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0            # 0 = no top-k filter
    eos_token_id: Optional[int] = None

    def __post_init__(self):
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


def _kth_value(x, k):
    """[..., 1] the k-th largest of each row of ``x [..., V]`` (``k`` an int
    or an int array of the rows' shape, within 1..V), as float32: the value
    a descending sort holds at ``k - 1``, found without sorting."""
    return bits_to_float(kth_largest(ordered_bits(x, True), k))[..., None]


def _top_k_filter(logits, k):
    """Keep each row's k largest logits, -inf the rest. ``k`` int scalar
    (static) — k <= 0 or >= vocab is a no-op."""
    V = logits.shape[-1]
    k_eff = min(int(k), V)
    if k_eff <= 0 or k_eff >= V:
        return logits
    return jnp.where(logits < _kth_value(logits, k_eff), _NEG_INF, logits)


def sample_static(logits, key, *, do_sample: bool, temperature: float,
                  top_k: int):
    """[B, V] logits -> [B] token ids with call-wide scalar params (the
    generate() path; params are part of the compile key)."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits.astype(jnp.float32)
    logits = logits / jnp.maximum(jnp.float32(temperature), 1e-6)
    logits = _top_k_filter(logits, top_k)
    return jax.random.categorical(key, logits, axis=-1)


def sample_batched(logits, key, temperatures, top_ks, greedy):
    """[B, V] logits -> [B] token ids with per-row parameter ARRAYS.

    ``temperatures`` [B] f32, ``top_ks`` [B] int32 (0 = off), ``greedy`` [B]
    bool. All three ride as device arrays, so the engine's single compiled
    decode step serves any mix of greedy and sampled requests, and the work
    follows them (module docstring): the draw runs only on a step with a row
    that is not greedy, the k-th value only where such a row has a top-k.
    """
    B, V = logits.shape
    best = jnp.argmax(logits.astype(jnp.float32), axis=-1)
    draws = ~greedy

    def draw():
        # the float32 copy of the logits is made here, under the condition
        scaled = logits.astype(jnp.float32) / jnp.maximum(
            temperatures.astype(jnp.float32), 1e-6)[:, None]
        # per-row top-k via the k-th order statistic: row b keeps values >=
        # the (top_ks[b])-th largest. top_ks <= 0 disables the filter for
        # that row; a greedy row's filter is skipped with its draw.
        filter_on = (top_ks > 0) & (top_ks < V) & draws
        kth = lax.cond(
            jnp.any(filter_on),
            lambda: _kth_value(scaled, jnp.clip(top_ks.astype(jnp.int32),
                                                1, V)),
            lambda: jnp.zeros((B, 1), jnp.float32))
        filtered = jnp.where(filter_on[:, None] & (scaled < kth), _NEG_INF,
                             scaled)
        return jax.random.categorical(key, filtered, axis=-1)

    sampled = lax.cond(jnp.any(draws), draw, lambda: best)
    return jnp.where(greedy, best, sampled)
