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
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

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


def _top_k_filter(logits, k):
    """Keep each row's k largest logits, -inf the rest. ``k`` int scalar
    (static) — k <= 0 or >= vocab is a no-op."""
    V = logits.shape[-1]
    k_eff = min(int(k), V)
    if k_eff <= 0 or k_eff >= V:
        return logits
    kth = jnp.sort(logits, axis=-1)[..., -k_eff][..., None]
    return jnp.where(logits < kth, _NEG_INF, logits)


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
    decode step serves any mix of greedy and sampled requests.
    """
    V = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    scaled = lf / jnp.maximum(temperatures.astype(jnp.float32), 1e-6)[:, None]
    # per-row top-k via the k-th order statistic: row b keeps values >= the
    # (top_ks[b])-th largest. top_ks <= 0 disables the filter for that row.
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]  # [B, V] descending
    k_idx = jnp.clip(top_ks.astype(jnp.int32) - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)  # [B, 1]
    filter_on = (top_ks > 0) & (top_ks < V)
    filtered = jnp.where(filter_on[:, None] & (scaled < kth), _NEG_INF, scaled)
    sampled = jax.random.categorical(key, filtered, axis=-1)
    return jnp.where(greedy, jnp.argmax(lf, axis=-1), sampled)
