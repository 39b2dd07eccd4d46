"""TPU-native LLM serving: static-shape KV-cache decode + continuous batching.

Public surface:

- :class:`Engine` / :class:`EngineConfig` — offline/online serving engine
  with slot-based continuous batching over preallocated block-paged pools.
- :class:`SamplingParams` — per-request decoding controls.
- :class:`Request` / :class:`Scheduler` — FIFO queue + slot table.
- :class:`RequestTracer` / :class:`SLOConfig` — per-request span traces
  (queue→prefill→decode→finish, ``requests-host*.jsonl``) and the SLO
  monitor (``serving.slo.violations{phase}``, flight-recorder forensics).
- :class:`PagedKVCache` / :class:`PageAllocator` — the engine's cache
  (fixed-size pages + per-slot page table) and the exact-cover free-list
  allocator the scheduler drives.
- :func:`paged_write_kv` / :func:`paged_gather` /
  :func:`paged_decode_attend` — the write and attend over it, which a
  model's layers call: they live below the model (``kernels/pools.py``,
  ``kernels/paged_attention.py``) and are re-exported here. The attend
  is the Pallas kernel on a TPU and the oracle elsewhere
  (``kernels/tier.py``);
  :func:`use_paged_attention_impl` is the tests' seam to pin the tier
  (``oracle`` | ``pallas``) for traces entered under it.
- :func:`write_kv`, :func:`decode_attend` — the dense
  ``[B, H_kv, S_max, D]`` write/attend primitives: the paged attend's
  oracle, and the lockstep decode of :func:`cached_generate` and
  ``incubate.nn.FusedMultiTransformer``'s ``time_step``.
- :func:`cached_generate` — the static-shape lockstep decode loop
  ``models.gpt.GPTForCausalLM.generate`` delegates to (its own dense
  buffers, not the engine).
- :class:`PrefixCache` — radix trie from block-aligned token prefixes to
  physical page ids: cache-hit prompts splice shared (refcounted,
  copy-on-write) pages and prefill only their suffix
  (``EngineConfig(prefix_cache=True)``).
- :class:`SpeculativeConfig` / :func:`propose_ngram` /
  :func:`accept_greedy` — n-gram-draft speculative decoding over the
  one-compile verify-k program (``EngineConfig(speculative=k)``).
- :func:`extend_attend` / :func:`paged_extend_attend` — the multi-query
  cached-attention primitives suffix prefill and verify ride on (the
  second picks the Pallas kernel ``extend_flash`` or the first, its
  oracle, as ``kernels/tier`` says).

See ``paddle_tpu/serving/README.md`` for the design and metric names.
"""

from __future__ import annotations

# the device-side functions live below the model (kernels/); the package's
# public names for them stay, re-exported downward
from ..kernels.paged_attention import (  # noqa: F401
    decode_attend,
    extend_attend,
    paged_decode_attend,
    paged_extend_attend,
)
from ..kernels.pools import (  # noqa: F401
    PAGE_SENTINEL,
    paged_gather,
    paged_write_kv,
    write_kv,
)
from ..kernels.tier import use_paged_attention_impl  # noqa: F401
from .engine import Engine, EngineConfig, cached_generate  # noqa: F401
from .kv_cache import PagedKVCache  # noqa: F401
from .prefix_cache import PrefixCache  # noqa: F401
from .request_trace import (  # noqa: F401
    RequestTracer,
    SLOConfig,
    read_request_traces,
    request_trace_path,
)
from .sampling import SamplingParams  # noqa: F401
from .scheduler import PageAllocator, Request, Scheduler  # noqa: F401
from .speculative import (  # noqa: F401
    SpeculativeConfig,
    accept_greedy,
    propose_ngram,
)

__all__ = [
    "Engine",
    "EngineConfig",
    "PAGE_SENTINEL",
    "PageAllocator",
    "PagedKVCache",
    "PrefixCache",
    "Request",
    "RequestTracer",
    "SLOConfig",
    "SamplingParams",
    "Scheduler",
    "SpeculativeConfig",
    "accept_greedy",
    "cached_generate",
    "decode_attend",
    "extend_attend",
    "paged_decode_attend",
    "paged_extend_attend",
    "paged_gather",
    "paged_write_kv",
    "propose_ngram",
    "read_request_traces",
    "request_trace_path",
    "use_paged_attention_impl",
    "write_kv",
]
