"""TPU-native LLM serving engine: static-shape decode + continuous batching.

One path, drawn in four boxes whose arrows point one way, which the imports
follow (``serving`` -> ``models`` -> ``kernels``; tests/test_layering.py):

``Engine`` (host: scheduler, ``PageAllocator``, ``PrefixCache``, the page
tables of ``kv_cache.PagedKVCache``) -> four static-shape programs
``(params, *pools, ...)`` over its block-paged pools -> the model's protocol
(``prefill_with_cache`` / ``decode_step`` / ``extend_step`` on paged
entries) -> ``kernels``: ``pools.paged_write_kv`` and the attend, each
kernel beside its reference, where ``kernels/tier.default_paged_impl`` alone
says which of the two runs.

- **prefill/T** — one AOT-compiled executable per prompt-length bucket
  (powers of two up to ``max_seq_len``): the padded prompt runs the causal
  forward once, its K/V land in the pages of the request's table row, and
  the last real token's logits come back for the first sampled token (TTFT).
- **extend/T** — the suffix prefill a prefix-cache hit runs instead: the
  matched blocks' pages are spliced into the table row and only the rest
  of the prompt flows through the forward.
- **decode** — ONE executable for the whole engine lifetime: a ``[B_max]``
  batch of single tokens with per-row positions writes into the pools and
  attends over each row's live pages. Per-request SamplingParams ride as
  device arrays (sampling.sample_batched), so an arbitrary mix of
  greedy/sampled requests never triggers a recompile. Its operands stay
  on the device from one step to the next: the program puts out the next
  step's tokens and positions itself, and the host puts an operand again
  only when something other than the step changed its host mirror. The
  step is launched one step AHEAD of its fetch: ``step()`` k launches
  program k and then fetches and settles program k - 1, so the device
  never waits for the host between two decode steps (``Engine._decode``).
- **verify** — decode widened to ``[B_max, k+1]``: what the engine's one
  host decode step (``Engine._decode``) runs instead under speculation.

``cached_generate`` is a different job and not a fifth box: the lockstep
batch loop ``GPTForCausalLM.generate`` delegates to, over its own dense
``[B, H_kv, S_max, D]`` buffers, one prefill compile + one decode compile
total (asserted via the ``jit.compile.cache_miss{site=serving.*}``
observability counters). The engine's tests use it as their reference.

Everything is AOT-compiled (``jax.jit(fn).lower(...).compile()``): a shape
drift raises instead of silently recompiling per token — the property the
regression test in tests/test_serving.py pins down.
"""

from __future__ import annotations

import time
import warnings
import weakref
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import random as _random
from ..core.autograd import no_grad
from ..core.tensor import Tensor
from ..kernels.pools import paged_write_kv, write_kv, write_state_rows
from ..observability import instrument as _obs
from ..observability import memory as _obs_memory
from ..observability import metrics as _metrics
from ..observability.tracing import span as _span
from . import sampling as _sampling
from .kv_cache import PAGE_SENTINEL, PagedKVCache, _layer_buffers
from .prefix_cache import PrefixCache
from .request_trace import RequestTracer, SLOConfig
from .sampling import SamplingParams
from .scheduler import FINISHED, PageAllocator, Request, Scheduler
from .speculative import SpeculativeConfig, accept_greedy, propose_ngram

#: every serving executable takes (params, *pools, ...), where each pool is
#: a TUPLE of per-layer buffers (kv_cache.PagedKVCache: ``k`` and ``v``,
#: then whatever else the model declared), and returns the tuples of
#: updated buffers its caller rebinds — so the pool args are donated at
#: compile time (an argnum covers every leaf of its pytree). Layer ``l``'s
#: program output is a scatter into layer ``l``'s donated parameter, which
#: XLA aliases: the cache is written where it lies, and no program holds a
#: second copy of it (the analysis donation rule, donation-missing on
#: serving_prefill/serving_decode, checks the donation half). These are the
#: argnums of the two pools every model has; ``Engine.donate_argnums``
#: covers a model that declared more.
KV_DONATE_ARGNUMS = (1, 2)

#: the decode step's per-slot operands, in the programs' argument order
#: behind the page table. Each has a host mirror (``Engine._<name>``) and a
#: kept device array (``Engine._dev[<name>]``); a name in ``Engine._stale``
#: says the mirror was changed by something other than the decode program
#: since the array was put, and the next upload puts it whole.
#:
#: The mirror is the authority for the last four (``_HOST_OPERANDS``): the
#: host knows them for the step it is about to launch, the positions
#: because their mirror moves when a step is LAUNCHED. ``tokens`` is not
#: the host's to put once a plain engine runs: the step before is still in
#: flight when the next goes out (``Engine._decode``), so ``_tokens`` lags
#: the device by a step for every row that ran in it, and the carried
#: device array is the authority. What the host does know, the first token
#: of a slot it admitted and the 0 of one it finished, travels as the
#: ``host_tokens`` operand (``_tokens`` where ``Engine._from_host`` is set,
#: -1 elsewhere) that the decode program selects from in graph. Only a
#: speculative engine, which settles every step before the next, puts
#: ``tokens`` (its ``[B, k+1]`` block) from the mirror every step.
_OPERANDS = ("tokens", "positions", "temps", "top_ks", "greedy")
_HOST_OPERANDS = _OPERANDS[1:]

#: the ``jit.compile.*{site=}`` each kind of program accounts under. The
#: verify program REPLACES the plain decode step while speculation is on,
#: so it shares serving.decode: the one-compile-per-lifetime counter covers
#: both modes.
_SITES = {"prefill": "serving.prefill", "extend": "serving.prefill",
          "decode": "serving.decode", "verify": "serving.decode"}

_DUMMY_KEY = None


def _dummy_key():
    """Placeholder PRNG key for greedy-only compiled signatures (the arg is
    dead code under argmax; keeping the signature uniform avoids a second
    decode executable)."""
    global _DUMMY_KEY
    if _DUMMY_KEY is None:
        _DUMMY_KEY = jax.random.PRNGKey(0)
    return _DUMMY_KEY


def _aot(cache: Dict, key, site: str, fn, args,
         donate_argnums: Tuple[int, ...] = ()) -> "jax.stages.Compiled":
    """AOT compile-or-fetch with observability accounting: a dict hit bumps
    ``jit.compile.cache_hit{site=}``, a miss compiles under a ``compile``
    span (whose seconds feed ``jit.compile.seconds{site=}``) and bumps the
    miss counter. The
    compiled executable is shape-locked — drifting shapes raise rather
    than recompile, which is what makes the one-compile guarantee
    testable. ``donate_argnums`` marks input buffers the caller never
    reuses (the KV caches) so XLA aliases them into the outputs."""
    exe = cache.get(key)
    if exe is not None:
        _obs.record_compile(site, cache_hit=True)
        return exe
    with _span("compile", site=site, cache_hit=0) as sp, \
            warnings.catch_warnings():
        # CPU/interpreter backends may decline the aliasing; the donation
        # contract is still correct (and active on TPU) — keep logs quiet
        warnings.filterwarnings(
            "ignore", message=".*donated buffers.*", category=UserWarning)
        exe = jax.jit(fn, donate_argnums=tuple(donate_argnums)) \
            .lower(*args).compile()
    _obs.record_compile(site, seconds=sp.seconds, cache_hit=False)
    _obs_memory.record_executable(site, exe)
    cache[key] = exe
    return exe


def _param_dtype(params: Dict[str, jax.Array]):
    for v in params.values():
        if jnp.issubdtype(v.dtype, jnp.floating):
            return v.dtype
    return jnp.float32


def _updated(cache, new) -> Tuple[Tuple[jax.Array, ...], ...]:
    """The pools' buffer tuples a program returns (by pool, then by the
    pool's layers), from the per-layer entries (``(k, v)`` Tensor pairs, or
    one Tensor per pool the layer holds) ``decode_step`` / ``extend_step``
    hand back; ``cache`` (a ``PagedKVCache``, None for ``cached_generate``'s
    dense pair) knows which layers hold which pool."""
    values = [tuple(t._value for t in layer) for layer in new]
    return tuple(zip(*values)) if cache is None \
        else cache.pools_from_layers(values)


def _write_prompt(write, pools, kvs):
    """``write(buffer, new)`` over every layer of every pool: the prompt's
    per-layer entries (one Tensor per pool, as ``prefill_with_cache``
    returns them) into the cache's buffer tuples; returns the updated
    tuples."""
    return tuple(tuple(write(c, e._value) for c, e in zip(pool, entries))
                 for pool, entries in zip(pools, zip(*kvs)))


def _write_prompt_dense(kc, vc, kvs):
    """Each layer's prompt K/V ``[B, Hkv, T, D]`` into that layer's dense
    ``[B, Hkv, S_max, D]`` buffer (``cached_generate``'s), positions
    ``[0, T)``."""
    return _write_prompt(lambda c, new: write_kv(c, new, jnp.int32(0)),
                         (kc, vc), kvs)


def _write_prompt_paged(cache, pools, kvs, page_rows, rows=None):
    """Each layer's prompt entries into that layer's pools: a paged pool's
    ``[1, heads, T, width]`` at positions ``[0, T)``, routed by the slot's
    table row in the pool's page group (``page_rows``: one a group), one
    scatter of the bucket's pages per pool (``paged_write_kv``; blocks
    without a page, sentinels, land on the trash page: the bucket's tail, a
    window group's blocks behind the tails it keeps, blocks the slot
    shares); a state pool's ``[len(rows), ...]`` (the state at each cut,
    then at the end) onto ``rows`` (``write_state_rows``)."""
    tables, zero = [r[None, :] for r in page_rows], jnp.zeros((1,), jnp.int32)
    paged = len(cache.pool_specs)
    return tuple(
        tuple((paged_write_kv(c, new, tables[cache.pool_group[j]], zero)
               if j < paged else write_state_rows(c, new, rows))
              for c, new in zip(pool, news))
        for j, (pool, news) in enumerate(zip(pools, _updated(cache, kvs))))


def _call(model, params, method, *args, **kw):
    """``model.<method>`` on ``params``: (logits, per-layer new entries,
    per-layer step statistics or None). A model that counts something in
    its step (a routed FFN's expert loads) returns it third."""
    with no_grad():
        (logits, new, *more), _ = model.functional_call(
            params, {}, *args, method=method, **kw)
    return logits._value, new, (more[0]._value if more else None)


# ---------------------------------------------------------------------------
# Batch decode loop: the static-shape core GPTForCausalLM.generate rides on.
# ---------------------------------------------------------------------------

_GEN_EXE_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def cached_generate(model, input_ids, *, max_new_tokens: int = 32,
                    do_sample: bool = False, temperature: float = 1.0,
                    top_k: int = 0, eos_token_id=None):
    """Autoregressive decoding over a static KV cache — the drop-in body of
    ``GPTForCausalLM.generate`` (same API, same greedy/temperature/top-k
    and forced-eos-fill semantics as the old grown-prefix loop), at one
    prefill + one decode compilation instead of one compile per emitted
    token."""
    from ..ops._dispatch import as_tensor

    ids = as_tensor(input_ids)
    if max_new_tokens <= 0:
        return ids
    idsv = ids._value
    B, S = int(idsv.shape[0]), int(idsv.shape[1])
    cfg = model.cfg
    S_max = S + max_new_tokens
    params, _ = model.functional_state()
    dt = _param_dtype(params)
    shape = (B, cfg.num_kv_heads, S_max, cfg.head_dim)
    kc = _layer_buffers(cfg.num_layers, shape, dt)
    vc = _layer_buffers(cfg.num_layers, shape, dt)

    exe_cache = _GEN_EXE_CACHE.setdefault(model, {})
    tok_dtype = idsv.dtype

    def prefill_fn(p, kc, vc, ids):
        with no_grad():
            (logits, kvs), _ = model.functional_call(
                p, {}, Tensor(ids), method="prefill_with_cache")
        kc, vc = _write_prompt_dense(kc, vc, kvs)
        return logits._value, kc, vc

    pkey = ("prefill", B, S, S_max, str(tok_dtype), str(dt))
    prefill = _aot(exe_cache, pkey, "serving.prefill", prefill_fn,
                   (params, kc, vc, idsv),
                   donate_argnums=KV_DONATE_ARGNUMS)

    def decode_fn(p, kc, vc, tokens, positions, key):
        with no_grad():
            (logits, new), _ = model.functional_call(
                p, {}, Tensor(tokens), list(zip(kc, vc)),
                Tensor(positions), method="decode_step")
        nxt = _sampling.sample_static(
            logits._value, key, do_sample=do_sample,
            temperature=temperature, top_k=top_k)
        return (nxt.astype(tokens.dtype),) + _updated(None, new)

    dkey = ("decode", B, S_max, str(tok_dtype), str(dt),
            do_sample, float(temperature), int(top_k))
    tok0 = jnp.zeros((B,), tok_dtype)
    pos0 = jnp.full((B,), S - 1, jnp.int32)
    decode = _aot(exe_cache, dkey, "serving.decode", decode_fn,
                  (params, kc, vc, tok0, pos0, _dummy_key()),
                  donate_argnums=KV_DONATE_ARGNUMS)

    logits0, kc, vc = prefill(params, kc, vc, idsv)
    finished = np.zeros((B,), bool)
    toks: List[np.ndarray] = []
    key = _random.next_key() if do_sample else _dummy_key()
    nxt = np.asarray(_sampling.sample_static(
        logits0, key, do_sample=do_sample, temperature=temperature,
        top_k=top_k)).astype(np.asarray(idsv).dtype)
    for i in range(max_new_tokens):
        if i > 0:
            pos = jnp.full((B,), S - 1 + i, jnp.int32)
            key = _random.next_key() if do_sample else _dummy_key()
            nxt_dev, kc, vc = decode(params, kc, vc, jnp.asarray(toks[-1]),
                                     pos, key)
            nxt = np.asarray(nxt_dev)
        if eos_token_id is not None:
            nxt = np.where(finished, eos_token_id, nxt).astype(nxt.dtype)
            finished = finished | (nxt == eos_token_id)
        toks.append(nxt)
        if eos_token_id is not None and bool(finished.all()):
            break
    out = np.concatenate([np.asarray(idsv)]
                         + [t[:, None] for t in toks], axis=1)
    return Tensor(jnp.asarray(out))


# ---------------------------------------------------------------------------
# Continuous-batching engine
# ---------------------------------------------------------------------------

@dataclass
class EngineConfig:
    """Static serving envelope, fixed at engine construction (the shapes
    every compiled executable is locked to)."""

    max_batch_size: int = 4      # decode slots (B_max)
    max_seq_len: int = 128       # per-slot prompt + generation budget (S_max)
    prefill_buckets: Optional[Tuple[int, ...]] = None  # default: pow2 <= S_max
    cache_dtype: Optional[str] = None  # default: the model's param dtype
    # per-request tracing / SLO monitoring (request_trace.py): a directory
    # enables the requests-host*.jsonl trace file; an SLOConfig enables the
    # serving.slo.violations counters + flight-recorder violation traces
    # (either works without the other)
    request_trace_dir: Optional[str] = None
    trace_sample_every: int = 1
    slo: Optional["SLOConfig"] = None
    # K/V live in fixed-size pages routed by a per-slot page table, so HBM
    # scales with LIVE tokens and a smaller ``kv_pages`` pool serves the
    # same (B_max, S_max) envelope
    page_size: int = 16          # tokens per KV page (shrunk to divide S_max)
    kv_pages: Optional[int] = None  # pool size; default = full budget + trash
    # the pool size of each further page GROUP a model declares, by the
    # group's name ({"window": pages}: a sliding layer's pools hold a
    # window a slot, not a context); default = the full budget each
    group_pages: Optional[Dict[str, int]] = None
    # radix prefix cache (prefix_cache.py): finished prompts' full KV
    # blocks stay indexed by token content, and a new request whose prompt
    # shares a block-aligned prefix splices the SAME physical pages into
    # its table (refcounted, copy-on-write) and prefills only the suffix.
    prefix_cache: bool = False
    # speculative decoding (speculative.py): True / an int k / a
    # SpeculativeConfig. When on, the engine's decode step is the verify-k
    # program — [B, k+1] static shape, compiled ONCE at construction — fed
    # by the n-gram draft proposer; greedy rows emit up to k+1 tokens per
    # step with output identical to one-at-a-time greedy decode.
    speculative: Optional[Union[bool, int, "SpeculativeConfig"]] = None
    # rows of the snapshot pool of a model that keeps recurrent state
    # (kv_cache.PagedKVCache): how many block boundaries of cached prompts
    # the prefix cache can resume such a model at. Default: two a slot with
    # the prefix cache on, none without it.
    state_snapshots: Optional[int] = None

    def __post_init__(self):
        if isinstance(self.speculative, bool):
            self.speculative = SpeculativeConfig() if self.speculative else None
        elif isinstance(self.speculative, int):
            self.speculative = SpeculativeConfig(k=int(self.speculative))
        if (self.speculative is not None
                and not isinstance(self.speculative, SpeculativeConfig)):
            raise ValueError(
                f"speculative={self.speculative!r}; want True, an int k, or "
                "a SpeculativeConfig")
        while self.page_size > 1 and self.max_seq_len % self.page_size:
            self.page_size //= 2
        if self.prefill_buckets is None:
            buckets = []
            b = 8
            while b < self.max_seq_len:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_seq_len)
            self.prefill_buckets = tuple(buckets)
        else:
            self.prefill_buckets = tuple(sorted(set(self.prefill_buckets)))


class _SlotState:
    __slots__ = ("request",)

    def __init__(self, request=None):
        self.request = request


@dataclass
class _Flight:
    """A decode step that was launched and not yet settled: what the host
    fetches (``out``; ``sampled0`` and ``drafts`` beside it under
    speculation), the number of its launch, the ``(request, slot)`` of every
    row the program runs for, the ``serving/decode`` attributes that
    describe it, and the seconds its launch took the host."""

    launch: int
    out: jax.Array
    rows: List[Tuple[Request, int]]
    attrs: Dict
    seconds: float
    sampled0: Optional[jax.Array] = None
    drafts: Optional[Dict[int, List[int]]] = None


class Engine:
    """Offline/online LLM serving engine over a cache-aware causal LM.

    The model must speak the protocol of serving/README.md ("The model's
    side"): ``cfg.num_layers``, ``functional_state()``, the
    ``prefill_with_cache`` / ``decode_step`` / ``extend_step`` methods
    (callable through ``functional_call``), and either ``cache_pools()`` +
    ``max_context`` or, as GPTForCausalLM has them, ``cfg.num_kv_heads`` /
    ``cfg.head_dim`` / ``cfg.max_seq_len``.

        engine = Engine(model, max_batch_size=4, max_seq_len=128)
        outputs = engine.generate([[5, 17, 3], [9, 2]],
                                  SamplingParams(max_new_tokens=16))

    Request flow: ``add_request`` queues; each ``step()`` first admits
    waiting requests into any free KV-cache slots (prefill + first token —
    continuous batching: admission happens the moment a slot frees, between
    decode steps), then LAUNCHES one batched decode step for every running
    request and settles the step the call before launched: ``step()``
    returns step N - 1's tokens while step N runs (``step``, ``_decode``).
    All serving metrics are flag-gated through
    ``paddle_tpu.observability`` (see serving/README.md for the names).
    """

    def __init__(self, model, config: Optional[EngineConfig] = None, **kw):
        self.model = model
        model.eval()
        self.config = config or EngineConfig(**kw)
        cfg = model.cfg
        # the longest sequence the model's positions serve: what it
        # declares, else (GPT) the length of its position table
        context = getattr(model, "max_context", None) or cfg.max_seq_len
        if self.config.max_seq_len > context:
            raise ValueError(
                f"engine max_seq_len {self.config.max_seq_len} exceeds the "
                f"model's declared context ({context})")
        # the per-layer pools: the model's declaration, else K and V of
        # cfg.num_kv_heads x cfg.head_dim
        declared = getattr(model, "cache_pools", None)
        pools = declared() if declared is not None else [
            ("k", cfg.num_kv_heads, cfg.head_dim),
            ("v", cfg.num_kv_heads, cfg.head_dim)]
        # the slot-indexed state of a model that keeps a recurrence
        declared = getattr(model, "state_pools", None)
        state = declared() if declared is not None else []
        self._stateful = bool(state)
        if state and self.config.speculative is not None:
            raise ValueError(
                "speculative decoding is refused for "
                f"{type(model).__name__}: it declares recurrent state "
                f"({', '.join(sp[0] for sp in state)}), and the verify step "
                "rolls a rejected draft back by not advancing positions "
                "over K/V it wrote, which a state that has absorbed the "
                "draft cannot do")
        snapshots = self.config.state_snapshots
        if snapshots is None:
            snapshots = 2 * self.config.max_batch_size
        if not (state and self.config.prefix_cache):
            snapshots = 0
        self.donate_argnums = tuple(range(1, 1 + len(pools) + len(state)))
        self.params, _ = model.functional_state()
        dt = (self.config.cache_dtype if self.config.cache_dtype is not None
              else _param_dtype(self.params))
        B, S_max = self.config.max_batch_size, self.config.max_seq_len
        ps = self.config.page_size
        num_pages = self.config.kv_pages
        if num_pages is None:
            num_pages = B * (S_max // ps) + 1  # full budget + trash page
        self.cache = PagedKVCache(cfg.num_layers, B, pools[0][1], S_max,
                                  pools[0][2], dt, page_size=ps,
                                  num_pages=num_pages, pools=pools,
                                  state_pools=state, num_snapshots=snapshots,
                                  group_pages=self.config.group_pages)
        groups = self.cache.groups
        # one allocator a page group; ``page_alloc`` is the first group's
        # (a cache of one group: the one allocator there always was)
        self.page_allocs: List[PageAllocator] = [
            PageAllocator(pages, name if len(groups) > 1 else None)
            for name, _, pages in groups]
        self.page_alloc = self.page_allocs[0]
        #: [(group, window in tokens)] of the groups that keep a window
        self._windows = [(g, w) for g, (_, w, _) in enumerate(groups) if w]
        if self._windows and (self.config.speculative is not None
                              or (groups[0][1] and self.config.prefix_cache)):
            raise ValueError(
                "a model with sliding-window pools is served without "
                "speculation, and with the prefix cache only beside a "
                "group that keeps every token")
        # a pool that more layers read than hold it (``PagedKVCache
        # .pool_readers``): how many read the first such pool
        shared = [r for r, held in zip(self.cache.pool_readers,
                                       self.cache.pool_layers) if r != held]
        self._shared_readers = len(shared[0]) if shared else 0
        if shared:
            _metrics.gauge("serving.shared_pool.readers",
                           self._shared_readers)
        # the first block each slot still maps in each window group
        self._win_from = {g: np.zeros((B,), np.int64) for g, _ in self._windows}
        # references dropped behind windows / matched tokens run again for
        # want of a window's pages, so far
        self.window_pages_freed = 0
        self.resume_cut_tokens = 0
        # snapshot ids [1, snapshots], refcounted as pages are: the trie
        # holds one reference a node that carries one, an admission one on
        # the snapshot it resumes from until its program is enqueued
        self.snapshot_alloc: Optional[PageAllocator] = \
            PageAllocator(snapshots + 1) if snapshots else None
        _metrics.gauge("serving.kv_cache.bytes", self.cache.nbytes)
        _obs_memory.record_kv_cache(self.cache.nbytes)
        self.scheduler = Scheduler(B)
        self.tracer: Optional[RequestTracer] = None
        if self.config.request_trace_dir or self.config.slo is not None:
            self.tracer = RequestTracer(
                self.config.request_trace_dir,
                sample_every=self.config.trace_sample_every,
                slo=self.config.slo)
        self._slots: List[_SlotState] = [_SlotState() for _ in range(B)]
        # vectorized per-slot decode state: the host mirrors of _OPERANDS,
        # the arrays the device holds of them, and which mirrors changed
        # since (admission and finish mark the four the host is the
        # authority for and name the slot in ``_from_host``; the plain
        # decode step advances tokens and positions on both sides and marks
        # nothing)
        self._tokens = np.zeros((B,), np.int32)
        self._positions = np.zeros((B,), np.int32)
        self._temps = np.ones((B,), np.float32)
        self._top_ks = np.zeros((B,), np.int32)
        self._greedy = np.ones((B,), bool)
        self._stale = set(_OPERANDS)
        # slots whose next token the host decided (``_OPERANDS``), and the
        # ``host_tokens`` row that says "none": what a step is handed
        # behind no admission or finish
        self._from_host = np.zeros((B,), bool)
        self._no_host_tokens = jax.device_put(np.full((B,), -1, np.int32))
        self._dev: Dict[str, jax.Array] = {
            "host_tokens": self._no_host_tokens}
        # the plain decode step that was launched and not yet fetched
        self._flight: Optional[_Flight] = None
        self._exe: Dict = {}
        self._step_i = 0  # engine steps so far: the spans' ``step``
        # calls of a compiled executable so far (an eager stretch counts
        # one): the spans' ``launch`` / ``waits_for``, which a reader of the
        # device's trace joins to the program runs, in order
        self._launch_i = 0
        self._cow_copies = 0  # copy-on-write page copies so far
        # decode steps whose program ran the sampler's argmax alone (every
        # row greedy) / drew as well (``sampling.sample_batched``)
        self.sampler_steps_argmax = 0
        self.sampler_steps_draw = 0
        # decode steps launched with the step before still unfetched / with
        # nothing in flight (the first behind an empty engine or a short
        # pool, every speculative step); rows a step ran for a request that
        # had finished by the time it was settled
        self.steps_ahead = 0
        self.steps_drained = 0
        self.dropped_rows = 0
        self.prefix_cache: Optional[PrefixCache] = None
        if self.config.prefix_cache:
            self.prefix_cache = PrefixCache(
                self.cache.page_size, self.page_alloc, self.snapshot_alloc,
                more=[(a, w) for a, (_, w, _) in zip(self.page_allocs[1:],
                                                     groups[1:])])
            # pages can be shared from here on: have the copy-on-write
            # programs compiled now, never between two decode steps
            for g in range(len(groups)):
                self.cache.copy_page_exe(g)
        self.spec: Optional[SpeculativeConfig] = self.config.speculative
        # cumulative speculation accounting (greedy rows only — sampled
        # rows ignore drafts and always emit 1 token from position 0)
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_emitted = 0
        self._spec_slots = 0
        if self.spec is not None:
            # with speculation on, the verify-k program IS the engine's
            # decode step — compile it here so the serving.decode lifetime
            # compile count is sealed at exactly one
            self._verify_exe()

    # -- weight management --
    def load_weights(self, params, shardings=None, allow_missing=False):
        """Hot-swap serving weights from a live parameter tree — e.g. the
        params of a training step on its OWN mesh — without a host round
        trip: each leaf moves device-to-device through the resharding
        planner (distributed.resharding) onto the serving layout, with
        ``jax.device_put`` as the per-leaf fallback.

        `shardings` (optional {name: NamedSharding}) selects the serving
        layout per param; by default each current param's own sharding is
        kept, so the AOT-compiled prefill/decode executables stay valid.
        Shapes and dtypes must match the compiled params exactly."""
        from ..distributed import resharding as _resharding

        missing = [k for k in self.params if k not in params]
        if missing and not allow_missing:
            raise KeyError(f"load_weights: missing params {missing[:4]}"
                           + ("..." if len(missing) > 4 else ""))
        new = {}
        for name, cur in self.params.items():
            if name not in params:
                new[name] = cur
                continue
            leaf = params[name]
            leaf = getattr(leaf, "_value", leaf)  # unwrap Tensor
            if (tuple(leaf.shape) != tuple(cur.shape)
                    or str(leaf.dtype) != str(cur.dtype)):
                raise ValueError(
                    f"load_weights: param {name!r} is "
                    f"{leaf.shape}/{leaf.dtype}, engine compiled for "
                    f"{cur.shape}/{cur.dtype}")
            dst = (shardings or {}).get(name, cur.sharding)
            new[name] = _resharding.reshard(leaf, dst)
        self.params = new
        if shardings:
            # layouts changed: the AOT executables were compiled against
            # the old shardings — drop them so the next step recompiles
            self._exe.clear()
        return self

    # -- request API --
    def add_request(self, prompt_ids: Sequence[int],
                    sampling: Optional[SamplingParams] = None) -> Request:
        req = Request(prompt_ids, sampling)
        if len(req.prompt_ids) >= self.config.max_seq_len:
            raise ValueError(
                f"prompt of {len(req.prompt_ids)} tokens leaves no room to "
                f"generate within max_seq_len={self.config.max_seq_len}")
        self.scheduler.add(req)
        if self.tracer is not None:
            self.tracer.on_queued(req)
        return req

    @property
    def has_unfinished(self) -> bool:
        return self.scheduler.has_unfinished

    def generate(self, prompts: Sequence[Sequence[int]],
                 sampling: Union[SamplingParams, Sequence[SamplingParams],
                                 None] = None) -> List[List[int]]:
        """Offline convenience: queue every prompt, run steps to drain, and
        return each prompt's generated token ids (prompt excluded), in
        order."""
        if isinstance(sampling, SamplingParams) or sampling is None:
            sampling = [sampling] * len(prompts)
        if len(sampling) != len(prompts):
            raise ValueError("len(sampling) != len(prompts)")
        reqs = [self.add_request(p, sp) for p, sp in zip(prompts, sampling)]
        with _span("serving/generate", requests=len(reqs)) as drain:
            # (one call more where the last finish was an ``eos``: the step
            # launched beside it is fetched and dropped, nothing is left in
            # flight for the caller)
            while self.scheduler.has_unfinished or self._flight is not None:
                self.step()
        total = sum(r.num_generated for r in reqs)
        if drain.seconds > 0:
            _metrics.gauge("serving.tokens_per_sec", total / drain.seconds)
        return [r.output_ids for r in reqs]

    # -- engine loop --
    def step(self):
        """One scheduler iteration: admit waiting requests into free slots
        (bucketed prefill + first token each), then LAUNCH one batched
        decode step over every running request and settle the step the call
        before launched (``_decode``). The whole of it is one
        ``serving/step`` span whose children are the phases
        (serving/README.md lists them).

        What a caller sees: a call returns decode step N - 1's tokens while
        step N runs on the device. The call that admits a request shows its
        prefill's token alone; a request that needs n decode steps is
        finished after n + 1 calls; a token is visible when the device has
        made it, as it always was. When ``has_unfinished`` turns false on an
        ``eos`` a step may still be in flight: its rows belong to finished
        requests, and the next call fetches and drops it (``generate``
        makes that call itself). A speculative engine (``self.spec``)
        launches, fetches and settles each verify step in one call."""
        self._step_i += 1
        with _span("serving/step", step=self._step_i,
                   running=len(self.scheduler.running),
                   waiting=len(self.scheduler.waiting)) as sp:
            sp.set(emitted=self._admit() + self._decode())

    # -- internals --
    def _bucket(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if b >= n:
                return b
        return self.config.max_seq_len

    def prefill_program(self, T: int):
        """(fn, example_args) for the T-token prefill bucket — the pure
        program ``_prefill_exe`` compiles, exposed so the static analyzer
        (paddle_tpu.analysis) can trace it without compiling/executing.
        The pool args (positions ``self.donate_argnums``) are donated at
        compile; callers must rebind from the outputs.

        The slot's table row (``page_row [num_blocks]`` int32, runtime
        data) says where: each layer's prompt K/V lands in that layer's
        pools as one scatter of the bucket's pages (``_write_prompt_paged``;
        the bucket tail past the allocated pages clamps to the trash
        page)."""
        model, n = self.model, len(self.cache.pools)
        nb, G = self.cache.num_blocks, len(self.cache.groups)

        cache = self.cache

        @jax.named_scope("serving/prefill")
        def paged_prefill_fn(p, *a):
            pools, (ids, *page_rows, length), state = \
                a[:n], a[n:n + G + 2], a[n + G + 2:]
            # (a model with recurrent state hands out its state before each
            # cut too, for the rows ``_state_arg`` names)
            more = {"cuts": Tensor(state[0][None, 1:3])} if state else {}
            logits, kvs, _ = _call(model, p, "prefill_with_cache",
                                   Tensor(ids),
                                   lengths=Tensor(length[None]), **more)
            return (logits,) + _write_prompt_paged(
                cache, pools, kvs, page_rows, *(s[3:] for s in state))

        args = (self.params, *self.cache.pools,
                jnp.zeros((1, T), jnp.int32),
                *(jnp.zeros((nb,), jnp.int32) for _ in range(G)),
                jnp.int32(1)) + self._state_arg()
        return paged_prefill_fn, args

    def _state_arg(self, slot: int = 0, source: Optional[int] = None,
                   cuts: Sequence[Tuple[int, int]] = ()) -> Tuple:
        """What the prefill and extend programs of a model with recurrent
        state take last, one int32 array ``[source row, cut, cut, row, row,
        slot]``: the row an extend starts from (snapshot ``source``'s; a
        prefill starts from zero), the state BEFORE each cut (``cuts``:
        ``[(tokens from the run's first, snapshot id)]``, two at most) goes
        to that snapshot's row, the end state to the slot's, written last:
        a cut that is not wanted names the slot's row too. Nothing for any
        other model: its programs are the ones they were."""
        if not self._stateful:
            return ()
        row = self.cache.snapshot_row
        (c0, r0), (c1, r1) = [(0, slot)] * (2 - len(cuts)) + [
            (tokens, row(snap)) for tokens, snap in cuts]
        return (jnp.asarray(np.array(
            [slot if source is None else row(source), c0, c1, r0, r1, slot],
            np.int32)),)

    def decode_program(self):
        """(fn, example_args) for the batched decode step — see
        ``prefill_program`` for the donation contract.

        The page table rides as one ``[B, num_blocks]`` int32 operand. Its
        CONTENTS change every admission/finish but the shape never does —
        the decode executable stays ONE compile for the engine lifetime
        (tests pin the compile counter), and the paged attend reads each
        slot's live pages out of the pools.

        Operands behind the tables: the ``_OPERANDS`` (the carried
        ``tokens`` first), then ``host_tokens [B]`` int32, then the key. A
        row of ``host_tokens`` that is >= 0 REPLACES the carried token, in
        graph: the first token of a slot the host admitted, the 0 of one it
        finished. The host may not know the other rows' tokens (the step
        that makes them can still be in flight, ``Engine._decode``), so it
        never puts ``tokens`` whole; on a step behind no admission or
        finish ``host_tokens`` is the engine's one kept array of -1 and
        nothing is put.

        Outputs: the array the host fetches (the sampled tokens, a model's
        ``step_stats`` behind them), then the NEXT step's ``tokens`` and
        ``positions`` (``Engine._decode`` keeps them on the device and hands
        them back: their two argnums are donated too,
        ``donate_argnums_of``), then the pools."""
        model, cache = self.model, self.cache
        B, nb = self.config.max_batch_size, self.cache.num_blocks
        n, G = len(cache.pools), len(cache.groups)

        @jax.named_scope("serving/decode")
        def paged_decode_fn(p, *a):
            pools, tables = a[:n], a[n:n + G]
            tokens, positions, temps, top_ks, greedy, host_tokens, key = \
                a[n + G:]
            page_table = tables[0]
            tokens = jnp.where(host_tokens >= 0,
                               host_tokens.astype(tokens.dtype), tokens)
            logits, new, stats = _call(
                model, p, "decode_step", Tensor(tokens),
                cache.layer_entries(pools, tables), Tensor(positions))
            nxt = _sampling.sample_batched(logits, key, temps, top_ks,
                                           greedy).astype(jnp.int32)
            # the next step's tokens and positions, kept on the device: a
            # live slot (its first block is mapped) takes its new token and
            # moves on one position; a dead slot stays at token 0, position
            # 0, where the host's mirrors have it (and where the paged
            # attend's loop over a slot's pages makes no trip)
            if cache.groups[0][1] is None:
                live = page_table[:, 0] != PAGE_SENTINEL
            else:
                # a window group maps no first block once a slot has moved
                # on: the block of its position says whether it lives
                live = jnp.take_along_axis(page_table, jnp.minimum(
                    positions // cache.page_size, nb - 1)[:, None],
                    axis=1)[:, 0] != PAGE_SENTINEL
            next_tokens = jnp.where(live, nxt, 0).astype(tokens.dtype)
            next_positions = positions + live.astype(positions.dtype)
            if stats is not None:
                # the step's statistics ride behind the tokens, in the one
                # array the host fetches
                nxt = jnp.concatenate(
                    [nxt, stats.astype(jnp.int32).reshape(-1)])
            return (nxt, next_tokens, next_positions) + _updated(cache, new)

        args = (self.params, *self.cache.pools,
                *(jnp.zeros((B, nb), jnp.int32) for _ in range(G)),
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
                jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                jnp.ones((B,), bool), jnp.full((B,), -1, jnp.int32),
                _dummy_key())
        return paged_decode_fn, args

    def extend_program(self, T: int):
        """(fn, example_args) for the T-token suffix prefill a prefix-cache
        hit runs instead of a full prefill: the matched blocks' pages are
        already spliced into the slot's table row, so only the suffix
        (padded to bucket ``T``) flows through the forward — K/V scatter at
        positions ``start..start+T-1`` through the SAME page-table routing
        as decode (bucket padding past the allocated pages lands on the
        trash page), attention covers cached prefix + suffix, and the last
        real suffix token's logits come back for the first sampled token."""
        model, cache = self.model, self.cache
        nb, n = self.cache.num_blocks, len(self.cache.pools)
        G = len(cache.groups)

        @jax.named_scope("serving/extend")
        def extend_fn(p, *a):
            pools, (ids, *page_rows, start, length), state = \
                a[:n], a[n:n + G + 3], a[n + G + 3:]
            # a model with recurrent state starts from the row it is told,
            # has to know which tokens are padding, and writes its state
            # before each cut and at the end where told (``_state_arg``)
            more = {"lengths": Tensor(length[None]),
                    "cuts": Tensor(state[0][None, 1:3])} if state else {}
            lv, new, _ = _call(                     # logits [1, T, V]
                model, p, "extend_step", Tensor(ids),
                cache.layer_entries(pools, [r[None, :] for r in page_rows],
                                    *((s[0], s[3:]) for s in state)),
                Tensor(start[None]), **more)
            if lv.shape[1] == 1:    # the model cut the batch to that row
                return (lv[0],) + _updated(cache, new)
            idx = jnp.clip(length - 1, 0, T - 1)
            last = lax.dynamic_index_in_dim(lv[0], idx, keepdims=False)
            return (last[None],) + _updated(cache, new)  # [1, V], like prefill

        args = (self.params, *self.cache.pools,
                jnp.zeros((1, T), jnp.int32),
                *(jnp.zeros((nb,), jnp.int32) for _ in range(G)),
                jnp.int32(0), jnp.int32(1)) + self._state_arg()
        return extend_fn, args

    def verify_program(self, k: Optional[int] = None):
        """(fn, example_args) for the speculative verify step — the decode
        program widened to a static ``[B, k+1]`` token block: row ``b``
        carries its pending token plus ``k`` n-gram drafts, the forward
        writes their K/V at positions ``positions[b]..positions[b]+k``
        (writes past the sequence budget route to the trash page) and
        attends each with its own causal mask. Returns per-position argmax
        targets ``[B, k+1]`` (the greedy acceptance oracle), a sampled
        token from position 0 (what non-greedy rows emit), and the caches.
        Rollback of rejected drafts costs nothing here: their K/V lies at
        positions the NEXT verify step rewrites before any attend reads
        them, so the host just advances positions by the accepted count.

        ``k`` defaults to the engine's SpeculativeConfig; passing it
        explicitly lets the analyzer trace the program on an engine without
        speculation enabled (analysis/corpus.py's serving_verify entry)."""
        if k is None:
            if self.spec is None:
                raise ValueError("verify_program(k=None) needs "
                                 "EngineConfig(speculative=...)")
            k = self.spec.k
        model, cache = self.model, self.cache
        B, nb = self.config.max_batch_size, self.cache.num_blocks
        n = len(cache.pools)

        @jax.named_scope("serving/verify")
        def verify_fn(p, *a):
            pools = a[:n]
            page_table, tokens, positions, temps, top_ks, greedy, key = a[n:]
            lv, new, _ = _call(                     # logits [B, k+1, V]
                model, p, "extend_step", Tensor(tokens),
                cache.layer_entries(pools, page_table), Tensor(positions))
            targets = jnp.argmax(lv, axis=-1).astype(jnp.int32)
            sampled0 = _sampling.sample_batched(lv[:, 0], key, temps,
                                                top_ks, greedy)
            return (targets, sampled0.astype(jnp.int32)) \
                + _updated(cache, new)

        args = (self.params, *self.cache.pools,
                jnp.zeros((B, nb), jnp.int32),
                jnp.zeros((B, k + 1), jnp.int32),
                jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32),
                jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool),
                _dummy_key())
        return verify_fn, args

    def sharding_contract(self, nargs: int):
        """Tier-2 analysis declaration for the prefill/decode programs:
        the engine serves from device-local state, so every argument and
        every output must stay fully replicated — if sharding ever leaks
        into a serving program (a partitioned param tree wired in without
        a serving-side mesh plan), spmd-contract-mismatch trips. The page
        pools and the page table are device-local replicated state like
        the rest (``nargs`` is the program's own count)."""
        from ..analysis.sharding_flow import ShardingContract
        from jax.sharding import PartitionSpec as P

        return ShardingContract(in_shardings=(P(),) * nargs,
                                out_shardings=P(), axis_sizes={})

    @property
    def kernel_sites(self) -> Dict[Tuple, Dict[str, int]]:
        """{program key: {kernel name: Mosaic calls}} over the executables
        compiled so far (keys ``("prefill", T)``, ``("decode",)``,
        ``("extend", T)``, ``("verify",)``) — which Pallas kernels actually
        made it into what serves; empty dicts on CPU."""
        from ..kernels.mesh import kernel_sites

        return {key: kernel_sites(exe) for key, exe in self._exe.items()}

    def donate_argnums_of(self, kind: str) -> Tuple[int, ...]:
        """The argnums a program of this kind (``"prefill"``, ``"extend"``,
        ``"decode"``, ``"verify"``) is compiled to donate: the pools, and
        for decode the tokens and positions it puts out again for the next
        step."""
        if kind != "decode":
            return self.donate_argnums
        n = len(self.cache.pools) + len(self.cache.groups)
        return self.donate_argnums + (n + 1, n + 2)

    def _held(self, *key):
        """The executable of program ``key`` (``("decode",)``,
        ``("prefill", T)``, ...): the one the engine holds, found before
        anything is built (the hit still counts under the program's site);
        only on a miss is ``<kind>_program()`` called for the function and
        its example arguments, and the result compiled and kept."""
        kind = key[0]
        exe = self._exe.get(key)
        if exe is not None:
            _obs.record_compile(_SITES[kind], cache_hit=True)
            return exe
        fn, args = getattr(self, kind + "_program")(*key[1:])
        return _aot(self._exe, key, _SITES[kind], fn, args,
                    donate_argnums=self.donate_argnums_of(kind))

    def _prefill_exe(self, T: int):
        return self._held("prefill", T)

    def _decode_exe(self):
        return self._held("decode")

    def _extend_exe(self, T: int):
        return self._held("extend", T)

    def _verify_exe(self):
        return self._held("verify")

    def compile_programs(self, prefill: Sequence[int] = (),
                         extend: Sequence[int] = ()) -> List[Tuple]:
        """Compile, before traffic arrives, the decode program (the verify
        program under speculation) and the named prefill / extend buckets;
        returns the keys of the programs it compiled (those the engine
        already holds are left out). What ``_prefill_exe`` and its siblings
        would compile one after another on first use is traced here one
        program at a time (a trace swaps the model's parameters for its own,
        in place) and then compiled by the backend side by side, one thread
        a program: a big model's start-up is the backend's seconds, and they
        do not depend on one another. The accounting is ``_aot``'s: one
        ``compile`` span and one ``jit.compile.cache_miss{site=}`` a
        program."""
        from concurrent.futures import ThreadPoolExecutor

        keys = [("verify",) if self.spec is not None else ("decode",)]
        keys += [("prefill", int(T)) for T in prefill]
        keys += [("extend", int(T)) for T in extend]
        keys = [k for k in dict.fromkeys(keys) if k not in self._exe]

        def compile_one(item):
            key, site, lowered, traced_s = item
            with _span("compile", site=site, cache_hit=0) as sp:
                exe = lowered.compile()
            return key, site, exe, traced_s + sp.seconds

        with warnings.catch_warnings():
            warnings.filterwarnings(    # as in _aot
                "ignore", message=".*donated buffers.*", category=UserWarning)
            todo, done = [], []
            for key in keys:
                t0 = time.perf_counter()
                fn, args = getattr(self, key[0] + "_program")(*key[1:])
                lowered = jax.jit(fn, donate_argnums=self.donate_argnums_of(
                    key[0])).lower(*args)
                todo.append((key, _SITES[key[0]], lowered,
                             time.perf_counter() - t0))
            if todo:
                with ThreadPoolExecutor(len(todo)) as pool:
                    done = list(pool.map(compile_one, todo))
        for key, site, exe, seconds in done:
            _obs.record_compile(site, seconds=seconds, cache_hit=False)
            _obs_memory.record_executable(site, exe)
            self._exe[key] = exe
        return keys

    def _admit(self) -> int:
        """Admit waiting requests while slots are free; returns how many
        (each emits its first token)."""
        admitted = 0
        while self.cache.free_slots and self.scheduler.waiting:
            # PEEK before committing: admission can backpressure on the
            # page pool, leaving the head request queued until a finish
            # frees pages
            if not self._admit_one(self.scheduler.waiting[0]):
                break
            admitted += 1
        return admitted

    def _admit_one(self, req: Request) -> bool:
        """One admission, from the peek to the first token on the host, as
        one ``serving/admit`` span over its phases; False (the span and its
        ``alloc`` child say ``blocked=1``) when the page pool (or, for a
        model with recurrent state, the snapshot pool) is short and the
        request stays queued.

        A model with recurrent state resumes where a SNAPSHOT lies, not
        where the pages reach: of the ``hit_blocks`` the trie matched only
        the first ``snapshot_blocks`` (the deepest node on the path that
        carries a snapshot) are spliced, and every token behind it runs
        again into pages of the request's own. It takes a snapshot where
        the prompt left the cached path and at the prompt's last whole
        block (``serving/snapshot``). All of it is the ONE program an
        admission launches (the ``launch`` of its prefill or extend span):
        the extend starts from the snapshot's row and writes the new
        snapshots' rows from inside its scan (``_state_arg``); no row is
        copied. The sampler's eager ops behind it take the next number, on
        the ``serving/admit/sample`` span that also waits for them."""
        n = len(req.prompt_ids)
        owner = f"req{req.request_id}"
        ps = self.cache.page_size
        with _span("serving/admit", request_id=req.request_id,
                   prompt_tokens=n) as adm:
            hit_blocks, splice, hit_pages = 0, 0, [[]]
            if self.prefix_cache is not None:
                with _span("serving/admit/match",
                           request_id=req.request_id):
                    # (a model with window groups resumes where their
                    # pages still reach, ``splice``, not where the match
                    # ends: the rest runs again)
                    hit_blocks, splice, hit_pages = \
                        self.prefix_cache.match_groups(req.prompt_ids)
            source, cuts = None, []
            if self.snapshot_alloc is not None:
                # (with window groups beside the state ``match_groups`` has
                # cut the match back to the deepest depth that has BOTH the
                # windows' pages and a snapshot)
                splice, source = self.prefix_cache.deepest_snapshot(
                    req.prompt_ids, splice if self._windows else hit_blocks)
                # (the deepest first to go where the whole pool is smaller
                # than one admission's two)
                cuts = sorted({hit_blocks, n // ps} - {0, splice})[
                    -self.snapshot_alloc.num_allocatable:]
            elif self._stateful:
                splice = 0      # no snapshots: nothing to resume from
            with _span("serving/admit/alloc",
                       request_id=req.request_id) as alloc:
                evicted = 0
                if source is not None:
                    # hold the snapshot to resume from through the
                    # evictions below
                    self.snapshot_alloc.retain([source], owner=owner)
                # per page group, the blocks spliced from the trie and
                # the blocks mapped fresh
                plan = self._page_plan(n, hit_blocks, splice)
                pages, evicted = self._alloc_groups(
                    [len(fresh) for _, _, fresh in plan], owner)
                taken, dropped = [], 0
                if pages is not None and cuts:
                    before = self.prefix_cache.snapshots_dropped
                    taken = self.prefix_cache.reserve_snapshots(len(cuts),
                                                                owner)
                    # snapshots that left their nodes to make room
                    dropped = self.prefix_cache.snapshots_dropped - before
                if pages is None or taken is None:
                    for page_alloc, got in zip(self.page_allocs, pages or ()):
                        page_alloc.free(got, owner=owner)
                    if source is not None:
                        self.snapshot_alloc.free([source], owner=owner)
                    alloc.set(pages=0, evicted=evicted, blocked=1)
                    adm.set(blocked=1)
                    return False
                self.scheduler.next_waiting()  # pops the peeked head
                slot = self.cache.alloc_slot()
                req.slot = slot
                if hit_pages[0]:
                    req.prefix_hit_blocks = splice if self._windows \
                        else hit_blocks
                for g, ((lo, hi, fresh), got) in enumerate(zip(plan, pages)):
                    if hi > lo:
                        # the SPLICE: this request becomes one more sharer
                        # of the matched blocks' physical pages — a refcount
                        # bump and a table-row write, no device work for
                        # the prefix
                        self.page_allocs[g].retain(hit_pages[g][lo:hi],
                                                   owner=owner)
                        self.cache.assign_pages(slot, hit_pages[g][lo:hi],
                                                start_block=lo, group=g)
                    self.cache.assign_at(slot, fresh, got, group=g)
                alloc.set(pages=len(pages[0]), evicted=evicted)
            adm.set(queued_s=req.admit_time - req.arrival_time,
                    hit_blocks=hit_blocks)
            if self._shared_readers:
                # rows of the run that entered the layers behind the
                # model's cut (its cross-decoder): the last real token's
                # alone, where the model cuts
                cut = getattr(self.model.cfg, "cut_layer", None) is not None
                adm.set(cross_rows=1 if cut else n - splice * ps)
            if self._stateful:
                adm.set(snapshot_blocks=splice,
                        recomputed_tokens=(hit_blocks - splice) * ps)
            if self._windows:
                cut = (hit_blocks - splice) * ps
                adm.set(resume_blocks=splice, recomputed_tokens=cut)
                self.resume_cut_tokens += cut
                _metrics.counter("serving.prefix.resume_cut_tokens", cut)
            if self.prefix_cache is not None:
                if hit_blocks:
                    _metrics.counter("serving.prefix.hits", 1)
                    _metrics.histogram("serving.prefix.splice_seconds",
                                       alloc.seconds)
                else:
                    _metrics.counter("serving.prefix.misses", 1)
            sp = req.sampling
            # the prompt from the splice on: one program, whatever it
            # resumes from and whichever snapshots it takes
            snaps = list(zip(cuts, taken))
            logits = self._run_prompt(req, slot, splice * ps, n, source,
                                      snaps, plan[0][1])
            if source is not None:
                # its reader is enqueued: the hold on the snapshot goes
                with _span("serving/admit/restore",
                           request_id=req.request_id, blocks=splice):
                    self.snapshot_alloc.free([source], owner=owner)
            # eager ops (a greedy request's one argmax, a sampled one's key
            # and draw): one launch number for the stretch
            self._launch_i += 1
            with _span("serving/admit/sample", request_id=req.request_id,
                       launch=self._launch_i, eager=1,
                       waits_for=self._launch_i):
                if self.prefix_cache is not None:
                    # index this prompt's FULL blocks (shared ones are
                    # already nodes; fresh ones take a trie-owned reference
                    # and become matchable the moment the next prompt
                    # agrees), and hand their nodes the snapshots the
                    # program wrote
                    self.prefix_cache.insert(
                        req.prompt_ids,
                        self.cache.slot_pages(slot)[:n // ps],
                        self._rows_for_trie(slot, n // ps,
                                            [b for b, _ in snaps]))
                    for block, snap in snaps:
                        with _span("serving/snapshot",
                                   request_id=req.request_id, blocks=block,
                                   evicted=dropped, reason=(
                                       "prompt_end" if block == n // ps
                                       else "branch")):
                            self.prefix_cache.attach_snapshot(
                                req.prompt_ids, block, snap, owner)
                        dropped = 0     # counted once an admission
                key = _random.next_key() if sp.do_sample else _dummy_key()
                tok = int(np.asarray(_sampling.sample_static(
                    logits, key, do_sample=sp.do_sample,
                    temperature=sp.temperature, top_k=sp.top_k))[0])
            if self._windows:
                # what the trie was handed stays with it; the slot keeps
                # the window before its next token
                self._slide([(slot, n, owner)])
        # the first token's time is the end of its serving/admit span
        req.first_token_time = time.perf_counter()
        _metrics.histogram("serving.prefill.seconds", adm.seconds)
        _metrics.histogram("serving.ttft.seconds",
                           req.first_token_time - req.arrival_time)
        _metrics.counter("serving.tokens.generated", 1)
        if self.tracer is not None:
            self.tracer.on_prefill(req)
        self._slots[slot].request = req
        self._stale.update(_HOST_OPERANDS)
        self._from_host[slot] = True
        self._tokens[slot] = tok
        self._positions[slot] = n  # first generated token's index
        self._temps[slot] = sp.temperature
        self._top_ks[slot] = sp.top_k
        self._greedy[slot] = not sp.do_sample
        req.output_ids.append(tok)
        self._maybe_finish(req, tok)
        return True

    def _rows_for_trie(self, slot: int, blocks: int, snapshot_blocks):
        """What ``insert`` is handed of the further page groups: the slot's
        table rows as far as the prompt's whole ``blocks``. Beside recurrent
        state a depth can be resumed only where a snapshot lies, so of a
        window group the trie is handed the windows before the snapshots
        this admission took alone (-1 elsewhere): every other page would be
        held for nothing until its node is evicted."""
        rows = [self.cache.page_tables[g][slot, :blocks]
                for g in range(1, len(self.page_allocs))]
        if not (self._stateful and self._windows):
            return rows
        ps = self.cache.page_size
        for g, window in self._windows:
            back = (window + ps - 2) // ps
            keep = np.zeros((blocks,), bool)
            for b in snapshot_blocks:
                keep[max(0, b - back):b] = True
            rows[g - 1] = np.where(keep, rows[g - 1], PAGE_SENTINEL)
        return rows

    def _page_plan(self, n: int, hit: int, splice: int):
        """Per page group ``(lo, hi, fresh)`` for a prompt of ``n`` tokens
        that the trie matched ``hit`` blocks of and that resumes at block
        ``splice``: the blocks ``[lo, hi)`` are spliced from the trie, the
        blocks ``fresh`` mapped to pages of the request's own.

        A group that keeps every token splices ``[0, splice)`` and maps
        the rest up to the block of the first decode step, ``n // ps``. In
        a model with window groups a prompt that must start over (``splice``
        0) still SHARES such a group's matched pages, ``[0, hit)``: its
        prefill writes nothing there (``_run_prompt``'s ``shared``).

        A window group splices the window before the resume point; behind
        it an extend maps every block it writes (it reads them back through
        the table: they go when it has run, ``_slide``); a prefill, whose
        keys never pass through the pool, maps only what is wanted
        afterwards: the blocks a resume at the prompt's end looks back on
        (the live window is among them) and, where the prompt left a
        cached path it could not resume (``hit`` > 0), the window before
        that point, for the trie (``insert``), so that the next prompt
        that parts there can."""
        ps = self.cache.page_size
        last = n // ps
        plan = []
        for _, window, _ in self.cache.groups:
            if not window:
                hi = hit if self._windows and not splice else splice
                plan.append((0, hi, list(range(hi, last + 1))))
                continue
            back = (window + ps - 2) // ps
            if splice:
                fresh = range(splice, last + 1)
            else:
                fresh = sorted(set(range(max(0, last - back), last + 1))
                               | set(range(max(0, hit - back), hit)))
            plan.append((max(0, splice - back), splice, list(fresh)))
        return plan

    def _alloc(self, group: int, k: int, owner: str):
        """``k`` fresh pages of a page group: ``(pages or None, trie nodes
        evicted)``. Where the pool is short the least recently used cached
        prefixes are reclaimed first, and the allocation tried again."""
        page_alloc = self.page_allocs[group]
        pages, evicted = page_alloc.alloc(k, owner=owner), 0
        if pages is None and self.prefix_cache is not None:
            evicted = self.prefix_cache.evict_lru(k, group)
            pages = page_alloc.alloc(k, owner=owner)
        return pages, evicted

    def _alloc_groups(self, need: Sequence[int], owner: str):
        """``need[g]`` fresh pages of each page group, all or nothing:
        ``(pages by group, trie nodes evicted)``; where a pool is short the
        least recently used cached prefixes go first (``evict_lru``), and
        ``(None, evicted)`` (nothing held) where that is not enough."""
        got, evicted = [], 0
        for g, k in enumerate(need):
            pages, gone = self._alloc(g, k, owner)
            evicted += gone
            if pages is None:
                for a, held in zip(self.page_allocs, got):
                    a.free(held, owner=owner)
                return None, evicted
            got.append(pages)
        return got, evicted

    def _slide(self, slots: Sequence[Tuple[int, int, str]]):
        """The sliding rule, the one place: for each ``(slot, position of
        its next token, owner)``, every window group's blocks that lie
        wholly before ``position - window + 1`` are unmapped from the slot
        and its reference on their pages dropped (a page the trie holds
        too lives on there). One ``serving/window/slide`` span, and only
        where something goes."""
        ps = self.cache.page_size
        todo = [(slot, g, first, owner)
                for slot, pos, owner in slots for g, w in self._windows
                for first in [max(0, pos - w + 1) // ps]
                if first > self._win_from[g][slot]]
        if not todo:
            return
        with _span("serving/window/slide", slots=len(todo)) as sp:
            freed = 0
            for slot, g, first, owner in todo:
                pages = self.cache.unmap_before(slot, first, g)
                self.page_allocs[g].free(pages, owner=owner)
                self._win_from[g][slot] = first
                freed += len(pages)
            sp.set(freed=freed)
        self.window_pages_freed += freed
        _metrics.counter("serving.window.pages_freed", freed)
        _metrics.gauge("serving.window.pages_live", sum(
            self.page_allocs[g].num_allocated for g, _ in self._windows))

    def _run_prompt(self, req: Request, slot: int, start: int, end: int,
                    source: Optional[int] = None,
                    snaps: Sequence[Tuple[int, int]] = (), shared: int = 0):
        """Tokens ``[start, end)`` of the request's prompt through the
        bucketed prefill program (from position 0) or, behind what the slot
        already holds, the extend program (the suffix-only prefill; >= 1
        token by construction: matching is capped at (n-1)//ps blocks): the
        last token's logits ``[1, V]``. A model with recurrent state starts
        from snapshot ``source`` and writes its state after ``block`` whole
        blocks to snapshot ``id``'s row, for each ``(block, id)`` of
        ``snaps``. Each page group's row of the slot's goes with it; a
        prefill over ``shared`` blocks that the slot shares with the trie
        (a prompt that starts over in a model with window groups) is
        handed the first group's row WITHOUT them: it writes nothing
        there."""
        m = end - start
        T = self._bucket(m)
        kind = "extend" if start else "prefill"
        self._launch_i += 1
        with _span("serving/admit/" + kind, request_id=req.request_id,
                   tokens=m, bucket=T, start=start, launch=self._launch_i):
            ids = np.zeros((1, T), np.int32)
            ids[0, :m] = req.prompt_ids[start:end]
            # host scalars: ``jnp.int32(m)`` is a program of its own on the
            # device (a ``convert_element_type`` run an operand)
            where = (np.int32(start), np.int32(m)) if start \
                else (np.int32(m),)
            rows = [t[slot] for t in self.cache.page_tables]
            if shared and not start:
                rows[0] = rows[0].copy()
                rows[0][:shared] = PAGE_SENTINEL
            logits, *self.cache.pools = self._held(kind, T)(
                self.params, *self.cache.pools, jnp.asarray(ids),
                *map(jnp.asarray, rows), *where,
                *self._state_arg(slot, source, [
                    (block * self.cache.page_size - start, snap)
                    for block, snap in snaps]))
        return logits

    def _ensure_writable(self, slot: int, block: int, owner: str,
                         group: int = 0) -> bool:
        """Copy-on-write guard: a slot about to WRITE ``block`` must own its
        page exclusively. By construction the engine never maps a shared
        page at a position it writes (prefix matching is capped below the
        suffix, and decode/draft writes land strictly after the prompt),
        so this is a defensive invariant-keeper — but if a shared page IS
        in the write path, the slot gets a private byte-copy first and
        drops its reference on the original, so the other sharers never
        observe the write. False = no page free for the copy."""
        page_alloc = self.page_allocs[group]
        page = int(self.cache.page_tables[group][slot, block])
        if page == PAGE_SENTINEL or not page_alloc.is_shared(page):
            return True
        fresh, _ = self._alloc(group, 1, owner)
        if fresh is None:
            return False
        self._launch_i += 1
        self.cache.copy_page(page, fresh[0], group)
        self._cow_copies += 1
        self.cache.repoint(slot, block, fresh[0], group)
        page_alloc.free([page], owner=owner)
        return True

    def _grow_pages(self, width: int = 1, wait: bool = False,
                    ending: Sequence[Request] = ()) -> bool:
        """Before a decode step, make sure every running slot has private
        writable pages mapped for the ``width`` positions it may write
        (1 for plain decode, ``k+1`` for speculative verify — positions
        past the sequence budget route to the trash page in-graph and need
        no mapping), from the position mirror: the position the step about
        to be launched writes. First the requests of ``ending`` (``_ending``:
        the step in flight makes their last token) are released, so they
        take no page and leave theirs to the others.

        A slot that can't grow finishes ``cache_full`` (its generated prefix
        is intact) — the pages it frees may already unblock the next slot or
        the next waiting request. Under ``wait`` (a step is in flight) it
        does not: the first slot that can't grow ends the pass, False comes
        back, and the caller settles the step in flight, whose finishes may
        free the page, before it grows again."""
        ps, S_max = self.cache.page_size, self.config.max_seq_len
        allocated = cache_full = 0
        short = False
        moved = []      # (slot, position, owner) of a model with windows
        cow_before = self._cow_copies
        first = self._launch_i + 1  # of the copies' launches, if any
        with _span("serving/decode/grow_pages") as sp:
            for req in ending:
                self._release(req)
            for slot, st in enumerate(self._slots):
                req = st.request
                if req is None:
                    continue
                owner = f"req{req.request_id}"
                p = int(self._positions[slot])
                last = min(p + width - 1, S_max - 1)
                ok = True
                for g, table in enumerate(self.cache.page_tables):
                    for block in range(p // ps, last // ps + 1):
                        if table[slot, block] == PAGE_SENTINEL:
                            pages, _ = self._alloc(g, 1, owner)
                            if pages is None:
                                ok = False
                                break
                            self.cache.assign_pages(slot, pages,
                                                    start_block=block, group=g)
                            allocated += 1
                        elif not self._ensure_writable(slot, block, owner, g):
                            ok = False
                            break
                    if not ok:
                        break
                if not ok and wait:
                    short = True
                    break
                if not ok:
                    self._finish(req, "cache_full")
                    cache_full += 1
                elif self._windows:
                    moved.append((slot, p, owner))
            sp.set(allocated=allocated, cache_full=cache_full,
                   cow_copies=self._cow_copies - cow_before)
            if ending:
                sp.set(released=len(ending))
            if self._launch_i >= first:
                sp.set(launch=first, launches=self._launch_i - first + 1)
            if short:
                sp.set(short=1)
                return False
        if moved:
            self._slide(moved)
        return True

    def _ending(self, flight: Optional[_Flight]) -> List[Request]:
        """The requests that the step in flight finishes whatever token it
        makes, which the host knows by COUNT before it has the token: the
        token is the request's ``max_new_tokens``-th, or fills the sequence
        budget (``_maybe_finish``)."""
        if flight is None:
            return []
        S_max = self.config.max_seq_len
        return [req for req, _ in flight.rows if req.state != FINISHED
                and (req.num_generated + 1 >= req.sampling.max_new_tokens
                     or len(req.prompt_ids) + req.num_generated + 1 >= S_max)]

    def _decode(self) -> int:
        """One batched decode step, as one ``serving/decode`` span over its
        phases; returns the tokens emitted.

        **The plain step is launched one step AHEAD of its fetch.** A call
        launches step N (``grow_pages``, ``upload``, ``dispatch``) and only
        then fetches and settles step N - 1, which the call before left in
        flight (``_flight``): the device has step N queued behind N - 1 and
        starts it the moment that ends, so neither the launch nor the last
        step's copy-out nor the host's settle is time the device waits. The
        host knows everything about step N but the token VALUES:

        - the position mirror moves when a step is LAUNCHED, as the
          program's ``next_positions`` does, so ``_grow_pages``, ``_slide``
          and the span's ``ctx_tokens`` read the position step N writes;
        - ``output_ids``, ``_tokens``, ``_maybe_finish`` and the scheduler's
          finish move at settle, one step behind. A finish the host can
          COUNT (``_ending``: the token in flight is the request's last by
          ``max_new_tokens`` or by the sequence budget) takes the row out of
          step N: its slot and pages are released BEFORE the launch
          (``_release``), so step N sees a dead slot there as it always did
          behind a finish: no page grown, no draw, and no step at all where
          no other row lives. An ``eos`` cannot be counted: that row runs
          once more, its token is DROPPED at settle (``dropped``), its K/V
          lands at its own next position, its state row is overwritten by
          the next admission's program, and its pages may go at once because
          every later program is queued behind the step;
        - the tokens the host does decide reach the program as
          ``host_tokens`` (``_OPERANDS``), never as a whole ``tokens`` put;
        - where the pool has no page for a row while a step is in flight,
          that step is settled FIRST (its finishes may free the page) and
          the pass made again: a short pool runs in the old order.

        What describes one launched step stays together: ``running``,
        ``draws``, ``ctx_tokens`` and the rest are kept with the step in
        flight and set, with the model's ``step_stats``, on the
        ``serving/decode`` span of the call that settles it.

        Under speculation the step is the verify-k program: propose ``k``
        n-gram drafts per row (``serving/decode/propose``), run the ONE
        verify executable over the static ``[B, k+1]`` block, then settle
        per row on the host — greedy rows keep the longest draft prefix the
        model's argmax agrees with plus the model's own token at the
        divergence (1..k+1 tokens, exactly the one-at-a-time greedy
        stream), sampled rows emit position 0's sampled token. Rejected
        drafts cost nothing: their K/V sits at positions the next verify
        step overwrites before attending, so rollback is just NOT advancing
        ``_positions`` past the kept tokens. The host picks the next step's
        tokens and positions from the fetched ones, so a verify step is
        launched, fetched and settled in ONE call and nothing is in flight
        between calls: the order follows ``self.spec``, nothing else.

        The step's operands (``_OPERANDS`` and the page table) stay on the
        device between steps; the decode program makes on the device the
        update the host makes on its mirrors (its ``next_tokens`` /
        ``next_positions`` outputs, handed back as the next step's
        arguments). ``_admit_one`` and ``_finish`` mark the four operands
        the host is the authority for and the slot's ``host_tokens`` row,
        the cache's table writers drop its kept copy, and a verify step
        marks tokens and positions. ``upload`` puts exactly what is marked —
        on most plain steps nothing."""
        k = 0 if self.spec is None else self.spec.k
        due, self._flight = self._flight, None
        emitted = 0
        with _span("serving/decode", step=self._step_i) as sp:
            if not self._grow_pages(k + 1, wait=due is not None,
                                    ending=self._ending(due)):
                emitted += self._settle(due, sp)
                due = None
                self._grow_pages(k + 1)
            flight = self._launch(sp, ahead=due is not None)
            if self.spec is None:
                self._flight = flight
            else:
                due = flight
            if due is not None:
                emitted += self._settle(due, sp)
            elif flight is None and not emitted:
                sp.set(running=0)
        return emitted

    def _launch(self, sp, ahead: bool) -> Optional[_Flight]:
        """Upload what the host changed and call the decode (verify)
        program for every slot that holds a request; None, and nothing
        launched, where none does. ``sp``: the ``serving/decode`` span
        (false with tracing off: the step's attributes are then not
        computed)."""
        spec = self.spec
        B = len(self._slots)
        rows = [(s.request, slot) for slot, s in enumerate(self._slots)
                if s.request is not None]
        if not rows:
            return None
        slots = [slot for _, slot in rows]
        # live rows that are not greedy (a dead slot's row reads greedy):
        # with none the program's sampler runs its argmax alone
        draws = B - int(np.count_nonzero(self._greedy))
        if draws:
            self.sampler_steps_draw += 1
        else:
            self.sampler_steps_argmax += 1
        attrs = {"running": len(rows)}
        if sp:
            # cached tokens the step's attention may read (each row's
            # context, the token it writes included) and how many of them
            # it does read, where the model selects; pages the paged-decode
            # kernel's loops walk (a layer) this step, of the table entries
            # a grid over the table would
            ctx = self._positions[slots] + 1
            sel = getattr(self.model, "selected_tokens", None)
            end = (ctx - 1) // self.cache.page_size     # a slot's last page
            attrs.update(draws=draws, ctx_tokens=int(ctx.sum()),
                         selected_tokens=int((ctx if sel is None
                                              else sel(ctx)).sum()),
                         live_pages=int((end + 1).sum()),
                         table_pages=B * self.cache.num_blocks)
            if "latent_tokens_read" in getattr(self.model, "step_stats", ()):
                # the live pages counted ONCE each, however many slots map
                # them (sessions on one document): what a step's attention
                # has to bring in, a layer
                table = self.cache.page_table[slots]
                mapped = np.arange(table.shape[1])[None, :] <= end[:, None]
                seen = np.zeros((self.cache.num_pages,), bool)
                seen[table[mapped]] = True
                attrs.update(distinct_pages=int(seen.sum()))
            if self._windows:
                # the window groups' pages that are mapped or cached, of
                # those they have
                held = [self.page_allocs[g] for g, _ in self._windows]
                attrs.update(
                    window_pages_live=sum(a.num_allocated for a in held),
                    window_pages=sum(a.num_allocatable for a in held))
        tokens, step_s, drafts = self._tokens, 0.0, None
        if spec is not None:
            k = spec.k
            with _span("serving/decode/propose") as prop:
                tokens = np.zeros((B, k + 1), np.int32)
                drafts = {}
                for req, slot in rows:
                    drafts[slot] = propose_ngram(
                        req.prompt_ids + req.output_ids, k, spec.ngram)
                    tokens[slot, 0] = self._tokens[slot]
                    tokens[slot, 1:] = drafts[slot]
            step_s = prop.seconds
        with _span("serving/decode/upload") as up:
            if not draws:
                key = _dummy_key()
            else:
                # the key's eager ops: one launch number
                self._launch_i += 1
                up.set(launch=self._launch_i, eager=1)
                key = _random.next_key()
            # put what the host changed, in one call and from copies
            # (a put may alias host memory the mirrors go on changing);
            # everything else is the array the device already holds
            table_put = int(self.cache.table_changed)
            tables = self.cache.tables_device()
            stale = {name: (tokens if name == "tokens"
                            else getattr(self, "_" + name)).copy()
                     for name in self._stale}
            if spec is None and self._from_host.any():
                stale["host_tokens"] = np.where(
                    self._from_host, self._tokens, -1).astype(np.int32)
                self._from_host[:] = False
            if stale:
                self._dev.update(jax.device_put(stale))
                self._stale.clear()
            if up:
                up.set(puts=len(stale) + table_put, table_put=table_put)
            args = (*tables, *(self._dev[name] for name in _OPERANDS),
                    *((self._dev["host_tokens"],) if spec is None else ()),
                    key)
        self._launch_i += 1
        if ahead:
            self.steps_ahead += 1
        else:
            self.steps_drained += 1
        with _span("serving/decode/dispatch", launch=self._launch_i,
                   ahead=int(ahead)) as disp:
            exe = self._decode_exe() if spec is None else self._verify_exe()
            out = exe(self.params, *self.cache.pools, *args)
            sampled0 = None
            if spec is None:
                # behind what the host fetches come the next step's
                # tokens and positions (their inputs were donated),
                # then the pools; the mirror of the positions moves with
                # the launch
                toks, self._dev["tokens"], self._dev["positions"], \
                    *self.cache.pools = out
                self._positions[slots] += 1
                # the host's tokens went once
                self._dev["host_tokens"] = self._no_host_tokens
            else:
                # the argmax targets and position 0's sample; the host
                # decides the next tokens and positions in settle
                toks, sampled0, *self.cache.pools = out
                self._stale.update(("tokens", "positions"))
        return _Flight(self._launch_i, toks, rows, attrs,
                       step_s + up.seconds + disp.seconds, sampled0, drafts)

    def _settle(self, flight: _Flight, sp) -> int:
        """Fetch a launched step's tokens and settle them per request:
        append, ``_maybe_finish``; a row whose request has FINISHED since
        the launch is dropped. The step's attributes and the model's
        ``step_stats`` go on ``sp``, the ``serving/decode`` span of this
        call. Returns the tokens emitted."""
        spec = self.spec
        B = len(self._slots)
        with _span("serving/decode/fetch", waits_for=flight.launch) as fetch:
            toks = np.asarray(flight.out)
            sampled0 = toks if spec is None else np.asarray(flight.sampled0)
        if sp:
            sp.set(**flight.attrs)
            if spec is None and toks.shape[0] > B:
                # a model that counts in its step (decoder.DecoderLM: per
                # layer the distinct experts routed to, the largest
                # expert's rows) sent its counts behind the tokens
                stats = toks[B:].reshape(self.model.cfg.num_layers, -1)
                sp.set(**{name: stats[:, i].tolist() for i, name in
                          enumerate(self.model.step_stats)})
        step_s = flight.seconds + fetch.seconds
        _metrics.histogram("serving.decode.step.seconds", step_s)
        emitted_total = drafted = accepted = dropped = 0
        with _span("serving/decode/settle") as settle:
            before = len(self.scheduler.running)
            for req, slot in flight.rows:
                if req.state == FINISHED:
                    dropped += 1
                    continue
                if spec is not None and self._greedy[slot]:
                    k = spec.k
                    a, emitted = accept_greedy(flight.drafts[slot],
                                               toks[slot])
                    req.draft_tokens += k
                    req.accepted_tokens += a
                    drafted += k
                    accepted += a
                    self._spec_slots += k + 1
                    self._spec_emitted += len(emitted)
                else:
                    emitted = [sampled0[slot]]
                self.scheduler.observe_decode_step(req, step_s)
                if self.tracer is not None:
                    self.tracer.on_decode_step(req)
                for tok in emitted:
                    tok = int(tok)
                    req.output_ids.append(tok)
                    if self._slots[slot].request is req:    # not released
                        self._tokens[slot] = tok
                    if spec is not None:
                        self._positions[slot] += 1
                    emitted_total += 1
                    self._maybe_finish(req, tok)
                    if req.state == FINISHED:
                        break
            settle.set(finished=before - len(self.scheduler.running),
                       dropped=dropped)
        _metrics.counter("serving.tokens.generated", emitted_total)
        if dropped:
            self.dropped_rows += dropped
            _metrics.counter("serving.decode.dropped_rows", dropped)
        if drafted:
            self._spec_drafted += drafted
            self._spec_accepted += accepted
            _metrics.counter("serving.spec.draft_tokens", drafted)
            _metrics.counter("serving.spec.accepted_tokens", accepted)
            _metrics.gauge("serving.spec.accept_rate",
                           self._spec_emitted / self._spec_slots)
        return emitted_total

    def _maybe_finish(self, req: Request, tok: int):
        sp = req.sampling
        reason = None
        if sp.eos_token_id is not None and tok == sp.eos_token_id:
            reason = "eos"
        elif req.num_generated >= sp.max_new_tokens:
            reason = "length"
        elif len(req.prompt_ids) + req.num_generated >= self.config.max_seq_len:
            reason = "cache_full"  # next token would fall off the cache
        if reason is None:
            return
        self._finish(req, reason)

    def _finish(self, req: Request, reason: str):
        self.scheduler.finish(req, reason)
        if self.tracer is not None:
            self.tracer.on_finish(req)
        if self._slots[req.slot].request is req:
            self._release(req)

    def _release(self, req: Request):
        """Give back what a request holds on the device: its slot, the
        slot's rows of the decode operands (token 0, position 0, a greedy
        row: what a dead slot reads) and its references on the pages its
        slot mapped. At its finish or, where the host can COUNT the finish
        before it has the token (``_ending``), before the next step is
        launched: that step then runs without the row. The pages may go
        while a step that reads them is still in flight, because every later
        program is queued behind it."""
        slot = req.slot
        self._slots[slot].request = None
        self._stale.update(_HOST_OPERANDS)
        self._from_host[slot] = True
        self._tokens[slot] = 0
        self._positions[slot] = 0
        self._temps[slot] = 1.0
        self._top_ks[slot] = 0
        self._greedy[slot] = True
        # drop this request's reference on every page its slot mapped —
        # pages the prefix cache (or another sharer) still references stay
        # live; the rest return to the pool. The allocator raises on
        # double-free (naming page ids and owners), so leaks and corruption
        # can't pass silently. clear_slot is idempotent: a second call
        # returns [] and frees nothing.
        for g, page_alloc in enumerate(self.page_allocs):
            page_alloc.free(self.cache.clear_slot(slot, g),
                            owner=f"req{req.request_id}")
        for first in self._win_from.values():
            first[slot] = 0
        self.cache.free_slot(slot)
