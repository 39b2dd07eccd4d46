"""Runtime kernel autotuning (phi/kernels/autotune: cache.h AlgorithmsCache,
auto_tune_base.h AutoTuneBase::PickBestAlgorithm, switch_autotune.cc).

Reference behavior: the first executions of a tunable op time every candidate
algorithm (cuDNN conv algos, transpose tilings), cache the winner keyed by the
op's shape/dtype signature, and later executions hit the cache. TPU re-design:
the tunables are Pallas grid/block configurations (block_q/block_k for flash
attention, tile sizes for norms) — XLA owns everything else. The cache
persists as JSON (autotune.json under core.cache.cache_root(), beside the
compile cache) so tuning cost is paid once per checkout, mirroring the
reference's process-lifetime cache but surviving restarts (compile times on
TPU make re-tuning much more expensive than re-running a cuDNN search). It
is consulted only while autotune is enabled: with tuning off, no file can
change a block size.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "AutoTuneCache", "enable_autotune", "disable_autotune", "set_config",
    "autotune_status", "pick_best",
]

_state = {
    "enabled": False,
    "measure_repeats": 3,
    "persist": True,
}
_lock = threading.RLock()


def _cache_path() -> str:
    from ..core.cache import cache_root

    return (os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")
            or os.path.join(cache_root(), "autotune.json"))


class AutoTuneCache:
    """(kernel, signature) -> winning config, with hit/miss stats
    (cache.h AlgorithmsCache + autotune_status analog)."""

    def __init__(self):
        self._data: Dict[str, Dict[str, Any]] = {}
        self._hits = 0
        self._misses = 0
        self._loaded = False

    def _ensure_loaded(self):
        if self._loaded:
            return
        self._loaded = True
        path = _cache_path()
        try:
            with open(path) as f:
                disk = json.load(f)
            if isinstance(disk, dict):
                for k, v in disk.items():
                    if isinstance(v, dict):  # tolerate corrupt/old entries
                        self._data.setdefault(k, {}).update(v)
        except (OSError, ValueError):
            pass

    def get(self, kernel: str, key: str):
        with _lock:
            self._ensure_loaded()
            got = self._data.get(kernel, {}).get(key)
            if got is None:
                self._misses += 1
            else:
                self._hits += 1
            return got

    def put(self, kernel: str, key: str, config):
        with _lock:
            self._ensure_loaded()
            self._data.setdefault(kernel, {})[key] = config
            if _state["persist"]:
                self._save()

    def _save(self):
        path = _cache_path()
        try:
            # merge under what's on disk (ours wins) so clear() + put() can
            # never wipe configs tuned by other processes/sessions
            merged: Dict[str, Dict[str, Any]] = {}
            try:
                with open(path) as f:
                    disk = json.load(f)
                if isinstance(disk, dict):
                    merged.update({k: dict(v) for k, v in disk.items()
                                   if isinstance(v, dict)})
            except (OSError, ValueError):
                pass
            for k, v in self._data.items():
                merged.setdefault(k, {}).update(v)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(merged, f)
            os.replace(tmp, path)
        except OSError:
            pass  # cache is best-effort

    def clear(self):
        with _lock:
            self._data.clear()
            self._hits = self._misses = 0
            self._loaded = True  # don't resurrect from disk

    def size(self) -> int:
        with _lock:
            return sum(len(v) for v in self._data.values())

    def stats(self) -> Dict[str, float]:
        with _lock:
            total = self._hits + self._misses
            return {"hits": self._hits, "misses": self._misses,
                    "hit_rate": self._hits / total if total else 0.0,
                    "size": self.size()}


cache = AutoTuneCache()


def enable_autotune():
    _state["enabled"] = True


def disable_autotune():
    _state["enabled"] = False


def set_config(config: Optional[dict] = None):
    """paddle.incubate.autotune.set_config contract: {"kernel": {"enable":
    bool, ...}}; unknown sections are ignored (dataloader/layout tuning have
    no TPU meaning — XLA owns layout)."""
    if config is None:
        _state["enabled"] = True
        return
    if isinstance(config, str):  # reference contract: path to a JSON file
        with open(config) as f:
            config = json.load(f)
    kernel_cfg = config.get("kernel", {})
    if "enable" in kernel_cfg:
        _state["enabled"] = bool(kernel_cfg["enable"])
    if "repeats" in kernel_cfg:
        _state["measure_repeats"] = max(1, int(kernel_cfg["repeats"]))
    if "persist" in kernel_cfg:
        _state["persist"] = bool(kernel_cfg["persist"])


def autotune_status() -> Dict[str, Any]:
    s = dict(cache.stats())
    s["enabled"] = _state["enabled"]
    return s


def enabled() -> bool:
    return _state["enabled"]


def _measure(fn: Callable[[], Any]) -> float:
    """Median wall time of fn() ended by ``block_until_ready``
    (PickBestAlgorithm timing)."""
    import jax

    jax.block_until_ready(fn())  # warmup (compile)
    times = []
    for _ in range(_state["measure_repeats"]):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def pick_best(kernel: str, key: Sequence, candidates: List,
              make_run: Callable[[Any], Callable[[], Any]],
              default=None):
    """Return the best config for (kernel, key).

    - autotune disabled -> ``default`` (heuristic path: nothing measured,
      no cache consulted)
    - cache hit -> cached winner
    - else time every candidate via ``make_run(config)() -> output`` and
      cache the fastest (exceptions disqualify a candidate).
    """
    if not _state["enabled"] or not candidates:
        return default if default is not None else (candidates[0] if candidates else None)
    skey = json.dumps(list(key))
    hit = cache.get(kernel, skey)
    if hit is not None:
        return tuple(hit) if isinstance(hit, list) else hit
    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            t = _measure(make_run(cand))
        except Exception:
            continue
        if t < best_t:
            best, best_t = cand, t
    if best is None:
        return default
    cache.put(kernel, skey, best)
    return best
