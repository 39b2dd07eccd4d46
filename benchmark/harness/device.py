"""The device gate, the table of peaks, compile counting and memory."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(SystemExit):
    pass


def gate(chips: int):
    """The devices this cell runs on. Anything but ``chips`` TPU chips (or
    more) is an error before any work: no CPU fallback, no interpret mode."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: jax found no backend: {e}", file=sys.stderr)
        raise NoChip(2)
    d0 = devices[0]
    if d0.platform != "tpu" or len(devices) < chips:
        print(f"benchmark: this cell needs {chips} TPU chip(s); jax found "
              f"{len(devices)} x {d0.platform} {d0.device_kind!r}. Not "
              "falling back: failing.", file=sys.stderr)
        raise NoChip(2)
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    table = json.load(open(os.path.join(HERE, "peaks.json")))
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks recorded for device_kind {device_kind!r}; "
                       "add a row with its source to benchmark/harness/"
                       "peaks.json")
    return table[device_kind]


class CompileLog:
    """Counts what jax compiled and what its persistent cache answered
    (jax.monitoring listeners; copied from chip_smoke.CompileLog, PR 21)."""

    def __init__(self):
        import jax

        self.requests = self.hits = self.misses = 0
        self.compile_seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.compile_seconds += seconds

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"requests": self.requests, "cache_hits": self.hits,
                "cache_misses": self.misses,
                "compile_seconds": round(self.compile_seconds, 3)}


def memory_peak_bytes(devices) -> int:
    """Allocator peak on the fullest chip (it does not see a program's
    temporaries on this runtime — hbm_fill.* reads the compiler instead)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def bytes_limit(devices) -> int:
    return min(int((d.memory_stats() or {}).get("bytes_limit", 0))
               for d in devices)


def executable_bytes(exe) -> dict:
    """Arguments, outputs, temporaries and aliased bytes of one compiled
    program, per device, as the TPU compiler counted them."""
    ma = exe.memory_analysis()
    return {"argument": int(ma.argument_size_in_bytes),
            "output": int(ma.output_size_in_bytes),
            "temp": int(ma.temp_size_in_bytes),
            "alias": int(ma.alias_size_in_bytes)}
