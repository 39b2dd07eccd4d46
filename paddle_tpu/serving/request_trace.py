"""Per-request serving traces + SLO monitor.

Every request already carries a ``request_id`` (scheduler.Request); this
module follows it through the engine as spans — queue → prefill → decode →
finish — and appends ONE JSON line per finished (sampled) request to a
per-host file, the serving analog of the metrics exporter's
``metrics-host*.jsonl``:

    <directory>/requests-host<NNNNN>.jsonl
    {"schema": "paddle_tpu.requests.v1", "host": 0, "request_id": 7,
     "ts": <finish wall clock>, "prompt_tokens": 128, "generated_tokens":
     64, "finish_reason": "length", "ttft_s": ..., "tpot_s": ...,
     "spans": [{"name": "queue", "start_s": 0.0, "dur_s": ...},
               {"name": "prefill", ...},
               {"name": "decode", ..., "steps": 63, "max_step_s": ...},
               {"name": "finish", "start_s": ..., "dur_s": 0.0}],
     "slo_violations": ["tpot"]}

Span times are relative to the request's arrival (host perf counter), so
a trace line reads as a self-contained timeline. Host-aggregate
histograms (ttft/tpot percentiles) cannot answer "what happened to
request 93712" — this file can, and ``sample_every`` keeps it bounded
under production rates.

The SLO monitor rides the same hooks: configurable TTFT / TPOT /
per-decode-step targets, ``serving.slo.violations{phase=...}`` counters,
and — because a violation is exactly the moment you want forensics — the
full per-request trace is dropped into the flight recorder's ring
(``observability.flight_recorder.record_event``), sampled or not.
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from ..observability import flight_recorder as _flight
from ..observability import metrics as _metrics
from ..observability.export import _default_host

SCHEMA = "paddle_tpu.requests.v1"

#: trace span names, in lifecycle order
PHASES = ("queue", "prefill", "decode", "finish")


def request_trace_path(directory: str, host: int) -> str:
    return os.path.join(directory, f"requests-host{host:05d}.jsonl")


@dataclass(frozen=True)
class SLOConfig:
    """Latency targets, in seconds. ``decode_step_target_s`` flags
    mid-request stalls (one decode step far over the inter-token budget —
    invisible to the finish-time TPOT, which averages over the request);
    it defaults to 4x the TPOT target."""

    ttft_target_s: float = 0.5
    tpot_target_s: float = 0.05
    decode_step_target_s: Optional[float] = None

    @property
    def step_target_s(self) -> float:
        if self.decode_step_target_s is not None:
            return self.decode_step_target_s
        return 4.0 * self.tpot_target_s

    def as_dict(self) -> Dict[str, float]:
        return {"ttft_target_s": self.ttft_target_s,
                "tpot_target_s": self.tpot_target_s,
                "decode_step_target_s": self.step_target_s}


class RequestTracer:
    """Span collector + per-host JSONL writer + SLO checks.

    The engine drives the lifecycle hooks; everything here is host-side
    bookkeeping (dict updates per token), no device interaction. With
    ``directory=None`` no file is written — SLO accounting still runs.
    ``sample_every=N`` writes every Nth finished request (the first
    sampled); SLO-violating requests always reach the flight recorder
    regardless of sampling.
    """

    def __init__(self, directory: Optional[str] = None,
                 host: Optional[int] = None, sample_every: int = 1,
                 slo: Optional[SLOConfig] = None):
        self.directory = directory
        self.host = _default_host() if host is None else int(host)
        self.path = (request_trace_path(directory, self.host)
                     if directory else None)
        self.sample_every = max(1, int(sample_every))
        self.slo = slo
        self._lock = threading.Lock()
        self._live: Dict[int, Dict[str, Any]] = {}
        self._finished = 0
        self._written = 0
        self._violation_counts: Dict[str, int] = {}

    # -- lifecycle hooks (engine-driven) --
    def on_queued(self, req) -> None:
        self._live[req.request_id] = {
            "arrival": req.arrival_time,
            "prompt_tokens": len(req.prompt_ids),
            "decode_steps": 0,
            "decode_total_s": 0.0,
            "decode_max_s": 0.0,
            "violations": [],
        }

    def on_prefill(self, req) -> None:
        """The first token is on the host: ``req.admit_time`` and
        ``req.first_token_time`` are set."""
        tr = self._live.get(req.request_id)
        if tr is None:
            return
        tr["admit"] = req.admit_time
        tr["first_token"] = tr["last_token"] = req.first_token_time
        if self.slo is not None:
            ttft = req.first_token_time - req.arrival_time
            if ttft > self.slo.ttft_target_s:
                self._violate(req, tr, "ttft", ttft)

    def on_decode_step(self, req) -> None:
        """A decode step's tokens for ``req`` are on the host. The step's
        seconds are this request's own: since its previous tokens, so an
        admission that held the batch between two steps counts."""
        tr = self._live.get(req.request_id)
        if tr is None:
            return
        now = time.perf_counter()
        seconds, tr["last_token"] = now - tr["last_token"], now
        tr["decode_steps"] += 1
        tr["decode_total_s"] += seconds
        if seconds > tr["decode_max_s"]:
            tr["decode_max_s"] = seconds
        if self.slo is not None and seconds > self.slo.step_target_s:
            self._violate(req, tr, "decode_step", seconds)

    def on_finish(self, req) -> None:
        tr = self._live.pop(req.request_id, None)
        if tr is None:
            return
        tpot = None
        if req.first_token_time is not None and req.num_generated > 1:
            tpot = ((req.finish_time - req.first_token_time)
                    / (req.num_generated - 1))
        if (self.slo is not None and tpot is not None
                and tpot > self.slo.tpot_target_s):
            self._violate(req, tr, "tpot", tpot)
        record = self._record(req, tr, tpot)
        if tr["violations"]:
            _flight.record_event({"kind": "slo_violation", **record})
        self._finished += 1
        if self.path is not None and (self._finished - 1) % self.sample_every == 0:
            self._write(record)

    # -- internals --
    def _violate(self, req, tr: Dict[str, Any], phase: str,
                 seconds: float) -> None:
        if phase not in tr["violations"]:
            tr["violations"].append(phase)
        self._violation_counts[phase] = \
            self._violation_counts.get(phase, 0) + 1
        _metrics.counter("serving.slo.violations", 1, phase=phase)
        _metrics.histogram("serving.slo.excess_seconds",
                           seconds - {"ttft": self.slo.ttft_target_s,
                                      "tpot": self.slo.tpot_target_s,
                                      "decode_step": self.slo.step_target_s
                                      }[phase], phase=phase)

    def _record(self, req, tr: Dict[str, Any],
                tpot: Optional[float]) -> Dict[str, Any]:
        t0 = tr["arrival"]
        admit = tr.get("admit", req.finish_time)
        first = tr.get("first_token", admit)
        spans: List[Dict[str, Any]] = [
            {"name": "queue", "start_s": 0.0,
             "dur_s": round(admit - t0, 6)},
            {"name": "prefill", "start_s": round(admit - t0, 6),
             "dur_s": round(first - admit, 6)},
            {"name": "decode", "start_s": round(first - t0, 6),
             "dur_s": round(tr["decode_total_s"], 6),
             "steps": tr["decode_steps"],
             "max_step_s": round(tr["decode_max_s"], 6)},
            {"name": "finish", "start_s": round(req.finish_time - t0, 6),
             "dur_s": 0.0},
        ]
        return {
            "schema": SCHEMA,
            "host": self.host,
            "request_id": req.request_id,
            "ts": time.time(),
            "prompt_tokens": tr["prompt_tokens"],
            "generated_tokens": req.num_generated,
            "finish_reason": req.finish_reason,
            # serving-tier attribution: how much of TTFT the prefix cache
            # saved (blocks spliced instead of prefilled) and how much of
            # the decode the verifier batched (drafted vs accepted). Old
            # readers ignore the extra keys; read_request_traces tolerates
            # old-schema lines without them.
            "prefix_hit_blocks": int(getattr(req, "prefix_hit_blocks", 0)),
            "draft_tokens": int(getattr(req, "draft_tokens", 0)),
            "accepted_tokens": int(getattr(req, "accepted_tokens", 0)),
            "ttft_s": round(first - t0, 6),
            "tpot_s": round(tpot, 6) if tpot is not None else None,
            "spans": spans,
            "slo_violations": list(tr["violations"]),
        }

    def _write(self, record: Dict[str, Any]) -> None:
        try:
            os.makedirs(self.directory, exist_ok=True)
            line = json.dumps(record)
            with self._lock:
                with open(self.path, "a") as f:
                    f.write(line + "\n")
                self._written += 1
        except Exception:
            _metrics.counter("serving.trace.errors", 1)
            return
        _metrics.counter("serving.trace.writes", 1)
        _metrics.counter("serving.trace.bytes", len(line) + 1)

    def stats(self) -> Dict[str, Any]:
        return {"path": self.path, "finished": self._finished,
                "written": self._written,
                "sample_every": self.sample_every,
                "violations": dict(self._violation_counts)}


def read_request_traces(path: str) -> List[Dict[str, Any]]:
    """Parse a requests-host*.jsonl file; tolerates a torn tail like every
    other per-host dump reader."""
    out: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out
