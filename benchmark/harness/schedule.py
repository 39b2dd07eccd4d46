"""One general traffic generator, driven by the parameters of a traffic file.

The rule that makes runs repeat (PR 22 fell on its absence): **the seed never
changes how much work a run holds.** Every length is a point of a fixed
quantile grid of the stated distribution, and which prompt length goes with
which answer length (and which turns make up a session) is dealt once, by the
``pairing_seed`` written in the traffic file. The run's seed only orders the
requests (or sessions) of a cycle, fills the token ids and orders the arrival
gaps. Any two seeds give the same multiset of
(shared-prefix length, new prompt tokens, answer tokens) per cycle, and an
open loop's cycle always spans the same time.

Kinds (``traffic["kind"]``):

``stream``      training batches: ``batch`` rows of ``seq_len`` + 1 token ids
                per step from a host iterator, every row different.
``open_loop``   independent users. Requests are due on a schedule whatever
                the system does. A cycle is ``cycle_requests`` requests whose
                gaps are the quantile grid of the exponential distribution
                (as bursty as Poisson arrivals) scaled so that the cycle
                lasts exactly ``cycle_requests / rate_per_s`` seconds.
``sessions``    a closed loop over ``live_sessions`` clients. Each session
                opens with one of a few shared system prompts and sends
                ``turns`` turns; a turn's prompt is the whole history plus
                new tokens, sent when the last answer has arrived. A finished
                session is replaced by the next of the plan. With
                ``stagger_start`` the first ``live_sessions`` sessions start
                at turn 0, 1, 2, 0, ... (their earlier turns stand in the
                history as if answered), so that the loop starts in the mix
                of turns it keeps, not with every session at its first turn:
                started together, all sessions reach their longest context
                together and the page pool overflows (found by simulation,
                PERF.md section 6).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, stream: int) -> np.random.RandomState:
    return np.random.RandomState([int(seed) % (2**32), (int(seed) >> 32) % (2**32),
                                  stream])


def quantile_grid(spec: dict, n: int):
    """n points of the distribution ``spec`` at the quantiles (i + 1/2) / n,
    rounded to whole tokens and clipped to [min, max]. Kinds: ``lognormal``
    (median, sigma), ``uniform`` (min, max), ``fixed`` (value)."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = spec["dist"]
    if kind == "fixed":
        vals = [spec["value"]] * n
    elif kind == "uniform":
        vals = [spec["min"] + q * (spec["max"] - spec["min"]) for q in qs]
    elif kind == "lognormal":
        nd = NormalDist()
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q))
                for q in qs]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = spec.get("min", 1), spec.get("max", float("inf"))
    return [int(min(max(round(v), lo), hi)) for v in vals]


def exponential_gaps(n: int, rate: float):
    """n gaps at the exponential distribution's quantile grid, scaled to sum
    to exactly n / rate seconds."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


# ------------------------------------------------------------------ stream

def batches(traffic: dict, vocab: int, seed: int):
    """Endless iterator of (x, y) int32 [batch, seq_len]: y is x shifted by
    one. Every step draws fresh rows, all different."""
    rng = _rng(seed, 11)
    B, S = traffic["batch"], traffic["seq_len"]
    while True:
        ids = rng.randint(0, vocab, size=(B, S + 1), dtype=np.int32)
        yield ids[:, :-1], ids[:, 1:]


# --------------------------------------------------------------- open loop

def open_loop_cycle(traffic: dict, seed: int, cycle: int):
    """[(offset_s, prompt_len, answer_len)] of one cycle, offsets from the
    cycle's start. Same multisets for every seed and cycle."""
    n = traffic["cycle_requests"]
    answers = _rng(traffic["pairing_seed"], 1).permutation(
        quantile_grid(traffic["answer"], n))
    rng = _rng(seed, 1000 + cycle)
    order = rng.permutation(n)
    prompts = np.asarray(quantile_grid(traffic["prompt"], n))[order]
    answers = answers[order]
    gaps = rng.permutation(exponential_gaps(n, traffic["rate_per_s"]))
    # a request is due at the END of its gap less half the mean gap, so
    # that a cycle's arrivals are centred in it
    offs = np.cumsum(gaps) - 0.5 / traffic["rate_per_s"]
    return [(float(max(o, 0.0)), int(p), int(a))
            for o, p, a in zip(offs, prompts, answers)]


def open_loop(traffic: dict, vocab: int, seed: int):
    """Endless iterator of requests in due order: dicts with ``due`` (seconds
    from the start of the schedule), ``prompt`` (token ids), ``answer``
    (tokens to generate), ``shared`` (0: nothing is shared)."""
    span = traffic["cycle_requests"] / traffic["rate_per_s"]
    cycle = 0
    while True:
        rng = _rng(seed, 5000 + cycle)
        for off, p, a in sorted(open_loop_cycle(traffic, seed, cycle)):
            yield {"due": cycle * span + off, "shared": 0, "answer": a,
                   "prompt": rng.randint(0, vocab, size=p).tolist()}
        cycle += 1


# ---------------------------------------------------------------- sessions

def session_cycle(traffic: dict, seed: int, cycle: int):
    """[(system prompt id, [(new tokens, answer tokens)] * turns)] of one
    cycle of sessions. The sessions themselves (system prompts by their
    fixed popularity counts, and the lengths of each one's turns) are dealt
    by the file's ``pairing_seed`` and are the same for every run; the run's
    seed only orders them."""
    turns = traffic["turns"]
    counts = traffic["system_prompt_counts"]  # sessions per cycle, by prompt
    n = sum(counts)
    deal = _rng(traffic["pairing_seed"], 2)
    sys_ids = deal.permutation([i for i, c in enumerate(counts)
                                for _ in range(c)])
    news = deal.permutation(quantile_grid(traffic["new_tokens"], n * turns))
    answers = deal.permutation(quantile_grid(traffic["answer"], n * turns))
    plans = [(int(sys_ids[s]),
              [(int(news[s * turns + t]), int(answers[s * turns + t]))
               for t in range(turns)]) for s in range(n)]
    return [plans[i] for i in _rng(seed, 2000 + cycle).permutation(n)]


def system_prompts(traffic: dict, vocab: int, seed: int):
    rng = _rng(seed, 3000)
    return [rng.randint(0, vocab, size=traffic["system_prompt_tokens"]).tolist()
            for _ in traffic["system_prompt_counts"]]


def sessions(traffic: dict, vocab: int, seed: int):
    """Endless iterator of session plans: dicts with ``system`` (token ids of
    the shared system prompt), ``turns`` [(new token ids, answer tokens)]
    and ``filler`` (per turn, token ids standing for that turn's answer in
    the history of a session that the loop starts part-way, see
    ``stagger_start``)."""
    sysp = system_prompts(traffic, vocab, seed)
    cycle = 0
    while True:
        rng = _rng(seed, 6000 + cycle)
        for sid, turns in session_cycle(traffic, seed, cycle):
            yield {"system_id": sid, "system": sysp[sid],
                   "turns": [(rng.randint(0, vocab, size=n).tolist(), a)
                             for n, a in turns],
                   "filler": [rng.randint(0, vocab, size=a).tolist()
                              for _, a in turns]}
        cycle += 1


# ------------------------------------------------- what a mix can produce

def work_multiset(traffic: dict, seed: int, cycle: int = 0):
    """Sorted [(shared prefix tokens, new prompt tokens, answer tokens)] of
    one cycle: the same list for every seed (a test pins this)."""
    if traffic["kind"] == "open_loop":
        return sorted((0, p, a) for _, p, a in
                      open_loop_cycle(traffic, seed, cycle))
    if traffic["kind"] == "sessions":
        # what a turn shares is its whole history: the system prompt and
        # every earlier turn's new tokens and answer
        out = []
        for _, turns in session_cycle(traffic, seed, cycle):
            hist = traffic["system_prompt_tokens"]
            for n, a in turns:
                out.append((hist, n, a))
                hist += n + a
        return sorted(out)
    raise ValueError(traffic["kind"])


def prompt_shapes(traffic: dict):
    """Prompt lengths a mix can send through a full prefill and through a
    suffix prefill after a prefix hit: {"prefill": (min, max), "extend":
    (min, max) or None}. The runner turns them into the prefill buckets to
    warm up."""
    if traffic["kind"] == "open_loop":
        g = quantile_grid(traffic["prompt"], traffic["cycle_requests"])
        return {"prefill": (min(g), max(g)), "extend": None}
    if traffic["kind"] == "sessions":
        n = sum(traffic["system_prompt_counts"]) * traffic["turns"]
        new = quantile_grid(traffic["new_tokens"], n)
        ans = quantile_grid(traffic["answer"], n)
        sysn = traffic["system_prompt_tokens"]
        # full prefill: a first turn whose system prompt is not cached yet;
        # extend: new tokens after a cached system prompt (turn 1), or the
        # last answer plus new tokens after a cached history (later turns).
        # Matching is by whole pages, so up to page - 1 more tokens
        page = traffic.get("page_size", 16)
        return {"prefill": (sysn + min(new), sysn + max(new)),
                "extend": (min(new), max(ans) + max(new) + page - 1)}
    raise ValueError(traffic["kind"])
