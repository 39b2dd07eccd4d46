"""Runner "serve": serving.Engine under an open loop or a closed loop of
sessions, one thread: hand over what is due, one ``engine.step()``, look at
what came out.

Times are the benchmark's own: a request's clock starts when it was DUE, not
when the engine first saw it. A token's timestamp is the host clock after the
``step()`` that produced it, which is when a caller of ``step()`` can see it
(a request admitted in a step shows its first two tokens together: the
prefill's and that step's decode's).
"""

from __future__ import annotations

import gc
import time


from . import check, common, device, schedule, stats


class Live:
    __slots__ = ("req", "due", "handed", "stamps", "plan", "turn")

    def __init__(self, req, due, handed, plan=None, turn=0):
        self.req, self.due, self.handed = req, due, handed
        self.stamps, self.plan, self.turn = [], plan, turn


def build(run):
    from paddle_tpu.serving import Engine, EngineConfig

    e = run.config["engine"]
    model, shapes = common.build_model(run)
    with run.phase("engine_construct"):
        eng = Engine(model, EngineConfig(
            max_batch_size=e["max_batch_size"], max_seq_len=e["max_seq_len"],
            prefill_buckets=tuple(e["prefill_buckets"]),
            page_size=e["page_size"], kv_pages=e["kv_pages"],
            prefix_cache=e["prefix_cache"], speculative=e["speculative"]))
    return model, eng, shapes


def buckets_for(lo: int, hi: int, buckets):
    """The configured prefill buckets that prompt lengths lo..hi land in."""
    pick = lambda n: next((b for b in buckets if b >= n), buckets[-1])
    b_lo, b_hi = pick(lo), pick(hi)
    return [b for b in buckets if b_lo <= b <= b_hi]


def warm_up(run, eng):
    """Compile (or load from the persistent cache) every program this
    cell's traffic can reach, and no other."""
    shp = schedule.prompt_shapes(run.traffic)
    bk = run.config["engine"]["prefill_buckets"]
    eng._decode_exe()
    for b in buckets_for(*shp["prefill"], bk):
        eng._prefill_exe(b)
    if shp["extend"] and run.config["engine"]["prefix_cache"]:
        for b in buckets_for(*shp["extend"], bk):
            eng._extend_exe(b)
    sites = {"/".join(map(str, k)): v for k, v in eng.kernel_sites.items()}
    run.say(f"engine programs and their Mosaic calls: {sites}")
    bad = [k for k, v in eng.kernel_sites.items()
           if (k[0] == "decode" and v.get("paged_decode", 0) < 1)
           or (k[0] == "prefill" and v.get("flash_fwd", 0) < 1)]
    if bad:
        run.fail_run(f"Mosaic kernel absent from engine programs {bad}")
    for k, exe in eng._exe.items():
        run.exe_bytes["/".join(map(str, k))] = device.executable_bytes(exe)
    run.say(f"engine executable bytes (TPU compiler): {run.exe_bytes}")


class Loop:
    """The single-threaded serving loop and what it records."""

    def __init__(self, run, eng):
        from paddle_tpu.serving import SamplingParams

        self.run, self.eng, self.SP = run, eng, SamplingParams
        self.t, self.vocab = run.traffic, run.config["model"]["vocab_size"]
        self.live = []          # Live, handed and not ended
        self.ended = []         # dicts, in order of ending
        self.token_stamps = []  # every emitted token's host time
        self.steps = []         # (t_start, t_end, emitting slots, ctx tokens)
        self.lateness = []      # (handed time, handed - due)
        self.t_zero = None
        if self.t["kind"] == "open_loop":
            self.src = schedule.open_loop(self.t, self.vocab, run.seed)
            self.pending = next(self.src)
        else:
            self.src = schedule.sessions(self.t, self.vocab, run.seed)
            self.ready = []     # (due, plan, turn, history ids)

    def start(self):
        self.t_zero = time.perf_counter()
        if self.t["kind"] == "sessions":
            turns = self.t["turns"] if self.t.get("stagger_start") else 1
            for i in range(self.t["live_sessions"]):
                plan, k, hist = next(self.src), i % turns, None
                if k:   # starts part-way: earlier turns stand in the history
                    hist = list(plan["system"])
                    for (new, _), fill in zip(plan["turns"][:k], plan["filler"]):
                        hist += new + fill
                self.ready.append((self.t_zero, plan, k, hist))

    def _hand(self, due, prompt, answer, plan=None, turn=0):
        with self.run.span("bench/add_request"):
            req = self.eng.add_request(prompt, self.SP(
                max_new_tokens=answer, eos_token_id=None))
        now = time.perf_counter()
        self.lateness.append((now, now - due))
        self.live.append(Live(req, due, now, plan, turn))

    def hand_due(self, now):
        if self.t["kind"] == "open_loop":
            while self.t_zero + self.pending["due"] <= now:
                r = self.pending
                self._hand(self.t_zero + r["due"], r["prompt"], r["answer"])
                self.pending = next(self.src)
        else:
            ready, self.ready = self.ready, []
            for due, plan, turn, hist in ready:
                new, ans = plan["turns"][turn]
                prompt = (plan["system"] if hist is None else hist) + new
                self._hand(due, prompt, ans, plan, turn)

    def next_due(self):
        if self.t["kind"] == "open_loop":
            return self.t_zero + self.pending["due"]
        return None

    def step(self):
        ts = time.perf_counter()
        with self.run.span("bench/engine_step"):
            self.eng.step()
        te = time.perf_counter()
        emitting = ctx = 0
        still = []
        for lv in self.live:
            req = lv.req
            n = req.num_generated - len(lv.stamps)
            if n:
                emitting += 1
                ctx += len(req.prompt_ids) + req.num_generated
                lv.stamps.extend([te] * n)
                self.token_stamps.extend([te] * n)
            if req.finish_reason is not None:
                self._end(lv, te)
            else:
                still.append(lv)
        self.live = still
        self.steps.append((ts, te, emitting, ctx))

    def _end(self, lv, te):
        req = lv.req
        self.ended.append({
            "due": lv.due, "handed": lv.handed, "end": te,
            "stamps": lv.stamps, "reason": req.finish_reason,
            "prompt": req.prompt_ids, "output": list(req.output_ids),
            "hit_tokens": req.prefix_hit_blocks * self.eng.cache.page_size,
            "turn": lv.turn})
        if lv.plan is not None:
            if lv.turn + 1 < len(lv.plan["turns"]):
                hist = req.prompt_ids + list(req.output_ids)
                self.ready.append((te, lv.plan, lv.turn + 1, hist))
            else:
                self.ready.append((te, next(self.src), 0, None))

    def run_until(self, stop):
        """Loop until ``stop()`` is true (checked after every step)."""
        while not stop():
            now = time.perf_counter()
            self.hand_due(now)
            if self.eng.has_unfinished:
                self.step()
            else:
                nd = self.next_due()
                time.sleep(max(min((nd or now) - time.perf_counter(), 0.002), 0))


def run(run):
    log = run.compile_log
    t = run.traffic
    # a traced run measures the traced stretch only: stopping and reducing
    # the trace holds the loop for seconds, and every request due meanwhile
    # would be handed over late (read on the chip: 15 s of lateness)
    seconds = (min(run.cell.get("trace_seconds", 20.0), run.seconds)
               if run.trace_on else run.seconds)
    model, eng, shapes = build(run)
    with run.phase("programs"):
        warm_up(run, eng)
    loop = Loop(run, eng)
    loop.start()
    with run.phase("run_in"):
        if t["kind"] == "open_loop":
            # the window opens when request number run_in_requests is due:
            # a whole number of cycles, so at the same phase of the schedule
            # and after the same multiset of work for every seed
            open_at = loop.t_zero + t["run_in_requests"] / t["rate_per_s"]
            loop.run_until(lambda: time.perf_counter() >= open_at)
        else:
            loop.run_until(lambda: len(loop.ended) >= t["run_in_completed"])
    run.say(f"run-in: {len(loop.ended)} requests ended, "
            f"{len(loop.token_stamps)} tokens, {len(loop.steps)} engine steps")
    compiles_before = log.requests

    # ------------------------------------------------------ the window
    run.setup_s = time.perf_counter() - run.t_start
    if run.trace_on:
        common.start_trace(run)
    t0 = time.perf_counter()
    with run.span("bench/window"):
        loop.run_until(lambda: time.perf_counter() - t0 >= seconds)
    t1 = t0 + seconds
    if run.trace_on:
        common.stop_trace(run)
    run.window = (t0, t1)
    if log.requests != compiles_before:
        run.fail_run(f"{log.requests - compiles_before} compile request(s) "
                     "inside the measured window")

    # ------------------------------------------------------ reduction
    done = [r for r in loop.ended if t0 <= r["end"] < t1]
    ok = [r for r in done if r["reason"] == "length"]
    run.attempted, run.failed = len(done), len(done) - len(ok)
    tokens = stats.tokens_in_window(loop.token_stamps, t0, t1)
    per_tok = [(r["end"] - r["due"]) / len(r["output"]) * 1e3 for r in ok]
    gaps = [g * 1e3 for g in stats.gaps_in_window(
        [r["stamps"] for r in loop.ended] + [lv.stamps for lv in loop.live],
        t0, t1)]
    e2e = run.end_to_end
    e2e["serve_out_tok_s"] = tokens / seconds
    if per_tok:
        e2e["latency_per_tok_p50_ms"] = stats.percentile(per_tok, 50)
    if gaps:
        e2e["tok_gap_p95_ms"] = stats.percentile(gaps, 95)
    run.say(f"window: {len(done)} requests ended ({run.failed} failed), "
            f"{tokens} tokens emitted, {len(per_tok)} latency samples, "
            f"{len(gaps)} token gaps, in {seconds:.0f} s")
    admitted = [r for r in loop.ended + [
        {"handed": lv.handed, "prompt": lv.req.prompt_ids, "due": lv.due,
         "stamps": lv.stamps,
         "hit_tokens": lv.req.prefix_hit_blocks * eng.cache.page_size}
        for lv in loop.live] if t0 <= r["handed"] < t1]
    run.counters.update(
        steps=[s for s in loop.steps if t0 <= s[1] < t1],
        max_batch_size=run.config["engine"]["max_batch_size"],
        lateness_ms=[l * 1e3 for h, l in loop.lateness if t0 <= h < t1],
        prompt_tokens_admitted=sum(len(r["prompt"]) for r in admitted),
        prompt_tokens_hit=sum(r["hit_tokens"] for r in admitted),
        ttft_ms=[(r["stamps"][0] - r["due"]) * 1e3 for r in admitted
                 if r["stamps"]],
        tpot_ms=[(r["stamps"][-1] - r["stamps"][0]) / (len(r["stamps"]) - 1)
                 * 1e3 for r in ok if len(r["stamps"]) > 1],
        gaps_ms=gaps, model=run.config["model"],
        waiting_end=len(eng.scheduler.waiting))
    run.memory_peak = device.memory_peak_bytes(run.devices)

    # ------------------------------------- the check, engine freed first
    ck = run.config["check"]
    sample = check.pick_sample(ok, run.seed, ck["sample_requests"])
    del loop, eng, model
    gc.collect()
    t_ref = time.perf_counter()
    if not sample:
        run.say("check: no request finished in the window; nothing to compare")
        run.correct = False
    else:
        gap, mean, n = check.served_gap(run.config["model"], shapes,
                                        run.seed, sample, say=run.say)
        run.say(f"check: {len(sample)} finished requests, {n} served tokens "
                f"(longest {max(len(r['prompt']) + len(r['output']) for r in sample)} "
                "tokens of context)")
        what = "gap of a served token's logit below the reference's best"
        lim = ck["limits"]
        run.correct = bool(
            run.compare("mean " + what, mean, lim["served_gap_mean"])
            & run.compare("widest " + what, gap, lim["served_gap_widest"]))
        if run.with_control:
            run.say("control: the tokens an fp8 reference puts first, in "
                    "the served tokens' place")
            low, low_mean, _ = check.served_gap(
                run.config["model"], shapes, run.seed, sample, control=True,
                say=run.say)
            sound, run.compared = run.compared, []
            run.control_correct = bool(
                run.compare("mean " + what, low_mean, lim["served_gap_mean"])
                & run.compare("widest " + what, low, lim["served_gap_widest"]))
            run.control_compared, run.compared = run.compared, sound
    run.reference_s = time.perf_counter() - t_ref
