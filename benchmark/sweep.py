"""The rate sweep that finds an open-loop cell's knee, once, on the chip.

    python3 benchmark/sweep.py --workload serve-1p3b-chat --rates 1.0,1.3,1.6,1.9

One process, one engine: for each offered rate, one cycle of run-in and two
cycles of window of the cell's own traffic (same lengths, gaps scaled to the
rate), then the engine is drained. Prints per rate what was offered and what
came out. The knee is the highest rate at which the backlog does not grow;
the traffic file then fixes four fifths of it. No run of the benchmark ever
searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=424242)
    a = ap.parse_args(argv)

    import run as bench_run
    from harness import device, run_serve, stats

    run = bench_run.new_run(a.workload, a.seed, 0, 0)
    run.compile_log = device.CompileLog()
    model, eng, _ = run_serve.build(run)
    run_serve.warm_up(run, eng)
    rows = []
    for rate in [float(r) for r in a.rates.split(",")]:
        run.traffic = dict(run.traffic, rate_per_s=rate)
        n = run.traffic["cycle_requests"]
        loop = run_serve.Loop(run, eng)
        loop.start()
        t_open = loop.t_zero + run.traffic["run_in_requests"] / rate
        t_close = t_open + 2 * n / rate
        loop.run_until(lambda: time.perf_counter() >= t_open)
        waiting_open = len(eng.scheduler.waiting)
        loop.run_until(lambda: time.perf_counter() >= t_close)
        waiting_close = len(eng.scheduler.waiting)
        done = [r for r in loop.ended if t_open <= r["end"] < t_close]
        steps = [s for s in loop.steps if t_open <= s[1] < t_close]
        per_tok = [(r["end"] - r["due"]) / len(r["output"]) * 1e3 for r in done]
        gaps = [g * 1e3 for g in stats.gaps_in_window(
            [r["stamps"] for r in loop.ended] + [lv.stamps for lv in loop.live],
            t_open, t_close)]
        ttft = [(r["stamps"][0] - r["due"]) * 1e3 for r in done if r["stamps"]]
        row = {
            "offered_req_s": rate, "window_s": round(t_close - t_open, 2),
            "completed_req_s": len(done) / (t_close - t_open),
            "out_tok_s": stats.tokens_in_window(loop.token_stamps, t_open,
                                                t_close) / (t_close - t_open),
            "latency_per_tok_p50_ms": stats.percentile(per_tok, 50),
            "latency_per_tok_p90_ms": stats.percentile(per_tok, 90),
            "tok_gap_p95_ms": stats.percentile(gaps, 95),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "occupancy": sum(s[2] for s in steps) / max(len(steps), 1)
            / run.config["engine"]["max_batch_size"],
            "waiting_at_open": waiting_open, "waiting_at_close": waiting_close,
            "failed": sum(1 for r in done if r["reason"] != "length")}
        run.say("sweep: " + json.dumps(row))
        rows.append(row)
        while eng.has_unfinished:   # drain before the next rate
            eng.step()
    print(json.dumps({"workload": a.workload, "device": run.dev_tag,
                      "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
