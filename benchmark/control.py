"""Readings that the check's limits are set from, on the chip, several seeds
in one process.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 \\
        [--control-seeds 11,12,13] [--seconds 20]

For every seed: a whole run of the cell (set-up, a window of ``--seconds``,
the check), printing each number compared. For every control seed also the
control: the reference computed with fp8 matmuls put in the program's place,
which must come out NOT correct. The last line is a JSON summary: per number
the largest sound reading and the smallest control reading. The benchmark's
own runs never run this; it claims no speed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    a = ap.parse_args(argv)

    import run as bench_run

    man = bench_run.manifest()
    seeds = [int(s) for s in a.seeds.split(",") if s]
    ctrl = {int(s) for s in a.control_seeds.split(",") if s}
    sound, control, verdicts = {}, {}, []
    for seed in seeds:
        r = bench_run.new_run(a.workload, seed, a.seconds, 0)
        r.with_control = seed in ctrl
        out = bench_run.drive(r, man)
        r.say("result: " + json.dumps(out))
        for what, v, _, _ in r.compared:
            key = what.split(":")[0].split(" (")[0]
            sound.setdefault(key, []).append(v)
        for what, v, _, _ in r.control_compared:
            key = what.split(":")[0].split(" (")[0]
            control.setdefault(key, []).append(v)
        verdicts.append({"seed": seed, "correct": out["correct"],
                         "control_correct": r.control_correct,
                         "failed": out["failed"]})
        del r, out
        gc.collect()
    print(json.dumps({
        "workload": a.workload, "verdicts": verdicts,
        "sound_largest": {k: max(v) for k, v in sound.items()},
        "sound_all": sound,
        "control_smallest": {k: min(v) for k, v in control.items()},
        "control_all": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
