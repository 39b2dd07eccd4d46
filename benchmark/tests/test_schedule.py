"""The seed orders and fills the requests; it never changes how much work a
run holds."""

import json
import os

import pytest

from harness import common, schedule

SEEDS = (0, 1, 7, 2**31 + 12345, 987654321012)
SERVING = ("chat-steady", "prefix-sessions")


@pytest.mark.parametrize("name", SERVING)
def test_same_work_for_any_two_seeds(name):
    t = common.load_json("traffic", name + ".json")
    want = schedule.work_multiset(t, SEEDS[0], 0)
    assert len(want) > 8
    for seed in SEEDS:
        for cycle in (0, 1, 5):
            assert schedule.work_multiset(t, seed, cycle) == want
    # the run-in is a count written in the file, the same for every seed
    assert isinstance(t.get("run_in_requests", t.get("run_in_completed")), int)


@pytest.mark.parametrize("name", SERVING)
def test_one_seed_gives_one_schedule_and_seeds_differ(name):
    t = common.load_json("traffic", name + ".json")
    gen = schedule.open_loop if t["kind"] == "open_loop" else schedule.sessions
    take = lambda seed: [next(g) for g in [gen(t, 50304, seed)] for _ in range(40)]
    assert take(5) == take(5)
    assert take(5) != take(6)


def test_open_loop_cycle_spans_the_same_time_for_every_seed():
    t = common.load_json("traffic", "chat-steady.json")
    span = t["cycle_requests"] / t["rate_per_s"]
    for seed in SEEDS:
        dues = [r["due"] for r, _ in zip(schedule.open_loop(t, 100, seed),
                                         range(3 * t["cycle_requests"]))]
        assert dues == sorted(dues)
        for c in range(3):
            cyc = dues[c * t["cycle_requests"]:(c + 1) * t["cycle_requests"]]
            assert c * span <= cyc[0] and cyc[-1] <= (c + 1) * span
    # the window opens on a cycle boundary, and holds whole cycles
    assert t["run_in_requests"] % t["cycle_requests"] == 0
    man = json.load(open(os.path.join(common.ROOT, "BENCHMARK.json")))
    cycles = man["run_seconds"] / span
    assert abs(cycles - round(cycles)) < 0.02, cycles


def test_lengths_are_the_quantile_grid_not_draws():
    spec = {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32,
            "max": 1520}
    g = schedule.quantile_grid(spec, 32)
    assert g == sorted(g) and g[0] == 32 and g[-1] == 1520
    assert abs(g[15] - 256) < 25 and abs(g[16] - 256) < 25
    gaps = schedule.exponential_gaps(32, 1.25)
    assert abs(sum(gaps) - 32 / 1.25) < 1e-9


def test_every_request_fits_the_engine_budget():
    cfg = common.load_json("configs", "gpt3-1p3b-serve.json")
    limit = cfg["engine"]["max_seq_len"]
    for name in SERVING:
        t = common.load_json("traffic", name + ".json")
        for shared, new, ans in schedule.work_multiset(t, 3):
            assert shared + new + ans < limit
        shp = schedule.prompt_shapes(t)
        assert shp["prefill"][1] <= max(cfg["engine"]["prefill_buckets"])
        if shp["extend"]:
            assert shp["extend"][1] <= max(cfg["engine"]["extend_buckets"])


def test_training_rows_all_differ_and_repeat_per_seed():
    t = {"kind": "stream", "batch": 4, "seq_len": 16}
    a = next(schedule.batches(t, 50304, 2**31 + 5))
    b = next(schedule.batches(t, 50304, 2**31 + 5))
    assert (a[0] == b[0]).all() and (a[0][:, 1:] == a[1][:, :-1]).all()
    assert len({tuple(r) for r in a[0]}) == 4
