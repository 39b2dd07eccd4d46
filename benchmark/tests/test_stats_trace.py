"""Percentiles, window-edge token counting and the trace reduction, on
hand-made timelines (and on the small chip trace kept beside this file)."""

import os
import re

import pytest

from harness import stats, trace


def test_percentile_matches_hand_values():
    assert stats.percentile([], 50) is None
    assert stats.percentile([3], 95) == 3
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([10, 20], 90) == 19


def test_tokens_count_where_they_are_emitted():
    # one request straddles each edge of the window [10, 20)
    stamps = sorted([8.0, 9.5, 10.0, 10.5, 15.0, 19.999, 20.0, 21.0])
    assert stats.tokens_in_window(stamps, 10.0, 20.0) == 4
    assert stats.tokens_in_window(stamps, 0.0, 100.0) == len(stamps)
    assert stats.tokens_in_window([], 0.0, 1.0) == 0


def test_gaps_belong_to_the_window_of_their_later_token():
    reqs = [[9.0, 10.2, 10.4], [19.9, 20.1], [12.0]]
    gaps = stats.gaps_in_window(reqs, 10.0, 20.0)
    assert sorted(round(g, 6) for g in gaps) == [0.2, 1.2]


def _tr():
    ops = [(0.0, 1.0, "fusion.1", "bf16[4]"), (0.5, 1.5, "copy.2", ""),
           (3.0, 4.0, "paged_decode.7", ""), (3.5, 3.75, "all-reduce.1", ""),
           (6.0, 7.0, "all-reduce-done.2", "")]
    spans = [(1.4, 3.1, "bench/engine_step"), (2.0, 2.5, "bench/add_request"),
             (4.0, 5.0, "bench/engine_step")]
    return {"window": (0.0, 8.0), "devices": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": [(0.0, 1.5, "jit_paged_decode_fn(1)"),
                                          (3.0, 4.0, "jit_extend_fn(2)")]},
            "spans": spans}


def test_busy_union_and_idle_gaps_by_span():
    tr = _tr()
    assert trace.busy_seconds(tr) == pytest.approx(1.5 + 1.0 + 1.0)
    assert trace.window_seconds(tr) == 8.0
    gaps = dict(trace.idle_gaps_by_span(tr))
    # gap 1.5..3.0: add_request covers 2.0..2.5 (innermost), engine_step the
    # rest; gap 4..6: engine_step 4..5; gaps 5..6 and 7..8 under no span
    assert gaps["bench/add_request"] == pytest.approx(0.5)
    assert gaps["bench/engine_step"] == pytest.approx(1.0 + 1.0)
    assert gaps["_no_benchmark_span_"] == pytest.approx(2.0)
    assert sum(gaps.values()) == pytest.approx(8.0 - 3.5)


def test_kernel_sums_top_ops_and_exposed_collectives():
    tr = _tr()
    assert trace.op_seconds(tr, lambda n: "paged_decode" in n) == 1.0
    assert trace.op_count(tr, lambda n: "paged_decode" in n) == 1
    top = trace.top_ops(tr, 2)
    assert top[0][0] in ("fusion_bf16_4_", "copy", "paged_decode",
                         "all-reduce-done") and len(top) == 2
    # all-reduce.1 runs wholly under paged_decode; all-reduce-done.2 alone
    assert trace.exposed_collective_seconds(tr) == pytest.approx(1.0)
    assert trace.module_runs(tr, lambda n: "decode" in n) == [1.5]
    assert trace.base_name("%fusion.12") == "fusion"
    line = "%paged_decode.7 = bf16[32,16,128]{2,1,0} custom-call(%fusion.3)"
    assert trace.instr_name(line) == "paged_decode.7"
    assert trace.base_name(line) == "paged_decode"


SMALL = os.path.join(os.path.dirname(__file__), "data",
                     "small_trace.xplane.pb")


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_reduction_of_the_recorded_chip_trace():
    """A trace recorded on a v5e chip by tests/record_trace.py: four jitted
    steps under bench/engine_step, 2 ms sleeps under bench/add_request."""
    tr = trace.reduce_file(SMALL)
    assert list(tr["devices"]) == ["/device:TPU:0"]
    assert len(trace.span_seconds_between(tr, "bench/engine_step")) == 4
    busy, win = trace.busy_seconds(tr), trace.window_seconds(tr)
    assert 0 < busy < win
    assert 3 <= len(trace.module_runs(tr, lambda n: True)) <= 4
    names = {n for _, _, n, _ in tr["devices"]["/device:TPU:0"]}
    assert all(" = " not in n and not n.startswith("%") for n in names)
    gaps = dict(trace.idle_gaps_by_span(tr))
    assert gaps["bench/add_request"] >= 4 * 0.002 * 0.9
    assert sum(gaps.values()) == pytest.approx(win - busy, rel=1e-6)
    assert all(re.fullmatch(r"[\w.\-]+", k) for k, _ in trace.top_ops(tr))
