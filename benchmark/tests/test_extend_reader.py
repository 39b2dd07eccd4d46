"""``harness/readers_extend.py`` and ``roofline/extend_flash.py`` on a
recorded run built by hand: the extend-attention kernels' share of their
roofline. Counts and arithmetic only: nothing here is a device
measurement."""

import pytest

from harness import common
from roofline import extend_flash

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def recorded_run(kernel_s=0.004, admissions=((400, 16600), (100, 300)),
                 names=("fusion.4/extend_flash.1",
                        "fusion.5/window_extend_flash.2"),
                 config="command-a-plus-l4-ep8-serve.json", traced=True):
    """One extend program an admission: a full layer's call and three
    sliding layers' calls of ``kernel_s`` each, beside other ops; a decode
    program before them whose ops the reader must not count."""
    from harness import program_spans

    ops, mods, ring, t = [], [], [], 100.0
    ops.append((t, t + 0.01, "fusion.7/paged_decode.1", "bf16[32,128,128]"))
    mods.append((t, t + 0.02, "jit_paged_decode_fn"))
    t += 0.05
    for tokens, start in admissions:
        s = t
        for name, n in zip(names, (1, 3)):
            for _ in range(n):
                ops.append((t, t + kernel_s, name, "bf16[1,128,512,128]"))
                t += kernel_s
        ops.append((t, t + 0.03, "fusion.9", "bf16[512,4096]"))
        t += 0.03
        mods.append((s, t, "jit_extend_fn"))
        ring.append((s, t, "serving/admit/extend{bucket=512}",
                     {"tokens": tokens, "bucket": 512, "start": start}))
        t += 0.01
    # a cold prefill in the stretch: no ``extend`` span, nothing counted
    ring.append((t, t + 0.5, "serving/admit/prefill{bucket=18432}",
                 {"tokens": 16500, "bucket": 18432, "start": 0}))

    class Run:
        trace = {"devices": {0: ops}, "modules": {0: mods}} if traced \
            else None
        trace_host = (99.0, t + 1.0) if traced else None
        peaks = PEAKS
        counters = {}
        said = []

        def say(self, msg):
            self.said.append(msg)

    Run.config = common.load_json("configs", config)
    return Run(), ring, program_spans


@pytest.fixture()
def ring(monkeypatch):
    def install(run_ring):
        run, spans, program_spans = run_ring
        monkeypatch.setattr(program_spans, "ring", lambda: spans)
        return run
    return install


def test_visible_keys_by_hand():
    # a full layer: every key up to the query's own
    assert extend_flash.visible(3, 10) == 11 + 12 + 13
    # a window of 12: the first query sees 11, then 12 each
    assert extend_flash.visible(3, 10, 12) == 11 + 12 + 12
    # a context shorter than the window all along is a full layer's
    assert extend_flash.visible(5, 2, 4096) == extend_flash.visible(5, 2)
    # deep behind the window every query sees exactly a window
    assert extend_flash.visible(400, 16600, 4096) == 400 * 4096
    for tokens, start, window in ((7, 0, 4), (64, 4000, 4096), (1, 0, 1)):
        assert extend_flash.visible(tokens, start, window) == sum(
            min(start + t + 1, window) for t in range(tokens))


def test_call_counts_kv_once_a_kv_head():
    w = extend_flash.call(400, 16600, 128, 8, 128)
    assert w["flops"] == 4.0 * 128 * 128 * (400 * 16600 + 400 * 401 / 2)
    assert w["bytes"] == 2.0 * 128 * (2 * 128 * 400 + 2 * 8 * 17000)
    win = extend_flash.call(400, 16600, 128, 8, 128, 4096)
    assert win["flops"] == 4.0 * 128 * 128 * 400 * 4096
    assert win["bytes"] == 2.0 * 128 * (2 * 128 * 400 + 2 * 8 * (4096 + 399))
    # sixteen query heads on a K/V head: compute decides
    assert extend_flash.min_seconds(w, PEAKS)[1] == "compute"


def test_roofline_reader_on_a_recorded_run(ring):
    from harness import readers_extend

    run = ring(recorded_run())
    share = readers_extend.extend_flash_roofline(run)
    # by hand: the full layer sees every key before a query, the three
    # sliding layers at most 4,096; 128 heads, 4 x 128 FLOPs a visible key;
    # over eight calls of 4 ms (both names hold ``extend_flash``)
    vis = (400 * 16600 + 400 * 401 / 2) + 3 * 400 * 4096 \
        + 4 * (100 * 300 + 100 * 101 / 2)
    want = 4 * 128 * 128 * vis / 197e12 / (8 * 0.004)
    assert abs(share - 100 * want) < 1e-6 and 0 < share < 100
    assert "compute-bound" in run.said[-1]


def test_share_reads_100_at_the_chips_best(ring):
    from harness import readers_extend

    w = extend_flash.call(400, 16600, 128, 8, 128)
    win = extend_flash.call(400, 16600, 128, 8, 128, 4096)
    t = extend_flash.min_seconds(
        {k: w[k] + 3 * win[k] for k in w}, PEAKS)[0]
    run = ring(recorded_run(kernel_s=t / 4, admissions=((400, 16600),)))
    assert abs(readers_extend.extend_flash_roofline(run) - 100.0) < 1e-6


@pytest.mark.parametrize("why, kw", [
    ("the parent: no op carries the name",
     {"names": ("fusion_f32_8_1024_", "fusion_bf16_8_1024_128_")}),
    ("an untraced run", {"traced": False}),
    ("no admission through an extend in the stretch", {"admissions": ()}),
    ("a configuration of another kind",
     {"config": "olmo-hybrid-7b-l16-serve.json"}),
])
def test_reader_gives_none_where_there_is_nothing_to_read(why, kw, ring):
    from harness import readers_extend

    assert readers_extend.extend_flash_roofline(ring(recorded_run(**kw))) \
        is None, why


def test_the_metric_is_declared_and_found():
    import run as bench_run

    man = bench_run.manifest()
    m = [m for m in man["per_layer"]
         if m["name"] == "extend_flash_roofline.rag"]
    assert m == [{"name": "extend_flash_roofline.rag", "unit": "%",
                  "better": "higher", "source": "device_trace",
                  "layer": "L2 kernels", "moves": "latency_per_tok_p50_ms",
                  "workloads": ["serve-cmda-plus-ep8-rag16k"]}]
    assert man["per_layer"][-1] == m[0]      # appended, nothing moved
    from harness import readers_extend
    assert bench_run.reader("extend_flash_roofline.rag") \
        is readers_extend.extend_flash_roofline
