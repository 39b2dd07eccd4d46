"""Phase spans inside serving.Engine and the train step (ISSUE 24).

Covers the one span primitive (ids, parents, attributes, the shared no-op),
the engine's span tree per step (plain and speculative), what stays empty
with everything off, the profiler-session path (ring fills with the flag
off, registry stays empty, spans sit on the XPlane's clock), the ``compile``
span, the documented ``serving.*.seconds`` histograms now fed from spans,
the blocked-admission span, ``Request.admit_time`` and the ``train/step``
span.
"""

import glob
import os
from collections import Counter, defaultdict

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

ADMIT_PHASES = {"serving/admit/match", "serving/admit/alloc",
                "serving/admit/prefill", "serving/admit/extend",
                "serving/admit/sample"}


@pytest.fixture
def telemetry():
    obs.enable()
    obs.reset()
    obs.clear_spans()
    yield obs
    obs.disable()
    obs.reset()
    obs.clear_spans()


@pytest.fixture
def quiet():
    """Flag off, registry and ring empty before and after."""
    obs.disable()
    obs.reset()
    obs.clear_spans()
    yield
    obs.reset()
    obs.clear_spans()


def _tiny():
    paddle.seed(0)
    m = gpt_tiny(dropout=0.0, num_layers=2)
    m.eval()
    return m


def _prompts():
    rng = np.random.default_rng(3)
    first = [int(t) for t in rng.integers(1, 100, (40,))]
    other = [int(t) for t in rng.integers(1, 100, (20,))]
    # shares two whole 16-token blocks with ``first``: a prefix hit
    third = first[:32] + [int(t) for t in rng.integers(1, 100, (9,))]
    return [first, other, third]


def _engine(model, speculative=None, **kw):
    cfg = dict(max_batch_size=2, max_seq_len=64, page_size=16,
               prefix_cache=True, speculative=speculative)
    cfg.update(kw)
    return Engine(model, EngineConfig(**cfg))


def _generate(eng, n=6):
    return eng.generate(_prompts(), SamplingParams(max_new_tokens=n))


def _children(spans):
    kids = defaultdict(list)
    for e in spans:
        kids[e["parent"]].append(e)
    return kids


SPEC = pytest.mark.parametrize("speculative", [None, 2],
                               ids=["plain", "speculative"])


# ---------------- the primitive --------------------------------------------
def test_span_ids_parents_and_attributes(telemetry):
    with obs.span("outer", site="a", step=3) as outer:
        with obs.span("inner", request_id=7) as inner:
            inner.set(tokens=5)
        outer.set(emitted=2)
    inner_ev, outer_ev = obs.spans()
    assert outer_ev["parent"] is None and inner_ev["parent"] == outer_ev["id"]
    # strings label the span (name and histogram series); numbers do not
    assert outer_ev["name"] == "outer{site=a}" and inner_ev["name"] == "inner"
    assert outer_ev["attrs"] == {"site": "a", "step": 3, "emitted": 2}
    assert inner_ev["attrs"] == {"request_id": 7, "tokens": 5}
    assert outer_ev["ts"] <= inner_ev["ts"]
    assert inner_ev["ts"] + inner_ev["dur"] <= outer_ev["ts"] + outer_ev["dur"]
    hists = obs.snapshot()["histograms"]
    assert hists["outer.seconds{site=a}"]["count"] == 1
    assert hists["inner.seconds"]["count"] == 1
    assert outer.seconds == pytest.approx(outer_ev["dur"] * 1e-6)


def test_span_off_is_one_shared_noop(quiet):
    a, b = obs.span("x", step=1), obs.span("y")
    assert a is b
    with a as sp:
        sp.set(anything=1)
    assert sp.seconds == 0.0
    assert obs.spans() == [] and len(obs.get_registry()) == 0


# ---------------- the engine's span tree -----------------------------------
@SPEC
def test_every_step_has_its_phases_in_order(telemetry, speculative):
    eng = _engine(_tiny(), speculative)
    obs.clear_spans()  # the verify program compiles at construction
    _generate(eng)
    spans = obs.spans()
    by_id = {e["id"]: e for e in spans}
    kids = _children(spans)
    steps = [e for e in spans if e["name"] == "serving/step"]
    assert len(steps) >= 3
    assert [s["attrs"]["step"] for s in steps] == list(
        range(1, len(steps) + 1))
    # a plain step LAUNCHES before it fetches the step before: its first
    # call of a busy stretch has no fetch, its last no launch
    launch = (["serving/decode/propose"] if speculative else []) + [
        "serving/decode/upload", "serving/decode/dispatch"]
    settle = ["serving/decode/fetch", "serving/decode/settle"]
    decode_order = ["serving/decode/grow_pages"] + launch + settle
    allowed = [decode_order] if speculative else [
        decode_order[:1] + launch, decode_order, decode_order[:1] + settle]
    seen = set()
    admitted = hits = 0
    decode_s = covered_s = 0.0
    for st in steps:
        names = [c["name"] for c in kids[st["id"]]]
        # admissions first, then the one decode step
        assert names == ["serving/admit"] * (len(names) - 1) + [
            "serving/decode"]
        assert by_id[st["parent"]]["name"] == "serving/generate"
        emitted = 0
        for adm in kids[st["id"]][:-1]:
            a = adm["attrs"]
            phases = kids[adm["id"]]
            kind = "extend" if a["hit_blocks"] else "prefill"
            assert [p["name"] for p in phases] == [
                "serving/admit/match", "serving/admit/alloc",
                "serving/admit/" + kind, "serving/admit/sample"]
            # spans of one admission share the request's id
            assert {p["attrs"]["request_id"] for p in phases} == {
                a["request_id"]}
            assert a["queued_s"] >= 0 and a["prompt_tokens"] >= 20
            work = phases[2]["attrs"]
            assert 0 < work["tokens"] <= work["bucket"]
            assert work["tokens"] == a["prompt_tokens"] - 16 * a["hit_blocks"]
            assert phases[1]["attrs"]["pages"] >= 1
            admitted += 1
            hits += bool(a["hit_blocks"])
            emitted += 1
        dec = kids[st["id"]][-1]
        assert dec["attrs"]["step"] == st["attrs"]["step"]
        phases = kids[dec["id"]]
        names = [p["name"] for p in phases]
        assert names in allowed
        seen.add(tuple(names))
        assert all(p["parent"] == dec["id"] for p in phases)
        assert {"allocated", "cow_copies", "cache_full"} <= set(
            phases[0]["attrs"])
        decode_s += dec["dur"]
        covered_s += sum(p["dur"] for p in phases)
        if names[-1] != "serving/decode/settle":
            # nothing settled: the span describes no step
            assert "running" not in dec["attrs"]
            assert st["attrs"]["emitted"] == emitted
            continue
        done = phases[-1]["attrs"]
        assert 0 <= done["finished"] <= dec["attrs"]["running"]
        # every row of the settled step gave a token or was dropped
        assert st["attrs"]["emitted"] >= emitted + dec["attrs"]["running"] \
            - done["dropped"]
    assert admitted == 3 and hits == 1
    assert len(seen) == len(allowed)        # each shape of step was met
    # the children cover the decode step: nothing sizeable runs between them
    assert covered_s >= 0.95 * decode_s
    # every recorded span of the engine is in some step's tree
    assert {e["name"] for e in spans} <= {
        "serving/generate", "serving/step", "serving/admit",
        "serving/decode"} | ADMIT_PHASES | set(decode_order) | {
        e["name"] for e in spans if e["name"].startswith("compile")}


@SPEC
def test_all_off_leaves_nothing_and_tokens_match(quiet, speculative):
    off = _generate(_engine(_tiny(), speculative))
    assert obs.spans() == [] and len(obs.get_registry()) == 0
    obs.enable()
    try:
        on = _generate(_engine(_tiny(), speculative))
        assert obs.spans() and len(obs.get_registry()) > 0
    finally:
        obs.disable()
    assert on == off


def test_profiler_session_fills_the_ring_on_the_trace_clock(quiet, tmp_path):
    """Flag OFF, a jax profiler session recording: the ring fills, the
    registry stays empty, and every ring span is the XPlane event of the
    same name once moved by the offset of a surrounding annotation."""
    import time

    from jax.profiler import ProfileData

    eng = _engine(_tiny())
    for _ in range(2):      # compile outside the session: the second pass
        _generate(eng)      # hits the prefixes the first one cached
    assert obs.spans() == []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test/around"):
            t_around = time.perf_counter()
            _generate(eng)
    finally:
        jax.profiler.stop_trace()
    ring = obs.spans()
    assert len(ring) > 20 and len(obs.get_registry()) == 0
    with obs.span("after"):
        pass
    assert len(obs.spans()) == len(ring)   # the session is over
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serving/", "test/", "compile")):
                    events[e.name].append(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9))
    (around,) = events.pop("test/around")
    # perf_counter was read just inside the annotation
    offset = around[0] - t_around
    mine = defaultdict(list)
    for e in ring:
        mine[e["name"]].append((e["ts"] * 1e-6 + offset,
                                (e["ts"] + e["dur"]) * 1e-6 + offset))
    assert Counter({k: len(v) for k, v in mine.items()}) == Counter(
        {k: len(v) for k, v in events.items()})
    worst = max(abs(a - b) for name in mine
                for m, x in zip(sorted(mine[name]), sorted(events[name]))
                for a, b in zip(m, x))
    assert worst < 0.2e-3, worst


def test_compile_span_once_per_site_on_first_use(telemetry):
    eng = _engine(_tiny())
    _generate(eng)
    compiles = [e for e in obs.spans() if e["name"].startswith("compile")]
    by_parent = {e["id"]: e for e in obs.spans()}
    got = Counter((e["attrs"]["site"], by_parent[e["parent"]]["name"])
                  for e in compiles)
    # prompts of 40 and 20 tokens: prefill buckets 64 and 32; the 9-token
    # suffix: extend bucket 16 (site serving.prefill too); one decode
    assert got == Counter({
        ("serving.prefill", "serving/admit/prefill"): 2,
        ("serving.prefill", "serving/admit/extend"): 1,
        ("serving.decode", "serving/decode/dispatch"): 1})
    assert all(e["attrs"]["cache_hit"] == 0 and e["name"] ==
               "compile{site=%s}" % e["attrs"]["site"] for e in compiles)
    obs.clear_spans()
    hits = obs.snapshot()["counters"].get(
        "jit.compile.cache_hit{site=serving.prefill}", 0)
    eng._decode_exe(), eng._prefill_exe(64), eng._extend_exe(16)
    assert obs.spans() == []
    assert obs.snapshot()["counters"][
        "jit.compile.cache_hit{site=serving.prefill}"] == hits + 2


@SPEC
def test_documented_histograms_are_fed_from_the_spans(telemetry, speculative):
    eng = _engine(_tiny(), speculative)
    obs.reset()
    obs.clear_spans()
    _generate(eng)
    spans = obs.spans()
    n = Counter(e["name"] for e in spans)
    h = {k: v["count"] for k, v in obs.snapshot()["histograms"].items()}
    assert h["serving.prefill.seconds"] == n["serving/admit"] == 3
    assert h["serving.ttft.seconds"] == n["serving/admit"]
    assert h["serving.decode.step.seconds"] == n["serving/decode/dispatch"]
    # one a row that was settled: a row run for a request that had finished
    # by then (its last token was in flight at the launch) is dropped
    assert h["serving.decode.token.seconds"] == sum(
        e["attrs"]["running"] for e in spans if e["name"] == "serving/decode"
        and "running" in e["attrs"]) - sum(
        e["attrs"]["dropped"] for e in spans
        if e["name"] == "serving/decode/settle")
    assert h["serving.prefix.splice_seconds"] == sum(
        1 for e in spans if e["name"] == "serving/admit"
        and e["attrs"]["hit_blocks"])
    assert h["serving.tpot.seconds"] == 3
    # and each span has its own <name>.seconds series
    assert h["serving/step.seconds"] == n["serving/step"]
    assert h["serving/decode/fetch.seconds"] == n["serving/decode/fetch"]


def test_blocked_admission_is_a_span(telemetry):
    # 6 allocatable pages: the 40-token prompt takes 3, the 20-token one 2,
    # and the third (one slot is free again only later) finds the pool short
    eng = _engine(_tiny(), kv_pages=7, max_batch_size=3, prefix_cache=False)
    out = _generate(eng, n=4)
    assert all(len(o) == 4 for o in out)
    spans = obs.spans()
    blocked = [e for e in spans if e["name"] == "serving/admit"
               and e["attrs"].get("blocked")]
    assert blocked and all("queued_s" not in e["attrs"] for e in blocked)
    kids = _children(spans)
    for adm in blocked:
        (alloc,) = kids[adm["id"]]
        assert alloc["name"] == "serving/admit/alloc"
        assert alloc["attrs"]["blocked"] == 1 and alloc["attrs"]["pages"] == 0
    done = [e for e in spans if e["name"] == "serving/admit"
            and "queued_s" in e["attrs"]]
    assert len(done) == 3
    # the blocked request waited in the queue for at least one engine step
    assert max(e["attrs"]["queued_s"] for e in done) > 0


def test_admit_time_is_kept_with_everything_off(quiet):
    eng = _engine(_tiny())
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=3))
            for p in _prompts()]
    assert all(r.admit_time is None for r in reqs)
    while eng.has_unfinished:
        eng.step()
    for r in reqs:
        assert r.arrival_time <= r.admit_time <= r.first_token_time \
            <= r.finish_time
    # two slots, three requests: the third was admitted after a finish
    assert reqs[2].admit_time > reqs[0].first_token_time
    assert obs.spans() == []


# ---------------- launch numbers -------------------------------------------
#: every span name of serving/README.md's table (``compile{site=}`` and
#: ``serving/snapshot{reason=}`` carry their one label each)
DOCUMENTED = {"serving/generate", "serving/step", "serving/admit",
              "serving/decode", "serving/decode/grow_pages",
              "serving/decode/propose", "serving/decode/upload",
              "serving/decode/dispatch", "serving/decode/fetch",
              "serving/decode/settle", "compile{site=serving.prefill}",
              "compile{site=serving.decode}"} | ADMIT_PHASES


def _numbered(spans):
    """[(number, span)] of the spans that carry a launch, in number order."""
    return sorted(((e["attrs"]["launch"], e) for e in spans
                   if "launch" in e["attrs"]), key=lambda ne: ne[0])


@SPEC
def test_every_launch_has_the_next_number_and_fetches_wait_for_their_own(
        telemetry, speculative):
    eng = _engine(_tiny(), speculative)
    assert eng._launch_i == 0       # construction compiles, launches nothing
    _generate(eng)
    spans = obs.spans()
    numbered = _numbered(spans)
    # decode (or verify), prefill, extend and the sampler's eager stretch:
    # each takes the next number, none twice, none left out
    assert [n for n, _ in numbered] == list(range(1, eng._launch_i + 1))
    steps = sum(1 for e in spans if e["name"] == "serving/decode/fetch")
    assert Counter(e["name"] for _, e in numbered) == Counter({
        "serving/decode/dispatch": steps, "serving/admit/prefill": 2,
        "serving/admit/extend": 1, "serving/admit/sample": 3})
    # numbers are facts of one span: no attribute became a label
    assert {e["name"] for e in spans} <= DOCUMENTED
    for e in spans:
        for k in ("launch", "launches", "waits_for", "eager"):
            assert isinstance(e["attrs"].get(k, 0), int)
    kids = _children(spans)
    launched, ahead = [], 0
    for dec in (e for e in spans if e["name"] == "serving/decode"):
        by = {c["name"]: c for c in kids[dec["id"]]}
        disp, fetch = (by.get("serving/decode/" + leaf)
                       for leaf in ("dispatch", "fetch"))
        if speculative:
            # a verify step is fetched by the call that launched it
            assert fetch["attrs"]["waits_for"] == disp["attrs"]["launch"]
            assert disp["attrs"]["ahead"] == 0
            continue
        if disp is not None:
            # a plain step goes out BEFORE the fetch of the step before,
            # and ``ahead`` says whether there was one
            assert disp["attrs"]["ahead"] == len(launched) <= 1
            ahead += disp["attrs"]["ahead"]
            launched.append(disp["attrs"]["launch"])
            assert "launch" not in by["serving/decode/upload"]["attrs"]
        if fetch is not None:
            # each fetch waits for the oldest launch not yet fetched
            assert fetch["attrs"]["waits_for"] == launched.pop(0)
            if disp is not None:
                assert disp["ts"] + disp["dur"] <= fetch["ts"]
                assert fetch["attrs"]["waits_for"] < disp["attrs"]["launch"]
        assert "launch" not in by["serving/decode/grow_pages"]["attrs"]
    # nothing is left in flight, and the counters count what the spans say
    assert not launched and eng._flight is None
    assert (eng.steps_ahead, eng.steps_ahead + eng.steps_drained) == (
        ahead, steps)
    assert (ahead > 0) == (speculative is None)
    admits = [e for e in spans if e["name"] == "serving/admit"]
    assert len(admits) == 3
    for adm in admits:
        assert "programs" not in adm["attrs"]
        mine = [c["attrs"] for c in kids[adm["id"]] if "launch" in c["attrs"]]
        work, sample = mine
        # what ``programs`` counted: the compiled launches under the span
        assert sum(a.get("launches", 1) for a in mine
                   if not a.get("eager")) == 1
        assert "eager" not in work and "bucket" in work
        assert (sample["launch"], sample["waits_for"], sample["eager"]) == (
            work["launch"] + 1, work["launch"] + 1, 1)


@SPEC
def test_the_counter_advances_with_everything_off(quiet, speculative):
    off = _engine(_tiny(), speculative)
    _generate(off)
    assert obs.spans() == [] and len(obs.get_registry()) == 0
    obs.enable()
    try:
        on = _engine(_tiny(), speculative)
        _generate(on)
        fetches = sum(1 for e in obs.spans()
                      if e["name"] == "serving/decode/fetch")
    finally:
        obs.disable()
    # three admissions of two launches each and one a decode step
    assert off._launch_i == on._launch_i == 6 + fetches


def test_a_sampled_row_and_a_page_copy_take_numbers_too(telemetry):
    eng = _engine(_tiny())
    prompt = _prompts()[1]
    req = eng.add_request(prompt, SamplingParams(
        max_new_tokens=4, do_sample=True, temperature=0.8, top_k=5))
    eng.step()
    # another sharer of the page the next step writes: copy-on-write
    page = int(eng.cache.page_table[req.slot, len(prompt) // 16])
    eng.page_alloc.retain([page], owner="test")
    eng.step()
    spans = obs.spans()
    numbered = _numbered(spans)
    assert [(e["name"].rsplit("/", 1)[-1], n, e["attrs"].get("launches", 1),
             e["attrs"].get("eager", 0)) for n, e in numbered] == [
        ("prefill", 1, 1, 0), ("sample", 2, 1, 1),
        ("upload", 3, 1, 1), ("dispatch", 4, 1, 0),     # the step's key
        ("grow_pages", 5, 1, 0),                        # the one copy
        ("upload", 6, 1, 1), ("dispatch", 7, 1, 0)]
    assert eng._launch_i == 7
    grow = [e["attrs"] for e in spans
            if e["name"] == "serving/decode/grow_pages"]
    assert "launch" not in grow[0] and grow[1]["cow_copies"] == 1
    assert {e["name"] for e in spans} <= DOCUMENTED


# ---------------- the train step -------------------------------------------
def test_train_step_span_and_compile(telemetry):
    from paddle_tpu.distributed.fleet.utils import make_sharded_train_step

    paddle.seed(0)
    model = gpt_tiny(dropout=0.0, num_layers=2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = make_sharded_train_step(model, opt)
    x = np.random.RandomState(0).randint(0, 128, size=(4, 16))
    y = np.roll(x, -1, axis=1)
    obs.clear_spans()
    for _ in range(3):
        float(step(x, y))
    spans = obs.spans()
    steps = [e for e in spans if e["name"] == "train/step"]
    assert [(e["attrs"]["step"], e["attrs"]["first"]) for e in steps] == [
        (1, 1), (2, 0), (3, 0)]
    (comp,) = [e for e in spans if e["name"].startswith("compile")]
    assert comp["attrs"]["site"] == "sharded_train_step"
    assert comp["parent"] == steps[0]["id"]
    snap = obs.snapshot()["histograms"]
    # warm steps feed the documented histogram from the span's own seconds
    assert snap["train.step.dispatch_seconds"]["count"] == 2
    assert snap["train.step.dispatch_seconds"]["sum"] == pytest.approx(
        sum(e["dur"] for e in steps[1:]) * 1e-6)
    assert snap["train/step.seconds"]["count"] == 3
