"""BENCHMARK.json against the contract, and against the files it names."""

import json
import os
import re

import pytest

import run as bench_run
from harness import common

MAN = json.load(open(os.path.join(common.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_sizes():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["benchmark"] and MAN["command"][-1] == "benchmark/run.py"
    assert 1 <= MAN["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(common.ROOT, "BENCHMARK.json")) < 64 * 1024
    cells = len(MAN["workloads"])
    # a full check must fit 43200 s with the full 24 cells
    assert (2 + 14 * 24) * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(cells // 4, 1)


def test_names_units_and_entry_keys():
    names = []
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
        names.append(m["name"])
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_every_entry_has_its_files():
    used = set()
    for w in MAN["workloads"]:
        cell = common.load_json("workloads", w["name"] + ".json")
        assert {k: cell[k] for k in w} == w
        common.load_json("traffic", w["traffic"] + ".json")
        used.add(w["config"])
    for c in MAN["configs"]:
        f = json.load(open(os.path.join(common.ROOT, c["file"])))
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(common.BENCH, "harness",
                                           f"run_{f['runner']}.py"))
    assert used == {c["name"] for c in MAN["configs"]}
    for m in MAN["per_layer"]:
        assert callable(bench_run.reader(m["name"]))


def test_what_each_cell_reports():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    for w in MAN["workloads"]:
        mine = [m["name"] for m in bench_run.metrics_of(MAN, w["name"], "end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2
        layer = bench_run.metrics_of(MAN, w["name"], "per_layer")
        assert layer
        for m in layer:  # the metric it should move is reported here
            assert m["moves"] in mine, (w["name"], m["name"])
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        assert m.get("moves", "setup_s") in e2e


def test_peaks_table_refuses_an_unknown_device():
    from harness import device

    assert device.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("cpu")
    with pytest.raises(KeyError):
        device.peaks("_source")


def test_run_py_fails_on_a_cpu_and_prints_no_result():
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         MAN["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=common.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "TPU" in r.stderr
