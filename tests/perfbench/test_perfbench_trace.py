"""The trace reducer on a recorded chip trace (perfbench/testdata/: device
plane of the first traced run of mlp_krum_alie_n1024 on the v5e, PR 24,
trimmed to three span periods, names cut to 72 characters) and on a
hand-made one.  The numbers are what the reduction gave when the recording
was made; they pin the arithmetic, not the chip."""

import gzip
import json
import os

import pytest

from perfbench import run, tracereduce


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(run.HERE, "testdata",
                        "trace_mlp_krum_alie_n1024.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_trace_reproduces_its_numbers(recorded):
    r = tracereduce.reduce(recorded)
    assert r["chips"] == 1 and r["periods"] == 3
    assert r["busy_s"] == pytest.approx(0.516895333, abs=1e-9)
    assert r["window_s"] == pytest.approx(0.527475979, abs=1e-9)
    assert r["idle_pct"] == pytest.approx(2.005901011844935, abs=1e-6)
    name, seconds = r["device_ops"][0]
    assert name.startswith("%fusion.67 = bf16[65536,28,28]")   # the gather
    assert seconds == pytest.approx(0.339900301, abs=1e-9)
    assert r["device_ops"][1][0].startswith("%fusion.75 = f32[1024,1024]")
    assert r["device_ops"][1][1] == pytest.approx(0.07814069, abs=1e-9)
    assert len(r["device_ops"]) == tracereduce.TOP
    gap, seconds = r["idle_gaps"][0]
    assert gap == "host between jit_evaluate and jit_convert_element_type"
    assert seconds == pytest.approx(0.010566428, abs=1e-9)
    # self times and gaps account for the window
    assert r["busy_s"] + sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"], abs=1e-9)


def plane(name, modules, ops):
    return {"name": name, "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}


def test_nesting_union_and_gap_names_on_a_hand_made_trace():
    modules = [["jit_span(1)", 0, 100], ["jit_eval(2)", 110, 20],
               ["jit_span(1)", 200, 100], ["jit_eval(2)", 310, 20],
               ["jit_span(1)", 400, 100]]
    ops = [["while", 0, 100], ["gram", 10, 50], ["sort", 60, 30],
           ["scan", 110, 20],
           ["while", 200, 100], ["gram", 210, 50], ["sort", 260, 30],
           ["scan", 310, 20], ["while", 400, 100]]
    r = tracereduce.reduce({"planes": [plane("/device:TPU:0", modules, ops)]})
    assert r["periods"] == 2
    assert r["window_s"] == pytest.approx(400e-9)
    assert r["busy_s"] == pytest.approx(240e-9)          # 2 x (100 + 20)
    assert r["idle_pct"] == pytest.approx(40.0)
    # 'while' keeps only what its children do not cover
    assert dict(map(tuple, r["device_ops"])) == pytest.approx(
        {"gram": 100e-9, "sort": 60e-9, "while": 40e-9, "scan": 40e-9})
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"host between jit_eval and jit_span": 140e-9,
         "host between jit_span and jit_eval": 20e-9})


def test_busy_is_averaged_over_the_chips_that_ran_something():
    mods = [["jit_span(1)", 0, 50], ["jit_span(1)", 100, 50]]
    half = plane("/device:TPU:0", mods, [["a", 0, 50], ["a", 100, 50]])
    full = plane("/device:TPU:1", mods, [["a", 0, 100], ["a", 100, 50]])
    quiet = plane("/device:TPU:2", [], [])
    host = plane("/host:CPU", mods, [["python", 0, 100]])
    r = tracereduce.reduce({"planes": [half, full, quiet, host]})
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["window_s"] == pytest.approx(100e-9)


def test_nothing_on_the_device_reads_as_nothing():
    assert tracereduce.reduce(None) is None
    assert tracereduce.reduce({"planes": []}) is None
    assert tracereduce.reduce({"planes": [plane("/host:CPU", [], [])]}) is None
    assert tracereduce.program_name("jit_span(123)") == "jit_span"
