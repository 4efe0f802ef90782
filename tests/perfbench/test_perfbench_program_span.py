"""The reader of the program's own host spans and counters
(perfbench/readers/program_span.py) and the five metric files that use it.
The readings are checked on a synthetic ring; a CPU run's times mean
nothing and none is asserted."""

import json
import os

import pytest

from perfbench import run
from perfbench.readers import program_span

METRICS = {"host_seam_ms": "round loop", "host_log_ms": "round loop",
           "setup_data_s": "entry and device selection",
           "setup_build_s": "entry and device selection",
           "trace_lower_s": "entry and device selection"}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_loads_and_names_a_layer_the_benchmark_has(name):
    m = run.load_json("metrics", name)
    assert m["kind"] == "per_layer" and m["reader"] == "program_span"
    assert m["args"] == {"what": name} and m["better"] == "lower"
    assert m["source"] == ("program_counter" if name == "trace_lower_s"
                           else "program_span")
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    names = [e["name"] for e in b["per_layer"]]
    # what PR 24 accepted comes first, in the order it had; PR 25's five
    # were appended to it, and later PRs append after them
    accepted = b["per_layer"][:names.index("host_seam_ms")]
    assert not {e["name"] for e in accepted} & set(METRICS)
    assert m["layer"] == METRICS[name]
    assert m["layer"] in {e["layer"] for e in accepted}
    entry = [e for e in b["per_layer"] if e["name"] == name]
    assert len(entry) == 1
    assert entry[0]["moves"] == m["moves"] == (
        "rounds_per_s" if name.startswith("host_") else "setup_s")
    assert names[len(accepted):len(accepted) + len(METRICS)] == [
        "host_seam_ms", "host_log_ms", "setup_data_s", "setup_build_s",
        "trace_lower_s"]


def _interval(dispatch, eval_, wait, log, poll):
    names = ("dispatch_span", "dispatch_eval", "wait_device", "log", "poll")
    return [("interval." + n, a, b) for n, (a, b) in
            zip(names, (dispatch, eval_, wait, log, poll))]


def _ring():
    """An older experiment's set-up, then the set-up of the one the window
    ran (t = 10..30), two warm-up intervals, and a window of three
    intervals whose marks (read inside each poll) are at 100, 110, 120
    and 130 s."""
    spans = [("setup.dataset", 1.0, 2.0), ("setup.attacker", 2.0, 2.5),
             ("setup.experiment", 2.5, 4.0), ("setup.place_data", 3.0, 3.5),
             ("setup.dataset", 10.0, 14.0), ("setup.attacker", 14.0, 14.5),
             ("setup.experiment", 15.0, 30.0),
             ("setup.model_init", 15.0, 16.0),
             ("setup.partition", 16.0, 17.0),
             ("setup.place_data", 17.0, 20.0),
             ("setup.build_round_fns", 20.0, 30.0)]
    spans += _interval((30.0, 31.0), (31.0, 31.1), (31.1, 89.0),
                       (89.0, 89.5), (89.5, 89.6))
    spans += _interval((89.7, 89.8), (89.8, 89.9), (89.9, 99.9),
                       (99.9, 99.95), (99.95, 100.3))
    spans += _interval((100.301, 100.302), (100.302, 100.303),
                       (100.303, 109.996), (109.996, 109.9962),
                       (109.9990, 110.0004))
    spans += _interval((110.0005, 110.0010), (110.001, 110.002),
                       (110.002, 119.997), (119.997, 119.9974),
                       (119.9990, 120.0004))
    spans += _interval((120.0005, 120.0010), (120.001, 120.002),
                       (120.002, 129.995), (129.995, 129.9956),
                       (129.9990, 130.0004))
    marks = [(5, 100.0), (10, 110.0), (15, 120.0), (20, 130.0)]
    lowerings = [
        {"stage": "jaxpr_trace", "name": "old", "secs": 1.0, "t": 3.0},
        {"stage": "jaxpr_trace", "name": "init", "secs": 0.5, "t": 15.5},
        # a nested trace inside its caller's: counted once
        {"stage": "jaxpr_trace", "name": "inner", "secs": 1.0, "t": 33.0},
        {"stage": "jaxpr_trace", "name": "span", "secs": 4.0, "t": 35.0},
        {"stage": "jaxpr_to_mlir", "name": "jit(span)", "secs": 2.0,
         "t": 37.0},
        # inside the window (there should be none; it is not set-up)
        {"stage": "jaxpr_trace", "name": "late", "secs": 1.0, "t": 105.0},
    ]
    return sorted(spans, key=lambda s: (s[1], -s[2])), lowerings, marks


def test_reads_none_on_an_empty_recorder_and_without_the_program(monkeypatch):
    marks = [(5, 100.0), (10, 110.0)]
    monkeypatch.setattr(program_span, "recorded", lambda: ([], []))
    for what in METRICS:
        assert program_span.read({"marks": marks}, what=what) is None
    # a program without the recorder (an older commit)
    monkeypatch.setattr(program_span, "recorded", lambda: None)
    for what in METRICS:
        assert program_span.read({"marks": marks}, what=what) is None
    # no window
    spans, lowerings, _ = _ring()
    monkeypatch.setattr(program_span, "recorded",
                        lambda: (spans, lowerings))
    for what in METRICS:
        assert program_span.read({"marks": []}, what=what) is None
    with pytest.raises(ValueError):
        program_span.read({"marks": marks}, what="no_such_reading")


def test_medians_and_sums_on_a_synthetic_ring(monkeypatch):
    spans, lowerings, marks = _ring()
    monkeypatch.setattr(program_span, "recorded",
                        lambda: (spans, lowerings))
    obs = {"marks": marks}
    # seams inside the window: end of a wait to the end of the next
    # dispatch, 109.996 -> 110.0010 and 119.997 -> 120.0010; the
    # warm-up's wait began before the window and the last interval has
    # no dispatch after it
    assert program_span.read(obs, what="host_seam_ms") == pytest.approx(
        (5.0 + 4.0) / 2)
    # the three logs that lie inside the window: 0.2, 0.4, 0.6 ms
    assert program_span.read(obs, what="host_log_ms") == pytest.approx(0.4)
    # the newest experiment's set-up, not the older one's
    assert program_span.read(obs, what="setup_data_s") == pytest.approx(
        4.0 + 3.0)
    assert program_span.read(obs, what="setup_build_s") == pytest.approx(
        0.5 + 15.0 - 3.0)
    # union of [15, 15.5], [32, 33] inside [31, 35], [35, 37]; the old
    # experiment's and the window's are left out
    assert program_span.read(obs, what="trace_lower_s") == pytest.approx(
        0.5 + 4.0 + 2.0)


def test_reads_the_live_recorder_after_a_tiny_run():
    """Through the program's real recorder: one tiny experiment built and
    run on the CPU, the reader finds its spans (values not asserted)."""
    import time

    from attacking_federate_learning_tpu.utils import costs

    costs.install_cache_counters()
    cell = run.load_cell("tiny_cpu", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tiny"))
    t0 = time.perf_counter()
    res = run.measure(cell, 7, 0.5, True)
    assert set(METRICS) <= set(res["metrics"])
    for name in METRICS:
        value = res["metrics"][name]["value"]
        assert 0 <= value < 1e3 * (time.perf_counter() - t0)
    got = program_span.recorded()
    assert got is not None and got[0] and got[1]
