"""The runner's functions end to end on the CPU, through a test-only cell
(tests/perfbench/tiny/): the keys of the result object, the window's
arithmetic, and the refusal to run without a TPU.  Times read here are CPU
walls and mean nothing; only shapes and counts are checked."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import run

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


@pytest.fixture(scope="module")
def results():
    cell = run.load_cell("tiny_cpu", TINY)
    return {trace: run.measure(cell, 2**31 + 12345, 1.0, bool(trace))
            for trace in (0, 1)}


def test_result_object_has_the_contract_keys(results):
    for trace, res in results.items():
        assert {"correct", "attempted", "failed", "metrics",
                "device"} <= set(res)
        assert res["correct"] is True and res["failed"] == 0
        assert res["attempted"] > 0 and res["attempted"] % 5 == 0
        assert {"platform", "kind", "count",
                "memory_peak_bytes"} <= set(res["device"])
        for value in res["metrics"].values():
            assert set(value) == {"value", "unit"}
        json.dumps(res)


def test_untraced_run_reports_the_end_to_end_metrics(results):
    got = set(results[0]["metrics"])
    assert got == {"setup_s", "rounds_per_s", "peak_hbm_gb"}
    assert results[0]["metrics"]["rounds_per_s"]["value"] > 0


def test_traced_run_reports_per_layer_metrics_only(results):
    got = set(results[1]["metrics"])
    assert {"compile_s", "cache_misses", "interval_p50_ms", "eval_ms",
            "deliver_ms", "defense_ms"} <= got
    assert not got & {"setup_s", "rounds_per_s", "peak_hbm_gb"}
    # no TPU plane in a CPU trace and no peaks for a CPU: the readers find
    # nothing to read and the metrics are left out, not invented
    assert not got & {"device_idle_pct", "defense_roofline"}
    assert "breakdown" not in results[1]


def test_window_opens_after_warm_up_and_closes_on_time(monkeypatch):
    clock = iter([0.0, 1.0, 1.0, 2.0, 3.5])
    monkeypatch.setattr(run.time, "perf_counter", lambda: next(clock))
    w = run.Window(seconds=2.0)
    assert not w.should_preempt(0, 0)       # the interval that compiles
    assert not w.should_preempt(0, 5)       # one more: the window opens
    assert not w.should_preempt(0, 10)      # 1.0 s in
    assert w.should_preempt(0, 15)          # 2.5 s in: closed
    monkeypatch.undo()
    assert w.setup_end == 1.0 and w.source == "perfbench_window_closed"
    marks = w.window_marks()
    assert [r for r, _ in marks] == [5, 10, 15]
    from perfbench.readers import interval_quantile, interval_rate
    obs = {"marks": marks}
    assert interval_rate.read(obs) == pytest.approx(10 / 2.5)
    assert interval_quantile.read(obs, q=0.95) == pytest.approx(1500.0)
    assert interval_quantile.read(obs, q=0.5) == pytest.approx(1000.0)


def test_no_tpu_means_a_nonzero_exit_and_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = sorted(os.listdir(os.path.join(run.HERE, "workloads")))[0]
    proc = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         cell[:-len(".json")], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
