"""The program's one span vocabulary (ISSUE 25): the host recorder
(utils/profiling.py ``PhaseTimer`` / ``RECORDER``), the ``interval.*``
phases of ``_run_body``, the ``setup.*`` phases of building an experiment
and the compile pipeline's trace/lower counter (utils/costs.py
``trace_lower_log``).  Times read here are CPU walls and mean nothing;
only names, order and counts are checked."""

import json
import time

import jax
import jax.numpy as jnp
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.utils import costs, profiling
from attacking_federate_learning_tpu.utils.metrics import RunLogger
from attacking_federate_learning_tpu.utils.profiling import (
    RECORDER, PhaseTimer
)


def test_recorder_ring_is_bounded_and_totals_are_not():
    timer = PhaseTimer(ring=4)
    for i in range(10):
        with timer.span(f"s{i % 2}"):
            pass
    snap = timer.snapshot()
    assert len(snap["spans"]) == 4
    assert [s[0] for s in snap["spans"]] == ["s0", "s1", "s0", "s1"]
    assert snap["counts"] == {"s0": 5, "s1": 5}
    assert set(snap["totals"]) == {"s0", "s1"}


def test_recorder_totals_counts_and_summary_since():
    timer = PhaseTimer()
    with timer.span("a"):
        pass
    before = timer.snapshot()
    with timer.span("a"):
        pass
    with timer.span("b"):
        pass
    assert timer.counts == {"a": 2, "b": 1}
    total = sum(end - start for name, start, end in timer.ring
                if name == "a")
    assert timer.totals["a"] == pytest.approx(total)
    assert {k: v["count"] for k, v in timer.summary().items()} == {
        "a": 2, "b": 1}
    assert {k: v["count"] for k, v in
            timer.summary(since=before).items()} == {"a": 1, "b": 1}
    assert timer.summary(since=timer.snapshot()) == {}


def test_spans_are_recorded_with_no_profiler_running():
    n = RECORDER.counts.get("test.nothing_traces", 0)
    with profiling.span("test.nothing_traces"):
        pass
    assert RECORDER.counts["test.nothing_traces"] == n + 1
    name, start, end = RECORDER.ring[-1]
    assert name == "test.nothing_traces" and end >= start


def test_snapshot_orders_by_start_with_a_parent_before_its_children():
    timer = PhaseTimer()
    with timer.span("outer"):
        with timer.span("first"):
            pass
        with timer.span("second"):
            pass
    with timer.span("after"):
        pass
    # the ring holds spans in the order they END; a snapshot in the order
    # they start
    assert [s[0] for s in timer.ring] == ["first", "second", "outer",
                                          "after"]
    spans = timer.snapshot()["spans"]
    assert [s[0] for s in spans] == ["outer", "first", "second", "after"]
    assert all(a[1] <= b[1] for a, b in zip(spans, spans[1:]))


def test_span_as_a_decorator_keeps_the_function_and_records_each_call():
    @profiling.span("test.decorated")
    def double(x, *, y=0):
        """doc"""
        return 2 * x + y

    n = RECORDER.counts.get("test.decorated", 0)
    assert double(2, y=1) == 5 and double(1) == 2
    assert double.__name__ == "double" and double.__doc__ == "doc"
    assert RECORDER.counts["test.decorated"] == n + 2


# --- the round loop's host phases -----------------------------------------

class _Never:
    """A ``shutdown=`` that never preempts (the poll is still a phase)."""
    source = None

    def should_preempt(self, start, round_):
        return False


def _names_since(t):
    return [s[0] for s in RECORDER.snapshot()["spans"] if s[1] >= t]


def _run(tmp_path, shutdown=None, journal=None, checkpointer=None, **kw):
    base = dict(dataset=C.SYNTH_MNIST, users_count=9, mal_prop=0.22,
                batch_size=16, epochs=12, test_step=4, synth_train=256,
                synth_test=64, defense="Krum", log_dir=str(tmp_path))
    base.update(kw)
    cfg = ExperimentConfig(**base)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    before = time.perf_counter()
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    with RunLogger(cfg, cfg.output, cfg.log_dir) as logger:
        exp.run(logger, shutdown=shutdown, journal=journal,
                checkpointer=checkpointer)
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    # nothing else runs in this process meanwhile: the spans since are
    # this experiment's (by the clock, not by position: the ring is
    # bounded and a long test process may have filled it)
    return _names_since(before), events


def _intervals(names):
    """Split the ``interval.*`` names at each ``interval.dispatch_span``."""
    out = []
    for name in names:
        if not name.startswith("interval."):
            continue
        if name == "interval.dispatch_span":
            out.append([])
        out[-1].append(name)
    return out


def test_run_body_emits_every_interval_phase_once_and_in_order(tmp_path):
    names, events = _run(tmp_path, shutdown=_Never())
    setup = [n for n in names if n.startswith("setup.")]
    assert setup == ["setup.experiment", "setup.model_init",
                     "setup.partition", "setup.place_data",
                     "setup.build_round_fns"]
    intervals = _intervals(names)
    # rounds 0, 1-4, 5-8, 9-11: four spans, four evals
    assert len(intervals) == 4
    for got in intervals:
        assert got == ["interval.dispatch_span", "interval.dispatch_eval",
                       "interval.wait_device", "interval.log",
                       "interval.poll"]
    # the run's totals leave once, in the 'profile' event
    profile = [e for e in events if e["kind"] == "profile"]
    assert len(profile) == 1
    phases = profile[0]["phases"]
    assert phases["interval.wait_device"]["count"] == 4
    assert not any(k.startswith("setup.") for k in phases)


def test_run_body_names_the_optional_phases_where_they_run(tmp_path):
    from attacking_federate_learning_tpu.utils.checkpoint import (
        Checkpointer
    )
    from attacking_federate_learning_tpu.utils.lifecycle import RunJournal

    kw = dict(telemetry=True, checkpoint_every=4,
              run_dir=str(tmp_path / "runs"))
    cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, **kw)
    journal = RunJournal(str(tmp_path / "runs"), "spans")
    names, _ = _run(tmp_path, journal=journal,
                    checkpointer=Checkpointer(cfg), **kw)
    intervals = _intervals(names)
    order = ["interval.dispatch_span", "interval.fetch_telemetry",
             "interval.journal", "interval.dispatch_eval",
             "interval.wait_device", "interval.log", "interval.checkpoint",
             "interval.journal", "interval.checkpoint"]
    for got in intervals:
        # every phase that ran is in the documented order (a phase whose
        # work was not due is absent), and waiting precedes logging
        it = iter(order)
        assert all(name in it for name in got), got
        assert got.index("interval.wait_device") \
            < got.index("interval.log")
        assert "interval.fetch_telemetry" in got
        assert got.count("interval.journal") == 2
    assert any("interval.checkpoint" in got for got in intervals)


def test_wait_span_is_named_where_the_attack_can_craft_a_nan(tmp_path):
    exp_names, _ = _run(tmp_path, epochs=5)
    assert "interval.wait_span" not in exp_names
    base = dict(dataset=C.SYNTH_MNIST, users_count=9, mal_prop=0.22,
                batch_size=16, epochs=5, test_step=4, synth_train=256,
                synth_test=64, defense="Krum", log_dir=str(tmp_path))
    cfg = ExperimentConfig(**base)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=256, synth_test=64)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    exp._check_attack_nan = True
    before = time.perf_counter()
    exp.run_span(0, 2)
    assert _names_since(before) == [
        "interval.checkpoint", "interval.dispatch_span",
        "interval.wait_span"]


# --- the compile pipeline's other two stages -------------------------------

def test_trace_lower_log_grows_on_a_fresh_jit_and_compile_log_is_unchanged():
    costs.install_cache_counters()
    lowered0, compiled0 = costs.trace_lower_log(), costs.compile_log()

    @jax.jit
    def fresh_program_for_the_trace_lower_log(x):
        return jnp.tanh(x) @ x.T + 25.0

    fresh_program_for_the_trace_lower_log(jnp.ones((8, 8))).block_until_ready()
    lowered = costs.trace_lower_log()[len(lowered0):]
    stages = {e["stage"] for e in lowered
              if "fresh_program_for_the_trace_lower_log" in str(e["name"])}
    assert stages == {"jaxpr_trace", "jaxpr_to_mlir"}
    for e in lowered:
        assert set(e) == {"stage", "name", "secs", "t"}
        assert e["secs"] >= 0 and e["t"] > 0
    ts = [e["t"] for e in lowered]
    assert ts == sorted(ts)
    compiled = [c for c in costs.compile_log()[len(compiled0):]
                if "fresh_program_for_the_trace_lower_log" in c["name"]]
    assert len(compiled) == 1
    assert set(compiled[0]) == {"name", "compile_s", "cache"}
    assert compiled[0]["cache"] in ("hit", "miss", "uncached")
    # a second call traces, lowers and compiles nothing
    n_low, n_comp = len(costs.trace_lower_log()), len(costs.compile_log())
    fresh_program_for_the_trace_lower_log(jnp.ones((8, 8))).block_until_ready()
    assert len(costs.trace_lower_log()) == n_low
    assert len(costs.compile_log()) == n_comp
