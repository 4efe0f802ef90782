"""Preemption-safe run lifecycle (ISSUE 4): graceful shutdown, the
exactly-once run journal, schema-v3 lifecycle events, and the report
rollup.

Acceptance contract: SIGTERM/SIGINT at a span boundary checkpoints,
journals 'preempted' and raises Preempted (exit 75 via the CLI); the
journal gives exactly-once round/eval accounting across restarts and
survives torn writes; v1/v2 logs stay valid under the v3 schema; and a
'lifecycle'-bearing run log reports its transitions.
"""

import json
import os
import signal
import threading

import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.utils.checkpoint import Checkpointer
from attacking_federate_learning_tpu.utils.lifecycle import (
    EXIT_DIVERGED, EXIT_OK, EXIT_PREEMPTED, GracefulShutdown, Preempted,
    RunJournal, classify_failure, run_id_for
)
from attacking_federate_learning_tpu.utils.metrics import (
    RunLogger, validate_event
)


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 10)
    kw.setdefault("mal_prop", 0.2)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 10)
    kw.setdefault("test_step", 5)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("log_dir", str(tmp_path / "logs"))
    kw.setdefault("run_dir", str(tmp_path / "runs"))
    return ExperimentConfig(**kw)


# ---------------------------------------------------------------------------
# the journal

def test_journal_exactly_once_and_replay(tmp_path):
    """Commits are monotonic (re-executions clamp to the fresh suffix),
    and a reopened journal replays its high-water mark and eval set."""
    j = RunJournal(str(tmp_path), "r1")
    assert j.start_attempt(0) == 1
    j.commit_rounds(0, 3)
    j.commit_eval(0)
    # Re-execution (rollback or resume replay) below the mark: no-op.
    j.commit_rounds(0, 3)
    j.commit_rounds(2, 5)          # clamped to [4, 5]
    j.commit_eval(0)               # duplicate eval: no-op
    j.commit_eval(5)
    j.finish("done")
    j.close()

    j2 = RunJournal(str(tmp_path), "r1")
    assert j2.high == 5
    assert j2.evals == {0, 5}
    assert j2.attempt == 1
    assert not j2.fresh_round(5) and j2.fresh_round(6)
    assert not j2.fresh_eval(5) and j2.fresh_eval(9)
    assert j2.verify(epochs=6) == []
    # Coverage gaps and cadence mismatches are named.
    problems = j2.verify(epochs=8, test_step=5)
    assert any("never committed" in p for p in problems)
    assert any("eval set mismatch" in p for p in problems)


def test_journal_duplicate_detection_from_raw_file(tmp_path):
    """verify() audits the RAW file, so even a buggy writer (or two
    uncoordinated ones) is caught."""
    d = tmp_path / "dup"
    os.makedirs(d)
    with open(d / "journal.jsonl", "w") as f:
        f.write(json.dumps({"kind": "rounds", "start": 0, "end": 2}) + "\n")
        f.write(json.dumps({"kind": "rounds", "start": 2, "end": 3}) + "\n")
        f.write(json.dumps({"kind": "eval", "round": 0}) + "\n")
        f.write(json.dumps({"kind": "eval", "round": 0}) + "\n")
    j = RunJournal(str(tmp_path), "dup")
    problems = j.verify(epochs=4)
    assert any("more than once: [2]" in p for p in problems)
    assert any("evals committed more than once: [0]" in p for p in problems)


def test_journal_torn_line_sealed_and_skipped(tmp_path):
    """A SIGKILL mid-append leaves a torn last line: the next attempt
    seals it with a newline, the reader skips (and counts) it, and new
    records stay parseable."""
    d = tmp_path / "torn"
    os.makedirs(d)
    with open(d / "journal.jsonl", "w") as f:
        f.write(json.dumps({"kind": "rounds", "start": 0, "end": 4}) + "\n")
        f.write('{"kind": "rounds", "start": 5, "e')     # torn mid-write
    j = RunJournal(str(tmp_path), "torn")
    assert j.high == 4
    assert j.torn_lines == 1
    j.commit_rounds(5, 7)          # appends after sealing the tail
    j.close()
    j2 = RunJournal(str(tmp_path), "torn")
    assert j2.high == 7
    assert j2.verify(epochs=8) == []


def test_manifest_status_transitions(tmp_path):
    j = RunJournal(str(tmp_path), "m")
    j.start_attempt(0)
    assert j.read_manifest()["status"] == "running"
    j.commit_rounds(0, 9)
    j.finish("preempted", EXIT_PREEMPTED, checkpoint="x.npz")
    man = j.read_manifest()
    assert man["status"] == "preempted"
    assert man["exit_code"] == EXIT_PREEMPTED
    assert man["last_round"] == 9 and man["rounds_committed"] == 10
    j.close()
    j2 = RunJournal(str(tmp_path), "m")
    assert j2.start_attempt(10) == 2
    assert j2.read_manifest()["attempt"] == 2


def test_run_id_identity(tmp_path):
    """Stable across processes and across io-only differences; distinct
    across anything that shapes the trajectory."""
    a = _cfg(tmp_path)
    b = _cfg(tmp_path, log_dir=str(tmp_path / "elsewhere"),
             run_dir=str(tmp_path / "other"), output="tee.txt")
    c = _cfg(tmp_path, seed=1)
    d = _cfg(tmp_path, defense="Krum")
    assert run_id_for(a) == run_id_for(b)
    assert run_id_for(a) != run_id_for(c)
    assert run_id_for(a) != run_id_for(d)
    assert run_id_for(a).startswith("SYNTH_MNIST_NoDefense_s0_")


# ---------------------------------------------------------------------------
# graceful shutdown

def test_graceful_shutdown_flag_and_restore():
    sd = GracefulShutdown(signals=(signal.SIGUSR1,))
    before = signal.getsignal(signal.SIGUSR1)
    with sd:
        assert not sd.requested
        os.kill(os.getpid(), signal.SIGUSR1)
        assert sd.requested and sd.source == "SIGUSR1"
        assert sd.should_preempt(0, 0)
    assert signal.getsignal(signal.SIGUSR1) == before


def test_injected_preempt_fires_once_per_lifecycle():
    """preempt_at_round fires for the attempt that STARTED at or before
    the injection point; the resumed attempt (which starts past it)
    must run to completion instead of re-preempting forever."""
    sd = GracefulShutdown(preempt_at_round=4)
    assert not sd.should_preempt(0, 3)
    assert sd.should_preempt(0, 4)
    assert sd.should_preempt(0, 6)       # first boundary past the mark
    assert sd.source == "injected"
    resumed = GracefulShutdown(preempt_at_round=4)
    assert not resumed.should_preempt(5, 7)


# ---------------------------------------------------------------------------
# engine integration

def _engine(cfg, ds=None):
    from attacking_federate_learning_tpu.data.datasets import load_dataset
    ds = ds or load_dataset(cfg.dataset, seed=0,
                            synth_train=cfg.synth_train,
                            synth_test=cfg.synth_test)
    return FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)


def test_engine_preempt_checkpoints_then_resumes_exactly_once(tmp_path):
    """The full lifecycle in-process: injected preempt at a boundary ->
    auto-checkpoint + 'preempted' manifest + Preempted raised; a fresh
    engine resumes, finishes, and the journal + event stream account
    for every round and eval exactly once."""
    cfg = _cfg(tmp_path, checkpoint_every=3)
    rid = run_id_for(cfg)

    exp = _engine(cfg)
    ck = Checkpointer(cfg)
    j = RunJournal(cfg.run_dir, rid)
    sd = GracefulShutdown(preempt_at_round=4)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="lc") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, checkpointer=ck, journal=j, shutdown=sd)
    man = RunJournal(cfg.run_dir, rid).read_manifest()
    assert man["status"] == "preempted"
    assert os.path.exists(man["checkpoint"])

    resumed = _engine(cfg)
    ck2 = Checkpointer(cfg)
    state, extra = ck2.resume(ck2.latest(), with_extra=True)
    resumed.state = state
    resumed.restore_fault_state(extra)
    j2 = RunJournal(cfg.run_dir, rid)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="lc") as logger:
        resumed.run(logger, checkpointer=ck2, journal=j2,
                    shutdown=GracefulShutdown(preempt_at_round=4))
    final = RunJournal(cfg.run_dir, rid)
    assert final.verify(epochs=cfg.epochs, test_step=cfg.test_step) == []
    assert final.read_manifest()["status"] == "done"

    with open(os.path.join(cfg.log_dir, "lc.jsonl")) as f:
        events = [json.loads(line) for line in f]
    for e in events:
        validate_event(e)
    evals = [e["round"] for e in events if e["kind"] == "eval"]
    assert sorted(evals) == [0, 5, 9] and len(set(evals)) == len(evals)
    phases = [e["phase"] for e in events if e["kind"] == "lifecycle"]
    assert phases == ["start", "preempt", "resume", "complete"]


def test_engine_real_sigterm_preempts_at_first_boundary(tmp_path):
    """An actual SIGTERM delivered to the process (not the injection
    seam) is honored at the next span boundary."""
    cfg = _cfg(tmp_path, epochs=6, checkpoint_every=2)
    exp = _engine(cfg)
    sd = GracefulShutdown(signals=(signal.SIGTERM,))
    with sd:
        # Deliver before the loop starts: the request must be honored
        # at the FIRST boundary (deterministic — a timer-thread kill
        # mid-run would race the tiny run's wall clock).
        os.kill(os.getpid(), signal.SIGTERM)
        with RunLogger(cfg, None, cfg.log_dir, jsonl_name="sig") as logger:
            with pytest.raises(Preempted) as ei:
                exp.run(logger, checkpointer=Checkpointer(cfg),
                        journal=RunJournal(cfg.run_dir, "sig"),
                        shutdown=sd)
    assert ei.value.source == "SIGTERM"
    assert int(exp.state.round) >= 1        # at least one round banked
    assert RunJournal(cfg.run_dir, "sig").read_manifest()[
        "status"] == "preempted"


def test_preempt_without_checkpointer_still_checkpoints(tmp_path):
    """--no-checkpoint callers still get a resume point on preempt (a
    preempt that loses the run would defeat the point)."""
    cfg = _cfg(tmp_path, epochs=6)
    exp = _engine(cfg)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="nock") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, journal=None,
                    shutdown=GracefulShutdown(preempt_at_round=2))
    autos = [n for n in os.listdir(os.path.join(cfg.run_dir, cfg.dataset))
             if n.startswith("checkpoint-auto-")]
    assert autos


# ---------------------------------------------------------------------------
# schema v3

def test_v3_lifecycle_schema_rules():
    validate_event({"kind": "lifecycle", "phase": "preempt", "v": 3})
    validate_event({"kind": "lifecycle", "phase": "retry", "round": 4,
                    "attempt": 2, "v": 3})
    # v1/v2 logs stay valid under the v3 reader.
    validate_event({"kind": "round", "round": 1, "v": 1})
    validate_event({"kind": "heartbeat", "rss_mb": 1.0,
                    "last_event_age_s": 0.0, "v": 2})
    # A v3-only kind stamped older is an emitter bug.
    with pytest.raises(ValueError, match="need schema v3"):
        validate_event({"kind": "lifecycle", "phase": "x", "v": 2})
    with pytest.raises(ValueError, match="missing required"):
        validate_event({"kind": "lifecycle", "v": 3})


def test_check_events_accepts_v3(tmp_path):
    import importlib.util

    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "check_events.py")
    spec = importlib.util.spec_from_file_location("check_events", path)
    ce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ce)

    good = str(tmp_path / "v3.jsonl")
    with open(good, "w") as f:
        f.write(json.dumps({"kind": "lifecycle", "phase": "start",
                            "attempt": 1, "v": 3}) + "\n")
        f.write(json.dumps({"kind": "eval", "round": 0, "test_loss": 0.1,
                            "accuracy": 50.0, "correct": 32,
                            "test_size": 64, "v": 1}) + "\n")
        f.write(json.dumps({"kind": "heartbeat", "rss_mb": 1.0,
                            "last_event_age_s": 0.1, "v": 2}) + "\n")
    assert ce.main([good]) == 0
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps({"kind": "lifecycle", "phase": "start",
                            "v": 2}) + "\n")
    assert ce.main([bad]) == 1


# ---------------------------------------------------------------------------
# failure classes + exit codes

def test_classify_failure_classes():
    assert classify_failure(EXIT_OK) == "done"
    assert classify_failure(EXIT_PREEMPTED) == "preempted"
    assert classify_failure(EXIT_DIVERGED) == "divergence"
    assert classify_failure(1, "RESOURCE_EXHAUSTED: out of memory") == "oom"
    assert classify_failure(-9, "std::bad_alloc") == "oom"
    assert classify_failure(1, "Unable to initialize backend") == "backend"
    assert classify_failure(1, "TPU initialization failed") == "backend"
    assert classify_failure(
        1, "FloatingPointError: server state diverged") == "divergence"
    assert classify_failure(-9, "") == "crash"
    # A supervisor-detected stall wins over whatever the kill left.
    assert classify_failure(-15, "", stalled=True) == "stall"
    assert classify_failure(EXIT_PREEMPTED, "", stalled=True) == "stall"


# ---------------------------------------------------------------------------
# report rollup

def test_report_lifecycle_summary(capsys):
    from attacking_federate_learning_tpu import report

    events = [
        {"kind": "lifecycle", "phase": "start", "attempt": 1, "v": 3},
        {"kind": "lifecycle", "phase": "preempt", "round": 4,
         "attempt": 1, "v": 3},
        {"kind": "lifecycle", "phase": "retry", "failure": "preempted",
         "v": 3},
        {"kind": "lifecycle", "phase": "degrade", "failure": "oom",
         "step": "batch_halved_to_8", "v": 3},
        {"kind": "lifecycle", "phase": "resume", "round": 5,
         "attempt": 2, "v": 3},
        {"kind": "lifecycle", "phase": "complete", "round": 9,
         "attempt": 2, "v": 3},
    ]
    s = report.summarize_run(events)
    lc = s["lifecycle"]
    assert lc["attempts"] == 2
    assert lc["last_phase"] == "complete"
    assert lc["phases"]["preempt"] == 1
    assert lc["degradations"] == ["batch_halved_to_8"]
    assert lc["failures"] == {"preempted": 1, "oom": 1}
    report._print_run("x", s, print)
    out = capsys.readouterr().out
    assert "lifecycle:" in out and "degradations" in out


def test_threaded_sigterm_is_seen_by_main_thread(tmp_path):
    """Signals sent from a worker thread (the supervisor's SIGTERM
    arrives asynchronously in the real topology) still set the flag in
    the main thread's handler."""
    sd = GracefulShutdown(signals=(signal.SIGUSR2,))
    with sd:
        t = threading.Thread(
            target=lambda: os.kill(os.getpid(), signal.SIGUSR2))
        t.start()
        t.join()
        # The handler runs between bytecodes of the main thread; give
        # it one explicit chance.
        for _ in range(100):
            if sd.requested:
                break
        assert sd.requested


def test_exactly_once_faulted_replay_suppression(tmp_path):
    """With fault injection on (per-round 'fault' events with or
    without telemetry), a resume replays rounds below the journal mark
    WITHOUT re-emitting their events — the stream stays exactly-once
    even though the rounds re-execute."""
    from attacking_federate_learning_tpu.config import FaultConfig

    fc = FaultConfig(dropout=0.2, straggler=0.15)
    cfg = _cfg(tmp_path, users_count=12, epochs=8, test_step=4,
               defense="TrimmedMean", faults=fc, checkpoint_every=3)
    rid = "faulted_once"
    exp = _engine(cfg)
    ck = Checkpointer(cfg)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="f1") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, rid),
                    shutdown=GracefulShutdown(preempt_at_round=4))
    resumed = _engine(cfg)
    state, extra = ck.resume(ck.latest(), with_extra=True)
    resumed.state = state
    resumed.restore_fault_state(extra)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="f1") as logger:
        resumed.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, rid),
                    shutdown=GracefulShutdown(preempt_at_round=4))
    with open(os.path.join(cfg.log_dir, "f1.jsonl")) as f:
        events = [json.loads(line) for line in f]
    fault_rounds = [e["round"] for e in events if e["kind"] == "fault"]
    assert sorted(fault_rounds) == list(range(8))      # once each
    assert RunJournal(cfg.run_dir, rid).verify(
        epochs=8, test_step=4) == []
