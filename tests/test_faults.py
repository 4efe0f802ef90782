"""Fault-injection harness + graceful degradation (ISSUE 2).

Acceptance contract: with faults OFF the engine path is untouched (the
config defaults to ``faults=None`` and every pre-existing trajectory
test pins that); a seeded dropout+straggler+corrupt run under each
mask-aware distance defense completes 30 rounds without raising, with
per-round 'fault' events matching the injected schedule exactly; a
killed run resumes from the last auto-checkpoint bit-for-bit; and a
diverging run rolls back to the last good checkpoint instead of
aborting (bounded by max_rollbacks).
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import (
    ExperimentConfig, FaultConfig
)
from attacking_federate_learning_tpu.core import faults as F
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.defenses.kernels import (
    bulyan, krum, trimmed_mean
)
from attacking_federate_learning_tpu.defenses.median import median
from attacking_federate_learning_tpu.utils.checkpoint import Checkpointer
from attacking_federate_learning_tpu.utils.metrics import RunLogger


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


def _run(cfg, tmp_path, name, checkpointer=None):
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name=name) as logger:
        exp.run(logger, checkpointer=checkpointer)
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    return exp, events


# ---------------------------------------------------------------------------
# the fault model itself (core/faults.py)

def test_fault_masks_deterministic_and_honest_corruption():
    """The schedule is a pure function of (config, round): two draws
    agree, and corruption never touches the attacker's rows [0, f)."""
    fc = FaultConfig(dropout=0.3, straggler=0.2, corrupt=0.3)
    cfg = ExperimentConfig(faults=fc, dataset=C.SYNTH_MNIST)
    key = F.fault_key(cfg)
    for t in (0, 3, 17):
        a = [np.asarray(x) for x in F.fault_masks(key, t, 16, 4, fc)]
        b = [np.asarray(x) for x in F.fault_masks(key, t, 16, 4, fc)]
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        drop, stale, corrupt = a
        assert not corrupt[:4].any()          # honest rows only
        assert not (drop & stale).any()       # exclusive
        assert not (drop & corrupt).any()
        assert not (stale & corrupt).any()
    # Cold ring buffer: stragglers suppressed at t < delay.
    drop0, stale0, _ = (np.asarray(x)
                        for x in F.fault_masks(key, 0, 16, 4, fc))
    assert not stale0.any()


def test_apply_faults_straggler_ring_buffer():
    """A straggler at round t submits what it computed at t-delay; the
    buffer carries fresh (pre-fault) submissions."""
    fc = FaultConfig(straggler=0.999, straggler_delay=2)
    cfg = ExperimentConfig(faults=fc, dataset=C.SYNTH_MNIST)
    key = F.fault_key(cfg)
    m, d = 6, 5
    state = F.init_fault_state(fc, m, d)
    grads_at = {t: jnp.full((m, d), float(t + 1)) for t in range(5)}
    for t in range(5):
        out, dropped, state, stats = F.apply_faults(
            grads_at[t], t, key, state, fc, 0)
        out = np.asarray(out)
        stale = np.asarray(F.fault_masks(key, t, m, 0, fc)[1])
        if t < 2:
            assert not stale.any()
            np.testing.assert_array_equal(out, np.asarray(grads_at[t]))
        else:
            assert stale.any()                # p=0.999: virtually sure
            np.testing.assert_array_equal(out[stale],
                                          np.asarray(grads_at[t - 2])[stale])
            np.testing.assert_array_equal(out[~stale],
                                          np.asarray(grads_at[t])[~stale])
            assert int(stats["fault_injected_straggler"]) == stale.sum()


def test_quarantine_masks_nonfinite_and_dropped():
    G = jnp.asarray(np.ones((5, 4), np.float32))
    G = G.at[1].set(jnp.nan).at[3].set(jnp.inf)
    dropped = jnp.asarray([False, False, True, False, False])
    clean, mask, stats = F.quarantine(G, dropped)
    np.testing.assert_array_equal(np.asarray(mask),
                                  [True, False, False, False, True])
    assert np.isfinite(np.asarray(clean)).all()
    assert int(stats["fault_quarantined"]) == 3


# ---------------------------------------------------------------------------
# mask-aware kernels: the quarantine mask must reproduce the
# shrunk-cohort estimator exactly (defenses/kernels.py)

@pytest.mark.parametrize("name,fn", [
    ("Krum", krum), ("TrimmedMean", trimmed_mean), ("Bulyan", bulyan),
    ("Median", median),
])
def test_masked_kernel_matches_survivor_submatrix(name, fn):
    rng = np.random.default_rng(7)
    n, f, d = 13, 2, 40
    G = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    dead = [3, 8]
    mask = jnp.asarray([i not in dead for i in range(n)])
    Gz = G.at[jnp.asarray(dead)].set(0.0)     # quarantine zeroes dead rows
    keep = np.asarray([i for i in range(n) if i not in dead])
    got = np.asarray(fn(Gz, n, f, mask=mask))
    want = np.asarray(fn(G[keep], len(keep), f))
    np.testing.assert_allclose(got, want, atol=1e-6)
    # And identically under jit (the fused round traces this path).
    got_j = np.asarray(jax.jit(
        lambda g, m: fn(g, n, f, mask=m))(Gz, mask))
    np.testing.assert_array_equal(got, got_j)


@pytest.mark.parametrize("name,fn", [
    ("Krum", krum), ("TrimmedMean", trimmed_mean), ("Bulyan", bulyan),
    ("Median", median),
])
def test_masked_kernel_all_alive_matches_unmasked(name, fn):
    rng = np.random.default_rng(11)
    n, f = 12, 2
    G = jnp.asarray(rng.standard_normal((n, 30)).astype(np.float32))
    a = np.asarray(fn(G, n, f))
    b = np.asarray(fn(G, n, f, mask=jnp.ones((n,), bool)))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_host_impls_reject_mask():
    G = jnp.zeros((9, 4))
    with pytest.raises(ValueError, match="mask"):
        trimmed_mean(G, 9, 2, impl="host", mask=jnp.ones((9,), bool))
    with pytest.raises(ValueError, match="mask"):
        median(G, 9, 2, impl="host", mask=jnp.ones((9,), bool))
    with pytest.raises(ValueError, match="mask"):
        bulyan(G, 9, 1, selection_impl="host", mask=jnp.ones((9,), bool))


# ---------------------------------------------------------------------------
# engine integration

def test_faults_disabled_is_reference_path(tmp_path):
    """faults=None and an all-zero FaultConfig both leave the engine on
    the reference path: no fault state, no fault events."""
    cfg = _cfg(tmp_path, epochs=2)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0))
    assert exp.faults is None and exp._fault_state is None
    cfg0 = _cfg(tmp_path, epochs=2,
                faults=FaultConfig(dropout=0.0, straggler=0.0, corrupt=0.0))
    exp0 = FederatedExperiment(cfg0, attacker=DriftAttack(1.0))
    assert exp0.faults is None


def test_no_fault_round_hlo_bit_identical(tmp_path):
    """Acceptance: with all fault flags off the compiled round program
    is bit-identical — faults=None and an all-zero FaultConfig lower to
    byte-identical HLO, and none of the fault machinery's ops appear in
    it (same methodology as PR 1's telemetry bit-identity pin)."""
    ds = load_dataset(C.SYNTH_MNIST, seed=0, synth_train=256,
                      synth_test=64)

    def lowered(faults):
        cfg = _cfg(tmp_path, epochs=2, defense="Krum", faults=faults)
        exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=ds)
        args = ((exp.data, exp.state, jnp.asarray(0, jnp.int32))
                if exp.faults is None
                else (exp.data, exp.state, jnp.asarray(0, jnp.int32),
                      exp._fault_state))
        return exp._fused_round.lower(*args).as_text()

    none_text = lowered(None)
    zero_text = lowered(FaultConfig(dropout=0.0, straggler=0.0,
                                    corrupt=0.0))
    assert none_text == zero_text
    # The faulted build is a different program (sanity that the pin
    # above is not vacuous) — but only when faults are actually on.
    faulted = lowered(FaultConfig(dropout=0.2))
    assert faulted != none_text


def test_fault_requires_mask_aware_defense(tmp_path):
    with pytest.raises(ValueError, match="mask-aware"):
        FederatedExperiment(
            _cfg(tmp_path, defense="GeoMedian",
                 faults=FaultConfig(dropout=0.1)),
            attacker=DriftAttack(1.0))


@pytest.mark.parametrize("match", [
    "participation",
    # ISSUE 9 satellite: the rejection must name --aggregation async
    # as the supported straggler route (stragglers become extra
    # arrival delay in the buffered round, core/async_rounds.py).
    "aggregation async",
    "extra arrival delay",
])
def test_straggler_requires_full_participation(tmp_path, match):
    with pytest.raises(ValueError, match=match):
        FederatedExperiment(
            _cfg(tmp_path, participation=0.5,
                 faults=FaultConfig(straggler=0.1)),
            attacker=DriftAttack(1.0))


def _load_fault_matrix():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "fault_matrix.py")
    spec = importlib.util.spec_from_file_location("fault_matrix", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("defense", ["Krum", "TrimmedMean", "Bulyan"])
def test_faulted_30round_run_counts_match_schedule(tmp_path, defense):
    """Acceptance: dropout=0.2/straggler=0.1/corrupt=0.05, 30 rounds,
    no raise, finite weights, and every per-round 'fault' event matches
    the host replay of the injected schedule exactly."""
    fm = _load_fault_matrix()
    cfg = _cfg(tmp_path, users_count=15, epochs=30, test_step=30,
               defense=defense,
               faults=FaultConfig(dropout=0.2, straggler=0.1,
                                  corrupt=0.05))
    exp, events = _run(cfg, tmp_path, f"acc30_{defense}")
    assert int(exp.state.round) == 30
    assert np.isfinite(np.asarray(exp.state.weights)).all()
    fault_events = sorted((e for e in events if e["kind"] == "fault"),
                          key=lambda e: e["round"])
    assert [e["round"] for e in fault_events] == list(range(30))
    want = fm.expected_schedule(cfg, exp.m, exp.m_mal, 30)
    for got, exp_row in zip(fault_events, want):
        for k, v in exp_row.items():
            assert int(got[k]) == v, (got, exp_row)


def test_fault_span_matches_per_round(tmp_path):
    """The scanned fault span (one program per interval) must produce
    exactly the per-round dispatch's weights and fault state."""
    fc = FaultConfig(dropout=0.2, straggler=0.2, corrupt=0.1)
    cfg = _cfg(tmp_path, users_count=12, epochs=7, defense="TrimmedMean",
               faults=fc)
    a = FederatedExperiment(cfg, attacker=DriftAttack(1.0))
    for t in range(7):
        a.run_round(t)
    b = FederatedExperiment(cfg, attacker=DriftAttack(1.0))
    b.run_span(0, 7)
    np.testing.assert_array_equal(np.asarray(a.state.weights),
                                  np.asarray(b.state.weights))
    np.testing.assert_array_equal(np.asarray(a._fault_state["stale"]),
                                  np.asarray(b._fault_state["stale"]))


def test_resume_after_kill_bit_for_bit(tmp_path):
    """A run killed mid-span resumes from the last auto-checkpoint
    bit-for-bit: same final weights as the uninterrupted run, straggler
    ring buffer included (Checkpointer ``extra``)."""
    fc = FaultConfig(dropout=0.2, straggler=0.15, corrupt=0.05)
    cfg = _cfg(tmp_path, users_count=12, epochs=10, test_step=5,
               defense="TrimmedMean", faults=fc, checkpoint_every=3)

    full = FederatedExperiment(cfg, attacker=DriftAttack(1.0))
    ck = Checkpointer(cfg)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="full") as logger:
        full.run(logger, checkpointer=ck)
    # np.array(copy=True): on this backend np.asarray can be a zero-copy
    # view whose buffer the allocator reuses once the next experiment
    # starts compiling (the engine's own snapshots copy for the same
    # reason, core/engine.py:_host_copy).
    w_full = np.array(full.state.weights, copy=True)
    v_full = np.array(full.state.velocity, copy=True)

    # "SIGKILL after round 7": everything after the round-7 auto
    # checkpoint is lost; a fresh process resumes from it.
    auto7 = os.path.join(ck.dir, "checkpoint-auto-00000007.npz")
    assert os.path.exists(auto7), sorted(os.listdir(ck.dir))
    resumed = FederatedExperiment(cfg, attacker=DriftAttack(1.0))
    state, extra = Checkpointer(cfg).resume(auto7, with_extra=True)
    resumed.state = state
    resumed.restore_fault_state(extra)
    assert "stale" in extra                   # the ring buffer traveled
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="resumed") as logger:
        resumed.run(logger)
    np.testing.assert_array_equal(np.asarray(resumed.state.weights),
                                  w_full)
    np.testing.assert_array_equal(np.asarray(resumed.state.velocity),
                                  v_full)


def test_sigterm_preempt_resume_bit_for_bit(tmp_path):
    """SIGTERM-at-arbitrary-round (ISSUE 4 acceptance): a faulted run
    gracefully preempted at a seeded-random round and restarted
    finishes with final weights bit-for-bit equal to the uninterrupted
    run, and its journal + event stream record every round and eval
    exactly once across the two attempts.  Extends the SIGKILL+resume
    test above: SIGKILL loses work back to the last auto-checkpoint;
    the graceful path (utils/lifecycle.py) loses nothing — the preempt
    boundary IS a checkpoint."""
    from attacking_federate_learning_tpu.utils.lifecycle import (
        GracefulShutdown, Preempted, RunJournal
    )

    kill_round = int(np.random.default_rng(11).integers(1, 9))
    fc = FaultConfig(dropout=0.2, straggler=0.15, corrupt=0.05)

    def cfg_for(run_dir):
        # Distinct run dirs: runs/<dataset>/ is shared, and the
        # reference run's checkpoints must not become the supervised
        # run's resume targets.
        return _cfg(tmp_path, users_count=12, epochs=10, test_step=5,
                    defense="TrimmedMean", faults=fc, checkpoint_every=3,
                    run_dir=str(tmp_path / run_dir))

    cfg_ref = cfg_for("runs_ref")
    full = FederatedExperiment(cfg_ref, attacker=DriftAttack(1.0))
    with RunLogger(cfg_ref, None, cfg_ref.log_dir,
                   jsonl_name="sig_full") as logger:
        full.run(logger, checkpointer=Checkpointer(cfg_ref))
    w_full = np.array(full.state.weights, copy=True)
    v_full = np.array(full.state.velocity, copy=True)

    cfg = cfg_for("runs_sup")
    ck = Checkpointer(cfg)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0))
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="sig_sup") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "sig"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    resumed = FederatedExperiment(cfg, attacker=DriftAttack(1.0))
    state, extra = ck.resume(ck.latest(), with_extra=True)
    resumed.state = state
    resumed.restore_fault_state(extra)
    assert "stale" in extra                  # the ring buffer traveled
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="sig_sup") as logger:
        resumed.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "sig"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    np.testing.assert_array_equal(np.asarray(resumed.state.weights),
                                  w_full)
    np.testing.assert_array_equal(np.asarray(resumed.state.velocity),
                                  v_full)
    # Exactly-once: the journal audits clean, and the shared event
    # stream (both attempts append to one JSONL) carries every round's
    # fault event and every eval exactly once.
    assert RunJournal(cfg.run_dir, "sig").verify(
        epochs=10, test_step=5) == []
    with open(os.path.join(cfg.log_dir, "sig_sup.jsonl")) as f:
        events = [json.loads(line) for line in f]
    fault_rounds = [e["round"] for e in events if e["kind"] == "fault"]
    assert sorted(fault_rounds) == list(range(10))
    eval_rounds = [e["round"] for e in events if e["kind"] == "eval"]
    assert sorted(eval_rounds) == [0, 5, 9]


def test_watchdog_rollback_then_abort(tmp_path):
    """Finite bit-scaled corruption under NoDefense explodes the server
    norm: the watchdog rolls back to the last good auto-checkpoint
    (emitting 'fault' rollback events, state restored) and only after
    max_rollbacks raises — with a finite state left behind."""
    fc = FaultConfig(dropout=0.0, straggler=0.0, corrupt=0.3,
                     corrupt_mode="scale", corrupt_scale=1e30,
                     watchdog_norm=1e6, max_rollbacks=1)
    cfg = _cfg(tmp_path, users_count=10, epochs=10, test_step=5,
               defense="NoDefense", mal_prop=0.0, faults=fc,
               checkpoint_every=2)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=256, synth_test=64)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(0.0), dataset=ds)
    ck = Checkpointer(cfg)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="diverge") as logger:
        with pytest.raises(FloatingPointError, match="diverged"):
            exp.run(logger, checkpointer=ck)
    assert np.isfinite(np.asarray(exp.state.weights)).all()
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    rollbacks = [e for e in events
                 if e["kind"] == "fault" and e.get("rolled_back")]
    # max_rollbacks=1: one rollback-and-retry, then the aborting one.
    assert len(rollbacks) == 2
    assert rollbacks[0]["restored_round"] == rollbacks[1]["restored_round"]
    # The deterministic retry diverged at the same boundary: the
    # rollback-after-divergence trajectory reproduces the clean run
    # from that checkpoint.
    assert rollbacks[0]["round"] == rollbacks[1]["round"]
    # The on-failure auto-checkpoint persists the restored round.
    restored = rollbacks[0]["restored_round"]
    assert any(f"{restored:08d}" in p for p in os.listdir(ck.dir))


def test_rollback_retry_reproduces_clean_resume(tmp_path):
    """Rollback-after-divergence reproduces the same trajectory as a
    clean run resumed from that checkpoint: a fresh engine resumed from
    the on-failure auto-checkpoint diverges at the same boundary."""
    fc = FaultConfig(corrupt=0.3, corrupt_mode="scale", corrupt_scale=1e30,
                     watchdog_norm=1e6, max_rollbacks=0)
    cfg = _cfg(tmp_path, users_count=10, epochs=10, test_step=5,
               defense="NoDefense", mal_prop=0.0, faults=fc,
               checkpoint_every=2)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=256, synth_test=64)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(0.0), dataset=ds)
    ck = Checkpointer(cfg)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="d0") as logger:
        with pytest.raises(FloatingPointError):
            exp.run(logger, checkpointer=ck)
    with open(logger.jsonl_path) as f:
        rb = [json.loads(line) for line in f]
    rb = [e for e in rb if e["kind"] == "fault" and e.get("rolled_back")]
    diverged_at, restored = rb[0]["round"], rb[0]["restored_round"]

    # Clean engine, resumed from the persisted rollback target.
    path = Checkpointer(cfg).latest_auto()
    state, extra = Checkpointer(cfg).resume(path, with_extra=True)
    assert int(state.round) == restored
    fresh = FederatedExperiment(cfg, attacker=DriftAttack(0.0), dataset=ds)
    fresh.state = state
    fresh.restore_fault_state(extra)
    fresh.run_span(restored, diverged_at - restored + 1)
    w = np.asarray(fresh.state.weights)
    assert (not np.isfinite(w).all()
            or float(np.linalg.norm(w)) > fc.watchdog_norm)


def test_staged_path_threads_faults(tmp_path):
    """The staged (per-round host) dispatch applies the same fault seam:
    a non-fusable attack + faults yields the identical schedule counts."""
    fm = _load_fault_matrix()

    class StagedDrift(DriftAttack):
        fusable = False

    fc = FaultConfig(dropout=0.25, corrupt=0.1)
    cfg = _cfg(tmp_path, users_count=12, epochs=4, test_step=4,
               defense="Krum", faults=fc)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=256, synth_test=64)
    exp = FederatedExperiment(cfg, attacker=StagedDrift(1.0), dataset=ds)
    assert exp._staged
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="staged") as logger:
        exp.run(logger)
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    fault_events = sorted((e for e in events if e["kind"] == "fault"),
                          key=lambda e: e["round"])
    want = fm.expected_schedule(cfg, exp.m, exp.m_mal, 4)
    assert len(fault_events) == 4
    for got, exp_row in zip(fault_events, want):
        for k, v in exp_row.items():
            assert int(got[k]) == v


# ---------------------------------------------------------------------------
# CI hook: the fault_matrix smoke itself (next to the check_events hook)

def test_fault_matrix_smoke(tmp_path):
    fm = _load_fault_matrix()
    rc = fm.main(["--epochs", "3", "--users", "10",
                  "--defenses", "NoDefense,Median",
                  "--log-dir", str(tmp_path)])
    assert rc == 0


# ---------------------------------------------------------------------------
# report: the fault/recovery table

def test_report_fault_recovery_table(tmp_path, capsys):
    from attacking_federate_learning_tpu import report

    cfg = _cfg(tmp_path, users_count=12, epochs=5, test_step=5,
               defense="Median",
               faults=FaultConfig(dropout=0.3, corrupt=0.1))
    _, events = _run(cfg, tmp_path, "rep_fault")
    s = report.summarize_run(events)
    flt = s["faults"]
    assert flt["rounds"] == 5
    total_injected = sum(flt["injected"].values())
    assert total_injected >= flt["quarantined"] > 0
    report._print_run("x", s, print)
    out = capsys.readouterr().out
    assert "faults over 5 rounds" in out and "quarantined" in out
