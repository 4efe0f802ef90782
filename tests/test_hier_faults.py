"""Fault domains for the hierarchical/SPMD tree (ISSUE 19).

Acceptance contract: per-client faults run inside each megabatch scan
step and a correlated shard-DOMAIN axis (``FaultConfig.shard_dropout``)
kills whole megabatches, flowing into tier-2 as per-shard alive counts
— the tier-2 estimate under shard death is BIT-EQUAL to the
survivor-submatrix estimator (a fully-dead shard can never win
selection or touch a trim); with faults off the hierarchical round
program stays HLO byte-identical; the emitted per-round 'fault' events
(per-shard survivor vector and tier-2 ladder action included) match
the host replay (core/faults.py hier_fault_schedule) exactly — per
round, per span, and on the (8, 1) SPMD mesh; a gracefully preempted
faulted⊕telemetry SPMD run resumes bit-for-bit with an exactly-once
journal; and the remaining composition rejections (shard-dropout⊕flat,
straggler⊕SPMD) are loud, with the campaign pre-check and engine
construction agreeing on the message.
"""

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
from attacking_federate_learning_tpu.core.population import ACTION_NAMES
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.defenses.kernels import (
    TIER2_DEFENSES, bulyan, krum, shard_bulyan, shard_mean, trimmed_mean
)
from attacking_federate_learning_tpu.defenses.median import median
from attacking_federate_learning_tpu.ops.federated import shard_reduce
from attacking_federate_learning_tpu.utils.checkpoint import Checkpointer
from attacking_federate_learning_tpu.utils.metrics import RunLogger

needs_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                             reason="needs 8 (virtual) devices")

_DS = {}


def _dataset(name=C.SYNTH_MNIST):
    if name not in _DS:
        _DS[name] = load_dataset(name, seed=0, synth_train=256,
                                 synth_test=64)
    return _DS[name]


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 16)
    kw.setdefault("mal_prop", 0.25)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 6)
    kw.setdefault("test_step", 3)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("aggregation", "hierarchical")
    kw.setdefault("megabatch", 4)
    kw.setdefault("defense", "TrimmedMean")
    kw.setdefault("log_dir", str(tmp_path / "logs"))
    kw.setdefault("run_dir", str(tmp_path / "runs"))
    return ExperimentConfig(**kw)


def _run(cfg, name):
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                              dataset=_dataset())
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name=name) as logger:
        exp.run(logger)
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    return exp, events


# ---------------------------------------------------------------------------
# the shard-domain schedule itself (core/faults.py)

def test_domain_alive_deterministic_and_dwell_windowed():
    """Domain death is pure in (key, t) and dwells: a shard whose
    onset fires at t stays dead through [t, t + dwell) — the alive row
    at t is the AND over the dwell window's onset draws."""
    fc = FaultConfig(shard_dropout=0.35, shard_dropout_dwell=3)
    cfg = ExperimentConfig(faults=fc, dataset=C.SYNTH_MNIST,
                           users_count=16, defense="TrimmedMean",
                           aggregation="hierarchical", megabatch=4)
    key = F.fault_key(cfg)
    S = 8
    rows = {t: np.asarray(F.domain_alive_row(key, t, S, fc))
            for t in range(12)}
    for t in (0, 5, 11):
        np.testing.assert_array_equal(
            rows[t], np.asarray(F.domain_alive_row(key, t, S, fc)))
    # Reconstruct the per-round onsets (dwell=1 <=> the raw draw) and
    # pin the window semantics against the dwell-3 rows.
    fc1 = FaultConfig(shard_dropout=0.35, shard_dropout_dwell=1)
    onset = {t: ~np.asarray(F.domain_alive_row(key, t, S, fc1))
             for t in range(12)}
    for t in range(12):
        want = ~(onset[t]
                 | (onset[t - 1] if t >= 1 else False)
                 | (onset[t - 2] if t >= 2 else False))
        np.testing.assert_array_equal(rows[t], want, err_msg=f"t={t}")
    assert any(not rows[t].all() for t in range(12))   # deaths fired
    # shard_dropout=0 is the all-alive constant row, never a draw.
    np.testing.assert_array_equal(
        np.asarray(F.domain_alive_row(key, 3, S, FaultConfig())),
        np.ones(S, bool))


# ---------------------------------------------------------------------------
# tier-2 under shard death: masked kernel == survivor submatrix,
# BIT-equal (the acceptance pin)

_T2_FLAT = {"Krum": krum, "TrimmedMean": trimmed_mean,
            "Bulyan": bulyan, "Median": median}


@pytest.mark.parametrize("name", sorted(_T2_FLAT))
def test_tier2_masked_matches_survivor_submatrix(name):
    """shard_reduce with alive_counts carrying zeros (dead domains)
    must reproduce the flat kernel over the surviving shards' estimate
    submatrix — dead shards are EXCLUDED, not averaged in.  The
    selection kernels and the median are bit-equal; the trimmed
    mean's masked accumulation sums in mask order and lands within
    the flat masked pin's 1e-6 band.  Identical under jit (the fused
    round traces this path)."""
    rng = np.random.default_rng(19)
    S, f2, d = 9, 1, 40
    ests = jnp.asarray(rng.standard_normal((S, d)).astype(np.float32))
    dead = [2, 6]
    alive = jnp.asarray([0 if s in dead else 4 - (s % 2)
                         for s in range(S)], jnp.int32)
    # The engine zeroes dead rows before tier-2 (a dead domain's
    # estimate can be NaN); the kernels must not read them anyway.
    ez = ests.at[jnp.asarray(dead)].set(0.0)
    keep = np.asarray([s for s in range(S) if s not in dead])
    fn = TIER2_DEFENSES[name]
    got = np.asarray(shard_reduce(fn, ez, S, f2, alive_counts=alive))
    want = np.asarray(_T2_FLAT[name](ests[keep], len(keep), f2))
    if name == "TrimmedMean":
        np.testing.assert_allclose(got, want, atol=1e-6)
    else:
        np.testing.assert_array_equal(got, want)
    got_j = np.asarray(jax.jit(
        lambda e, a: shard_reduce(fn, e, S, f2, alive_counts=a))(
            ez, alive))
    np.testing.assert_array_equal(got, got_j)


def test_tier2_nodefense_weights_by_alive_counts():
    """Tier-2 NoDefense restores the flat masked mean's per-client
    weighting: each surviving shard's estimate weighted by its
    effective cohort, dead shards at weight zero."""
    rng = np.random.default_rng(3)
    S, d = 4, 12
    ests = jnp.asarray(rng.standard_normal((S, d)).astype(np.float32))
    alive = jnp.asarray([4, 2, 0, 3], jnp.int32)
    got = np.asarray(shard_mean(ests, S, 0, alive_counts=alive))
    e = np.asarray(ests)
    want = (4 * e[0] + 2 * e[1] + 3 * e[3]) / 9.0
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_tier2_bulyan_selection_clipped_under_shard_death():
    """Bulyan's selection count is STATIC (S - 2f) but the effective
    cohort shrinks with dead domains: at S=9, f2=1 with two dead
    shards the masked pass must clip its picks to e - 2f = 5 of the
    static 7-slot buffer and still bit-match Bulyan over the 7
    survivors (exactly the 4f+3 validity floor)."""
    rng = np.random.default_rng(23)
    S, f2, d = 9, 1, 32
    ests = jnp.asarray(rng.standard_normal((S, d)).astype(np.float32))
    dead = [0, 5]
    alive = jnp.asarray([0 if s in dead else 4 for s in range(S)],
                        jnp.int32)
    ez = ests.at[jnp.asarray(dead)].set(0.0)
    keep = np.asarray([s for s in range(S) if s not in dead])
    got = np.asarray(shard_bulyan(ez, S, f2, alive_counts=alive))
    want = np.asarray(bulyan(ests[keep], len(keep), f2))
    np.testing.assert_array_equal(got, want)
    # The (S,) selection record marks exactly e - 2f = 5 survivors and
    # never a dead shard.
    _, diag = shard_bulyan(ez, S, f2, alive_counts=alive,
                           telemetry=True)
    sel = np.asarray(diag["selection_mask"])
    assert sel.shape == (S,) and sel[dead].sum() == 0
    assert int(sel.sum()) == len(keep) - 2 * f2


# ---------------------------------------------------------------------------
# the ladder plan (host): remask -> fallback -> hold vs surviving shards

def test_plan_tier2_actions_ladder_thresholds():
    """The plan degrades monotonically as domains die: full survival
    plans remask (normal masked kernel), a survivor count below the
    defense's validity bound falls back to Median, and a cohort too
    small even for that holds the round."""
    acts = F.plan_tier2_actions([8, 7, 6, 4, 0], "Krum", 2)
    names = [ACTION_NAMES[a] for a in acts]
    assert names[0] == names[1] == "remask"    # >= 2f + 3 = 7
    assert names[2] == "fallback"    # Krum invalid, Median (2f+1) ok
    assert names[3] == "hold"        # below even Median's floor
    assert names[4] == "hold"        # nothing alive at all
    # Median's own floor IS the fallback's floor: its ladder has no
    # fallback rung — remask until 2f + 1, then hold.
    assert [ACTION_NAMES[a]
            for a in F.plan_tier2_actions([8, 5, 4, 0], "Median", 2)] \
        == ["remask", "remask", "hold", "hold"]


# ---------------------------------------------------------------------------
# engine: faults-off hier HLO byte-identity

def test_no_fault_hier_round_hlo_bit_identical(tmp_path):
    """With all fault flags off the hierarchical round program is
    byte-identical — faults=None and an all-zero FaultConfig lower to
    the same HLO (the PERF_BASELINE pin's unit-level mirror), and the
    faulted build is a different program."""
    def lowered(faults):
        cfg = _cfg(tmp_path, epochs=2, faults=faults)
        exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=_dataset())
        if exp.faults is None:
            args = (exp.data, exp.state, jnp.asarray(0, jnp.int32))
        else:
            args = (exp.data, exp.state, jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32), exp._fault_state)
        return exp._fused_round.lower(*args).as_text()

    none_text = lowered(None)
    zero_text = lowered(FaultConfig(dropout=0.0, straggler=0.0,
                                    corrupt=0.0, shard_dropout=0.0))
    assert none_text == zero_text
    assert lowered(FaultConfig(dropout=0.2,
                               shard_dropout=0.25)) != none_text


# ---------------------------------------------------------------------------
# engine: emitted events == host replay, per round and per span

def _replay(exp, t0, count):
    rows = F.hier_fault_schedule(exp._fault_key, t0, count,
                                 exp._placement, exp.faults)
    acts = F.plan_tier2_actions([r["shards_alive"] for r in rows],
                                exp._tier2_name, exp._tier2_f)
    return rows, acts


def test_hier_fault_events_match_host_replay(tmp_path):
    """A faulted 6-round hierarchical run (dropout + straggler +
    corrupt + shard-domain death) completes with finite weights and
    every 'fault' event — per-shard survivor vector and tier-2 ladder
    action included — equal to the host replay exactly."""
    cfg = _cfg(tmp_path,
               faults=FaultConfig(dropout=0.2, straggler=0.1,
                                  straggler_delay=2, corrupt=0.1,
                                  shard_dropout=0.3,
                                  shard_dropout_dwell=2))
    exp, events = _run(cfg, "hier_replay")
    assert int(exp.state.round) == 6
    assert np.isfinite(np.asarray(exp.state.weights)).all()
    flt = sorted((e for e in events if e["kind"] == "fault"),
                 key=lambda e: e["round"])
    assert [e["round"] for e in flt] == list(range(6))
    rows, acts = _replay(exp, 0, 6)
    for got, want, act in zip(flt, rows, acts):
        for k in ("injected_dropout", "injected_straggler",
                  "injected_corrupt", "quarantined", "shards_dead",
                  "shards_alive"):
            assert int(got[k]) == want[k], (got, want)
        assert [int(x) for x in got["shard_alive"]] == \
            want["shard_alive"]
        assert int(got["tier2_action"]) == int(act)
    assert any(r["shards_dead"] > 0 for r in rows)   # deaths fired


def test_hier_fault_span_matches_per_round(tmp_path):
    """The scanned faulted span (actions as a per-round operand) must
    produce exactly the per-round dispatch's weights and fault state,
    straggler ring included."""
    fc = FaultConfig(dropout=0.2, straggler=0.2, straggler_delay=2,
                     corrupt=0.1, shard_dropout=0.25,
                     shard_dropout_dwell=2)
    cfg = _cfg(tmp_path, users_count=12, epochs=7, faults=fc)
    a = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                            dataset=_dataset())
    for t in range(7):
        a.run_round(t)
    b = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                            dataset=_dataset())
    b.run_span(0, 7)
    np.testing.assert_array_equal(np.asarray(a.state.weights),
                                  np.asarray(b.state.weights))
    np.testing.assert_array_equal(np.asarray(a._fault_state["stale"]),
                                  np.asarray(b._fault_state["stale"]))


# ---------------------------------------------------------------------------
# composition rejections: loud, and pre-check == construction

def test_shard_dropout_requires_hierarchical(tmp_path):
    """Correlated shard-domain death has no domains to kill on the
    flat path — rejected naming the flags, and the campaign pre-check
    returns the construction message verbatim."""
    from attacking_federate_learning_tpu.campaigns.spec import (
        composition_reject_reason
    )

    overrides = dict(
        dataset=C.SYNTH_MNIST, users_count=16, mal_prop=0.25,
        batch_size=16, epochs=2, defense="Median",
        synth_train=256, synth_test=64,
        faults=dict(shard_dropout=0.3))
    reason = composition_reject_reason(overrides)
    assert reason is not None and "shard-DOMAIN" in reason
    assert "--aggregation hierarchical" in reason
    cfg = ExperimentConfig(**overrides)        # config itself is fine
    with pytest.raises(ValueError) as ei:
        FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                            dataset=_dataset())
    assert str(ei.value) == reason


def test_straggler_rejects_spmd_mesh(tmp_path):
    """The straggler ring buffer is a cross-round carry the SPMD
    client_map cannot thread: hier ⊕ mesh(clients>1) ⊕ straggler is
    loudly rejected (and the stateless fault axes are named as the
    composing alternative)."""
    from attacking_federate_learning_tpu.campaigns.spec import (
        composition_reject_reason
    )

    overrides = dict(
        dataset=C.SYNTH_MNIST, users_count=32, mal_prop=0.25,
        batch_size=8, epochs=2, aggregation="hierarchical",
        megabatch=4, mesh_shape=[8, 1], defense="TrimmedMean",
        synth_train=256, synth_test=64,
        faults=dict(straggler=0.1))
    reason = composition_reject_reason(overrides)
    assert reason is not None and "SPMD client_map" in reason
    assert "--fault-straggler" in reason
    # The same cell without the straggler axis pre-validates clean.
    overrides["faults"] = dict(dropout=0.2, shard_dropout=0.25)
    assert composition_reject_reason(overrides) is None


# ---------------------------------------------------------------------------
# SPMD: faulted sharded == unsharded, and preempt -> resume bit-for-bit

@needs_8
def test_spmd_faulted_round_matches_scan(tmp_path):
    """Faulted rounds on the (8, 1) mesh reproduce the sequential scan
    path — weights inside the measured ulp band, every integer fault
    count (per-shard survivor vector included) EXACTLY the host
    replay on both paths."""
    fc = FaultConfig(dropout=0.2, corrupt=0.1, shard_dropout=0.25,
                     shard_dropout_dwell=2)
    kw = dict(users_count=32, batch_size=8, epochs=2, faults=fc)
    ref = FederatedExperiment(_cfg(tmp_path, **kw),
                              attacker=DriftAttack(1.0),
                              dataset=_dataset())
    spmd = FederatedExperiment(_cfg(tmp_path, mesh_shape=(8, 1), **kw),
                               attacker=DriftAttack(1.0),
                               dataset=_dataset())
    assert spmd._hier_spmd and not ref._hier_spmd
    for t in range(2):
        ref.run_round(t)
        spmd.run_round(t)
        rt, st = ref.last_round_telemetry, spmd.last_round_telemetry
        row = F.hier_fault_schedule(ref._fault_key, t, 1,
                                    ref._placement, ref.faults)[0]
        for tele in (rt, st):
            for k in ("injected_dropout", "injected_corrupt",
                      "quarantined", "shards_dead", "shards_alive"):
                assert int(np.asarray(tele[f"fault_{k}"])) == row[k]
            np.testing.assert_array_equal(
                np.asarray(tele["fault_shard_alive"]),
                row["shard_alive"])
    np.testing.assert_allclose(np.asarray(spmd.state.weights),
                               np.asarray(ref.state.weights),
                               atol=2e-5, rtol=1e-5)


@needs_8
def test_spmd_faulted_preempt_resume_bit_for_bit(tmp_path):
    """faults ⊕ hierarchical ⊕ telemetry on the (8, 1) mesh: a
    SIGTERM-preempted run resumes to final weights bit-for-bit equal
    to the uninterrupted run, with the journal and shared event stream
    recording every round's fault event and every eval exactly once
    across the two attempts."""
    from attacking_federate_learning_tpu.utils.lifecycle import (
        GracefulShutdown, Preempted, RunJournal
    )

    fc = FaultConfig(dropout=0.2, corrupt=0.05, shard_dropout=0.25,
                     shard_dropout_dwell=2)
    kill_round = 3

    def cfg_for(run_dir):
        return _cfg(tmp_path, users_count=32, batch_size=8, epochs=6,
                    test_step=3, checkpoint_every=2, telemetry=True,
                    mesh_shape=(8, 1), faults=fc,
                    run_dir=str(tmp_path / run_dir))

    cfg_ref = cfg_for("runs_ref")
    full = FederatedExperiment(cfg_ref, attacker=DriftAttack(1.0),
                               dataset=_dataset())
    assert full._hier_spmd
    with RunLogger(cfg_ref, None, cfg_ref.log_dir,
                   jsonl_name="fsp_full") as logger:
        full.run(logger, checkpointer=Checkpointer(cfg_ref))
    w_full = np.array(full.state.weights, copy=True)
    v_full = np.array(full.state.velocity, copy=True)

    cfg = cfg_for("runs_sup")
    ck = Checkpointer(cfg)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                              dataset=_dataset())
    with RunLogger(cfg, None, cfg.log_dir,
                   jsonl_name="fsp_sup") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "fsp"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    resumed = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=_dataset())
    state, extra = ck.resume(ck.latest(), with_extra=True)
    resumed.state = state
    resumed.restore_fault_state(extra)
    with RunLogger(cfg, None, cfg.log_dir,
                   jsonl_name="fsp_sup") as logger:
        resumed.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "fsp"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    np.testing.assert_array_equal(np.asarray(resumed.state.weights),
                                  w_full)
    np.testing.assert_array_equal(np.asarray(resumed.state.velocity),
                                  v_full)
    assert RunJournal(cfg.run_dir, "fsp").verify(
        epochs=6, test_step=3) == []
    with open(os.path.join(cfg.log_dir, "fsp_sup.jsonl")) as f:
        events = [json.loads(line) for line in f]
    fault_rounds = [e["round"] for e in events if e["kind"] == "fault"]
    assert sorted(fault_rounds) == list(range(6))
    # And the stitched event stream still equals the host replay.
    flt = sorted((e for e in events if e["kind"] == "fault"),
                 key=lambda e: e["round"])
    rows, acts = _replay(resumed, 0, 6)
    for got, want, act in zip(flt, rows, acts):
        assert [int(x) for x in got["shard_alive"]] == \
            want["shard_alive"]
        assert int(got["tier2_action"]) == int(act)
