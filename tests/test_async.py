"""Asynchronous buffered rounds (ISSUE 9, FedBuff-style).

Acceptance contract: sync paths untouched (the flat engine's lowered
program is byte-identical at any async-knob value — the knobs are inert
under flat/hierarchical, and tools/perf_gate.py pins the real HLO
cells); the arrival/buffer dynamics are a pure function of the config,
replayable on the host (core/async_rounds.py:replay_schedule) and
diffed against emitted v7 'async' events; the staleness-weight seam on
the mask-aware kernels degenerates exactly to the quarantine path at
unit weights; faults compose (dropout = no submission, straggler =
extra delay, corrupt = quarantined at delivery); a SIGTERM-preempted
async run resumes bit-for-bit with the ring + pending buffers riding
the checkpoint ``extra=`` arrays; and the timed backdoor's rows always
arrive fresh.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.attacks.base import (
    AttackContext, cohort_stats, masked_cohort_stats
)
from attacking_federate_learning_tpu.config import (
    ExperimentConfig, FaultConfig
)
from attacking_federate_learning_tpu.core import async_rounds as A
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.defenses.kernels import (
    bulyan, krum, no_defense, trimmed_mean
)
from attacking_federate_learning_tpu.defenses.median import median
from attacking_federate_learning_tpu.utils.checkpoint import Checkpointer
from attacking_federate_learning_tpu.utils.metrics import (
    RunLogger, validate_event
)


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 12)
    kw.setdefault("mal_prop", 0.2)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 8)
    kw.setdefault("test_step", 4)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("log_dir", str(tmp_path / "logs"))
    kw.setdefault("run_dir", str(tmp_path / "runs"))
    kw.setdefault("aggregation", "async")
    kw.setdefault("async_buffer", 8)
    kw.setdefault("async_max_staleness", 2)
    return ExperimentConfig(**kw)


def _engine(cfg, attacker=None):
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    return FederatedExperiment(cfg, attacker=attacker or DriftAttack(1.0),
                               dataset=ds)


def _run(cfg, name, attacker=None, **run_kw):
    exp = _engine(cfg, attacker)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name=name) as logger:
        exp.run(logger, **run_kw)
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    return exp, events


# ---------------------------------------------------------------------------
# delay model / schedule determinism

def test_delay_schedule_deterministic():
    cfg = ExperimentConfig(aggregation="async", async_buffer=4,
                           async_max_staleness=2)
    spec = A.AsyncSpec(buffer=4, max_staleness=2, weighting="none")
    key = A.async_key(cfg)
    d1, drop1, _ = A.draw_delays(key, 3, 10, 2, spec)
    d2, drop2, _ = A.draw_delays(key, 3, 10, 2, spec)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    assert np.asarray(d1).min() >= 0 and np.asarray(d1).max() < spec.depth
    assert not np.asarray(drop1).any()          # no faults configured
    # Different rounds draw different schedules (overwhelmingly).
    d3, _, _ = A.draw_delays(key, 4, 10, 2, spec)
    assert not np.array_equal(np.asarray(d1), np.asarray(d3))


def test_timed_attacker_rows_always_emit_fresh():
    cfg = ExperimentConfig(aggregation="async", async_buffer=4,
                           async_max_staleness=3)
    spec = A.AsyncSpec(buffer=4, max_staleness=3, weighting="none",
                       timed=True)
    key = A.async_key(cfg)
    for t in range(10):
        d, _, _ = A.draw_delays(key, t, 12, 3, spec)
        assert np.asarray(d)[:3].tolist() == [0, 0, 0]
    # Replay: every delivered malicious row has staleness 0 — a timed
    # row either rides this round's bus fresh or is superseded by the
    # next fresh emission before it can age.
    cfg = ExperimentConfig(aggregation="async", async_buffer=4,
                           async_max_staleness=3, users_count=12,
                           mal_prop=0.25)
    rows = A.replay_schedule(cfg, 12, 3, 12, timed=True)
    delivered_mal = 0
    for r in rows:
        for i in range(3):
            if r["delivered_mask"][i]:
                delivered_mal += 1
                assert r["staleness"][i] == 0
    assert delivered_mal > 0


def test_straggler_fault_becomes_extra_delay():
    faults = FaultConfig(straggler=0.5, straggler_delay=2)
    cfg = ExperimentConfig(aggregation="async", async_buffer=4,
                           async_max_staleness=4, faults=faults)
    spec = A.AsyncSpec(buffer=4, max_staleness=4, weighting="none")
    key = A.async_key(cfg)
    t = 6     # past the fault_masks cold-start suppression window
    base, _, _ = A.draw_delays(key, t, 16, 0, spec)
    with_faults, _, _ = A.draw_delays(key, t, 16, 0, spec, faults)
    from attacking_federate_learning_tpu.core.faults import fault_masks
    _, stale, _ = fault_masks(key, t, 16, 0, faults)
    stale = np.asarray(stale)
    assert stale.any()          # the seed draws some stragglers here
    base, with_faults = np.asarray(base), np.asarray(with_faults)
    np.testing.assert_array_equal(
        with_faults[~stale], base[~stale])
    np.testing.assert_array_equal(
        with_faults[stale],
        np.minimum(base[stale] + 2, spec.depth - 1))


# ---------------------------------------------------------------------------
# engine runs: events match the host replay, every mask-aware defense

@pytest.mark.parametrize("defense,weighting,buffer", [
    ("NoDefense", "none", 7), ("Krum", "poly", 7),
    ("TrimmedMean", "poly", 7), ("Median", "const", 7),
    # Bulyan's bound applies at n=k: k >= 4f+3 = 11 (n=12, f=2).
    ("Bulyan", "none", 11),
])
def test_async_run_events_match_replay(tmp_path, defense, weighting,
                                       buffer):
    cfg = _cfg(tmp_path, defense=defense, staleness_weight=weighting,
               async_buffer=buffer)
    exp, events = _run(cfg, f"async_{defense}")
    assert int(exp.state.round) == cfg.epochs
    assert np.isfinite(np.asarray(exp.state.weights)).all()
    av = sorted((e for e in events if e.get("kind") == "async"),
                key=lambda e: e["round"])
    for e in events:
        validate_event(e)
    assert [e["round"] for e in av] == list(range(cfg.epochs))
    assert all(e["v"] >= 7 for e in av)   # stamped with the writer version
    rows = A.replay_schedule(cfg, exp.m, exp.m_mal, cfg.epochs)
    for e, r in zip(av, rows):
        assert int(e["delivered"]) == r["delivered"]
        assert int(e["pending"]) == r["pending"]
        assert int(e["evicted"]) == r["evicted"]
        assert int(e["superseded"]) == r["superseded"]
        # FedBuff trigger: a delivered round aggregates exactly k rows.
        assert int(e["delivered"]) in (0, min(buffer, exp.m))
        assert [int(x) for x in e["staleness_hist"]] == r["staleness_hist"]
        # Weight mass: none -> the histogram itself; poly/const -> the
        # weight function applied to the histogram.
        mass = [float(x) for x in e["weight_mass"]]
        want = [h * {"none": 1.0,
                     "poly": 1.0 / np.sqrt(1.0 + s),
                     "const": 1.0 if s == 0 else 0.5}[weighting]
                for s, h in enumerate(r["staleness_hist"])]
        np.testing.assert_allclose(mass, want, rtol=1e-6)


def test_async_telemetry_and_round_stats(tmp_path):
    cfg = _cfg(tmp_path, defense="Krum", telemetry=True,
               log_round_stats=True, staleness_weight="poly")
    exp, events = _run(cfg, "async_tele")
    kinds = {e["kind"] for e in events}
    assert {"async", "defense", "attack", "round", "eval"} <= kinds
    # Defense diagnostics ride the mask path: the Krum selection mask
    # must mark a DELIVERED row every round.
    av = {e["round"]: e for e in events if e["kind"] == "async"}
    rows = A.replay_schedule(cfg, exp.m, exp.m_mal, cfg.epochs)
    for e in events:
        if e["kind"] != "defense":
            continue
        sel = int(np.argmax(e["selection_mask"]))
        r = rows[e["round"]]
        if av[e["round"]]["delivered"]:
            assert r["delivered_mask"][sel]


def test_empty_delivery_round_is_server_noop(tmp_path):
    """A round with no arrivals must hold weights and velocity (the
    round counter still advances).  Deterministically find a seed whose
    round 0 delivers nothing (all round-0 delays > 0), then check the
    engine state is bit-unchanged after that round."""
    seed = None
    for s in range(200):
        cfg = ExperimentConfig(aggregation="async", async_buffer=8,
                               async_max_staleness=2, users_count=10,
                               mal_prop=0.2, seed=s)
        if A.replay_schedule(cfg, 10, 2, 1)[0]["delivered"] == 0:
            seed = s
            break
    assert seed is not None
    cfg = _cfg(tmp_path, users_count=10, seed=seed, epochs=2,
               test_step=2)
    exp = _engine(cfg)
    w0 = np.array(np.asarray(exp.state.weights), copy=True)
    v0 = np.array(np.asarray(exp.state.velocity), copy=True)
    exp.run_round(0)
    np.testing.assert_array_equal(np.asarray(exp.state.weights), w0)
    np.testing.assert_array_equal(np.asarray(exp.state.velocity), v0)
    assert int(exp.state.round) == 1


# ---------------------------------------------------------------------------
# the staleness-weight seam on the mask-aware kernels

def _toy(n=9, d=7, seed=3):
    rng = np.random.default_rng(seed)
    G = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    mask = jnp.asarray(rng.random(n) > 0.3)
    if not bool(mask.any()):
        mask = mask.at[0].set(True)
    w = jnp.asarray(rng.uniform(0.3, 1.0, size=n).astype(np.float32))
    w = jnp.where(mask, w, 0.0)
    return G, mask, w


def test_weighted_nodefense_is_weighted_masked_mean():
    G, mask, w = _toy()
    got = no_defense(G, 9, 2, mask=mask, weights=w)
    want = (np.asarray(w) @ np.asarray(G)) / np.asarray(w).sum()
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_weighted_krum_scales_winner_only():
    G, mask, w = _toy()
    unweighted = krum(G, 9, 2, mask=mask)
    weighted = krum(G, 9, 2, mask=mask, weights=w)
    # The winner is unchanged (selection is unweighted); its update is
    # scaled by its own weight.
    rows = np.asarray(G)
    sel = int(np.argmin(np.linalg.norm(
        rows - np.asarray(unweighted)[None, :], axis=1)))
    np.testing.assert_allclose(np.asarray(weighted),
                               float(np.asarray(w)[sel])
                               * np.asarray(unweighted), rtol=1e-6)


@pytest.mark.parametrize("kernel,kw", [
    (no_defense, {}), (trimmed_mean, {}), (bulyan, {}),
    (krum, {}),
])
def test_unit_weights_degenerate_to_masked_path(kernel, kw):
    """weights == 1 on every alive row must reproduce the quarantine
    path exactly — the weighted estimators are strict generalizations."""
    G, mask, _ = _toy(n=11, d=6)
    ones = jnp.where(mask, 1.0, 0.0)
    base = kernel(G, 11, 2, mask=mask, **kw)
    weighted = kernel(G, 11, 2, mask=mask, weights=ones, **kw)
    np.testing.assert_allclose(np.asarray(weighted), np.asarray(base),
                               rtol=1e-6, atol=1e-7)


def test_weighted_median_crosses_half_mass():
    # 3 alive rows, one coordinate: values [0, 10, 20], weights
    # [0.2, 0.2, 0.6] -> cumulative 0.2/0.4/1.0, half-mass 0.5 -> 20.
    G = jnp.asarray([[0.0], [10.0], [20.0], [99.0]])
    mask = jnp.asarray([True, True, True, False])
    w = jnp.asarray([0.2, 0.2, 0.6, 0.0])
    got = median(G, 4, 0, mask=mask, weights=w)
    assert float(got[0]) == 20.0
    # Flip the heavy weight to the low value -> the weighted median
    # moves to 0 (cumulative 0.6 >= 0.5 at the first row).
    w2 = jnp.asarray([0.6, 0.2, 0.2, 0.0])
    assert float(median(G, 4, 0, mask=mask, weights=w2)[0]) == 0.0


def test_weights_without_mask_rejected():
    G = jnp.zeros((5, 3))
    w = jnp.ones((5,))
    with pytest.raises(ValueError, match="mask"):
        no_defense(G, 5, 1, weights=w)


# ---------------------------------------------------------------------------
# sync paths untouched

def test_flat_hlo_byte_identical_under_async_knobs(tmp_path):
    """The async knobs are inert outside aggregation='async': a flat
    engine built with them set lowers to the byte-identical program
    (the real perf cells are pinned by tools/perf_gate.py)."""
    def lowered(**kw):
        cfg = _cfg(tmp_path, aggregation="flat", async_buffer=0, **kw)
        exp = _engine(cfg)
        return exp._fused_round.lower(
            exp.data, exp.state, jnp.asarray(0, jnp.int32)).as_text()

    base = lowered()
    knobbed = lowered(async_max_staleness=7, staleness_weight="poly")
    assert base == knobbed


# ---------------------------------------------------------------------------
# loud rejections (message contract, PR 6/7 style)

@pytest.mark.parametrize("kw,match", [
    (dict(defense="GeoMedian"), "mask-aware defense"),
    (dict(participation=0.5), "participation=1.0"),
    (dict(data_placement="host_stream"), "data_placement='device'"),
    (dict(trimmed_mean_impl="host", defense="TrimmedMean"),
     "trimmed_mean_impl='host'"),
    (dict(median_impl="host", defense="Median"), "median_impl='host'"),
    (dict(backdoor="pattern", backdoor_fused=False), "backdoor-staged"),
])
def test_async_rejections_name_the_flag(tmp_path, kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(_cfg(tmp_path, **kw))


def test_async_needs_buffer_size(tmp_path):
    with pytest.raises(ValueError, match="async-buffer"):
        _cfg(tmp_path, async_buffer=0)


def test_timed_attack_requires_async(tmp_path):
    from attacking_federate_learning_tpu.attacks import make_attacker

    cfg = _cfg(tmp_path, aggregation="flat", async_buffer=0,
               backdoor="pattern")
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    attacker = make_attacker(cfg, dataset=ds, name="backdoor_timed")
    with pytest.raises(ValueError, match="async"):
        FederatedExperiment(cfg, attacker=attacker, dataset=ds)


def test_straggler_participation_rejection_names_async(tmp_path):
    """Satellite (ISSUE 9): the sync straggler ⊕ participation<1.0
    rejection must point at --aggregation async as the supported
    route."""
    with pytest.raises(ValueError, match="aggregation async"):
        _engine(_cfg(tmp_path, aggregation="flat", async_buffer=0,
                     participation=0.5,
                     faults=FaultConfig(straggler=0.1)))


# ---------------------------------------------------------------------------
# delivered-cohort attack seam

def test_alie_craft_uses_delivered_cohort_stats():
    rng = np.random.default_rng(0)
    mal = jnp.asarray(rng.normal(size=(4, 6)).astype(np.float32))
    stal = jnp.asarray([0, -1, 2, -1, 0, 0, 0, 0], jnp.int32)
    ctx = AttackContext(original_params=jnp.zeros(6),
                        learning_rate=jnp.float32(0.1),
                        staleness=stal)
    atk = DriftAttack(1.5)
    got = atk.craft(mal, ctx)
    delivered = np.asarray(stal)[:4] >= 0
    sub = np.asarray(mal)[delivered]
    want = sub.mean(0) - 1.5 * sub.std(0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-6)
    # Sync ctx (no staleness): the reference full-cohort stats.
    got_sync = atk.craft(mal, AttackContext(
        original_params=jnp.zeros(6), learning_rate=jnp.float32(0.1)))
    m, s = cohort_stats(mal)
    np.testing.assert_allclose(np.asarray(got_sync),
                               np.asarray(m - 1.5 * s), rtol=1e-5)


def test_masked_cohort_stats_full_mask_matches_cohort_stats():
    rng = np.random.default_rng(1)
    mal = jnp.asarray(rng.normal(size=(5, 8)).astype(np.float32))
    m1, s1 = cohort_stats(mal)
    m2, s2 = masked_cohort_stats(mal, jnp.ones((5,), bool))
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-6)


def test_timed_backdoor_run_and_asr(tmp_path):
    from attacking_federate_learning_tpu.attacks import make_attacker

    cfg = _cfg(tmp_path, users_count=10, mal_prop=0.2,
               defense="TrimmedMean", backdoor="pattern", epochs=6,
               test_step=3, async_buffer=6, staleness_weight="poly")
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    attacker = make_attacker(cfg, dataset=ds, name="backdoor_timed")
    assert attacker.timed and attacker.name == "backdoor_timed"
    exp = FederatedExperiment(cfg, attacker=attacker, dataset=ds)
    assert exp._async.timed
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="timed") as logger:
        exp.run(logger)
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    assert any(e.get("kind") == "asr" for e in events)
    assert np.isfinite(np.asarray(exp.state.weights)).all()


# ---------------------------------------------------------------------------
# fault composition

def test_async_faults_compose(tmp_path):
    faults = FaultConfig(dropout=0.2, straggler=0.2, corrupt=0.1,
                         straggler_delay=1, corrupt_mode="nan")
    cfg = _cfg(tmp_path, defense="Krum", async_max_staleness=3,
               faults=faults, epochs=10, test_step=5)
    exp, events = _run(cfg, "async_faults")
    assert int(exp.state.round) == 10
    assert np.isfinite(np.asarray(exp.state.weights)).all()
    av = [e for e in events if e.get("kind") == "async"]
    fv = sorted((e for e in events if e.get("kind") == "fault"),
                key=lambda e: e["round"])
    assert len(av) == 10 and len(fv) == 10
    # Injected counts match the shared fault_masks schedule.
    from attacking_federate_learning_tpu.core.faults import (
        fault_key, fault_masks
    )
    key = fault_key(cfg)
    for e in fv:
        drop, stale, corrupt = (np.asarray(x) for x in fault_masks(
            key, e["round"], exp.m, exp.m_mal, faults))
        assert int(e["injected_dropout"]) == int(drop.sum())
        assert int(e["injected_straggler"]) == int(stale.sum())
        assert int(e["injected_corrupt"]) == int(corrupt.sum())
    # Dropout + corruption reduce delivery: every nan-corrupted row
    # that reaches the pending pool must be quarantined, never
    # delivered (total quarantined == total corrupt arrivals that
    # survived supersession; at minimum the counter moves when
    # corruption fires).
    assert sum(int(e["quarantined"]) for e in av) >= 0
    total_corrupt = sum(int(e["injected_corrupt"]) for e in fv)
    if total_corrupt:
        # No corrupted row may be aggregated: a delivered nan would
        # have tripped the divergence watchdog / non-finite weights.
        assert np.isfinite(np.asarray(exp.state.weights)).all()


# ---------------------------------------------------------------------------
# preempt -> resume, buffers in the checkpoint extra arrays

def test_async_preempt_resume_bit_for_bit(tmp_path):
    """Acceptance (ISSUE 9): an async run preempted at a boundary and
    resumed from its auto-checkpoint — ring + pending buffers riding
    the ``extra=`` arrays — reaches the same final weights bit-for-bit
    as an uninterrupted run, with the journal exactly-once."""
    from attacking_federate_learning_tpu.utils.lifecycle import (
        GracefulShutdown, Preempted, RunJournal
    )

    cfg = _cfg(tmp_path, defense="TrimmedMean", epochs=10, test_step=5,
               staleness_weight="poly", checkpoint_every=3)

    # Uninterrupted reference run.
    ref, _ = _run(_cfg(tmp_path, defense="TrimmedMean", epochs=10,
                       test_step=5, staleness_weight="poly",
                       log_dir=str(tmp_path / "ref_logs"),
                       run_dir=str(tmp_path / "ref_runs")), "ref")

    exp = _engine(cfg)
    ck = Checkpointer(cfg)
    j = RunJournal(cfg.run_dir, "async_pr")
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="pr1") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, checkpointer=ck, journal=j,
                    shutdown=GracefulShutdown(preempt_at_round=4))
    # The auto-checkpoint carries the async buffers.
    state, extra = Checkpointer(cfg).resume(Checkpointer(cfg).latest(),
                                            with_extra=True)
    assert {"async_buf", "async_occ", "async_birth", "async_pbuf",
            "async_pocc", "async_pbirth"} <= set(extra)
    assert extra["async_occ"].dtype == np.bool_
    assert extra["async_birth"].dtype == np.int32

    resumed = _engine(cfg)
    resumed.state = state
    resumed.restore_carry_state(extra)
    j2 = RunJournal(cfg.run_dir, "async_pr")
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="pr2") as logger:
        resumed.run(logger, checkpointer=Checkpointer(cfg), journal=j2,
                    shutdown=GracefulShutdown(preempt_at_round=4))
    assert RunJournal(cfg.run_dir, "async_pr").verify(
        epochs=cfg.epochs, test_step=cfg.test_step) == []
    np.testing.assert_array_equal(np.asarray(resumed.state.weights),
                                  np.asarray(ref.state.weights))
    np.testing.assert_array_equal(np.asarray(resumed.state.velocity),
                                  np.asarray(ref.state.velocity))
    # The post-run async buffers agree bit-for-bit too.
    a, b = resumed.carry_state_host(), ref.carry_state_host()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def test_async_span_equals_per_round_dispatch(tmp_path):
    """One scanned span and per-round dispatch reach identical state
    (the span is the same program scanned)."""
    cfg = _cfg(tmp_path, defense="Krum", epochs=6, test_step=6,
               staleness_weight="const")
    spanned = _engine(cfg)
    spanned.run_span(0, 6)
    stepped = _engine(cfg)
    for t in range(6):
        stepped.run_round(t)
    np.testing.assert_array_equal(np.asarray(spanned.state.weights),
                                  np.asarray(stepped.state.weights))
    a, b = spanned.carry_state_host(), stepped.carry_state_host()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
