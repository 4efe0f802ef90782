"""Hierarchical two-tier aggregation (ISSUE 6).

Acceptance contract: each tier-1 shard estimate bit-matches the flat
kernel applied to that shard's rows (masked-fault variants included);
``aggregation='flat'`` builds byte-identical HLO whatever the new knobs
hold; spread-vs-concentrated colluder placement produces the measured
tolerance flip on SYNTH_MNIST_HARD; and a SIGTERM-preempted
hierarchical run resumes bit-for-bit (same harness as test_faults.py's
lifecycle tests).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import (
    DriftAttack, make_attacker
)
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.defenses.kernels import (
    TIER2_DEFENSES, bulyan, krum, shard_krum, shard_mean, trimmed_mean
)
from attacking_federate_learning_tpu.defenses.median import median
from attacking_federate_learning_tpu.ops.federated import (
    Placement, client_map, make_placement, tier1_assumed, tier2_assumed,
    two_tier_aggregate
)
from attacking_federate_learning_tpu.utils.checkpoint import Checkpointer
from attacking_federate_learning_tpu.utils.metrics import RunLogger


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 12)
    kw.setdefault("mal_prop", 0.25)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 10)
    kw.setdefault("test_step", 5)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("log_dir", str(tmp_path / "logs"))
    kw.setdefault("run_dir", str(tmp_path / "runs"))
    return ExperimentConfig(**kw)


def _hier(tmp_path, **kw):
    kw.setdefault("aggregation", "hierarchical")
    kw.setdefault("megabatch", 4)
    return _cfg(tmp_path, **kw)


_DS = {}


def _dataset(name=C.SYNTH_MNIST):
    if name not in _DS:
        _DS[name] = load_dataset(name, seed=0, synth_train=256,
                                 synth_test=64)
    return _DS[name]


# ---------------------------------------------------------------------------
# placement (ops/federated.py)

def test_placement_spread_and_concentrated():
    for mode, want_counts in (("spread", (2, 2, 1)),
                              ("concentrated", (5, 0, 0))):
        pl = make_placement(24, 5, 8, mode)
        assert isinstance(pl, Placement)
        assert pl.mal_counts == want_counts
        # Every client exactly once, malicious-first within each shard.
        assert sorted(pl.grid.reshape(-1).tolist()) == list(range(24))
        for s in range(pl.num_shards):
            rows = pl.grid[s]
            c = pl.mal_counts[s]
            assert (rows[:c] < 5).all() and (rows[c:] >= 5).all()
        # Groups partition the shards and share one static count each.
        sids = [sid for _, group in pl.groups for sid in group]
        assert sorted(sids) == list(range(pl.num_shards))
        for count, group in pl.groups:
            assert all(pl.mal_counts[s] == count for s in group)


def test_placement_validation_and_assumed_bounds():
    with pytest.raises(ValueError, match="divide"):
        make_placement(10, 2, 3)
    with pytest.raises(ValueError, match="mal_placement"):
        make_placement(12, 2, 4, "clumped")
    assert tier1_assumed(13, 4) == 4        # ceil(13/4)
    assert tier1_assumed(0, 4) == 0
    assert tier2_assumed(13, 16) == 1       # ceil(13/16)
    assert tier2_assumed(33, 16) == 3


# ---------------------------------------------------------------------------
# acceptance (a): tier-1 estimates bit-match the flat kernels per shard

_T1 = {"Krum": krum, "TrimmedMean": trimmed_mean, "Bulyan": bulyan,
       "Median": median}


@pytest.mark.parametrize("name", sorted(_T1))
@pytest.mark.parametrize("masked", [False, True])
def test_tier1_shard_estimates_bit_match_flat_kernel(name, masked):
    """client_map's per-shard tier-1 pass IS the flat kernel on that
    shard's rows: under ``jax.disable_jit`` (op-identical dispatch) the
    two-tier composition is bit-for-bit the hand-built
    tier-2-over-per-shard-flat-kernels, masked-fault variants included
    (alive counts from the row mask).  The compiled scan is then
    allowed the usual XLA reassociation ulps on the coordinate-sum
    kernels (selection kernels stay bitwise — they return input rows)."""
    t1 = _T1[name]
    n, m, f = 32, 8, 3
    pl = make_placement(n, f, m, "spread")
    f1 = tier1_assumed(f, pl.num_shards)
    f2 = max(tier2_assumed(f, m), 1)
    rng = np.random.default_rng(7)
    G = jnp.asarray(rng.standard_normal((n, 40)).astype(np.float32))
    mask = jnp.asarray(rng.random(n) > 0.25) if masked else None
    t2 = TIER2_DEFENSES[name if name != "Bulyan" else "TrimmedMean"]

    def hand_built():
        ests, alive = [], []
        for s in range(pl.num_shards):
            ids = jnp.asarray(pl.grid[s])
            if masked:
                sm = mask[ids]
                ests.append(t1(G[ids], m, f1, mask=sm))
                alive.append(jnp.sum(sm).astype(jnp.int32))
            else:
                ests.append(t1(G[ids], m, f1))
        ests_m = jnp.stack(ests).astype(jnp.float32)
        return t2(ests_m, pl.num_shards, f2,
                  alive_counts=jnp.stack(alive) if masked else None)

    # Bit-for-bit under op-identical dispatch: the two-tier path calls
    # exactly the flat kernel per shard.
    with jax.disable_jit():
        exact = two_tier_aggregate(G, pl, t1, t2, f1, f2, mask=mask)
        ref_exact = hand_built()
    np.testing.assert_array_equal(np.asarray(exact),
                                  np.asarray(ref_exact))

    # Compiled regime: selection kernels stay bitwise; coordinate-sum
    # tails may reassociate inside the scan body (ulp band).
    agg = two_tier_aggregate(G, pl, t1, t2, f1, f2, mask=mask)
    ref = hand_built()
    if name in ("Krum", "Median"):
        np.testing.assert_array_equal(np.asarray(agg), np.asarray(ref))
    else:
        np.testing.assert_allclose(np.asarray(agg), np.asarray(ref),
                                   atol=5e-7, rtol=1e-6)


def test_shard_kernels_exclude_dead_shards():
    """alive_counts == 0 shards (every client quarantined) can never
    win tier-2 selection or weight the tier-2 mean — the shard_*
    entries map alive counts onto the kernels' quarantine mask seam."""
    rng = np.random.default_rng(3)
    E = jnp.asarray(rng.standard_normal((5, 16)).astype(np.float32))
    poisoned = E.at[0].set(1e4)             # dead shard with a wild row
    alive = jnp.asarray([0, 7, 8, 8, 6], jnp.int32)
    got = shard_krum(poisoned, 5, 1, alive_counts=alive)
    ref = krum(E[1:], 4, 1)                 # krum over the live shards
    # The winner must be a live shard's estimate (never row 0).
    assert np.isfinite(np.asarray(got)).all()
    assert not np.array_equal(np.asarray(got), np.asarray(poisoned[0]))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # Weighted tier-2 mean: dead shard contributes zero weight.
    wm = shard_mean(poisoned, 5, 0, alive_counts=alive)
    ref_m = (np.asarray(alive[1:], np.float32)
             @ np.asarray(E[1:])) / float(alive[1:].sum())
    np.testing.assert_allclose(np.asarray(wm), ref_m, rtol=1e-6)


def test_client_map_reorders_groups_to_shard_order():
    """Concentrated placement makes groups non-contiguous in shard id;
    the stacked output must still land in shard order."""
    pl = make_placement(24, 5, 8, "concentrated")   # counts (5, 0, 0)
    G = jnp.arange(24, dtype=jnp.float32)[:, None] * jnp.ones((1, 3))

    def shard_fn(ids, c_mal, G):
        return jnp.mean(G[ids], axis=0)

    out = np.asarray(client_map(shard_fn, pl, G))
    ref = np.stack([np.asarray(G)[pl.grid[s]].mean(0)
                    for s in range(3)])
    np.testing.assert_array_equal(out, ref)


# ---------------------------------------------------------------------------
# acceptance (b): the flat path is untouched

def test_flat_hlo_byte_identical_whatever_the_hier_knobs(tmp_path):
    """aggregation='flat' (the default) lowers byte-identical HLO with
    the hierarchical knobs at defaults or set — the new config surface
    must not leak into the flat trace (same methodology as the faults
    HLO pin, test_faults.py)."""
    ds = _dataset()

    def lowered(**kw):
        cfg = _cfg(tmp_path, defense="Krum", **kw)
        exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=ds)
        return exp._fused_round.lower(
            exp.data, exp.state, jnp.asarray(0, jnp.int32)).as_text()

    base = lowered()
    knobbed = lowered(megabatch=4, tier2_defense="Median",
                      mal_placement="concentrated", tier1_corrupted=1,
                      tier2_corrupted=1)
    assert base == knobbed
    # Non-vacuous: the hierarchical build is a different program.
    hier = lowered(aggregation="hierarchical", megabatch=4)
    assert hier != base


# ---------------------------------------------------------------------------
# engine equivalences

def test_hier_nodefense_no_attack_matches_flat(tmp_path):
    """With NoDefense tiers and no attack, the two-tier mean-of-means
    over equal megabatches is the flat FedAvg mean — same trajectory to
    summation-order tolerance."""
    ds = _dataset()
    flat = FederatedExperiment(
        _cfg(tmp_path, mal_prop=0.0, defense="NoDefense", epochs=4),
        dataset=ds)
    flat.run_span(0, 4)
    hier = FederatedExperiment(
        _hier(tmp_path, mal_prop=0.0, defense="NoDefense", epochs=4),
        dataset=ds)
    hier.run_span(0, 4)
    np.testing.assert_allclose(np.asarray(hier.state.weights),
                               np.asarray(flat.state.weights),
                               atol=1e-6, rtol=1e-5)


def test_hier_round_equals_span_bitwise(tmp_path):
    """Per-round dispatch and the scanned span are the same program
    family (hier_core under jit vs fori_loop) — bit-identical states,
    like the flat engine's span pin."""
    ds = _dataset()
    a = FederatedExperiment(_hier(tmp_path, defense="Krum", epochs=4),
                            attacker=DriftAttack(1.0), dataset=ds)
    for t in range(4):
        a.run_round(t)
    b = FederatedExperiment(_hier(tmp_path, defense="Krum", epochs=4),
                            attacker=DriftAttack(1.0), dataset=ds)
    b.run_span(0, 4)
    np.testing.assert_array_equal(np.asarray(a.state.weights),
                                  np.asarray(b.state.weights))


def test_hier_cost_entries_and_megabatch_bound(tmp_path):
    """The cost ledger exposes hier_round/hier_span/tier2_* entry
    points, and the hierarchical round's temp bytes at the same cohort
    undercut the flat round's (the (n, d)/(n, n) buffers are gone —
    the small-scale shadow of the perf-gate memproof)."""
    ds = _dataset()
    hier = FederatedExperiment(
        _hier(tmp_path, users_count=48, megabatch=8, defense="Krum",
              tier2_defense="Krum"),
        attacker=DriftAttack(1.0), dataset=ds)
    led = hier.cost_report()
    names = [r.name for r in led.records]
    assert "hier_round" in names and "hier_span" in names
    assert "tier2_Krum" in names and not led.errors
    flat = FederatedExperiment(
        _cfg(tmp_path, users_count=48, defense="Krum"),
        attacker=DriftAttack(1.0), dataset=ds)
    led_f = flat.cost_report()
    temp = {r.name: r.temp_bytes for r in led.records}
    temp_f = {r.name: r.temp_bytes for r in led_f.records}
    assert temp["hier_round"] < temp_f["fused_round"]


# ---------------------------------------------------------------------------
# acceptance (c): the colluder-placement tolerance flip

def test_mal_placement_tolerance_flip(tmp_path):
    """SYNTH_MNIST_HARD, n=64, m=16, f=16, ALIE z=1.5 (behavioral-test
    batch 64): spread colluders put ~f/S identical crafted rows in
    EVERY megabatch — duplicates have zero mutual distance, so
    per-shard Krum selects the crafted vector everywhere and the run
    collapses like flat Krum does at this f.  Concentrated colluders
    saturate one megabatch but leave the other tier-1 estimates clean,
    and tier-2 Krum (f2=1) rejects the poisoned estimate — the
    defense is RESCUED (measured ~69% vs ~11%; GRID_RESULTS.md row).
    """
    ds = load_dataset(C.SYNTH_MNIST_HARD, seed=0)

    def acc(placement):
        cfg = ExperimentConfig(
            dataset=C.SYNTH_MNIST_HARD, users_count=64, mal_prop=0.25,
            batch_size=64, epochs=10, test_step=10, num_std=1.5,
            defense="Krum", seed=0, aggregation="hierarchical",
            megabatch=16, mal_placement=placement,
            log_dir=str(tmp_path / "logs"),
            run_dir=str(tmp_path / "runs"))
        exp = FederatedExperiment(
            cfg, attacker=make_attacker(cfg, dataset=ds), dataset=ds)
        exp.run_span(0, 10)
        _, correct = exp.evaluate(exp.state.weights)
        return 100.0 * float(correct) / len(ds.test_y)

    a_spread, a_conc = acc("spread"), acc("concentrated")
    assert a_conc - a_spread > 25.0, (a_spread, a_conc)
    assert a_spread < 35.0          # spread collapses
    assert a_conc > 50.0            # concentrated is rescued


# ---------------------------------------------------------------------------
# acceptance (d): SIGTERM preempt + resume mid-scan, bit-for-bit

def test_hier_preempt_resume_bit_for_bit(tmp_path):
    """Same harness as test_faults.py's SIGTERM test: a hierarchical
    run gracefully preempted at a seeded round and restarted finishes
    with final weights bit-for-bit equal to the uninterrupted run, and
    the journal audits exactly-once."""
    from attacking_federate_learning_tpu.utils.lifecycle import (
        GracefulShutdown, Preempted, RunJournal
    )

    kill_round = int(np.random.default_rng(23).integers(1, 9))
    ds = _dataset()

    def cfg_for(run_dir):
        return _hier(tmp_path, defense="Krum", epochs=10, test_step=5,
                     checkpoint_every=3, run_dir=str(tmp_path / run_dir))

    cfg_ref = cfg_for("runs_ref")
    full = FederatedExperiment(cfg_ref, attacker=DriftAttack(1.0),
                               dataset=ds)
    with RunLogger(cfg_ref, None, cfg_ref.log_dir,
                   jsonl_name="hier_full") as logger:
        full.run(logger, checkpointer=Checkpointer(cfg_ref))
    w_full = np.array(full.state.weights, copy=True)
    v_full = np.array(full.state.velocity, copy=True)

    cfg = cfg_for("runs_sup")
    ck = Checkpointer(cfg)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir,
                   jsonl_name="hier_sup") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "hier"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    resumed = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=ds)
    state, _extra = ck.resume(ck.latest(), with_extra=True)
    resumed.state = state
    with RunLogger(cfg, None, cfg.log_dir,
                   jsonl_name="hier_sup") as logger:
        resumed.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "hier"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    np.testing.assert_array_equal(np.asarray(resumed.state.weights),
                                  w_full)
    np.testing.assert_array_equal(np.asarray(resumed.state.velocity),
                                  v_full)
    assert RunJournal(cfg.run_dir, "hier").verify(
        epochs=10, test_step=5) == []
    with open(os.path.join(cfg.log_dir, "hier_sup.jsonl")) as f:
        events = [json.loads(line) for line in f]
    evals = [e["round"] for e in events if e["kind"] == "eval"]
    assert evals == sorted(set(evals))      # each eval exactly once


# ---------------------------------------------------------------------------
# config / CLI surface

def test_hier_config_validation(tmp_path):
    with pytest.raises(ValueError, match="megabatch"):
        _cfg(tmp_path, aggregation="hierarchical")          # no megabatch
    with pytest.raises(ValueError, match="divide"):
        _cfg(tmp_path, aggregation="hierarchical", megabatch=5)
    with pytest.raises(ValueError, match="shards"):
        _cfg(tmp_path, aggregation="hierarchical", megabatch=12)
    with pytest.raises(ValueError, match="aggregation"):
        _cfg(tmp_path, aggregation="tree")
    with pytest.raises(ValueError, match="tier2_defense"):
        _cfg(tmp_path, tier2_defense="FLTrust")


def test_hier_engine_rejects_unsupported_combos(tmp_path):
    # NOTE (ISSUE 8): telemetry/log_round_stats are no longer in this
    # matrix — they are supported hierarchical compositions now (the
    # per-shard diagnostics ride the scan as (S, m) stacks); ISSUE 19
    # likewise removed fault injection (per-shard quarantine masks
    # inside the scan step, tests/test_hier_faults.py); the remaining
    # rejections pin only the still-unsupported set.
    ds = _dataset()
    for kw, match in (
            (dict(participation=0.5), "participation"),
            (dict(data_placement="host_stream"), "device"),
            (dict(defense="GeoMedian"), "tier-1"),
            (dict(distance_impl="host"), "distance_impl"),
            (dict(trimmed_mean_impl="host"), "trimmed_mean_impl"),
    ):
        with pytest.raises(ValueError, match=match):
            FederatedExperiment(_hier(tmp_path, **kw),
                                attacker=DriftAttack(1.0), dataset=ds)
    # Tier validity bounds surface at init, not trace time.
    with pytest.raises(ValueError, match="Bulyan requires"):
        FederatedExperiment(
            _hier(tmp_path, defense="Bulyan", tier1_corrupted=2),
            attacker=DriftAttack(1.0), dataset=ds)


# ---------------------------------------------------------------------------
# ISSUE 8: per-shard telemetry, tier-2 forensics, colluder localization

def test_two_tier_telemetry_bit_matches_flat_kernels():
    """two_tier_aggregate(telemetry=True): each stacked tier-1
    diagnostics row is BIT-FOR-BIT the flat kernel's telemetry on that
    shard's sub-matrix (the ISSUE 8 acceptance contract), the tier-2
    diag is the shard_* entry's (S,) selection record, and the
    aggregate itself is bit-equal to the telemetry-off call."""
    n, m, f = 32, 8, 3
    pl = make_placement(n, f, m, "concentrated")
    f1 = tier1_assumed(f, pl.num_shards)
    f2 = max(tier2_assumed(f, m), 1)
    rng = np.random.default_rng(11)
    G = jnp.asarray(rng.standard_normal((n, 40)).astype(np.float32))
    t2 = TIER2_DEFENSES["Krum"]
    with jax.disable_jit():
        plain = two_tier_aggregate(G, pl, krum, t2, f1, f2)
        agg, t1d, t2d = two_tier_aggregate(G, pl, krum, t2, f1, f2,
                                           telemetry=True)
        # Per-shard rows == the flat kernel's telemetry on the same
        # sub-matrix (op-identical dispatch -> bitwise).
        for s in range(pl.num_shards):
            _, want = krum(G[jnp.asarray(pl.grid[s])], m, f1,
                           telemetry=True)
            for k in want:
                np.testing.assert_array_equal(
                    np.asarray(t1d[k][s]), np.asarray(want[k]), err_msg=k)
        # Tier-2 record: one-hot over the shard axis.
        _, want2 = krum(jnp.stack([
            krum(G[jnp.asarray(pl.grid[s])], m, f1)
            for s in range(pl.num_shards)]).astype(jnp.float32),
            pl.num_shards, f2, telemetry=True)
        np.testing.assert_array_equal(
            np.asarray(t2d["selection_mask"]),
            np.asarray(want2["selection_mask"]))
    np.testing.assert_array_equal(np.asarray(agg), np.asarray(plain))
    assert np.asarray(t1d["selection_mask"]).shape == (pl.num_shards, m)
    assert np.asarray(t2d["selection_mask"]).shape == (pl.num_shards,)


def test_shard_kernels_telemetry_passthrough():
    """Every TIER2_DEFENSES entry takes telemetry= and returns a
    bit-identical aggregate plus a fixed-shape (S,)/() diag."""
    rng = np.random.default_rng(5)
    E = jnp.asarray(rng.standard_normal((7, 24)).astype(np.float32))
    for name, fn in sorted(TIER2_DEFENSES.items()):
        plain = np.asarray(fn(E, 7, 1))
        agg, diag = fn(E, 7, 1, telemetry=True)
        np.testing.assert_array_equal(plain, np.asarray(agg),
                                      err_msg=name)
        for k, v in diag.items():
            assert np.asarray(v).shape in ((), (7,)), (name, k)
    assert TIER2_DEFENSES["NoDefense"](E, 7, 0, telemetry=True)[1] == {}


def test_hier_telemetry_on_off_bit_identical_and_hlo_clean(tmp_path):
    """Engine acceptance: telemetry must be a pure observer of the
    hierarchical round — on/off final weights bit-equal (span path),
    and the telemetry-OFF compiled round carries none of the stacked
    (S, m) diagnostics tensors (the structural half of the
    byte-identity pin; tools/perf_gate.py's hier cells staying
    byte-exact is the other half)."""
    ds = _dataset()
    off = FederatedExperiment(_hier(tmp_path, defense="Krum", epochs=4),
                              attacker=DriftAttack(1.0), dataset=ds)
    off.run_span(0, 4)
    on = FederatedExperiment(
        _hier(tmp_path, defense="Krum", epochs=4, telemetry=True),
        attacker=DriftAttack(1.0), dataset=ds)
    on.run_span(0, 4)
    np.testing.assert_array_equal(np.asarray(off.state.weights),
                                  np.asarray(on.state.weights))
    np.testing.assert_array_equal(np.asarray(off.state.velocity),
                                  np.asarray(on.state.velocity))
    # Structural HLO pin: S=3, m=4 — the stacked per-shard mask/score/
    # norm tensors are f32[3,4]; the off program must not contain one
    # (compiled-HLO text, the wire_hlo_facts convention).
    text_off = off._fused_round.lower(
        off.data, off.state, jnp.asarray(0, jnp.int32)).compile().as_text()
    text_on = on._fused_round.lower(
        on.data, on.state, jnp.asarray(0, jnp.int32)).compile().as_text()
    assert "f32[3,4]" not in text_off
    assert "f32[3,4]" in text_on          # non-vacuous
    # Stacked telemetry shapes: (rounds, S, m) tier-1, (rounds, S)
    # tier-2, from the span's one fetch.
    t0, stacked = on.last_span_telemetry
    host = jax.tree.map(np.asarray, stacked)
    assert host["shard_selection_mask"].shape == (4, 3, 4)
    assert host["tier2_selection_mask"].shape == (4, 3)
    # Per-round tier-1 masks are one-hot per shard (Krum), and the
    # tier-2 mask is one-hot over shards.
    assert (host["shard_selection_mask"].sum(axis=2) == 1.0).all()
    assert (host["tier2_selection_mask"].sum(axis=1) == 1.0).all()


def test_hier_round_stats(tmp_path):
    """--round-stats on a hierarchical run: per-round scalar diag with
    the flat keys, computed exactly from the (S, m) norm stack (same n
    values, different reduction shape)."""
    ds = _dataset()
    exp = FederatedExperiment(
        _hier(tmp_path, defense="Krum", log_round_stats=True),
        attacker=DriftAttack(1.0), dataset=ds)
    exp.run_round(0)
    diag = {k: float(v) for k, v in exp.last_round_stats.items()}
    assert set(diag) == {"grad_norm_mean", "grad_norm_max",
                         "grad_norm_min", "update_norm", "faded_lr"}
    assert diag["grad_norm_max"] >= diag["grad_norm_mean"] >= (
        diag["grad_norm_min"]) > 0


def test_hier_tele_cost_entry(tmp_path):
    """The telemetry engine ledgers its span under hier_tele_span —
    the perf-gate hier_krum_tele cell's entry point."""
    ds = _dataset()
    exp = FederatedExperiment(
        _hier(tmp_path, defense="Krum", telemetry=True),
        attacker=DriftAttack(1.0), dataset=ds)
    led = exp.cost_report()
    names = [r.name for r in led.records]
    assert "hier_tele_span" in names and not led.errors


def test_hier_telemetry_events_and_forensics_localization(tmp_path):
    """Satellite acceptance: a 10-round concentrated-placement Krum
    run emits one schema-v6 'shard_selection' event per round whose
    tier-2 mask rejects the colluder shard, and `report forensics`
    localizes it — the verdict NAMES the malicious shard(s)."""
    from attacking_federate_learning_tpu import report

    ds = load_dataset(C.SYNTH_MNIST_HARD, seed=0)
    cfg = ExperimentConfig(
        dataset=C.SYNTH_MNIST_HARD, users_count=20, mal_prop=0.2,
        batch_size=64, epochs=10, test_step=10, num_std=1.5,
        defense="Krum", seed=0, aggregation="hierarchical",
        megabatch=5, mal_placement="concentrated", telemetry=True,
        log_dir=str(tmp_path / "logs"), run_dir=str(tmp_path / "runs"))
    exp = FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                              dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="fx") as logger:
        exp.run(logger)
    path = os.path.join(cfg.log_dir, "fx.jsonl")
    events = report.load_events([path])       # schema-validates v6
    ss = [e for e in events if e["kind"] == "shard_selection"]
    assert len(ss) == 10
    assert all(e["v"] >= 6 for e in ss)   # stamped with the writer version
    assert ss[0]["mal_counts"] == [4, 0, 0, 0]
    # Placement packs all 4 colluders into shard 0; tier-2 Krum must
    # reject its estimate (zero selection mass) every round — the
    # measured GRID round-6 rescue, now attributed.
    for e in ss:
        assert e["tier2_selection_mask"][0] == 0.0
    fx = report.forensics_summary(events)
    assert fx["malicious_shards"] == [0]
    assert fx["localization"]["verdict"] == "localized"
    assert fx["localization"]["isolated_shards"] == [0]
    assert fx["tier2"]["mal_rejected_rounds"] == 10
    assert fx["tier2"]["malicious_share"] == 0.0
    # Tier-1 concentration: the colluder shard's selection collapses
    # onto its own malicious rows (the duplicate-collapse mechanism).
    row0 = next(r for r in fx["tier1"] if r["shard"] == 0)
    assert row0["malicious_share"] > 0.9
    # The CLI surface agrees: `report forensics` exits 0 and the
    # emitted v6 'forensics' event validates.
    ev_path = str(tmp_path / "fx_verdict.jsonl")
    assert report.forensics_main([path, "--events", ev_path]) == 0
    rec = json.loads(open(ev_path).read().strip())
    assert rec["kind"] == "forensics" and rec["v"] >= 6
    assert rec["verdict"] == "localized"
    assert rec["isolated_shards"] == [0]
    # A flat log (no shard_selection events) is a named failure.
    flat = str(tmp_path / "flat.jsonl")
    with open(flat, "w") as f:
        f.write(json.dumps({"kind": "round", "round": 0, "v": 1}) + "\n")
    assert report.forensics_main([flat]) == 1


def test_trace_export_forensics_track(tmp_path):
    """Synthetic shard_selection/forensics events land as the tier-2
    rejection counter + forensics instants, and the exported trace
    validates."""
    from attacking_federate_learning_tpu.utils.trace_export import (
        events_to_trace, validate_trace
    )

    events = [
        {"kind": "shard_selection", "round": 0, "defense": "Krum",
         "tier2_selection_mask": [0.0, 1.0, 0.0], "v": 6, "t": 1.0},
        {"kind": "shard_selection", "round": 1, "defense": "Krum",
         "tier2_kept_fraction": [0.05, 0.9, 0.85], "v": 6, "t": 2.0},
        {"kind": "shard_selection", "round": 2, "defense": "NoDefense",
         "v": 6, "t": 3.0},                   # no attribution: no point
        {"kind": "forensics", "verdict": "localized",
         "isolated_shards": [0], "v": 6, "t": 4.0},
    ]
    trace = events_to_trace(events)
    assert validate_trace(trace) == []
    counters = [e for e in trace["traceEvents"]
                if e["name"] == "tier2_rejected"]
    assert [e["args"]["tier2_rejected"] for e in counters] == [2.0, 1.0]
    instants = [e for e in trace["traceEvents"]
                if e["name"].startswith("tier2 reject")]
    assert len(instants) == 2
    assert instants[0]["args"]["rejected_shards"] == "0,2"
    assert instants[1]["args"]["rejected_shards"] == "0"
    assert any(e["name"] == "forensics:localized"
               for e in trace["traceEvents"])


def test_cli_hier_flags_roundtrip():
    from attacking_federate_learning_tpu.cli import (
        build_parser, config_from_args
    )

    args = build_parser().parse_args(
        ["-d", "Krum", "-s", "SYNTH_MNIST", "-n", "12",
         "--aggregation", "hierarchical", "--megabatch", "4",
         "--tier2-defense", "TrimmedMean", "--mal-placement",
         "concentrated", "--tier1-corrupted", "2",
         "--tier2-corrupted", "1"])
    cfg = config_from_args(args)
    assert cfg.aggregation == "hierarchical" and cfg.megabatch == 4
    assert cfg.tier2_defense == "TrimmedMean"
    assert cfg.mal_placement == "concentrated"
    assert cfg.tier1_corrupted == 2 and cfg.tier2_corrupted == 1
