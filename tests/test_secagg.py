"""Secure-aggregation protocol layer (ISSUE 7, protocols/secagg.py).

Acceptance contract: pairwise masks cancel BIT-EXACTLY in the uint32
bitcast domain (``sum(masked) == sum(clear)`` bitwise, dropout-recovery
path included); a ``--secagg vanilla`` run's final weights are
bit-equal to the clear NoDefense run's (the protocol is behaviorally
invisible when nothing inspects individual updates); a SIGTERM-
preempted secagg run resumes bit-for-bit (masks are derived, never
stored); every unsupported composition raises at init with a message
naming the offending flag (the PR 6 hierarchical rejections included);
the compiled vanilla round carries the structural wire facts; and
``--secagg groupwise`` composes with the two-tier tree (tier-2 robust
kernels over per-group sums, v5 'secagg' events with group-sum norms).
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
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.protocols import secagg as sa
from attacking_federate_learning_tpu.utils.checkpoint import Checkpointer
from attacking_federate_learning_tpu.utils.metrics import (
    RunLogger, validate_event
)


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 12)
    kw.setdefault("mal_prop", 0.25)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 6)
    kw.setdefault("test_step", 3)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("defense", "NoDefense")
    kw.setdefault("log_dir", str(tmp_path / "logs"))
    kw.setdefault("run_dir", str(tmp_path / "runs"))
    return ExperimentConfig(**kw)


_DS = {}


def _dataset(name=C.SYNTH_MNIST):
    if name not in _DS:
        _DS[name] = load_dataset(name, seed=0, synth_train=256,
                                 synth_test=64)
    return _DS[name]


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


# ---------------------------------------------------------------------------
# protocol core: bit-exact mask cancellation (satellite 1)

def _matrix(n, d=257, seed=None):
    """An adversarially-scaled f32 matrix: magnitudes spanning ~16
    decades, the regime where f32 ADDITIVE masking could never cancel
    (rounding) — the uint32 bitcast domain must not care."""
    rng = np.random.default_rng(seed if seed is not None else n)
    G = rng.standard_normal((n, d)) * 10.0 ** rng.integers(-8, 8, (n, d))
    return jnp.asarray(G.astype(np.float32))


@pytest.mark.parametrize("n", [3, 19, 32])
def test_pairwise_cancellation_bitexact(n):
    """sum(masked) == sum(clear) BITWISE in the mod-2^32 domain: the
    antisymmetric per-pair masks cancel exactly in the modular column
    sum, while each individual wire row is garbage."""
    G = _matrix(n)
    ids = jnp.arange(n, dtype=jnp.int32)
    key_t = jax.random.fold_in(jax.random.key(7), 3)
    deltas = sa.pairwise_deltas(key_t, ids, G.shape[1])
    wire = sa.mask_rows(G, deltas)
    bits = jax.lax.bitcast_convert_type(G, jnp.uint32)
    np.testing.assert_array_equal(np.asarray(sa.modular_sum(wire)),
                                  np.asarray(sa.modular_sum(bits)))
    # Masking is not a no-op (every row actually moved).
    assert not (np.asarray(wire) == np.asarray(bits)).all(axis=1).any()
    # Per-row unmask is the exact inverse, and the sum check passes.
    rec, stats = sa.unmask_sum(wire, deltas, G, None, key_t, ids)
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(G))
    assert int(stats["secagg_sum_check_ok"]) == 1
    assert int(stats["secagg_recovery"]) == 0


@pytest.mark.parametrize("n", [3, 19, 32])
def test_dropout_recovery_exact(n):
    """The Bonawitz recovery identity, bitwise: with dropped clients
    the survivors' modular sum minus the pair-by-pair reconstructed
    residue equals the clear survivors' modular sum exactly, and the
    reconstruction count is |alive| * |dropped| revealed pairs."""
    G = _matrix(n)
    ids = jnp.arange(n, dtype=jnp.int32)
    key_t = jax.random.fold_in(jax.random.key(7), 5)
    deltas = sa.pairwise_deltas(key_t, ids, G.shape[1])
    wire = sa.mask_rows(G, deltas)
    rng = np.random.default_rng(n)
    alive = rng.random(n) > 0.3
    alive[:2] = [False, True]            # >= 1 dropped, >= 1 survivor
    alive = jnp.asarray(alive)
    rec, stats = sa.unmask_sum(wire, deltas, G, alive, key_t, ids)
    n_alive, n_drop = int(alive.sum()), int((~alive).sum())
    assert int(stats["secagg_sum_check_ok"]) == 1
    assert int(stats["secagg_dropped"]) == n_drop
    assert int(stats["secagg_recovery"]) == 1
    assert int(stats["secagg_masks_reconstructed"]) == n_alive * n_drop
    np.testing.assert_array_equal(
        np.asarray(rec),
        np.where(np.asarray(alive)[:, None], np.asarray(G), 0.0))
    # The residue really is the survivors' unpaired mask mass: the
    # explicit identity modsum(wire[alive]) - R == modsum(clear[alive]).
    R, pairs = sa.recovery_residue(key_t, ids, alive, G.shape[1])
    bits = jax.lax.bitcast_convert_type(G, jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(sa.modular_sum(wire, alive) - R),
        np.asarray(sa.modular_sum(bits, alive)))
    assert int(pairs) == n_alive * n_drop


def test_mask_roundtrip_preserves_every_bit_pattern():
    """NaN/Inf/denormal rows ride the wire bit-exactly: the bitcast
    domain is invariant to float semantics (np.array_equal on the BIT
    view — NaN != NaN in float compare, but its pattern must survive)."""
    G = jnp.asarray(np.array(
        [[np.nan, np.inf, -np.inf, 0.0, -0.0],
         [1e-44, -1e-44, 3.14, -2.5e38, 2.5e38],
         [1.0, 2.0, 3.0, 4.0, 5.0]], np.float32))
    ids = jnp.arange(3, dtype=jnp.int32)
    key_t = jax.random.fold_in(jax.random.key(0), 0)
    deltas = sa.pairwise_deltas(key_t, ids, 5)
    rec = sa.unmask_rows(sa.mask_rows(G, deltas), deltas)
    np.testing.assert_array_equal(
        np.asarray(jax.lax.bitcast_convert_type(rec, jnp.uint32)),
        np.asarray(jax.lax.bitcast_convert_type(G, jnp.uint32)))


def test_masks_are_derived_not_stored():
    """Two independent derivations from the same config produce the
    identical mask stream (the preempt/resume re-derivation witness),
    and different rounds/seeds produce different streams."""
    cfg_a = ExperimentConfig(seed=3)
    key_a, key_b = sa.secagg_key(cfg_a), sa.secagg_key(
        ExperimentConfig(seed=3))
    ids = jnp.arange(5, dtype=jnp.int32)
    d_a = sa.pairwise_deltas(jax.random.fold_in(key_a, 2), ids, 17)
    d_b = sa.pairwise_deltas(jax.random.fold_in(key_b, 2), ids, 17)
    np.testing.assert_array_equal(np.asarray(d_a), np.asarray(d_b))
    d_c = sa.pairwise_deltas(jax.random.fold_in(key_a, 3), ids, 17)
    assert not np.array_equal(np.asarray(d_a), np.asarray(d_c))
    d_d = sa.pairwise_deltas(
        jax.random.fold_in(sa.secagg_key(ExperimentConfig(seed=4)), 2),
        ids, 17)
    assert not np.array_equal(np.asarray(d_a), np.asarray(d_d))


# ---------------------------------------------------------------------------
# acceptance: the protocol is behaviorally invisible

def test_vanilla_run_bit_equal_clear_nodefense(tmp_path):
    """--secagg vanilla final weights are bit-equal to the clear
    NoDefense run under an active ALIE-style attack: nothing in the
    run inspects individual updates, so masking must change nothing."""
    ds = _dataset()
    clear = FederatedExperiment(_cfg(tmp_path),
                                attacker=DriftAttack(1.0), dataset=ds)
    clear.run_span(0, 6)
    masked = FederatedExperiment(_cfg(tmp_path, secagg="vanilla"),
                                 attacker=DriftAttack(1.0), dataset=ds)
    masked.run_span(0, 6)
    np.testing.assert_array_equal(np.asarray(masked.state.weights),
                                  np.asarray(clear.state.weights))
    np.testing.assert_array_equal(np.asarray(masked.state.velocity),
                                  np.asarray(clear.state.velocity))


def test_vanilla_dropout_recovery_run(tmp_path):
    """--fault-dropout under --secagg vanilla: every dropout round
    completes as a mask-reconstruction round (exact sum recovery,
    counted in v5 'secagg' events) and the run stays bit-equal to the
    clear faulted run — recovery is exact, not approximate."""
    ds = _dataset()

    def run(tag, **kw):
        cfg = _cfg(tmp_path, faults=FaultConfig(dropout=0.25), **kw)
        exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=ds)
        with RunLogger(cfg, None, cfg.log_dir, jsonl_name=tag) as logger:
            exp.run(logger)
        return exp

    clear = run("clear_faulted")
    masked = run("secagg_faulted", secagg="vanilla")
    np.testing.assert_array_equal(np.asarray(masked.state.weights),
                                  np.asarray(clear.state.weights))
    events = _events(tmp_path / "logs" / "secagg_faulted.jsonl")
    sec = [e for e in events if e.get("kind") == "secagg"]
    faults = [e for e in events if e.get("kind") == "fault"]
    assert len(sec) == 6 and len(faults) == 6    # one per round, both
    assert all(e["sum_check_ok"] == 1 for e in sec)
    # The seeded schedule drops clients (the clear twin's fault events
    # witness it); every such round must be a recovery round whose
    # reconstruction count matches alive * dropped.
    assert sum(e["recovery"] for e in sec) >= 1
    for e in sec:
        drop = e["dropped"]
        assert e["recovery"] == (1 if drop else 0)
        assert e["masks_reconstructed"] == (12 - drop) * drop
        fe = next(f for f in faults if f["round"] == e["round"])
        assert fe["injected_dropout"] == drop


def test_groupwise_composes_with_hierarchy(tmp_path):
    """--secagg groupwise x --aggregation hierarchical: tier-2 robust
    kernels run over per-group sums end-to-end, 'secagg' events carry
    the per-group sum norms, and with a NoDefense tier-2 the protocol
    is behaviorally invisible against the plain hierarchical run."""
    ds = _dataset()
    cfg = _cfg(tmp_path, secagg="groupwise", aggregation="hierarchical",
               megabatch=4, tier2_defense="Krum")
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="gw") as logger:
        exp.run(logger)
    sec = [e for e in _events(tmp_path / "logs" / "gw.jsonl")
           if e.get("kind") == "secagg"]
    assert len(sec) == 6
    for e in sec:
        assert e["sum_check_ok"] == 1 and e["groups"] == 3
        assert len(e["group_sum_norms"]) == 3
        assert all(x > 0 for x in e["group_sum_norms"])

    masked = FederatedExperiment(
        _cfg(tmp_path, secagg="groupwise", aggregation="hierarchical",
             megabatch=4),
        attacker=DriftAttack(1.0), dataset=ds)
    masked.run_span(0, 6)
    plain = FederatedExperiment(
        _cfg(tmp_path, aggregation="hierarchical", megabatch=4),
        attacker=DriftAttack(1.0), dataset=ds)
    plain.run_span(0, 6)
    np.testing.assert_array_equal(np.asarray(masked.state.weights),
                                  np.asarray(plain.state.weights))


# ---------------------------------------------------------------------------
# satellite 2: SIGTERM preempt -> resume bit-for-bit (masks re-derived)

def test_secagg_preempt_resume_bit_for_bit(tmp_path):
    """test_hierarchy.py's journal-audit harness under --secagg
    vanilla + dropout faults: the mask PRNG state is derived, not
    stored, so the resumed attempt re-derives identical masks — final
    weights bit-equal to the uninterrupted run, journal exactly-once,
    and the resumed attempt's 'secagg' events (recovery counts
    included) byte-match the uninterrupted run's for the same rounds."""
    from attacking_federate_learning_tpu.utils.lifecycle import (
        GracefulShutdown, Preempted, RunJournal
    )

    kill_round = int(np.random.default_rng(31).integers(1, 9))
    ds = _dataset()

    def cfg_for(run_dir):
        return _cfg(tmp_path, secagg="vanilla",
                    faults=FaultConfig(dropout=0.25), epochs=10,
                    test_step=5, checkpoint_every=3,
                    run_dir=str(tmp_path / run_dir))

    cfg_ref = cfg_for("runs_ref")
    full = FederatedExperiment(cfg_ref, attacker=DriftAttack(1.0),
                               dataset=ds)
    with RunLogger(cfg_ref, None, cfg_ref.log_dir,
                   jsonl_name="sa_full") as logger:
        full.run(logger, checkpointer=Checkpointer(cfg_ref))
    w_full = np.array(full.state.weights, copy=True)

    cfg = cfg_for("runs_sup")
    ck = Checkpointer(cfg)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir,
                   jsonl_name="sa_sup") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "sa"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    resumed = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=ds)
    state, extra = ck.resume(ck.latest(), with_extra=True)
    resumed.state = state
    resumed.restore_fault_state(extra)
    with RunLogger(cfg, None, cfg.log_dir,
                   jsonl_name="sa_sup") as logger:
        resumed.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "sa"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    np.testing.assert_array_equal(np.asarray(resumed.state.weights),
                                  w_full)
    assert RunJournal(cfg.run_dir, "sa").verify(
        epochs=10, test_step=5) == []
    sup = [e for e in _events(tmp_path / "logs" / "sa_sup.jsonl")
           if e.get("kind") == "secagg"]
    ref = {e["round"]: e for e in
           _events(tmp_path / "logs" / "sa_full.jsonl")
           if e.get("kind") == "secagg"}
    rounds = [e["round"] for e in sup]
    assert rounds == sorted(set(rounds))        # exactly once per round
    assert set(rounds) == set(ref)
    for e in sup:                               # identical re-derivation
        for k in ("sum_check_ok", "dropped", "masks_reconstructed",
                  "recovery"):
            assert e[k] == ref[e["round"]][k], (e["round"], k)


# ---------------------------------------------------------------------------
# satellite 3: the loud-rejection message contract

# (cfg_kwargs, message fragment naming the offending flag).  Config-level
# rejections raise at ExperimentConfig construction.  (ISSUE 8 relaxed
# the matrix: --telemetry/--round-stats now compose with groupwise —
# tier-2 selection over group sums is server-visible — so only the
# VANILLA rows stay pinned here: one masked cohort sum has nothing
# per-client or per-group to observe.)
_CONFIG_REJECTS = [
    (dict(secagg="vanilla", defense="Krum"), "--secagg vanilla"),
    (dict(secagg="vanilla", defense="Bulyan"), "--tier2-defense"),
    (dict(secagg="groupwise", aggregation="hierarchical", megabatch=4,
          defense="TrimmedMean"), "--tier2-defense"),
    (dict(secagg="vanilla", aggregation="hierarchical", megabatch=4),
     "--secagg groupwise"),
    (dict(secagg="groupwise"), "--aggregation hierarchical"),
    (dict(secagg="vanilla", telemetry=True), "--telemetry"),
    (dict(secagg="vanilla", log_round_stats=True), "--round-stats"),
    (dict(secagg="vanilla", backdoor="pattern", backdoor_fused=False),
     "--backdoor-staged"),
    (dict(secagg="vanilla", participation=0.5), "--participation"),
    (dict(secagg="vanilla", grad_dtype="bfloat16"), "grad_dtype"),
    (dict(secagg="vanilla", faults=FaultConfig(straggler=0.2)),
     "--fault-straggler"),
    (dict(secagg="vanilla", faults=FaultConfig(corrupt=0.2)),
     "--fault-corrupt"),
    (dict(secagg="sideways"), "--secagg"),
]

# PR 6's hierarchical rejections, pinned to flag-naming messages too
# (minus telemetry/round-stats — supported since ISSUE 8 — and fault
# injection — supported since ISSUE 19, tests/test_hier_faults.py).
_ENGINE_REJECTS = [
    (dict(aggregation="hierarchical", megabatch=4, participation=0.5),
     "participation"),
    (dict(aggregation="hierarchical", megabatch=4,
          data_placement="host_stream"), "device"),
    (dict(aggregation="hierarchical", megabatch=4, backdoor="pattern",
          backdoor_fused=False), "--backdoor-staged"),
    (dict(aggregation="hierarchical", megabatch=4,
          trimmed_mean_impl="host"), "trimmed_mean_impl"),
    (dict(aggregation="hierarchical", megabatch=4,
          distance_impl="host"), "distance_impl"),
]


@pytest.mark.parametrize("kw,match", _CONFIG_REJECTS)
def test_secagg_config_rejections_name_the_flag(tmp_path, kw, match):
    with pytest.raises(ValueError, match=match):
        _cfg(tmp_path, **kw)


@pytest.mark.parametrize("kw,match", _ENGINE_REJECTS)
def test_hier_engine_rejections_name_the_flag(tmp_path, kw, match):
    with pytest.raises(ValueError, match=match):
        FederatedExperiment(_cfg(tmp_path, defense="Krum", **kw),
                            attacker=DriftAttack(1.0),
                            dataset=_dataset())


def test_groupwise_telemetry_composition(tmp_path):
    """ISSUE 8: --telemetry now composes with --secagg groupwise.  The
    observable surface is the GROUP-SUM level only: 'shard_selection'
    events carry tier-2 fields, never per-client stacks (no
    shard_grad_norms, no shard_selection_mask — tier-1 is NoDefense
    over rows the threat model hides); 'secagg' events grow the
    per-group envelope (cosine-to-mean next to the sum norms); and the
    run's weights stay bit-equal to the telemetry-off twin."""
    ds = _dataset()

    def cfg(**kw):
        return _cfg(tmp_path, secagg="groupwise",
                    aggregation="hierarchical", megabatch=4,
                    tier2_defense="Krum", **kw)

    off = FederatedExperiment(cfg(), attacker=DriftAttack(1.0),
                              dataset=ds)
    off.run_span(0, 6)
    c_on = cfg(telemetry=True)
    on = FederatedExperiment(c_on, attacker=DriftAttack(1.0), dataset=ds)
    with RunLogger(c_on, None, c_on.log_dir,
                   jsonl_name="gw_tele") as logger:
        on.run(logger)
    np.testing.assert_array_equal(np.asarray(off.state.weights),
                                  np.asarray(on.state.weights))
    events = _events(tmp_path / "logs" / "gw_tele.jsonl")
    ss = [e for e in events if e.get("kind") == "shard_selection"]
    assert len(ss) == 6 and all(e["v"] >= 6 for e in ss)
    for e in ss:
        assert len(e["tier2_selection_mask"]) == 3   # S groups
        # Per-client stacks must NOT appear under secagg: the server
        # never holds the rows they would be computed from.
        assert not any(k.startswith("shard_") for k in e)
    sec = [e for e in events if e.get("kind") == "secagg"]
    assert len(sec) == 6
    for e in sec:
        assert len(e["group_cos_to_mean"]) == 3
        assert all(-1.0 - 1e-5 <= x <= 1.0 + 1e-5
                   for x in e["group_cos_to_mean"])
    # Forensics runs on the groupwise stream too (tier-2-only view).
    from attacking_federate_learning_tpu.report import forensics_summary
    fx = forensics_summary(events)
    assert fx is not None and fx["tier2"]["rounds"] == 6
    assert "tier1" not in fx


def test_groupwise_round_stats_composition(tmp_path):
    """--round-stats under groupwise reports group-sum norm stats (the
    server-visible quantity), not per-client gradient norms."""
    ds = _dataset()
    exp = FederatedExperiment(
        _cfg(tmp_path, secagg="groupwise", aggregation="hierarchical",
             megabatch=4, tier2_defense="Krum", log_round_stats=True),
        attacker=DriftAttack(1.0), dataset=ds)
    exp.run_round(0)
    diag = {k: float(v) for k, v in exp.last_round_stats.items()}
    assert set(diag) == {"group_sum_norm_mean", "group_sum_norm_max",
                         "group_sum_norm_min", "update_norm",
                         "faded_lr"}
    assert diag["group_sum_norm_max"] >= diag["group_sum_norm_mean"] > 0


def test_secagg_rejects_nonfusable_attacker(tmp_path):
    """The engine-level half of the contract: a non-fusable attacker
    handed in programmatically (the --backdoor-staged path arrives as
    one) is rejected before any tracing."""
    class Staged(DriftAttack):
        fusable = False

    with pytest.raises(ValueError, match="fusable"):
        FederatedExperiment(_cfg(tmp_path, secagg="vanilla"),
                            attacker=Staged(1.0), dataset=_dataset())


# ---------------------------------------------------------------------------
# acceptance: HLO structure (secagg off byte-identical; vanilla wire pin)

def test_secagg_off_hlo_has_no_protocol_trace(tmp_path):
    """cfg.secagg='off' (the default) compiles a round with no uint32
    wire tensor and no secagg events — PERF_BASELINE's byte-exact
    FLOPs/bytes pins the stronger no-drift claim; this is the direct
    witness that the off path never touches the protocol."""
    ds = _dataset()
    exp = FederatedExperiment(_cfg(tmp_path), attacker=DriftAttack(1.0),
                              dataset=ds)
    text = exp._fused_round.lower(
        exp.data, exp.state, jnp.asarray(0, jnp.int32),
        None).compile().as_text()
    facts = sa.wire_hlo_facts(text, 12, exp.flat.dim)
    assert not facts["wire_present"]
    assert facts["unmask_instructions"] == 0
    assert exp._secagg is None


def test_vanilla_wire_hlo_pin(tmp_path):
    """The perf_gate-memproof-style structural pin on the compiled
    vanilla round (tools/perf_gate.py wireproof runs the same facts in
    CI): the masked u32 wire exists, the server's reconstruction of
    the per-client matrix feeds ONLY the cohort-sum reduce, and no
    (n, n) distance matrix exists."""
    ds = _dataset()
    exp = FederatedExperiment(_cfg(tmp_path, secagg="vanilla"),
                              attacker=DriftAttack(1.0), dataset=ds)
    text = exp._fused_round.lower(
        exp.data, exp.state, jnp.asarray(0, jnp.int32),
        None).compile().as_text()
    facts = sa.wire_hlo_facts(text, 12, exp.flat.dim)
    assert facts["wire_present"]
    assert facts["unmask_instructions"] >= 1
    assert facts["unmask_reduce_only"]
    assert not facts["distance_matrix"]


# ---------------------------------------------------------------------------
# schema v5, validator, report rollup

def test_secagg_event_schema_v5(tmp_path):
    validate_event({"kind": "secagg", "round": 3, "sum_check_ok": 1,
                    "v": 5})
    with pytest.raises(ValueError, match="need schema v5"):
        validate_event({"kind": "secagg", "round": 3, "v": 4})
    with pytest.raises(ValueError, match="missing required"):
        validate_event({"kind": "secagg", "v": 5})
    # tools/check_events.py speaks v5.
    import importlib.util as ilu
    spec = ilu.spec_from_file_location(
        "check_events", os.path.join(os.path.dirname(__file__),
                                     os.pardir, "tools",
                                     "check_events.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    p = tmp_path / "sec.jsonl"
    p.write_text(json.dumps({"kind": "secagg", "round": 0,
                             "sum_check_ok": 1, "recovery": 1,
                             "masks_reconstructed": 11, "v": 5,
                             "t": 0.1}) + "\n"
                 + json.dumps({"kind": "secagg", "round": 1, "v": 3,
                               "t": 0.2}) + "\n")
    counts, legacy, errors = mod.check_file(str(p))
    assert counts == {"secagg": 1}
    assert len(errors) == 1 and "need schema v5" in errors[0][1]


def test_report_secagg_rollup(tmp_path):
    from attacking_federate_learning_tpu.report import summarize_run

    events = [
        {"kind": "secagg", "round": 0, "sum_check_ok": 1, "dropped": 0,
         "masks_reconstructed": 0, "recovery": 0, "v": 5},
        {"kind": "secagg", "round": 1, "sum_check_ok": 1, "dropped": 2,
         "masks_reconstructed": 20, "recovery": 1,
         "group_sum_norms": [1.5, 2.5, 3.5], "v": 5},
        {"kind": "eval", "round": 1, "test_loss": 0.1, "accuracy": 50.0,
         "correct": 32, "test_size": 64, "v": 5},
    ]
    s = summarize_run(events)
    assert s["secagg"] == {
        "rounds": 2, "recovery_rounds": 1, "masks_reconstructed": 20,
        "sum_check_failures": 0, "groups": 3,
        "group_sum_norms_last": [1.5, 2.5, 3.5]}


# ---------------------------------------------------------------------------
# satellite 4: runs diff --band

def test_runs_diff_band_ulp_tolerance():
    from attacking_federate_learning_tpu.runs_cli import (
        _f32_ord, diff_trajectories
    )

    x = 193.0
    x1 = float(np.nextafter(np.float32(x), np.float32(np.inf)))
    assert _f32_ord(x1) - _f32_ord(x) == 1
    a = [{"kind": "round", "round": 0, "grad_norm_mean": x, "v": 5},
         {"kind": "round", "round": 1, "grad_norm_mean": -x, "v": 5}]
    b = [{"kind": "round", "round": 0, "grad_norm_mean": x1, "v": 5},
         {"kind": "round", "round": 1, "grad_norm_mean": -x, "v": 5}]
    exact = diff_trajectories(a, b)
    assert exact["divergence_round"] == 0
    assert not exact["bit_identical"]
    banded = diff_trajectories(a, b, band=1)
    assert banded["divergence_round"] is None
    assert banded.get("identical_within_band")
    assert not banded["bit_identical"]          # banded != bit-exact
    # Identical streams under band 0 still report bit-identity.
    assert diff_trajectories(a, list(a))["bit_identical"]
    # A real drift (beyond the band) still diverges.
    c = [{"kind": "round", "round": 0, "grad_norm_mean": x + 1.0,
          "v": 5}]
    assert diff_trajectories(a, c, band=4)["divergence_round"] == 0
    # Negative floats band correctly across the sign-magnitude seam.
    d1 = [{"kind": "round", "round": 0, "g": -0.0, "v": 5}]
    d2 = [{"kind": "round", "round": 0, "g": 0.0, "v": 5}]
    assert diff_trajectories(d1, d2, band=1)["divergence_round"] is None


# ---------------------------------------------------------------------------
# CLI surface

def test_cli_secagg_flag_roundtrip():
    from attacking_federate_learning_tpu.cli import (
        build_parser, config_from_args
    )

    args = build_parser().parse_args(
        ["-d", "NoDefense", "-s", "SYNTH_MNIST", "-n", "12",
         "--secagg", "groupwise", "--aggregation", "hierarchical",
         "--megabatch", "4", "--tier2-defense", "Krum"])
    cfg = config_from_args(args)
    assert cfg.secagg == "groupwise"
    assert cfg.aggregation == "hierarchical" and cfg.megabatch == 4
    assert cfg.tier2_defense == "Krum"
    args = build_parser().parse_args(["-d", "NoDefense", "--secagg",
                                      "vanilla"])
    assert config_from_args(args).secagg == "vanilla"
