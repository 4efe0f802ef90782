"""Numerics & determinism observatory (ISSUE 20).

Acceptance contract: the device health counters hold their units
(nonfinite by stage, norm dynamic range, tie proximity banded at k ulp
of the boundary's own scale, Gram cancellation depth); the host ulp
machinery is the shared f32 lattice (ordinals, NaN conventions, the
f64-adjudicated verdict classes the divergence ledger persists into
NUMERICS_BASELINE.json); numerics-off programs stay HLO byte-identical
(the kernel seam here, all 41 perf_gate entry points plus the
bit-identity behavioral twin in CI via --numproof); numerics without
margins is rejected at the kernel and host impls at config time; every
engine (flat, hierarchical, async) emits one schema-v14 ``numerics``
event per round; same-seed twins are bit-deterministic while seeded
diverging twins get a stage-attributed first-divergence from
``runs diff --band``; and the reader stack (rollups, series, drift,
``check_events --stats`` over mixed-version logs, the trace counter
track, the numerics gate's banding rules) holds its contracts.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.defenses.kernels import (
    bulyan, krum, trimmed_mean
)
from attacking_federate_learning_tpu.defenses.median import median
from attacking_federate_learning_tpu.utils import numerics as N
from attacking_federate_learning_tpu.utils.metrics import RunLogger


def _grads(n=12, d=40, seed=0):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(size=(n, d)).astype(np.float32))


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 12)
    kw.setdefault("mal_prop", 0.2)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 4)
    kw.setdefault("test_step", 2)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("defense", "Krum")
    kw.setdefault("numerics", True)
    kw.setdefault("log_dir", str(tmp_path / "logs"))
    kw.setdefault("run_dir", str(tmp_path / "runs"))
    return ExperimentConfig(**kw)


def _run(cfg, name, z=1.5):
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(z), dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name=name) as logger:
        exp.run(logger)
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    return exp, events


def _numerics_events(events):
    return [e for e in events if e.get("kind") == "numerics"]


# ---------------------------------------------------------------------------
# tentpole: device health counters hold their units

def test_nonfinite_count_and_mask():
    x = jnp.asarray(np.array([[1.0, np.inf, 2.0],
                              [np.nan, 3.0, -np.inf]], np.float32))
    assert int(N.nonfinite_count(x)) == 3
    mask = jnp.asarray(np.array([True, False]))
    assert int(N.nonfinite_count(x, mask)) == 1


def test_norm_dynamic_range_units():
    G = jnp.asarray(np.array([[4.0, 0.0], [1.0, 0.0]], np.float32))
    # norms 4 and 1: log2(4/1) = 2 bits of dynamic range.
    assert float(N.norm_dynamic_range(G)) == pytest.approx(2.0)
    # Fewer than two usable rows is degenerate, not an error.
    assert float(N.norm_dynamic_range(
        G, jnp.asarray(np.array([True, False])))) == 0.0
    assert float(N.norm_dynamic_range(jnp.zeros((3, 2)))) == 0.0
    # Nonfinite rows are excluded from the range, not propagated.
    Gn = jnp.asarray(np.array([[np.inf, 0.0], [2.0, 0.0], [1.0, 0.0]],
                              np.float32))
    assert float(N.norm_dynamic_range(Gn)) == pytest.approx(1.0)


def test_tie_proximity_bands_at_boundary_scale():
    """A margin within k ulp AT THE BOUNDARY'S SCALE counts as a tie;
    the same absolute margin at a tiny scale does not."""
    scale = 1.0
    band = N.TIE_BAND_ULPS * (2.0 ** -23) * scale
    m = jnp.asarray(np.array([band * 0.5, -band * 0.5, band * 4.0,
                              np.inf, -np.inf], np.float32))
    assert int(N.tie_proximity(m, scale)) == 2
    # Shrinking the boundary scale shrinks the band with it.
    assert int(N.tie_proximity(m, scale * 1e-3)) == 0
    # ulp_at never underflows to a zero band.
    assert float(N.ulp_at(0.0)) > 0.0


def test_cancellation_bits_units():
    # 2^20 max term cancelling to a 2^-4 survivor: 24 bits gone.
    assert float(N.cancellation_bits(2.0 ** 20, 2.0 ** -4)) == \
        pytest.approx(24.0)
    # No cancellation (result at the term scale) reports 0, not noise.
    assert float(N.cancellation_bits(8.0, 8.0)) == 0.0


def test_gram_cancellation_bits():
    D = jnp.asarray(np.array([[np.inf, 4.0, 16.0],
                              [4.0, np.inf, 1.0],
                              [16.0, 1.0, np.inf]], np.float32))
    # max finite 16, min positive 1 -> 4 bits.
    assert float(N.gram_cancellation_bits(D)) == pytest.approx(4.0)
    # Masking row 2 removes both extremes -> 4/4 -> 0 bits.
    mask = jnp.asarray(np.array([True, True, False]))
    assert float(N.gram_cancellation_bits(D, mask)) == 0.0
    # An identical cohort (no positive distance) is 0, not -inf/NaN.
    assert float(N.gram_cancellation_bits(
        jnp.full((3, 3), jnp.inf) * 0.0)) == 0.0


# ---------------------------------------------------------------------------
# tentpole: host ulp machinery (the ledger's referee)

def test_f32_ords_and_ulp_diff_lattice():
    a = np.float32(1.0)
    b = np.nextafter(a, np.float32(2.0), dtype=np.float32)
    assert int(N.ulp_diff([a], [b])[0]) == 1
    # The ordinal is monotone across the sign change.
    vals = np.array([-1.0, -0.0, 0.0, 1e-30, 1.0], np.float32)
    ords = N.f32_ords(vals)
    assert list(np.argsort(ords)) == [0, 1, 2, 3, 4]
    # NaN-vs-NaN is the same non-value; NaN-vs-number is unbandable.
    assert int(N.ulp_diff([np.nan], [np.nan])[0]) == 0
    assert int(N.ulp_diff([np.nan], [1.0])[0]) == 2 ** 31


def test_max_ulp_argmax():
    a = np.zeros(4, np.float32)
    b = a.copy()
    b[2] = np.nextafter(np.float32(0.0), np.float32(1.0),
                        dtype=np.float32)
    u, i = N.max_ulp(a, b)
    assert (u, i) == (1, 2)
    assert N.max_ulp(a, a) == (0, -1)


def test_adjudicate_verdict_classes():
    oracle = np.array([1.0, 2.0, 3.0], np.float64)
    o32 = oracle.astype(np.float32)
    # Bit-identical -> exact.
    assert N.adjudicate(o32, o32, oracle)["verdict"] == "exact"
    # 1-ulp wiggle around the oracle -> tie_band, inside the band.
    b = o32.copy()
    b[1] = np.nextafter(b[1], np.float32(10.0), dtype=np.float32)
    rec = N.adjudicate(o32, b, oracle)
    assert rec["verdict"] == "tie_band" and rec["in_tie_band"]
    assert rec["max_ulp"] == 1 and rec["argmax_coord"] == 1
    # One impl far off the oracle while the other sits on it: the
    # accuracy asymmetry is named, not averaged away.
    far = o32.copy()
    far[0] = o32[0] * np.float32(1.5)
    assert N.adjudicate(o32, far, oracle)["verdict"] == "a_closer"
    assert N.adjudicate(far, o32, oracle)["verdict"] == "b_closer"
    # Each impl wrong on a different coordinate -> split.
    a = o32.copy()
    a[2] = o32[2] * np.float32(1.5)
    assert N.adjudicate(a, far, oracle)["verdict"] == "split"


# ---------------------------------------------------------------------------
# seam contracts: numerics-off HLO identity, kernel + config rejections

def test_numerics_off_is_hlo_identical():
    """numerics=False must be a trace-time no-op: the lowered program
    is byte-identical to one that never mentions the kwarg (the
    engine-level twin is tools/perf_gate.py --numproof)."""
    n, d, f = 12, 40, 2
    spec = jax.ShapeDtypeStruct((n, d), jnp.float32)
    for fn in (
        lambda kw: jax.jit(lambda g: krum(g, n, f, telemetry=True, **kw)),
        lambda kw: jax.jit(lambda g: trimmed_mean(g, n, f, telemetry=True,
                                                  **kw)),
        lambda kw: jax.jit(lambda g: median(g, n, f, telemetry=True,
                                            **kw)),
        lambda kw: jax.jit(lambda g: bulyan(g, n, f, telemetry=True,
                                            **kw)),
    ):
        base = fn({}).lower(spec).as_text()
        off = fn({"margins": False, "numerics": False}).lower(
            spec).as_text()
        assert base == off


def test_kernel_numerics_require_margins():
    """Kernel tie counters band the PR 18 margin tensors; numerics
    without margins has nothing to band and is a caller bug."""
    G = _grads()
    for call in (
        lambda: krum(G, 12, 2, telemetry=True, numerics=True),
        lambda: trimmed_mean(G, 12, 2, telemetry=True, numerics=True),
        lambda: median(G, 12, 2, telemetry=True, numerics=True),
        lambda: bulyan(G, 12, 2, telemetry=True, numerics=True),
    ):
        with pytest.raises(ValueError, match="requires margins"):
            call()


def test_kernel_numerics_fields():
    """With margins on, each margin-bearing kernel returns its num_*
    counters next to (never inside) the margin fields."""
    G = _grads(12, 40, seed=9)
    _, diag = krum(G, 12, 2, telemetry=True, margins=True, numerics=True)
    for f in ("num_tie_rows", "num_cancel_bits"):
        assert f in diag
    assert float(diag["num_cancel_bits"]) >= 0.0
    _, diag = trimmed_mean(G, 12, 2, telemetry=True, margins=True,
                           numerics=True)
    assert "num_tie_rows" in diag


def test_config_rejects_host_impls_under_numerics():
    """--numerics on a margin-bearing defense shares --margins'
    on-device-impl requirement (the tie counters ride the margin
    tensors); on any other defense only the defense-agnostic stage
    counters run and no constraint applies."""
    for knob, defense in (
        ("distance_impl", "Krum"),
        ("median_impl", "Median"),
        ("bulyan_selection_impl", "Bulyan"),
    ):
        with pytest.raises(ValueError, match=knob):
            ExperimentConfig(numerics=True, defense=defense,
                             **{knob: "host"})
    # Stage counters compose with everything else.
    ExperimentConfig(numerics=True, defense="Krum")
    ExperimentConfig(numerics=True, defense="NoDefense")
    ExperimentConfig(numerics=True, defense="DnC")


# ---------------------------------------------------------------------------
# engine: the schema-v14 numerics event, all three engines

def test_flat_numerics_events(tmp_path):
    """--numerics alone emits one v14 numerics event per round with
    the full flat field set — and neither margin nor defense telemetry
    events ride along on the wire."""
    cfg = _cfg(tmp_path, defense="Krum")
    _, events = _run(cfg, "num_flat.jsonl")
    nev = _numerics_events(events)
    assert len(nev) == cfg.epochs
    for e in nev:
        assert e["v"] >= 14
        assert e["defense"] == "Krum"
        assert e["tie_band_ulps"] == N.TIE_BAND_ULPS
        for f in ("nonfinite_pre", "nonfinite_post", "nonfinite_agg",
                  "range_log2", "tie_rows", "cancel_bits",
                  "nonfinite_total", "tie_locked"):
            assert f in e, f
        assert e["nonfinite_total"] == 0
        assert e["tie_locked"] in (0, 1)
        assert e["range_log2"] >= 0.0
    assert not [e for e in events if e.get("kind") in
                ("margin", "defense")]


def test_flat_numerics_without_margin_defense(tmp_path):
    """On a defense with no margin tensors, only the defense-agnostic
    stage counters appear — no fabricated tie/cancellation numbers."""
    cfg = _cfg(tmp_path, defense="NoDefense")
    _, events = _run(cfg, "num_nodef.jsonl")
    nev = _numerics_events(events)
    assert len(nev) == cfg.epochs
    for e in nev:
        assert "nonfinite_pre" in e and "range_log2" in e
        assert "tie_rows" not in e and "cancel_bits" not in e


def test_hier_numerics_events(tmp_path):
    """Hierarchical rounds carry shard_/tier2_ tie and cancellation
    stacks on the same names, plus the defense-agnostic stage counters
    measured once at the engine level."""
    cfg = _cfg(tmp_path, defense="Krum", aggregation="hierarchical",
               megabatch=4, tier2_defense="Krum")
    _, events = _run(cfg, "num_hier.jsonl")
    nev = _numerics_events(events)
    assert len(nev) == cfg.epochs
    for e in nev:
        for f in ("shard_tie_rows", "shard_cancel_bits",
                  "tier2_tie_rows", "tier2_cancel_bits",
                  "nonfinite_post", "nonfinite_agg", "range_log2",
                  "nonfinite_total", "tie_locked"):
            assert f in e, f


def test_async_numerics_events(tmp_path):
    cfg = _cfg(tmp_path, defense="Krum", aggregation="async",
               async_buffer=6, epochs=6)
    _, events = _run(cfg, "num_async.jsonl")
    nev = _numerics_events(events)
    assert len(nev) == cfg.epochs
    for e in nev:
        assert "tie_rows" in e and "nonfinite_pre" in e


# ---------------------------------------------------------------------------
# determinism + runs diff stage attribution (satellite: runs diff --band)

def test_same_seed_twins_are_bit_deterministic(tmp_path):
    """Two same-seed runs reproduce their numerics trajectory to the
    bit — the determinism bar runs diff enforces at band 0."""
    from attacking_federate_learning_tpu import runs_cli

    cfg = _cfg(tmp_path, defense="Krum")
    _, ev_a = _run(cfg, "num_twin_a.jsonl")
    _, ev_b = _run(cfg, "num_twin_b.jsonl")
    d = runs_cli.diff_trajectories(_numerics_events(ev_a),
                                   _numerics_events(ev_b), band=0)
    assert d["bit_identical"] is True
    assert d["divergence_round"] is None


def test_runs_diff_attributes_divergence_stage(tmp_path):
    """Two seeded twins whose attacks differ diverge in their numerics
    records; runs diff names the round, the pipeline stage and the f32
    ulp size of the first mismatch."""
    from attacking_federate_learning_tpu import runs_cli

    cfg = _cfg(tmp_path, defense="Krum")
    _, ev_a = _run(cfg, "num_div_a.jsonl", z=1.5)
    _, ev_b = _run(cfg, "num_div_b.jsonl", z=0.5)
    d = runs_cli.diff_trajectories(_numerics_events(ev_a),
                                   _numerics_events(ev_b), band=0)
    assert d["divergence_round"] is not None
    assert d["divergence_kind"] == "numerics"
    assert d["divergence_stage"] in ("deliver", "quarantine",
                                     "tier1_aggregate", "apply")
    assert d["divergence_anchor"] in d["divergence_fields"]
    assert d["divergence_ulp"] is not None and d["divergence_ulp"] > 0
    # The anchored field observes the stage the report names.
    assert N.stage_of(d["divergence_anchor"]) == d["divergence_stage"]
    # A band wide enough to cover the envelope reports clean.
    wide = runs_cli.diff_trajectories(
        _numerics_events(ev_a), _numerics_events(ev_a), band=4)
    assert wide.get("identical_within_band") is True


def test_stage_attribution_units():
    assert N.stage_of("nonfinite_pre") == "deliver"
    assert N.stage_of("nonfinite_post") == "quarantine"
    assert N.stage_of("tie_rows") == "tier1_aggregate"
    assert N.stage_of("shard_cancel_bits") == "tier1_aggregate"
    assert N.stage_of("tier2_tie_rows") == "tier2_aggregate"
    assert N.stage_of("nonfinite_agg") == "apply"
    assert N.stage_of("attack_z_used", kind="margin") == "deliver"
    assert N.stage_of("margin_gap", kind="margin") == "tier1_aggregate"
    # Attribution picks the largest comparable ulp as its anchor.
    stage, ulp, anchor = N.divergence_attribution(
        {"nonfinite_pre": [0, 0.0],            # 0 ulp
         "cancel_bits": [1.0, 1.5],            # large
         "tie_rows": [None, 2]})               # not comparable
    assert anchor == "cancel_bits" and stage == "tier1_aggregate"
    assert ulp == N.field_ulp(1.0, 1.5)
    # Nothing comparable: stage still attributes, ulp stays None.
    stage, ulp, anchor = N.divergence_attribution(
        {"tier2_tie_rows": [None, [1]]})
    assert stage == "tier2_aggregate" and ulp is None


# ---------------------------------------------------------------------------
# reader stack: rollups, series, drift, report

def test_numerics_rollups_units():
    r = N.numerics_rollups({"nonfinite_pre": 2, "nonfinite_post": 1.0,
                            "shard_nonfinite_agg": [1, 0, 3],
                            "tie_rows": 0, "cancel_bits": 40.0})
    assert r == {"nonfinite_total": 7, "tie_locked": 0}
    r = N.numerics_rollups({"shard_tie_rows": [0, 2, 0],
                            "nonfinite_agg": float("nan")})
    assert r == {"nonfinite_total": 0, "tie_locked": 1}
    r = N.numerics_rollups({"tier2_tie_rows": 1})
    assert r["tie_locked"] == 1


def test_numerics_series_and_drift():
    events = []
    for t, (tr, cb) in enumerate([(0, 10.0), (2, 12.0), (1, 11.0)]):
        events.append({"kind": "numerics", "round": t, "defense": "Krum",
                       "tie_rows": tr, "cancel_bits": cb,
                       "shard_tie_rows": [tr, tr + 1]})
    events.append({"kind": "eval", "round": 1})
    ser = N.numerics_series(events)
    assert ser["tie_rows"] == [(0, 0), (1, 2), (2, 1)]
    # Hier stacks reduce to their max — the conservative health view.
    assert ser["shard_tie_rows"] == [(0, 1), (1, 3), (2, 2)]
    other = N.numerics_series(
        [{"kind": "numerics", "round": t, "tie_rows": v}
         for t, v in [(0, 0), (1, 2), (2, 5)]])
    assert N.numerics_drift(ser, other, "tie_rows") == (2, 1, 5)
    assert N.numerics_drift(ser, ser, "tie_rows") is None


def test_report_numerics_summary(tmp_path):
    from attacking_federate_learning_tpu.report import numerics_summary

    cfg = _cfg(tmp_path, defense="Krum")
    _, events = _run(cfg, "num_report.jsonl")
    nm = numerics_summary(events)
    assert nm is not None
    assert nm["rounds"] == cfg.epochs
    assert nm["nonfinite_total"] == 0
    assert 0 <= nm["tie_locked_rounds"] <= cfg.epochs
    assert numerics_summary(
        [e for e in events if e.get("kind") != "numerics"]) is None


def test_runs_numerics_backend_reads_engine_events(tmp_path):
    """The numerics series loader digests a real engine stream the way
    the ``runs numerics`` verb renders it."""
    cfg = _cfg(tmp_path, defense="Median", epochs=4)
    _, events = _run(cfg, "num_runscli.jsonl")
    ser = N.numerics_series(events)
    assert ser
    assert len(ser["tie_rows"]) == cfg.epochs
    assert all(f in N.SERIES_FIELDS or
               f.split("_", 1)[1] in N.SERIES_FIELDS
               for f in ser)


# ---------------------------------------------------------------------------
# satellites: check_events --stats over a mixed-version log, trace track,
# the numerics gate's banding rules

def _load_tool(name):
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_events_stats_mixed_version_log(tmp_path):
    """One log holding v12 margin, v13 fault and v14 numerics rows —
    the realistic resumed-run file — validates cleanly and the stats
    histogram keeps the versions apart; a numerics kind stamped v13 is
    an emitter bug."""
    from attacking_federate_learning_tpu.utils.metrics import (
        validate_event
    )

    ce = _load_tool("check_events")
    p = tmp_path / "mixed.jsonl"
    rows = [
        {"kind": "margin", "round": 0, "defense": "Krum",
         "malicious_count": 2, "colluder_margin": -0.5, "v": 12,
         "t": 0.1},
        {"kind": "fault", "round": 0, "injected": 1, "v": 13, "t": 0.2},
        {"kind": "numerics", "round": 0, "defense": "Krum",
         "tie_rows": 0, "nonfinite_total": 0, "tie_locked": 0,
         "v": 14, "t": 0.3},
        {"kind": "numerics", "round": 1, "defense": "Krum",
         "tie_rows": 2, "nonfinite_total": 0, "tie_locked": 1,
         "v": 14, "t": 0.4},
        {"kind": "round", "round": 0, "v": 1, "t": 0.5},
    ]
    for r in rows:
        validate_event(r)
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    counts, legacy, errors = ce.check_file(str(p))
    assert not errors
    assert counts == {"margin": 1, "fault": 1, "numerics": 2,
                      "round": 1}
    stats = ce.file_stats(str(p))
    assert stats["numerics"] == {"count": 2, "versions": {14: 2}}
    assert stats["margin"]["versions"] == {12: 1}
    assert stats["fault"]["versions"] == {13: 1}
    # A numerics kind stamped with a pre-v14 version is an emitter bug.
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "numerics", "round": 0,
                               "defense": "Krum", "v": 13,
                               "t": 0.1}) + "\n")
    _, _, errors = ce.check_file(str(bad))
    assert errors


def test_trace_export_numerics_counter_track():
    from attacking_federate_learning_tpu.utils.trace_export import (
        events_to_trace, validate_trace
    )

    events = [
        {"kind": "numerics", "round": 0, "t": 0.1, "defense": "Krum",
         "nonfinite_total": 0, "tie_rows": 0, "cancel_bits": 12.5},
        {"kind": "numerics", "round": 1, "t": 0.2, "defense": "Krum",
         "nonfinite_total": 3, "tie_rows": 1, "cancel_bits": 40.0},
        # Hier stacks are lists; no scalar to draw, no point emitted.
        {"kind": "numerics", "round": 2, "t": 0.3, "defense": "Krum",
         "shard_tie_rows": [0, 1], "nonfinite_total": float("nan")},
    ]
    trace = events_to_trace(events)
    assert validate_trace(trace) == []
    pts = [e for e in trace["traceEvents"]
           if e.get("ph") == "C" and e["name"] == "numerics"]
    assert len(pts) == 2
    assert pts[0]["args"] == {"nonfinite_total": 0.0, "tie_rows": 0.0,
                              "cancel_bits": 12.5}
    assert pts[1]["args"]["nonfinite_total"] == 3.0


def test_numerics_gate_banding_rules():
    """The drift gate's diff logic: growth past the pinned ulp
    envelope, a verdict flip and an availability flip all fail; a
    shrinking envelope and an unchanged ledger pass."""
    ng = _load_tool("numerics_gate")

    def cell(max_ulp=2, verdict="tie_band"):
        return {"cohorts": {"drift": {"max_ulp": max_ulp,
                                      "n_mismatch": 1,
                                      "argmax_coord": 0,
                                      "in_tie_band": True,
                                      "verdict": verdict,
                                      "band_ulps": 8}}}

    base = {"Krum/topk": cell(), "Median/native_host": cell(0, "exact")}
    ok = ng.diff(base, {"Krum/topk": cell(),
                        "Median/native_host": cell(0, "exact")})
    assert not ok
    # Envelope growth fails; shrink passes.
    assert ng.diff(base, {"Krum/topk": cell(5),
                          "Median/native_host": cell(0, "exact")})
    assert not ng.diff(base, {"Krum/topk": cell(1),
                              "Median/native_host": cell(0, "exact")})
    # Verdict flip fails even inside the band.
    assert ng.diff(base, {"Krum/topk": cell(),
                          "Median/native_host": cell(0, "b_closer")})
    # Availability flip (a cell vanishing or erroring) fails.
    assert ng.diff(base, {"Krum/topk": cell()})
    assert ng.diff(base, {"Krum/topk": cell(),
                          "Median/native_host": {"cohorts": {
                              "drift": {"skipped": "impl unavailable"}}}})


def test_numerics_baseline_is_fresh():
    """The checked-in ledger matches this module's constants and holds
    the measured envelope classes the docs cite."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "NUMERICS_BASELINE.json")
    with open(path) as f:
        base = json.load(f)
    assert base["tie_band_ulps"] == N.TIE_BAND_ULPS
    cells = base["cells"]
    assert len(cells) >= 13
    for name, cell in cells.items():
        assert "/" in name
        for rec in cell["cohorts"].values():
            if "skipped" in rec:
                continue
            assert rec["verdict"] in ("exact", "tie_band", "a_closer",
                                      "b_closer", "split")
    # The pinned anchor facts: Krum's top-k twin is exact, and the
    # trimmed-mean variants sit in a small tie band.
    assert all(r["verdict"] == "exact"
               for r in cells["Krum/topk"]["cohorts"].values()
               if "skipped" not in r)
