"""Robustness-margin observatory (ISSUE 18).

Acceptance contract: every margin carries an exactness identity — a
row is Krum/Bulyan-selected iff its selection margin > 0 (one-sided at
exact f32 score ties), a row's trim survival mass is bit-equal to the
telemetry kept-fraction, the median pick masses reconstruct the
aggregate; margins-off programs stay HLO byte-identical (the kernel
seam here, all 41 perf_gate entry points in CI); every off-device
impl is rejected at config AND kernel level with a clear error; the
engine emits one schema-v12 ``margin`` event per round (flat,
hierarchical, async), joining traffic's ``f_eff`` when present; the
30-round Bulyan z=1.5 collapse shows its tie-locked margin signature;
and the rollup/series/drift helpers behind ``runs margins``,
``tools/check_events.py --stats`` and the trace counter track hold
their units.
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
from attacking_federate_learning_tpu.config import (
    ExperimentConfig, TrafficConfig
)
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.defenses.kernels import (
    bulyan, krum, trimmed_mean, trimmed_mean_of
)
from attacking_federate_learning_tpu.defenses.median import median
from attacking_federate_learning_tpu.utils import margins as M
from attacking_federate_learning_tpu.utils.metrics import RunLogger


def _grads(n=12, d=40, seed=0):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(size=(n, d)).astype(np.float32))


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 12)
    kw.setdefault("mal_prop", 0.2)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 6)
    kw.setdefault("test_step", 3)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("defense", "Krum")
    kw.setdefault("margins", True)
    kw.setdefault("log_dir", str(tmp_path / "logs"))
    kw.setdefault("run_dir", str(tmp_path / "runs"))
    return ExperimentConfig(**kw)


def _run(cfg, name):
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.5), dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name=name) as logger:
        exp.run(logger)
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    return exp, events


def _margin_events(events):
    return [e for e in events if e.get("kind") == "margin"]


# ---------------------------------------------------------------------------
# tentpole: per-kernel exactness identities

def test_krum_margin_identity():
    """Selected iff margin > 0 (continuous inputs don't tie), and the
    winner's margin IS the winner/runner-up gap."""
    G = _grads(11, 30)
    agg, diag = krum(G, 11, 2, telemetry=True, margins=True)
    sel = np.asarray(diag["selection_mask"])
    m = np.asarray(diag["margin_selection"])
    scores = np.sort(np.asarray(diag["scores"]))
    np.testing.assert_array_equal(m > 0, sel == 1.0)
    assert float(diag["margin_gap"]) == pytest.approx(
        float(scores[1] - scores[0]))
    assert float(m[np.argmax(sel)]) == pytest.approx(
        float(diag["margin_gap"]))


def test_krum_margin_identity_masked_weighted():
    """Dead rows report -inf margins and can't carry the identity;
    weights scale the aggregate but never the margins (selection is
    unweighted)."""
    G = _grads(11, 30, seed=3)
    mask = jnp.asarray(np.array([True] * 8 + [False] * 3))
    w = jnp.asarray(np.linspace(0.5, 1.5, 11).astype(np.float32))
    agg, diag = krum(G, 11, 2, telemetry=True, margins=True, mask=mask)
    aggw, diagw = krum(G, 11, 2, telemetry=True, margins=True, mask=mask,
                       weights=w)
    for d in (diag, diagw):
        m = np.asarray(d["margin_selection"])
        sel = np.asarray(d["selection_mask"])
        assert np.all(m[8:] == -np.inf)
        np.testing.assert_array_equal(m > 0, sel == 1.0)
    np.testing.assert_array_equal(np.asarray(diag["margin_selection"]),
                                  np.asarray(diagw["margin_selection"]))
    winner = int(np.argmax(np.asarray(diag["selection_mask"])))
    np.testing.assert_allclose(np.asarray(aggw),
                               np.asarray(agg) * float(w[winner]),
                               rtol=1e-6)


def test_trimmed_mean_margin_kept_frac_bit_equal():
    """margin_kept_frac (rank membership) is BIT-equal to the
    scatter-based telemetry kept_fraction — same keep set, same sum/d
    reduction."""
    G = _grads(13, 50, seed=1)
    _, diag = trimmed_mean(G, 13, 3, telemetry=True, margins=True)
    np.testing.assert_array_equal(np.asarray(diag["margin_kept_frac"]),
                                  np.asarray(diag["kept_fraction"]))
    # Boundary distance is inside-positive: fully-kept rows cannot sit
    # strictly outside the envelope everywhere.
    bd = np.asarray(diag["margin_boundary_dist"])
    assert np.isfinite(bd).all()


def test_trimmed_mean_margin_masked():
    """Dead rows: zero kept fraction, -inf boundary distance; alive
    rows keep e - f - 1 of the alive count."""
    G = _grads(12, 40, seed=2)
    mask = jnp.asarray(np.array([True] * 9 + [False] * 3))
    _, diag = trimmed_mean(G, 12, 2, telemetry=True, margins=True,
                           mask=mask)
    kf = np.asarray(diag["margin_kept_frac"])
    bd = np.asarray(diag["margin_boundary_dist"])
    assert np.all(kf[9:] == 0.0)
    assert np.all(bd[9:] == -np.inf)
    # 9 alive, keep 9 - 2 - 1 = 6 rows per coordinate.
    assert np.sum(kf) == pytest.approx(6.0, rel=1e-6)


def test_median_margin_reconstructs_aggregate():
    """The pick masses ARE the aggregate's rank membership: summing
    pick_mass * value per coordinate reproduces the median, unmasked
    and masked+weighted."""
    G = _grads(12, 40, seed=4)
    agg, diag = median(G, 12, 2, telemetry=True, margins=True)
    picks = M.median_pick_margins(G)
    np.testing.assert_array_equal(
        np.asarray(diag["margin_kept_frac"]),
        np.asarray(picks["margin_kept_frac"]))
    mask = jnp.asarray(np.array([True] * 9 + [False] * 3))
    w = jnp.asarray(np.linspace(0.5, 1.5, 12).astype(np.float32))
    aggw, diagw = median(G, 12, 2, telemetry=True, margins=True,
                         mask=mask, weights=w)
    # The weighted lower median picks exactly one row per coordinate
    # (mass 1.0), so the reconstruction is exact.
    alive = np.array([True] * 9 + [False] * 3)
    pick = M.median_pick_margins(G, mask=mask, weights=w)
    kf = np.asarray(pick["margin_kept_frac"])
    assert np.all(kf[~alive] == 0.0)
    recon = np.zeros(G.shape[1], np.float32)
    ranks_picked = 0
    vals = np.where(alive[:, None], np.asarray(G), np.inf)
    order = np.argsort(vals, axis=0)
    ranks = np.argsort(order, axis=0)
    wv = np.where(alive, np.asarray(w), 0.0)
    for j in range(G.shape[1]):
        col_w = wv[order[:, j]]
        cum = np.cumsum(col_w)
        pr = int(np.argmax(cum >= wv.sum() / 2.0))
        row = int(order[pr, j])
        recon[j] = vals[row, j]
        ranks_picked += 1
    np.testing.assert_array_equal(recon, np.asarray(aggw))
    assert np.all(np.asarray(diagw["margin_boundary_dist"])[~alive]
                  == -np.inf)


def test_bulyan_margin_identity():
    """Strictly positive margin implies selected; alive unselected
    rows sit at margin <= 0; trim survival lives only on selected
    rows."""
    G = _grads(15, 40, seed=5)
    _, diag = bulyan(G, 15, 2, telemetry=True, margins=True)
    m = np.asarray(diag["margin_selection"])
    sel = np.asarray(diag["selection_mask"])
    tk = np.asarray(diag["margin_trim_kept"])
    assert np.all(sel[m > 0] == 1.0)
    assert np.all(m[sel == 0.0] <= 0.0)
    assert np.all(tk[sel == 0.0] == 0.0)
    assert np.all(tk[sel == 1.0] > 0.0)
    # Trip slack vector covers every selection trip (q=1 -> set_size).
    assert np.asarray(diag["margin_slack"]).shape == (15 - 4,)


def test_bulyan_margin_identity_masked():
    G = _grads(15, 40, seed=6)
    mask = jnp.asarray(np.array([True] * 11 + [False] * 4))
    _, diag = bulyan(G, 15, 2, telemetry=True, margins=True, mask=mask)
    m = np.asarray(diag["margin_selection"])
    sel = np.asarray(diag["selection_mask"])
    assert np.all(m[11:] == -np.inf)
    assert np.all(sel[m > 0] == 1.0)
    alive_unsel = (np.arange(15) < 11) & (sel == 0.0)
    assert np.all(m[alive_unsel] <= 0.0)


# ---------------------------------------------------------------------------
# seam contracts: margins-off HLO identity, config + kernel rejections

def test_margins_off_is_hlo_identical():
    """margins=False must be a trace-time no-op: the lowered program
    is byte-identical to one that never mentions the kwarg (the
    engine-level twin is tools/perf_gate.py's 41-entry pin)."""
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
        off = fn({"margins": False}).lower(spec).as_text()
        assert base == off


def test_margins_require_telemetry():
    G = _grads()
    for call in (
        lambda: krum(G, 12, 2, margins=True),
        lambda: trimmed_mean(G, 12, 2, margins=True),
        lambda: median(G, 12, 2, margins=True),
        lambda: bulyan(G, 12, 2, margins=True),
    ):
        with pytest.raises(ValueError, match="requires telemetry"):
            call()


def test_host_impls_reject_margins():
    """Every off-device impl raises at the kernel: it returns only its
    aggregate, never the per-row tensors the margins read."""
    G = _grads()
    with pytest.raises(ValueError, match="on-device ranks"):
        trimmed_mean_of(G, 9, impl="host", telemetry=True, margins=True)
    with pytest.raises(ValueError, match="on-device ranks"):
        median(G, 12, 2, impl="host", telemetry=True, margins=True)
    with pytest.raises(ValueError, match="score-returning engine"):
        krum(G, 12, 2, distance_impl="host", telemetry=True, margins=True)
    with pytest.raises(ValueError, match="full-host engine"):
        bulyan(G, 12, 2, distance_impl="host", telemetry=True,
               margins=True)
    with pytest.raises(ValueError, match="selection_impl='host'"):
        bulyan(G, 12, 2, selection_impl="host", telemetry=True,
               margins=True)


def test_config_rejects_host_impls_and_non_margin_defenses():
    """--margins composition errors surface at config time, naming the
    offending knob."""
    with pytest.raises(ValueError, match="no selection/trim decision"):
        ExperimentConfig(margins=True, defense="NoDefense")
    for knob, defense in (
        ("trimmed_mean_impl", "TrimmedMean"),
        ("median_impl", "Median"),
        ("bulyan_trim_impl", "Bulyan"),
        ("distance_impl", "Krum"),
        ("bulyan_selection_impl", "Bulyan"),
    ):
        with pytest.raises(ValueError, match=knob):
            ExperimentConfig(margins=True, defense=defense,
                             **{knob: "host"})
    # The on-device impls compose.
    ExperimentConfig(margins=True, defense="Krum")


# ---------------------------------------------------------------------------
# engine: the schema-v12 margin event, all three engines + traffic

def test_flat_margin_events_without_telemetry(tmp_path):
    """--margins alone emits one v12 margin event per round carrying
    the colluder ledger — and NO defense telemetry events (margins is
    not a telemetry superset on the wire)."""
    cfg = _cfg(tmp_path, defense="TrimmedMean")
    exp, events = _run(cfg, "margins_flat.jsonl")
    mev = _margin_events(events)
    assert len(mev) == cfg.epochs
    for e in mev:
        assert e["v"] >= 12
        assert e["defense"] == "TrimmedMean"
        assert e["malicious_count"] == exp.m_mal
        assert "colluder_kept_mass" in e and "honest_kept_mass" in e
        assert "margin_kept_frac" in e
    assert not [e for e in events if e.get("kind") == "defense"]


def test_flat_margin_events_with_telemetry(tmp_path):
    """margins + telemetry: margin fields live ONLY in the margin
    event; the defense telemetry event keeps its pre-v12 shape."""
    cfg = _cfg(tmp_path, defense="Krum", telemetry=True)
    _, events = _run(cfg, "margins_tele.jsonl")
    mev = _margin_events(events)
    dev = [e for e in events if e.get("kind") == "defense"]
    assert mev and dev
    for e in dev:
        assert not any(k.startswith("margin_") for k in e)
        assert "selection_mask" in e
    for e in mev:
        assert "colluder_margin" in e
        assert "attack_z_used" in e    # DriftAttack envelope utilization


def test_hier_margin_events(tmp_path):
    """Hierarchical rounds carry per-shard margin stacks plus shard_/
    tier2_ rollups in one margin event."""
    cfg = _cfg(tmp_path, defense="Krum", users_count=12,
               aggregation="hierarchical", megabatch=4,
               tier2_defense="Krum", epochs=4)
    _, events = _run(cfg, "margins_hier.jsonl")
    mev = _margin_events(events)
    assert len(mev) == cfg.epochs
    for e in mev:
        assert "shard_margin_selection" in e
        assert "tier2_margin_selection" in e
        assert "shard_colluder_margin" in e
        assert "tier2_colluder_margin" in e


def test_async_margin_events_tolerate_empty_rounds(tmp_path):
    """FedBuff rounds make no fabricated numbers: a round without a
    decision carries a NaN gap, and a round whose delivered buffer
    holds no colluder simply omits the colluder margin (every
    malicious row's selection margin is non-finite — dead under the
    delivery mask)."""
    cfg = _cfg(tmp_path, defense="Krum", aggregation="async",
               async_buffer=6, epochs=8)
    exp, events = _run(cfg, "margins_async.jsonl")
    mev = _margin_events(events)
    assert mev
    finite = [e for e in mev if e.get("colluder_margin") is not None
              and math.isfinite(e["colluder_margin"])]
    assert finite, "no round ever delivered a colluder decision"
    for e in mev:
        if e.get("colluder_margin") is None:
            gap = e.get("margin_gap")
            sel = e.get("margin_selection")
            assert (gap is None or math.isnan(gap)
                    or (sel is not None
                        and not any(v is not None and math.isfinite(v)
                                    for v in sel[:exp.m_mal])))


def test_margin_events_join_traffic_f_eff(tmp_path):
    """Under --traffic-population the margin event carries the round's
    effective-f, bit-matching the v11 traffic event it rode with."""
    cfg = _cfg(tmp_path, defense="Krum", epochs=8,
               traffic=TrafficConfig(population=64, min_cohort=4,
                                     fallback_defense="Median"))
    _, events = _run(cfg, "margins_traffic.jsonl")
    mev = {e["round"]: e for e in _margin_events(events)}
    tev = {e["round"]: e for e in events if e.get("kind") == "traffic"}
    assert mev and tev
    joined = 0
    for r, e in mev.items():
        if r in tev:
            assert e["f_eff"] == tev[r]["f_eff"]
            joined += 1
    assert joined


# ---------------------------------------------------------------------------
# behavior: the 30-round Bulyan z=1.5 tie-locked collapse signature

def test_bulyan_margin_collapse_signature():
    """The IID z=1.5 collapse through the margin observatory
    (BEHAVIOR_BASELINE bulyan_margin_collapse): the colluder margin
    never goes positive, and most rounds are tie-locked at EXACTLY
    zero — identical crafted rows are score-degenerate, so a selected
    colluder's runner-up is its own twin and equal f32 scores subtract
    to an exact 0."""
    cfg = ExperimentConfig(
        dataset=C.SYNTH_MNIST_HARD, users_count=19, mal_prop=0.2,
        batch_size=64, epochs=30, test_step=30, seed=0,
        synth_train=4000, synth_test=1000, defense="Bulyan",
        num_std=1.5, margins=True)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=4000,
                      synth_test=1000)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.5), dataset=ds)
    cms = []
    for t in range(30):
        exp.run_round(t)
        mf = {k[len("defense_"):]: np.asarray(v)
              for k, v in exp.last_round_telemetry.items()
              if k.startswith("defense_margin_")}
        cms.append(M.margin_rollups(mf, exp.m_mal)["colluder_margin"])
    assert all(v <= 0.0 for v in cms)
    assert sum(1 for v in cms if v == 0.0) >= 20


# ---------------------------------------------------------------------------
# rollups / series / drift units (the runs-margins backend)

def test_margin_rollups_units():
    fields = {"margin_selection": [0.5, -1.0, -2.0, 0.25],
              "margin_trim_kept": [0.2, 0.0, 0.4, 0.6],
              "margin_gap": 0.75}
    r = M.margin_rollups(fields, 2)
    assert r["colluder_margin"] == -0.5
    assert r["colluder_selected"] == 1
    assert r["colluder_kept_mass"] == pytest.approx(0.1)
    assert r["honest_kept_mass"] == pytest.approx(0.5)
    assert r["margin_gap"] == 0.75
    # -inf (dead/rejected) rows never poison the ledger.
    r = M.margin_rollups({"margin_selection": [-np.inf, 0.5]}, 2)
    assert r["colluder_margin"] == -0.5


def test_tier2_margin_rollups_units():
    r = M.tier2_margin_rollups(
        {"margin_selection": [0.3, -0.2, -0.7],
         "margin_trim_kept": [1.0, 0.5, 0.0]},
        [True, False, True])
    assert r["colluder_margin"] == pytest.approx(-0.3)
    assert r["colluder_selected"] == 1
    assert r["colluder_kept_mass"] == pytest.approx(0.5)


def test_margin_series_and_drift():
    events = []
    for t, cm in enumerate([-0.1, 0.2, 0.3]):
        events.append({"kind": "margin", "round": t, "defense": "Krum",
                       "colluder_margin": cm, "f_eff": 2})
    events.append({"kind": "eval", "round": 1})
    ser = M.margin_series(events)
    assert list(ser) == ["Krum"]
    assert ser["Krum"]["round"] == [0, 1, 2]
    assert ser["Krum"]["colluder_margin"] == [-0.1, 0.2, 0.3]
    other = {"round": [0, 1, 2, 3],
             "colluder_margin": [-0.2, -0.2, 0.4, 0.1]}
    dr = M.margin_drift(ser["Krum"], other)
    assert dr["rounds"] == [0, 1, 2]
    assert dr["sign_flips"] == [1]
    np.testing.assert_allclose(dr["delta"], [-0.1, -0.4, 0.1])


def test_runs_margins_backend_reads_engine_events(tmp_path):
    """runs_cli's series loader digests a real margin stream."""
    from attacking_federate_learning_tpu import runs_cli

    cfg = _cfg(tmp_path, defense="Median", epochs=4)
    _, events = _run(cfg, "margins_runscli.jsonl")
    ser = runs_cli._margin_series_data(events)
    assert ser and "Median" in ser
    assert len(ser["Median"]["round"]) == cfg.epochs
    assert runs_cli._margin_series_data(
        [e for e in events if e.get("kind") != "margin"]) is None


# ---------------------------------------------------------------------------
# satellites: check_events --stats, trace counter track

def _load_tool(name):
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_events_validates_and_stats_margin_stream(tmp_path):
    from attacking_federate_learning_tpu.utils.metrics import (
        SCHEMA_VERSION, validate_event
    )

    ce = _load_tool("check_events")
    p = tmp_path / "margins.jsonl"
    rows = [
        {"kind": "margin", "round": 0, "defense": "Krum",
         "malicious_count": 2, "colluder_margin": -0.5,
         "v": SCHEMA_VERSION, "t": 0.1},
        {"kind": "round", "round": 0, "v": 1, "t": 0.2},
        {"kind": "margin", "round": 1, "defense": "Krum",
         "malicious_count": 2, "colluder_margin": 0.25,
         "v": SCHEMA_VERSION, "t": 0.3},
    ]
    for r in rows:
        validate_event(r)
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    counts, legacy, errors = ce.check_file(str(p))
    assert not errors and counts == {"margin": 2, "round": 1}
    stats = ce.file_stats(str(p))
    assert stats["margin"] == {"count": 2,
                               "versions": {SCHEMA_VERSION: 2}}
    assert stats["round"] == {"count": 1, "versions": {1: 1}}
    # A margin kind stamped with a pre-v12 version is an emitter bug.
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "margin", "round": 0,
                               "defense": "Krum", "v": 11,
                               "t": 0.1}) + "\n")
    _, _, errors = ce.check_file(str(bad))
    assert errors


def test_trace_export_margin_counter_track():
    from attacking_federate_learning_tpu.utils.trace_export import (
        events_to_trace, validate_trace
    )

    events = [
        {"kind": "margin", "round": 0, "t": 0.1, "defense": "Bulyan",
         "colluder_margin": -0.0},
        {"kind": "margin", "round": 1, "t": 0.2, "defense": "Bulyan",
         "colluder_margin": 0.4},
        # No decision this round: no counter point, not a NaN.
        {"kind": "margin", "round": 2, "t": 0.3, "defense": "Bulyan",
         "margin_gap": float("nan")},
    ]
    trace = events_to_trace(events)
    assert validate_trace(trace) == []
    pts = [e for e in trace["traceEvents"]
           if e.get("ph") == "C" and e["name"] == "colluder_margin"]
    assert [p["args"]["colluder_margin"] for p in pts] == [-0.0, 0.4]
