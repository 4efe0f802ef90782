#!/usr/bin/env python
"""Deterministic behavioral-drift gate: the science, machine-checked.

tools/perf_gate.py pins the COMPILED PROGRAMS (static HLO cost facts);
nothing pins the BEHAVIOR — the defense x attack accuracy/ASR surface
that is the paper's entire contribution.  Through PR 4 that baseline
lived in hand-maintained tables (PARITY.md, GRID_RESULTS.md) and in the
behavioral tests' generous directional margins; a constant drifting by
a few points (an attack z, a trim fraction, a selection quirk) could
slide through every margin and silently rewrite the science.

This gate replays a pinned set of SYNTH_MNIST_HARD defense x attack
cells — seeded, CPU, short-round, the same low-SNR dataset the
behavioral tests pin (tests/test_behavior.py; CLAUDE.md "behavioral
tuning facts") — and diffs final/max accuracy, backdoor ASR and Krum
selection concentration against the checked-in BEHAVIOR_BASELINE.json.

Tolerance policy (ARCHITECTURE.md "Run registry & science gate"):

- metrics with ``band == 0`` must match EXACTLY — an identical program
  on an identical (env, seed) replays bit-for-bit, so any drift is a
  real behavioral change;
- selection-mediated metrics carry a small MEASURED band: PR 4's
  ulp-tie adjudication (tests/test_distance_impl.py, bench.py
  adjudicate_f32_flip) showed Krum/Bulyan selections rest on f32
  near-ties where a legal compile-schedule change (reduction reorder,
  re-fusion) flips a pick at 1 ulp and the flip cascades into the
  trajectory.  Exact-match there would veto legal optimizations; the
  bands bound how far a legal flip was ever observed to move each
  metric.

The baseline records its environment (jax/jaxlib/platform); on a
mismatch the comparison is meaningless and the gate SKIPS with a loud
notice and exit 0 unless ``--strict-env`` (perf_gate's policy) —
regenerate with ``--update`` after a toolchain bump.

Usage:
    python tools/science_gate.py                   # gate
    python tools/science_gate.py --update          # (re)generate
    python tools/science_gate.py --cells krum_alie05,nodefense_clean
    python tools/science_gate.py --events logs/gate.jsonl   # v4 'gate'
                                                            # events

Exit status: 0 clean (or env-skip), 1 on any named cell.metric drift,
2 when the baseline is missing.  CI-wired via tools/smoke.sh leg 5 and
tests/test_science_gate.py (which exercises the diff on perturbed
measurements — the "a constant changed" failure mode — without paying
for cell replays).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BASELINE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BEHAVIOR_BASELINE.json")

# The pinned grid slice: the behavioral-test constants (n=19, ~21%
# malicious, batch 64 — ALIE strength depends on 1/sqrt(batch),
# CLAUDE.md) at gate-sized rounds.  Cells cover the mechanisms the
# paper's surface is made of: the clean baselines, the z-dependent ALIE
# split (z=0.5 defeats averaging AND Krum; z=1.5 degrades the
# coordinate-wise and Bulyan estimators), and backdoor ASR.
ROUNDS = 10
CELLS = {
    "nodefense_clean": dict(defense="NoDefense", attack=None),
    "nodefense_alie05": dict(defense="NoDefense", z=0.5),
    "krum_clean": dict(defense="Krum", attack=None, telemetry=True),
    "krum_alie05": dict(defense="Krum", z=0.5, telemetry=True),
    "krum_alie15": dict(defense="Krum", z=1.5, telemetry=True),
    "trimmedmean_alie15": dict(defense="TrimmedMean", z=1.5),
    "bulyan_alie15": dict(defense="Bulyan", z=1.5),
    "backdoor_trimmedmean": dict(defense="TrimmedMean", backdoor=True),
    # --- PR 7: the secure-aggregation scenario (protocols/secagg.py).
    # vanilla must replay the clear NoDefense cell bit-for-bit (the
    # protocol is behaviorally invisible — masking cancels exactly),
    # so its values double as a cross-cell invariant with
    # nodefense_alie05.  groupwise composes with the two-tier tree:
    # n=20/m=5 so the megabatch divides, tier-2 Krum over group sums
    # (selection-mediated -> banded like the krum cells).
    "secagg_vanilla_alie05": dict(defense="NoDefense", z=0.5,
                                  secagg="vanilla"),
    "secagg_groupwise_alie15": dict(defense="NoDefense", z=1.5, n=20,
                                    mal_prop=0.2, secagg="groupwise",
                                    aggregation="hierarchical",
                                    megabatch=5, tier2_defense="Krum"),
    # --- PR 8: hierarchical forensics (ISSUE 8 acceptance).  The
    # concentrated-placement Krum row from the round-6 science, now
    # pinned through the TELEMETRY path: n=20/m=5 packs all f=4
    # colluders into shard 0, tier-2 Krum must reject that shard's
    # estimate every round, and the forensics layer
    # (report.py:forensics_summary over the same shard_selection
    # stream a logged run emits) must return the 'localized' verdict
    # naming shard 0 — tier-2 rejection counts pinned, banded like
    # every selection-mediated cell.
    "hier_krum_conc_forensics": dict(defense="Krum", z=1.5, n=20,
                                     mal_prop=0.2,
                                     aggregation="hierarchical",
                                     megabatch=5,
                                     mal_placement="concentrated",
                                     telemetry=True),
    # --- PR 9: asynchronous buffered rounds (ISSUE 9, core/
    # async_rounds.py).  The behavioral-test constants under the
    # FedBuff regime: k=12 of n=19 aggregated per applied round,
    # staleness bound 2, poly weighting.  The clean NoDefense cell is
    # a pure deterministic replay (no selection anywhere — the FIFO
    # order is PRNG-fixed), band 0; the Krum×ALIE cell is
    # selection-mediated, banded like the sync krum cells.
    "async_nodefense_clean": dict(defense="NoDefense", attack=None,
                                  aggregation="async", async_buffer=12,
                                  async_max_staleness=2,
                                  staleness_weight="poly"),
    "async_krum_alie15": dict(defense="Krum", z=1.5,
                              aggregation="async", async_buffer=12,
                              async_max_staleness=2,
                              staleness_weight="poly"),
    # --- PR 17: population traffic (ISSUE 17, core/population.py).
    # The behavioral constants under sampled-cohort churn: each
    # round's 19 rows are drawn from a deliberately tight 24-client
    # registry at rate 0.5 (dwell-3 churn episodes), so the cohort
    # under-fills Krum's 2f+3 validity bound on some rounds and walks
    # the whole degradation ladder (7 remask / 2 TrimmedMean fallback
    # / 1 hold at these constants).  The schedule facts (arrived_mean,
    # degraded_rounds) replay exactly — the schedule is pure in
    # (TrafficConfig, seed, t) — band 0; the accuracy is
    # Krum-selection-mediated over a changing cohort, banded like the
    # other krum cells.
    "traffic_krum_churn": dict(defense="Krum", z=1.5,
                               traffic=dict(population=24, rate=0.5,
                                            churn_dwell=3,
                                            fallback_defense="TrimmedMean",
                                            seed=17)),
    # --- PR 18: robustness margins (ISSUE 18, utils/margins.py).  The
    # GRID round-5 Bulyan z=1.5 pair (19 clients, 20% malicious,
    # style_strength 0.5, 30 rounds — margins need the full study
    # length; the tie structure only breaks after convergence starts),
    # now pinned through the MARGIN observatory.  The MEASURED
    # mechanism, sharper than the working hypothesis of a simple sign
    # flip: under IID the identical crafted rows are score-degenerate,
    # so a selected colluder's runner-up is its own twin and the
    # colluder margin is EXACTLY zero (equal f32 scores subtract to
    # zero under any legal schedule) — the selection is tie-locked at
    # the decision boundary ~28/30 rounds, colluders are almost never
    # selected by a strictly positive margin (2 round-events), and
    # training collapses to ~10%.  Under femnist_style the honest
    # rows' per-client structure widens the cohort sigma, the crafted
    # cluster stops straddling the cut, and the tie-lock BREAKS from
    # ~round 19: strictly-signed margins appear and PERSIST (19/30 tie
    # rounds, 11 strict-selection events) while training converges —
    # the round-5 rescue, restated as the margin leaving the decision
    # boundary.  All margin metrics are selection-mediated (banded);
    # the collapse/rescue bands do not overlap.
    "bulyan_margin_collapse": dict(defense="Bulyan", z=1.5,
                                   mal_prop=0.2, margins=True,
                                   rounds=30),
    "bulyan_margin_rescue": dict(defense="Bulyan", z=1.5, mal_prop=0.2,
                                 margins=True, rounds=30,
                                 partition="femnist_style",
                                 style_strength=0.5),
    # --- PR 19: shard-domain faults in the hierarchical tree (ISSUE
    # 19, core/faults.py).  The behavioral constants under correlated
    # shard death: n=20/m=4 gives S=5 shards — exactly tier-2 Krum's
    # 2f+3 validity floor at f2=1, so every dead domain under-fills the
    # bound and the round walks the remask/fallback/hold ladder.  The
    # schedule facts (dead-domain rounds, shard-round deaths,
    # quarantine total, per-rung ladder counts) replay exactly — the
    # schedule is pure in (fault key, t) — band 0; the accuracy is
    # Krum-selection-mediated over a changing shard cohort, banded
    # like the other krum cells.
    "hier_krum_shard_dropout": dict(defense="Krum", z=1.5, n=20,
                                    mal_prop=0.2,
                                    aggregation="hierarchical",
                                    megabatch=4,
                                    faults=dict(dropout=0.1,
                                                shard_dropout=0.2,
                                                shard_dropout_dwell=2)),
}

# Per-metric tolerance bands (absolute; 0 = exact).  Authored here,
# recorded into the baseline at --update so the gate run states the
# policy it was compared under.  Rationale: mean/coordinate-wise paths
# with no data-dependent selection replay exactly; selection-mediated
# cells (Krum picks, Bulyan's select+trim, the backdoor's clip-envelope
# race) may legally move under a 1-ulp compile-schedule flip
# (tests/test_distance_impl.py::test_engine_bulyan_blockwise — the
# measured mechanism), so they carry bands sized generously below any
# real behavioral effect (the PARITY table's effects are tens of
# points).
DEFAULT_BANDS = {"final_accuracy": 0.0, "max_accuracy": 0.0}
CELL_BANDS = {
    "krum_clean": {"final_accuracy": 2.0, "max_accuracy": 2.0,
                   "top1_share": 0.1, "malicious_share": 0.05,
                   "distinct_winners": 2},
    "krum_alie05": {"final_accuracy": 3.0, "max_accuracy": 3.0,
                    "top1_share": 0.1, "malicious_share": 0.1,
                    "distinct_winners": 2},
    "krum_alie15": {"final_accuracy": 2.0, "max_accuracy": 2.0,
                    "top1_share": 0.1, "malicious_share": 0.05,
                    "distinct_winners": 2},
    "bulyan_alie15": {"final_accuracy": 5.0, "max_accuracy": 5.0},
    "trimmedmean_alie15": {"final_accuracy": 2.0, "max_accuracy": 2.0},
    "backdoor_trimmedmean": {"final_accuracy": 2.0, "max_accuracy": 2.0,
                             "final_asr": 5.0},
    # vanilla secagg is the NoDefense mean over a bit-identically
    # recovered matrix: no selection anywhere, so exact (band 0 via
    # DEFAULT_BANDS).  groupwise runs tier-2 Krum over group sums:
    # selection-mediated, same band family as the krum cells.
    "secagg_groupwise_alie15": {"final_accuracy": 2.0,
                                "max_accuracy": 2.0},
    # Forensics attribution: the localization VERDICT is pinned exact
    # (the colluder shard's estimate is the crafted vector itself —
    # no ulp tie to flip), the round counts and the tier-2 selection
    # mass carry small bands for the usual selection-mediated wiggle.
    "hier_krum_conc_forensics": {"final_accuracy": 5.0,
                                 "max_accuracy": 5.0,
                                 "localized": 0.0,
                                 "stabilized_round": 2.0,
                                 "mal_rejected_rounds": 2.0,
                                 "tier2_malicious_share": 0.05},
    # async_nodefense_clean is exact (band 0 via DEFAULT_BANDS): the
    # weighted mean + deterministic FIFO replay bit-for-bit.  The
    # async Krum cell is selection-mediated (delivered-cohort Krum
    # picks rest on the same f32 near-ties as the sync cells).
    "async_krum_alie15": {"final_accuracy": 3.0, "max_accuracy": 3.0},
    # Churned-cohort Krum: accuracy is selection-mediated (same ulp-tie
    # mechanism, now over per-round sampled rows); the schedule facts
    # are exact host replays (band 0 via the metric defaults).
    "traffic_krum_churn": {"final_accuracy": 3.0, "max_accuracy": 3.0},
    # Faulted-hierarchy Krum: accuracy is selection-mediated at BOTH
    # tiers (per-shard Krum over a quarantined cohort, tier-2 over the
    # survivors); the shard-domain schedule facts are exact host
    # replays (band 0 via the metric defaults).
    "hier_krum_shard_dropout": {"final_accuracy": 3.0,
                                "max_accuracy": 3.0},
    # Margin cells: every metric reads the f32 distance scores the
    # selections rest on, so all carry selection-mediated bands; the
    # DISCRIMINATORS (margin_tie_rounds 28 vs 19, band 3/4;
    # colluder_selected_total 2 vs 11, band 3/4) keep non-overlapping
    # bands, so a legal ulp flip cannot turn one cell into the other.
    "bulyan_margin_collapse": {"final_accuracy": 5.0,
                               "max_accuracy": 5.0,
                               "margin_tie_rounds": 3,
                               "colluder_margin_min": 1.2,
                               "colluder_margin_final": 0.05,
                               "margin_breached_rounds": 2,
                               "colluder_selected_total": 3},
    "bulyan_margin_rescue": {"final_accuracy": 5.0,
                             "max_accuracy": 5.0,
                             "margin_tie_rounds": 4,
                             "colluder_margin_min": 0.5,
                             "colluder_margin_final": 0.3,
                             "margin_breached_rounds": 2,
                             "colluder_selected_total": 4},
}


def environment() -> dict:
    import importlib.metadata as md

    import jax

    def _v(pkg):
        try:
            return md.version(pkg)
        except Exception:
            return "unknown"

    return {"jax": _v("jax"), "jaxlib": _v("jaxlib"),
            "platform": jax.devices()[0].platform}


def measure_cell(name: str, spec: dict, rounds: int = ROUNDS) -> dict:
    """Replay one pinned cell; returns {metric: value}.  Seeded,
    short-round, CPU-sized — the behavioral-test recipe
    (tests/conftest.py:hard_final_accuracy) at gate cadence."""
    import numpy as np

    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.attacks import (
        DriftAttack, NoAttack, make_attacker
    )
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset

    # A cell may pin its own length (the margin cells ride the GRID
    # round-5 30-round protocol — the tie structure they pin only
    # breaks after convergence starts); everything else runs at the
    # gate cadence.
    rounds = spec.get("rounds", rounds)
    backdoor = spec.get("backdoor", False)
    attacked = spec.get("attack", "alie") is not None or backdoor
    cfg = ExperimentConfig(
        dataset=C.SYNTH_MNIST_HARD, users_count=spec.get("n", 19),
        mal_prop=spec.get("mal_prop", 0.21 if attacked else 0.0),
        batch_size=64,
        epochs=rounds, test_step=max(1, rounds // 2), seed=0,
        synth_train=4000, synth_test=1000,
        defense=spec["defense"],
        num_std=spec.get("z", 1.5),
        backdoor="pattern" if backdoor else False,
        telemetry=bool(spec.get("telemetry")),
        secagg=spec.get("secagg", "off"),
        aggregation=spec.get("aggregation", "flat"),
        megabatch=spec.get("megabatch", 0),
        tier2_defense=spec.get("tier2_defense"),
        mal_placement=spec.get("mal_placement", "spread"),
        margins=bool(spec.get("margins")),
        partition=spec.get("partition", "iid"),
        style_strength=spec.get("style_strength", 0.25),
        async_buffer=spec.get("async_buffer", 0),
        async_max_staleness=spec.get("async_max_staleness", 2),
        staleness_weight=spec.get("staleness_weight", "none"),
        traffic=(C.TrafficConfig(**spec["traffic"])
                 if "traffic" in spec else None),
        faults=(C.FaultConfig(**spec["faults"])
                if "faults" in spec else None))
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    if backdoor:
        attacker = make_attacker(cfg, dataset=ds, name="backdoor")
    elif spec.get("attack", "alie") is None:
        attacker = NoAttack()
    else:
        attacker = DriftAttack(cfg.num_std)
    exp = FederatedExperiment(cfg, attacker=attacker, dataset=ds)

    accs, winners, shard_events, margin_rounds = [], [], [], []
    hier = cfg.aggregation == "hierarchical"
    eval_rounds = {t for t in range(rounds)
                   if t % cfg.test_step == 0 or t == rounds - 1}
    for t in range(rounds):
        exp.run_round(t)
        if cfg.margins and exp.last_round_telemetry is not None:
            # The colluder-survival rollup over the round's margin
            # fields — the same reduction the engine's v12 'margin'
            # event carries (utils/margins.py:margin_rollups).
            from attacking_federate_learning_tpu.utils.margins import (
                margin_rollups
            )
            mf = {k[len("defense_"):]: np.asarray(v)
                  for k, v in exp.last_round_telemetry.items()
                  if k.startswith("defense_margin_")}
            if mf:
                margin_rounds.append(margin_rollups(mf, exp.m_mal))
        if cfg.telemetry and exp.last_round_telemetry is not None:
            if hier:
                # Rebuild the round's 'shard_selection' payload the
                # engine would log (core/engine.py shares the static
                # fields), so the forensics verdict the gate pins is
                # computed by the SAME code path 'report forensics'
                # runs on a real event log.
                rec = {"kind": "shard_selection", "round": t,
                       **exp._shard_static_fields()}
                for k, v in exp.last_round_telemetry.items():
                    if k.startswith(("shard_", "tier2_")):
                        rec[k] = np.asarray(v).astype(float).tolist()
                shard_events.append(rec)
            else:
                mask = np.asarray(exp.last_round_telemetry.get(
                    "defense_selection_mask"))
                if mask.ndim == 1 and np.isfinite(mask).all():
                    winners.append(int(np.argmax(mask)))
        if t in eval_rounds:
            _, correct = exp.evaluate(exp.state.weights)
            accs.append(100.0 * float(correct) / len(ds.test_y))
    out = {"final_accuracy": round(accs[-1], 4),
           "max_accuracy": round(max(accs), 4)}
    if cfg.traffic is not None and cfg.traffic.enabled:
        # Schedule facts from the host replay (pure in config + t):
        # average arrived cohort and ladder-degraded round count.
        from attacking_federate_learning_tpu.core.population import (
            replay_traffic
        )

        tev = replay_traffic(cfg, rounds)
        out["arrived_mean"] = round(
            sum(e["arrived"] for e in tev) / len(tev), 4)
        out["degraded_rounds"] = sum(
            1 for e in tev if e["action"] != "remask")
    if cfg.faults is not None and hier:
        # Shard-domain schedule facts from the host replay (pure in
        # the fault key + t): dead-domain incidence, quarantine mass,
        # and the tier-2 ladder's per-rung round counts.
        from attacking_federate_learning_tpu.core.faults import (
            hier_fault_schedule, plan_tier2_actions
        )
        from attacking_federate_learning_tpu.core.population import (
            ACTION_NAMES
        )

        rows = hier_fault_schedule(exp._fault_key, 0, rounds,
                                   exp._placement, exp.faults)
        acts = plan_tier2_actions([r["shards_alive"] for r in rows],
                                  exp._tier2_name, exp._tier2_f)
        out["dead_domain_rounds"] = sum(
            1 for r in rows if r["shards_dead"] > 0)
        out["shard_deaths_total"] = sum(r["shards_dead"] for r in rows)
        out["quarantined_total"] = sum(r["quarantined"] for r in rows)
        for i, rung in enumerate(ACTION_NAMES):
            out[f"tier2_{rung}_rounds"] = int(np.sum(acts == i))
    if shard_events:
        from attacking_federate_learning_tpu.report import (
            forensics_summary
        )

        fx = forensics_summary(shard_events)
        loc, t2 = fx["localization"], fx.get("tier2", {})
        localized = loc.get("verdict") == "localized"
        out["localized"] = 1 if localized else 0
        out["stabilized_round"] = (loc.get("stabilized_round")
                                   if localized else -1)
        if "mal_rejected_rounds" in t2:
            out["mal_rejected_rounds"] = t2["mal_rejected_rounds"]
            out["tier2_malicious_share"] = t2["malicious_share"]
    if margin_rounds:
        cms = [r["colluder_margin"] for r in margin_rounds
               if r.get("colluder_margin") is not None]
        out["colluder_margin_min"] = round(float(min(cms)), 4)
        out["colluder_margin_final"] = round(float(cms[-1]), 4)
        out["margin_breached_rounds"] = sum(1 for v in cms if v <= 0)
        out["colluder_selected_total"] = int(sum(
            r.get("colluder_selected", 0) for r in margin_rounds))
        # The tie ledger the PR-18 acceptance pins: rounds where the
        # colluder margin sits EXACTLY at the selection cut (0.0 — a
        # selected colluder's runner-up is its identical twin, and
        # equal f32 scores subtract to an exact zero).  A collapse run
        # is tie-locked nearly every round; a rescue run breaks the
        # lock (strictly-signed margins appear and persist).
        out["margin_tie_rounds"] = sum(1 for v in cms if v == 0.0)
    if backdoor:
        out["final_asr"] = round(
            float(exp.attacker.test_asr(exp.state.weights)), 4)
    if winners:
        counts: dict = {}
        for w in winners:
            counts[w] = counts.get(w, 0) + 1
        top1 = max(counts.values())
        out["top1_share"] = round(top1 / len(winners), 4)
        out["distinct_winners"] = len(counts)
        out["malicious_share"] = round(
            sum(1 for w in winners if w < exp.m_mal) / len(winners), 4)
    return out


def bands_for(cell: str) -> dict:
    return {**DEFAULT_BANDS, **CELL_BANDS.get(cell, {})}


def measure(cells, rounds: int = ROUNDS) -> dict:
    out = {}
    for name in cells:
        t0 = time.time()
        vals = measure_cell(name, CELLS[name], rounds)
        out[name] = {m: {"value": v, "band": bands_for(name).get(m, 0.0)}
                     for m, v in vals.items()}
        print(f"  measured {name} ({time.time() - t0:.1f} s): "
              + "  ".join(f"{m}={v}" for m, v in vals.items()))
    return out


def diff(baseline: dict, measured: dict) -> list:
    """'<cell>.<metric>: ...' drift strings (empty = clean).  Bands come
    from the BASELINE (the policy in force when it was generated);
    missing cells/metrics are drifts too — a silently vanished metric
    must not pass the gate."""
    problems = []
    for cell, metrics in baseline.items():
        got_cell = measured.get(cell)
        if got_cell is None:
            problems.append(f"{cell}: cell not measured")
            continue
        for metric, want in metrics.items():
            got = got_cell.get(metric)
            if got is None:
                problems.append(f"{cell}.{metric}: metric missing from "
                                f"the measurement")
                continue
            w = want["value"]
            g = got["value"] if isinstance(got, dict) else got
            band = float(want.get("band", 0.0))
            if band == 0.0:
                if g != w:
                    problems.append(
                        f"{cell}.{metric}: measured {g} != baseline {w} "
                        f"(exact-match metric: this cell replays "
                        f"bit-deterministically)")
            elif abs(float(g) - float(w)) > band:
                problems.append(
                    f"{cell}.{metric}: measured {g} vs baseline {w} "
                    f"(|delta| {abs(float(g) - float(w)):.4g} > "
                    f"band {band} — beyond any legal ulp-tie flip)")
        for metric in got_cell:
            if metric not in metrics:
                problems.append(f"{cell}.{metric}: new metric not in "
                                f"baseline (regenerate with --update)")
    return problems


def emit_gate_events(path: str, cells: dict, problems: list,
                     status_all: str):
    """One v4 'gate' event per cell (utils/metrics.py schema) — the
    gate's verdict in the same stream every other rollup lives in."""
    from attacking_federate_learning_tpu.utils.metrics import (
        SCHEMA_VERSION, validate_event
    )

    bad_cells = {p.split(".", 1)[0].split(":", 1)[0] for p in problems}
    with open(path, "a") as f:
        for cell, metrics in cells.items():
            rec = {"kind": "gate", "cell": cell,
                   "status": "fail" if cell in bad_cells else status_all,
                   "v": SCHEMA_VERSION, "t": round(time.time(), 3)}
            for m, v in metrics.items():
                rec[m] = v["value"] if isinstance(v, dict) else v
            validate_event(rec)
            f.write(json.dumps(rec) + "\n")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Deterministic behavioral-drift gate over pinned "
                    "SYNTH_MNIST_HARD defense x attack cells.")
    p.add_argument("--baseline", default=BASELINE)
    p.add_argument("--update", action="store_true",
                   help="write a fresh baseline instead of gating")
    p.add_argument("--cells", default=",".join(CELLS),
                   help="comma-separated subset of the pinned cells")
    p.add_argument("--rounds", type=int, default=ROUNDS,
                   help="rounds per cell (changing this invalidates "
                        "the baseline; it is recorded there)")
    p.add_argument("--strict-env", action="store_true",
                   help="treat a baseline/environment mismatch as a "
                        "failure instead of a skip")
    p.add_argument("--events", default=None, metavar="JSONL",
                   help="append one v4 'gate' event per cell to this "
                        "run log")
    args = p.parse_args(argv)

    cells = [c.strip() for c in args.cells.split(",") if c.strip()]
    unknown = [c for c in cells if c not in CELLS]
    if unknown:
        print(f"unknown cells: {unknown} (known: {sorted(CELLS)})")
        return 2

    env = environment()
    if env["platform"] != "cpu":
        # The pinned cells are CPU replays by construction — a CI gate
        # never spends chip time.
        print(f"SKIP science_gate: backend is {env['platform']!r}, the "
              f"pinned cells are CPU replays (set JAX_PLATFORMS=cpu)")
        return 0 if not args.strict_env else 1

    if args.update:
        measured = measure(cells, args.rounds)
        payload = {"env": env, "rounds": args.rounds,
                   "generated": time.strftime("%Y-%m-%dT%H:%M:%S"),
                   "argv": list(argv or sys.argv[1:]),
                   "policy": "band 0 = exact (bit-deterministic "
                             "replay); band > 0 = measured ulp-tie "
                             "envelope (see module docstring)",
                   "cells": measured}
        with open(args.baseline, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.baseline} ({len(measured)} cells, "
              f"jax {env['jax']}, {env['platform']}, "
              f"{args.rounds} rounds)")
        return 0

    if not os.path.exists(args.baseline):
        print(f"no baseline at {args.baseline}; run with --update first")
        return 2
    with open(args.baseline) as f:
        base = json.load(f)
    benv = base.get("env", {})
    if benv != env or base.get("rounds") != args.rounds:
        msg = (f"environment mismatch: baseline "
               f"(env {benv}, rounds {base.get('rounds')}) vs current "
               f"(env {env}, rounds {args.rounds}) — behavioral "
               f"trajectories are only comparable within one (jax, "
               f"platform, rounds) tuple; regenerate with --update")
        if args.strict_env:
            print(f"FAIL science_gate: {msg}")
            return 1
        print(f"SKIP science_gate: {msg}")
        return 0

    baseline_cells = {c: v for c, v in base["cells"].items() if c in cells}
    measured = measure(cells, args.rounds)
    problems = diff(baseline_cells, measured)
    if args.events:
        emit_gate_events(args.events, measured, problems,
                         "fail" if problems else "pass")
    if problems:
        print(f"FAIL science_gate: {len(problems)} behavioral drift(s)")
        for prob in problems:
            print(f"  {prob}")
        return 1
    n = sum(len(v) for v in measured.values())
    print(f"ok   science_gate: {len(cells)} cells, {n} metrics match "
          f"BEHAVIOR_BASELINE.json (exact where bit-deterministic, "
          f"measured ulp-tie bands elsewhere)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
