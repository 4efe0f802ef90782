#!/usr/bin/env python
"""Deterministic perf-regression gate: static HLO facts, no stopwatch.

CPU wall-clock is not a device metric and chip time is budgeted, so
this gate needs no timer: it replays
a pinned set of small configs, extracts each compiled entry point's
STATIC cost facts (utils/costs.py: cost_analysis FLOPs / bytes
accessed, memory_analysis buffer sizes) and diffs them against the
checked-in ``PERF_BASELINE.json``:

- ``flops`` / ``bytes_accessed`` / ``argument_bytes`` / ``output_bytes``
  must match EXACTLY — they are pure functions of (HLO, XLA version,
  platform), so any drift is a real change to the compiled program
  (e.g. a defense kernel growing a second distance computation);
- ``temp_bytes`` / ``peak_bytes`` compare within ``--tolerance``
  (default 5%) — buffer assignment may legally wiggle with scheduling.

The baseline records the environment it was generated in (jax/jaxlib
version, platform).  On a mismatched environment the comparison is
meaningless (XLA's cost model changed under us), so the gate SKIPS with
a loud notice and exit 0 unless ``--strict-env`` — regenerate with
``--update`` after a toolchain bump.

Usage:
    python tools/perf_gate.py                  # gate against baseline
    python tools/perf_gate.py --update         # (re)generate baseline
    python tools/perf_gate.py --cells krum,bulyan --tolerance 0.1

Exit status: 0 clean (or env-skip), 1 on any named regression, 2 when
the baseline is missing (run --update first).  CI-wired via
tests/test_costs.py next to the fault_matrix/check_events hooks;
tools/smoke.sh runs all three.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BASELINE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "PERF_BASELINE.json")

# The pinned cells: small enough to compile in CI time on CPU, wide
# enough to cover the cost-relevant program families — the O(n^2 d)
# distance defenses, the coordinate-wise sorts, the fused-vs-telemetry
# round programs, the plain mean, and the hierarchical (two-tier)
# streaming rounds (entry points hier_round/hier_span/tier2_*;
# core/engine.py aggregation='hierarchical').  Hierarchical cells
# override the base topology so both placement groups and the tier
# validity bounds (Bulyan m >= 4*f1+3) are exercised.
CELLS = {
    "nodefense": dict(defense="NoDefense"),
    "krum": dict(defense="Krum"),
    "trimmed_mean": dict(defense="TrimmedMean"),
    "bulyan": dict(defense="Bulyan"),
    "median": dict(defense="Median"),
    "krum_telemetry": dict(defense="Krum", telemetry=True),
    "hier_krum": dict(defense="Krum", aggregation="hierarchical",
                      users_count=12, mal_prop=0.25, megabatch=4),
    "hier_bulyan": dict(defense="Bulyan", aggregation="hierarchical",
                        users_count=24, mal_prop=0.125, megabatch=8,
                        tier2_defense="TrimmedMean"),
    # ISSUE 8: the hierarchical TELEMETRY cost cell — the telemetry
    # engine's hier_round / hier_tele_span with the per-shard + tier-2
    # diagnostics stacked through the scan, so the telemetry COST
    # gates like everything else.  The telemetry-OFF hot path is
    # pinned by the hier_krum/hier_bulyan cells above staying
    # byte-exact (telemetry is a trace-time flag; any residue in the
    # off path moves their FLOPs/bytes and fails the gate).
    "hier_krum_tele": dict(defense="Krum", aggregation="hierarchical",
                           users_count=12, mal_prop=0.25, megabatch=4,
                           telemetry=True),
}

EXACT = ("flops", "bytes_accessed", "argument_bytes", "output_bytes",
         "collective_bytes")
TOLERANT = ("temp_bytes", "peak_bytes")


def _ensure_virtual_devices(n: int = 8) -> None:
    """Raise the virtual CPU device count to n BEFORE backend init so
    the shardproof leg can build an 8-device mesh in a standalone run
    (same lazily-read XLA_FLAGS seam as __graft_entry__.py; a no-op
    when jax's backend already initialized — shardproof then checks
    the live device count and skips loudly if it is short)."""
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}")


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


def _pinned_experiment(overrides: dict):
    """The pinned small experiment every proof leg replays."""
    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.attacks import DriftAttack
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset

    base = dict(
        dataset=C.SYNTH_MNIST, users_count=11, mal_prop=0.2,
        batch_size=16, epochs=5, test_step=5, seed=0,
        synth_train=256, synth_test=64)
    base.update(overrides)   # hierarchical cells override the topology
    cfg = ExperimentConfig(**base)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=256, synth_test=64)
    return FederatedExperiment(cfg, attacker=DriftAttack(1.5), dataset=ds)


def measure_cell(name: str, overrides: dict) -> dict:
    """Build the pinned small experiment and return {entry: facts}."""
    exp = _pinned_experiment(overrides)
    ledger = exp.cost_report()
    if ledger.errors:
        msgs = "; ".join(f"{n}: {m}" for n, m in ledger.errors)
        raise RuntimeError(f"cell {name}: cost analysis failed ({msgs})")
    return ledger.summary()


# --- hierarchical memory proof (ISSUE 6 acceptance) --------------------
# Static, deterministic, baseline-free: at the 10k north star
# (n=10,240, d=79,510, m=512) the hierarchical round's peak-proxy bytes
# must be bounded by the MEGABATCH, not the cohort — the (n, d) gradient
# matrix (3.26 GB) and the (n, n) distance matrix (419 MB) must not
# exist in the program.  Two independent witnesses: the lowered HLO text
# contains no tensor of either shape, and memory_analysis' temp bytes
# stay under MEM_FACTOR * m * d * 4 (measured ~2.6x — scan double
# buffers + the per-megabatch distance/sort intermediates; 6x leaves
# scheduling slack while sitting 8x below the (n, d) wall).

MEMPROOF = dict(n=10_240, d=79_510, m=512, mem_factor=6.0)


def memproof() -> int:
    """Build the north-star hierarchical config, lower + compile ONE
    round, and gate its static memory facts.  Returns 0 clean, 1 on a
    violation.  No baseline: the bound is absolute (O(m*d)), so it
    cannot drift silently with --update."""
    import jax.numpy as jnp

    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset
    from attacking_federate_learning_tpu.utils.costs import (
        compiled_cost_facts
    )

    n, m = MEMPROOF["n"], MEMPROOF["m"]
    cfg = ExperimentConfig(
        dataset=C.SYNTH_MNIST, users_count=n, mal_prop=0.24,
        batch_size=1, epochs=5, test_step=5, seed=0, synth_train=n,
        synth_test=64, defense="Bulyan", aggregation="hierarchical",
        megabatch=m, tier2_defense="Bulyan", tier2_corrupted=4)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=n, synth_test=64)
    exp = FederatedExperiment(cfg, dataset=ds)
    d = exp.flat.dim
    assert d == MEMPROOF["d"], f"wire dim moved: {d}"
    lowered = exp._fused_round.lower(
        exp.data, exp.state, jnp.asarray(0, jnp.int32), None)
    text = lowered.as_text()
    problems = []
    for shape in (f"f32[{n},{d}]", f"bf16[{n},{d}]", f"f32[{n},{n}]"):
        if shape in text:
            problems.append(f"memproof: {shape} tensor present in the "
                            f"hierarchical round HLO — the cohort-sized "
                            f"array is back")
    facts = compiled_cost_facts(lowered.compile())
    bound = MEMPROOF["mem_factor"] * m * d * 4
    for metric in ("temp_bytes",):
        got = facts[metric]
        if got > bound:
            problems.append(
                f"memproof: {metric}={got / 1e6:.0f} MB exceeds the "
                f"O(m*d) bound {bound / 1e6:.0f} MB "
                f"({MEMPROOF['mem_factor']}x megabatch)")
    if problems:
        print(f"FAIL perf_gate --memproof: {len(problems)} violation(s)")
        for prob in problems:
            print(f"  {prob}")
        return 1
    print(f"ok   perf_gate memproof: hier_round @ n={n}, m={m}, d={d}: "
          f"temp={facts['temp_bytes'] / 1e6:.0f} MB <= "
          f"{bound / 1e6:.0f} MB (vs (n,d)={n * d * 4 / 1e6:.0f} MB); "
          f"no (n,d)/(n,n) tensor in the HLO; "
          f"flops={facts['flops']:.3e}")
    return wireproof()


# --- secagg structural proof (ISSUE 7 acceptance) ----------------------
# Baseline-free like the memproof: compile one --secagg vanilla round
# and gate its structural HLO facts (protocols/secagg.py
# wire_hlo_facts) — the masked u32 wire must exist (the optimization
# barrier kept the compiler from cancelling the protocol away), the
# server-side reconstruction of the per-client matrix may feed ONLY
# the cohort-sum reduce (no defense/sort/diagnostic reads per-client
# rows post-masking), and no (n, n) distance matrix may exist.

WIREPROOF = dict(n=19, batch=16)


def wireproof() -> int:
    import jax.numpy as jnp

    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.attacks import DriftAttack
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset
    from attacking_federate_learning_tpu.protocols.secagg import (
        wire_hlo_facts
    )

    n = WIREPROOF["n"]
    cfg = ExperimentConfig(
        dataset=C.SYNTH_MNIST, users_count=n, mal_prop=0.21,
        batch_size=WIREPROOF["batch"], epochs=5, test_step=5, seed=0,
        synth_train=256, synth_test=64, defense="NoDefense",
        secagg="vanilla")
    ds = load_dataset(cfg.dataset, seed=0, synth_train=256, synth_test=64)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.5), dataset=ds)
    text = exp._fused_round.lower(
        exp.data, exp.state, jnp.asarray(0, jnp.int32),
        None).compile().as_text()
    facts = wire_hlo_facts(text, n, exp.flat.dim)
    problems = []
    if not facts["wire_present"]:
        problems.append("wireproof: no u32 (n, d) wire tensor in the "
                        "vanilla-secagg round HLO — the masking was "
                        "compiled away")
    if not facts["unmask_reduce_only"]:
        problems.append(
            f"wireproof: the reconstructed per-client matrix has "
            f"non-reduce consumers "
            f"({facts['unmask_instructions']} unmask instruction(s)) — "
            f"a server-side op reads per-client rows post-masking")
    if facts["distance_matrix"]:
        problems.append("wireproof: an (n, n) distance matrix exists "
                        "under secagg — a pairwise defense ran over "
                        "per-client rows")
    if problems:
        print(f"FAIL perf_gate --memproof (secagg wireproof): "
              f"{len(problems)} violation(s)")
        for prob in problems:
            print(f"  {prob}")
        return 1
    print(f"ok   perf_gate wireproof: secagg-vanilla round @ n={n}: "
          f"u32 wire present, unmask feeds only the cohort-sum "
          f"reduce, no (n, n) distance matrix")
    return shardproof()


# --- hierarchical SPMD proof (ISSUE 12 acceptance) ---------------------
# Baseline-free like the memproof.  Three structural facts about the
# SPMD tier-1 mapping (ops/federated.py:_client_map_spmd), all provable
# on the 8-virtual-CPU-device mesh with no hardware:
#
# (a) scan-path fidelity: for EVERY pinned hierarchical cell, the
#     engine built on a 1-device clients axis produces an entry ledger
#     whose exact facts (FLOPs/bytes/args/outputs, collective bytes=0)
#     EQUAL the no-mesh scan path's — the mesh knobs must not perturb
#     the sequential program (its HLO differs only in the sharding-
#     propagation header any MeshPlan has always stamped);
# (b) the 8-device hier round is truly sharded: the compiled per-
#     device program holds NO full (n, d) / (S, m, d) / (n, n) tensor
#     (the "involuntary full rematerialization" seam is gone), and its
#     collective traffic is pinned to the explicit estimate all_gather
#     — within [1.0, 1.25]x of S*d*4 bytes;
# (c) sharded == unsharded: a 2-round SPMD run reproduces the scan
#     path's weights inside the measured ulp band (bit-equal on this
#     box; the tolerance covers GSPMD reduction reordering on others).

SHARDPROOF = dict(n=64, m=4, mesh_clients=8, coll_slack=1.25,
                  atol=2e-5)


def _hier_experiment(shardings, **overrides):
    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.attacks import DriftAttack
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset

    base = dict(
        dataset=C.SYNTH_MNIST, users_count=11, mal_prop=0.2,
        batch_size=16, epochs=5, test_step=5, seed=0,
        synth_train=256, synth_test=64)
    base.update(overrides)
    cfg = ExperimentConfig(**base)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=256, synth_test=64)
    return FederatedExperiment(cfg, attacker=DriftAttack(1.5), dataset=ds,
                               shardings=shardings)


def shardproof() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from attacking_federate_learning_tpu.parallel.mesh import make_plan
    from attacking_federate_learning_tpu.utils.costs import (
        compiled_cost_facts
    )

    if len(jax.devices()) < 8:
        print(f"SKIP perf_gate shardproof: needs 8 (virtual) devices, "
              f"have {len(jax.devices())} — the backend initialized "
              f"before the device-count flag could apply; run "
              f"tools/perf_gate.py standalone (it raises the count "
              f"itself) or under the test harness")
        return 0

    problems = []

    # (a) scan-path fidelity on a 1-device clients axis, per hier cell.
    plan1 = make_plan((1, 1), devices=jax.devices()[:1])
    hier_cells = sorted(c for c in CELLS if c.startswith("hier_"))
    for cell in hier_cells:
        ref = _hier_experiment(None, **CELLS[cell]).cost_report()
        got = _hier_experiment(plan1, **CELLS[cell]).cost_report()
        if ref.errors or got.errors:
            problems.append(f"shardproof[{cell}]: cost analysis failed "
                            f"({ref.errors + got.errors})")
            continue
        want, have = ref.summary(), got.summary()
        if set(want) != set(have):
            problems.append(
                f"shardproof[{cell}]: 1-device-mesh entry points "
                f"{sorted(have)} != scan path's {sorted(want)}")
            continue
        for entry, facts in want.items():
            for metric in EXACT:
                if have[entry].get(metric) != facts.get(metric):
                    problems.append(
                        f"shardproof[{cell}].{entry}.{metric}: "
                        f"1-device mesh {have[entry].get(metric)} != "
                        f"scan path {facts.get(metric)} — the mesh "
                        f"knobs changed the sequential program")
            if have[entry].get("collective_bytes"):
                problems.append(
                    f"shardproof[{cell}].{entry}: collective ops on a "
                    f"1-device mesh (the scan path grew a collective)")

    # (b) structural facts of the 8-device SPMD round.
    n, m = SHARDPROOF["n"], SHARDPROOF["m"]
    plan8 = make_plan((SHARDPROOF["mesh_clients"], 1))
    exp8 = _hier_experiment(
        plan8, users_count=n, mal_prop=0.25, defense="Krum",
        aggregation="hierarchical", megabatch=m)
    d, S = exp8.flat.dim, n // m
    compiled = exp8._fused_round.lower(
        exp8.data, exp8.state, jnp.asarray(0, jnp.int32), None).compile()
    text = compiled.as_text()
    for shape in (f"f32[{n},{d}]", f"bf16[{n},{d}]",
                  f"f32[{S},{m},{d}]", f"f32[{n},{n}]"):
        if shape in text:
            problems.append(
                f"shardproof: {shape} tensor present in the 8-device "
                f"hier round — a full cohort-sized array was "
                f"rematerialized")
    coll = compiled_cost_facts(compiled)["collective_bytes"]
    lo, hi = S * d * 4, SHARDPROOF["coll_slack"] * S * d * 4
    if not lo <= coll <= hi:
        problems.append(
            f"shardproof: collective bytes {coll} outside the O(S*d) "
            f"pin [{lo}, {hi:.0f}] — the estimate all_gather is "
            f"missing or a resharding collective crept in")

    # (c) sharded == unsharded inside the ulp band.
    if not problems:
        exp_ref = _hier_experiment(
            None, users_count=n, mal_prop=0.25, defense="Krum",
            aggregation="hierarchical", megabatch=m)
        for t in range(2):
            exp8.run_round(t)
            exp_ref.run_round(t)
        w8 = np.asarray(exp8.state.weights)
        wr = np.asarray(exp_ref.state.weights)
        diff = float(np.max(np.abs(w8 - wr)))
        if diff > SHARDPROOF["atol"]:
            problems.append(
                f"shardproof: sharded round diverged from the scan "
                f"path: max|diff|={diff:.3e} > {SHARDPROOF['atol']}")
    else:
        diff = float("nan")

    if problems:
        print(f"FAIL perf_gate --shardproof: {len(problems)} "
              f"violation(s)")
        for prob in problems:
            print(f"  {prob}")
        return 1
    print(f"ok   perf_gate shardproof: {len(hier_cells)} hier cells "
          f"1-device-mesh == scan path (exact facts, 0 collective "
          f"bytes); 8-device SPMD round @ n={n}, m={m}, d={d}: no "
          f"(n,d)/(S,m,d)/(n,n) tensor, collective bytes {coll} "
          f"~= S*d*4 ({S * d * 4}); sharded==unsharded to "
          f"max|diff|={diff:.1e}")
    return stageproof()


# --- stage-attribution proof (ISSUE 15 acceptance) ---------------------
# Baseline-free like the memproof.  The stage ledger (utils/costs.py:
# stage_attribution over the jax.named_scope stage names threaded through
# the engines) must hold three facts for EVERY pinned cell's compiled
# round program:
#
# (a) coverage: >= 95% of the modeled FLOP mass (and >= 85% of the
#     byte mass — the remainder is XLA-inserted layout copies that
#     carry no op metadata) books under a named stage;
# (b) exact partition: per metric, the six stage shares plus
#     ``unattributed`` sum to the whole-program cost_analysis total
#     EXACTLY (the split is of actuals, not of the model);
# (c) the annotation is metadata-only: a scopes-off twin of the same
#     cell compiles to an hlo_fingerprint-identical program (the
#     canonicalized, metadata-stripped hash) — checked on one cell per
#     program family to bound gate time.
#
# The wire ledger rides along: every hierarchical cell's
# tier1_to_tier2 seam must equal S*d*4 — the same number PR 12's
# shardproof pins as the 8-device all_gather's measured
# collective_bytes, which the 8-device leg below re-derives FROM the
# ledger (ledger <= measured <= 1.25x ledger).

STAGEPROOF = dict(flops_floor=0.95, bytes_floor=0.85, coll_slack=1.25,
                  fingerprint_cells=("krum", "hier_krum"))


def _round_compiled(exp):
    """Lower + compile the cell's round entry point (the program the
    gate pins as fused_round/hier_round/async_round)."""
    import jax.numpy as jnp

    t0 = jnp.asarray(0, jnp.int32)
    if exp._async is not None:
        return exp._fused_round.lower(
            exp.data, exp.state, t0, exp._async_state, None).compile()
    if exp.faults is not None:
        return exp._fused_round.lower(
            exp.data, exp.state, t0, exp._fault_state, None).compile()
    return exp._fused_round.lower(exp.data, exp.state, t0).compile()


def stageproof(cells=None) -> int:
    """Gate the stage/wire ledger facts over the pinned cells.
    Returns 0 clean, 1 on a violation.  No baseline: coverage floors,
    exact partition and the S*d*4 seam identity are absolute."""
    import math

    from attacking_federate_learning_tpu.utils.costs import (
        compiled_cost_facts, hlo_fingerprint, set_stage_scopes,
        stage_attribution
    )

    names = [c for c in CELLS if cells is None or c in cells]
    problems = []
    covs = []
    for name in names:
        exp = _pinned_experiment(CELLS[name])
        compiled = _round_compiled(exp)
        facts = compiled_cost_facts(compiled)
        att = stage_attribution(compiled.as_text(), facts)
        cov_f = att["coverage"]["flops"]
        cov_b = att["coverage"]["bytes_accessed"]
        f_floor = STAGEPROOF["flops_floor"]
        b_floor = STAGEPROOF["bytes_floor"]
        covs.append(cov_f)
        if cov_f < f_floor:
            problems.append(
                f"stageproof[{name}]: named-stage FLOP coverage "
                f"{cov_f:.1%} below the {f_floor:.0%} floor")
        if cov_b < b_floor:
            problems.append(
                f"stageproof[{name}]: named-stage byte coverage "
                f"{cov_b:.1%} below the {b_floor:.0%} floor")
        for metric, total in (("flops", facts.get("flops")),
                              ("bytes_accessed",
                               facts.get("bytes_accessed")),
                              ("temp_bytes", facts.get("temp_bytes"))):
            if total is None or total < 0:
                continue
            parts = [v[metric] for v in att["stages"].values()]
            parts.append(att["unattributed"][metric])
            got = math.fsum(parts)
            if not math.isclose(got, total, rel_tol=1e-9, abs_tol=1e-6):
                problems.append(
                    f"stageproof[{name}].{metric}: stage shares sum to "
                    f"{got} != whole-program total {total} — the "
                    f"partition is no longer exact")
        if not att["stages"]["tier1_aggregate"]["flops"] > 0:
            problems.append(
                f"stageproof[{name}]: tier1_aggregate attributed 0 "
                f"FLOPs — the defense-dispatch scope came unwired")
        hier = CELLS[name].get("aggregation") == "hierarchical"
        if hier:
            if not att["stages"]["tier2_aggregate"]["flops"] > 0:
                problems.append(
                    f"stageproof[{name}]: tier2_aggregate attributed "
                    f"0 FLOPs in a hierarchical cell — the "
                    f"shard_reduce scope came unwired")
            wire = exp.wire_ledger()
            S = exp._placement.num_shards
            want = S * exp.flat.dim * 4
            got = wire["seams"]["tier1_to_tier2"]["bytes"]
            if got != want:
                problems.append(
                    f"stageproof[{name}]: wire ledger tier1_to_tier2 "
                    f"{got} != S*d*4 = {want} — the ledger lost the "
                    f"PR-12 collective identity")
        if name in STAGEPROOF["fingerprint_cells"]:
            prev = set_stage_scopes(False)
            try:
                twin = _round_compiled(_pinned_experiment(CELLS[name]))
            finally:
                set_stage_scopes(prev)
            if (hlo_fingerprint(compiled.as_text())
                    != hlo_fingerprint(twin.as_text())):
                problems.append(
                    f"stageproof[{name}]: scopes-on round fingerprint "
                    f"!= scopes-off twin — the stage annotation is no "
                    f"longer metadata-only")

    # The measured SPMD cross-check: the 8-device hier round's
    # collective bytes must land inside [1.0, 1.25]x of the WIRE
    # LEDGER's tier1_to_tier2 seam (the ledger predicts the wire, the
    # compiler realizes it).
    import jax
    coll = None
    if len(jax.devices()) >= 8:
        from attacking_federate_learning_tpu.parallel.mesh import (
            make_plan
        )
        n, m = SHARDPROOF["n"], SHARDPROOF["m"]
        exp8 = _hier_experiment(
            make_plan((SHARDPROOF["mesh_clients"], 1)), users_count=n,
            mal_prop=0.25, defense="Krum", aggregation="hierarchical",
            megabatch=m)
        ledger_bytes = (exp8.wire_ledger()["seams"]["tier1_to_tier2"]
                        ["bytes"])
        coll = compiled_cost_facts(_round_compiled(exp8))[
            "collective_bytes"]
        if not (ledger_bytes <= coll
                <= STAGEPROOF["coll_slack"] * ledger_bytes):
            problems.append(
                f"stageproof: 8-device measured collective bytes "
                f"{coll} outside [1.0, "
                f"{STAGEPROOF['coll_slack']}]x the wire ledger's "
                f"tier1_to_tier2 seam {ledger_bytes}")
    else:
        print(f"note perf_gate stageproof: <8 devices "
              f"({len(jax.devices())}) — skipping the measured SPMD "
              f"wire cross-check (the per-cell ledger identity above "
              f"still gates)")

    if problems:
        print(f"FAIL perf_gate --stageproof: {len(problems)} "
              f"violation(s)")
        for prob in problems:
            print(f"  {prob}")
        return 1
    spmd = (f", 8-device collective {coll} within "
            f"{STAGEPROOF['coll_slack']}x the ledger seam"
            if coll is not None else "")
    print(f"ok   perf_gate stageproof: {len(names)} cells partition "
          f">= {STAGEPROOF['flops_floor']:.0%} of FLOPs into named "
          f"stages (min {min(covs, default=1.0):.1%})", end="")
    print(f", stage sums exact, "
          f"{len([c for c in names if c in STAGEPROOF['fingerprint_cells']])} "
          f"scopes-off twins fingerprint-identical, hier "
          f"tier1_to_tier2 == S*d*4{spmd}")
    return numproof()


# --- numerics-observatory proof (ISSUE 20 acceptance) ------------------
# Baseline-free like the memproof.  The numerics observatory
# (utils/numerics.py counters threaded via cfg.numerics) must be a
# pure trace-time observer:
#
# (a) kernel twin: each margin-bearing defense kernel jitted with NO
#     observatory kwargs lowers to HLO text byte-identical to the
#     explicit margins=False, numerics=False spelling — the kwargs
#     leave zero residue when off (the off-path COST identity across
#     all 41 baseline entry points is pinned by the main gate, which
#     chains into this proof);
# (b) behavioral twin: a numerics-ON pinned experiment reaches
#     bit-identical weights to its numerics-OFF twin — the counters
#     observe the round, they never steer it.

NUMPROOF = dict(rounds=3, cells=("krum", "hier_krum"))


def numproof() -> int:
    """Gate the numerics-observatory observer facts.  Returns 0
    clean, 1 on a violation.  No baseline: HLO-text identity and
    weight bit-identity are absolute."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from attacking_federate_learning_tpu.defenses.kernels import (
        bulyan, krum, trimmed_mean
    )
    from attacking_federate_learning_tpu.defenses.median import median

    problems = []
    G = jnp.zeros((12, 32), jnp.float32)
    kernels = {
        "krum": (lambda g: krum(g, 12, 2, telemetry=True),
                 lambda g: krum(g, 12, 2, telemetry=True,
                                margins=False, numerics=False)),
        "trimmed_mean": (
            lambda g: trimmed_mean(g, 12, 2, telemetry=True),
            lambda g: trimmed_mean(g, 12, 2, telemetry=True,
                                   margins=False, numerics=False)),
        "median": (lambda g: median(g, 12, 2, telemetry=True),
                   lambda g: median(g, 12, 2, telemetry=True,
                                    margins=False, numerics=False)),
        "bulyan": (lambda g: bulyan(g, 12, 2, telemetry=True),
                   lambda g: bulyan(g, 12, 2, telemetry=True,
                                    margins=False, numerics=False)),
    }
    for name, (bare, explicit) in kernels.items():
        t_bare = jax.jit(bare).lower(G).as_text()
        t_off = jax.jit(explicit).lower(G).as_text()
        if t_bare != t_off:
            problems.append(
                f"numproof[{name}]: margins=False, numerics=False "
                f"lowers to different HLO than the bare call — the "
                f"observatory kwargs leave residue when off")

    for cell in NUMPROOF["cells"]:
        exp_off = _pinned_experiment(CELLS[cell])
        exp_on = _pinned_experiment({**CELLS[cell], "numerics": True})
        for t in range(NUMPROOF["rounds"]):
            exp_off.run_round(t)
            exp_on.run_round(t)
        w_off = np.asarray(exp_off.state.weights)
        w_on = np.asarray(exp_on.state.weights)
        if not np.array_equal(w_off.view(np.uint32),
                              w_on.view(np.uint32)):
            bad = int(np.sum(w_off.view(np.uint32)
                             != w_on.view(np.uint32)))
            problems.append(
                f"numproof[{cell}]: numerics-ON weights diverged from "
                f"the OFF twin after {NUMPROOF['rounds']} rounds "
                f"({bad} coords differ) — the counters steered the "
                f"round")

    if problems:
        print(f"FAIL perf_gate --numproof: {len(problems)} "
              f"violation(s)")
        for prob in problems:
            print(f"  {prob}")
        return 1
    print(f"ok   perf_gate numproof: {len(kernels)} kernel twins "
          f"HLO-text identical with the observatory kwargs off, "
          f"{len(NUMPROOF['cells'])} numerics-ON cells bit-identical "
          f"to their OFF twins over {NUMPROOF['rounds']} rounds")
    return 0


def measure(cells) -> dict:
    out = {}
    for name in cells:
        out[name] = measure_cell(name, CELLS[name])
        print(f"  measured {name}: "
              + "  ".join(f"{e}={f['flops']:.3e}f"
                          for e, f in out[name].items()))
    return out


def diff(baseline: dict, measured: dict, tolerance: float) -> list:
    """Returns a list of '<cell>.<entry>.<metric>: ...' regression
    strings (empty = clean).  Missing/extra entries are regressions
    too — a silently vanished entry point must not pass the gate."""
    problems = []
    for cell, entries in baseline.items():
        if cell not in measured:
            problems.append(f"{cell}: cell not measured")
            continue
        got_entries = measured[cell]
        for entry, want in entries.items():
            got = got_entries.get(entry)
            if got is None:
                problems.append(f"{cell}.{entry}: entry point missing "
                                f"from the measured ledger")
                continue
            for metric in EXACT:
                if got.get(metric) != want.get(metric):
                    problems.append(
                        f"{cell}.{entry}.{metric}: measured "
                        f"{got.get(metric)} != baseline "
                        f"{want.get(metric)} (exact-match metric)")
            for metric in TOLERANT:
                w, g = want.get(metric), got.get(metric)
                if w in (None, 0):
                    if g != w:
                        problems.append(
                            f"{cell}.{entry}.{metric}: measured {g} != "
                            f"baseline {w}")
                    continue
                rel = abs(g - w) / abs(w)
                if rel > tolerance:
                    problems.append(
                        f"{cell}.{entry}.{metric}: measured {g} vs "
                        f"baseline {w} ({100 * rel:.1f}% > "
                        f"{100 * tolerance:.0f}% tolerance)")
        for entry in got_entries:
            if entry not in entries:
                problems.append(f"{cell}.{entry}: new entry point not in "
                                f"baseline (regenerate with --update)")
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Deterministic (static-HLO) perf-regression gate "
                    "over pinned small configs (utils/costs.py).")
    p.add_argument("--baseline", default=BASELINE)
    p.add_argument("--update", action="store_true",
                   help="write a fresh baseline instead of gating")
    p.add_argument("--cells", default=",".join(CELLS),
                   help="comma-separated subset of the pinned cells")
    p.add_argument("--tolerance", type=float, default=0.05,
                   help="relative tolerance for the memory metrics "
                        "(FLOPs/bytes are always exact)")
    p.add_argument("--strict-env", action="store_true",
                   help="treat a baseline/environment mismatch as a "
                        "failure instead of a skip")
    p.add_argument("--memproof", action="store_true",
                   help="additionally run the hierarchical O(m*d) "
                        "memory proof at the 10k north star, the "
                        "secagg-vanilla wire proof, the hierarchical "
                        "SPMD shard proof and the stage/wire-ledger "
                        "proof (absolute structural facts, no "
                        "baseline; tools/smoke.sh leg 4 runs all four)")
    p.add_argument("--shardproof", action="store_true",
                   help="run ONLY the hierarchical SPMD shard proof "
                        "(ISSUE 12): every pinned hier cell on a "
                        "1-device clients axis matches the scan "
                        "path's exact cost facts, the 8-virtual-"
                        "device SPMD round holds no full "
                        "(n,d)/(S,m,d)/(n,n) tensor, its collective "
                        "bytes pin to the O(S*d) estimate "
                        "all_gather, and sharded==unsharded inside "
                        "the ulp band")
    p.add_argument("--stageproof", action="store_true",
                   help="run ONLY the stage/wire-ledger proof "
                        "(ISSUE 15): every pinned cell's round "
                        "partitions >= 95% of FLOPs into the named "
                        "stage set with exact sums, the stage "
                        "annotation is metadata-only (scopes-off "
                        "twin fingerprints match), and the "
                        "hierarchical wire ledger's tier1_to_tier2 "
                        "seam equals S*d*4 (honors --cells)")
    p.add_argument("--numproof", action="store_true",
                   help="run ONLY the numerics-observatory proof "
                        "(ISSUE 20): every margin-bearing kernel's "
                        "bare call lowers to HLO text identical to "
                        "the explicit margins=False, numerics=False "
                        "spelling, and numerics-ON pinned cells "
                        "reach bit-identical weights to their OFF "
                        "twins (the counters observe, never steer)")
    args = p.parse_args(argv)

    # The shard proof needs an 8-device mesh; the flag must land
    # before the first jax.devices() in this process (lazy backend
    # init) — harmless for every other leg (single-device jits cost
    # the same whatever the visible device count; the checked-in
    # baseline is verified under both 1- and 8-device envs by
    # tools/smoke.sh and tests/test_costs.py).
    _ensure_virtual_devices()

    if args.shardproof and not args.memproof:
        return shardproof()
    if args.numproof and not args.memproof:
        return numproof()

    cells = [c.strip() for c in args.cells.split(",") if c.strip()]
    unknown = [c for c in cells if c not in CELLS]
    if unknown:
        print(f"unknown cells: {unknown} (known: {sorted(CELLS)})")
        return 2

    if args.stageproof and not args.memproof:
        return stageproof(cells)

    env = environment()
    if args.update:
        measured = measure(cells)
        payload = {"env": env, "tolerance": args.tolerance,
                   "cells": measured}
        with open(args.baseline, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.baseline} "
              f"({sum(len(v) for v in measured.values())} entry points, "
              f"jax {env['jax']}, {env['platform']})")
        return memproof() if args.memproof else stageproof(cells)

    if not os.path.exists(args.baseline):
        print(f"no baseline at {args.baseline}; run with --update first")
        return 2
    with open(args.baseline) as f:
        base = json.load(f)
    benv = base.get("env", {})
    if benv != env:
        msg = (f"environment mismatch: baseline {benv} vs current {env} "
               f"— static cost facts are only comparable within one "
               f"(jax, platform) pair; regenerate with --update")
        if args.strict_env:
            print(f"FAIL perf_gate: {msg}")
            return 1
        print(f"SKIP perf_gate: {msg}")
        return 0

    baseline_cells = {c: v for c, v in base["cells"].items() if c in cells}
    measured = measure(cells)
    problems = diff(baseline_cells, measured, args.tolerance)
    if problems:
        print(f"FAIL perf_gate: {len(problems)} regression(s)")
        for prob in problems:
            print(f"  {prob}")
        return 1
    n = sum(len(v) for v in measured.values())
    print(f"ok   perf_gate: {len(cells)} cells, {n} entry points match "
          f"the baseline (FLOPs/bytes exact, memory within "
          f"{100 * args.tolerance:.0f}%)")
    return memproof() if args.memproof else stageproof(cells)


if __name__ == "__main__":
    raise SystemExit(main())
