#!/usr/bin/env python
"""Noise-banded wall-clock gate over measured stage walls.

tools/perf_gate.py deliberately refuses to gate wall time (static HLO
facts only) because raw stopwatch numbers on this box are noisy — one
shared core, background capture watchers, compile-cache state.  This
gate makes wall time gateable anyway by measuring it the way
utils/walls.py books it (per-stage op time from a profiler trace, not
one end-to-end stopwatch) and comparing MEDIANS over k repeats against
a checked-in ``WALL_BASELINE.json`` inside explicit noise bands:

    band_us(stage) = max(rel_band * base_median,
                         mad_mult * (base_MAD + cur_MAD),
                         floor_us)

- the k-repeat median discards scheduler hiccups in any single repeat;
- the MAD term widens the band when the stage is *measurably* noisy
  (either at baseline time or now) instead of guessing a tolerance;
- the relative band and the absolute floor keep tiny stages (sub-ms
  ``apply``) from failing on microsecond jitter.

Only regressions gate (current median above the band's upper edge);
getting faster prints a note.  Two absolute facts ride along, baseline
or not: the booked partition must be exact (WallRecord.check) and each
capture must actually contain op events — a capture with none means
the ``--xla_cpu_enable_xprof_traceme`` flag missed the first compile
and the "walls" would be vacuously green.

The baseline records its environment (jax/jaxlib version, platform,
cpu count) and provenance (k, rounds per repeat, cell set).  On a
mismatched environment wall numbers are meaningless, so the gate SKIPS
loudly with exit 0 unless ``--strict-env``; regenerate with
``--update`` after a toolchain or host change.

Usage:
    python tools/wall_gate.py                   # gate against baseline
    python tools/wall_gate.py --update          # (re)generate baseline
    python tools/wall_gate.py -k 5 --cells krum

Exit status: 0 clean (or env-skip), 1 on a regression / broken
partition / op-eventless capture, 2 when the baseline is missing.
tools/smoke.sh runs the self-consistency leg (fresh --update followed
by a gate against it in a temp dir).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BASELINE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "WALL_BASELINE.json")

# Pinned cells: one per engine family that owns a span entry point.
# Small enough that k repeats of ROUNDS rounds stay in CI time on CPU;
# the per-stage SHAPE (which stage dominates) is what the gate pins,
# not absolute throughput.
CELLS = {
    "krum": dict(defense="Krum"),
    "hier_krum": dict(defense="Krum", aggregation="hierarchical",
                      users_count=12, mal_prop=0.25, megabatch=4),
}

ROUNDS = 3          # rounds per traced repeat (one span call)
DEFAULT_K = 3

BAND = dict(rel_band=0.75, mad_mult=10.0, floor_us=25_000.0)

# An op-time fraction this low means the capture was mostly events the
# HLO join could not explain — the booking is untrustworthy, fail
# rather than gate noise against noise.
OP_TIME_FLOOR = 0.5


def environment() -> dict:
    import importlib.metadata as md

    import jax

    def _v(pkg):
        try:
            return md.version(pkg)
        except Exception:
            return "unknown"

    return {"jax": _v("jax"), "jaxlib": _v("jaxlib"),
            "platform": jax.devices()[0].platform,
            "cpus": os.cpu_count()}


def _median(vals):
    vals = sorted(vals)
    n = len(vals)
    if not n:
        return 0.0
    mid = n // 2
    return vals[mid] if n % 2 else 0.5 * (vals[mid - 1] + vals[mid])


def _mad(vals):
    med = _median(vals)
    return _median([abs(v - med) for v in vals])


def _pinned_experiment(overrides: dict):
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
    return FederatedExperiment(cfg, attacker=DriftAttack(1.5), dataset=ds)


def measure_cell(name: str, overrides: dict, k: int,
                 problems: list) -> dict:
    """k traced repeats of one ROUNDS-round span; returns the
    per-stage sample lists (us) plus booking diagnostics.  The warmup
    span compiles the program OUTSIDE any trace so repeat 0 measures
    execution, not compilation."""
    import jax

    from attacking_federate_learning_tpu.utils import walls
    from attacking_federate_learning_tpu.utils.profiling import (
        xla_trace
    )

    exp = _pinned_experiment(overrides)
    epoch = 0
    exp.run_span(epoch, ROUNDS)                       # warmup/compile
    jax.block_until_ready(exp.state.weights)
    epoch += ROUNDS
    samples: dict = {}
    fracs = []
    root = tempfile.mkdtemp(prefix=f"wallgate_{name}_")
    try:
        for rep in range(k):
            td = os.path.join(root, f"rep{rep}")
            with xla_trace(td):
                exp.run_span(epoch, ROUNDS)
                jax.block_until_ready(exp.state.weights)
            epoch += ROUNDS
            rec = walls.book_trace(
                td, exp._span_hlo_text(ROUNDS),
                name=exp._span_entry_name(),
                platform=jax.default_backend(), rounds=ROUNDS)
            if rec is None:
                problems.append(f"{name}[rep{rep}]: capture produced "
                                f"no trace file")
                continue
            rec.check()                               # exact partition
            cov = rec.coverage
            if cov["op_events"] == 0:
                problems.append(
                    f"{name}[rep{rep}]: 0 op events in the capture — "
                    f"the xprof-traceme flag missed the first compile "
                    f"of this process; nothing to gate")
                continue
            if cov["op_time_fraction"] < OP_TIME_FLOOR:
                problems.append(
                    f"{name}[rep{rep}]: op-time fraction "
                    f"{cov['op_time_fraction']:.2f} below the "
                    f"{OP_TIME_FLOOR} floor — booking untrustworthy")
            fracs.append(cov["op_time_fraction"])
            rows = dict(rec.stages)
            rows["unattributed"] = rec.unattributed_us
            for stage, us in rows.items():
                samples.setdefault(stage, []).append(float(us))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    out = {"entry": exp._span_entry_name(), "rounds": ROUNDS,
           "op_time_fraction": round(_median(fracs), 4) if fracs
           else 0.0,
           "stages": {}}
    for stage, vals in sorted(samples.items()):
        out["stages"][stage] = {
            "median_us": round(_median(vals), 3),
            "mad_us": round(_mad(vals), 3),
            "k": len(vals)}
    return out


def measure(cells, k: int, problems: list) -> dict:
    out = {}
    for name in cells:
        out[name] = measure_cell(name, CELLS[name], k, problems)
        stages = out[name]["stages"]
        top = max(stages, key=lambda s: stages[s]["median_us"]) \
            if stages else "-"
        print(f"  measured {name} ({out[name]['entry']}, k={k}): "
              + "  ".join(
                  f"{s}={v['median_us'] / 1e3:.1f}ms"
                  for s, v in stages.items())
              + f"  [top: {top}]")
    return out


def band_us(base: dict, cur_mad: float, cfg: dict) -> float:
    return max(cfg["rel_band"] * base["median_us"],
               cfg["mad_mult"] * (base["mad_us"] + cur_mad),
               cfg["floor_us"])


def diff(baseline: dict, measured: dict, band_cfg: dict) -> list:
    """Regression strings (empty = clean).  Only slower-than-band
    gates; a vanished stage or entry point gates too (the program
    family changed under the baseline)."""
    problems = []
    for cell, base in baseline.items():
        got = measured.get(cell)
        if got is None:
            problems.append(f"{cell}: cell not measured")
            continue
        if got["entry"] != base["entry"]:
            problems.append(
                f"{cell}: span entry point {got['entry']} != "
                f"baseline {base['entry']} (regenerate with --update)")
            continue
        for stage, want in base["stages"].items():
            have = got["stages"].get(stage)
            if have is None:
                # A stage present at baseline vanishing entirely is a
                # program change, not noise.
                problems.append(
                    f"{cell}.{stage}: stage present in baseline "
                    f"({want['median_us'] / 1e3:.1f} ms) but absent "
                    f"from the fresh capture")
                continue
            band = band_us(want, have["mad_us"], band_cfg)
            excess = have["median_us"] - (want["median_us"] + band)
            if excess > 0:
                problems.append(
                    f"{cell}.{stage}: median {have['median_us'] / 1e3:.1f}"
                    f" ms above baseline {want['median_us'] / 1e3:.1f} ms"
                    f" + band {band / 1e3:.1f} ms "
                    f"(over by {excess / 1e3:.1f} ms)")
            elif have["median_us"] + band < want["median_us"]:
                print(f"note wall_gate {cell}.{stage}: faster than the "
                      f"baseline band "
                      f"({have['median_us'] / 1e3:.1f} ms vs "
                      f"{want['median_us'] / 1e3:.1f} ms) — consider "
                      f"--update to tighten")
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Noise-banded measured-walls gate over pinned "
                    "small configs (utils/walls.py booking, k-repeat "
                    "median + MAD bands).")
    p.add_argument("--baseline", default=BASELINE)
    p.add_argument("--update", action="store_true",
                   help="write a fresh baseline instead of gating")
    p.add_argument("--cells", default=",".join(CELLS),
                   help="comma-separated subset of the pinned cells")
    p.add_argument("-k", "--repeats", type=int, default=DEFAULT_K,
                   help=f"traced repeats per cell (default "
                        f"{DEFAULT_K}; medians over these)")
    p.add_argument("--rel-band", type=float, default=BAND["rel_band"])
    p.add_argument("--mad-mult", type=float, default=BAND["mad_mult"])
    p.add_argument("--floor-us", type=float, default=BAND["floor_us"])
    p.add_argument("--strict-env", action="store_true",
                   help="treat a baseline/environment mismatch as a "
                        "failure instead of a skip")
    args = p.parse_args(argv)

    cells = [c.strip() for c in args.cells.split(",") if c.strip()]
    unknown = [c for c in cells if c not in CELLS]
    if unknown:
        print(f"unknown cells: {unknown} (known: {sorted(CELLS)})")
        return 2

    # Must land before the FIRST compile of this process — XLA parses
    # XLA_FLAGS exactly once.
    from attacking_federate_learning_tpu.utils.profiling import (
        ensure_op_profiling
    )
    ensure_op_profiling()

    band_cfg = dict(rel_band=args.rel_band, mad_mult=args.mad_mult,
                    floor_us=args.floor_us)
    env = environment()

    if args.update:
        problems: list = []
        measured = measure(cells, args.repeats, problems)
        if problems:
            print(f"FAIL wall_gate --update: {len(problems)} capture "
                  f"problem(s)")
            for prob in problems:
                print(f"  {prob}")
            return 1
        payload = {"env": env, "band": band_cfg, "k": args.repeats,
                   "rounds": ROUNDS, "cells": measured}
        with open(args.baseline, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.baseline} ({len(measured)} cells, "
              f"k={args.repeats}, jax {env['jax']}, {env['platform']})")
        return 0

    if not os.path.exists(args.baseline):
        print(f"no baseline at {args.baseline}; run with --update first")
        return 2
    with open(args.baseline) as f:
        base = json.load(f)
    benv = base.get("env", {})
    if benv != env:
        msg = (f"environment mismatch: baseline {benv} vs current "
               f"{env} — wall medians are only comparable within one "
               f"(jax, platform, host) tuple; regenerate with --update")
        if args.strict_env:
            print(f"FAIL wall_gate: {msg}")
            return 1
        print(f"SKIP wall_gate: {msg}")
        return 0

    problems = []
    measured = measure(cells, args.repeats, problems)
    baseline_cells = {c: v for c, v in base["cells"].items()
                      if c in cells}
    problems += diff(baseline_cells, measured, band_cfg)
    if problems:
        print(f"FAIL wall_gate: {len(problems)} problem(s)")
        for prob in problems:
            print(f"  {prob}")
        return 1
    nstages = sum(len(v["stages"]) for v in measured.values())
    print(f"ok   wall_gate: {len(cells)} cells, {nstages} stage "
          f"medians inside the noise bands (k={args.repeats}, "
          f"rel {args.rel_band:.0%} / MAD x{args.mad_mult:.0f} / "
          f"floor {args.floor_us / 1e3:.0f} ms)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
