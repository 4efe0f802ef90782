#!/usr/bin/env python
"""Numeric-drift gate over the cross-implementation divergence ledger.

tools/impl_drift.py measures, for every shipped impl pair of every
defense (xla / native / host, masked / weighted
variants, the scan-vs-sharded hier traversal), the f32 ulp envelope
between the pair on identical seeded cohorts plus an f64-adjudicated
verdict (defenses/oracle.py in double as referee).  This gate persists
that matrix into a checked-in ``NUMERICS_BASELINE.json`` and fails
when the numerics MOVE:

- **band exceeded**: a cell-cohort's measured ``max_ulp`` grows past
  its baseline envelope — an impl pair drifted apart (the PR 4
  bulyan-blockwise class: a reduction-order change that widens a
  1-ulp band into a selection flip);
- **verdict flip**: the f64-adjudicated verdict changes (e.g.
  ``tie_band`` -> ``split``, or an accuracy asymmetry inverts) — the
  pair's relationship to the double-precision truth changed even if
  the raw envelope did not;
- **availability flip**: a cell measured at baseline is skipped now
  (or the reverse) — an impl route appeared or vanished, which is a
  ledger fact, not noise.

Shrinking envelopes print a note (consider ``--update`` to tighten)
but never gate — only regressions fail.

Ulp envelopes are only comparable within one (jax, jaxlib, numpy,
platform) tuple, so on a baseline/environment mismatch the gate SKIPS
loudly with exit 0 unless ``--strict-env``; regenerate with
``--update`` after a toolchain change (provenance rides the file).

Usage:
    python tools/numerics_gate.py             # gate against baseline
    python tools/numerics_gate.py --update    # (re)generate baseline

Exit status: 0 clean (or env-skip), 1 on drift, 2 when the baseline is
missing.  tools/smoke.sh runs the self-consistency leg (fresh --update
followed by a gate against it in a temp dir); tools/perf_gate.py
--numproof separately pins that the in-jit numerics counters stay off
the numerics-off HLO.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BASELINE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "NUMERICS_BASELINE.json")


def environment() -> dict:
    import importlib.metadata as md

    import jax

    def _v(pkg):
        try:
            return md.version(pkg)
        except Exception:
            return "unknown"

    return {"jax": _v("jax"), "jaxlib": _v("jaxlib"),
            "numpy": _v("numpy"),
            "platform": jax.devices()[0].platform}


def diff(baseline_cells: dict, measured: dict) -> list:
    """Drift strings (empty = clean): band-exceeded, verdict-flip, or
    availability-flip per cell-cohort; a vanished cell gates too."""
    problems = []
    for cell, base in sorted(baseline_cells.items()):
        got = measured.get(cell)
        if got is None:
            problems.append(f"{cell}: cell not measured (variant "
                            f"removed? regenerate with --update)")
            continue
        for cname, want in sorted(base["cohorts"].items()):
            have = got["cohorts"].get(cname)
            if have is None:
                problems.append(f"{cell}[{cname}]: cohort missing from "
                                f"the fresh measurement")
                continue
            b_skip, h_skip = "skipped" in want, "skipped" in have
            if b_skip != h_skip:
                what = ("now skipped: " + have["skipped"][:60]
                        if h_skip else "now measurable")
                problems.append(
                    f"{cell}[{cname}]: impl availability flipped "
                    f"({what}) — regenerate with --update if intended")
                continue
            if b_skip:
                continue
            if have["max_ulp"] > want["max_ulp"]:
                problems.append(
                    f"{cell}[{cname}]: band exceeded — max_ulp "
                    f"{have['max_ulp']} > baseline envelope "
                    f"{want['max_ulp']} (mismatch "
                    f"{want['n_mismatch']}->{have['n_mismatch']} "
                    f"coords)")
            elif have["max_ulp"] < want["max_ulp"]:
                print(f"note numerics_gate {cell}[{cname}]: envelope "
                      f"shrank ({want['max_ulp']} -> "
                      f"{have['max_ulp']} ulp) — consider --update "
                      f"to tighten")
            if have["verdict"] != want["verdict"]:
                problems.append(
                    f"{cell}[{cname}]: verdict flip — "
                    f"{want['verdict']} -> {have['verdict']} "
                    f"(f64-adjudicated relationship changed)")
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Gate the cross-implementation ulp envelopes and "
                    "f64 verdicts against NUMERICS_BASELINE.json "
                    "(tools/impl_drift.py measurement).")
    p.add_argument("--baseline", default=BASELINE)
    p.add_argument("--update", action="store_true",
                   help="write a fresh baseline instead of gating")
    p.add_argument("--seed", type=int, default=None,
                   help="cohort seed (default: the baseline's; "
                        "impl_drift.SEED when updating)")
    p.add_argument("--strict-env", action="store_true",
                   help="treat a baseline/environment mismatch as a "
                        "failure instead of a skip")
    args = p.parse_args(argv)

    from tools import impl_drift
    from attacking_federate_learning_tpu.utils.numerics import (
        TIE_BAND_ULPS
    )

    env = environment()

    if args.update:
        seed = impl_drift.SEED if args.seed is None else args.seed
        cells = impl_drift.measure(seed=seed)
        payload = {
            "provenance": {**env, "seed": seed,
                           "cohort": {"n": impl_drift.N,
                                      "d": impl_drift.D,
                                      "f": impl_drift.F}},
            "tie_band_ulps": TIE_BAND_ULPS,
            "cells": cells,
        }
        with open(args.baseline, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        n_skip = sum(1 for c in cells.values()
                     for r in c["cohorts"].values() if "skipped" in r)
        print(f"wrote {args.baseline} ({len(cells)} cells, "
              f"{n_skip} skipped cell-cohorts, seed {seed}, "
              f"jax {env['jax']}, {env['platform']})")
        return 0

    if not os.path.exists(args.baseline):
        print(f"no baseline at {args.baseline}; run with --update "
              f"first")
        return 2
    with open(args.baseline) as f:
        base = json.load(f)
    benv = {k: base.get("provenance", {}).get(k) for k in env}
    if benv != env:
        msg = (f"environment mismatch: baseline {benv} vs current "
               f"{env} — ulp envelopes are only comparable within one "
               f"(jax, numpy, platform) tuple; regenerate with "
               f"--update")
        if args.strict_env:
            print(f"FAIL numerics_gate: {msg}")
            return 1
        print(f"SKIP numerics_gate: {msg}")
        return 0

    seed = base.get("provenance", {}).get("seed", impl_drift.SEED) \
        if args.seed is None else args.seed
    measured = impl_drift.measure(seed=seed)
    problems = diff(base["cells"], measured)
    if problems:
        print(f"FAIL numerics_gate: {len(problems)} drift(s)")
        for prob in problems:
            print(f"  {prob}")
        return 1
    n_pairs = sum(len(c["cohorts"]) for c in measured.values())
    print(f"ok   numerics_gate: {len(measured)} impl pairs, "
          f"{n_pairs} cell-cohorts inside their baseline envelopes "
          f"(tie band {base.get('tie_band_ulps', TIE_BAND_ULPS)} ulp, "
          f"seed {seed})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
