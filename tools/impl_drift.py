#!/usr/bin/env python
"""Cross-implementation divergence ledger (ISSUE 20).

Every defense in this repo ships several implementations that are
supposed to agree — the XLA kernels, the native C++ selection
engine, the host BLAS routes, the
masked/weighted fault- and staleness-seam variants, and two shipped
traversal orders for the hierarchical tier-1 sweep (vmap'd shards vs a
lax.scan over shards).  History says "supposed to agree" needs a
measured envelope, not faith: the PR 4 bulyan-blockwise cascade was a
1-ulp Gram cancellation and tests/test_native.py pins a 3/1000
<=1-ulp tie-swap band.

This tool runs every available impl pair over identical seeded
attack-shaped cohorts (a DriftAttack-shaped cohort plus a near-tie one
with an exact duplicate row and a 1-ulp twin) and records, per pair:

- ``max_ulp`` / ``n_mismatch`` / ``argmax_coord``: the raw divergence
  envelope in f32 ulp (utils/numerics.py:ulp_diff — NaN-vs-NaN is 0,
  NaN-vs-number is the 2**31 sentinel);
- ``in_tie_band``: whether every divergent coordinate sits within
  TIE_BAND_ULPS of both the other impl and the referee;
- ``verdict``: the f64-adjudicated call (defenses/oracle.py re-run in
  double as referee) — 'exact', 'tie_band', 'a_closer'/'b_closer'
  (one impl is strictly nearer the f64 truth: an accuracy asymmetry
  worth keeping), or 'split'.

Impl variants that cannot run in this environment (e.g. a native .so
that fails to build) are recorded as ``skipped`` cells with the error,
never silently dropped — availability is part of the ledger.

``tools/numerics_gate.py`` persists this matrix into
``NUMERICS_BASELINE.json`` and gates regressions (envelope growth or a
verdict flip).  Standalone:

    JAX_PLATFORMS=cpu python tools/impl_drift.py
    ... --json out.json      # dump the raw matrix
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SEED = 0
N, D, F = 16, 64, 3


def cohorts(seed: int = SEED) -> dict:
    """Identical attack-shaped inputs for every impl pair.

    ``drift``: honest rows N(0,1), colluders parked at mean - 1.5 sigma
    (the DriftAttack shape the behavioral tests use).  ``neartie``: the
    same cohort with an exact duplicate row and a 1-ulp perturbed twin
    — the inputs where evaluation-order differences are allowed to
    flip selections, so the ledger measures the flip instead of
    assuming it away."""
    import numpy as np

    rng = np.random.default_rng(seed)
    base = rng.normal(size=(N, D)).astype(np.float32)
    mu = base[F:].mean(axis=0)
    sd = base[F:].std(axis=0)
    drift = base.copy()
    drift[:F] = (mu - 1.5 * sd).astype(np.float32)
    tie = drift.copy()
    tie[6] = tie[5]
    tie[7] = np.nextafter(tie[5], np.float32(np.inf))
    return {"drift": drift, "neartie": tie}


def _variants() -> dict:
    """{defense: (oracle64, ref_fn, {variant: fn})} — each fn maps the
    (n, d) f32 cohort to the aggregated (d,) vector through one shipped
    implementation route."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from attacking_federate_learning_tpu.defenses.kernels import (
        bulyan, krum, trimmed_mean, trimmed_mean_of
    )
    from attacking_federate_learning_tpu.defenses.median import median
    from attacking_federate_learning_tpu.defenses.oracle import (
        np_bulyan, np_krum, np_trimmed_mean
    )

    def ones(n):
        return jnp.ones((n,), bool)

    def unit_w(n):
        return jnp.ones((n,), jnp.float32)

    def arr(fn):
        def run(G):
            return np.asarray(fn(jnp.asarray(G)), np.float32)
        return run

    # The two shipped hierarchical tier-1 traversal orders over the
    # SAME kernel: vmap'd shards (the sharded/groupwise route) vs a
    # lax.scan over shards (the sequential-megabatch route).  Both
    # reduce each 4-row shard with trimmed_mean_of(keep=2) and mean the
    # shard estimates — the scan-vs-sharded hier question at kernel
    # granularity.
    shards = 4

    def hier_vmap(G):
        Gs = G.reshape(shards, N // shards, D)
        ests = jax.vmap(lambda S: trimmed_mean_of(S, 2))(Gs)
        return jnp.mean(ests, axis=0)

    def hier_scan(G):
        Gs = G.reshape(shards, N // shards, D)

        def step(acc, S):
            return acc + trimmed_mean_of(S, 2), None

        tot, _ = jax.lax.scan(step, jnp.zeros((D,), jnp.float32), Gs)
        return tot / shards

    def hier_oracle(G64):
        ests = [np_trimmed_mean(S, N // shards, 1)
                for S in G64.reshape(shards, N // shards, D)]
        return np.mean(ests, axis=0)

    return {
        "Krum": (
            lambda G64: np_krum(G64, N, F),
            arr(lambda G: krum(G, N, F)),
            {
                "topk": arr(lambda G: krum(G, N, F, method="topk")),
                "dist_host": arr(
                    lambda G: krum(G, N, F, distance_impl="host")),
                "masked": arr(lambda G: krum(G, N, F, mask=ones(N))),
            }),
        "TrimmedMean": (
            lambda G64: np_trimmed_mean(G64, N, F),
            arr(lambda G: trimmed_mean(G, N, F)),
            {
                "native_host": arr(
                    lambda G: trimmed_mean(G, N, F, impl="host")),
                "masked": arr(
                    lambda G: trimmed_mean(G, N, F, mask=ones(N))),
                "weighted": arr(
                    lambda G: trimmed_mean(G, N, F, mask=ones(N),
                                           weights=unit_w(N))),
            }),
        "Median": (
            lambda G64: __import__("numpy").median(G64, axis=0),
            arr(lambda G: median(G, N, F)),
            {
                "native_host": arr(
                    lambda G: median(G, N, F, impl="host")),
                "masked": arr(lambda G: median(G, N, F, mask=ones(N))),
                "weighted": arr(
                    lambda G: median(G, N, F, mask=ones(N),
                                     weights=unit_w(N))),
            }),
        "Bulyan": (
            lambda G64: np_bulyan(G64, N, F),
            arr(lambda G: bulyan(G, N, F)),
            {
                "sel_native": arr(
                    lambda G: bulyan(G, N, F, selection_impl="host")),
                "trim_native": arr(
                    lambda G: bulyan(G, N, F, trim_impl="host")),
                "masked": arr(lambda G: bulyan(G, N, F, mask=ones(N))),
            }),
        "HierTrim": (
            hier_oracle,
            arr(hier_vmap),
            {"scan": arr(hier_scan)}),
    }


def measure(seed: int = SEED, band_ulps: int | None = None) -> dict:
    """{"Defense/variant": {"cohorts": {name: adjudication-record or
    {"skipped": reason}}}} — the full ledger, deterministic for a
    (seed, environment) pair."""
    from attacking_federate_learning_tpu.utils.numerics import (
        TIE_BAND_ULPS, adjudicate
    )

    if band_ulps is None:
        band_ulps = TIE_BAND_ULPS
    cells: dict = {}
    data = cohorts(seed)
    for defense, (oracle, ref_fn, variants) in _variants().items():
        refs, oracles = {}, {}
        for cname, G in data.items():
            oracles[cname] = oracle(G.astype("float64"))
            try:
                refs[cname] = ref_fn(G)
            except Exception as e:  # ref unavailable: whole family skips
                refs[cname] = e
        for vname, fn in variants.items():
            rec: dict = {"cohorts": {}}
            for cname, G in data.items():
                if isinstance(refs[cname], Exception):
                    rec["cohorts"][cname] = {
                        "skipped": f"ref: {type(refs[cname]).__name__}: "
                                   f"{refs[cname]}"}
                    continue
                try:
                    got = fn(G)
                except Exception as e:
                    rec["cohorts"][cname] = {
                        "skipped": f"{type(e).__name__}: {e}"}
                    continue
                rec["cohorts"][cname] = adjudicate(
                    refs[cname], got, oracles[cname],
                    band_ulps=band_ulps)
            cells[f"{defense}/{vname}"] = rec
    return cells


def render(cells: dict) -> str:
    lines = [f"{'cell':<26} {'cohort':<8} {'max_ulp':>8} "
             f"{'mismatch':>8}  verdict"]
    for cell in sorted(cells):
        for cname, rec in sorted(cells[cell]["cohorts"].items()):
            if "skipped" in rec:
                lines.append(f"{cell:<26} {cname:<8} {'-':>8} {'-':>8}"
                             f"  skipped ({rec['skipped'][:40]})")
            else:
                lines.append(
                    f"{cell:<26} {cname:<8} {rec['max_ulp']:>8} "
                    f"{rec['n_mismatch']:>8}  {rec['verdict']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Cross-implementation divergence ledger: every "
                    "impl pair over identical seeded cohorts, "
                    "f64-adjudicated (utils/numerics.py).")
    p.add_argument("--seed", type=int, default=SEED)
    p.add_argument("--json", metavar="PATH",
                   help="also dump the raw matrix as JSON")
    args = p.parse_args(argv)

    cells = measure(seed=args.seed)
    print(render(cells))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(cells, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json} ({len(cells)} cells)")
    skipped = sum(1 for c in cells.values()
                  for r in c["cohorts"].values() if "skipped" in r)
    if skipped:
        print(f"note: {skipped} skipped cell-cohort(s) — availability "
              f"is recorded, not hidden")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
