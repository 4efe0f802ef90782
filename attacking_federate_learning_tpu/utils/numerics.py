"""Numerics & determinism observatory (ISSUE 20).

Every hard triage in this repo's history has been a floating-point one
(the PR 4 bulyan-blockwise 1-ulp cascade, the test_native.py 3/1000
tie band, the PR 18 tie-lock at margin 0.0) — this module makes f32
behavior a first-class observable, in the same three layers the
margins observatory uses (utils/margins.py):

- **Device helpers** (pure jnp, fixed shapes, safe inside jit):
  nonfinite counters by stage, gradient-norm dynamic range,
  cancellation-depth estimates on the distance Gram, and
  tie-proximity counters that REUSE the PR 18 margin tensors (no new
  O(n^2 d) reductions — the margins are already the signed distance
  to each decision boundary; we only band them at k ulp of the
  boundary's own scale).  The engine threads them like margins and
  emits one schema-v14 'numerics' event per round (core/engine.py).

- **Host ulp machinery** (NumPy): the monotone f32 ordinal (shared
  semantics with runs_cli._f32_ord), elementwise/max ulp distance,
  and the f64-adjudicated verdict for an impl pair — the referee the
  cross-implementation divergence ledger (tools/impl_drift.py) and
  its gate (tools/numerics_gate.py) persist into
  NUMERICS_BASELINE.json.

- **Reader helpers**: per-round series extraction for the
  ``runs numerics`` verb, field->stage attribution for the upgraded
  ``runs diff --band`` divergence report, and host rollups for the
  event emitter.

This module never imports defenses/kernels.py (the kernels import it).
"""

from __future__ import annotations

import math

import numpy as np

try:  # the host-side half works without a jax runtime (tools/)
    import jax.numpy as jnp
except Exception:  # pragma: no cover - jax is baked into this image
    jnp = None

# Default tie band: a decision whose margin sits within this many ulp
# (at the boundary's own magnitude) of zero is one a legal 1-ulp
# evaluation-order difference could plausibly flip — 8 ulp covers the
# measured cross-engine envelope (tests/test_native.py's <=1-ulp tie
# swaps) with headroom.
TIE_BAND_ULPS = 8

_EPS32 = 2.0 ** -23           # f32 machine epsilon (ulp at 1.0)
_TINY32 = 2.0 ** -126         # smallest normal f32

# ---------------------------------------------------------------------------
# Device-side health counters (fixed-shape, jit-safe)
# ---------------------------------------------------------------------------


def nonfinite_count(x, mask=None):
    """() int32 count of non-finite entries of ``x`` (f32 view).

    ``mask`` (n,) bool restricts a (n, d) matrix to its alive rows —
    the post-quarantine counter must not re-count what quarantine
    already zeroed out of the aggregable cohort."""
    bad = ~jnp.isfinite(x.astype(jnp.float32))
    if mask is not None:
        keep = mask
        if bad.ndim == 2:
            keep = mask[:, None]
        bad = bad & keep
    return jnp.sum(bad).astype(jnp.int32)


def norm_dynamic_range(x, mask=None):
    """() f32 log2(max/min) over the finite nonzero row norms of the
    (n, d) matrix — the gradient-norm dynamic range.  0.0 when fewer
    than two usable rows exist (degenerate, not an error)."""
    norms = jnp.linalg.norm(x.astype(jnp.float32), axis=-1)
    ok = jnp.isfinite(norms) & (norms > 0)
    if mask is not None:
        ok = ok & mask
    hi = jnp.max(jnp.where(ok, norms, -jnp.inf))
    lo = jnp.min(jnp.where(ok, norms, jnp.inf))
    usable = jnp.isfinite(hi) & jnp.isfinite(lo) & (lo > 0)
    rng = jnp.where(usable,
                    jnp.log2(jnp.maximum(hi, _TINY32))
                    - jnp.log2(jnp.maximum(lo, _TINY32)),
                    jnp.float32(0.0))
    return rng.astype(jnp.float32)


def max_finite_abs(x):
    """() f32 largest finite |entry| of ``x`` — the boundary scale the
    trim-stage tie band is measured at (dead-row +inf sentinels and
    nonfinite inputs are excluded).  0.0 when nothing finite remains."""
    a = jnp.abs(jnp.asarray(x, jnp.float32))
    m = jnp.max(jnp.where(jnp.isfinite(a), a, -jnp.inf))
    return jnp.where(jnp.isfinite(m), m,
                     jnp.float32(0.0)).astype(jnp.float32)


def ulp_at(scale):
    """f32 spacing at magnitude ``|scale|`` (eps * |scale|, floored at
    the smallest normal so a zero-scale boundary still has a band)."""
    s = jnp.abs(jnp.asarray(scale, jnp.float32))
    return jnp.maximum(s * jnp.float32(_EPS32), jnp.float32(_TINY32))


def tie_proximity(margin, scale, k=TIE_BAND_ULPS):
    """() int32 count of finite margin entries within ``k`` ulp (at
    the boundary scale) of zero — decisions a k-ulp evaluation
    perturbation could flip.  ``margin`` is a PR 18 margin tensor
    (signed distance to the decision boundary, utils/margins.py), so
    this costs one (n,)-sized reduction and no new distance work."""
    band = jnp.float32(k) * ulp_at(scale)
    m = jnp.asarray(margin, jnp.float32)
    near = jnp.isfinite(m) & (jnp.abs(m) <= band)
    return jnp.sum(near).astype(jnp.int32)


def cancellation_bits(max_term, min_positive):
    """() f32 log2(max accumulated term / min positive result): the
    bits a ||a||^2 + ||b||^2 - 2ab Gram subtraction cancelled to
    produce its smallest surviving value — the measured tie-band
    driver (ops/distances.py; PR 4's adjudicated failure mode)."""
    mt = jnp.maximum(jnp.abs(jnp.asarray(max_term, jnp.float32)),
                     jnp.float32(_TINY32))
    mp = jnp.maximum(jnp.abs(jnp.asarray(min_positive, jnp.float32)),
                     jnp.float32(_TINY32))
    return jnp.maximum(jnp.log2(mt) - jnp.log2(mp),
                       jnp.float32(0.0)).astype(jnp.float32)


def gram_cancellation_bits(Dm, mask=None):
    """Cancellation-depth estimate over an (n, n) squared-distance
    matrix (+inf diagonal convention, defenses/kernels.py): the
    largest finite entry against the smallest positive one.  Rows
    masked dead are excluded pairwise.  0.0 when no positive finite
    off-diagonal distance exists (identical cohort)."""
    Df = jnp.asarray(Dm, jnp.float32)
    finite = jnp.isfinite(Df)
    if mask is not None:
        finite = finite & (mask[:, None] & mask[None, :])
    pos = finite & (Df > 0)
    any_pos = jnp.any(pos)
    min_pos = jnp.min(jnp.where(pos, Df, jnp.inf))
    max_fin = jnp.max(jnp.where(finite, Df, -jnp.inf))
    bits = cancellation_bits(
        jnp.where(any_pos, max_fin, jnp.float32(1.0)),
        jnp.where(any_pos, min_pos, jnp.float32(1.0)))
    return jnp.where(any_pos, bits, jnp.float32(0.0))


# ---------------------------------------------------------------------------
# Host-side ulp machinery (NumPy; shared semantics with runs_cli._f32_ord)
# ---------------------------------------------------------------------------


def f32_ords(a):
    """Monotone int64 ordinal of each value in the f32 domain:
    adjacent representable f32s differ by exactly 1 (the vectorized
    twin of runs_cli._f32_ord — one lattice, two spellings)."""
    bits = np.ascontiguousarray(
        np.asarray(a, np.float32)).view(np.uint32).astype(np.int64)
    return np.where(bits < 0x80000000, bits, 0x80000000 - bits)


def ulp_diff(a, b):
    """Elementwise f32 ulp distance (int64).  NaN-vs-NaN is 0 ulp
    (same non-value); NaN-vs-number is the +inf sentinel 2**31 (no
    finite band admits it)."""
    af = np.asarray(a, np.float32).ravel()
    bf = np.asarray(b, np.float32).ravel()
    d = np.abs(f32_ords(af) - f32_ords(bf))
    na, nb = np.isnan(af), np.isnan(bf)
    d = np.where(na & nb, 0, d)
    d = np.where(na ^ nb, np.int64(2) ** 31, d)
    return d


def max_ulp(a, b):
    """(max ulp distance, argmax flat coordinate) between two arrays;
    (0, -1) for empty or bit-identical inputs."""
    d = ulp_diff(a, b)
    if d.size == 0 or not d.any():
        return 0, -1
    i = int(np.argmax(d))
    return int(d[i]), i


def adjudicate(a, b, oracle64, band_ulps=TIE_BAND_ULPS):
    """f64-refereed verdict for one impl pair on identical inputs.

    ``oracle64`` is the f64 reference result (defenses/oracle.py run
    in double); both f32 outputs are measured against its f32
    rounding.  Returns a JSON-ready record:

    - ``max_ulp`` / ``n_mismatch`` / ``argmax_coord``: the pair's raw
      divergence envelope;
    - ``in_tie_band``: every divergent coordinate sits within
      ``band_ulps`` of BOTH the other impl and the oracle — the PR 4
      "legal reduction-order flip" class;
    - ``verdict``: 'exact' (bit-identical), 'tie_band', 'a_closer' /
      'b_closer' (one impl is strictly nearer the f64 truth over the
      divergent coordinates — an accuracy asymmetry worth keeping),
      or 'split' (neither dominates and the band is exceeded)."""
    a32 = np.asarray(a, np.float32).ravel()
    b32 = np.asarray(b, np.float32).ravel()
    oc = np.asarray(oracle64, np.float64).ravel().astype(np.float32)
    d = ulp_diff(a32, b32)
    mis = np.nonzero(d)[0]
    rec = {"max_ulp": 0, "n_mismatch": 0, "argmax_coord": -1,
           "in_tie_band": True, "verdict": "exact",
           "band_ulps": int(band_ulps)}
    if mis.size == 0:
        return rec
    i = int(np.argmax(d))
    da = ulp_diff(a32, oc)[mis]
    db = ulp_diff(b32, oc)[mis]
    in_band = bool(int(d.max()) <= band_ulps
                   and int(max(da.max(), db.max())) <= band_ulps)
    if in_band:
        verdict = "tie_band"
    elif int(np.sum(da < db)) and not int(np.sum(db < da)):
        verdict = "a_closer"
    elif int(np.sum(db < da)) and not int(np.sum(da < db)):
        verdict = "b_closer"
    else:
        verdict = "split"
    rec.update(max_ulp=int(d[i]), n_mismatch=int(mis.size),
               argmax_coord=i, in_tie_band=in_band, verdict=verdict)
    return rec


# ---------------------------------------------------------------------------
# Event-side helpers (emitter rollups, series, stage attribution)
# ---------------------------------------------------------------------------

# Per-round 'numerics' event fields a reader can series (host scalars;
# hier stacks carry shard_/tier2_ prefixes on the same names).
SERIES_FIELDS = ("nonfinite_pre", "nonfinite_post", "nonfinite_agg",
                 "range_log2", "tie_rows", "cancel_bits",
                 "nonfinite_total", "tie_locked")

# Which pipeline stage (utils/costs.py STAGES list) each numerics
# counter observes — the attribution `runs diff --band` names when two
# runs first diverge in a margin/numerics record.
FIELD_STAGE = {
    "nonfinite_pre": "deliver",          # post-attack wire matrix
    "range_log2": "deliver",
    "nonfinite_post": "quarantine",      # post-quarantine aggregable
    "tie_rows": "tier1_aggregate",       # selection/trim boundary
    "cancel_bits": "tier1_aggregate",    # distance Gram
    "nonfinite_agg": "apply",            # applied update
    "nonfinite_total": "apply",
    "tie_locked": "tier1_aggregate",
}

# Margin-event fields attribute by construction (utils/margins.py):
# attack-side envelope utilization observes the delivery seam, every
# defense-side margin the tier-1 decision.
_MARGIN_STAGE_DEFAULT = "tier1_aggregate"


def stage_of(field, kind="numerics"):
    """Stage token a diverging margin/numerics event field observes."""
    f = str(field)
    if f.startswith("tier2_"):
        return "tier2_aggregate"
    if f.startswith("shard_"):
        f = f[len("shard_"):]
    if kind == "margin":
        return "deliver" if f.startswith("attack_") \
            else _MARGIN_STAGE_DEFAULT
    return FIELD_STAGE.get(f, "tier1_aggregate")


def field_ulp(a, b):
    """Event-log ulp distance between two JSON payload values (floats
    or flat numeric lists); None when not comparable that way."""
    num = (int, float)
    if (isinstance(a, num) and isinstance(b, num)
            and not isinstance(a, bool) and not isinstance(b, bool)):
        return int(ulp_diff([a], [b])[0])
    if (isinstance(a, list) and isinstance(b, list)
            and len(a) == len(b) and a
            and all(isinstance(x, num) for x in a)
            and all(isinstance(x, num) for x in b)):
        return int(ulp_diff(a, b).max())
    return None


def divergence_attribution(fields, kind="numerics"):
    """For a ``runs diff`` divergence record's ``{field: [va, vb]}``
    map on a margin/numerics event: (stage, max ulp over the
    attributable fields, the field that carries it).  Ulp is None when
    no differing field is numerically comparable."""
    best_field, best_ulp = None, None
    for k in sorted(fields):
        va, vb = fields[k]
        u = field_ulp(va, vb)
        if u is not None and (best_ulp is None or u > best_ulp):
            best_field, best_ulp = k, u
    anchor = best_field if best_field is not None else sorted(fields)[0]
    return stage_of(anchor, kind=kind), best_ulp, anchor


def numerics_rollups(fields):
    """Host-side derived summary merged into the per-round 'numerics'
    event: total nonfinite count across stages and the tie-lock flag
    (any decision within the tie band this round — the PR 18 Bulyan
    collapse signature is this flag pinned at 1)."""
    total = 0
    for k, v in fields.items():
        base = k[len("shard_"):] if k.startswith("shard_") else (
            k[len("tier2_"):] if k.startswith("tier2_") else k)
        if base.startswith("nonfinite"):
            if isinstance(v, list):
                total += int(sum(x for x in v
                                 if isinstance(x, (int, float))
                                 and math.isfinite(x)))
            elif isinstance(v, (int, float)) and math.isfinite(v):
                total += int(v)
    locked = 0
    for k, v in fields.items():
        base = k[len("shard_"):] if k.startswith("shard_") else (
            k[len("tier2_"):] if k.startswith("tier2_") else k)
        if base == "tie_rows":
            vs = v if isinstance(v, list) else [v]
            if any(isinstance(x, (int, float)) and x > 0 for x in vs):
                locked = 1
    return {"nonfinite_total": total, "tie_locked": locked}


def numerics_series(events):
    """{field: [(round, value), ...]} over a run's 'numerics' events,
    rounds ascending — the `runs numerics` trajectory (hier stacks are
    reduced to their max, the conservative health view)."""
    rows = sorted((e for e in events if e.get("kind") == "numerics"),
                  key=lambda e: e.get("round", 0))
    out = {}
    for e in rows:
        r = e.get("round")
        if not isinstance(r, (int, float)):
            continue
        for f in SERIES_FIELDS:
            for key in (f, "shard_" + f, "tier2_" + f):
                v = e.get(key)
                if isinstance(v, list):
                    vs = [x for x in v if isinstance(x, (int, float))
                          and math.isfinite(x)]
                    v = max(vs) if vs else None
                if isinstance(v, (int, float)) and math.isfinite(v):
                    out.setdefault(key, []).append((int(r), v))
    return out


def numerics_drift(series_a, series_b, field="tie_rows"):
    """First round where two runs' numerics series for ``field``
    differ: (round, value_a, value_b), or None when they agree over
    every shared round (the determinism bar for same-seed twins)."""
    da = dict(series_a.get(field, ()))
    db = dict(series_b.get(field, ()))
    for r in sorted(set(da) & set(db)):
        if da[r] != db[r]:
            return int(r), da[r], db[r]
    return None
