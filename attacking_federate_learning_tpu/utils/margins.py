"""Decision-margin reductions and rollups — the margin observatory.

ALIE and Bulyan are *margin* arguments: the attack works exactly when
the crafted rows sit inside the defense's acceptance region, so the
per-round observable that explains GRID_RESULTS' accuracy cells (the
Bulyan IID z=1.5 collapse, the femnist_style rescue) is each row's
signed distance to the decision boundary.  This module owns both
halves of that measurement:

- **Device-side reductions** (jit-traceable, fixed shapes, no host
  callbacks): the rank/score algebra shared by the defense kernels'
  ``margins=`` seam (defenses/kernels.py, defenses/median.py).  Each
  helper mirrors its kernel's exact sort/selection semantics so the
  margins carry exactness identities instead of approximations:

  * a row is Krum/Bulyan-selected **iff** its selection margin > 0
    (one-sided at exact f32 score ties, where a winner's margin
    degrades to 0 — measure-zero on continuous inputs);
  * a row's trim survival mass equals the telemetry kept-fraction
    bit for bit (same keep set, same sum/d reduction).

- **Host-side rollups** (plain NumPy over event fields): the
  colluder-survival ledger — per-round scalars in DEFENSE sign
  (``colluder_margin`` > 0 means every malicious row sits strictly
  outside the acceptance region; <= 0 means at least one colluder is
  inside) — plus the series/drift helpers behind ``runs margins``.

Sign conventions.  Per-row ``margin_selection`` is ATTACK-side:
positive means the row was selected (it beat the acceptance
threshold), negative means rejected — so "selected iff margin > 0"
reads naturally.  The rollup ``colluder_margin`` flips the sign of
the worst (= most-inside) malicious row, giving the DEFENSE-side
robustness margin: ``colluder_margin = -max(margin_selection[:f])``
is the minimum distance any colluder still has to cover; <= 0 means
at least one colluder is inside the acceptance region.  Boundary
distances (``margin_boundary_dist``) are inside-positive the same
way.

What the observatory actually measures in the pinned GRID round-5
pair (tools/science_gate.py, BEHAVIOR_BASELINE): identical crafted
colluder rows are score-degenerate — a selected colluder's runner-up
is its identical twin, so equal f32 scores subtract to EXACTLY 0.0
and the margin tie-locks at the decision boundary.  The IID z=1.5
collapse stays tie-locked 28/30 rounds (colluders selected at margin
0, accuracy 10%); the femnist_style rescue is NOT a sign flip to
positive margins — colluders are still selected, but the tie-lock
breaks from ~round 19 (19/30 tie rounds, 11 strict-selection events)
while the wider honest cohort sigma neutralizes the drift and
training converges at 99%.  The discriminators the gate pins are
``margin_tie_rounds`` and ``colluder_selected_total``, whose bands
do not overlap — not the margin's sign.

This module never imports defense kernels (the kernels import it),
and the device helpers never touch the host (the engine threads them
out of the fused round program as auxiliary jit outputs).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np
from jax import lax


# Margin field names a defense diagnostics pytree may carry; the engine
# routes exactly these keys out of the telemetry dict into the schema
# v12 ``margin`` event (core/engine.py:_emit_round_telemetry).
MARGIN_KEYS = ("margin_selection", "margin_gap", "margin_slack",
               "margin_kept_frac", "margin_boundary_dist",
               "margin_trim_kept")


# --- device-side reductions (jit-traceable, fixed shapes) --------------


def krum_margins(scores, selected_idx, mask=None):
    """Selection margins from a Krum score vector.

    ``margin_selection[i]``: signed distance of row ``i``'s score to
    the selection threshold — for the winner, runner-up score minus
    its own (>= 0, > 0 off ties); for everyone else, the winning
    score minus its own (<= 0).  ``margin_gap`` is the winner/runner-up
    score gap (the same number the winner's margin reports).  Dead
    rows under ``mask`` are forced to -inf (their +inf scores would
    otherwise produce inf/nan arithmetic)."""
    n = scores.shape[0]
    kk = min(2, n)
    neg, _ = lax.top_k(-scores, kk)
    s1 = -neg[0]
    s2 = -neg[kk - 1]
    rows = jnp.arange(n)
    margin = jnp.where(rows == selected_idx, s2, s1) - scores
    if mask is not None:
        margin = jnp.where(mask, margin, -jnp.inf)
    return {"margin_selection": margin.astype(jnp.float32),
            "margin_gap": (s2 - s1).astype(jnp.float32)}


def rank_keep_margins(key, number_to_consider, order=None):
    """Trim-envelope margins from a per-coordinate sort key.

    ``key`` is the (n, d) matrix the trimmed mean ranks rows by per
    coordinate (|deviation from the anchor median|, dead rows already
    at +inf); ``number_to_consider`` (static or traced) is the keep
    count.  Returns

    - ``margin_kept_frac`` (n,): per row, the fraction of coordinates
      where it survived the trim — computed from rank membership, so
      it is bit-equal to the scatter-based telemetry ``kept_fraction``
      (same stable sort, same keep set, same sum/d);
    - ``margin_boundary_dist`` (n,): per row, the mean over
      coordinates of (trim boundary - key) — inside-positive distance
      to the envelope edge, where the boundary is the midpoint of the
      last-kept and first-trimmed key values (falling back to the
      last-kept value when the first-trimmed is a +inf sentinel).

    ``order``: the kernel's already-computed stable argsort of
    ``key`` along axis 0, to avoid a second sort."""
    n = key.shape[0]
    if order is None:
        order = jnp.argsort(key, axis=0, stable=True)
    ranks = jnp.argsort(order, axis=0, stable=True)
    k = jnp.asarray(number_to_consider, jnp.int32)
    keep = ranks < k
    # sum-then-divide, NOT jnp.mean (which multiplies by the
    # reciprocal): bit-equality with the kernels' scatter-based
    # ``.at[...].add(1.0) / d`` kept_fraction depends on the division.
    kept_frac = jnp.sum(keep.astype(jnp.float32), axis=1) / key.shape[1]
    srt = jnp.take_along_axis(key, order, axis=0)
    lo = jnp.take(srt, jnp.maximum(k - 1, 0), axis=0, mode="clip")
    hi = jnp.take(srt, jnp.minimum(k, n - 1), axis=0, mode="clip")
    boundary = jnp.where(jnp.isfinite(hi), 0.5 * (lo + hi), lo)
    dist = jnp.mean(boundary[None, :] - key, axis=1)
    return {"margin_kept_frac": kept_frac.astype(jnp.float32),
            "margin_boundary_dist": dist.astype(jnp.float32)}


def median_pick_margins(users_grads, mask=None, weights=None):
    """Pick-mass margins for the coordinate-wise median.

    Re-derives the exact rank membership of kernels.masked_median /
    ``jnp.median`` (same +inf-sentinel sort, same middle-rank picks,
    same weighted lower-median crossing) and reports

    - ``margin_kept_frac`` (n,): per row, the mean over coordinates of
      its pick weight (0.5/0.5 on the two middles at even alive
      counts, 1.0 on the single middle / weighted pick) — the mass
      the row contributes to the aggregate; summing over rows gives
      1.0 per coordinate, and the picked values reconstruct the
      aggregate (pinned test-side);
    - ``margin_boundary_dist`` (n,): minus the mean |distance to the
      rank-derived median| per coordinate — inside-positive proximity
      to the decision point (the median itself), dead rows -inf."""
    n = users_grads.shape[0]
    alive = (jnp.ones((n,), bool) if mask is None
             else mask.astype(bool))
    vals = jnp.where(alive[:, None], users_grads, jnp.inf)
    order = jnp.argsort(vals, axis=0)
    ranks = jnp.argsort(order, axis=0)
    if weights is not None:
        w = jnp.where(alive, weights, 0.0)
        w_srt = jnp.take_along_axis(
            jnp.broadcast_to(w[:, None], vals.shape), order, axis=0)
        cum = jnp.cumsum(w_srt, axis=0)
        half = jnp.sum(w) / 2.0
        pick_rank = jnp.argmax(cum >= half, axis=0)
        pick = (ranks == pick_rank[None, :]).astype(jnp.float32)
    else:
        e = jnp.sum(alive).astype(jnp.int32)
        lo_r, hi_r = (e - 1) // 2, e // 2
        pick = (0.5 * (ranks == lo_r).astype(jnp.float32)
                + 0.5 * (ranks == hi_r).astype(jnp.float32))
    kept_frac = jnp.mean(pick, axis=1)
    med = jnp.sum(jnp.where(alive[:, None], users_grads, 0.0) * pick,
                  axis=0)
    dist = -jnp.mean(jnp.abs(users_grads - med[None, :]), axis=1)
    dist = jnp.where(alive, dist, -jnp.inf)
    return {"margin_kept_frac": kept_frac.astype(jnp.float32),
            "margin_boundary_dist": dist.astype(jnp.float32)}


# --- host-side rollups (NumPy over event fields) -----------------------


def _finite(a):
    a = np.asarray(a, np.float64)
    return a[np.isfinite(a)]


def margin_rollups(fields, mal_count):
    """Colluder-survival scalars from one round's per-row margin fields.

    ``fields``: margin_* arrays/lists as the kernel returned them (rows
    [0, mal_count) are the malicious clients — the attack-seam
    contract).  Returns DEFENSE-sign scalars:

    - ``colluder_margin``: -max over finite malicious selection
      margins (boundary distances when the defense has no selection) —
      the minimum distance any colluder still has to cover; <= 0 means
      at least one colluder is inside the acceptance region.
    - ``colluder_selected``: how many malicious rows were selected
      (selection margin > 0).
    - ``colluder_kept_mass`` / ``honest_kept_mass``: mean surviving
      coordinate mass over malicious / honest rows (trim kept-fraction;
      Bulyan uses its trim-stage survival).
    """
    out = {}
    f = int(mal_count)
    sel = fields.get("margin_selection")
    bd = fields.get("margin_boundary_dist")
    basis = sel if sel is not None else bd
    if basis is not None and f > 0:
        mal = _finite(np.asarray(basis, np.float64)[:f])
        if mal.size:
            out["colluder_margin"] = float(-np.max(mal))
    if sel is not None and f > 0:
        out["colluder_selected"] = int(
            np.sum(np.asarray(sel, np.float64)[:f] > 0))
    kept = fields.get("margin_trim_kept", fields.get("margin_kept_frac"))
    if kept is not None:
        kept = np.asarray(kept, np.float64)
        if f > 0:
            out["colluder_kept_mass"] = float(np.mean(kept[:f]))
        if kept.size > f:
            out["honest_kept_mass"] = float(np.mean(kept[f:]))
    gap = fields.get("margin_gap")
    if gap is not None and np.ndim(gap) == 0:
        out["margin_gap"] = float(gap)
    return out


def hier_margin_rollups(stacks, mal_counts):
    """Rollups over a hierarchical round's (S, n) margin stacks.

    ``stacks``: margin_* fields stacked over the shard axis (the
    client_map output); ``mal_counts``: (S,) per-shard malicious-row
    counts (rows [0, mal_counts[s]) of shard s are malicious — the
    placement contract).  Aggregates the per-shard rollups the way the
    ledger reads them: the WORST shard margin (min), the TOTAL
    selected-colluder count, the mean kept masses."""
    mal_counts = [int(c) for c in mal_counts]
    margins, selected = [], 0
    kept_c, kept_h = [], []
    any_sel = False
    for s, f_s in enumerate(mal_counts):
        row_fields = {k: np.asarray(v)[s] for k, v in stacks.items()
                      if np.ndim(v) >= 2 or k == "margin_gap"}
        r = margin_rollups(row_fields, f_s)
        if "colluder_margin" in r:
            margins.append(r["colluder_margin"])
        if "colluder_selected" in r:
            any_sel = True
            selected += r["colluder_selected"]
        if "colluder_kept_mass" in r:
            kept_c.append(r["colluder_kept_mass"])
        if "honest_kept_mass" in r:
            kept_h.append(r["honest_kept_mass"])
    out = {}
    if margins:
        out["colluder_margin"] = float(min(margins))
    if any_sel:
        out["colluder_selected"] = int(selected)
    if kept_c:
        out["colluder_kept_mass"] = float(np.mean(kept_c))
    if kept_h:
        out["honest_kept_mass"] = float(np.mean(kept_h))
    return out


def tier2_margin_rollups(fields, colluder_shards):
    """Rollups over the tier-2 (cross-shard) margin fields.

    ``fields``: margin_* vectors over the (S,) SHARD axis;
    ``colluder_shards``: boolean/int mask of shards holding malicious
    clients.  Tier-2's "colluders" are those shards' estimates; the
    same defense-sign scalars as :func:`margin_rollups`, prefixed
    ``tier2_`` by the caller."""
    cs = np.asarray(colluder_shards, bool)
    idx = np.flatnonzero(cs)
    out = {}
    sel = fields.get("margin_selection")
    bd = fields.get("margin_boundary_dist")
    basis = sel if sel is not None else bd
    if basis is not None and idx.size:
        mal = _finite(np.asarray(basis, np.float64)[idx])
        if mal.size:
            out["colluder_margin"] = float(-np.max(mal))
    if sel is not None and idx.size:
        out["colluder_selected"] = int(
            np.sum(np.asarray(sel, np.float64)[idx] > 0))
    kept = fields.get("margin_trim_kept", fields.get("margin_kept_frac"))
    if kept is not None and idx.size:
        out["colluder_kept_mass"] = float(
            np.mean(np.asarray(kept, np.float64)[idx]))
    return out


# --- run-level series / drift (the ``runs margins`` backend) -----------

# Scalar fields a margin event carries that trajectories plot; order is
# the render order.
SERIES_FIELDS = ("colluder_margin", "colluder_selected",
                 "colluder_kept_mass", "honest_kept_mass", "margin_gap",
                 "f_eff")


def margin_series(events):
    """Margin events (dicts, any order) -> per-defense round series:
    ``{defense: {"round": [...], "<field>": [...]}}`` with rounds
    ascending and missing scalars as None (a defense without a
    selection has no colluder_selected — the series keeps alignment)."""
    by_def = {}
    for e in events:
        if e.get("kind") != "margin":
            continue
        d = str(e.get("defense", "?"))
        rows = by_def.setdefault(d, [])
        rows.append(e)
    out = {}
    for d, rows in by_def.items():
        rows.sort(key=lambda e: int(e.get("round", 0)))
        ser = {"round": [int(e.get("round", 0)) for e in rows]}
        for fld in SERIES_FIELDS:
            ser[fld] = [e.get(fld) for e in rows]
        out[d] = ser
    return out


def margin_drift(series_a, series_b, field="colluder_margin",
                 tol=1e-6):
    """Cross-run drift on one margin field: align two
    :func:`margin_series` entries by round and report per-round deltas
    plus the rounds where the DEFENSE-sign margin flips sign between
    runs (the drift marks ``runs margins <a> <b>`` renders).  Returns
    ``{"rounds": [...], "delta": [...], "sign_flips": [...]}``."""
    a_by_r = dict(zip(series_a.get("round", []),
                      series_a.get(field, [])))
    b_by_r = dict(zip(series_b.get("round", []),
                      series_b.get(field, [])))
    rounds = sorted(set(a_by_r) & set(b_by_r))
    deltas, flips = [], []
    for r in rounds:
        va, vb = a_by_r[r], b_by_r[r]
        if va is None or vb is None:
            deltas.append(None)
            continue
        deltas.append(float(vb) - float(va))
        if (math.copysign(1.0, va) != math.copysign(1.0, vb)
                and (abs(va) > tol or abs(vb) > tol)):
            flips.append(r)
    return {"rounds": rounds, "delta": deltas, "sign_flips": flips}
