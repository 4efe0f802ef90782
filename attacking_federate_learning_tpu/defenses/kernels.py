"""Robust-aggregation kernels as compiled XLA.

Each defense is a pure function ``(users_grads (n, d), users_count,
corrupted_count) -> aggregated (d,)`` — the same contract as the reference's
registry (reference defences.py:73-75) — but vectorized over the client axis
instead of Python loops:

- Krum's O(n^2 * d) pairwise-distance dict (reference defences.py:16-21)
  becomes a Gram matmul (ops/distances.py; for a large cohort its upper
  block triangle, each pair once) + a per-row sum of the k smallest
  (a sort below ``KRUM_SELECT_MIN_ROWS`` rows, a selection from there up).
- TrimmedMean's per-coordinate Python loop (reference defences.py:44-52)
  becomes a stable argsort along the client axis + masked mean.
- Bulyan's destructive dict-popping selection loop (reference
  defences.py:55-70) becomes a fixed-trip ``lax.fori_loop`` over a static
  distance matrix with a boolean alive-mask, so shapes never change and jit
  compiles once.

Telemetry seam: every registered defense accepts ``telemetry=False``.
With it off (the default) the function returns the aggregated ``(d,)``
vector through the exact pre-telemetry code path — same compiled HLO, bit
for bit.  With it on it returns ``(aggregated, diagnostics)``, where the
diagnostics are a SMALL, FIXED-SHAPE pytree of device arrays (selection
masks and score vectors for Krum/Bulyan, per-client kept fractions for
the trimmed mean, clip scales/counts, trust scores, ...) that the engine
threads out of the fused round program as auxiliary jit outputs
(core/engine.py) — never via host callbacks.  ``telemetry`` is a Python
bool, so the branch resolves at trace time and the off path stays
untouched.  Host-engine variants that only return an aggregate (no
scores) fill their score slots with NaN — fixed shapes, explicit "not
measured".

Semantics match the reference's exact variants, quirks included
(SURVEY.md §2.4 #4-6): Krum scores sum the (users_count - corrupted_count)
*smallest* distances, not the paper's n-f-2 (reference defences.py:26,
33-34); TrimmedMean is the median-anchored variant keeping the
n-f-1 values closest to the median (defences.py:45, :50-51); Bulyan's
inner Krum runs with users_count shrinking per selection while
corrupted_count stays fixed (defences.py:62), and its final trim parameter
is 2f (defences.py:70).  Ties resolve to the lowest index, matching
``current_error < minimal_error`` (defences.py:35) and first-occurrence
``np.argmin``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from attacking_federate_learning_tpu.ops.distances import pairwise_distances
from attacking_federate_learning_tpu.utils.costs import stage_scope
from attacking_federate_learning_tpu.utils.margins import (
    krum_margins, rank_keep_margins
)
from attacking_federate_learning_tpu.utils.numerics import (
    cancellation_bits, gram_cancellation_bits, max_finite_abs,
    tie_proximity
)
from attacking_federate_learning_tpu.utils.plugins import Registry


DEFENSES = Registry("defense")


def stage_wrapped(fn, stage):
    """Defense-kernel dispatch seam of the stage ledger (utils/costs.py):
    every op a kernel traces carries ``stage`` in its op_name metadata,
    whatever call site invoked it (fused round, hier shard_fn, the
    standalone ``defense_<name>``/``tier2_<name>`` cost-report entries).
    Attribute-transparent: ``needs_round``/``needs_server_grad``/etc.
    survive the wrap — functools.wraps copies ``__dict__`` (where they
    live on both plain kernels and the engine's partials) and tolerates
    partials' missing ``__name__``."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with stage_scope(stage):
            return fn(*args, **kwargs)

    # Partial introspection (tests reach exp.defense_fn.keywords to pin
    # config wiring) rides through: partial's C-level attrs are not in
    # __dict__, so wraps alone would drop them.
    for attr in ("func", "args", "keywords"):
        if hasattr(fn, attr) and not hasattr(scoped, attr):
            setattr(scoped, attr, getattr(fn, attr))
    return scoped

def check_margin_seam(margins, telemetry):
    """The ``margins=`` seam (ISSUE 18) rides the telemetry diagnostics
    pytree — margins without telemetry has no carrier and is a caller
    bug (core/engine.py always passes telemetry=True when margins are
    on, even with --telemetry off; the engine then filters the
    non-margin diagnostics back out)."""
    if margins and not telemetry:
        raise ValueError(
            "defense margins=True requires telemetry=True (margin "
            "fields ride the diagnostics pytree; utils/margins.py)")


def check_numerics_seam(numerics, margins):
    """The ``numerics=`` seam (ISSUE 20) rides the margin tensors — a
    kernel's tie-proximity counters band the PR 18 margins at k ulp of
    the decision boundary, so numerics without margins has nothing to
    band and is a caller bug (core/engine.py passes margins=True
    whenever kernel numerics are on, even with --margins off, and
    filters the margin fields back out of the event stream)."""
    if numerics and not margins:
        raise ValueError(
            "defense numerics=True requires margins=True (tie counters "
            "band the margin tensors; utils/numerics.py)")


_INF = jnp.inf
# topk cancellation guard: required ratio of a row's kept score mass to
# the complement subtraction's noise floor (eps * log2(n) * rowsum).
# 1e4 keeps the relative score error under ~1e-4 whenever topk is used;
# below that the evaluation falls back to the exact sort path.
_TOPK_GUARD = 1e4
# Rows from which Krum's exact evaluator selects the k-th smallest
# distance of a row instead of sorting the row (:func:`_select_scores`).
# Below it the program is the sort's, instruction for instruction.  On
# the v5e the two read the same at n = 512 and the selection wins from
# 1,024 (0.20 against 0.29 ms; 7.3 against 106.1 at n = 10,240): PERF.md
# §6 (PR 33) has the sweep.
KRUM_SELECT_MIN_ROWS = 1024
# Bits of the threshold's pattern one pass over D decides: 2**bits - 1
# candidates counted a pass, ceil(31 / bits) passes.  A pass is bound by
# its read of D, so wider is faster until the compares catch up: 18.1 /
# 9.7 / 7.3 ms for 1 / 2 / 3 at n = 10,240 on the v5e (PERF.md §6, PR 33).
_SELECT_BITS = 3
_INF_KEY = 0x7F800000           # the int32 pattern of f32 +inf


def resolve_distance_impl(distance_impl, users_count=None, users_grads=None):
    """Resolve ``'auto'`` to a concrete distance engine for this backend.

    Backend-aware kernel dispatch (see defenses/host.py): XLA:CPU's
    single-thread gemm/sort lose ~2x to the host's native BLAS, so on an
    *eager* CPU-backend call 'auto' picks 'host' (a zero-copy view + BLAS),
    and 'xla' (MXU Gram matmul) everywhere else.  Traced operands stay on
    'xla': the host path would need a pure_callback whose (n, d) marshal
    costs more than the XLA kernel saves."""
    if distance_impl != "auto":
        return distance_impl
    if isinstance(users_count, jax.core.Tracer) or isinstance(
            users_grads, jax.core.Tracer):
        return "xla"
    return "host" if jax.default_backend() == "cpu" else "xla"


def _distances_for(users_grads, distance_dtype=None):
    """Distance matrix (zero diagonal) via the XLA Gram engine.

    ``distance_dtype='bfloat16'``: cast the operand for the distance
    computation ONLY — the Gram rides the MXU at native bf16 throughput
    (vs the ~6-pass f32 HIGHEST emulation) with f32 accumulation and f32
    squared norms (ops/distances.py).  Training numerics are untouched;
    this is a flagged opt-in deviation like the other quirk knobs (off
    by default; the 'host' engine ignores it — host BLAS is f32)."""
    if distance_dtype is not None:
        users_grads = users_grads.astype(jnp.dtype(distance_dtype))
    return pairwise_distances(users_grads)


def _host_defense(host_fn, users_grads, users_count, corrupted_count,
                  paper_scoring):
    """Run a row-returning defenses/host.py kernel (Bulyan; Krum goes
    through the scalar-index path in :func:`_host_krum_index`).  n/f must
    be static Python ints.  On a concrete (non-traced) gradient matrix
    this is a zero-copy ``np.asarray`` view plus the host BLAS kernel;
    inside a traced program it falls back to ``pure_callback`` (correct,
    but the callback marshals the full (n, d) operand — ~200 ms at n=512,
    d=79510 — so the engine keeps 'xla' for fused round programs and
    'host' for eager aggregation)."""
    import numpy as np

    n_static, f_static = int(users_count), int(corrupted_count)
    d = users_grads.shape[-1]

    def cb(g):
        return host_fn(np.asarray(g, np.float32), n_static, f_static,
                       paper_scoring=paper_scoring).astype(np.float32)

    if not isinstance(users_grads, jax.core.Tracer):
        return jnp.asarray(cb(users_grads))
    return jax.pure_callback(cb, jax.ShapeDtypeStruct((d,), jnp.float32),
                             users_grads.astype(jnp.float32))


def masked_median(users_grads, mask, weights=None):
    """Median along the client axis over the alive rows only.

    The alive count is data-dependent (traced), but shapes stay fixed:
    dead rows sort to the end (+inf sentinel) and the median gathers
    the middle one/two of the first ``e`` sorted entries with dynamic
    indices.  With an all-true mask this computes exactly
    ``jnp.median`` (same sort, same mean-of-two-middles).

    ``weights`` (the staleness seam, core/async_rounds.py): the
    WEIGHTED lower median — per coordinate, the smallest alive value
    whose cumulative weight reaches half the total weight mass.  With
    equal weights this is the classical lower median (NOT the
    mean-of-two-middles at even counts — the one documented deviation
    of the weighted path; it only runs under
    ``staleness_weight != 'none'``).
    """
    vals = jnp.where(mask[:, None], users_grads, _INF)
    srt = jnp.sort(vals, axis=0)
    if weights is not None:
        order = jnp.argsort(vals, axis=0)
        w = jnp.where(mask, weights, 0.0)
        w_srt = jnp.take_along_axis(
            jnp.broadcast_to(w[:, None], vals.shape), order, axis=0)
        cum = jnp.cumsum(w_srt, axis=0)
        half = jnp.sum(w) / 2.0
        # First sorted row whose cumulative weight reaches half; +inf
        # sentinels carry zero weight so the pick stays alive.
        pick = jnp.argmax(cum >= half, axis=0)
        return jnp.take_along_axis(srt, pick[None, :], axis=0)[0]
    e = jnp.sum(mask).astype(jnp.int32)
    lo = jnp.take(srt, (e - 1) // 2, axis=0)
    hi = jnp.take(srt, e // 2, axis=0)
    return (lo + hi) / 2


def masked_trimmed_mean_of(users_grads, mask, number_to_consider,
                           weights=None):
    """Mask-aware median-anchored trimmed mean (the quarantine seam).

    Same estimator as :func:`trimmed_mean_of` over the alive rows only:
    the anchor is the alive median, dead rows sort last (+inf deviation
    key), and the keep count ``number_to_consider`` may be traced
    (e - f - 1 with e the data-dependent alive count).  Fixed shapes
    throughout; the keep boundary is a rank comparison instead of a
    static slice.

    ``weights`` (the staleness seam, core/async_rounds.py): the TRIM
    stays rank-based and unweighted (robustness semantics — which
    values survive is a question of magnitude, not recency), but the
    kept deviations average with per-row weights, so a stale row's
    surviving coordinates contribute proportionally less.  The median
    anchor stays unweighted.  ``weights=None`` is byte-identical to
    the pre-seam path.
    """
    n = users_grads.shape[0]
    med = masked_median(users_grads, mask)
    dev = users_grads - med[None, :]
    key = jnp.where(mask[:, None], jnp.abs(dev), _INF)
    order = jnp.argsort(key, axis=0, stable=True)   # dead rows last
    sdev = jnp.take_along_axis(dev, order, axis=0)
    # Degenerate cohorts (too many quarantined rows for the trim) keep
    # at least one value instead of dividing by zero — the divergence
    # watchdog, not a NaN aggregate, is the recovery path.
    k = jnp.maximum(number_to_consider, 1)
    keep = jnp.arange(n)[:, None] < k
    if weights is not None:
        w = jnp.where(mask, weights, 0.0)
        w_s = jnp.take_along_axis(
            jnp.broadcast_to(w[:, None], sdev.shape), order, axis=0)
        wk = jnp.where(keep, w_s, 0.0)
        mass = jnp.maximum(jnp.sum(wk, axis=0), 1e-12)
        return jnp.sum(wk * sdev, axis=0) / mass + med
    return jnp.sum(jnp.where(keep, sdev, 0.0), axis=0) / k + med


def population_telemetry(users_grads):
    """Per-client update norms and cosine-to-mean — the population view
    the server can always observe (Bonawitz et al.: the update
    population is the server's only defense signal), independent of
    which defense runs.  Fixed shapes: two (n,) f32 vectors."""
    G = users_grads.astype(jnp.float32)
    norms = jnp.linalg.norm(G, axis=1)
    mean = jnp.mean(G, axis=0)
    cos = (G @ mean) / (norms * jnp.linalg.norm(mean) + 1e-12)
    return {"client_norms": norms, "cosine_to_mean": cos}


@DEFENSES.register("NoDefense")
def no_defense(users_grads, users_count, corrupted_count, telemetry=False,
               mask=None, weights=None, margins=False, numerics=False):
    """Plain FedAvg mean (reference defences.py:13-14).  ``mask`` (the
    quarantine seam, core/faults.py): mean over the alive rows only —
    a zeroed dropout row must not drag the average toward zero.
    ``weights`` (the staleness seam, core/async_rounds.py — requires
    ``mask``): the weighted alive mean ``sum(w_i g_i)/sum(w_i)`` —
    FedBuff's staleness-discounted aggregate.  ``margins=`` is
    accepted and ignored (a mean has no decision boundary to measure;
    config rejects --margins for a NoDefense tier-1, but the tier-2
    ``shard_mean`` wrapper forwards the flag here).  ``numerics=`` is
    likewise accepted and ignored (no decision boundary, no tie band;
    the engine-level health counters cover mean aggregation)."""
    check_weight_seam(mask, weights)
    check_margin_seam(margins, telemetry)
    check_numerics_seam(numerics, margins)
    if weights is not None:
        w = jnp.where(mask, weights, 0.0)
        agg = (w @ users_grads.astype(jnp.float32)) / jnp.maximum(
            jnp.sum(w), 1e-12)
    elif mask is None:
        agg = jnp.mean(users_grads, axis=0)
    else:
        e = jnp.maximum(jnp.sum(mask), 1)
        agg = jnp.sum(jnp.where(mask[:, None], users_grads, 0.0),
                      axis=0) / e
    if not telemetry:
        return agg
    return agg, {}


def _select_scores(D, k, alive):
    """Sum of each row's ``k`` smallest off-diagonal (alive) distances by
    selection: exactly what ``sum(sort(row)[:k])`` adds, without the order.

    Every entry is >= +0 or +inf, and for such f32 values the int32 bit
    pattern orders as the values do.  Per row the pattern ``t`` of the
    k-th smallest entry is the largest ``t`` with ``#(key < t) < k``,
    found from the high bit down, ``_SELECT_BITS`` bits a pass: a pass is
    one fused compare + row count over D that writes (n,) vectors.  The
    score is then ``sum(D[key < t]) + (k - #(key < t)) * value(t)``, the
    ties at the threshold included.  A row with fewer than ``k`` finite
    entries ends on a non-finite ``t`` and sums its finite entries only.

    The +inf of the diagonal and of dead rows/columns, and the key, are
    recomputed inside every pass from broadcast iotas so that they fuse
    into the count: no (n, n) value exists beside D.  ``k`` may be
    traced; the passes only compare against it.  The sign bit is cleared
    in the key, so a -0.0 orders as +0.0 and any NaN above +inf, as
    ``jnp.sort`` places them."""
    n = D.shape[0]
    hi = jnp.int32(_INF_KEY)

    def keyed():
        hole = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
                == lax.broadcasted_iota(jnp.int32, (n, n), 1))
        if alive is not None:
            hole = hole | ~(alive[None, :] & alive[:, None])
        Dm = jnp.where(hole, jnp.asarray(_INF, D.dtype), D)
        return Dm, lax.bitcast_convert_type(Dm, jnp.int32) & 0x7FFFFFFF

    def decide(i, carry):
        # The last pass may reach below bit 0: its shift stops at 0 and
        # its candidates re-set decided bits.  Counts are monotone in the
        # candidate, so the largest one with count < k is still the
        # answer's prefix, and its count the largest such count.
        t, below = carry
        shift = jnp.maximum(31 - _SELECT_BITS * (i + 1), 0)
        key = keyed()[1]
        best, best_below = t, below
        for j in range(1, 1 << _SELECT_BITS):
            cand = t | jnp.left_shift(jnp.int32(j), shift)
            cnt = jnp.sum(key < cand[:, None], axis=1, dtype=jnp.int32)
            fits = cnt < k
            best = jnp.where(fits, jnp.maximum(best, cand), best)
            best_below = jnp.where(fits, jnp.maximum(best_below, cnt),
                                   best_below)
        return best, best_below

    zeros = jnp.zeros((n,), jnp.int32)
    t, below = lax.fori_loop(0, -(-31 // _SELECT_BITS), decide,
                             (zeros, zeros))
    Dm, key = keyed()
    total = jnp.sum(jnp.where(key < jnp.minimum(t, hi)[:, None], Dm, 0.0),
                    axis=1)
    tied = (k - below).astype(D.dtype) * lax.bitcast_convert_type(t, D.dtype)
    return total + jnp.where(t < hi, tied, 0.0)


@functools.partial(stage_wrapped, stage="select")
def _krum_scores(D, users_count, corrupted_count, alive=None,
                 paper_scoring=False, method="sort"):
    """Per-user Krum score (sub-stage ``select`` of the stage ledger, for
    Krum and for Bulyan's selection loop): sum of the k smallest
    distances to other (alive) users.  Reference behavior sums k =
    users_count - corrupted_count (reference defences.py:26, 33-34; note
    the reference dict holds no self-distance, which the +inf diagonal
    reproduces);
    ``paper_scoring`` switches to the NIPS'17 paper's k = n - f - 2
    (SURVEY.md §2.4 #4).

    Two exact evaluation strategies:
    - 'sort': the exact evaluator, no subtraction: the sum of each row's
      k smallest participating entries.  Below ``KRUM_SELECT_MIN_ROWS``
      rows that is a full ascending sort per row + masked prefix sum;
      from there up (f32 D) it is :func:`_select_scores`, which finds the
      k-th smallest entry by bisection on the bit pattern and adds what
      lies below it — the same k values in row order instead of sorted
      order (~1e-7 relative), never the order itself.  The rule reads the
      static ``n`` only; there is no option.  Either form sums a row's
      finite entries only when it has fewer than k of them.
    - 'topk': complement identity.  A row always has exactly k + c
      participating entries where c = f - 1 (+2 under paper scoring) is
      *independent of Bulyan's shrinking pool*, so
      sum-of-k-smallest = rowsum - sum-of-c-largest, and ``lax.top_k`` of
      the small complement replaces the O(n log n)-per-row sort.
    - 'auto': 'topk' when the complement is small relative to n.

    Default is 'sort' — the oracle-verified path.  'topk' is numerically a
    subtraction, so it carries a runtime cancellation guard: with
    kept = rowsum - sum-of-complement, the subtraction's absolute error is
    ~eps * log2(n) * rowsum, so whenever any row's kept mass falls below
    ``_TOPK_GUARD * eps * log2(n) * rowsum`` (relative score error no
    longer <= 1/_TOPK_GUARD-ish) the evaluation falls back to the
    cancellation-free 'sort' evaluator via ``lax.cond`` — one branch executes at
    runtime, so the benign large-n/small-f regime keeps topk's cost while
    adversarial magnitudes (reference malicious.py-scale rows, which
    concentrate the rowsum in the complement) get sort's exactness
    automatically.  Inf/nan rowsums fail the guard explicitly
    (``isfinite(rowsum)`` is part of the reliability predicate), so
    overflow also lands on 'sort'.
    """
    n = D.shape[0]
    # entries per row = pool - 1, k = pool - f (- 2 paper) -> complement is
    # pool-independent: f - 1 (+ 2 under paper scoring).
    complement = corrupted_count - 1 + (2 if paper_scoring else 0)
    if method == "auto":
        method = "topk" if (0 <= complement <= max(n // 4, 1)) else "sort"

    def keep_count():
        return users_count - corrupted_count - (2 if paper_scoring else 0)

    def sort_scores():
        if n >= KRUM_SELECT_MIN_ROWS and D.dtype == jnp.float32:
            return _select_scores(D, keep_count(), alive)
        Dm = D + jnp.diag(jnp.full((n,), _INF, D.dtype))
        if alive is not None:
            row_dead = jnp.where(alive, 0.0, _INF)
            Dm = Dm + row_dead[None, :] + row_dead[:, None]
        k = keep_count()
        srt = jnp.sort(Dm, axis=1)  # ascending; masked entries land last
        prefix = (jnp.arange(n) < k) & jnp.isfinite(srt)
        return jnp.sum(jnp.where(prefix, srt, 0.0), axis=1)

    if method == "topk" and complement >= 0:
        pair_alive = None
        if alive is not None:
            pair_alive = alive[None, :] & alive[:, None]
        # Bool eye (n² i1, not f32 — 1/4 the bytes of the old distance-
        # diagonal eye) feeding straight into the select/reduce; XLA
        # fuses it into the masked rowsum (no standalone n² buffer in
        # the compiled program — checked via cost facts when the
        # distance-path eye was replaced, tests/test_distance_impl.py).
        mask = ~jnp.eye(n, dtype=bool) if pair_alive is None else (
            pair_alive & ~jnp.eye(n, dtype=bool))
        rowsum = jnp.sum(jnp.where(mask, D, 0.0), axis=1)
        if complement > 0:
            top, _ = lax.top_k(jnp.where(mask, D, -_INF), complement)
            kept = rowsum - jnp.sum(jnp.maximum(top, 0.0), axis=1)
            # Cancellation guard (see docstring): every row's kept mass
            # must clear the subtraction's noise floor, else re-evaluate
            # via the sort path.  Rows whose guard comparison is nan
            # (inf - inf) count as failing.
            eps = jnp.finfo(D.dtype).eps
            floor = (_TOPK_GUARD * eps * max(np.log2(max(n, 2)), 1.0)
                     * rowsum)
            # isfinite(rowsum): an overflowed rowsum gives kept = floor =
            # inf and inf >= inf would pass — overflow must fail the
            # guard, not just nan.
            reliable = jnp.all((kept >= floor) & jnp.isfinite(rowsum))
            scores = lax.cond(reliable, lambda: kept, sort_scores)
        else:
            scores = rowsum
    else:
        scores = sort_scores()
    if alive is not None:
        scores = jnp.where(alive, scores, _INF)
    return scores


def _host_krum_index(users_grads, users_count, corrupted_count,
                     paper_scoring):
    """Host-BLAS Krum index; pure_callback (scalar int out) under trace,
    zero-copy eager otherwise — same dispatch contract as _host_defense."""
    import numpy as np

    from attacking_federate_learning_tpu.defenses.host import (
        host_krum_index
    )

    n_static, f_static = int(users_count), int(corrupted_count)

    def cb(g):
        return np.int32(host_krum_index(np.asarray(g, np.float32),
                                        n_static, f_static,
                                        paper_scoring=paper_scoring))

    if not isinstance(users_grads, jax.core.Tracer):
        return jnp.asarray(cb(users_grads))
    return jax.pure_callback(cb, jax.ShapeDtypeStruct((), jnp.int32),
                             users_grads.astype(jnp.float32))


def _krum_scores_and_index(users_grads, users_count, corrupted_count,
                           paper_scoring, method, distance_impl, D,
                           distance_dtype, mask=None):
    """(scores-or-None, winner index) behind both :func:`krum_select`
    and the telemetry path.  Scores are ``None`` on the host engine —
    it returns only the scalar index (defenses/host.py), so telemetry
    fills that slot with NaN instead of paying a second (n,) marshal.

    ``mask`` (the quarantine seam, core/faults.py): dead rows are
    excluded from every score (their distance entries mask to +inf, the
    per-row keep count k follows the data-dependent alive pool e - f)
    and can never win — fixed shapes, scoring forced onto the exact
    'sort' evaluator (the topk complement identity assumes the static
    pool)."""
    if D is None:
        impl = resolve_distance_impl(distance_impl, users_count,
                                     users_grads)
        if impl == "host":
            if mask is not None:
                raise ValueError(
                    "mask-aware Krum needs a score-returning engine; "
                    "the host engine returns only the winner index "
                    "(defenses/host.py)")
            return None, _host_krum_index(users_grads, users_count,
                                          corrupted_count, paper_scoring)
        D = _distances_for(users_grads, distance_dtype)
    if mask is not None:
        scores = _krum_scores(D, jnp.sum(mask), corrupted_count,
                              alive=mask, paper_scoring=paper_scoring,
                              method="sort")
    else:
        scores = _krum_scores(D, users_count, corrupted_count,
                              paper_scoring=paper_scoring, method=method)
    with stage_scope("select"):
        return scores, jnp.argmin(scores)


def krum_select(users_grads, users_count, corrupted_count,
                paper_scoring=False, method="sort", distance_impl="xla",
                D=None, distance_dtype=None, mask=None):
    """Index of the Krum winner (reference ``krum(..., return_index=True)``,
    defences.py:39-40).  :func:`krum` is defined through this, so the
    selection the engine's round diagnostics report is — by construction —
    the client the defense aggregated, for every distance engine."""
    return _krum_scores_and_index(users_grads, users_count, corrupted_count,
                                  paper_scoring, method, distance_impl, D,
                                  distance_dtype, mask=mask)[1]


@DEFENSES.register("Krum")
def krum(users_grads, users_count, corrupted_count, paper_scoring=False,
         method="sort", distance_impl="xla", D=None, distance_dtype=None,
         telemetry=False, mask=None, weights=None, margins=False,
         numerics=False):
    """Krum selection (reference defences.py:23-42): the single gradient
    whose summed distance to its k nearest peers is minimal.

    ``distance_impl``: 'xla' (Gram matmul, ops/distances.py), 'host' (NumPy/BLAS
    via pure_callback — the CPU-backend path, defenses/host.py), or 'auto'
    (host on CPU, xla elsewhere).  ``D``: precomputed (n, n) distance matrix
    with zero diagonal — the engine passes one from the blockwise shard_map
    kernels (parallel/distances.py) for distance_impl in {ring, allgather}.
    ``distance_dtype``: see :func:`_distances_for` (bf16 MXU mode).

    ``telemetry=True`` additionally returns ``{'selection_mask': (n,)
    one-hot f32, 'scores': (n,) f32 Krum scores}`` — the same single
    distance computation, so the mask provably marks the aggregated row
    (NaN scores on the scalar-index host engine).

    ``mask`` (the quarantine seam, core/faults.py): quarantined rows
    can never win selection and are excluded from every row's score;
    the winner is the Krum choice of the alive sub-cohort.

    ``weights`` (the staleness seam, core/async_rounds.py — requires
    ``mask``): selection stays unweighted (distances don't age), but
    the winning row's contribution is scaled by ITS weight — a stale
    Krum winner moves the server proportionally less.

    ``margins=True`` (requires ``telemetry=True``; ISSUE 18)
    additionally returns ``margin_selection`` (n,) — each row's signed
    score distance to the selection threshold (selected iff > 0, one-
    sided at exact f32 score ties) — and ``margin_gap`` () — the
    winner/runner-up score gap (utils/margins.py:krum_margins).  Needs
    a score-returning engine: the scalar-index host path has no score
    vector to measure and raises.

    ``numerics=True`` (requires ``margins=True``; ISSUE 20)
    additionally returns ``num_tie_rows`` () int32 — rows whose
    selection margin sits within TIE_BAND_ULPS ulp (at the winner
    score's magnitude) of the boundary — and ``num_cancel_bits`` ()
    f32 — a documented cancellation-depth ESTIMATE: 2*max||g||^2 (the
    largest possible ||a||^2+||b||^2-2ab accumuland) against the
    winner's mean kept distance, since the (n, n) Gram is not in scope
    here and recomputing it would double the distance work
    (utils/numerics.py).
    """
    check_margin_seam(margins, telemetry)
    check_numerics_seam(numerics, margins)
    if not telemetry:
        idx = krum_select(users_grads, users_count, corrupted_count,
                          paper_scoring=paper_scoring, method=method,
                          distance_impl=distance_impl, D=D,
                          distance_dtype=distance_dtype, mask=mask)
        if weights is not None:
            return users_grads[idx] * weights[idx]
        return users_grads[idx]
    scores, idx = _krum_scores_and_index(
        users_grads, users_count, corrupted_count, paper_scoring, method,
        distance_impl, D, distance_dtype, mask=mask)
    n = users_grads.shape[0]
    scores_out = (jnp.full((n,), jnp.nan, jnp.float32) if scores is None
                  else scores.astype(jnp.float32))
    sel = jnp.zeros((n,), jnp.float32).at[idx].set(1.0)
    agg = (users_grads[idx] * weights[idx] if weights is not None
           else users_grads[idx])
    diag = {"selection_mask": sel, "scores": scores_out}
    if margins:
        if scores is None:
            raise ValueError(
                "Krum margins need a score-returning engine; "
                "distance_impl='host' returns only the winner index "
                "(defenses/host.py)")
        diag.update(krum_margins(scores, idx, mask=mask))
        if numerics:
            win = scores_out[idx]
            diag["num_tie_rows"] = tie_proximity(
                diag["margin_selection"], win)
            k_kept = jnp.maximum(
                (jnp.sum(mask) if mask is not None else users_count)
                - corrupted_count, 1).astype(jnp.float32)
            g32 = users_grads.astype(jnp.float32)
            sq = jnp.sum(g32 * g32, axis=1)
            if mask is not None:
                sq = jnp.where(mask, sq, 0.0)
            diag["num_cancel_bits"] = cancellation_bits(
                2.0 * jnp.max(sq), win / k_kept)
    return agg, diag


def trimmed_mean_of(users_grads, number_to_consider, impl="xla",
                    telemetry=False, margins=False, numerics=False):
    """Median-anchored trimmed mean along the client axis.

    Per coordinate (reference defences.py:48-51): subtract the median, keep
    the ``number_to_consider`` values of smallest magnitude (stable order,
    matching Python's stable ``sorted`` on key=abs), and return their mean
    plus the median.

    ``impl='host'`` is the single dispatch site for the native
    column-blocked kernel — shared by :func:`trimmed_mean` and Bulyan's
    ``trim_impl`` tail so the two can never diverge.  It returns only
    the aggregate, so telemetry fills the NaN slots.

    ``telemetry=True`` additionally returns ``{'kept_fraction': (n,) —
    per client, the fraction of coordinates where its value survived the
    trim (NaN on the host kernel, which returns only the aggregate) —
    'trim_fraction': () — the per-round fraction of clients trimmed per
    coordinate}``.

    ``margins=True`` (requires ``telemetry=True``; ISSUE 18)
    additionally returns ``margin_kept_frac``/``margin_boundary_dist``
    (utils/margins.py:rank_keep_margins) — the kept fraction from rank
    membership (bit-equal to the scatter-based ``kept_fraction``) and
    the inside-positive mean distance to the trim boundary.  The
    reductions are pure-XLA rank ops over the same key the estimator
    sorts by; the host kernel runs off-device and raises.

    ``numerics=True`` (requires ``margins=True``; ISSUE 20)
    additionally returns ``num_tie_rows`` () int32 — per-coordinate
    boundary distances within TIE_BAND_ULPS ulp of the trim cut,
    banded at the deviation key's largest finite magnitude
    (utils/numerics.py).
    """
    check_margin_seam(margins, telemetry)
    check_numerics_seam(numerics, margins)
    n = users_grads.shape[0]
    trim_frac = jnp.float32(1.0 - number_to_consider / n)

    if impl == "host":
        if margins:
            raise ValueError(
                "trimmed-mean margins need the on-device ranks; "
                "impl='host' returns only the aggregate "
                "(defenses/host.py)")
        from attacking_federate_learning_tpu.defenses.host import (
            host_trimmed_mean_of
        )
        k_static = int(number_to_consider)
        agg = host_coordwise(
            lambda g: host_trimmed_mean_of(g, k_static), users_grads)
        if not telemetry:
            return agg
        return agg, {"kept_fraction": jnp.full((n,), jnp.nan, jnp.float32),
                     "trim_fraction": trim_frac}
    med = jnp.median(users_grads, axis=0)
    dev = users_grads - med[None, :]
    order = jnp.argsort(jnp.abs(dev), axis=0, stable=True)
    kept_rows = order[:number_to_consider]
    kept = jnp.take_along_axis(dev, kept_rows, axis=0)
    agg = jnp.mean(kept, axis=0) + med
    if not telemetry:
        return agg
    d = users_grads.shape[1]
    kept_frac = (jnp.zeros((n,), jnp.float32)
                 .at[kept_rows.reshape(-1)].add(1.0) / d)
    diag = {"kept_fraction": kept_frac, "trim_fraction": trim_frac}
    if margins:
        key = jnp.abs(dev)
        mf = rank_keep_margins(key, number_to_consider, order=order)
        if numerics:
            mf["num_tie_rows"] = tie_proximity(
                mf["margin_boundary_dist"], max_finite_abs(key))
        diag.update(mf)
    return agg, diag


@DEFENSES.register("TrimmedMean")
def trimmed_mean(users_grads, users_count, corrupted_count, impl="xla",
                 telemetry=False, mask=None, weights=None,
                 margins=False, numerics=False):
    """Reference defences.py:44-52; keeps n - f - 1 coordinates.

    ``impl='host'`` (opt-in, config ``trimmed_mean_impl``) routes to the
    native column-blocked kernel (defenses/host.py ->
    native/bulyan_select.cpp:fl_trimmed_mean): at n=10,240, d=79,510 the
    XLA:CPU per-coordinate stable sort is minutes while the native
    kernel is ~25 s.  Unlike Krum's host path (which returns an exact
    input row, so dispatch cannot change results), the host trimmed
    mean differs from XLA by summation-order ulps — which is why it is
    NOT auto-dispatched: the staged/fused bit-identity invariant
    (tests/test_engine.py::test_backdoor_fused_equals_staged) holds
    only when both modes run the same kernel.

    ``mask`` (the quarantine seam, core/faults.py): the estimator runs
    over the alive rows only — alive median anchor, keep count
    e - f - 1 with e the data-dependent alive count (the trim budget
    shrinks with the cohort, it is not spent on quarantined rows).

    ``weights`` (the staleness seam, core/async_rounds.py — requires
    ``mask``): the trim stays rank-based; the kept deviations average
    weighted (see :func:`masked_trimmed_mean_of`).

    ``margins=True``: see :func:`trimmed_mean_of`; the masked variant
    ranks by the same alive-anchored key as
    :func:`masked_trimmed_mean_of` (dead rows +inf -> -inf boundary
    distance, zero kept fraction).  ``numerics=True``: see
    :func:`trimmed_mean_of` (the masked tie band is measured on the
    same alive-anchored key, whose dead-row +inf sentinels the
    finite-magnitude scale excludes)."""
    check_margin_seam(margins, telemetry)
    check_numerics_seam(numerics, margins)
    if mask is not None:
        if impl == "host":
            raise ValueError(
                "mask-aware TrimmedMean has no host kernel "
                "(defenses/host.py is maskless); use impl='xla'")
        n = users_grads.shape[0]
        e = jnp.sum(mask)
        agg = masked_trimmed_mean_of(users_grads, mask,
                                     e - corrupted_count - 1,
                                     weights=weights)
        if not telemetry:
            return agg
        diag = {"kept_fraction": jnp.full((n,), jnp.nan, jnp.float32),
                "trim_fraction":
                (1.0 - (e - corrupted_count - 1) / jnp.maximum(e, 1)
                 ).astype(jnp.float32)}
        if margins:
            # Same alive-anchored key masked_trimmed_mean_of ranks by.
            med = masked_median(users_grads, mask)
            key = jnp.where(mask[:, None],
                            jnp.abs(users_grads - med[None, :]), _INF)
            k = jnp.maximum(e - corrupted_count - 1, 1)
            mf = rank_keep_margins(key, k)
            if numerics:
                mf["num_tie_rows"] = tie_proximity(
                    mf["margin_boundary_dist"], max_finite_abs(key))
            diag.update(mf)
        return agg, diag
    number_to_consider = users_grads.shape[0] - corrupted_count - 1
    return trimmed_mean_of(users_grads, number_to_consider, impl=impl,
                           telemetry=telemetry, margins=margins,
                           numerics=numerics)


def host_coordwise(host_fn, users_grads):
    """Dispatch a coordinate-wise defenses/host.py kernel
    (``(n, d) f32 -> (d,) f32``): zero-copy eager call on concrete
    operands, ``pure_callback`` inside traced programs — the shared
    scaffold for the opt-in 'host' impls of TrimmedMean and Median."""
    import numpy as np

    d = users_grads.shape[-1]

    def cb(g):
        return host_fn(np.asarray(g, np.float32)).astype(np.float32)

    if not isinstance(users_grads, jax.core.Tracer):
        return jnp.asarray(cb(users_grads))
    return jax.pure_callback(cb, jax.ShapeDtypeStruct((d,), jnp.float32),
                             users_grads.astype(jnp.float32))


def _host_bulyan_selection_of(D, users_count, corrupted_count, set_size,
                              batch_select, paper_scoring):
    """Host-side exact selection over a DEVICE-computed distance matrix —
    the hybrid's host half (VERDICT r3 #2).  ``pure_callback`` under
    trace (marshals the (n, n) D — ~420 MB at n=10,240, the hybrid's one
    data motion), zero-copy eager otherwise; returns (set_size,) int32
    selected indices.  The native incremental engine
    (native/bulyan_select.cpp) makes the selection itself O(n^2) total;
    D must already carry the +inf diagonal."""
    import numpy as np

    from attacking_federate_learning_tpu.defenses.host import (
        host_bulyan_selection
    )

    n_static = int(users_count)
    f_static = int(corrupted_count)
    k_static = int(set_size)
    q_static = int(batch_select)

    def cb(Dh):
        return host_bulyan_selection(
            np.asarray(Dh, np.float32), n_static, f_static, k_static,
            batch_select=q_static,
            paper_scoring=paper_scoring).astype(np.int32)

    if not isinstance(D, jax.core.Tracer):
        return jnp.asarray(cb(D))
    return jax.pure_callback(cb,
                             jax.ShapeDtypeStruct((k_static,), jnp.int32),
                             D.astype(jnp.float32))


def _bulyan_diag(n, selected, Dm, users_count, corrupted_count,
                 paper_scoring, method):
    """Bulyan telemetry pytree: the (n,) multi-hot selection mask plus
    the INITIAL-pool Krum scores (the scores the first selection ranked;
    later trips re-score over the shrinking pool, which would be an
    (n, set_size) matrix — deliberately not carried).  ``Dm`` None (the
    full-host engine, which only returns the aggregate) fills NaN."""
    mask = jnp.zeros((n,), jnp.float32).at[selected].set(1.0)
    if Dm is None:
        scores = jnp.full((n,), jnp.nan, jnp.float32)
    else:
        scores = _krum_scores(Dm, users_count, corrupted_count,
                              paper_scoring=paper_scoring,
                              method=method).astype(jnp.float32)
    return {"selection_mask": mask, "scores": scores}


@DEFENSES.register("Bulyan")
def bulyan(users_grads, users_count, corrupted_count, paper_scoring=False,
           method="sort", distance_impl="xla", D=None, batch_select=1,
           distance_dtype=None, selection_impl="xla", trim_impl="xla",
           telemetry=False, mask=None, weights=None, margins=False,
           numerics=False):
    """Bulyan (reference defences.py:55-70): iteratively Krum-select
    n - 2f gradients (removing each winner from the pool, with the pool
    size — but not f — shrinking), then trim-mean the selection with
    parameter 2f.

    The selection loop sorts each distance row ONCE and evaluates every
    iteration's sum-of-k-smallest as an alive-masked prefix over the
    presorted rows — O(n^2) per selection instead of the O(n^2 log n)
    per-iteration re-sort, exactly the same scores (the k smallest form
    the same multiset whatever the tie order).  ``method`` therefore only
    affects top-level :func:`krum`; ``paper_scoring`` still selects the
    k = pool - f - 2 variant.  ``distance_impl`` / ``D``: same contract
    as :func:`krum`.

    ``batch_select=q`` is an explicit, flagged relaxation for the
    large-n regime on the *traced/XLA* path, where the reference's
    strictly sequential selection is O(n) iterations of O(n^2) scoring
    (BASELINE.md): each trip selects the q lowest-scoring alive clients
    against the SAME scores, re-scoring only between trips, so the loop
    runs ceil(set_size/q) trips instead of set_size.  q=1 IS the
    reference semantics (ties resolve to the lowest index either way:
    ``lax.top_k`` breaks ties toward lower indices, matching
    first-occurrence ``np.argmin``) — the default, and what every
    oracle/reference-parity test pins.  On the ``host`` impl, exact q=1
    no longer needs the relaxation at scale: the native incremental
    kernel (native/bulyan_select.cpp) maintains every row's prefix score
    in O(1) amortized per selection, making the whole exact selection
    O(n^2) total instead of O(n^2) per step.

    ``selection_impl='host'`` is the HYBRID exact path for the
    accelerator backend at large n (VERDICT r3 #2): the O(n^2 d)
    distance work stays on the device (MXU Gram via ``distance_impl``),
    only the (n, n) D ships to the host — once — for the native O(n^2)
    incremental selection, and the selected rows are gathered and
    trim-meaned back on the device.  That replaces the traced path's
    set_size sequential O(n^2) scoring trips (~5,300 dependent
    (10240, 10240) passes per aggregation at the north star) with one
    D transfer + seconds of host selection, while keeping exact q=1
    reference semantics.  Composes with ``batch_select`` and the
    ``D=`` seam; opt-in (config ``bulyan_selection_impl``), not
    auto-dispatched, because host selection resolves f32 score ties by
    the native engine's comparator (see native/bulyan_select.cpp) while
    the traced loop uses f32 throughout — identical outside ulp-band
    ties (tests/test_defenses.py pins hybrid==xla on plain inputs).

    ``trim_impl='host'`` routes the final trimmed-mean tail through the
    native column-blocked kernel (same opt-in standard — and the same
    ulps-not-bits caveat — as ``trimmed_mean_impl``): at the 10k north
    star the XLA:CPU stable argsort over the (n-2f, d) selection is
    minutes per aggregation while the native kernel is seconds, and on
    the CPU backend that tail, not the selection, is what dominates the
    hybrid.

    ``telemetry=True`` additionally returns the :func:`_bulyan_diag`
    pytree (multi-hot selection mask + initial-pool Krum scores).

    ``mask`` (the quarantine seam, core/faults.py): the selection pool
    starts from the alive rows; the SELECTED set keeps its static
    ``set_size`` shape (fixed shapes everywhere), with quarantined rows
    admitted only after every alive row (finite below-+inf sentinel) and
    excluded again from the final trimmed mean by an alive sub-mask —
    so a quarantined row can pad the selection buffer but never touches
    the aggregate.

    ``weights`` (the staleness seam, core/async_rounds.py — requires
    ``mask``): selection stays unweighted; the final masked trimmed
    mean over the selected rows averages with their per-row weights
    (:func:`masked_trimmed_mean_of`).

    ``margins=True`` (requires ``telemetry=True``; ISSUE 18) threads
    margin carries through the traced selection loop and additionally
    returns: ``margin_selection`` (n,) — per row, the signed score
    distance to its trip's selection cut (picks measure against the
    first unselected score, losers against the final trip's last pick;
    selected iff > 0, one-sided at exact f32 ties and on the masked
    variant, whose dead rows are forced to -inf); ``margin_gap`` () —
    the final trip's pick/runner-up slack; ``margin_slack`` (trips,) —
    that slack per selection trip; ``margin_trim_kept`` (n,) — the
    trim-stage kept fraction of each selected row scattered back to
    its client slot (zero for unselected rows).  Both off-device
    selection engines raise: the full-host path returns only the
    aggregate and the hybrid's native selection never ships per-trip
    scores back.

    ``numerics=True`` (requires ``margins=True``; ISSUE 20)
    additionally returns ``num_tie_rows`` () int32 — rows whose
    selection margin sits within TIE_BAND_ULPS ulp of the final trip's
    cut (the PR 18 tie-lock counter: the IID collapse pins this > 0
    every round) — and ``num_cancel_bits`` () f32 — the measured
    cancellation depth of the (n, n) distance Gram, the tie-band
    driver (utils/numerics.py:gram_cancellation_bits)."""
    check_margin_seam(margins, telemetry)
    check_numerics_seam(numerics, margins)
    n, _ = users_grads.shape
    f = corrupted_count
    set_size = users_count - 2 * f
    q = int(batch_select)
    if not (1 <= q):
        raise ValueError(f"batch_select must be >= 1, got {batch_select}")
    if selection_impl not in ("xla", "host"):
        raise ValueError(f"selection_impl must be 'xla' or 'host', "
                         f"got {selection_impl!r}")
    if trim_impl not in ("xla", "host"):
        raise ValueError(f"trim_impl must be 'xla' or 'host', "
                         f"got {trim_impl!r}")

    def trim_tail(selection, number_to_consider):
        return trimmed_mean_of(selection, number_to_consider,
                               impl=trim_impl)
    q = min(q, set_size)
    if mask is not None and selection_impl == "host":
        raise ValueError(
            "mask-aware Bulyan is incompatible with "
            "selection_impl='host': the native selection engine has no "
            "mask seam (native/bulyan_select.cpp)")
    if D is None:
        impl = resolve_distance_impl(distance_impl, users_count,
                                     users_grads)
        if impl == "host":
            if mask is not None:
                raise ValueError(
                    "mask-aware Bulyan has no full-host engine "
                    "(defenses/host.py is maskless)")
            if margins:
                raise ValueError(
                    "Bulyan margins need the traced selection loop; "
                    "the full-host engine returns only the aggregate "
                    "(defenses/host.py)")
            from attacking_federate_learning_tpu.defenses.host import (
                host_bulyan
            )
            host_fn = host_bulyan
            if q > 1:
                host_fn = functools.partial(host_bulyan, batch_select=q)
            agg = _host_defense(host_fn, users_grads, users_count,
                                corrupted_count, paper_scoring)
            if not telemetry:
                return agg
            # The full-host engine returns only the (d,) aggregate; the
            # selection never crosses back.  NaN mask/scores keep the
            # pytree shape fixed and say "not measured" explicitly.
            nan = jnp.full((n,), jnp.nan, jnp.float32)
            return agg, {"selection_mask": nan, "scores": nan}
        D = _distances_for(users_grads, distance_dtype)

    # +inf diagonal reproduces the reference's no-self-distance dict
    # (defences.py:16-21).
    Dm = D + jnp.diag(jnp.full((n,), _INF, D.dtype))

    if selection_impl == "host":
        if margins:
            raise ValueError(
                "Bulyan margins are incompatible with "
                "selection_impl='host': the native selection engine "
                "returns only the selected indices, never the per-trip "
                "scores the margins measure (native/bulyan_select.cpp)")
        # Hybrid: device distances above, host-native exact selection,
        # device gather + trimmed mean below.
        selected = _host_bulyan_selection_of(
            Dm, users_count, corrupted_count, set_size, q, paper_scoring)
        selection = users_grads[selected]
        agg = trim_tail(selection, set_size - 2 * f - 1)
        if not telemetry:
            return agg
        return agg, _bulyan_diag(n, selected, Dm, users_count,
                                 corrupted_count, paper_scoring, method)

    if mask is not None:
        # Mask-aware selection, fixed shapes: the ``selected`` buffer
        # stays (set_size,) whatever the alive count.  Three-level
        # eligibility ladder per trip — alive & unselected rows compete
        # on real scores; dead unselected rows carry a finite
        # below-+inf sentinel (picked only once the alive pool is
        # exhausted, deterministically by lowest index); already-
        # selected rows sit at +inf and can never be re-picked.  Dead
        # rows that do pad the selection are excluded from the final
        # trimmed mean by the alive sub-mask, so they never touch the
        # aggregate.  (A real score above the 3e38 sentinel would
        # misorder a pick; finite f32 sums sit well below it outside
        # deliberately overflowed inputs, which quarantine already
        # removed.)
        order_m = jnp.argsort(Dm, axis=1)
        sortedD_m = jnp.take_along_axis(Dm, order_m, axis=1)
        finite_m = jnp.isfinite(sortedD_m)
        trips_m = -(-set_size // q)
        dead_sentinel = jnp.float32(3e38)

        def body_m(t, carry):
            if margins:
                (remaining, selected, margin, slack, cut,
                 last_scores) = carry
            else:
                remaining, selected = carry
            alive_pool = remaining & mask
            # Reference shrinking-pool k, over the ALIVE pool (clamped:
            # a degenerate cohort keeps at least the nearest neighbor).
            k = jnp.maximum(jnp.sum(alive_pool) - f
                            - (2 if paper_scoring else 0), 1)
            alive_cols = alive_pool[order_m]
            rank = jnp.cumsum(alive_cols, axis=1)
            take = alive_cols & (rank <= k) & finite_m
            scores = jnp.sum(jnp.where(take, sortedD_m, 0.0), axis=1)
            scores = jnp.where(alive_pool, scores, dead_sentinel)
            scores = jnp.where(remaining, scores, _INF)
            if margins:
                # One extra score (the first unselected, ascending) is
                # this trip's selection cut — the margin carries ride
                # the SAME top_k evaluation (its first q entries are
                # the margins-off picks, ties and all).
                kk = min(q + 1, n)
                neg_vals, idxs_all = lax.top_k(-scores, kk)
                idxs = idxs_all[:q]
            else:
                _, idxs = lax.top_k(-scores, q)
            r = jnp.minimum(q, set_size - t * q)
            live = jnp.arange(q) < r
            kill = jnp.zeros((n,), bool).at[idxs].set(live)
            selected = lax.dynamic_update_slice(
                selected, jnp.where(live, idxs, 0).astype(jnp.int32),
                (t * q,))
            if not margins:
                return remaining & ~kill, selected
            vals = -neg_vals          # ascending kk smallest scores
            runner = jnp.take(vals, jnp.minimum(r, kk - 1), mode="clip")
            last_pick = jnp.take(vals, jnp.maximum(r - 1, 0),
                                 mode="clip")
            margin = margin.at[jnp.where(live, idxs, n)].set(
                runner - vals[:q], mode="drop")
            slack = slack.at[t].set(runner - last_pick)
            return (remaining & ~kill, selected, margin, slack,
                    last_pick, scores)

        if margins:
            (rem_f, selected, margin_sel, slack, cut,
             last_scores) = lax.fori_loop(
                0, trips_m, body_m,
                (jnp.ones((n,), bool),
                 jnp.zeros((trips_m * q,), jnp.int32),
                 jnp.zeros((n,), jnp.float32),
                 jnp.zeros((trips_m,), jnp.float32),
                 jnp.float32(0.0), jnp.zeros((n,), jnp.float32)))
        else:
            _, selected = lax.fori_loop(
                0, trips_m, body_m,
                (jnp.ones((n,), bool),
                 jnp.zeros((trips_m * q,), jnp.int32)))
        selected = selected[:set_size]
        selection = users_grads[selected]
        # Effective-cohort Bulyan selects e - 2f of the e alive rows.
        # Alive rows enter ``selected`` first and in exactly the order a
        # run over the alive sub-matrix would pick them (dead rows only
        # pad the tail), so clipping to the first e - 2f alive picks
        # reproduces the shrunk-cohort selection SET inside the static
        # (set_size,) buffer; the rest is excluded from the trim below.
        sel_alive = mask[selected]
        e_set = jnp.sum(mask) - 2 * f
        sel_mask = sel_alive & (jnp.cumsum(sel_alive) <= e_set)
        w_sel = None if weights is None else weights[selected]
        agg = masked_trimmed_mean_of(
            selection, sel_mask, jnp.sum(sel_mask) - 2 * f - 1,
            weights=w_sel)
        if not telemetry:
            return agg
        dm = jnp.zeros((n,), jnp.float32).at[selected].set(
            sel_mask.astype(jnp.float32))
        scores0 = _krum_scores(Dm, jnp.sum(mask), corrupted_count,
                               alive=mask, paper_scoring=paper_scoring,
                               method="sort").astype(jnp.float32)
        diag = {"selection_mask": dm, "scores": scores0}
        if margins:
            # Losers measure against the final trip's last pick (the
            # PADDED loop's cut — a lower bound on their distance to
            # the effective boundary when the cohort is degraded).
            # Picks the effective-cohort cumsum clipped out of the
            # selection are rejected rows whose trip-local margins
            # don't measure against the effective boundary — explicit
            # -inf ("rejected, unmeasured"), like dead rows, so the
            # selected-iff-margin>0 identity holds for every alive
            # row.  Trim-stage survival mirrors the
            # masked_trimmed_mean_of key over the selected rows.
            margin_sel = jnp.where(rem_f, cut - last_scores, margin_sel)
            clipped = jnp.zeros((n,), bool).at[selected].set(~sel_mask)
            margin_sel = jnp.where(clipped, -_INF, margin_sel)
            margin_sel = jnp.where(mask, margin_sel, -_INF)
            med_s = masked_median(selection, sel_mask)
            key_s = jnp.where(sel_mask[:, None],
                              jnp.abs(selection - med_s[None, :]), _INF)
            k_t = jnp.maximum(jnp.sum(sel_mask) - 2 * f - 1, 1)
            tm = rank_keep_margins(key_s, k_t)
            diag["margin_selection"] = margin_sel.astype(jnp.float32)
            diag["margin_gap"] = slack[trips_m - 1]
            diag["margin_slack"] = slack
            diag["margin_trim_kept"] = jnp.zeros(
                (n,), jnp.float32).at[selected].set(
                jnp.where(sel_mask, tm["margin_kept_frac"], 0.0))
            if numerics:
                diag["num_tie_rows"] = tie_proximity(
                    diag["margin_selection"], cut)
                diag["num_cancel_bits"] = gram_cancellation_bits(
                    Dm, mask=mask)
        return agg, diag

    # Presort once for the traced selection loop.
    order = jnp.argsort(Dm, axis=1)
    sortedD = jnp.take_along_axis(Dm, order, axis=1)
    finite = jnp.isfinite(sortedD)
    trips = -(-set_size // q)

    def body(t, carry):
        if margins:
            alive, selected, margin, slack, cut, last_scores = carry
        else:
            alive, selected = carry
        # Pool at trip start: everyone minus the t*q already selected.
        k = users_count - t * q - f - (2 if paper_scoring else 0)
        alive_cols = alive[order]                       # (n, n) gather
        rank = jnp.cumsum(alive_cols, axis=1)           # 1-based among alive
        take = alive_cols & (rank <= k) & finite
        scores = jnp.sum(jnp.where(take, sortedD, 0.0), axis=1)
        scores = jnp.where(alive, scores, _INF)
        # q lowest scores, ascending (ties -> lower index, like argmin);
        # only the first r count on the (possibly short) final trip.
        if margins:
            # One extra score — the first unselected, this trip's
            # selection cut; the first q entries of the widened top_k
            # are exactly the margins-off picks (same evaluation,
            # same tie resolution).
            kk = min(q + 1, n)
            neg_vals, idxs_all = lax.top_k(-scores, kk)
            idxs = idxs_all[:q]
        else:
            _, idxs = lax.top_k(-scores, q)
        r = jnp.minimum(q, set_size - t * q)
        live = jnp.arange(q) < r
        kill = jnp.zeros((n,), bool).at[idxs].set(live)
        selected = lax.dynamic_update_slice(
            selected, jnp.where(live, idxs, 0).astype(jnp.int32), (t * q,))
        if not margins:
            return alive & ~kill, selected
        vals = -neg_vals              # ascending kk smallest scores
        runner = jnp.take(vals, jnp.minimum(r, kk - 1), mode="clip")
        last_pick = jnp.take(vals, jnp.maximum(r - 1, 0), mode="clip")
        margin = margin.at[jnp.where(live, idxs, n)].set(
            runner - vals[:q], mode="drop")
        slack = slack.at[t].set(runner - last_pick)
        return alive & ~kill, selected, margin, slack, last_pick, scores

    alive0 = jnp.ones((n,), bool)
    sel0 = jnp.zeros((trips * q,), jnp.int32)
    if margins:
        (alive_f, selected, margin_sel, slack, cut,
         last_scores) = lax.fori_loop(
            0, trips, body,
            (alive0, sel0, jnp.zeros((n,), jnp.float32),
             jnp.zeros((trips,), jnp.float32), jnp.float32(0.0),
             jnp.zeros((n,), jnp.float32)))
    else:
        _, selected = lax.fori_loop(0, trips, body, (alive0, sel0))
    selected = selected[:set_size]

    selection = users_grads[selected]  # (set_size, d), in selection order
    number_to_consider = set_size - 2 * f - 1
    agg = trim_tail(selection, number_to_consider)
    if not telemetry:
        return agg
    diag = _bulyan_diag(n, selected, Dm, users_count, corrupted_count,
                        paper_scoring, method)
    if margins:
        # Losers measure against the final trip's last-pick score; the
        # trim-stage survival re-ranks the selection by the same key
        # trimmed_mean_of sorts by and scatters each selected row's
        # kept fraction back to its client slot.
        margin_sel = jnp.where(alive_f, cut - last_scores, margin_sel)
        med_s = jnp.median(selection, axis=0)
        tm = rank_keep_margins(jnp.abs(selection - med_s[None, :]),
                               number_to_consider)
        diag["margin_selection"] = margin_sel.astype(jnp.float32)
        diag["margin_gap"] = slack[trips - 1]
        diag["margin_slack"] = slack
        diag["margin_trim_kept"] = jnp.zeros(
            (n,), jnp.float32).at[selected].set(tm["margin_kept_frac"])
        if numerics:
            diag["num_tie_rows"] = tie_proximity(
                diag["margin_selection"], cut)
            diag["num_cancel_bits"] = gram_cancellation_bits(Dm)
    return agg, diag


# --- tier-2 (cross-shard) entries for hierarchical aggregation ----------
#
# The two-tier engine (ops/federated.py, core/engine.py
# aggregation='hierarchical') reduces per-megabatch tier-1 estimates with
# a SECOND robust pass over the (n/m, d) shard-estimate matrix.  Each
# shard_* entry is the corresponding flat kernel re-surfaced on that
# matrix: rows are shard estimates, ``shard_count`` plays users_count,
# ``corrupted_shards`` is the assumed number of colluder-controlled
# shards, and ``alive_counts`` (S,) int — the per-shard effective cohort
# from PR 2's fault masks — maps onto the kernels' existing quarantine
# ``mask=`` seam (a fully-dead shard's estimate can never win selection
# or touch a trim).  No new estimator math: the mask-aware paths are
# reused unchanged, which is what keeps tier-2 oracle-verified for free.
#
# Telemetry seam (ISSUE 8): every shard_* entry accepts the same
# trace-time ``telemetry=`` flag as the flat kernels and forwards it —
# the returned diagnostics pytree is the flat kernel's, re-read over
# the SHARD axis: a (S,) ``selection_mask`` says which shards'
# estimates the tier-2 reduction selected/kept/rejected, which is the
# raw material of the colluder-localization forensics (report.py).
# With it off (the default) the call is byte-for-byte the
# pre-telemetry path, same as the flat kernels' contract.

def check_weight_seam(mask, weights):
    """The staleness-weight seam (core/async_rounds.py) rides the
    quarantine mask: a ``weights=`` without a ``mask=`` has no
    delivered-cohort to weight and is a caller bug, rejected loudly."""
    if weights is not None and mask is None:
        raise ValueError(
            "defense weights= requires mask= (staleness weights apply "
            "to the delivered cohort only; core/async_rounds.py)")


def _alive_to_mask(alive_counts):
    return None if alive_counts is None else alive_counts > 0


def shard_mean(shard_estimates, shard_count, corrupted_shards,
               alive_counts=None, telemetry=False, margins=False,
               numerics=False):
    """Tier-2 NoDefense: alive-count-weighted mean of the shard
    estimates — with equal megabatches and no faults this is exactly
    the flat FedAvg mean (each estimate already averages m clients);
    with faults the weights restore the flat masked mean's
    per-client weighting.  ``telemetry=True`` returns ``(agg, {})`` —
    a mean rejects nothing, so there is nothing to attribute (and
    ``margins=`` / ``numerics=`` are likewise accepted and ignored: no
    decision boundary, no margin fields, no tie band)."""
    del corrupted_shards
    check_margin_seam(margins, telemetry)
    check_numerics_seam(numerics, margins)
    if alive_counts is None:
        agg = jnp.mean(shard_estimates, axis=0)
    else:
        w = alive_counts.astype(jnp.float32)
        agg = (w @ shard_estimates) / jnp.maximum(jnp.sum(w), 1.0)
    if not telemetry:
        return agg
    return agg, {}


def shard_krum(shard_estimates, shard_count, corrupted_shards,
               alive_counts=None, **kw):
    """Tier-2 Krum over shard estimates (mask-aware via alive counts)."""
    return krum(shard_estimates, shard_count, corrupted_shards,
                mask=_alive_to_mask(alive_counts), **kw)


def shard_trimmed_mean(shard_estimates, shard_count, corrupted_shards,
                       alive_counts=None, **kw):
    """Tier-2 median-anchored trimmed mean over shard estimates."""
    return trimmed_mean(shard_estimates, shard_count, corrupted_shards,
                        mask=_alive_to_mask(alive_counts), **kw)


def shard_bulyan(shard_estimates, shard_count, corrupted_shards,
                 alive_counts=None, **kw):
    """Tier-2 Bulyan over shard estimates (mask-aware via alive
    counts); the (S, S) distance pass is tiny — S = n/m shards."""
    return bulyan(shard_estimates, shard_count, corrupted_shards,
                  mask=_alive_to_mask(alive_counts), **kw)


def shard_median(shard_estimates, shard_count, corrupted_shards,
                 alive_counts=None, **kw):
    """Tier-2 coordinate-wise median over shard estimates."""
    # Local import: defenses/median.py imports DEFENSES from this module.
    from attacking_federate_learning_tpu.defenses.median import median
    return median(shard_estimates, shard_count, corrupted_shards,
                  mask=_alive_to_mask(alive_counts), **kw)


# Tier-2 dispatch surface (config.tier2_defense); tier-1 for the
# hierarchical engine is restricted to the same names — the mask-aware,
# oracle-verified kernel set.
#
# Group-sum seam (protocols/secagg.py, cfg.secagg='groupwise'): under
# group-wise secure aggregation the rows these kernels see are the
# per-megabatch SUMS the protocol exposes, scaled to means (sum / m) so
# they remain the same (S, d) estimate matrix the plain hierarchical
# tier produces — selection (Krum/Bulyan) is scale-covariant and the
# coordinate trims are row-wise, so no kernel changes: the only
# difference between "tier-2 over tier-1 estimates" and "tier-2 over
# secagg group sums" is which tensor the server was ever allowed to
# see, which is exactly the NET-SA measurement surface.
TIER2_DEFENSES = {"NoDefense": shard_mean, "Krum": shard_krum,
                  "TrimmedMean": shard_trimmed_mean,
                  "Bulyan": shard_bulyan, "Median": shard_median}


def check_tier2_args(name, shard_count, corrupted_shards):
    """Fail-fast validity for the tier-2 reduction: the Krum/Bulyan
    bounds via :func:`check_defense_args`, plus the trimmed mean's
    keep-count floor (S - f2 - 1 >= 1) that the flat path never hits
    because n >> f."""
    check_defense_args(name, shard_count, corrupted_shards)
    if (name in ("TrimmedMean",)
            and shard_count - corrupted_shards - 1 < 1):
        raise ValueError(
            f"tier-2 TrimmedMean keeps shard_count - corrupted_shards - 1 "
            f"estimates; got S={shard_count}, f2={corrupted_shards}")


def check_defense_args(name, users_count, corrupted_count):
    """Host-side guards mirroring the reference asserts (defences.py:25
    n >= 2f+1 for Krum; defences.py:56 n >= 4f+3 for Bulyan)."""
    if name == "Krum" and users_count < 2 * corrupted_count + 1:
        raise ValueError(
            f"Krum requires users_count >= 2*corrupted_count + 1 "
            f"(got n={users_count}, f={corrupted_count})")
    if name == "Bulyan" and users_count < 4 * corrupted_count + 3:
        raise ValueError(
            f"Bulyan requires users_count >= 4*corrupted_count + 3 "
            f"(got n={users_count}, f={corrupted_count})")
