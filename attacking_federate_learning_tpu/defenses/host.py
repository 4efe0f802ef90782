"""Host-BLAS defense kernels for the CPU backend.

Backend-aware kernel dispatch: on TPU the Krum/Bulyan distance engine is an
MXU Gram matmul (ops/distances.py), but XLA:CPU's
single-threaded gemm and sort are ~2x slower than the host's native BLAS on
this class of machine (measured: 433 ms XLA:CPU vs 226 ms OpenBLAS for the
(512, 79510) Gram).  So when the active backend is CPU the defense kernels
route the whole aggregation to these NumPy/BLAS implementations via
``jax.pure_callback`` (defenses/kernels.py ``distance_impl='host'``),
exactly like any production framework picks a different kernel per backend.

Semantics are identical to the reference variants (reference
defences.py:16-70, SURVEY.md §2.4 #4-6) and to the XLA kernels: Krum scores
sum the ``users_count - corrupted_count`` smallest distances (sum of a set,
so ``np.partition`` replaces the full row sort without changing the value);
ties resolve to the lowest index (first-occurrence ``np.argmin``, matching
reference defences.py:35); Bulyan's pool shrinks per selection while f stays
fixed.  Unlike defenses/oracle.py (a deliberately naive test oracle), this
module is a production path and is itself verified against the oracle in
tests/test_defenses.py.
"""

from __future__ import annotations

import numpy as np


def host_sq_distances(G: np.ndarray) -> np.ndarray:
    """(n, d) f32 -> (n, n) squared Euclidean distances, +inf diagonal.

    One BLAS Gram matmul + in-place epilogue — the same
    ``||g_i||^2 + ||g_j||^2 - 2 G G^T`` decomposition as the XLA kernel
    (ops/distances.py), so both paths compute identical values to f32
    tolerance.  The squared norms are read off the Gram diagonal (they ARE
    the diagonal), saving a full O(n d) pass, and the epilogue mutates the
    Gram buffer so no second n^2 array is allocated."""
    gram = G @ G.T
    sq = gram.diagonal().copy()
    gram *= -2.0
    gram += sq[:, None]
    gram += sq[None, :]
    np.maximum(gram, 0.0, out=gram)
    np.fill_diagonal(gram, np.inf)
    return gram


def host_pairwise_distances(G: np.ndarray) -> np.ndarray:
    """(n, d) f32 -> (n, n) Euclidean distances with +inf diagonal."""
    d2 = host_sq_distances(G)
    D = np.sqrt(d2, out=d2)
    np.fill_diagonal(D, np.inf)  # sqrt(inf) is inf, but keep it explicit
    return D


def _prefix_scores(sortedD, order, finite, alive, pool, f,
                   paper_scoring=False):
    """Sum of the k smallest alive distances per row, evaluated as an
    alive-masked rank prefix over presorted rows (same presort-once
    scheme as the XLA Bulyan, defenses/kernels.py); +inf for dead rows.
    k = pool - f, or pool - f - 2 under paper scoring (SURVEY.md §2.4
    #4)."""
    k = pool - f - (2 if paper_scoring else 0)
    alive_cols = alive[order]
    rank = np.cumsum(alive_cols, axis=1)
    take = alive_cols & (rank <= k) & finite
    scores = np.where(take, sortedD, 0.0).sum(axis=1)
    scores[~alive] = np.inf
    return scores


def host_krum_index(G, users_count, corrupted_count, paper_scoring=False):
    """Krum winner index (reference defences.py:23-42 semantics,
    ``return_index=True`` shape).

    Selection of the k nearest peers happens on *squared* distances
    (monotone in the true distance), so the sqrt runs only over the n*k
    selected entries instead of the full n^2 matrix; the score itself sums
    the square-rooted values, identical to the reference's norm sum."""
    G = np.asarray(G, np.float32)
    n = G.shape[0]
    d2 = host_sq_distances(G)
    k = users_count - corrupted_count - (2 if paper_scoring else 0)
    k = max(min(k, n - 1), 0)
    if k == 0:
        return 0
    part = np.partition(d2, k - 1, axis=1)[:, :k]
    scores = np.sqrt(part, out=part).sum(axis=1)
    return int(np.argmin(scores))


def host_krum(G, users_count, corrupted_count, paper_scoring=False):
    """Krum winner row."""
    G = np.asarray(G, np.float32)
    return G[host_krum_index(G, users_count, corrupted_count,
                             paper_scoring=paper_scoring)]


def _all_finite(a: np.ndarray) -> bool:
    """Full-finiteness check without materializing an (n, d) bool temp
    (420 MB at the 10k north-star tail): two scalar reductions — NaN
    propagates through min/max, ±inf is its own extremum."""
    return bool(np.isfinite(a.min()) and np.isfinite(a.max()))


def host_median(sel: np.ndarray):
    """Coordinate-wise median (defenses/median.py host path): the native
    column-blocked kernel when available AND the input is fully finite
    (std::nth_element on NaN is undefined behavior, and np.median's
    NaN-propagation must be preserved); np.median otherwise."""
    sel = np.asarray(sel, np.float32)
    if sel.size and _all_finite(sel):
        from attacking_federate_learning_tpu.native import native_median
        out = native_median(sel)
        if out is not None:
            return out
    return np.median(sel, axis=0).astype(np.float32)


def host_trimmed_mean_of(sel: np.ndarray, number_to_consider: int):
    """Median-anchored trimmed mean (reference defences.py:48-51), stable
    order on |deviation| to match Python's stable ``sorted``.

    Dispatches to the native column-blocked kernel
    (native/bulyan_select.cpp:fl_trimmed_mean) when available — the
    NumPy axis-0 formulation pays strided access across the whole (n, d)
    matrix for median/sort/masks, ~105 s at the exact-Bulyan 10k tail
    where the native kernel takes seconds.  Identical semantics
    (boundary ties keep the lowest row indices), pinned by
    tests/test_defenses.py::test_host_trimmed_mean_partition_matches_stable_sort."""
    sel = np.asarray(sel, np.float32)
    k = int(number_to_consider)
    if 0 < k <= sel.shape[0] and sel.size and _all_finite(sel):
        from attacking_federate_learning_tpu.native import (
            native_trimmed_mean
        )
        out = native_trimmed_mean(sel, k)
        if out is not None:
            return out
    med = np.median(sel, axis=0)
    dev = sel - med
    order = np.argsort(np.abs(dev), axis=0, kind="stable")
    kept = np.take_along_axis(dev, order[:k], axis=0)
    return (kept.mean(axis=0) + med).astype(np.float32)


def numpy_bulyan_selection(D, order, users_count, corrupted_count,
                           set_size, batch_select=1, paper_scoring=False):
    """Reference NumPy selection loop: presort-once, alive-masked rank
    prefixes, O(n^2) scoring per trip.  Kept as the semantic anchor and
    the fallback when the native kernel is unavailable."""
    n = D.shape[0]
    f = corrupted_count
    q = min(max(int(batch_select), 1), set_size)
    sortedD = np.take_along_axis(D, order, axis=1)
    finite = np.isfinite(sortedD)
    alive = np.ones(n, bool)
    selected = []
    while len(selected) < set_size:
        r = min(q, set_size - len(selected))
        scores = _prefix_scores(sortedD, order, finite, alive,
                                users_count - len(selected), f,
                                paper_scoring=paper_scoring)
        idxs = np.argsort(scores, kind="stable")[:r]
        selected.extend(int(i) for i in idxs)
        alive[idxs] = False
    return np.asarray(selected, np.int32)


def host_bulyan_selection(D, users_count, corrupted_count, set_size,
                          batch_select=1, paper_scoring=False):
    """Selected client indices, in selection order.

    Dispatches to the native incremental kernel
    (native/bulyan_select.cpp — O(n^2) total instead of O(n^2) *per
    selection*, which is what makes exact q=1 tractable at n=10,240)
    and falls back to :func:`numpy_bulyan_selection`.  Both produce the
    same selection: the scores are alive-prefix sums over each presorted
    row, invariant to tie order inside the sort (equal values are
    interchangeable within the prefix), and selection ties resolve to
    the lowest client index in both."""
    order = np.argsort(D, axis=1).astype(np.int32, copy=False)
    from attacking_federate_learning_tpu.native import (
        native_bulyan_selection
    )
    sel = native_bulyan_selection(D, order, users_count, corrupted_count,
                                  set_size, batch_select=batch_select,
                                  paper_scoring=paper_scoring)
    if sel is None:
        sel = numpy_bulyan_selection(D, order, users_count,
                                     corrupted_count, set_size,
                                     batch_select=batch_select,
                                     paper_scoring=paper_scoring)
    return sel


def host_bulyan(G, users_count, corrupted_count, paper_scoring=False,
                batch_select=1):
    """Bulyan (reference defences.py:55-70): iterative Krum selection with
    a shrinking pool, then trimmed mean with parameter 2f.

    ``batch_select=q`` mirrors the XLA kernel's flagged relaxation
    (defenses/kernels.py:bulyan): each trip takes the q lowest-scoring
    alive clients against the same scores (ties to the lowest index,
    matching both first-occurrence ``np.argmin`` and ``lax.top_k``),
    re-scoring between trips.  q=1 is reference-exact — and with the
    native incremental kernel it is also *fast* at 10k clients, so q=1
    stays the host default at every scale."""
    G = np.asarray(G, np.float32)
    f = corrupted_count
    set_size = users_count - 2 * f
    D = host_pairwise_distances(G)
    selected = host_bulyan_selection(D, users_count, f, set_size,
                                     batch_select=batch_select,
                                     paper_scoring=paper_scoring)
    sel = G[selected]
    return host_trimmed_mean_of(sel, set_size - 2 * f - 1)
