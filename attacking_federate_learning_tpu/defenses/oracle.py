"""NumPy oracle implementations of the defenses, for testing only.

Independent array-based re-derivations of the reference semantics
(reference defences.py:13-70), used by tests/test_defenses.py to verify the
XLA kernels.  Written against the *behavior* documented in SURVEY.md §2.4
(n-f Krum scoring, median-anchored trim, shrinking-pool Bulyan); kept
deliberately simple and loop-free where possible so a bug here is unlikely
to coincide with a bug in the kernels.
"""

from __future__ import annotations

import numpy as np


def np_pairwise_distances(G):
    diffs = G[:, None, :] - G[None, :, :]
    return np.linalg.norm(diffs, axis=-1)


def np_no_defense(G, users_count, corrupted_count):
    return np.mean(G, axis=0)


def np_krum_scores(G, users_count, corrupted_count, alive=None, D=None,
                   paper_scoring=False):
    """Per-user Krum score among alive users (+inf for the dead): the sum
    of the k = n - f nearest distances (k = n - f - 2 under the NIPS'17
    paper's scoring, SURVEY.md §2.4 #4)."""
    n = G.shape[0]
    if D is None:
        D = np_pairwise_distances(G)
    if alive is None:
        alive = np.ones(n, bool)
    k = users_count - corrupted_count - (2 if paper_scoring else 0)
    scores = np.full(n, np.inf)
    for i in range(n):
        if not alive[i]:
            continue
        others = [D[i, j] for j in range(n) if j != i and alive[j]]
        scores[i] = float(np.sum(np.sort(others)[:k]))
    return scores


def np_krum_select(G, users_count, corrupted_count, alive=None, D=None):
    """Index of the Krum winner among alive users (the first of equals)."""
    return int(np.argmin(np_krum_scores(G, users_count, corrupted_count,
                                        alive=alive, D=D)))


def np_krum(G, users_count, corrupted_count):
    return G[np_krum_select(G, users_count, corrupted_count)]


def np_trimmed_mean(G, users_count, corrupted_count):
    keep = G.shape[0] - corrupted_count - 1
    med = np.median(G, axis=0)
    dev = G - med
    order = np.argsort(np.abs(dev), axis=0, kind="stable")
    kept = np.take_along_axis(dev, order[:keep], axis=0)
    return np.mean(kept, axis=0) + med


def np_bulyan(G, users_count, corrupted_count):
    n = G.shape[0]
    f = corrupted_count
    set_size = users_count - 2 * f
    D = np_pairwise_distances(G)
    alive = np.ones(n, bool)
    selected = []
    while len(selected) < set_size:
        idx = np_krum_select(G, users_count - len(selected), f,
                             alive=alive, D=D)
        selected.append(idx)
        alive[idx] = False
    return np_trimmed_mean(G[selected], set_size, 2 * f)


NP_DEFENSES = {
    "NoDefense": np_no_defense,
    "Krum": np_krum,
    "TrimmedMean": np_trimmed_mean,
    "Bulyan": np_bulyan,
}
