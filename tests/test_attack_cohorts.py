"""The XLA defense kernels on attack-shaped cohorts, against the f64 oracle.

``tests/test_defenses.py::test_matches_oracle`` holds the kernels to
``defenses/oracle.py`` on Gaussian cohorts.  Real rounds are not Gaussian:
ALIE colluders send bit-identical rows at the z-envelope, a boosted
backdoor row sits far outside the honest cluster, sign-flippers mirror
honest rows.  Identical rows are exact score ties in exact arithmetic and
near-ties in f32 (a zero distance evaluated by Gram cancellation carries
~||g||·sqrt(2·eps) of noise), so this is where a change to the sort or to
the Gram's accumulation order shows first.  Every sweep here compares an
XLA kernel with the oracle evaluated in f64 on the same f32 rows, or a
mask-aware kernel with the oracle on the survivors' submatrix; a winner
may differ from the oracle's only inside the tie band, adjudicated by the
oracle's own f64 score gap.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from attacking_federate_learning_tpu.defenses import kernels as K
from attacking_federate_learning_tpu.defenses import oracle as O
from attacking_federate_learning_tpu.defenses.median import median
from attacking_federate_learning_tpu.ops import distances as D

EPS = float(np.finfo(np.float32).eps)


def _cohort(n, d, f, attack, seed=0):
    """The pinned defense x attack configs' gradient geometry, built
    directly: rows [0, f) are the colluders'."""
    rng = np.random.default_rng(seed)
    G = rng.standard_normal((n, d)).astype(np.float32)
    if attack == "alie":
        mu, sigma = G[f:].mean(0), G[f:].std(0)
        G[:f] = mu + 1.5 * sigma          # identical crafted rows: ties
    elif attack == "backdoor":
        G[:f] = 8.0 * rng.standard_normal(d).astype(np.float32)
    elif attack == "signflip":
        G[:f] = -G[f:2 * f]
    return G


_CASES = [(19, 300, 4, "none"), (21, 777, 5, "alie"),
          (32, 512, 8, "backdoor"), (24, 100, 6, "signflip"),
          (13, 79, 3, "alie"), (64, 1024, 15, "alie")]
# f = 1 is the empty complement under reference scoring (topk's scores ARE
# the rowsums); d = 79,510 is the MLP's wire, which nothing divides.
_KRUM_CASES = _CASES + [(11, 200, 1, "none"), (12, 79_510, 3, "alie")]


def _ids(cases):
    return [f"{n}-{d}-{f}-{attack}" for n, d, f, attack in cases]


def _degenerate_pair_band(G, f):
    """Identical crafted rows have zero distances evaluated by Gram
    cancellation: |d2_err| ~ eps·||g||², so each such pair's distance
    carries ~||g||·sqrt(2·eps) of engine-dependent noise and a crafted
    row's score up to f times that (measured to match within 2x; 4x
    safety).  A cohort without identical rows has no such pair: its
    scores stay at relative-ulp level and the band is zero."""
    if len(np.unique(G, axis=0)) == len(G):
        return 0.0
    max_norm = float(np.max(np.linalg.norm(G, axis=1)))
    return 4.0 * f * max_norm * float(np.sqrt(2.0 * EPS))


def _same_pick(G, got, want, scores64, band):
    """The kernel's winner is the oracle's, or an identical row, or a row
    whose f64 score is within the tie band of the oracle winner's."""
    return (got == want or np.array_equal(G[got], G[want])
            or abs(scores64[got] - scores64[want])
            <= band + 32 * EPS * scores64[want])


def _mask(n, seed, p_dead=0.25):
    alive = np.random.default_rng(seed).random(n) > p_dead
    return alive, np.flatnonzero(alive)


def _quarantined(G, alive):
    """The engine zeroes dead rows before the defense (core/faults.py)."""
    return jnp.asarray(np.where(alive[:, None], G, 0.0).astype(np.float32))


# ---------------------------------------------------------------------------
# Krum: scores and winner

@pytest.mark.parametrize("method", ["sort", "topk"])
@pytest.mark.parametrize("paper_scoring", [False, True],
                         ids=["reference", "paper"])
@pytest.mark.parametrize("n,d,f,attack", _KRUM_CASES,
                         ids=_ids(_KRUM_CASES))
def test_krum_scores_match_f64_oracle(n, d, f, attack, paper_scoring,
                                      method):
    G = _cohort(n, d, f, attack)
    want = O.np_krum_scores(G.astype(np.float64), n, f,
                            paper_scoring=paper_scoring)
    got = np.asarray(K._krum_scores(
        D.pairwise_distances(jnp.asarray(G)), n, f,
        paper_scoring=paper_scoring, method=method))
    band = _degenerate_pair_band(G, f)
    np.testing.assert_allclose(got, want, rtol=5e-6, atol=band)
    assert _same_pick(G, int(np.argmin(got)), int(np.argmin(want)), want,
                      band)


@pytest.mark.parametrize("n,d,f,attack", _CASES, ids=_ids(_CASES))
def test_krum_masked_equals_survivor_submatrix(n, d, f, attack):
    G = _cohort(n, d, f, attack)
    alive, keep = _mask(n, seed=n)
    scores = O.np_krum_scores(G[keep].astype(np.float64), len(keep), f)
    want = int(keep[np.argmin(scores)])
    got = int(K.krum_select(_quarantined(G, alive), n, f,
                            mask=jnp.asarray(alive)))
    assert alive[got]
    scores64 = np.full(n, np.inf)
    scores64[keep] = scores
    assert _same_pick(G, got, want, scores64, _degenerate_pair_band(G, f))


# ---------------------------------------------------------------------------
# trimmed mean and median: summation-order ulps against f64

@pytest.mark.parametrize("n,d,f,attack", _CASES, ids=_ids(_CASES))
def test_trimmed_mean_matches_f64_oracle(n, d, f, attack):
    G = _cohort(n, d, f, attack)
    want = O.np_trimmed_mean(G.astype(np.float64), n, f)
    got = np.asarray(K.trimmed_mean(jnp.asarray(G), n, f))
    np.testing.assert_allclose(got, want, rtol=3e-6, atol=3e-6)


@pytest.mark.parametrize("n,d,f,attack", _CASES, ids=_ids(_CASES))
def test_masked_trimmed_mean_equals_survivor_submatrix(n, d, f, attack):
    G = _cohort(n, d, f, attack)
    alive, keep = _mask(n, seed=n)
    want = O.np_trimmed_mean(G[keep].astype(np.float64), len(keep), f)
    Gz, mask = _quarantined(G, alive), jnp.asarray(alive)
    got = np.asarray(K.trimmed_mean(Gz, n, f, mask=mask))
    np.testing.assert_allclose(got, want, rtol=3e-6, atol=3e-6)
    # The staleness seam's branch at one common weight is the same
    # estimator: sum(w·kept) / sum(w).
    got_w = np.asarray(K.trimmed_mean(
        Gz, n, f, mask=mask, weights=jnp.full((n,), 0.7, jnp.float32)))
    np.testing.assert_allclose(got_w, want, rtol=3e-6, atol=3e-6)


_MEDIAN_SHAPES = [(19, 777), (22, 256), (13, 79)]


def _median_cohort(n, d):
    return _cohort(n, d, n // 4, "alie", seed=n * d)


@pytest.mark.parametrize("n,d", _MEDIAN_SHAPES)
def test_median_matches_f64(n, d):
    G = _median_cohort(n, d)
    want = np.median(G.astype(np.float64), axis=0)
    got = np.asarray(median(jnp.asarray(G), n, n // 4))
    # A middle element, or the correctly rounded mean of two.
    np.testing.assert_allclose(got, want, rtol=EPS, atol=0)


@pytest.mark.parametrize("n,d", _MEDIAN_SHAPES)
def test_masked_median_equals_survivor_submatrix(n, d):
    G = _median_cohort(n, d)
    alive, keep = _mask(n, seed=n * d, p_dead=0.3)
    Gz, mask = _quarantined(G, alive), jnp.asarray(alive)
    want = np.median(G[keep].astype(np.float64), axis=0)
    got = np.asarray(median(Gz, n, n // 4, mask=mask))
    np.testing.assert_allclose(got, want, rtol=EPS, atol=0)
    # Equal weights: the weighted LOWER median is an element of the
    # column, the survivors' order statistic (e - 1) // 2 — exact.
    lower = np.sort(G[keep], axis=0)[(len(keep) - 1) // 2]
    got_w = np.asarray(median(Gz, n, n // 4, mask=mask,
                              weights=jnp.ones((n,), jnp.float32)))
    np.testing.assert_array_equal(got_w, lower)


# ---------------------------------------------------------------------------
# Bulyan: selection loop + trim tail

_BULYAN_CASES = [(19, 300, 4, "alie"), (23, 512, 5, "backdoor"),
                 (32, 200, 7, "signflip")]


@pytest.mark.parametrize("n,d,f,attack", _BULYAN_CASES,
                         ids=_ids(_BULYAN_CASES))
def test_bulyan_matches_f64_oracle(n, d, f, attack):
    """Which of several identical rows the loop takes is free; how many
    it takes is not, and a different selection SET moves the aggregate
    by far more than summation order does."""
    G = _cohort(n, d, f, attack)
    want = O.np_bulyan(G.astype(np.float64), n, f)
    got, diag = K.bulyan(jnp.asarray(G), n, f, telemetry=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-6,
                               atol=3e-6)
    assert int(np.sum(np.asarray(diag["selection_mask"]))) == n - 2 * f


def test_bulyan_masked_equals_survivor_submatrix():
    n, d, f = 27, 300, 4
    G = _cohort(n, d, f, "alie")
    alive, keep = _mask(n, seed=2, p_dead=0.2)
    assert len(keep) >= 4 * f + 3
    want = O.np_bulyan(G[keep].astype(np.float64), len(keep), f)
    got = np.asarray(K.bulyan(_quarantined(G, alive), n, f,
                              mask=jnp.asarray(alive)))
    np.testing.assert_allclose(got, want, rtol=3e-6, atol=3e-6)


# ---------------------------------------------------------------------------
# the Gram's block triangle (ops/distances.py, PR 31) on these cohorts

@pytest.mark.parametrize("n,d,f,attack", _CASES, ids=_ids(_CASES))
def test_gram_triangle_on_attack_cohorts(n, d, f, attack, monkeypatch):
    block = 4 if n < 16 else 8 if n < 64 else 16
    # The colluders' identical rows straddle the first block edge.
    G = np.roll(_cohort(n, d, f, attack), block - f // 2, axis=0)
    taken = []
    inner = D._symmetric_gram

    def spy(G, precision, block):
        taken.append((G.shape[0], block))
        return inner(G, precision, block)

    monkeypatch.setattr(D, "GRAM_BLOCK_ROWS", block)
    monkeypatch.setattr(D, "_symmetric_gram", spy)
    Gj = jnp.asarray(G)
    panels = np.asarray(D.pairwise_distances(Gj))
    got = int(K.krum_select(Gj, n, f))
    assert taken == [(n, block)] * 2

    np.testing.assert_array_equal(panels, panels.T)
    np.testing.assert_array_equal(np.diag(panels), np.zeros(n, np.float32))
    G64 = G.astype(np.float64)
    pair_band = _degenerate_pair_band(G, 1)
    np.testing.assert_allclose(panels, O.np_pairwise_distances(G64),
                               rtol=5e-6, atol=pair_band)
    one_dot = D.zero_diagonal(jnp.sqrt(D.cross_sq_distances(Gj, Gj)))
    want = int(jnp.argmin(K._krum_scores(one_dot, n, f)))
    assert _same_pick(G, got, want, O.np_krum_scores(G64, n, f),
                      _degenerate_pair_band(G, f))


# ---------------------------------------------------------------------------
# the f32 tie-break band (tests/test_native.py's standard)

# n, d on and off the powers of two; f = 24 % of n, as the cells run.
_TIE_SHAPES = [(10, 45), (16, 128), (19, 97), (27, 199)]


def _tie_band_trial(select, rng, trial, n, d, f):
    """One randomized cohort through Krum's two evaluators; returns
    whether the winners differ.  A flip must sit inside the f32
    score-indeterminacy band, adjudicated by the oracle's f64 scores
    (bench.py:adjudicate_f32_flip is the template)."""
    G = rng.standard_normal((n, d)).astype(np.float32)
    if trial % 3 == 0:
        G[:f] = G[f:].mean(0) + 0.5 * G[f:].std(0)  # near-tie regime
    Gj = jnp.asarray(G)
    a, b = int(select["sort"](Gj, n, f)), int(select["topk"](Gj, n, f))
    if a == b:
        return False
    scores64 = O.np_krum_scores(G.astype(np.float64), n, f)
    gap = abs(scores64[a] - scores64[b])
    band = 32 * EPS * max(scores64[a], scores64[b])
    assert gap <= band, (
        f"trial {trial} (n={n}, d={d}): winners {a} vs {b} diverge "
        f"outside the f32 tie band (gap {gap:.3e} > band {band:.3e})")
    return True


def test_krum_sort_vs_topk_tie_band_sweep():
    """120 randomized cohorts over four shapes (eight compiles): the
    complement identity may pick another winner than the sort only
    inside the f32 tie band."""
    select = {m: jax.jit(functools.partial(K.krum_select, method=m),
                         static_argnums=(1, 2)) for m in ("sort", "topk")}
    flips = 0
    for trial in range(120):
        n, d = _TIE_SHAPES[trial % 4]
        flips += _tie_band_trial(select, np.random.default_rng(20_000 + trial),
                                 trial, n, d, max(1, int(0.24 * n)))
    assert flips < 30


def test_duplicate_row_ties_resolve_as_the_oracle_does():
    """Exact duplicate rows are exact score ties in f64, where the
    oracle takes the first.  The f32 kernel may take another row only
    if it is the same row, or inside the tie band."""
    n, d, f = 20, 128, 4
    G = _cohort(n, d, f, "none", seed=9)
    G[7] = G[11]
    G[:f] = G[0]
    scores64 = O.np_krum_scores(G.astype(np.float64), n, f)
    want = int(np.argmin(scores64))
    for method in ("sort", "topk"):
        got = int(K.krum_select(jnp.asarray(G), n, f, method=method))
        assert _same_pick(G, got, want, scores64,
                          _degenerate_pair_band(G, f)), (method, got, want)
