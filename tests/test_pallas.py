"""Pallas kernel suite vs the XLA references (interpret mode on CPU;
the Mosaic-compiled parity check is chip_smoke.py's kernel leg).

Parity contract (ISSUE 11, mirrored in PARITY.md):

- masked/weighted trimmed mean + median kernels replicate
  defenses/kernels.py's masked estimators op for op — pinned
  BIT-EXACT;
- unmasked trimmed mean / median and the fused Krum scores are
  ulp-bounded (the whole-matrix XLA program fuses its arithmetic
  differently than the tiled one — the same summation-order contract
  as the native host kernels, tests/test_native.py);
- selection outputs (Krum winner, Bulyan selection set) are bit-exact
  whenever the f32 score gap clears the tie band; inside the band a
  flip is legal and adjudicated with an f64 re-score, exactly the
  test_native standard.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from attacking_federate_learning_tpu.defenses.kernels import (
    _krum_scores, bulyan, krum, krum_select, masked_median,
    masked_trimmed_mean_of, trimmed_mean, trimmed_mean_of
)
from attacking_federate_learning_tpu.defenses.median import median
from attacking_federate_learning_tpu.ops.distances import pairwise_distances
from attacking_federate_learning_tpu.ops.pallas_distances import (
    pallas_pairwise_distances
)
from attacking_federate_learning_tpu.ops.pallas_defense import (
    krum_scores_cost, pallas_krum_scores, pallas_masked_median,
    pallas_masked_trimmed_mean, pallas_median_of, pallas_trimmed_mean_of
)


@pytest.mark.parametrize("n,d", [(16, 100), (40, 300), (64, 512)])
def test_pallas_matches_xla(n, d):
    rng = np.random.default_rng(n + d)
    G = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    want = np.asarray(pairwise_distances(G))
    got = np.asarray(pallas_pairwise_distances(G, bm=8, bn=8, bk=128,
                                               interpret=True))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_pallas_padding_is_harmless():
    # n and d far from the block multiples.
    rng = np.random.default_rng(0)
    G = jnp.asarray(rng.standard_normal((13, 77)).astype(np.float32))
    want = np.asarray(pairwise_distances(G))
    got = np.asarray(pallas_pairwise_distances(G, bm=8, bn=8, bk=128,
                                               interpret=True))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_pallas_unequal_tile_sizes():
    """bm != bn requires lcm padding — every output tile must be written."""
    rng = np.random.default_rng(7)
    G = jnp.asarray(rng.standard_normal((20, 64)).astype(np.float32))
    want = np.asarray(pairwise_distances(G))
    got = np.asarray(pallas_pairwise_distances(G, bm=8, bn=16, bk=64,
                                               interpret=True))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# attack-shaped cohort matrices: the pinned defense x attack configs'
# gradient geometry, built directly (identical ALIE colluder rows at the
# z-envelope, a boosted backdoor row, sign-flipped rows) so the parity
# suite exercises the tie structure real rounds produce.

def _cohort(n, d, f, attack, seed=0):
    rng = np.random.default_rng(seed)
    G = rng.standard_normal((n, d)).astype(np.float32)
    if attack == "alie":
        mu, sigma = G[f:].mean(0), G[f:].std(0)
        G[:f] = mu + 1.5 * sigma          # identical crafted rows: ties
    elif attack == "backdoor":
        G[:f] = 8.0 * rng.standard_normal(d).astype(np.float32)
    elif attack == "signflip":
        G[:f] = -G[f:2 * f] if f else G[:f]
    return jnp.asarray(G)


_CASES = [(19, 300, 4, "none"), (21, 777, 5, "alie"),
          (32, 512, 8, "backdoor"), (24, 100, 6, "signflip"),
          (13, 79, 3, "alie"), (64, 1024, 15, "alie")]


# ---------------------------------------------------------------------------
# fused distance -> Krum score kernel

def _degenerate_pair_band(f, G):
    """Identical crafted rows have zero distances evaluated by Gram
    cancellation: |d2_err| ~ eps·||g||², so each such pair's distance
    carries ~||g||·sqrt(2·eps) of engine-dependent noise and a crafted
    row's score up to f times that (measured to match within 2x; 4x
    safety).  Honest decisive rows stay at relative-ulp level."""
    max_norm = float(np.max(np.linalg.norm(np.asarray(G), axis=1)))
    return 4.0 * f * max_norm * float(
        np.sqrt(2.0 * np.finfo(np.float32).eps))


@pytest.mark.parametrize("n,d,f,attack", _CASES)
@pytest.mark.parametrize("paper_scoring", [False, True])
def test_fused_krum_scores_match_sort_path(n, d, f, attack,
                                           paper_scoring):
    G = _cohort(n, d, f, attack)
    want = np.asarray(_krum_scores(pairwise_distances(G), n, f,
                                   paper_scoring=paper_scoring))
    got, rowsum = pallas_krum_scores(G, n, f,
                                     paper_scoring=paper_scoring,
                                     bm=8, bn=8, bk=128, interpret=True)
    band = _degenerate_pair_band(f, G)
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-6,
                               atol=band)
    # The winner is the defense output: it must agree outside the tie
    # band (crafted cohorts hold EXACT-duplicate rows whose scores
    # differ only by degenerate-pair noise — a flip among those is a
    # legal tie, adjudicated against the reference's own score gap).
    ga, wa = int(np.argmin(np.asarray(got))), int(np.argmin(want))
    assert ga == wa or abs(want[ga] - want[wa]) <= band
    assert np.all(np.isfinite(np.asarray(rowsum)))


@pytest.mark.parametrize("bm,bn,bk", [(8, 8, 128), (8, 16, 64),
                                      (16, 8, 256)])
def test_fused_krum_scores_tile_boundaries(bm, bn, bk):
    """n, d far from every block multiple (incl. bm != bn lcm padding)."""
    G = _cohort(23, 333, 5, "alie", seed=3)
    want = np.asarray(_krum_scores(pairwise_distances(G), 23, 5))
    got, _ = pallas_krum_scores(G, 23, 5, bm=bm, bn=bn, bk=bk,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-6,
                               atol=1e-4)


def test_fused_krum_scores_wire_dim():
    """The production wire dim (d=79510, nothing divides cleanly)."""
    G = _cohort(12, 79_510, 3, "alie", seed=1)
    want = np.asarray(_krum_scores(pairwise_distances(G), 12, 3))
    got, _ = pallas_krum_scores(G, 12, 3, bm=8, bn=8, bk=512,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-6,
                               atol=2e-3)


def test_fused_krum_complement_zero():
    """f=1 (reference scoring) has an empty complement: scores ARE the
    rowsums — no subtraction, no guard, still the sort path's values."""
    G = _cohort(11, 200, 1, "none", seed=5)
    want = np.asarray(_krum_scores(pairwise_distances(G), 11, 1))
    got, rowsum = pallas_krum_scores(G, 11, 1, bm=8, bn=8, bk=128,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(rowsum))


def test_pallas_krum_dispatch_guard_falls_back_to_sort():
    """Adversarial magnitudes (reference malicious.py scale) concentrate
    the rowsum in the complement; the dispatch's cancellation guard must
    re-evaluate via the exact sort path — the selected index must match
    the oracle-verified sort evaluation, not the cancelled subtraction."""
    n, d, f = 19, 300, 4
    G = np.array(_cohort(n, d, f, "none"), copy=True)
    G[:f] *= 1e18                       # cancellation regime
    G = jnp.asarray(G)
    want = int(krum_select(G, n, f, distance_impl="xla"))
    got = int(krum_select(G, n, f, scores_impl="pallas"))
    assert got == want


def test_pallas_krum_kernel_entry():
    """krum(scores_impl='pallas') returns an exact input row (selection
    defense: agreement on the winner == bit-exact aggregate)."""
    G = _cohort(21, 400, 5, "alie")
    want = np.asarray(krum(G, 21, 5))
    got = np.asarray(krum(G, 21, 5, scores_impl="pallas"))
    np.testing.assert_array_equal(got, want)
    # telemetry carries the fused scores (real values, not NaN slots)
    agg, diag = krum(G, 21, 5, scores_impl="pallas", telemetry=True)
    assert np.isfinite(np.asarray(diag["scores"])).all()
    assert int(np.argmax(np.asarray(diag["selection_mask"]))) == int(
        np.argmin(np.asarray(diag["scores"])))


def test_pallas_krum_masked_path_matches_xla():
    """Quarantine mask forces the exact sort evaluator over the pallas
    distance matrix; winners must match the xla masked path."""
    n, d, f = 21, 300, 5
    G = _cohort(n, d, f, "alie")
    mask = jnp.asarray(np.random.default_rng(0).random(n) > 0.25)
    want = np.asarray(krum(G, n, f, mask=mask))
    got = np.asarray(krum(G, n, f, mask=mask, scores_impl="pallas"))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# tiled trimmed mean / median (masked bit-exact, unmasked ulp-bounded)

@pytest.mark.parametrize("n,d,f,attack", _CASES)
def test_pallas_trimmed_mean_ulp_bounded(n, d, f, attack):
    G = _cohort(n, d, f, attack)
    k = n - f - 1
    want = np.asarray(trimmed_mean_of(G, k))
    got = np.asarray(pallas_trimmed_mean_of(G, k, interpret=True))
    # Summation-order ulps only (the host-kernel contract): a few ulp
    # at these magnitudes.
    np.testing.assert_allclose(got, want, rtol=3e-6, atol=3e-6)


@pytest.mark.parametrize("n,d,f,attack", _CASES)
def test_pallas_masked_trimmed_mean_bit_exact(n, d, f, attack):
    G = _cohort(n, d, f, attack)
    rng = np.random.default_rng(n)
    mask = jnp.asarray(rng.random(n) > 0.25)
    want = np.asarray(masked_trimmed_mean_of(
        G, mask, jnp.sum(mask) - f - 1))
    got = np.asarray(pallas_masked_trimmed_mean(G, mask, f + 1,
                                                interpret=True))
    np.testing.assert_array_equal(got, want)
    # weighted (the async staleness seam)
    w = jnp.asarray(rng.random(n).astype(np.float32) + 0.1)
    want = np.asarray(masked_trimmed_mean_of(
        G, mask, jnp.sum(mask) - f - 1, weights=w))
    got = np.asarray(pallas_masked_trimmed_mean(
        G, mask, f + 1, weights=w, weighted=True, interpret=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,d", [(19, 777), (22, 256), (13, 79)])
def test_pallas_median_kernels(n, d):
    rng = np.random.default_rng(n * d)
    G = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(pallas_median_of(G, interpret=True)),
        np.asarray(jnp.median(G, axis=0)))
    mask = jnp.asarray(rng.random(n) > 0.3)
    np.testing.assert_array_equal(
        np.asarray(pallas_masked_median(G, mask, interpret=True)),
        np.asarray(masked_median(G, mask)))
    w = jnp.asarray(rng.random(n).astype(np.float32) + 0.1)
    np.testing.assert_array_equal(
        np.asarray(pallas_masked_median(G, mask, weights=w,
                                        weighted=True, interpret=True)),
        np.asarray(masked_median(G, mask, weights=w)))


def test_trimmed_mean_dispatch_pallas_impl():
    """The registry kernel's impl='pallas' branch: NaN telemetry slots
    (the kernel returns only the aggregate — the documented host-kernel
    convention) and the masked branch bit-matches the xla seam."""
    n, d, f = 19, 300, 4
    G = _cohort(n, d, f, "alie")
    agg, diag = trimmed_mean(G, n, f, impl="pallas", telemetry=True)
    np.testing.assert_allclose(np.asarray(agg),
                               np.asarray(trimmed_mean(G, n, f)),
                               rtol=3e-6, atol=3e-6)
    assert np.isnan(np.asarray(diag["kept_fraction"])).all()
    mask = jnp.asarray(np.random.default_rng(1).random(n) > 0.2)
    np.testing.assert_array_equal(
        np.asarray(trimmed_mean(G, n, f, impl="pallas", mask=mask)),
        np.asarray(trimmed_mean(G, n, f, mask=mask)))
    np.testing.assert_array_equal(
        np.asarray(median(G, n, f, impl="pallas", mask=mask)),
        np.asarray(median(G, n, f, mask=mask)))


# ---------------------------------------------------------------------------
# Bulyan: the all-on-device route

@pytest.mark.parametrize("n,d,f,attack", [(19, 300, 4, "alie"),
                                          (23, 512, 5, "backdoor"),
                                          (32, 200, 7, "signflip")])
def test_bulyan_pallas_route_matches_xla(n, d, f, attack):
    G = _cohort(n, d, f, attack)
    want_agg, want_diag = bulyan(G, n, f, telemetry=True)
    got_agg, got_diag = bulyan(G, n, f, selection_impl="pallas",
                               trim_impl="pallas", telemetry=True)
    # Identical selection math over a ulp-different D: on decisive
    # cohorts the selection SET must agree, and the trim tail is then
    # summation-order ulps.
    np.testing.assert_array_equal(
        np.asarray(got_diag["selection_mask"]),
        np.asarray(want_diag["selection_mask"]))
    np.testing.assert_allclose(np.asarray(got_agg),
                               np.asarray(want_agg), rtol=3e-6,
                               atol=3e-6)


def test_bulyan_pallas_route_masked():
    n, d, f = 23, 300, 4
    G = _cohort(n, d, f, "alie")
    mask = jnp.asarray(np.random.default_rng(2).random(n) > 0.2)
    want = np.asarray(bulyan(G, n, f, mask=mask))
    got = np.asarray(bulyan(G, n, f, mask=mask, selection_impl="pallas",
                            trim_impl="pallas"))
    np.testing.assert_allclose(got, want, rtol=3e-6, atol=3e-6)


def test_bulyan_pallas_route_never_marshals(monkeypatch):
    """The acceptance fact: no (n, n) pure_callback on the 'pallas'
    route — a callback firing inside the traced program would be the
    host marshal coming back."""
    import jax as jax_mod

    def boom(*a, **k):
        raise AssertionError("pure_callback on the pallas route")

    monkeypatch.setattr(jax_mod, "pure_callback", boom)
    G = _cohort(19, 200, 4, "alie")
    jax.jit(lambda g: bulyan(g, 19, 4, selection_impl="pallas",
                             trim_impl="pallas"))(G).block_until_ready()


# ---------------------------------------------------------------------------
# engine-level: the pallas route reproduces the xla trajectories

def _engine_weights(defense, rounds=3, **kw):
    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.attacks import DriftAttack
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset

    base = dict(dataset=C.SYNTH_MNIST, users_count=19, mal_prop=0.21,
                batch_size=16, epochs=rounds, test_step=5, seed=0,
                synth_train=256, synth_test=64, defense=defense)
    base.update(kw)
    cfg = ExperimentConfig(**base)
    ds = load_dataset(C.SYNTH_MNIST, seed=0, synth_train=256,
                      synth_test=64)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.5), dataset=ds)
    exp.run_span(0, rounds)
    return np.asarray(exp.state.weights)


@pytest.mark.parametrize("defense", ["Krum", "Bulyan"])
def test_engine_pallas_selection_trajectories_bit_equal(defense):
    """Selection defenses aggregate exact input rows: with decisive
    ALIE-regime data the pallas-route trajectory is bit-equal to xla."""
    np.testing.assert_array_equal(
        _engine_weights(defense, aggregation_impl="pallas"),
        _engine_weights(defense))


def test_engine_pallas_async_and_faulted_bit_equal():
    """The masked/weighted pallas kernels are bit-exact, so the async
    (weights seam) and faulted (quarantine seam) trajectories through
    the pallas route reproduce xla bit for bit."""
    kw = dict(aggregation="async", async_buffer=12,
              staleness_weight="poly")
    np.testing.assert_array_equal(
        _engine_weights("TrimmedMean", aggregation_impl="pallas", **kw),
        _engine_weights("TrimmedMean", **kw))
    np.testing.assert_array_equal(
        _engine_weights("Median", aggregation_impl="pallas",
                        faults=dict(dropout=0.2)),
        _engine_weights("Median", faults=dict(dropout=0.2)))


def test_engine_pallas_hierarchical_scan():
    """The pallas kernels inside the PR 6 per-shard scan: one
    hierarchical jit owns tier-1 end to end (ISSUE 11 tentpole)."""
    kw = dict(users_count=24, mal_prop=0.125, aggregation="hierarchical",
              megabatch=8, tier2_defense="TrimmedMean")
    np.testing.assert_array_equal(
        _engine_weights("Krum", aggregation_impl="pallas", **kw),
        _engine_weights("Krum", **kw))


# ---------------------------------------------------------------------------
# the f32 tie-break band contract (tests/test_native.py standard)

def test_duplicate_row_ties_resolve_identically():
    """Exact duplicate rows are exact score ties in BOTH engines (each
    computes the duplicates' scores from identical inputs), so the
    first-occurrence argmin must pick the same winner — the
    deterministic half of the tie contract."""
    n, d, f = 20, 128, 4
    G = np.array(_cohort(n, d, f, "none", seed=9), copy=True)
    G[7] = G[11]
    G[:f] = G[0]
    G = jnp.asarray(G)
    assert int(krum_select(G, n, f)) == int(
        krum_select(G, n, f, scores_impl="pallas"))


def _tie_band_trial(rng, trial, n, d):
    """One randomized cohort through both engines; returns whether the
    winners differ.  A flip must sit inside the f32 score-indeterminacy
    band, adjudicated with an exact f64 re-score (the measured-band
    reality test_native.py pins for the native comparator;
    bench.py:adjudicate_f32_flip is the template)."""
    f = max(1, int(0.24 * n))
    G = rng.standard_normal((n, d)).astype(np.float32)
    if trial % 3 == 0:
        G[:f] = G[f:].mean(0) + 0.5 * G[f:].std(0)  # near-tie regime
    Gj = jnp.asarray(G)
    a = int(krum_select(Gj, n, f))
    b = int(np.argmin(np.asarray(
        pallas_krum_scores(Gj, n, f, bm=8, bn=8, bk=64,
                           interpret=True)[0])))
    if a == b:
        return False
    # f64 exact re-score of both candidates: the gap must be inside
    # the f32 indeterminacy at these magnitudes.
    D = np.sqrt(np.maximum(
        ((G[:, None, :] - G[None, :, :]) ** 2).sum(-1), 0.0)
    ).astype(np.float64)
    np.fill_diagonal(D, np.inf)
    k = n - f
    srt = np.sort(D, axis=1)[:, :min(k, n - 1)]
    scores64 = srt.sum(1)
    gap = abs(scores64[a] - scores64[b])
    band = (32 * np.finfo(np.float32).eps
            * max(scores64[a], scores64[b]))
    assert gap <= band, (
        f"trial {trial} (n={n}, d={d}): winners {a} vs {b} diverge "
        f"outside the f32 tie band (gap {gap:.3e} > band {band:.3e})")
    return True


# Tile-aligned and ragged on both axes (bm = bn = 8, bk = 64).
_TIE_SHAPES = [(10, 45), (16, 128), (19, 97), (27, 199)]


def test_fused_krum_tie_band_sweep():
    """Randomized cross-engine sweep, 120 cohorts: any winner flip must
    sit inside the f32 tie band.  The cohorts share four shapes so the
    interpret-mode kernel compiles four times, not 120 (tier-1 budget);
    the random-shape sweep below is the slow twin."""
    flips = sum(_tie_band_trial(np.random.default_rng(20_000 + trial),
                                trial, *_TIE_SHAPES[trial % 4])
                for trial in range(120))
    # The sweep must have exercised the comparison, not vacuously passed.
    assert flips < 30


@pytest.mark.slow          # 120 shapes = 120 interpret-mode compiles, ~95 s
def test_fused_krum_tie_band_sweep_random_shapes():
    flips = 0
    for trial in range(120):
        rng = np.random.default_rng(10_000 + trial)
        n, d = int(rng.integers(10, 28)), int(rng.integers(32, 200))
        flips += _tie_band_trial(rng, trial, n, d)
    assert flips < 30


# ---------------------------------------------------------------------------
# campaign integration: impl axes pre-validate like every other knob

def test_campaign_impl_axes_prevalidate():
    from attacking_federate_learning_tpu.campaigns.spec import (
        CampaignSpec
    )

    spec = CampaignSpec(
        name="impl-compare",
        base=dict(dataset="SYNTH_MNIST", users_count=19, mal_prop=0.21,
                  batch_size=16, epochs=2, synth_train=256,
                  synth_test=64, defense="Krum"),
        axes={"aggregation_impl": ["xla", "pallas"],
              "backdoor_fused": [True, False],
              "backdoor": ["pattern"]},
    )
    cells = spec.expand()
    assert len(cells) == 4
    skips = {(c.overrides["aggregation_impl"],
              c.overrides["backdoor_fused"]): c.skip for c in cells}
    assert skips[("xla", True)] is None
    assert skips[("pallas", True)] is None
    # the pallas ⊕ host-staged backdoor seam: skipped with the config's
    # own message, never a crashed run
    assert "backdoor-staged" in skips[("pallas", False)]
    for c in cells:
        assert c.row()["aggregation_impl"] == c.overrides[
            "aggregation_impl"]


def test_campaign_bulyan_selection_axis():
    from attacking_federate_learning_tpu.campaigns.spec import (
        composition_reject_reason
    )

    base = dict(dataset="SYNTH_MNIST", users_count=23, mal_prop=0.21,
                batch_size=16, epochs=2, synth_train=256, synth_test=64,
                defense="Bulyan")
    assert composition_reject_reason(
        dict(base, bulyan_selection_impl="pallas")) is None
    r = composition_reject_reason(
        dict(base, bulyan_selection_impl="pallas", distance_impl="host"))
    assert r and "distance_impl" in r
    r = composition_reject_reason(
        dict(base, aggregation_impl="pallas",
             bulyan_selection_impl="host"))
    assert r and "marshal" in r


# ---------------------------------------------------------------------------
# cost-ledger fusion pin (slow: the 10k north-star compile)

@pytest.mark.slow
def test_fused_kernel_cost_ledger_beats_xla_at_north_star():
    """ISSUE 11 acceptance: at n=10,240 the fused distance->score
    kernel reads strictly fewer HBM bytes (operands-once accounting)
    than the XLA Gram+epilogue path, and no (n, n) tensor exists in
    its compiled program — tools/perf_gate.py --pallasproof is the
    same check, CI-wired via smoke leg 4."""
    import importlib.util
    import os as _os

    spec = importlib.util.spec_from_file_location(
        "perf_gate", _os.path.join(_os.path.dirname(__file__), "..",
                                   "tools", "perf_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.pallasproof() == 0


def test_krum_scores_cost_model_shapes():
    """The declared model is deterministic in the shapes and the
    operands-once view is tile-size-invariant (it counts logical
    operands, not the bm/bn re-reads the tile view counts)."""
    a = krum_scores_cost(1024, 4096, 200, bm=128, bn=128, bk=512)
    b = krum_scores_cost(1024, 4096, 200, bm=256, bn=256, bk=1024)
    assert a["bytes_accessed"] == b["bytes_accessed"]
    assert a["hbm_tile_bytes"] > b["hbm_tile_bytes"]
    assert a["bytes_accessed"] < a["hbm_tile_bytes"]


# ---------------------------------------------------------------------------
# interpret resolution: CPU test mode vs the compiled TPU route

def test_interpret_none_resolves_to_interpret_on_cpu():
    """interpret=None is the interpreter everywhere but the TPU backend,
    so the default call runs here with no Mosaic compile."""
    from attacking_federate_learning_tpu.ops.pallas_distances import (
        _interpret_default
    )

    assert jax.default_backend() == "cpu"
    assert _interpret_default(None) is True
    assert _interpret_default(False) is False
    G = jnp.ones((8, 128), jnp.float32)
    np.testing.assert_array_equal(np.asarray(pallas_pairwise_distances(G)),
                                  np.zeros((8, 8), np.float32))


def test_sort_kernels_refuse_the_compiled_route_by_name():
    """The compiled route of a sorting kernel (what interpret=None
    resolves to on a TPU) raises an error naming the kernel — it never
    drops to interpret mode or to the XLA twin."""
    G = jnp.ones((16, 256), jnp.float32)
    mask = jnp.ones((16,), bool)
    for name, call in [
            ("krum_score_fusion",
             lambda: pallas_krum_scores(G, 16, 3, interpret=False)),
            ("trimmed_mean_tile",
             lambda: pallas_trimmed_mean_of(G, 10, interpret=False)),
            ("median_tile", lambda: pallas_median_of(G, interpret=False)),
            ("masked_trimmed_mean_tile",
             lambda: pallas_masked_trimmed_mean(G, mask, 4,
                                                interpret=False)),
            ("masked_median_tile",
             lambda: pallas_masked_median(G, mask, interpret=False))]:
        with pytest.raises(NotImplementedError, match=name):
            call()


def test_mosaic_still_refuses_the_guarded_kernels():
    """The guard above is a claim about the installed jax, so observe
    it: past the guard, at the smoke's unaligned production shape, the
    TPU lowering of every guarded body must fail with Mosaic's OWN sort
    message (lowering for 'tpu' needs no TPU).  When a jax upgrade
    builds one, this fails and the guard goes."""
    from attacking_federate_learning_tpu.ops.pallas_defense import (
        raw_sort_kernels
    )

    G = jax.ShapeDtypeStruct((1000, 79_510), jnp.float32)
    for name, raw in raw_sort_kernels(1000, 240).items():
        with pytest.raises(NotImplementedError,
                           match="Pallas TPU lowering.*sort") as e:
            jax.jit(raw).trace(G).lower(lowering_platforms=("tpu",))
        assert name not in str(e.value), "the guard fired, not Mosaic"
