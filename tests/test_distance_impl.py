"""The distance-engine dispatch layer (VERDICT round-1 items #3/#4).

Every selectable ``distance_impl`` — xla, host (CPU BLAS, defenses/host.py),
ring / allgather (blockwise shard_map kernels, parallel/distances.py) — must produce the same aggregate as the oracle, both
through the kernel API and wired through the engine's config knob.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from attacking_federate_learning_tpu.defenses import host as H
from attacking_federate_learning_tpu.defenses import kernels as K
from attacking_federate_learning_tpu.defenses import oracle as O


CASES = [
    # (n, d, f) — n divisible by 8 where the blockwise kernels need a mesh
    (16, 40, 3),
    (24, 104, 5),
    (40, 33, 9),
]


def grads_for(n, d, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


# --------------------------------------------------------------------------
# host BLAS kernels (the CPU-backend production path) vs oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,d,f", CASES)
def test_host_krum_matches_oracle(n, d, f):
    G = grads_for(n, d, seed=n + d + f)
    want = O.np_krum(G.astype(np.float64), n, f)
    got = H.host_krum(G, n, f)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("n,d,f", CASES)
def test_host_bulyan_matches_oracle(n, d, f):
    if n < 4 * f + 3:
        pytest.skip("bulyan guard")
    G = grads_for(n, d, seed=n * 7 + f)
    want = O.np_bulyan(G.astype(np.float64), n, f)
    got = H.host_bulyan(G, n, f)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_host_krum_adversarial_magnitudes_and_ties():
    # Adversarial magnitudes (huge malicious rows) and exact duplicate rows
    # (ties) — the regimes where a complement/subtraction path would lose
    # precision and where tie-breaks must resolve to the lowest index.
    rng = np.random.default_rng(0)
    G = rng.standard_normal((12, 30)).astype(np.float32)
    G[0] = 1e6          # adversarial magnitude
    G[5] = G[3]         # exact tie pair
    for f in (2, 3):
        want = O.np_krum(G.astype(np.float64), 12, f)
        got = H.host_krum(G, 12, f)
        np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-4)
        xla = np.asarray(K.krum(jnp.asarray(G), 12, f))
        np.testing.assert_allclose(xla, want, atol=1e-3, rtol=1e-4)


# --------------------------------------------------------------------------
# kernel API dispatch
# --------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["xla", "host", "auto"])
def test_krum_kernel_dispatch(impl):
    n, d, f = 24, 104, 5
    G = grads_for(n, d, seed=1)
    want = O.np_krum(G.astype(np.float64), n, f)
    got = np.asarray(K.krum(jnp.asarray(G), n, f, distance_impl=impl))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "host", "auto"])
def test_bulyan_kernel_dispatch(impl):
    n, d, f = 24, 40, 5
    G = grads_for(n, d, seed=2)
    want = O.np_bulyan(G.astype(np.float64), n, f)
    got = np.asarray(K.bulyan(jnp.asarray(G), n, f, distance_impl=impl))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_host_impl_inside_jit_uses_callback():
    # Static n/f closed over; traced G goes through pure_callback — slower,
    # but must stay correct (the engine only picks this when told to).
    n, d, f = 16, 40, 3
    G = grads_for(n, d, seed=3)
    fn = jax.jit(lambda g: K.krum(g, n, f, distance_impl="host"))
    want = O.np_krum(G.astype(np.float64), n, f)
    np.testing.assert_allclose(np.asarray(fn(jnp.asarray(G))), want,
                               atol=2e-4, rtol=1e-4)


def test_resolve_auto():
    # On this CPU test backend: eager calls resolve to host, traced to xla.
    assert K.resolve_distance_impl("auto", 10, np.zeros((4, 2))) == "host"
    assert K.resolve_distance_impl("xla", 10, None) == "xla"
    seen = {}

    def probe(g):
        seen["impl"] = K.resolve_distance_impl("auto", 10, g)
        return g.sum()

    jax.jit(probe)(jnp.zeros((4, 2)))
    assert seen["impl"] == "xla"


# --------------------------------------------------------------------------
# engine wiring: cfg.distance_impl reaches the defense, including the
# blockwise shard_map engines over the 8-virtual-device mesh
# --------------------------------------------------------------------------
def _one_round_weights(distance_impl, mesh_shape=None, defense="Krum",
                       distance_dtype="float32"):
    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.attacks import DriftAttack
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset

    cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, users_count=16,
                           mal_prop=0.2, batch_size=16, epochs=2,
                           defense=defense, distance_impl=distance_impl,
                           distance_dtype=distance_dtype,
                           mesh_shape=mesh_shape,
                           synth_train=1024, synth_test=128)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=1024, synth_test=128)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    exp.run_round(0)
    exp.run_round(1)
    return np.asarray(exp.state.weights)


@pytest.mark.parametrize("impl,mesh", [
    ("xla", None),
    ("ring", (8, 1)),
    ("allgather", (8, 1)),
])
def test_engine_distance_impl_parity(impl, mesh):
    ref = _one_round_weights("auto")
    got = _one_round_weights(impl, mesh_shape=mesh)
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-5)


def test_engine_blockwise_requires_mesh():
    with pytest.raises(ValueError, match="needs a device mesh"):
        _one_round_weights("ring", mesh_shape=None)


class _BulyanEngineProbe:
    """One engine, stepped round by round with its realized Bulyan
    selection observable (the telemetry seam's multi-hot mask) and the
    pre-defense gradient matrix recomputable on the host for the tie
    replay.  Telemetry does not perturb the trajectory (PR-1 pin)."""

    def __init__(self, distance_impl, mesh_shape=None):
        from attacking_federate_learning_tpu import config as C
        from attacking_federate_learning_tpu.attacks import DriftAttack
        from attacking_federate_learning_tpu.config import ExperimentConfig
        from attacking_federate_learning_tpu.core.engine import (
            FederatedExperiment
        )
        from attacking_federate_learning_tpu.data.datasets import (
            load_dataset
        )

        cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, users_count=16,
                               mal_prop=0.2, batch_size=16, epochs=2,
                               defense="Bulyan",
                               distance_impl=distance_impl,
                               mesh_shape=mesh_shape, telemetry=True,
                               synth_train=1024, synth_test=128)
        ds = load_dataset(cfg.dataset, seed=0, synth_train=1024,
                          synth_test=128)
        self.exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                       dataset=ds)

    def pre_defense_grads(self, t):
        exp = self.exp
        grads = exp._compute_grads_impl(exp.state, t)
        grads = exp.attacker.apply(grads, exp.m_mal,
                                   exp._ctx_for(exp.state, t))
        return np.asarray(grads, np.float64)

    def step(self, t):
        """Run round t; returns the frozen selection set."""
        self.exp.run_round(t)
        mask = np.asarray(
            self.exp.last_round_telemetry["defense_selection_mask"])
        return frozenset(np.flatnonzero(mask > 0).tolist())

    @property
    def weights(self):
        return np.asarray(self.exp.state.weights)


def _bulyan_selection_steps(G, n, f):
    """Host replay of the Bulyan selection loop with BOTH f32 distance
    formulations the engines use (direct difference vs Gram — the
    bench.py:adjudicate_f32_flip template): per selection step, the
    top-2 mid-score gap against the measured indeterminacy band
    (4x the |diff-form - Gram-form| spread on this very data, plus the
    analytic worst-case f32 summation term).  Scores sum in float64 so
    each formulation's own error is isolated.  Returns
    [(pick, runner_up, gap, band), ...] for the set_size steps."""
    G32 = np.asarray(G, np.float32)
    d_diff = np.sqrt(((G32[:, None, :] - G32[None, :, :]) ** 2)
                     .sum(-1, dtype=np.float32))
    sq = (G32 * G32).sum(1, dtype=np.float32)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (G32 @ G32.T)
    d_gram = np.sqrt(np.maximum(d2, 0.0, dtype=np.float32))
    eps32 = float(np.finfo(np.float32).eps)
    alive = np.ones(n, bool)
    steps = []
    for s in range(n - 2 * f):
        n_cur = n - s
        k = n_cur - f          # reference n-f scoring quirk, shrinking n
        mids, spreads, absmax = {}, [], 0.0
        for i in range(n):
            if not alive[i]:
                continue
            pair = []
            for D in (d_diff, d_gram):
                v = np.asarray([D[i, j] for j in range(n)
                                if j != i and alive[j]], np.float64)
                pair.append(float(np.sort(v)[:k].sum()))
            mids[i] = 0.5 * (pair[0] + pair[1])
            spreads.append(abs(pair[0] - pair[1]))
            absmax = max(absmax, abs(pair[0]), abs(pair[1]))
        order = sorted(mids, key=mids.__getitem__)
        gap = mids[order[1]] - mids[order[0]]
        band = 4.0 * max(spreads) + 0.5 * n_cur * eps32 * absmax
        steps.append((order[0], order[1], gap, band))
        alive[order[0]] = False
    return steps


def _adjudicate_trim_flips(G_ref, G_got, sel, f, w_ref, w_got, lr):
    """Adjudicate per-coordinate trimmed-mean keep-set flips (the
    second place two correct engines can legally diverge): the two
    engines' gradient matrices already differ at the ulp level (the
    mesh-sharded and single-device reductions order sums differently),
    and a coordinate whose trim boundary — the gap between the keep-th
    and (keep+1)-th smallest |deviation-from-median| — sits inside
    that measured perturbation band can legally keep DIFFERENT rows,
    moving the aggregate by up to the boundary pair's combined
    deviation over the keep count.  Same measured-band standard as
    bench.py:adjudicate_f32_flip.  Returns indices of coordinates
    whose weight difference is NOT attributable to a legal flip."""
    S = sorted(sel)
    rows_ref = G_ref[S]
    rows_got = G_got[S]
    f2 = 2 * f
    keep = len(S) - f2 - 1
    eps32 = float(np.finfo(np.float32).eps)
    med = np.median(rows_ref, axis=0)
    a = np.sort(np.abs(rows_ref - med), axis=0)
    gap = a[keep] - a[keep - 1]          # trim-boundary gap, per coord
    # Measured input indeterminacy (x16 safety, same spirit as the x4
    # on the measured score spread in adjudicate_f32_flip — the median
    # and every deviation shift with the perturbation).
    band = 16.0 * (np.abs(rows_ref - rows_got).max(axis=0)
                   + eps32 * np.abs(rows_ref).max(axis=0))
    dw = np.abs(w_ref.astype(np.float64) - w_got.astype(np.float64))
    strict = 2e-5 + 1e-5 * np.abs(w_ref)     # the summation-noise floor
    # One boundary swap changes the kept mean by at most the boundary
    # pair's combined |dev| / keep; the weight moves lr x that.
    envelope = lr * (a[keep] + a[keep - 1] + 2.0 * band) / keep + strict
    viol = dw > strict
    illegal = viol & ((gap > band) | (dw > envelope))
    return np.flatnonzero(illegal), int(viol.sum())


def test_engine_bulyan_blockwise():
    """Blockwise-allgather D vs the in-program xla D, wired through the
    engine under Bulyan.  Two correct f32 engines may legally disagree
    wherever a selection rests on a near-tie (ARCHITECTURE.md "Known
    local failures"; the ulp-band reality tests/test_native.py pins),
    and Bulyan selects twice: the shrinking-pool Krum selection, and
    the per-coordinate trimmed-mean keep set — on iid gaussian-ish
    gradients the trim boundary is near-tied on a sizable fraction of
    coordinates, so a blanket 2e-5 weight tolerance mis-adjudicates
    legal flips as kernel bugs.  Instead (bench.py:adjudicate_f32_flip
    is the template — measured indeterminacy bands, not guessed
    tolerances):

    1. the realized SELECTION SETS (telemetry masks) are compared per
       round; a set flip is legal only if the host replay of the
       selection (both f32 distance formulations, f64 score sums)
       shows a step whose top-2 score gap is inside its band;
    2. with identical selection sets, every coordinate whose weights
       differ beyond summation noise must sit on a trim boundary
       within the measured inter-engine perturbation band AND inside
       the single-swap envelope.

    A decisive-gap disagreement still fails either stage — that would
    be a wrong kernel, not a tie."""
    ref = _BulyanEngineProbe("auto")
    got = _BulyanEngineProbe("allgather", mesh_shape=(8, 1))
    n, f = 16, ref.exp.m_mal
    lr = ref.exp.cfg.learning_rate
    for t in range(2):
        G_ref = ref.pre_defense_grads(t)
        G_got = got.pre_defense_grads(t)
        sel_ref, sel_got = ref.step(t), got.step(t)
        if sel_ref != sel_got:
            steps = _bulyan_selection_steps(G_ref, n, f)
            tied = [(p, q, g, b) for p, q, g, b in steps if g <= b]
            assert tied, (
                f"round {t}: selection flip {sorted(sel_ref ^ sel_got)} "
                f"with every step's top-2 gap DECISIVE (no step inside "
                f"its indeterminacy band): {steps}")
            return     # states legally diverged; later rounds can't compare
        illegal, n_viol = _adjudicate_trim_flips(
            G_ref, G_got, sel_ref, f, ref.weights, got.weights, lr)
        assert illegal.size == 0, (
            f"round {t}: {illegal.size}/{n_viol} diverging coordinates "
            f"are NOT legal trim-boundary ties (first: "
            f"{illegal[:5].tolist()}) — decisive disagreement between "
            f"the distance engines")
        if n_viol:
            return     # legally diverged at the trim stage; stop comparing
    np.testing.assert_allclose(got.weights, ref.weights,
                               atol=2e-5, rtol=1e-5)


def test_engine_blockwise_requires_divisible_cohort():
    with pytest.raises(ValueError, match="divisible"):
        from attacking_federate_learning_tpu import config as C
        from attacking_federate_learning_tpu.config import ExperimentConfig
        from attacking_federate_learning_tpu.core.engine import (
            FederatedExperiment
        )
        from attacking_federate_learning_tpu.data.datasets import (
            load_dataset
        )

        cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, users_count=10,
                               mal_prop=0.2, batch_size=8, epochs=1,
                               defense="Krum", distance_impl="ring",
                               mesh_shape=(8, 1),
                               synth_train=256, synth_test=64)
        ds = load_dataset(cfg.dataset, seed=0, synth_train=256,
                          synth_test=64)
        FederatedExperiment(cfg, dataset=ds)


def test_engine_ring_bf16_parity():
    """bf16 wire matrix through the ring engine matches the xla engine at
    bf16 tolerance (distances accumulate f32 in both)."""
    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.attacks import DriftAttack
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset

    def weights(impl, mesh):
        cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, users_count=16,
                               mal_prop=0.2, batch_size=8, epochs=1,
                               defense="Krum", distance_impl=impl,
                               grad_dtype="bfloat16", mesh_shape=mesh,
                               synth_train=512, synth_test=64)
        ds = load_dataset(cfg.dataset, seed=0, synth_train=512,
                          synth_test=64)
        exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=ds)
        exp.run_round(0)
        return np.asarray(exp.state.weights)

    np.testing.assert_allclose(weights("ring", (8, 1)),
                               weights("xla", None), atol=2e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# distance_dtype='bfloat16': the bf16-Gram MXU mode (round-3 flag) — cast
# for the distance computation only, f32 accumulation + f32 norms
# --------------------------------------------------------------------------
def test_bf16_distances_close_to_f32():
    from attacking_federate_learning_tpu.ops.distances import (
        pairwise_distances
    )

    G = grads_for(32, 500, seed=5)
    want = np.asarray(pairwise_distances(jnp.asarray(G)))
    got = np.asarray(pairwise_distances(jnp.asarray(G, jnp.bfloat16)))
    assert got.dtype == np.float32  # accumulation/norms stay f32
    # bf16 multiplies: ~0.4% per-element relative error, averaged down by
    # the d-length accumulation.
    np.testing.assert_allclose(got, want, atol=0.05, rtol=2e-2)


def test_krum_select_bf16_agrees():
    """On generic (non-tie) data the bf16-Gram selection matches f32 —
    eager and jitted."""
    G = jnp.asarray(grads_for(24, 300, seed=9))
    want = int(K.krum_select(G, 24, 5))
    got = int(K.krum_select(G, 24, 5, distance_dtype="bfloat16"))
    assert got == want
    jit_sel = jax.jit(K.krum_select, static_argnums=(1, 2),
                      static_argnames=("distance_dtype",))
    assert int(jit_sel(G, 24, 5, distance_dtype="bfloat16")) == want


def test_bulyan_bf16_close_to_f32():
    """On separated data (tight honest cluster, far malicious rows) the
    bf16-Gram selection picks the same set, so outputs match to bf16
    tolerance.  (On knife-edge iid data the discrete selection can
    legitimately differ between dtypes — that's inherent to any
    selection defense under a distance perturbation, not a bug.)"""
    rng = np.random.default_rng(11)
    base = rng.standard_normal(200).astype(np.float32)
    G = base + 0.05 * rng.standard_normal((31, 200)).astype(np.float32)
    G[:5] += 10.0  # malicious rows far from the honest cluster
    G = jnp.asarray(G)
    want = np.asarray(K.bulyan(G, 31, 5))
    got = np.asarray(K.bulyan(G, 31, 5, distance_dtype="bfloat16"))
    # Near-tied honest rows may swap a marginal selection between dtypes;
    # the bound is a fraction of the honest-cluster spread (0.05) — far
    # below the 10.0 malicious offset any contamination would show.
    np.testing.assert_allclose(got, want, atol=0.1, rtol=2e-2)
    assert float(np.max(np.abs(got - np.asarray(base)))) < 1.0


def test_engine_distance_dtype_bf16():
    """cfg.distance_dtype reaches the kernels through the engine wiring;
    the fused round runs and matches the f32 run closely (selection on
    well-separated synth gradients is dtype-robust)."""
    ref = _one_round_weights("xla")
    got = _one_round_weights("xla", distance_dtype="bfloat16")
    np.testing.assert_allclose(got, ref, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("impl", ["allgather", "ring"])
def test_engine_distance_dtype_bf16_blockwise(impl):
    # ring regression: the scan carry must be f32 even for bf16 operands
    # (parallel/distances.py) — bf16 tiles never exist.
    ref = _one_round_weights(impl, mesh_shape=(8, 1))
    got = _one_round_weights(impl, mesh_shape=(8, 1),
                             distance_dtype="bfloat16")
    np.testing.assert_allclose(got, ref, atol=1e-3, rtol=1e-3)


def test_distance_dtype_validation():
    from attacking_federate_learning_tpu.config import ExperimentConfig

    with pytest.raises(ValueError, match="distance_dtype"):
        ExperimentConfig(dataset="SYNTH_MNIST", users_count=8,
                         distance_dtype="float16")


# --------------------------------------------------------------------------
# ISSUE 6 satellite: diagonal zeroing, pinned via
# static cost facts (utils/costs.py — deterministic per (HLO, XLA,
# platform), no stopwatch)
# --------------------------------------------------------------------------
def _facts(lowered):
    from attacking_federate_learning_tpu.utils.costs import (
        compiled_cost_facts
    )
    return compiled_cost_facts(lowered.compile())


def test_zero_diagonal_matches_eye_formula_bitwise():
    """The iota-select diagonal zeroing computes exactly what the old
    ``D * (1 - eye(n))`` spelling computed: off-diagonal D*1.0 is D, the
    diagonal is exactly zero either way."""
    from attacking_federate_learning_tpu.ops.distances import (
        pairwise_distances, pairwise_sq_distances
    )

    G = jnp.asarray(grads_for(64, 32, seed=3))
    D_eye = jnp.sqrt(pairwise_sq_distances(G)) * (
        1.0 - jnp.eye(64, dtype=jnp.float32))
    np.testing.assert_array_equal(np.asarray(pairwise_distances(G)),
                                  np.asarray(D_eye))


def test_zero_diagonal_costs_no_more_than_eye():
    """The eye spelling pays an extra n^2-shaped construct+multiply on
    the hot path (~420 MB f32 materialized at n=10,240 before fusion
    gets a say); the iota select must be strictly cheaper in FLOPs and
    never worse in bytes/temp on the same shape.  n = 512 is under the
    Gram's two-block threshold (2 * GRAM_BLOCK_ROWS = 2,048), so both
    spellings sit on the same single dot."""
    from attacking_federate_learning_tpu.ops.distances import (
        pairwise_distances, pairwise_sq_distances
    )

    n, d = 512, 1024
    sds = jax.ShapeDtypeStruct((n, d), jnp.float32)

    def eye_style(G):
        D = jnp.sqrt(pairwise_sq_distances(G))
        return D * (1.0 - jnp.eye(n, dtype=D.dtype))

    new = _facts(jax.jit(pairwise_distances).lower(sds))
    old = _facts(jax.jit(eye_style).lower(sds))
    assert new["flops"] < old["flops"]
    assert new["bytes_accessed"] <= old["bytes_accessed"]
    assert new["temp_bytes"] <= old["temp_bytes"]
