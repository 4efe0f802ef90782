"""Per-round client participation sampling (beyond-reference; the
reference uses every client every round, server.py:54-56)."""

import numpy as np
import jax.numpy as jnp
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import make_attacker
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset


def _exp(**overrides):
    kw = dict(dataset=C.SYNTH_MNIST, users_count=20, mal_prop=0.25,
              batch_size=16, epochs=4, defense="TrimmedMean", num_std=1.0,
              participation=0.5, synth_train=512, synth_test=64)
    kw.update(overrides)
    cfg = ExperimentConfig(**kw)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=kw["synth_train"],
                      synth_test=64)
    return FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                               dataset=ds)


def test_cohort_sizes_static_and_scaled():
    exp = _exp()                      # n=20 f=5 p=0.5
    assert (exp.m, exp.m_mal) == (10, 2)  # round(0.5*5)=2
    full = _exp(participation=1.0)
    assert (full.m, full.m_mal) == (20, 5)


def test_participants_structure_and_variation():
    exp = _exp()
    p0 = np.asarray(exp._participants(exp.data, 0))
    p1 = np.asarray(exp._participants(exp.data, 1))
    assert len(p0) == exp.m
    assert len(set(p0.tolist())) == exp.m          # no duplicates
    assert np.all(p0[: exp.m_mal] < exp.f)         # malicious first
    assert np.all(p0[exp.m_mal:] >= exp.f)         # honest rest
    assert not np.array_equal(p0, p1)              # resampled per round
    # deterministic per (seed, round)
    np.testing.assert_array_equal(
        p0, np.asarray(exp._participants(exp.data, 0)))


def test_training_runs_and_defense_sees_cohort():
    exp = _exp(defense="Krum")        # guard: m=10 >= 2*2+1
    exp.run_span(0, 4)
    w = np.asarray(exp.state.weights)
    assert np.all(np.isfinite(w))
    assert int(exp.state.round) == 4


def test_guard_checks_cohort_not_population():
    # Bulyan needs (cohort) m >= 4*m_mal + 3.  With n=22, f=5 the full
    # population fails (22 < 23) — but the p=0.5 cohort (m=11,
    # m_mal=round(2.5)=2, bound 11) passes: the guard must judge what the
    # defense actually sees.
    kw = dict(users_count=22, mal_prop=0.23, defense="Bulyan")
    with pytest.raises(ValueError, match="Bulyan"):
        _exp(participation=1.0, **kw)
    exp = _exp(participation=0.5, **kw)
    assert (exp.m, exp.m_mal) == (11, 2)
    exp.run_round(0)  # and it trains


def test_streaming_matches_device_under_participation():
    a = _exp(data_placement="host_stream")
    b = _exp(data_placement="device")
    a.run_span(0, 3)
    b.run_span(0, 3)
    np.testing.assert_array_equal(np.asarray(a.state.weights),
                                  np.asarray(b.state.weights))


def test_partial_participation_differs_from_full():
    a = _exp(participation=0.5)
    b = _exp(participation=1.0)
    a.run_span(0, 2)
    b.run_span(0, 2)
    assert not np.array_equal(np.asarray(a.state.weights),
                              np.asarray(b.state.weights))


def test_validation():
    with pytest.raises(ValueError, match="participation"):
        ExperimentConfig(dataset=C.SYNTH_MNIST, participation=0.0)
    with pytest.raises(ValueError, match="participation"):
        ExperimentConfig(dataset=C.SYNTH_MNIST, participation=1.5)


def test_zero_malicious_cohort_rejected():
    # round(0.5 * 1) == 0 (banker's rounding): a silent attack-free "attack
    # run" must be refused up front.
    with pytest.raises(ValueError, match="malicious cohort to 0"):
        _exp(users_count=20, mal_prop=0.05, participation=0.5)


def test_all_malicious_tiny_cohort_rejected():
    # All-malicious population with a tiny cohort (the empty-honest-pool
    # crash scenario): refused at construction by the zero-malicious-cohort
    # guard (once m_mal >= 1, rounding can't demand more honest clients
    # than exist, so that second guard is a defensive backstop).
    with pytest.raises(ValueError):
        _exp(users_count=3, mal_prop=1.0, participation=0.1,
             defense="NoDefense")


def test_blockwise_guard_uses_cohort_rows():
    # n=20 divides 4 but the m=10 cohort doesn't divide... 10 % 4 != 0:
    # must raise cleanly at construction, not inside shard_map.
    with pytest.raises(ValueError, match="round cohort"):
        _exp(defense="Krum", distance_impl="ring", mesh_shape=(4, 2),
             participation=0.5)
