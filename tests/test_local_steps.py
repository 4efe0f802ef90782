"""FedAvg-style local steps (beyond-reference: the reference is strictly
FedSGD, its client optimizer never steps — reference user.py:80)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import make_attacker
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.client import (
    make_client_update_fn, make_loss_fn
)
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.models.base import get_model
from attacking_federate_learning_tpu.utils.flatten import make_flattener


def _weights(rounds=3, **overrides):
    kw = dict(dataset=C.SYNTH_MNIST, users_count=8, mal_prop=0.25,
              batch_size=16, epochs=rounds, defense="TrimmedMean",
              num_std=1.0, synth_train=512, synth_test=64)
    kw.update(overrides)
    cfg = ExperimentConfig(**kw)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=kw["synth_train"],
                      synth_test=64)
    exp = FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                              dataset=ds)
    exp.run_span(0, rounds)
    return np.asarray(exp.state.weights)


def test_local_steps_one_is_reference_fedsgd():
    # The k=1 wrapper must be bit-identical to make_client_grad_fn (the
    # pre-existing reference-semantics path), not merely self-consistent.
    from attacking_federate_learning_tpu.core.client import (
        make_client_grad_fn
    )

    model = get_model("mnist_mlp")
    params = model.init(jax.random.key(1))
    flat = make_flattener(params)
    w = flat.ravel(params)
    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.standard_normal((4, 1, 8, 784)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (4, 1, 8)).astype(np.int32))
    got = make_client_update_fn(model, flat, 1)(w, xs, ys, 0.07, 0.1)
    want = make_client_grad_fn(model, flat)(w, xs[:, 0], ys[:, 0])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_local_update_matches_manual_sgd():
    model = get_model("mnist_mlp")
    params = model.init(jax.random.key(0))
    flat = make_flattener(params)
    w0 = np.asarray(flat.ravel(params))
    loss = make_loss_fn(model, flat)
    grad = jax.grad(loss)

    rng = np.random.default_rng(0)
    n, k, B = 3, 4, 8
    xs = rng.standard_normal((n, k, B, 784)).astype(np.float32)
    ys = rng.integers(0, 10, (n, k, B)).astype(np.int32)
    lr = 0.05

    lr_report = 0.1   # the server's multiplier (constant-lr quirk)
    fn = make_client_update_fn(model, flat, local_steps=k)
    out = np.asarray(fn(jnp.asarray(w0), jnp.asarray(xs), jnp.asarray(ys),
                        lr, lr_report))

    for i in range(n):
        w = jnp.asarray(w0)
        for s in range(k):
            w = w - lr * grad(w, jnp.asarray(xs[i, s]),
                              jnp.asarray(ys[i, s]))
        pseudo = (w0 - np.asarray(w)) / lr_report
        np.testing.assert_allclose(out[i], pseudo, atol=1e-5, rtol=1e-5)


def test_local_steps_trains_and_interops_with_attack_defense():
    w1 = _weights(local_steps=1)
    w4 = _weights(local_steps=4)
    assert w4.shape == w1.shape
    assert np.all(np.isfinite(w4))
    assert not np.array_equal(w4, w1)


def test_local_steps_streaming_parity():
    kw = dict(local_steps=3)
    a = _weights(data_placement="host_stream", **kw)
    b = _weights(data_placement="device", **kw)
    np.testing.assert_array_equal(a, b)


def test_local_steps_converges_faster_per_round():
    # On the easy synth task, 4 local steps reach higher accuracy than 1
    # in the same (small) number of rounds.
    def acc(local_steps):
        cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, users_count=8,
                               mal_prop=0.0, batch_size=16, epochs=3,
                               defense="NoDefense", local_steps=local_steps,
                               synth_train=512, synth_test=256)
        ds = load_dataset(cfg.dataset, seed=0, synth_train=512,
                          synth_test=256)
        exp = FederatedExperiment(cfg, dataset=ds)
        exp.run_span(0, 3)
        _, correct = exp.evaluate(exp.state.weights)
        return float(correct)

    assert acc(4) > acc(1)


def test_local_steps_validated():
    with pytest.raises(ValueError, match="local_steps"):
        ExperimentConfig(dataset=C.SYNTH_MNIST, local_steps=0)


def test_local_steps_reduction_is_exact_under_server_lr():
    """FedAvg-as-FedSGD exactness: with k local steps, one server round
    (momentum 0, constant server lr) must land exactly on the weights a
    client would reach by k plain SGD steps at the faded lr — i.e. the
    lr_report divisor matches the server's multiplier."""
    cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, users_count=1,
                           mal_prop=0.0, batch_size=8, epochs=1,
                           defense="NoDefense", local_steps=3, momentum=0.0,
                           synth_train=64, synth_test=32)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=64, synth_test=32)
    exp = FederatedExperiment(cfg, dataset=ds)
    w0 = np.asarray(exp.state.weights)

    # Manual: the single client's 3 local SGD steps at the faded lr.
    from attacking_federate_learning_tpu.core.server import (
        faded_learning_rate
    )
    loss = make_loss_fn(exp.model, exp.flat)
    grad = jax.grad(loss)
    xs, ys = exp._gather_batches(exp.data, jnp.asarray(0, jnp.int32))
    xs = np.asarray(xs).reshape(1, 3, 8, *np.asarray(xs).shape[2:])
    ys = np.asarray(ys).reshape(1, 3, 8)
    lr = float(faded_learning_rate(cfg.learning_rate, cfg.fading_rate, 0))
    w = jnp.asarray(w0)
    for s in range(3):
        w = w - lr * grad(w, jnp.asarray(xs[0, s]), jnp.asarray(ys[0, s]))

    exp.run_round(0)
    np.testing.assert_allclose(np.asarray(exp.state.weights), np.asarray(w),
                               atol=1e-6, rtol=1e-6)


def test_cli_choices_match_registries():
    """Drift guard: the CLI's curated choice lists must cover exactly the
    registered defenses and attacks (grid.py derives from the registries;
    cli.py stays literal for import-weight reasons — this test keeps them
    in sync)."""
    from attacking_federate_learning_tpu import cli
    from attacking_federate_learning_tpu.attacks import ATTACKS
    from attacking_federate_learning_tpu.defenses import DEFENSES

    from attacking_federate_learning_tpu.models.base import MODELS

    parser = cli.build_parser()
    actions = {a.dest: a for a in parser._actions}
    assert set(actions["defense"].choices) == set(DEFENSES.names())
    assert set(actions["attack"].choices) == {"auto"} | set(ATTACKS.names())
    assert set(actions["model"].choices) == set(MODELS.names())


def test_remat_grads_identical():
    """jax.checkpoint must not change values — only the backward's memory
    schedule."""
    a = _weights(rounds=2, remat=True)
    b = _weights(rounds=2, remat=False)
    np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
