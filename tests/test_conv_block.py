"""ReLU behind the max-pool is ReLU in front of it (PERF.md section 6, PR 35).

The conv models compute ``relu(max_pool2d(conv2d(x)))``
(``layers.relu_max_pool2d``) where the reference nets say
``max_pool2d(relu(conv2d(x)))``.  ReLU is monotone and leaves positive
values as they are, so the two are one function with one gradient — not
to a tolerance: to the bit, ties and all-non-positive windows included.
The reference order lives on here, as the twin every case is held to.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu.core.client import (
    make_client_grad_fn, make_client_update_fn
)
from attacking_federate_learning_tpu.models import get_model
from attacking_federate_learning_tpu.models import layers as L
from attacking_federate_learning_tpu.utils.flatten import make_flattener

# name -> ((conv, pool size) ..., (fc ...)): the blocks of models/cifar10.py
# and models/mnist_cnn.py, in the order the reference applies them.
CONV_MODELS = {
    "cifar10_cnn": ((("conv1", 3), ("conv2", 4)), ("fc1", "fc2", "fc3")),
    "mnist_cnn": ((("conv1", 2), ("conv2", 2)), ("fc1", "fc2")),
}
N, B = 3, 4


def old_order(name):
    """``get_model(name)`` with the reference's literal block,
    ``max_pool2d(relu(conv2d(x)))``: ReLU at the convolution's size."""
    model = get_model(name)
    convs, fcs = CONV_MODELS[name]

    def apply(params, x):
        x = x.reshape((x.shape[0],) + model.input_shape)
        for conv, k in convs:
            x = L.max_pool2d(jax.nn.relu(L.conv2d(params[conv], x)), k)
        x = x.reshape((x.shape[0], -1))
        for fc in fcs[:-1]:
            x = jax.nn.relu(L.linear(params[fc], x))
        return L.log_softmax(L.linear(params[fcs[-1]], x))

    return model._replace(apply=apply)


def assert_same_bits(new, old):
    """Equal as bit patterns: a last-bit difference, a zero of the other
    sign or a nan of another payload all fail."""
    new, old = np.asarray(new), np.asarray(old)
    assert new.dtype == old.dtype == np.float32
    np.testing.assert_array_equal(new.view(np.uint32), old.view(np.uint32))


def _problem(name, seed):
    model = get_model(name)
    kp, kx, ky = jax.random.split(jax.random.key(seed), 3)
    params = model.init(kp)
    xs = jax.random.normal(kx, (N, B) + model.input_shape, jnp.float32)
    ys = jax.random.randint(ky, (N, B), 0, model.num_classes)
    return model, params, xs, ys


def _conv1_windows(params, xs, k):
    """conv-1's raw output cut into the pool's windows: (..., k*k)."""
    a = L.conv2d(params["conv1"], xs.reshape((-1,) + xs.shape[2:]))
    m, c, h, w = a.shape
    a = a[:, :, :h // k * k, :w // k * k]
    a = a.reshape(m, c, h // k, k, w // k, k).transpose(0, 1, 2, 4, 3, 5)
    return np.asarray(a.reshape(m, c, h // k, w // k, k * k))


@functools.cache
def _both_orders(name, remat=False):
    """(flattener, [(apply, client grads) of the model, of its twin]),
    jitted once for every case of a model."""
    flat = make_flattener(get_model(name).init(jax.random.key(0)))
    return flat, [(jax.jit(m.apply),
                   jax.jit(make_client_grad_fn(m, flat, remat=remat)))
                  for m in (get_model(name), old_order(name))]


def _assert_forward_and_grads_equal(name, params, xs, ys, remat=False):
    flat, ((apply, grads), (twin_apply, twin_grads)) = _both_orders(name,
                                                                    remat)
    w = flat.ravel(params)
    x2 = xs.reshape((-1,) + xs.shape[2:])
    assert_same_bits(apply(params, x2), twin_apply(params, x2))
    new = grads(w, xs, ys)
    assert new.shape == (N, flat.dim)
    assert_same_bits(new, twin_grads(w, xs, ys))
    return np.asarray(new)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("name", list(CONV_MODELS))
def test_random_inputs(name, seed):
    _, params, xs, ys = _problem(name, seed)
    g = _assert_forward_and_grads_equal(name, params, xs, ys)
    assert np.abs(g[:, :params["conv1"]["weight"].size]).max() > 0


@pytest.mark.parametrize("name", list(CONV_MODELS))
def test_ties_between_positive_maxima(name):
    """Inputs on a grid of halves and conv weights on a grid of eighths:
    every conv-1 value is an exact multiple of 1/16, so windows whose
    positive maximum occurs twice are common, and the pool's first
    arg-max rule decides where the gradient goes."""
    _, params, xs, ys = _problem(name, 3)
    xs = jnp.round(xs * 2) / 2
    for conv, _ in CONV_MODELS[name][0]:
        params[conv] = jax.tree.map(lambda a: jnp.round(a * 8) / 8 + 0.125,
                                    params[conv])
    win = _conv1_windows(params, xs, CONV_MODELS[name][0][0][1])
    top = win.max(-1, keepdims=True)
    tied = ((win == top).sum(-1) > 1) & (top[..., 0] > 0)
    assert tied.mean() > 0.02, tied.mean()
    _assert_forward_and_grads_equal(name, params, xs, ys)


@pytest.mark.parametrize("shift", ["all", "half"])
@pytest.mark.parametrize("name", list(CONV_MODELS))
def test_windows_with_no_positive_value(name, shift):
    """conv-1's bias pushed down until every window ('all'), or about
    every second one ('half'), has its maximum <= 0: the old order routes
    the gradient to the window's first zero and ReLU zeroes it there, the
    new order zeroes it before the pool."""
    _, params, xs, ys = _problem(name, 4)
    k = CONV_MODELS[name][0][0][1]
    top = _conv1_windows(params, xs, k).max(-1)
    down = 1e3 if shift == "all" else float(np.median(top))
    params["conv1"]["bias"] = params["conv1"]["bias"] - down
    dead = (_conv1_windows(params, xs, k).max(-1) <= 0).mean()
    assert (dead == 1.0) if shift == "all" else (0.3 < dead < 0.7), dead
    g = _assert_forward_and_grads_equal(name, params, xs, ys)
    if shift == "all":
        n1 = params["conv1"]["weight"].size + params["conv1"]["bias"].size
        assert not g[:, :n1].any()


@pytest.mark.parametrize("k", [2, 3, 4])
def test_signed_zeros_in_a_window(k):
    """The block alone on windows that mix -0.0, +0.0, negatives and a
    positive in every arrangement that could tell the orders apart: the
    value (its sign bit too) and the routed cotangent are the same."""
    rows = [
        [-0.0, 0.0], [0.0, -0.0], [-0.0, -0.0], [0.0, 0.0],
        [-0.0, -1.0], [-1.0, -0.0], [0.0, -1.0], [-1.0, 0.0],
        [-0.0, 0.0, 2.0], [2.0, -0.0, 2.0], [-0.0, 2.0, 0.0, 2.0],
        [-1.0, -2.0], [-3.0],
    ]
    x = np.full((len(rows), 2, k * k), -5.0, np.float32)
    for i, row in enumerate(rows):
        x[i, 0, :len(row)] = row
        x[i, 1, k * k - len(row):] = row[::-1]
    # (rows, 2, k*k) windows -> NCHW with the windows side by side.
    x = jnp.asarray(x.reshape(len(rows), 2, 1, k, k).transpose(0, 2, 3, 1, 4)
                    .reshape(len(rows), 1, k, 2 * k))
    g = jax.random.normal(jax.random.key(5), (len(rows), 1, 1, 2))

    def old(x):
        return L.max_pool2d(jax.nn.relu(x), k)

    def new(x):
        return L.relu_max_pool2d(x, k)

    for wrap in (lambda f: f, jax.jit):
        y_new, vjp_new = jax.vjp(wrap(new), x)
        y_old, vjp_old = jax.vjp(wrap(old), x)
        assert_same_bits(y_new, y_old)
        assert_same_bits(vjp_new(g)[0], vjp_old(g)[0])


@pytest.mark.parametrize("name", list(CONV_MODELS))
def test_remat(name):
    _, params, xs, ys = _problem(name, 6)
    _assert_forward_and_grads_equal(name, params, xs, ys, remat=True)


@pytest.mark.parametrize("name", list(CONV_MODELS))
def test_two_local_steps(name):
    model, params, xs, ys = _problem(name, 7)
    flat = make_flattener(params)
    w = flat.ravel(params)
    xs = jnp.stack([xs, xs[::-1]], axis=1)
    ys = jnp.stack([ys, ys[::-1]], axis=1)
    out = [jax.jit(make_client_update_fn(m, flat, local_steps=2))(
               w, xs, ys, 0.05, 0.1) for m in (model, old_order(name))]
    assert out[0].shape == (N, flat.dim)
    assert_same_bits(*out)
