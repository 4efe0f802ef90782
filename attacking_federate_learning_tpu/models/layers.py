"""Functional layers with torch-compatible parameter layouts and inits.

Parameter layout parity matters because the wire format (flat vector, see
utils/flatten.py) must match the reference byte-for-byte in ordering:
Linear weights are (out, in) applied as ``x @ W.T + b`` and Conv weights are
(O, I, kH, kW) in NCHW, exactly torch's ``.parameters()`` layouts used by the
reference models (reference data_sets.py:13-61).

Init parity: the reference xavier-initializes only fc1/conv1 weights
(reference data_sets.py:17, :37) and leaves everything else at torch defaults
(kaiming_uniform(a=sqrt(5)) for weights -> U(-1/sqrt(fan_in), 1/sqrt(fan_in));
bias U(-1/sqrt(fan_in), 1/sqrt(fan_in))).
"""

from __future__ import annotations

import math
from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax import lax


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def xavier_uniform(key, shape, fan_in, fan_out, dtype=jnp.float32):
    bound = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def torch_default_uniform(key, shape, fan_in, dtype=jnp.float32):
    # torch kaiming_uniform(a=sqrt(5)) reduces to U(+-1/sqrt(fan_in));
    # torch bias init uses the same bound.
    bound = 1.0 / math.sqrt(fan_in) if fan_in > 0 else 0.0
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def linear_init(key, in_features, out_features, xavier=False, dtype=jnp.float32):
    kw, kb = jax.random.split(key)
    if xavier:
        w = xavier_uniform(kw, (out_features, in_features), in_features,
                           out_features, dtype)
    else:
        w = torch_default_uniform(kw, (out_features, in_features), in_features,
                                  dtype)
    b = torch_default_uniform(kb, (out_features,), in_features, dtype)
    # OrderedDict: ravel_pytree sorts plain-dict keys, which would put bias
    # before weight and break wire-format parity with torch .parameters().
    return OrderedDict([("weight", w), ("bias", b)])


def conv_init(key, in_ch, out_ch, ksize, xavier=False, bias=True,
              dtype=jnp.float32):
    kw, kb = jax.random.split(key)
    fan_in = in_ch * ksize * ksize
    fan_out = out_ch * ksize * ksize
    shape = (out_ch, in_ch, ksize, ksize)
    if xavier:
        w = xavier_uniform(kw, shape, fan_in, fan_out, dtype)
    else:
        w = torch_default_uniform(kw, shape, fan_in, dtype)
    p = OrderedDict([("weight", w)])
    if bias:
        p["bias"] = torch_default_uniform(kb, (out_ch,), fan_in, dtype)
    return p


# --------------------------------------------------------------------------
# forward ops (NCHW throughout, matching the reference's torch layouts)
# --------------------------------------------------------------------------

def linear(p, x):
    return x @ p["weight"].T + p["bias"]


def conv2d(p, x, stride=1, padding="VALID"):
    y = lax.conv_general_dilated(
        x, p["weight"],
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    if "bias" in p:
        y = y + p["bias"][None, :, None, None]
    return y


def max_pool2d(x, ksize, stride=None):
    # torch MaxPool2d(k) defaults stride=k, no padding (floor mode) —
    # used by the reference CIFAR10 net (data_sets.py:38, :40).
    stride = stride or ksize
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, 1, ksize, ksize),
        window_strides=(1, 1, stride, stride),
        padding="VALID",
    )


def relu_max_pool2d(x, ksize):
    # relu(max_pool(x)), which IS torch's max_pool(relu(x)) — forward and
    # gradient, to the bit — because ReLU is monotone and keeps positives
    # as they are: max(relu(a_i)) == relu(max(a_i)) with no rounding; a
    # window whose maximum is > 0 has the same first arg-max before and
    # after ReLU, so the pool routes g to the same element and ReLU's
    # factor there is 1; a window whose maximum is <= 0 gets 0 in both
    # orders (pool-to-first-zero then ReLU'(x <= 0) = 0, or 0 * g first).
    # ReLU and its backward then run on the pooled tensor, k*k times
    # smaller (PERF.md section 6, PR 35; tests/test_conv_block.py holds
    # both orders equal as bit patterns).
    return jax.nn.relu(max_pool2d(x, ksize))


def avg_pool2d(x, ksize, stride=None):
    stride = stride or ksize
    summed = lax.reduce_window(
        x, 0.0, lax.add,
        window_dimensions=(1, 1, ksize, ksize),
        window_strides=(1, 1, stride, stride),
        padding="VALID",
    )
    return summed / (ksize * ksize)


def log_softmax(x):
    return jax.nn.log_softmax(x, axis=-1)


def nll_loss(log_probs, targets):
    # torch NLLLoss(mean) over log-probabilities (reference user.py:36,
    # server.py:17): (batch, classes) against (batch,) for a classifier,
    # (batch, length, vocabulary) against (batch, length) next tokens for
    # a sequence model.
    return -jnp.take_along_axis(
        log_probs, targets[..., None], axis=-1
    ).squeeze(-1).mean()
