"""MNIST CNN (LeNet-style): 2 conv + 2 fc, log-softmax head.

Beyond-reference model filling BASELINE.json benchmark config #2
("MNIST CNN, 100 clients, Krum vs ALIE") — the reference itself ships only
the MLP for MNIST (reference data_sets.py:13-30).  Architecture follows the
classic torch MNIST example: conv1 1->10 k5, MaxPool(2); conv2 10->20 k5,
MaxPool(2); fc 320 -> 50 -> 10.  Spatial trace on 28x28 NCHW input:
28 -conv5-> 24 -pool2-> 12 -conv5-> 8 -pool2-> 4.
ReLU behind the pool: same function and gradient, see
``layers.relu_max_pool2d``.
Parameter order conv1.{weight,bias}, conv2.{weight,bias}, fc1, fc2 —
d = 21,840.
"""

from __future__ import annotations

from collections import OrderedDict

import jax

from attacking_federate_learning_tpu.models import layers as L
from attacking_federate_learning_tpu.models.base import MODELS, Model


def _init(key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    # OrderedDict in torch .parameters() definition order (wire format).
    return OrderedDict([
        ("conv1", L.conv_init(k1, 1, 10, 5)),
        ("conv2", L.conv_init(k2, 10, 20, 5)),
        ("fc1", L.linear_init(k3, 320, 50)),
        ("fc2", L.linear_init(k4, 50, 10)),
    ])


def _apply(params, x):
    x = x.reshape((x.shape[0], 1, 28, 28))
    x = L.relu_max_pool2d(L.conv2d(params["conv1"], x), 2)
    x = L.relu_max_pool2d(L.conv2d(params["conv2"], x), 2)
    x = x.reshape((x.shape[0], -1))
    x = jax.nn.relu(L.linear(params["fc1"], x))
    return L.log_softmax(L.linear(params["fc2"], x))


@MODELS.register("mnist_cnn")
def mnist_cnn() -> Model:
    return Model(name="mnist_cnn", init=_init, apply=_apply,
                 input_shape=(1, 28, 28), num_classes=10)
