"""CIFAR-10 CNN: 2 conv + 3 fc, log-softmax head.

Reproduces reference ``Cifar10Net`` (data_sets.py:33-61): conv1 3->16 k3
(xavier weight, data_sets.py:37), MaxPool(3); conv2 16->64 k4, MaxPool(4);
fc 64 -> 384 -> 192 -> 10.  Spatial trace on 32x32 NCHW input:
32 -conv3-> 30 -pool3-> 10 -conv4-> 7 -pool4-> 1.
ReLU behind the pool: same function and gradient, see
``layers.relu_max_pool2d``.
Parameter order conv1.{weight,bias}, conv2.{weight,bias}, fc1..fc3 —
d = 117,706.
"""

from __future__ import annotations

from collections import OrderedDict

import jax

from attacking_federate_learning_tpu.models import layers as L
from attacking_federate_learning_tpu.models.base import MODELS, Model


def _init(key):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    # OrderedDict in torch .parameters() definition order (wire format).
    return OrderedDict([
        ("conv1", L.conv_init(k1, 3, 16, 3, xavier=True)),
        ("conv2", L.conv_init(k2, 16, 64, 4)),
        ("fc1", L.linear_init(k3, 64 * 1 * 1, 384)),
        ("fc2", L.linear_init(k4, 384, 192)),
        ("fc3", L.linear_init(k5, 192, 10)),
    ])


def _apply(params, x):
    x = x.reshape((x.shape[0], 3, 32, 32))
    x = L.relu_max_pool2d(L.conv2d(params["conv1"], x), 3)
    x = L.relu_max_pool2d(L.conv2d(params["conv2"], x), 4)
    x = x.reshape((x.shape[0], -1))
    x = jax.nn.relu(L.linear(params["fc1"], x))
    x = jax.nn.relu(L.linear(params["fc2"], x))
    return L.log_softmax(L.linear(params["fc3"], x))


@MODELS.register("cifar10_cnn")
def cifar10_cnn() -> Model:
    return Model(name="cifar10_cnn", init=_init, apply=_apply,
                 input_shape=(3, 32, 32), num_classes=10)
