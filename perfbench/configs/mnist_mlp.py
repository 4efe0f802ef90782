"""Plain reference of ``MnistNet`` (reference data_sets.py:13-30): 784 ->
100 -> 10, ReLU between, torch layouts.  NumPy, float64, from the flat
wire vector in torch ``.parameters()`` order: fc1.weight (100, 784),
fc1.bias, fc2.weight (10, 100), fc2.bias -- d = 79,510."""

import numpy as np

from perfbench import modelcheck

WIRE_DIM = 79_510


def logits(w, x):
    w = np.asarray(w, np.float64)
    if w.shape != (WIRE_DIM,):
        raise ValueError(f"mnist_mlp: wire vector of shape {w.shape}")
    W1, b1 = w[:78400].reshape(100, 784), w[78400:78500]
    W2, b2 = w[78500:79500].reshape(10, 100), w[79500:]
    x = np.asarray(x, np.float64).reshape(len(x), -1)
    return np.maximum(x @ W1.T + b1, 0.0) @ W2.T + b2


def check(exp, weights, dataset, seed):
    return modelcheck.count_check(exp, logits, weights, dataset)


def train_flops_per_sample():
    """Forward 2 FLOP a multiply-add, backward twice that (both gradients
    at every layer, the usual 3 x forward); recomputation not counted."""
    return 3 * 2 * (784 * 100 + 100 * 10)
