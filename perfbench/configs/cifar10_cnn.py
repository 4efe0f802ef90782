"""Plain reference of ``Cifar10Net`` (reference data_sets.py:33-61): conv
3->16 k3, ReLU, MaxPool(3); conv 16->64 k4, ReLU, MaxPool(4); fc 64 -> 384
-> 192 -> 10 with ReLU between.  32 -conv3-> 30 -pool3-> 10 -conv4-> 7
-pool4-> 1.  NumPy, float64, NCHW, from the flat wire vector in torch
``.parameters()`` order (conv weights (O, I, kH, kW), linear weights (out,
in), each followed by its bias) -- d = 117,706."""

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from perfbench import modelcheck

WIRE_DIM = 117_706
SHAPES = [(16, 3, 3, 3), (16,), (64, 16, 4, 4), (64,), (384, 64), (384,),
          (192, 384), (192,), (10, 192), (10,)]
CHUNK = 500     # samples per im2col block


def _conv(x, W, b):
    """VALID, stride 1: (N, I, H, W) * (O, I, k, k) -> (N, O, H-k+1, W-k+1)."""
    k = W.shape[-1]
    win = sliding_window_view(x, (k, k), axis=(2, 3))   # N I H' W' k k
    n, _, h, w_ = win.shape[:4]
    cols = win.transpose(0, 2, 3, 1, 4, 5).reshape(n * h * w_, -1)
    out = cols @ W.reshape(W.shape[0], -1).T + b
    return out.reshape(n, h, w_, -1).transpose(0, 3, 1, 2)


def _pool(x, k):
    """MaxPool(k), stride k, VALID (the ragged edge is dropped, as torch)."""
    n, c, h, w_ = x.shape
    x = x[:, :, :h // k * k, :w_ // k * k]
    return x.reshape(n, c, h // k, k, w_ // k, k).max(axis=(3, 5))


def logits(w, x):
    w = np.asarray(w, np.float64)
    if w.shape != (WIRE_DIM,):
        raise ValueError(f"cifar10_cnn: wire vector of shape {w.shape}")
    params, at = [], 0
    for shape in SHAPES:
        size = int(np.prod(shape))
        params.append(w[at:at + size].reshape(shape))
        at += size
    c1w, c1b, c2w, c2b, f1w, f1b, f2w, f2b, f3w, f3b = params
    out = []
    for lo in range(0, len(x), CHUNK):
        h = np.asarray(x[lo:lo + CHUNK], np.float64).reshape(-1, 3, 32, 32)
        h = _pool(np.maximum(_conv(h, c1w, c1b), 0.0), 3)
        h = _pool(np.maximum(_conv(h, c2w, c2b), 0.0), 4)
        h = h.reshape(len(h), -1)
        h = np.maximum(h @ f1w.T + f1b, 0.0)
        h = np.maximum(h @ f2w.T + f2b, 0.0)
        out.append(h @ f3w.T + f3b)
    return np.concatenate(out)


def check(exp, weights, dataset, seed):
    return modelcheck.count_check(exp, logits, weights, dataset)


def train_flops_per_sample():
    """Forward 2 FLOP a multiply-add, backward twice that (both gradients
    at every layer, the usual 3 x forward); recomputation not counted.
    Multiply-adds: conv1 30*30*16 outputs of 3*3*3, conv2 7*7*64 outputs of
    4*4*16, then the three linear layers."""
    macs = (30 * 30 * 16 * 27 + 7 * 7 * 64 * 256
            + 64 * 384 + 384 * 192 + 192 * 10)
    return 3 * 2 * macs
