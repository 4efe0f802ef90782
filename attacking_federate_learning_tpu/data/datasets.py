"""Datasets as device-residable arrays.

The reference streams data through torchvision ``DataLoader``s with one
DataLoader per client (reference user.py:46-55) — a host-side Python iterator
per client, which is exactly what serializes its round loop.  Here a dataset
is a pair of dense arrays (images normalized up-front, labels int32) that
lives in HBM; clients are rows of an index matrix and a "batch" is one
gather.  MNIST/CIFAR fit comfortably in HBM (MNIST train = 179 MB f32).

Loaders read the raw distribution files directly (MNIST IDX, CIFAR-10/100
python pickles) — no torchvision dependency.  When raw files are absent
(e.g. an air-gapped machine) the SYNTH_* datasets provide deterministic,
learnable class-structured data with identical shapes and normalization, so
every code path (training, triggers, defenses) exercises the same math.

Normalization matches the reference transforms: MNIST (x-0.1307)/0.3081
(reference data_sets.py:26-27), CIFAR10 (x-0.5)/0.5 (data_sets.py:56-57),
CIFAR100 per-channel stats (data_sets.py:154-155).  Backdoor triggers are
applied *after* normalization, as in the reference (data_sets.py:26-30
appends the trigger transform after Normalize; backdoor.py:49).
"""

from __future__ import annotations

import gzip
import os
import pickle
import struct
from typing import NamedTuple, Optional

import numpy as np

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.utils.profiling import span


class Dataset(NamedTuple):
    name: str
    train_x: np.ndarray   # (N, ...) normalized float32; (N, L) int32 ids
    train_y: np.ndarray   # (N,) int32; (N, L) int32 next tokens
    test_x: np.ndarray
    test_y: np.ndarray
    num_classes: int


MNIST_MEAN, MNIST_STD = 0.1307, 0.3081
CIFAR10_MEAN, CIFAR10_STD = 0.5, 0.5
CIFAR100_MEAN = np.array([125.3, 123.0, 113.9], np.float32) / 255.0
CIFAR100_STD = np.array([63.0, 62.1, 66.7], np.float32) / 255.0


# --------------------------------------------------------------------------
# raw-file loaders
# --------------------------------------------------------------------------

def _open_maybe_gz(path):
    if os.path.exists(path + ".gz"):
        return gzip.open(path + ".gz", "rb")
    return open(path, "rb")


def _read_idx(path) -> np.ndarray:
    with _open_maybe_gz(path) as f:
        magic = struct.unpack(">I", f.read(4))[0]
        ndim = magic & 0xFF
        dims = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
        return np.frombuffer(f.read(), dtype=np.uint8).reshape(dims)


def load_mnist(data_dir: str) -> Dataset:
    d = os.path.join(data_dir, "MNIST", "raw")
    if not os.path.isdir(d):
        d = data_dir
    tx = _read_idx(os.path.join(d, "train-images-idx3-ubyte"))
    ty = _read_idx(os.path.join(d, "train-labels-idx1-ubyte"))
    vx = _read_idx(os.path.join(d, "t10k-images-idx3-ubyte"))
    vy = _read_idx(os.path.join(d, "t10k-labels-idx1-ubyte"))

    def norm(x):
        x = x.astype(np.float32) / 255.0
        return ((x - MNIST_MEAN) / MNIST_STD)[:, None, :, :]  # (N,1,28,28)

    return Dataset("MNIST", norm(tx), ty.astype(np.int32),
                   norm(vx), vy.astype(np.int32), 10)


def _load_cifar_pickles(paths, key_x=b"data", key_y=b"labels"):
    xs, ys = [], []
    for p in paths:
        with open(p, "rb") as f:
            batch = pickle.load(f, encoding="bytes")
        xs.append(batch[key_x])
        ys.extend(batch[key_y])
    x = np.concatenate(xs).reshape(-1, 3, 32, 32)
    return x, np.asarray(ys, np.int32)


def load_cifar10(data_dir: str) -> Dataset:
    d = os.path.join(data_dir, "cifar-10-batches-py")
    if not os.path.isdir(d):
        d = data_dir
    tx, ty = _load_cifar_pickles(
        [os.path.join(d, f"data_batch_{i}") for i in range(1, 6)])
    vx, vy = _load_cifar_pickles([os.path.join(d, "test_batch")])

    def norm(x):
        return (x.astype(np.float32) / 255.0 - CIFAR10_MEAN) / CIFAR10_STD

    return Dataset("CIFAR10", norm(tx), ty, norm(vx), vy, 10)


def load_cifar100(data_dir: str) -> Dataset:
    d = os.path.join(data_dir, "cifar-100-python")
    if not os.path.isdir(d):
        d = data_dir
    tx, ty = _load_cifar_pickles([os.path.join(d, "train")],
                                 key_y=b"fine_labels")
    vx, vy = _load_cifar_pickles([os.path.join(d, "test")],
                                 key_y=b"fine_labels")

    def norm(x):
        x = x.astype(np.float32) / 255.0
        return (x - CIFAR100_MEAN[:, None, None]) / CIFAR100_STD[:, None, None]

    return Dataset("CIFAR100", norm(tx), ty, norm(vx), vy, 100)


# --------------------------------------------------------------------------
# deterministic synthetic datasets (shape/normalization-identical stand-ins)
# --------------------------------------------------------------------------

def make_synthetic(shape, num_classes: int, n_train: int, n_test: int,
                   seed: int, name: str,
                   mean, std, signal: float = 0.35,
                   noise_scale: float = 0.25,
                   smooth_protos: bool = False) -> Dataset:
    """Class-prototype Gaussians in pixel space, then normalized.

    Each class c gets a fixed prototype image p_c; samples are
    clip(0.5 + signal*p_c + noise_scale*noise, 0, 1).  The defaults make
    classes separable enough that an MLP clears 70% within a handful of FL
    rounds (the reference's checkpoint threshold, main.py:84); lower
    signal-to-noise (e.g. the *_HARD variants) slows convergence so
    attack-vs-defense accuracy deltas are visible in behavioral tests.

    ``smooth_protos``: draw the prototypes on a coarse (H/4, W/4) grid
    and nearest-upsample, giving them the low-frequency spatial
    structure conv+pool architectures are biased toward.  Per-pixel
    i.i.d. prototypes are near-invisible to a CNN (pooling averages
    them out — measured: cifar10_cnn stays at random accuracy on them
    while an MLP learns fine), so CNN-targeted synthetics must be
    spatially smooth to exercise real convergence.
    """
    rng = np.random.default_rng(seed)
    if smooth_protos and len(shape) == 3 and shape[1] % 4 == 0 \
            and shape[2] % 4 == 0:
        coarse = rng.standard_normal(
            (num_classes, shape[0], shape[1] // 4, shape[2] // 4)
        ).astype(np.float32)
        protos = np.kron(coarse, np.ones((1, 1, 4, 4), np.float32))
    else:
        protos = rng.standard_normal(
            (num_classes,) + shape).astype(np.float32)
    protos /= np.linalg.norm(protos.reshape(num_classes, -1), axis=1).reshape(
        (num_classes,) + (1,) * len(shape)) / np.sqrt(np.prod(shape))

    # MNIST-like quiet border: real digits leave the image margin near zero,
    # which is what lets a corner trigger persist (honest gradients barely
    # constrain border weights).  Applies only to 1-channel (MNIST-shaped)
    # synthetics — real CIFAR images have no quiet border.
    border = 4 if (shape[0] == 1 and shape[-1] >= 28) else 0
    if border:
        edge_mask = np.zeros(shape, np.float32)
        edge_mask[..., border:-border, border:-border] = 1.0
    else:
        edge_mask = np.ones(shape, np.float32)

    def gen(n):
        y = rng.integers(0, num_classes, size=n).astype(np.int32)
        noise = rng.standard_normal((n,) + shape).astype(np.float32)
        x = np.clip((0.5 + signal * protos[y] + noise_scale * noise)
                    * edge_mask, 0.0, 1.0)
        return (x - mean) / std, y

    tx, ty = gen(n_train)
    vx, vy = gen(n_test)
    return Dataset(name, tx, ty, vx, vy, num_classes)


# --------------------------------------------------------------------------
# deterministic synthetic token contexts (the sequence models' data)
# --------------------------------------------------------------------------

# tokens a context has unless --seq-len says: 24 in the tiny set, 8,192
# in every set of a published model's slice (C.token_vocab names the slice)
TOKEN_SEQ_LEN = {C.SYNTH_TOKENS_TINY: 24}
TOKEN_FOLLOW = 0.5          # P(next token = the previous one's successor)


def make_synthetic_tokens(vocab: int, seq_len: int, n_train: int,
                          n_test: int, seed: int, name: str) -> Dataset:
    """Contexts of ``seq_len`` token ids below ``vocab``, one context one
    document (no packing), with the next token at every position as the
    label: ``x`` (N, L) and ``y`` (N, L) with ``y[:, t]`` the token after
    ``x[:, t]`` (contexts are drawn one token longer), int32 throughout.

    Zipf-like unigram frequencies, p(id) ~ 1 / (id + shift) with shift =
    max(3, vocab / 192) (98.9 at 18,992 ids: the most frequent token is
    0.19 % of the stream; with a head as heavy as shift 3 gives, 3.8 %, how
    many tokens a chip's held experts draw is decided by where a dozen
    token ids happen to route, and a round's time moves 5 % with the seed:
    PERF.md section 6, PR 36), with a first-order dependence a model can
    learn: with probability
    ``TOKEN_FOLLOW`` the next token is the previous token's successor
    under a fixed seeded permutation of the vocabulary, else a fresh
    unigram draw.  A model that learns the unigram beats ln(vocab); one
    that learns the successor table halves what is left."""
    rng = np.random.default_rng(seed)
    successor = rng.permutation(vocab).astype(np.int32)
    cdf = np.cumsum(1.0 / (np.arange(vocab) + max(3.0, vocab / 192)))
    cdf /= cdf[-1]

    def gen(n):
        fresh = np.minimum(np.searchsorted(
            cdf, rng.random((n, seq_len + 1))), vocab - 1).astype(np.int32)
        follow = rng.random((n, seq_len + 1)) < TOKEN_FOLLOW
        tokens = fresh
        for t in range(1, seq_len + 1):
            tokens[:, t] = np.where(follow[:, t],
                                    successor[tokens[:, t - 1]],
                                    fresh[:, t])
        return (np.ascontiguousarray(tokens[:, :-1]),
                np.ascontiguousarray(tokens[:, 1:]))

    tx, ty = gen(n_train)
    vx, vy = gen(n_test)
    return Dataset(name, tx, ty, vx, vy, vocab)


def crop_contexts(dataset: Dataset, seq_len: Optional[int]) -> Dataset:
    """A token dataset's contexts cut to their first ``seq_len`` tokens
    (labels with them); anything else, or ``seq_len`` None, as it is."""
    if seq_len is None or C.token_vocab(dataset.name) is None:
        return dataset
    have = dataset.train_x.shape[1]
    if seq_len > have:
        raise ValueError(f"seq_len={seq_len} but {dataset.name} was made "
                         f"with contexts of {have} tokens")
    if seq_len == have:
        return dataset
    return dataset._replace(**{
        field: np.ascontiguousarray(getattr(dataset, field)[:, :seq_len])
        for field in ("train_x", "train_y", "test_x", "test_y")})


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

@span("setup.dataset")
def load_dataset(name: str, data_dir: str = "data", seed: int = 0,
                 synth_train: int = 10000, synth_test: int = 2000,
                 seq_len: Optional[int] = None) -> Dataset:
    if C.token_vocab(name) is not None:
        return make_synthetic_tokens(
            C.token_vocab(name),
            TOKEN_SEQ_LEN.get(name, 8192) if seq_len is None else seq_len,
            synth_train, synth_test, seed, name)
    if name == C.MNIST:
        try:
            return load_mnist(data_dir)
        except (FileNotFoundError, OSError):
            name = C.SYNTH_MNIST
    if name == C.CIFAR10:
        try:
            return load_cifar10(data_dir)
        except (FileNotFoundError, OSError):
            name = C.SYNTH_CIFAR10
    if name == C.CIFAR100:
        try:
            return load_cifar100(data_dir)
        except (FileNotFoundError, OSError):
            return make_synthetic(
                (3, 32, 32), 100, synth_train, synth_test, seed,
                C.CIFAR100 + "_SYNTH",
                CIFAR100_MEAN[:, None, None], CIFAR100_STD[:, None, None])
    if name == C.SYNTH_MNIST:
        return make_synthetic((1, 28, 28), 10, synth_train, synth_test, seed,
                              C.SYNTH_MNIST, MNIST_MEAN, MNIST_STD)
    if name == C.SYNTH_CIFAR10:
        return make_synthetic((3, 32, 32), 10, synth_train, synth_test, seed,
                              C.SYNTH_CIFAR10, CIFAR10_MEAN, CIFAR10_STD)
    if name == C.SYNTH_MNIST_HARD:
        # Low SNR: converges over tens of rounds instead of a handful, so
        # Byzantine attacks produce measurable accuracy deltas.
        return make_synthetic((1, 28, 28), 10, synth_train, synth_test, seed,
                              name, MNIST_MEAN, MNIST_STD,
                              signal=0.12, noise_scale=0.30)
    if name == C.SYNTH_CIFAR10_HARD:
        # CIFAR-shaped stand-in for convergence studies of the conv-net
        # + shadow-train composition (reference backdoor.py:108-159 at
        # data_sets.py:33-61 scale): spatially-smooth prototypes so a
        # CNN can actually learn them (see make_synthetic), at an SNR
        # low enough that training stays non-saturated over ~100+
        # rounds — the regime where the backdoor clip envelope is alive
        # (CLAUDE.md behavioral facts).
        return make_synthetic((3, 32, 32), 10, synth_train, synth_test, seed,
                              name, CIFAR10_MEAN, CIFAR10_STD,
                              signal=0.20, noise_scale=0.30,
                              smooth_protos=True)
    raise ValueError(f"Unknown dataset {name!r}")
