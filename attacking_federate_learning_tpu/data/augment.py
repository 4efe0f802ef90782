"""Train-time image augmentation inside the round program.

The reference's CIFAR100 train transform (reference data_sets.py:157-166) is
reflect-pad 4 -> RandomCrop(32) -> RandomHorizontalFlip -> normalize, applied
per sample by host-side torchvision workers.  Here the same augmentation is
a pure jittable op over the whole (n_clients, batch, C, H, W) gather — it
runs inside the fused round program on device, keyed from the experiment
seed and round index, so every round (and every resume) sees the same
deterministic stream (SURVEY.md §2.4 #13: all randomness is explicit
jax.random plumbing).

Crop/flip act on *normalized* images while the reference crops before
normalizing — elementwise normalization commutes with crop/flip, so the
pixel streams are identical.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def reflect_crop_flip(images, key, pad: int = 4):
    """Random crop-from-reflect-pad + horizontal flip, per image.

    images: (..., C, H, W); any number of leading batch axes.  Each image
    draws its own crop offset (uniform over the (2*pad+1)^2 grid, matching
    RandomCrop(H) on an H+2*pad padded image) and flip bit (p=0.5).
    """
    *lead, c, h, w = images.shape
    flat = images.reshape((-1, c, h, w))
    m = flat.shape[0]
    k_off, k_flip = jax.random.split(key)
    offsets = jax.random.randint(k_off, (m, 2), 0, 2 * pad + 1)
    flips = jax.random.bernoulli(k_flip, 0.5, (m,))

    def one(img, off, flip):
        padded = jnp.pad(img, ((0, 0), (pad, pad), (pad, pad)),
                         mode="reflect")
        crop = lax.dynamic_slice(padded, (0, off[0], off[1]), (c, h, w))
        return jnp.where(flip, crop[..., ::-1], crop)

    out = jax.vmap(one)(flat, offsets, flips)
    return out.reshape(images.shape)


def augment_key(seed: int):
    """The augmentation's own key stream, derived from the experiment
    seed; the engine passes it to the round program as an operand
    (core/engine.py RoundData) and folds the round index in there."""
    return jax.random.key(seed ^ 0x5EED_A06)


def round_augment_key(seed: int, t):
    """Per-round augmentation key: fold the round index into the
    experiment's seed stream (works with a traced ``t`` inside jit)."""
    return jax.random.fold_in(augment_key(seed), t)
