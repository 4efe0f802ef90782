"""Baseline Byzantine attacks for grid comparisons.

The reference ships exactly two attacks (ALIE and the clipped backdoor);
these textbook baselines give the defense grid its classical comparison
points.  Same pure ``craft`` seam as every other attack.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from attacking_federate_learning_tpu.attacks.base import Attack, cohort_stats


class SignFlipAttack(Attack):
    """Submit the negated cohort mean scaled by num_std — classic
    gradient-ascent Byzantine behavior."""

    name = "signflip"

    def craft(self, mal_grads, ctx=None):
        mean, _ = cohort_stats(mal_grads)
        return -self.num_std * mean


class GaussianNoiseAttack(Attack):
    """Replace the cohort gradient with pure Gaussian noise at num_std
    times the cohort's per-coordinate std."""

    name = "noise"

    def __init__(self, num_std: float, seed: int = 0):
        super().__init__(num_std)
        self._key = jax.random.key(seed)

    def operands(self):
        return self._key

    def craft(self, mal_grads, ctx=None):
        mean, stdev = cohort_stats(mal_grads)
        # Per-round key keeps the fused round a pure function of its
        # inputs while varying the noise each round.
        rnd = ctx.round if ctx is not None else 0
        key = jax.random.fold_in(self._operands_from(ctx),
                                 jnp.asarray(rnd, jnp.int32))
        noise = jax.random.normal(key, mean.shape, mean.dtype)
        return mean + self.num_std * stdev * noise
