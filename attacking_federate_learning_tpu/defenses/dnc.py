"""DnC — divide-and-conquer spectral defense (Shejwalkar & Houmansadr,
NDSS'21, the companion defense to the min-max/min-sum attacks in
attacks/minmax.py).

Beyond-reference addition.  Each of ``n_iters`` rounds: subsample a random
sketch of coordinates, center the cohort there, take the top singular
direction of the centered sketch (power iteration — cheap, static-shape,
jit-native), score every client by its squared projection, and mark the
``filter_frac * f`` highest-scoring clients as outliers.  A client survives
only if NO iteration marked it; the aggregate is the mean of survivors
(falling back to the overall mean if the intersection empties — possible
at small cohorts).

Sketch keys derive deterministically from (seed, round, iteration): the
engine feeds the round index through the ``needs_round`` seam (the same
attribute convention FLTrust uses for ``needs_server_grad``), so every
round sees FRESH coordinate subsets — the paper's subsampling assumption —
while runs still reproduce exactly (SURVEY.md §2.4 #13).  When the sketch
covers all of d, scores are permutation-invariant, so a single iteration
suffices and the others are skipped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from attacking_federate_learning_tpu.defenses.kernels import DEFENSES

_N_ITERS = 5
_FILTER_FRAC = 1.5
_SKETCH_DIM = 2048
_POWER_STEPS = 10


def _top_direction(Sc, key):
    """Dominant right singular vector of the centered sketch via power
    iteration on Sc^T Sc (r-dim; never materializes the r x r Gram).

    The iterate starts from a key-derived random vector, not a constant:
    a fixed init lets a defense-aware adversary craft gradients whose
    dominant direction is orthogonal to it, stalling convergence toward
    a lesser direction; a random init has measure-zero overlap failure."""
    r = Sc.shape[1]
    v = jax.random.normal(key, (r,), Sc.dtype)
    v = v / jnp.maximum(jnp.linalg.norm(v), 1e-12)
    for _ in range(_POWER_STEPS):
        v = Sc.T @ (Sc @ v)
        v = v / jnp.maximum(jnp.linalg.norm(v), 1e-12)
    return v


def sketch_key(seed: int):
    """The sketch key stream of an experiment seed."""
    return jax.random.key(seed ^ 0xD0C)


@DEFENSES.register("DnC")
def dnc(users_grads, users_count, corrupted_count, n_iters: int = _N_ITERS,
        filter_frac: float = _FILTER_FRAC, sketch_dim: int = _SKETCH_DIM,
        seed: int = 0, round=0, telemetry=False, key=None):
    """``key``: the sketch stream ``sketch_key(seed)`` as an array — the
    engine's round programs pass it as an operand so the compiled text
    does not carry the seed; None derives it from ``seed`` here.
    ``telemetry=True`` additionally returns ``{'survivor_mask': (n,)
    f32 0/1 — clients no iteration marked as outliers, 'survivor_count':
    () int32}``."""
    G = users_grads.astype(jnp.float32)
    n, d = G.shape
    # Outliers removed per iteration; capped so at least one client can
    # survive every iteration.
    remove = min(int(filter_frac * corrupted_count), n - 1)
    if remove == 0:
        agg = jnp.mean(G, axis=0)
        if not telemetry:
            return agg
        return agg, {"survivor_mask": jnp.ones((n,), jnp.float32),
                     "survivor_count": jnp.asarray(n, jnp.int32)}
    keep = n - remove
    r = min(sketch_dim, d)
    if r == d:
        # Full-coverage sketch: every iteration sees the same matrix, and
        # power iteration converges to the same dominant direction from
        # any (random) init — one iteration suffices.
        n_iters = 1
    base_key = jax.random.fold_in(
        sketch_key(seed) if key is None else key,
        jnp.asarray(round, jnp.int32))

    good = jnp.ones((n,), bool)
    for i in range(n_iters):
        k_idx, k_pow = jax.random.split(jax.random.fold_in(base_key, i))
        if r == d:
            S = G
        else:
            idx = jax.random.choice(k_idx, d, (r,), replace=False)
            S = G[:, idx]
        Sc = S - jnp.mean(S, axis=0)[None, :]
        v = _top_direction(Sc, k_pow)
        scores = (Sc @ v) ** 2
        # Clients whose score ranks within the keep smallest survive
        # this iteration.
        _, keep_idx = lax.top_k(-scores, keep)
        good = good & jnp.zeros((n,), bool).at[keep_idx].set(True)

    w = good.astype(jnp.float32)
    survivors = jnp.sum(w)
    survivor_mean = (w @ G) / jnp.maximum(survivors, 1.0)
    # Empty intersection (possible at small n): overall mean, not zeros.
    agg = jnp.where(survivors > 0, survivor_mean, jnp.mean(G, axis=0))
    if not telemetry:
        return agg
    return agg, {"survivor_mask": w,
                 "survivor_count": survivors.astype(jnp.int32)}


# Engine seam: pass the round index so sketches refresh every round
# (core/engine.py:_aggregate_impl).
dnc.needs_round = True
