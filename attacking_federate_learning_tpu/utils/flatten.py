"""The wire format: params pytree <-> flat vector.

The reference's load-bearing abstraction is a flat float vector of all model
parameters (``flatten_params`` reference user.py:17-18, ``row_into_parameters``
user.py:21-28): server state, the (n_users, d) gradient matrix, defense inputs
and attack perturbations all live in that format.

Here the pytree is the primary representation (models run on pytrees) and the
flat vector appears only at the defense/attack boundary, via a pair of jitted
bijections built once per model with ``jax.flatten_util.ravel_pytree``.
Because model pytrees are ordered dicts in torch ``.parameters()`` order and
weights keep torch's (out, in) / (O, I, H, W) layouts, the flat vector is
bit-layout-compatible with the reference's wire format: a flat vector produced
by the reference loads into these models unchanged.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.flatten_util
import jax.numpy as jnp


LANES = 128     # a TPU vector's lanes: the width leaves are joined at


class FlatParams(NamedTuple):
    """Bijection between a model's params pytree and the flat wire vector."""
    ravel: Callable[[Any], jax.Array]     # pytree -> (d,)
    unravel: Callable[[jax.Array], Any]   # (d,) -> pytree
    dim: int                              # d


def make_flattener(example_params) -> FlatParams:
    """The bijection for ``example_params``' structure.  Only shapes are
    read: ``ravel_pytree`` runs under ``eval_shape``, so building it
    concatenates nothing (at d = 3.7e8 that concatenation is a second
    1.48 GB row and two minutes of compiling)."""
    made = {}

    def probe(tree):
        flat, made["unravel"] = jax.flatten_util.ravel_pytree(tree)
        return flat

    dim = int(jax.eval_shape(probe, example_params).shape[0])
    return FlatParams(ravel=ravel, unravel=made["unravel"], dim=dim)


def ravel(tree) -> jax.Array:
    """pytree -> (d,) in ``ravel_pytree``'s order.  Where every leaf is
    whole vectors of :data:`LANES` they are joined as (size / LANES,
    LANES) pieces, which the TPU compiler flattens by whole vectors (see
    :func:`write_row`); the values are ``ravel_pytree``'s either way."""
    leaves = jax.tree.leaves(tree)
    if leaves and all(leaf.size % LANES == 0 and leaf.dtype == leaves[0].dtype
                      for leaf in leaves):
        return _join_lanes(leaves)
    return jax.flatten_util.ravel_pytree(tree)[0]


@jax.jit        # one program when called eagerly, not one a leaf
def _join_lanes(leaves):
    return jnp.concatenate(
        [leaf.reshape(-1, LANES) for leaf in leaves]).reshape(-1)


def write_row(wire, row, tree):
    """``tree``'s leaves written into row ``row`` (traced) of the (n, d)
    wire matrix in the matrix's own dtype: the row :func:`ravel` would
    give, each leaf cast before it is joined, so no f32 (d,) copy exists
    beside the leaves.  A leaf reshaped straight to one dimension costs
    the TPU compiler minutes at 10^8 elements; joined by whole vectors
    (:func:`ravel`) it costs seconds (PERF.md section 6, PR 36)."""
    leaves = [leaf.astype(wire.dtype) for leaf in jax.tree.leaves(tree)]
    assert sum(leaf.size for leaf in leaves) == wire.shape[1], wire.shape
    return jax.lax.dynamic_update_slice(wire, ravel(leaves)[None, :],
                                        (row, 0))


def ravel_batch(trees) -> jax.Array:
    """Stacked pytrees (leading client axis) -> (n, d) matrix."""
    return jax.vmap(lambda t: jax.flatten_util.ravel_pytree(t)[0])(trees)


def tree_zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)
