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
TILE_ROWS = 8   # the rows one TPU tile of an f32 or a bf16 matrix holds


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


def _platform():
    return jax.default_backend()


def wire_path(rows: int) -> str:
    """How :func:`write_row` writes a row into an (n, d) wire of ``rows``
    = n on the default backend: ``"tiles"`` where every tile of the
    matrix holds every row, ``"plain"`` elsewhere.  The TPU compiler
    tiles an f32 or a bf16 wire 8 rows deep ((8, 128), and (8, 128)(2, 1)
    with rows packed in pairs), so on a TPU a wire of at most
    :data:`TILE_ROWS` rows is one band of tiles and any row's write reads
    and writes all of it: a dynamic update does so at a third of the
    chip's bandwidth behind a padded copy of the row, a select over the
    wire streams it, in place as the scan's carry (PERF.md, section 6).
    The CPU keeps rows whole and updates one in place."""
    return "tiles" if _platform() == "tpu" and rows <= TILE_ROWS else "plain"


def write_row(wire, row, tree):
    """``tree``'s leaves written into row ``row`` (traced) of the (n, d)
    wire matrix in the matrix's own dtype: the row :func:`ravel` would
    give, each leaf cast before it is joined, so no f32 (d,) copy exists
    beside the leaves.  A leaf reshaped straight to one dimension costs
    the TPU compiler minutes at 10^8 elements; joined by whole vectors
    (:func:`ravel`) it costs seconds (PERF.md section 6, PR 36)."""
    leaves = [leaf.astype(wire.dtype) for leaf in jax.tree.leaves(tree)]
    assert sum(leaf.size for leaf in leaves) == wire.shape[1], wire.shape
    flat = ravel(leaves)
    if wire_path(wire.shape[0]) == "tiles":
        i = jax.lax.broadcasted_iota(jnp.int32, (wire.shape[0], 1), 0)
        return jnp.where(i == row, flat[None, :], wire)
    return jax.lax.dynamic_update_slice(wire, flat[None, :], (row, 0))


def ravel_batch(trees) -> jax.Array:
    """Stacked pytrees (leading client axis) -> (n, d) matrix."""
    return jax.vmap(lambda t: jax.flatten_util.ravel_pytree(t)[0])(trees)


def tree_zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)
