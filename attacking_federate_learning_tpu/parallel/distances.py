"""Explicit blockwise pairwise-distance kernels over the client mesh axis.

The automatic path (ops/distances.py under pjit) lets XLA turn the Gram
matmul into a collective matmul.  These shard_map variants make the
communication schedule explicit for the 10k-client regime (SURVEY.md §5
"long-context": ring-blockwise over *clients* instead of sequence):

- ``allgather``: each device all-gathers G once and computes its
  (n/p, n) distance tile.  One collective, peak memory O(n*d) per device.
- ``ring``: each device holds only its (n/p, d) block; blocks rotate around
  the ring via ``ppermute`` while each device accumulates one
  (n/p, n/p) output tile per step.  Peak memory O(n*d/p) — the
  ring-attention-style schedule for client counts where a replicated G
  would not fit.

Both return the full (n, n) matrix sharded over rows, bitwise-matching the
single-device kernel to f32 tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from attacking_federate_learning_tpu.ops.distances import cross_sq_distances
from attacking_federate_learning_tpu.parallel.mesh import CLIENTS


def _tile(a_blk, b_blk):
    # Shared math with the single-device kernel (incl. the bf16 f32-accum
    # policy) so blockwise results match it exactly.
    return cross_sq_distances(a_blk, b_blk)


def pairwise_distances_allgather(G, mesh, axis=CLIENTS):
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(axis, None), out_specs=P(axis, None))
    def block(gb):
        g_all = lax.all_gather(gb, axis, tiled=True)      # (n, d)
        return jnp.sqrt(_tile(gb, g_all))                 # (n/p, n)

    D = block(G)
    n = G.shape[0]
    return D * (1.0 - jnp.eye(n, dtype=D.dtype))


def pairwise_distances_ring(G, mesh, axis=CLIENTS):
    p = mesh.shape[axis]

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(axis, None), out_specs=P(axis, None))
    def block(gb):
        me = lax.axis_index(axis)
        blk = gb.shape[0]
        n = blk * p
        perm = [(i, (i + 1) % p) for i in range(p)]  # ring schedule

        def step(carry, _):
            remote, src, out = carry
            tile = jnp.sqrt(_tile(gb, remote))            # (n/p, n/p)
            out = lax.dynamic_update_slice(out, tile, (0, src * blk))
            remote = lax.ppermute(remote, axis, perm)
            # After a shift we hold the previous neighbor's block.
            src = ((src + p - 1) % p).astype(jnp.int32)
            return (remote, src, out), None

        # Varying carry: the accumulator is device-varying (holds
        # per-shard tiles) and shard_map's varying-type check requires
        # the scan carry marked so.  f32 always: the cross_sq_distances
        # tiles accumulate f32 even for bf16 operands
        # (distance_dtype='bfloat16'), and the carry must match the
        # tile dtype.
        out0 = lax.pcast(jnp.zeros((blk, n), jnp.float32), axis,
                         to="varying")
        src0 = jnp.asarray(me, jnp.int32)
        (_, _, out), _ = lax.scan(step, (gb, src0, out0), None, length=p)
        return out

    D = block(G)
    n = G.shape[0]
    return D * (1.0 - jnp.eye(n, dtype=D.dtype))
