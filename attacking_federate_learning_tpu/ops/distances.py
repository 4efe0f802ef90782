"""Pairwise Euclidean distances over the client axis.

The reference builds an O(n^2) dict-of-dicts of ``np.linalg.norm(g_i - g_j)``
in a Python double loop (reference defences.py:16-21) — the #1 hotspot for
Krum/Bulyan.  On TPU the whole matrix is one Gram matmul on the MXU:

    D^2 = ||g_i||^2 + ||g_j||^2 - 2 G G^T

computed in f32 with HIGHEST matmul precision so it agrees with the
reference's float computation to test tolerance.  For the multi-device path
G arrives row-sharded over the 'clients' mesh axis and XLA turns the Gram
matmul into a collective matmul over ICI — see parallel/distances.py for the
explicit blockwise shard_map variant.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from attacking_federate_learning_tpu.utils.costs import stage_scope


def cross_sq_distances(A, B, precision=None):
    """(m, d), (n, d) -> (m, n) squared Euclidean distances in f32.

    f32 inputs use HIGHEST matmul precision (parity with the reference's
    float math); bf16 inputs ride the MXU at native precision with f32
    accumulation (``preferred_element_type``) and f32 squared norms — the
    large-n memory/speed mode (config.grad_dtype='bfloat16').  Shared by
    the single-device kernel and the blockwise shard_map tiles
    (parallel/distances.py) so every path computes identical values.
    """
    if precision is None:
        precision = (lax.Precision.DEFAULT if A.dtype == jnp.bfloat16
                     else lax.Precision.HIGHEST)
    sq_a = jnp.sum(A.astype(jnp.float32) * A.astype(jnp.float32), axis=-1)
    sq_b = jnp.sum(B.astype(jnp.float32) * B.astype(jnp.float32), axis=-1)
    gram = jnp.matmul(A, B.T, precision=precision,
                      preferred_element_type=jnp.float32)
    d2 = sq_a[:, None] + sq_b[None, :] - 2.0 * gram
    return jnp.maximum(d2, 0.0)


def pairwise_sq_distances(G, precision=None):
    """(n, d) -> (n, n) squared Euclidean distance matrix in f32.
    Sub-stage ``gram`` of the stage ledger (utils/costs.py), so Krum
    and Bulyan both carry it."""
    with stage_scope("gram"):
        return cross_sq_distances(G, G, precision)


def zero_diagonal(D):
    """Exact zeros on the diagonal of a square matrix.

    An iota comparison select, NOT ``D * (1 - eye(n))``: the eye
    spelling materializes an (n, n) f32 intermediate on the hot path
    (~420 MB at n=10,240) before the multiply, while broadcasted iotas
    fuse into the consumer — same values, one fewer n² buffer
    (pinned by tests/test_distance_impl.py cost assertions).
    """
    n = D.shape[0]
    i = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.where(i == j, jnp.zeros((), D.dtype), D)


def pairwise_distances(G, precision=None):
    """(n, d) -> (n, n) Euclidean distance matrix, zero diagonal."""
    # The sqrt and the diagonal fuse into the Gram's epilogue, and a
    # fusion is named by its root: they carry the sub-stage too.
    with stage_scope("gram"):
        D = jnp.sqrt(pairwise_sq_distances(G, precision))
        # Exact zeros on the diagonal (the matmul identity can leave
        # ~1e-4 noise).
        return zero_diagonal(D)
