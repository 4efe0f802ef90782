"""Pairwise Euclidean distances over the client axis.

The reference builds an O(n^2) dict-of-dicts of ``np.linalg.norm(g_i - g_j)``
in a Python double loop (reference defences.py:16-21) — the #1 hotspot for
Krum/Bulyan.  On TPU the matrix comes from a Gram matmul on the MXU:

    D^2 = ||g_i||^2 + ||g_j||^2 - 2 G G^T

computed in f32 with HIGHEST matmul precision so it agrees with the
reference's float computation to test tolerance.

``G G^T`` is symmetric, and a single dot computes both halves.  From
``2 * GRAM_BLOCK_ROWS`` rows up, :func:`pairwise_sq_distances` computes each
pair of clients once: for every row block the panel of products on and to
the right of the diagonal block (same operand dtype, same precision), and
the lower triangle is the upper one read transposed, fused with the
epilogue.  The rule reads only what the operand's type says: the static
``n``, and whether it lives on one device.  Below two blocks, or on a mesh,
the program is the single dot it always was, instruction for instruction.
A/B tiles (:func:`cross_sq_distances`, parallel/distances.py) are not
symmetric and stay one dot.

For the multi-device path G arrives row-sharded over the 'clients' mesh axis
and XLA turns the Gram matmul into a collective matmul over ICI — see
parallel/distances.py for the explicit blockwise shard_map variant.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from attacking_federate_learning_tpu.utils.costs import stage_scope


# Rows of one block of the self-Gram's upper triangle.  A cohort of at
# least two blocks takes the block-triangle path: (k + 1) / 2k of the
# products at k blocks, 55 % at the north-star cohort's ten.  PERF.md §6
# (PR 31) has the chip's readings for 512 / 1,024 / 2,048.
GRAM_BLOCK_ROWS = 1024


# Columns from which a single-device self-Gram is summed over column blocks
# (:func:`_wide_sq_distances`), and the block's width.  One dot over 3.7e8
# columns accumulates every product into one f32 accumulator per entry: on
# correlated bf16 rows (cosine 0.5) its Krum scores read 1.64e-05 from
# float64 on the chip, over the benchmark's tie band of 1e-5; blocks of
# 2**20 columns keep each accumulator's run short, add the 354 partial Grams
# afterwards and read 1.96e-06 (PERF.md section 6, PR 36).  Both existing
# cells are three orders of magnitude narrower and keep their program.
WIDE_COLUMNS = 1 << 26
GRAM_COLUMN_BLOCK = 1 << 20


def _sq_norms(A):
    return jnp.sum(A.astype(jnp.float32) * A.astype(jnp.float32), axis=-1)


def _gram(A, B, precision):
    if precision is None:
        precision = (lax.Precision.DEFAULT if A.dtype == jnp.bfloat16
                     else lax.Precision.HIGHEST)
    return jnp.matmul(A, B.T, precision=precision,
                      preferred_element_type=jnp.float32)


def _sq_from_gram(sq_a, sq_b, gram):
    d2 = sq_a[:, None] + sq_b[None, :] - 2.0 * gram
    return jnp.maximum(d2, 0.0)


def cross_sq_distances(A, B, precision=None):
    """(m, d), (n, d) -> (m, n) squared Euclidean distances in f32.

    f32 inputs use HIGHEST matmul precision (parity with the reference's
    float math); bf16 inputs ride the MXU at native precision with f32
    accumulation (``preferred_element_type``) and f32 squared norms — the
    large-n memory/speed mode (config.grad_dtype='bfloat16').  Shared by
    the single-device kernel and the blockwise shard_map tiles
    (parallel/distances.py) so every path computes identical values.
    """
    return _sq_from_gram(_sq_norms(A), _sq_norms(B), _gram(A, B, precision))


def _symmetric_gram(G, precision, block):
    """``G G^T`` from its upper block triangle: row block i contributes
    the panel ``G[i*b:(i+1)*b] G[i*b:]^T`` (the diagonal block whole,
    nothing left of it), and every entry below the diagonal is the
    transposed entry — inside the diagonal blocks too, whose two halves
    the MXU accumulates in different orders, so the result is symmetric
    to the bit on every backend.  Shapes are static, so a ragged ``n`` is
    a shorter last panel.  The row slices feed the dots in place (no
    second copy of ``G``); the iotas broadcast, so no (n, n) mask
    exists."""
    n = G.shape[0]
    upper = jnp.concatenate([
        jnp.pad(_gram(G[lo:lo + block], G[lo:], precision), ((0, 0), (lo, 0)))
        for lo in range(0, n, block)])
    row = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.where(col >= row, upper, upper.T)


def _self_sq_distances(G, precision=None, block=None):
    """:func:`cross_sq_distances` of G with itself, each pair of rows
    multiplied once when there are at least two blocks of them.  ``a + b``
    commutes in f32, so with a symmetric Gram the result is exactly
    symmetric.  ``block`` defaults to :data:`GRAM_BLOCK_ROWS`, read at
    trace time (the CPU tests lower it to reach the path at n = 48)."""
    block = GRAM_BLOCK_ROWS if block is None else block
    if (G.shape[1] >= WIDE_COLUMNS
            and jax.typeof(G).sharding.mesh.size <= 1):
        return _wide_sq_distances(G, precision)
    # A mesh of several devices in the operand's type: static row slices
    # of a row-sharded G make GSPMD reshard every panel (PERF.md §6, PR
    # 31), so a sharded cohort keeps the one dot GSPMD partitions whole.
    if G.shape[0] < 2 * block or jax.typeof(G).sharding.mesh.size > 1:
        return cross_sq_distances(G, G, precision)
    sq = _sq_norms(G)
    return _sq_from_gram(sq, sq, _symmetric_gram(G, precision, block))


def _wide_sq_distances(G, precision=None, width=None):
    """:func:`cross_sq_distances` of a very wide G with itself: Gram and
    squared norms summed over column blocks of ``width``, in f32, with G
    read in its own dtype block by block (no f32 copy of the matrix)."""
    width = GRAM_COLUMN_BLOCK if width is None else width
    n, d = G.shape
    whole = d // width

    def part(B):
        return _gram(B, B, precision), _sq_norms(B)

    def body(i, acc):
        gram, sq = part(lax.dynamic_slice_in_dim(G, i * width, width, axis=1))
        return acc[0] + gram, acc[1] + sq

    gram, sq = lax.fori_loop(
        0, whole, body,
        (jnp.zeros((n, n), jnp.float32), jnp.zeros((n,), jnp.float32)))
    if whole * width < d:
        tail_gram, tail_sq = part(G[:, whole * width:])
        gram, sq = gram + tail_gram, sq + tail_sq
    return _sq_from_gram(sq, sq, gram)


def pairwise_sq_distances(G, precision=None):
    """(n, d) -> (n, n) squared Euclidean distance matrix in f32.
    Sub-stage ``gram`` of the stage ledger (utils/costs.py), so Krum
    and Bulyan both carry it."""
    with stage_scope("gram"):
        return _self_sq_distances(G, precision)


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
