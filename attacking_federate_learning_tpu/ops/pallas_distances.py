"""Pallas TPU kernel: fused pairwise-distance tiles.

The XLA path (ops/distances.py) materializes the full Gram matrix to HBM and
then runs the ``sq_i + sq_j - 2*gram -> sqrt`` epilogue as a second
HBM-bound pass.  This kernel fuses the epilogue into the matmul's output
tile while it is still in VMEM: grid (n/BM, n/BN, d/BK) with the contraction
innermost, an f32 VMEM accumulator per (BM, BN) tile, and the
distance transform applied on the final k step — one HBM write of D and no
Gram round-trip.  This is the 10k-client regime kernel (SURVEY.md §5
"long-context"): at n=10240, skipping the Gram round-trip saves ~800 MB of
HBM traffic per aggregation.

``interpret=None`` compiles through Mosaic on the TPU backend and runs
the Pallas interpreter everywhere else (the CPU test mode);
``chip_smoke.py`` proves the TPU route is the compiled one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Importable without TPU hardware; interpret=True runs the same kernel on CPU.
from jax.experimental.pallas import tpu as pltpu

from attacking_federate_learning_tpu.ops.distances import zero_diagonal


def _dist_kernel(nk, gi_ref, gj_ref, sqi_ref, sqj_ref, out_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(gi_ref[:], gj_ref[:].T,
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        d2 = sqi_ref[:] + sqj_ref[:] - 2.0 * acc_ref[:]
        out_ref[:] = jnp.sqrt(jnp.maximum(d2, 0.0)).astype(out_ref.dtype)


def _interpret_default(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def pallas_pairwise_distances(G, bm=128, bn=128, bk=512, interpret=None):
    """(n, d) -> (n, n) Euclidean distances, zero diagonal.

    Matches ops.distances.pairwise_distances to f32 tolerance; zero-padding
    of n and d is harmless (zero rows/columns change neither norms nor
    dots) and sliced off the output.
    """
    interpret = _interpret_default(interpret)
    n, d = G.shape
    # bf16 inputs keep their dtype into the matmul (MXU-native throughput,
    # f32 accumulation via preferred_element_type in _dist_kernel); norms
    # are always f32.  Everything else computes in f32.
    if G.dtype != jnp.bfloat16:
        G = G.astype(jnp.float32)
    # lcm: rows enter the grid as both i-blocks (bm) and j-blocks (bn); a
    # max() pad would leave output tiles unwritten when bm != bn.
    Gp = _pad_to(_pad_to(G, 1, bk), 0, math.lcm(bm, bn))
    np_, dp = Gp.shape
    # One hoisted f32 view feeds the squared norms; the matmul operand
    # stays Gp (bf16 rides the MXU natively), so at most one f32 cast of
    # the padded matrix exists in the program (pinned by
    # tests/test_distance_impl.py — a second materialization would show
    # up as ~np*dp*4 extra temp bytes).
    Gf = Gp.astype(jnp.float32)
    sq = jnp.sum(Gf * Gf, axis=1)
    sq_col = sq[:, None]                      # (np, 1) row norms
    sq_row = sq[None, :]                      # (1, np) col norms
    nk = dp // bk

    grid = (np_ // bm, np_ // bn, nk)
    kernel = functools.partial(_dist_kernel, nk)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    D = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((np_, np_), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # G rows
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),   # G cols
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # ||g_i||^2
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),    # ||g_j||^2
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=scratch,
        interpret=interpret,
    )(Gp, Gp, sq_col, sq_row)
    D = D[:n, :n]
    # Iota-select diagonal zeroing (ops/distances.py:zero_diagonal):
    # the eye spelling would materialize a second (n, n) f32 buffer.
    return zero_diagonal(D)
