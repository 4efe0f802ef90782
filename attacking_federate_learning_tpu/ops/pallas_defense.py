"""Pallas TPU defense-kernel suite: the tier-1 pipeline on-device.

ops/pallas_distances.py fused the distance epilogue into the Gram
matmul's output tile; this module grows that into the full defense hot
path (ROADMAP item 1, ISSUE 11) so the O(n^2 d) tier-1 estimators run
on the accelerator end to end — no Gram round-trip, no second HBM pass
over the (n, n) matrix, and no ``pure_callback`` host marshal:

- :func:`pallas_krum_scores` — fused **distance -> Krum score** kernel.
  Same grid as the distance kernel ((n/bm, n/bn, d/bk), contraction
  innermost), but the (bm, bn) distance tile never leaves VMEM: the
  epilogue folds it into a per-row running ``rowsum`` and a running
  top-``c`` *largest* buffer (the complement identity of
  defenses/kernels.py:_krum_scores — a row always has exactly k + c
  scoring entries with c = f - 1, +2 under paper scoring, so
  sum-of-k-smallest = rowsum − sum-of-c-largest), and the (n,) scores
  are written on the last j step.  The (n, n) matrix is never
  materialized: output bytes drop from n²·4 to n·4 and the second
  HBM read of D disappears (:func:`krum_scores_cost` is the exact
  declared tile-traffic model, pinned against the XLA Gram+epilogue
  path by tools/perf_gate.py --pallasproof).
- :func:`pallas_trimmed_mean_of` / :func:`pallas_median_of` — tiled
  **coordinate-wise selection** over (n, d): each grid step owns one
  (n, bd) column block in VMEM and runs the reference estimator's
  median/stable-argsort/keep pipeline inside it, replacing the
  whole-matrix XLA sort whose CPU cost motivated the native host
  escape (defenses/host.py).
- :func:`pallas_masked_trimmed_mean` / :func:`pallas_masked_median` —
  the same tiles with the quarantine ``mask=`` / staleness ``weights=``
  seam (core/faults.py, core/async_rounds.py) replicated INSIDE the
  kernel, so fault/async/hierarchical rounds ride the pallas route
  unchanged.  These replicate defenses/kernels.py's masked estimators
  op for op and are pinned BIT-EXACT against them
  (tests/test_pallas.py); the unmasked kernels are ulp-bounded instead
  (XLA fuses the full-matrix mean+median differently than the tiled
  program — the same summation-order contract as the native host
  kernels, PARITY.md).

Numerics contract: the fused Krum scores are the complement
evaluation — numerically the ``krum_scoring_method='topk'`` class, so
the kernels.py dispatch wraps them in the same cancellation guard
(kept mass vs the subtraction noise floor) with a ``lax.cond``
fallback to the exact sort path over the pallas distance matrix.
Selection outputs (Krum/Bulyan return input rows) are therefore
bit-exact whenever the score gap clears the f32 tie band — the same
measured-band contract tests/test_native.py pins for the native
comparator.

Every kernel resolves ``interpret=None`` to interpret mode off-TPU, so
CPU CI exercises the exact kernel bodies.  On the TPU backend every
kernel here sorts and Mosaic has no sort, so the compiled route raises
by name (:func:`_sort_kernel_interpret`); ``chip_smoke.py``'s kernel leg
re-checks that refusal, and ``tools/pallas_microbench.py`` would time
the large-tile configuration of whatever starts to compile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Importable without TPU hardware; interpret=True runs the same kernels
# on CPU (tests/conftest.py pins the backend there).
from jax.experimental.pallas import tpu as pltpu

from attacking_federate_learning_tpu.ops.pallas_distances import (
    _interpret_default, _pad_to
)

_INF = jnp.inf


def _sort_kernel_interpret(kernel, interpret):
    """Resolve ``interpret`` for a kernel whose body sorts.  Pallas' TPU
    lowering has no TensorCore rule for ``lax.sort`` (jnp.sort / argsort
    / median all reach it; jax 0.9.0 on the v5e), so the compiled route
    does not exist: it raises here, naming the kernel, instead of
    surfacing as an anonymous lowering failure of the whole round
    program — and it never drops to interpret mode or to the XLA twin on
    its own.  That is a claim about the installed Mosaic, so it is
    re-observed rather than trusted: :func:`raw_sort_kernels` hands out
    the bodies PAST this guard, and ``chip_smoke.py`` and
    tests/test_pallas.py fail the day Mosaic lowers one of them."""
    interpret = _interpret_default(interpret)
    if not interpret:
        raise NotImplementedError(
            f"pallas kernel '{kernel}' has no compiled TPU route: Mosaic "
            f"refused its sort when last checked (jax 0.9.0: "
            f"'Unimplemented primitive in Pallas TPU lowering for "
            f"KernelType.TC: sort'; chip_smoke.py re-checks); select the "
            f"XLA implementation (the *_impl='xla' defaults) on TPU")
    return interpret


def _lane_pad(c, lanes=128):
    """Round a scratch lane count up to the TPU lane width (>= 1 tile)."""
    return max(-(-max(c, 1) // lanes) * lanes, lanes)


# ---------------------------------------------------------------------------
# fused distance -> Krum score
# ---------------------------------------------------------------------------

def krum_scores_cost(n, d, corrupted_count=0, bm=128, bn=128, bk=512):
    """Exact declared cost of the fused kernel, deterministic in the
    shapes alone, in BOTH accounting conventions:

    - ``bytes_accessed``: XLA ``cost_analysis`` semantics — every
      logical operand/output counted ONCE per op (the convention the
      whole cost observatory gates on).  For the fused kernel that is
      the two G operand views, the norm vectors and the (n,)-class
      outputs: ~2·n·d·4 bytes.  The XLA Gram+epilogue path pays the
      same operand term PLUS one n²·4 pass per (n, n) intermediate
      (Gram write, distance transform, sort, prefix reduce), which is
      exactly what the fusion deletes — the perf-gate pallasproof pins
      this model strictly below the XLA path's measured number.
    - ``hbm_tile_bytes``: the physical tile traffic the BlockSpecs
      stream per sweep (each G tile is re-read once per opposing row
      block — the ``pl.CostEstimate`` handed to Mosaic).  Shrinks
      with bm/bn; the CI defaults favor small-n coverage, the
      chip micro-bench (tools/pallas_microbench.py) runs
      the balanced large-tile configuration.

    The interpret-mode emulation's cost_analysis is NEITHER number
    (the grid loop body is counted once and the emulation copies
    inflate temp bytes), which is why the proof pins the model, not
    the emulation."""
    np_ = -(-n // math.lcm(bm, bn)) * math.lcm(bm, bn)
    dp = -(-d // bk) * bk
    ni, nj, nk = np_ // bm, np_ // bn, dp // bk
    steps = ni * nj * nk
    tile_bytes = (steps * 4 * (bm * bk + bn * bk)
                  + ni * nj * 4 * (bm + bn) + 2 * np_ * 4)
    once_bytes = 4 * (2 * np_ * dp + 4 * np_)
    flops = 2 * np_ * np_ * dp + 8 * np_ * np_  # matmul + epilogue
    return {"flops": flops, "bytes_accessed": once_bytes,
            "hbm_tile_bytes": tile_bytes}


def _krum_score_kernel(n, nk, nj, comp, cp, gi_ref, gj_ref, sqi_ref,
                       sqj_ref, score_ref, rowsum_ref, acc_ref, top_ref):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(gi_ref[:], gj_ref[:].T,
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        bm, bn = acc_ref.shape
        d2 = sqi_ref[:] + sqj_ref[:] - 2.0 * acc_ref[:]
        dist = jnp.sqrt(jnp.maximum(d2, 0.0))
        # Padding columns and the diagonal never score: the reference
        # dict holds no self-distance (defences.py:16-21) and zero
        # rows are an artifact of the lcm/bk padding.
        rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        valid = (cols < n) & (rows != cols)

        @pl.when(j == 0)
        def _reset():
            rowsum_ref[:] = jnp.zeros_like(rowsum_ref)
            top_ref[:] = jnp.full_like(top_ref, -_INF)

        rowsum_ref[:] += jnp.sum(jnp.where(valid, dist, 0.0), axis=1,
                                 keepdims=True)
        if comp > 0:
            # Streaming top-c largest per row: merge this tile's
            # candidates into the running buffer (one descending sort
            # of (bm, cp + bn) — O((c+bn) log) per tile, amortized
            # noise next to the bm·bn·bk matmul).
            cand = jnp.where(valid, dist, -_INF)
            merged = jnp.concatenate([top_ref[:], cand], axis=1)
            top_ref[:] = -jnp.sort(-merged, axis=1)[:, :cp]

        @pl.when(j == nj - 1)
        def _write():
            if comp > 0:
                t = top_ref[:, :comp]
                tsum = jnp.sum(jnp.where(jnp.isfinite(t), t, 0.0),
                               axis=1, keepdims=True)
                score_ref[:] = rowsum_ref[:] - tsum
            else:
                score_ref[:] = rowsum_ref[:]


def _krum_scores_call(G, users_count, corrupted_count, paper_scoring,
                      bm, bn, bk, interpret):
    """The fused kernel itself, ``interpret`` already resolved."""
    n, d = G.shape
    comp = corrupted_count - 1 + (2 if paper_scoring else 0)
    if not 0 <= comp <= max(n - 1, 0):
        raise ValueError(
            f"fused Krum scores need 0 <= f-1(+2) <= n-1 entries per "
            f"row (n={n}, f={corrupted_count}, "
            f"paper_scoring={paper_scoring})")
    if G.dtype != jnp.bfloat16:
        G = G.astype(jnp.float32)
    Gp = _pad_to(_pad_to(G, 1, bk), 0, math.lcm(bm, bn))
    np_, dp = Gp.shape
    Gf = Gp.astype(jnp.float32)
    sq = jnp.sum(Gf * Gf, axis=1)
    cp = _lane_pad(comp)
    nk, nj = dp // bk, np_ // bn
    cost = krum_scores_cost(n, d, corrupted_count, bm, bn, bk)
    kernel = functools.partial(_krum_score_kernel, n, nk, nj, comp, cp)
    scores, rowsum = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((np_, 1), jnp.float32),
                   jax.ShapeDtypeStruct((np_, 1), jnp.float32)),
        grid=(np_ // bm, nj, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # G rows
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),   # G cols
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),    # ||g_i||^2
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),    # ||g_j||^2
        ],
        out_specs=(pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0))),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, cp), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=cost["flops"],
            bytes_accessed=cost["hbm_tile_bytes"], transcendentals=0),
        interpret=interpret,
    )(Gp, Gp, sq[:, None], sq[None, :])
    return scores[:n, 0], rowsum[:n, 0]


@functools.partial(jax.jit,
                   static_argnames=("users_count", "corrupted_count",
                                    "paper_scoring", "bm", "bn", "bk",
                                    "interpret"))
def pallas_krum_scores(G, users_count, corrupted_count,
                       paper_scoring=False, bm=128, bn=128, bk=512,
                       interpret=None):
    """(n, d) -> ((n,) Krum scores, (n,) distance rowsums), one sweep.

    Reference scoring semantics (defenses/kernels.py:_krum_scores):
    each row's score sums its k = users_count - corrupted_count
    (- 2 under ``paper_scoring``) smallest distances to the other
    rows, evaluated via the complement identity (rowsum minus the
    c = f - 1 (+2) largest).  The rowsum comes back too so the caller
    can apply the topk cancellation guard without a second pass.

    bf16 operands ride the MXU natively with f32 accumulation and f32
    norms, mirroring pallas_pairwise_distances; anything else computes
    in f32.  Static pool only — the quarantine-masked path keeps the
    exact sort evaluator over the pallas distance matrix
    (defenses/kernels.py dispatch)."""
    return _krum_scores_call(
        G, users_count, corrupted_count, paper_scoring, bm, bn, bk,
        _sort_kernel_interpret("krum_score_fusion", interpret))


# ---------------------------------------------------------------------------
# tiled coordinate-wise kernels (trimmed mean / median, masked/weighted)
# ---------------------------------------------------------------------------

def _coord_block(n, d, bd):
    """Default column-tile width: (n, bd) f32 + sort temps must sit in
    VMEM, so the tile narrows as the client axis grows."""
    if bd is None:
        bd = 256 if n <= 4096 else 128
    return min(bd, _lane_pad(d))


def _trim_kernel(number_to_consider, g_ref, out_ref):
    # Reference estimator, verbatim per column block
    # (defenses/kernels.py:trimmed_mean_of): median anchor, stable
    # |deviation| argsort along the client axis, mean of the kept
    # deviations plus the anchor.
    G = g_ref[:]
    med = jnp.median(G, axis=0)
    dev = G - med[None, :]
    order = jnp.argsort(jnp.abs(dev), axis=0, stable=True)
    kept = jnp.take_along_axis(dev, order[:number_to_consider], axis=0)
    out_ref[0, :] = jnp.mean(kept, axis=0) + med


def _trimmed_mean_call(G, number_to_consider, bd, interpret):
    n, d = G.shape
    bd = _coord_block(n, d, bd)
    Gp = _pad_to(G.astype(jnp.float32), 1, bd)
    dp = Gp.shape[1]
    out = pl.pallas_call(
        functools.partial(_trim_kernel, int(number_to_consider)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((n, bd), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, bd), lambda j: (0, j)),
        interpret=interpret,
    )(Gp)
    return out[0, :d]


@functools.partial(jax.jit, static_argnames=("number_to_consider", "bd",
                                             "interpret"))
def pallas_trimmed_mean_of(G, number_to_consider, bd=None, interpret=None):
    """Tiled median-anchored trimmed mean: (n, d) -> (d,), keep count
    static.  Matches defenses/kernels.py:trimmed_mean_of to summation-
    order ulps (the whole-matrix XLA program fuses its mean+median
    arithmetic differently than the tiled one — PARITY.md)."""
    return _trimmed_mean_call(
        G, number_to_consider, bd,
        _sort_kernel_interpret("trimmed_mean_tile", interpret))


def _median_kernel(g_ref, out_ref):
    out_ref[0, :] = jnp.median(g_ref[:], axis=0)


def _median_call(G, bd, interpret):
    n, d = G.shape
    bd = _coord_block(n, d, bd)
    Gp = _pad_to(G.astype(jnp.float32), 1, bd)
    dp = Gp.shape[1]
    out = pl.pallas_call(
        _median_kernel,
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((n, bd), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, bd), lambda j: (0, j)),
        interpret=interpret,
    )(Gp)
    return out[0, :d]


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def pallas_median_of(G, bd=None, interpret=None):
    """Tiled coordinate-wise median: (n, d) -> (d,)."""
    return _median_call(G, bd,
                        _sort_kernel_interpret("median_tile", interpret))


def _masked_median_cols(G, mask, maskv, w_ref, weighted):
    """kernels.masked_median replicated on one (n, bd) column block;
    ``mask`` is the (n, 1) bool column, ``maskv`` its (n,) view."""
    vals = jnp.where(mask, G, _INF)
    srt = jnp.sort(vals, axis=0)
    if weighted:
        order = jnp.argsort(vals, axis=0)
        w = jnp.where(mask, w_ref[:], 0.0)
        w_srt = jnp.take_along_axis(jnp.broadcast_to(w, vals.shape),
                                    order, axis=0)
        cum = jnp.cumsum(w_srt, axis=0)
        half = jnp.sum(w) / 2.0
        pick = jnp.argmax(cum >= half, axis=0)
        return jnp.take_along_axis(srt, pick[None, :], axis=0)[0]
    e = jnp.sum(maskv).astype(jnp.int32)
    lo = jnp.take(srt, (e - 1) // 2, axis=0)
    hi = jnp.take(srt, e // 2, axis=0)
    return (lo + hi) / 2


def _masked_median_kernel(weighted, g_ref, m_ref, w_ref, out_ref):
    mask = m_ref[:] > 0
    out_ref[0, :] = _masked_median_cols(g_ref[:], mask, mask[:, 0],
                                        w_ref, weighted)


def _masked_trim_kernel(k_delta, weighted, g_ref, m_ref, w_ref, out_ref):
    # kernels.masked_trimmed_mean_of, verbatim per column block: alive
    # median anchor (always unweighted), dead rows carry an +inf
    # deviation key (stable argsort puts them last), keep count
    # k = max(e - k_delta, 1) derived from the mask INSIDE the kernel
    # so no traced scalar crosses the pallas boundary.
    G = g_ref[:]
    n = G.shape[0]
    mask = m_ref[:] > 0
    maskv = mask[:, 0]
    med = _masked_median_cols(G, mask, maskv, w_ref, False)
    dev = G - med[None, :]
    key = jnp.where(mask, jnp.abs(dev), _INF)
    order = jnp.argsort(key, axis=0, stable=True)
    sdev = jnp.take_along_axis(dev, order, axis=0)
    e = jnp.sum(maskv).astype(jnp.int32)
    k = jnp.maximum(e - k_delta, 1)
    keep = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) < k
    if weighted:
        w = jnp.where(mask, w_ref[:], 0.0)
        w_s = jnp.take_along_axis(jnp.broadcast_to(w, sdev.shape),
                                  order, axis=0)
        wk = jnp.where(keep, w_s, 0.0)
        mass = jnp.maximum(jnp.sum(wk, axis=0), 1e-12)
        out_ref[0, :] = jnp.sum(wk * sdev, axis=0) / mass + med
    else:
        out_ref[0, :] = jnp.sum(jnp.where(keep, sdev, 0.0),
                                axis=0) / k + med


def _masked_coord_call(kernel, G, mask, weights, bd, interpret):
    n, d = G.shape
    bd = _coord_block(n, d, bd)
    Gp = _pad_to(G.astype(jnp.float32), 1, bd)
    dp = Gp.shape[1]
    m2 = mask.astype(jnp.float32)[:, None]
    w = (weights if weights is not None
         else jnp.ones((n,), jnp.float32)).astype(jnp.float32)[:, None]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((n, bd), lambda j: (0, j)),
                  pl.BlockSpec((n, 1), lambda j: (0, 0)),
                  pl.BlockSpec((n, 1), lambda j: (0, 0))],
        out_specs=pl.BlockSpec((1, bd), lambda j: (0, j)),
        interpret=interpret,
    )(Gp, m2, w)
    return out[0, :d]


@functools.partial(jax.jit, static_argnames=("k_delta", "weighted", "bd",
                                             "interpret"))
def pallas_masked_trimmed_mean(G, mask, k_delta, weights=None,
                               weighted=False, bd=None, interpret=None):
    """Mask-aware tiled trimmed mean — the quarantine/staleness seam on
    the pallas route.  ``k_delta`` is the STATIC part of the keep
    count: k = max(alive - k_delta, 1), i.e. k_delta = f + 1 for
    TrimmedMean and 2f + 1 for Bulyan's tail — the traced alive count
    is derived from the mask inside the kernel.  Bit-exact against
    kernels.masked_trimmed_mean_of (pinned, tests/test_pallas.py);
    ``weighted`` must say statically whether ``weights`` is real
    (a None weights with weighted=True averages unit weights)."""
    return _masked_coord_call(
        functools.partial(_masked_trim_kernel, int(k_delta),
                          bool(weighted)),
        G, mask, weights, bd,
        _sort_kernel_interpret("masked_trimmed_mean_tile", interpret))


@functools.partial(jax.jit, static_argnames=("weighted", "bd",
                                             "interpret"))
def pallas_masked_median(G, mask, weights=None, weighted=False, bd=None,
                         interpret=None):
    """Mask-aware tiled median (weighted = the lower weighted median,
    kernels.masked_median's one documented deviation).  Bit-exact
    against kernels.masked_median (pinned, tests/test_pallas.py)."""
    return _masked_coord_call(
        functools.partial(_masked_median_kernel, bool(weighted)),
        G, mask, weights, bd,
        _sort_kernel_interpret("masked_median_tile", interpret))


def raw_sort_kernels(users_count, corrupted_count):
    """name -> (G -> that kernel's ``pallas_call`` at ``interpret=False``,
    default tiles, the keep counts the defenses pass), PAST
    :func:`_sort_kernel_interpret`.  Not a route: lowering one of these
    for TPU is how the guard's claim is re-observed — Mosaic must refuse
    it by its own ``sort`` message."""
    n, f = users_count, corrupted_count

    def alive(G):
        return jnp.ones((G.shape[0],), bool)

    return {
        "krum_score_fusion": lambda G: _krum_scores_call(
            G, n, f, False, 128, 128, 512, False),
        "trimmed_mean_tile": lambda G: _trimmed_mean_call(
            G, n - f - 1, None, False),
        "median_tile": lambda G: _median_call(G, None, False),
        "masked_trimmed_mean_tile": lambda G: _masked_coord_call(
            functools.partial(_masked_trim_kernel, f + 1, False),
            G, alive(G), None, None, False),
        "masked_median_tile": lambda G: _masked_coord_call(
            functools.partial(_masked_median_kernel, False),
            G, alive(G), None, None, False),
    }
