"""Causal grouped-query attention as fused Pallas TPU kernels: forward, dq
and dk/dv, each walking a (query tile, key tile) grid over the tiles a
query tile can see, with online softmax, so that no (queries, keys) score,
probability or gradient tensor reaches HBM.

Layouts are the projections' own, so nothing is transposed around a call:
q (B, L, heads * Dk), k (B, L, kv_heads * Dk), v (B, L, kv_heads * Dv) and
the output (B, L, heads * Dv), the ``heads / kv_heads`` query heads of a kv
group side by side in one (tile, R * Dk) block that every key tile of the
group is read once for.  A grid step carries ``h`` neighbouring kv groups
(:func:`groups_per_step`): where a group has few query heads (latent
attention expands its keys to one a head) a step of one group is too short
for its fixed cost.  Each head's products, masks and accumulations are the
same for any ``h``, in the same key-tile order, and so are the results.
The q.k width Dk may differ from the value width Dv and need not be whole
lanes (latent attention's 192 = 128 + 64 against values of 128): q and k
are then zero-padded to whole lanes, which adds nothing to a score, and
the scale stays ``1 / sqrt(Dk)`` of the unpadded width.  On the MXU a contraction of 192 takes the same two 128-deep passes
as one of 256.
The backward keeps q, k, v and the log-sum-exp of every query row,
(B, kv_heads, R, L) f32 (``checkpoint_name`` ``attention_lse``), and
recomputes scores and probabilities from it tile by tile; the dq kernel
sums ``di = rowsum(P * dP)`` (``rowsum(dO * O)``) in a first sweep over its
key tiles, so that the f32 output is not kept for the backward (the
layer's own rematerialization keeps the copy its projection reads).

Precision is the configuration's: f32 parameters and activations, matmuls
at the TPU's default precision.  q, k, v, the output and every gradient
are f32 at the kernels' boundary; the operands of the MXU products (q, k,
v, p, dO, dS) are rounded to bf16 once inside, with f32 accumulation, as
XLA's DEFAULT precision rounds an f32 einsum's; the scale, the mask, the
row max and sum, the log-sum-exp and ``di = rowsum(dO * O)`` stay f32.

The grid's key axis has as many steps as the widest query tile reads
(``back + 1``): a step past a tile's last visible key tile re-reads the
same block (no copy) and computes nothing, and only the tiles on the
diagonal and at the window's edge build a mask.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from attacking_federate_learning_tpu.utils.costs import stage_scope

LANES = 128
MASKED = -1e30          # a masked score: exp() of it is exactly 0 in f32
MXU = jnp.bfloat16      # the products' operands: one bf16 pass
# batch, kv group and the outer tile axis are independent; the inner walks
# the tiles one accumulator reads
PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=96 * 2 ** 20)
# query heads a grid step carries, at most (:func:`groups_per_step`)
HEADS_PER_STEP = 8
NT = (((1,), (1,)), ((), ()))       # x @ y.T
NN = (((1,), (0,)), ((), ()))       # x @ y


def fits(length, v_dim, tile):
    """Whether the kernels take this shape: values of whole lanes (q and k
    are padded to them), tiles of whole lanes and a context of whole
    tiles."""
    return v_dim % LANES == 0 and tile % LANES == 0 and length % tile == 0


def back(length, window, tile):
    """Key tiles behind its own a query tile reads: every earlier one on a
    global layer, ``ceil((window - 1) / tile)`` on a window layer."""
    tiles = length // tile
    if window is None:
        return tiles - 1
    return min(tiles - 1, -(-(window - 1) // tile))


def groups_per_step(heads, groups):
    """kv groups a grid step carries: the largest divisor h of ``groups``
    with h * ``heads`` query heads within :data:`HEADS_PER_STEP`, and 1
    where even one group has more."""
    return max(h for h in range(1, groups + 1)
               if groups % h == 0 and (h == 1 or h * heads <= HEADS_PER_STEP))


def grid_steps(length, window, tile, heads, groups):
    """Grid steps of the forward, dq and dk/dv kernels together for one
    context through one layer of ``groups`` kv groups of ``heads`` query
    heads (dq sweeps its key tiles twice)."""
    per_kernel = (groups // groups_per_step(heads, groups) * (length // tile)
                  * (back(length, window, tile) + 1))
    return 4 * per_kernel


def computed_pairs(length, window, tile):
    """(query, key) pairs a head computes: whole tiles, those visible to
    some query of the tile and no others."""
    tiles, b = length // tile, back(length, window, tile)
    return sum(min(i, b) + 1 for i in range(tiles)) * tile * tile


def visible_pairs(length, window):
    """(query, key) pairs the mask leaves visible, a head."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def _visible(i, j, tile, window, transposed=False):
    """The visibility of query tile i's rows to key tile j's columns
    (rows of keys and columns of queries where ``transposed``)."""
    shape = (tile, tile)
    qi = i * tile + lax.broadcasted_iota(jnp.int32, shape,
                                         1 if transposed else 0)
    kj = j * tile + lax.broadcasted_iota(jnp.int32, shape,
                                         0 if transposed else 1)
    seen = kj <= qi
    if window is not None:
        seen = seen & (qi - kj < window)
    return seen


def _edge(i, j, tile, window):
    """Whether tile (i, j) holds a pair the mask hides: on the diagonal,
    or a key older than the window for the tile's last query."""
    edge = i == j
    if window is not None:
        edge = edge | ((i - j) * tile + tile - 1 >= window)
    return edge


def _tiled(x, width):
    """A lane-replicated (rows, 128) statistic across ``width`` lanes."""
    return x if width == LANES else jnp.tile(x, (1, width // LANES))


def _group(ref, g, groups):
    """Group g's (R, tile) rows of a statistics block; a step of one group
    has the group axis squeezed out of its block."""
    return ref if groups == 1 else ref.at[g]


def _stats_spec(groups, heads, tile, index):
    """The block of a (B, G, R, L) statistic a step reads or writes."""
    return pl.BlockSpec((None, None if groups == 1 else groups, heads, tile),
                        index)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                groups, heads, qk_dim, v_dim, tile, back, window, scale):
    i, step = pl.program_id(2), pl.program_id(3)
    j = jnp.maximum(i - back, 0) + step
    K, D = qk_dim, v_dim

    @pl.when(step == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, MASKED)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def block(masked):
        seen = None
        for g in range(groups):
            k = k_ref[:, g * K:(g + 1) * K].astype(MXU)
            v = v_ref[:, g * D:(g + 1) * D].astype(MXU)
            if masked and seen is None:             # once, for every group
                seen = _visible(i, j, tile, window)
            for n in range(g * heads, (g + 1) * heads):
                q = q_ref[:, n * K:(n + 1) * K].astype(MXU)
                s = lax.dot_general(
                    q, k, NT, preferred_element_type=jnp.float32) * scale
                if masked:
                    s = jnp.where(seen, s, MASKED)
                m_prev = m_sc[n]
                m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - _tiled(m_next, tile))
                alpha = jnp.exp(m_prev - m_next)
                l_sc[n] = alpha * l_sc[n] + lax.broadcast_in_dim(
                    p.sum(axis=-1), m_prev.shape, (0,))
                m_sc[n] = m_next
                acc_sc[n] = _tiled(alpha, D) * acc_sc[n] + lax.dot_general(
                    p.astype(MXU), v, NN, preferred_element_type=jnp.float32)

    run = j <= i
    edge = _edge(i, j, tile, window)
    pl.when(run & edge)(lambda: block(True))
    pl.when(run & ~edge)(lambda: block(False))

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        for g in range(groups):
            lse_g = _group(lse_ref, g, groups)
            for r in range(heads):
                n = g * heads + r
                l = l_sc[n]
                o_ref[:, n * D:(n + 1) * D] = acc_sc[n] / _tiled(l, D)
                lse = m_sc[n] + jnp.log(l)                 # (tile, 128)
                lse_g[r:r + 1, :] = jnp.transpose(lse)[0:1, :]


def _shape(q, k, K):
    """(batch, length, query heads a kv group, kv groups, groups a step)."""
    B, L, HK = q.shape
    G = k.shape[2] // K
    R = HK // (G * K)
    return B, L, R, G, groups_per_step(R, G)


def _forward(q, k, v, K, D, scale, window, tile, interpret):
    B, L, R, G, h = _shape(q, k, K)
    tiles, b = L // tile, back(L, window, tile)
    kernel = functools.partial(
        _fwd_kernel, groups=h, heads=R, qk_dim=K, v_dim=D, tile=tile,
        back=b, window=window, scale=scale)

    def kv_tile(bi, g, i, step):
        return bi, jnp.minimum(jnp.maximum(i - b, 0) + step, i), g

    return pl.pallas_call(
        kernel,
        grid=(B, G // h, tiles, b + 1),
        in_specs=[
            pl.BlockSpec((None, tile, h * R * K),
                         lambda bi, g, i, s: (bi, i, g)),
            pl.BlockSpec((None, tile, h * K), kv_tile),
            pl.BlockSpec((None, tile, h * D), kv_tile),
        ],
        out_specs=[
            pl.BlockSpec((None, tile, h * R * D),
                         lambda bi, g, i, s: (bi, i, g)),
            _stats_spec(h, R, tile, lambda bi, g, i, s: (bi, g, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, L, G * R * D), jnp.float32),
                   jax.ShapeDtypeStruct((B, G, R, L), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h * R, tile, LANES), jnp.float32),
                        pltpu.VMEM((h * R, tile, LANES), jnp.float32),
                        pltpu.VMEM((h * R, tile, D), jnp.float32)],
        compiler_params=PARAMS, interpret=interpret, name="flash_fwd",
    )(q, k, v)


# --------------------------------------------------------------------------
# backward: dq over the key tiles a query tile reads, dk / dv over the
# query tiles that read a key tile
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dq_ref, di_ref, acc_sc,
               di_sc, *, groups, heads, qk_dim, v_dim, tile, back, window,
               scale):
    """Two sweeps over the key tiles a query tile reads: the first sums
    ``di = rowsum(P * dP)`` (which is ``rowsum(dO * O)``, so that the
    output need not be kept for the backward), the second the gradient."""
    i, step = pl.program_id(2), pl.program_id(3)
    span = back + 1
    second = step >= span
    j = jnp.maximum(i - back, 0) + step % span
    K, D = qk_dim, v_dim

    @pl.when(step == 0)
    def _():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        di_sc[...] = jnp.zeros_like(di_sc)

    def block(masked, second):
        seen = None
        for g in range(groups):
            k = k_ref[:, g * K:(g + 1) * K].astype(MXU)
            v = v_ref[:, g * D:(g + 1) * D].astype(MXU)
            if masked and seen is None:             # once, for every group
                seen = _visible(i, j, tile, window)
            lse_g = _group(lse_ref, g, groups)
            for r in range(heads):
                n = g * heads + r
                cols = slice(n * K, (n + 1) * K)
                q = q_ref[:, cols].astype(MXU)
                s = lax.dot_general(
                    q, k, NT, preferred_element_type=jnp.float32) * scale
                if masked:
                    s = jnp.where(seen, s, MASKED)
                p = jnp.exp(s - jnp.expand_dims(lse_g[r], -1))
                dp = lax.dot_general(
                    do_ref[:, n * D:(n + 1) * D].astype(MXU), v, NT,
                    preferred_element_type=jnp.float32)
                if not second:
                    di_sc[n] += lax.broadcast_in_dim(
                        (p * dp).sum(axis=-1), di_sc.shape[1:], (0,))
                    continue
                ds = p * (dp - _tiled(di_sc[n], tile))
                acc_sc[:, cols] += lax.dot_general(
                    ds.astype(MXU), k, NN, preferred_element_type=jnp.float32)

    run = j <= i
    edge = _edge(i, j, tile, window)
    for later in (False, True):
        now = run & (second if later else ~second)
        pl.when(now & edge)(functools.partial(block, True, later))
        pl.when(now & ~edge)(functools.partial(block, False, later))

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = acc_sc[...] * scale
        for g in range(groups):
            di_g = _group(di_ref, g, groups)
            for r in range(heads):
                di_g[r:r + 1, :] = jnp.transpose(
                    di_sc[g * heads + r])[0:1, :]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, groups, heads, qk_dim, v_dim, tile, tiles,
                window, scale):
    j, step = pl.program_id(2), pl.program_id(3)
    i = j + step
    K, D = qk_dim, v_dim

    @pl.when(step == 0)
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def block(masked):
        # keys down the rows, queries across the columns: a query's
        # log-sum-exp and di are a row that broadcasts down the sublanes
        seen = None
        for g in range(groups):
            kc, vc = slice(g * K, (g + 1) * K), slice(g * D, (g + 1) * D)
            k = k_ref[:, kc].astype(MXU)
            v = v_ref[:, vc].astype(MXU)
            if masked and seen is None:             # once, for every group
                seen = _visible(i, j, tile, window, transposed=True)
            lse_g, di_g = (_group(x, g, groups) for x in (lse_ref, di_ref))
            for r in range(heads):
                n = g * heads + r
                q = q_ref[:, n * K:(n + 1) * K].astype(MXU)
                do = do_ref[:, n * D:(n + 1) * D].astype(MXU)
                s = lax.dot_general(
                    k, q, NT, preferred_element_type=jnp.float32) * scale
                if masked:
                    s = jnp.where(seen, s, MASKED)
                p = jnp.exp(s - lse_g[r:r + 1, :])
                dv_sc[:, vc] += lax.dot_general(
                    p.astype(MXU), do, NN, preferred_element_type=jnp.float32)
                dp = lax.dot_general(v, do, NT,
                                     preferred_element_type=jnp.float32)
                ds = p * (dp - di_g[r:r + 1, :])
                dk_sc[:, kc] += lax.dot_general(
                    ds.astype(MXU), q, NN, preferred_element_type=jnp.float32)

    run = i < tiles
    edge = _edge(i, j, tile, window)
    pl.when(run & edge)(lambda: block(True))
    pl.when(run & ~edge)(lambda: block(False))

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = dk_sc[...] * scale
        dv_ref[...] = dv_sc[...]


def _backward(q, k, v, lse, do, K, D, scale, window, tile, interpret):
    B, L, R, G, h = _shape(q, k, K)
    tiles, b = L // tile, back(L, window, tile)
    rows = pl.BlockSpec((None, tile, h * R * K),
                        lambda bi, g, i, s: (bi, i, g))
    out_rows = pl.BlockSpec((None, tile, h * R * D),
                            lambda bi, g, i, s: (bi, i, g))
    stats = _stats_spec(h, R, tile, lambda bi, g, i, s: (bi, g, 0, i))

    def kv_tile(bi, g, i, step):
        return bi, jnp.minimum(jnp.maximum(i - b, 0) + step % (b + 1), i), g

    dq, di = pl.pallas_call(
        functools.partial(_dq_kernel, groups=h, heads=R, qk_dim=K, v_dim=D,
                          tile=tile, back=b, window=window, scale=scale),
        grid=(B, G // h, tiles, 2 * (b + 1)),
        in_specs=[rows, pl.BlockSpec((None, tile, h * K), kv_tile),
                  pl.BlockSpec((None, tile, h * D), kv_tile), out_rows,
                  stats],
        out_specs=[rows, stats],
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tile, h * R * K), jnp.float32),
                        pltpu.VMEM((h * R, tile, LANES), jnp.float32)],
        compiler_params=PARAMS, interpret=interpret, name="flash_dq",
    )(q, k, v, do, lse)

    def q_tile(bi, g, j, step):
        return bi, jnp.minimum(j + step, tiles - 1), g

    def q_stats(bi, g, j, step):
        return bi, g, 0, jnp.minimum(j + step, tiles - 1)

    keys = pl.BlockSpec((None, tile, h * K), lambda bi, g, j, s: (bi, j, g))
    values = pl.BlockSpec((None, tile, h * D),
                          lambda bi, g, j, s: (bi, j, g))
    q_rows = pl.BlockSpec((None, tile, h * R * K), q_tile)
    do_rows = pl.BlockSpec((None, tile, h * R * D), q_tile)
    q_stat = _stats_spec(h, R, tile, q_stats)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, groups=h, heads=R, qk_dim=K, v_dim=D,
                          tile=tile, tiles=tiles, window=window, scale=scale),
        grid=(B, G // h, tiles, b + 1),
        in_specs=[q_rows, keys, values, do_rows, q_stat, q_stat],
        out_specs=[keys, values],
        out_shape=[jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tile, h * K), jnp.float32),
                        pltpu.VMEM((tile, h * D), jnp.float32)],
        compiler_params=PARAMS, interpret=interpret, name="flash_dkv",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


# --------------------------------------------------------------------------
# the differentiable op
# --------------------------------------------------------------------------

def attention(q, k, v, window, tile, interpret=None):
    """Causal grouped-query attention, (B, L, heads, Dk) x (B, L, kv_heads,
    Dk) x (B, L, kv_heads, Dv) -> (B, L, heads * Dv) in f32; ``window`` None
    or the look-back of a window layer; tiles of ``tile`` queries by
    ``tile`` keys (:func:`fits` says which shapes).  ``interpret`` None runs
    the kernels compiled on a TPU and interpreted elsewhere."""
    B, L, H, Dk = q.shape
    D = v.shape[-1]
    if not fits(L, D, tile):
        raise ValueError(f"no tiles of {tile} fit a context of {L} with "
                         f"values of {D}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    K = -(-Dk // LANES) * LANES             # the q.k width the kernels run
    if K != Dk:
        pad = ((0, 0), (0, 0), (0, 0), (0, K - Dk))
        q, k = jnp.pad(q, pad), jnp.pad(k, pad)
    flat = lambda x: x.reshape(B, L, -1)
    return _attention(flat(q), flat(k), flat(v), K, D, 1.0 / math.sqrt(Dk),
                      window, tile, bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attention(q, k, v, K, D, scale, window, tile, interpret):
    return _forward(q, k, v, K, D, scale, window, tile, interpret)[0]


def _attention_fwd(q, k, v, K, D, scale, window, tile, interpret):
    o, lse = _forward(q, k, v, K, D, scale, window, tile, interpret)
    # named, so that a rematerialized layer that keeps its output keeps
    # this too, and its backward does not run the forward kernel again
    return o, (q, k, v, checkpoint_name(lse, "attention_lse"))


def _attention_bwd(K, D, scale, window, tile, interpret, res, do):
    with stage_scope("attention"):
        return _backward(*res, do, K, D, scale, window, tile, interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)
