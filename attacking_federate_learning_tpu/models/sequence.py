"""Sequence client model: a decoder of sparse-expert layers, holding one
chip's share of an expert-parallel deployment, in two forms.  The first
(SmallThinker's) has grouped-query attention, global and windowed layers
mixed by a per-layer layout, and a router that reads the attention block's
input; the second (DeepSeek-V3's, :data:`KANANA_EP16`) has latent attention,
leading dense layers, and a sigmoid router behind attention beside shared
experts.  The first form:

Per layer, with ``rms(u) = u / sqrt(mean(u^2) + eps) * g`` and ``h`` the
residual stream of a context of token ids (``h0 = E[x]``):

- ``a = rms1(h)``; **router logits** ``r = a @ W_r`` (one per published
  expert), taken here, in front of attention.
- ``q = a @ W_q`` (heads x head_dim), ``k = a @ W_k``, ``v = a @ W_v``
  (kv_heads x head_dim, each shared by heads / kv_heads query heads).  A
  layer whose ``rope_layout`` entry is 1 rotates q and k (rotate-half over
  the whole head, positions 0..L-1); a 0 layer has no positions.  Key j is
  visible to query i where j <= i and, on a layer whose
  ``window_layout`` entry is 1, i - j < window.  Softmax in f32, times v,
  heads joined, ``@ W_o``; ``h += that``.
- ``m = rms2(h)``; the ``top_k`` largest of ``r``, weights = softmax over
  those logits.  Expert e: ``(relu(m @ W_gate[e]) * (m @ W_up[e])) @
  W_down[e]``.  **This chip adds the weighted outputs of the selected
  experts it holds** (``experts_held``); what the absent experts would
  add is left out and nothing stands in for them.  ``h += that``.
- After the last layer ``log_softmax(rms_f(h) @ W_head)``.

The second form (``kv_rank`` > 0, ``router`` "sigmoid"), per layer:

- ``a = rms1(h)``; ``q = a @ W_q``, heads x (``head_dim`` + ``rope_dim``),
  split into a part without positions and one with them.  **Latent**: ``[c,
  k_r] = a @ W_kva`` (``kv_rank`` + ``rope_dim``), ``c = rms_kv(c)``, ``[k_n,
  v] = c @ W_kvb`` (heads x 2 ``head_dim``); the position parts of q and the
  ONE key head ``k_r`` rotate over adjacent pairs (``rope_interleave``), and
  ``k_r`` is broadcast to every head.  Causal softmax of ``(q_n.k_n +
  q_r.k_r) / sqrt(head_dim + rope_dim)``, times v; ``h += o @ W_o``.
- ``m = rms2(h)``.  A leading dense layer (the first ``dense_layers``):
  ``h += (silu(m @ W_gate) * (m @ W_up)) @ W_down`` of ``dense_width``.  An
  expert layer: ``sigma = sigmoid(m @ W_r)`` over every published expert, the
  ``top_k`` largest of ``sigma + b`` selected (``b``, :func:`selection_bias`,
  selects and never weighs), weights ``sigma_sel / sum(sigma_sel) *
  routed_scale``; ``h +=`` the held experts' weighted SwiGLU outputs plus the
  shared experts' SwiGLU (``shared_width``), which every token passes.

What is built for size (PERF.md section 6, PR 36):

- On a TPU attention is the fused kernels of ``ops/flash_attention.py``
  (PERF.md section 6, PR 37): online softmax over the (``query_block`` x
  ``query_block``) tiles a window can see, no score tensor in HBM, the
  log-sum-exp kept for the backward.  Elsewhere, and for heads narrower
  than the lanes, it walks the queries in blocks of ``query_block`` rows,
  each reading only a span of keys its block can see (:func:`key_spans`: a
  window layer reads at most ``window + query_block`` keys, not the
  context), each block under ``jax.checkpoint`` so that no (heads, L, L)
  tensor exists forward or backward.
- Experts are grouped products over the (token, slot) pairs routed to the
  held experts, none dropped and none padded to a per-expert maximum: the
  pairs are sorted by expert and walked by a scan in chunks of one
  context's rows (``jax.lax.ragged_dot`` over each chunk's groups); a chunk
  past the held pairs is skipped by ``lax.cond``.  Rows move by gathers in both
  directions (:func:`_dispatch` / :func:`_combine` are each other's
  transpose), so the backward pass has no scatter-add.
- Each layer is rematerialized (its attention output kept, :data:`SAVED`),
  and the training loss computes head and cross-entropy in row chunks, so
  the (L, vocabulary) logits exist only in ``apply`` (eval, checks).

- Consecutive layers of one kind (a run) are one ``lax.scan`` over their
  stacked parameters: the compiler builds each kind of layer once.

Matrices are (in, out), applied ``x @ W``; parameters are f32, init
N(0, 0.02) (the embedding N(0, 1): :data:`EMBED_STD`), norms 1.  Wire order: embed; per run of layers of one kind
norm1, q, k, v, o, router, norm2, gate, up, down, each stacked over the
run's layers; final norm; head.  In the second form a run's pieces are
norm1, q, kv_a, kv_norm, kv_b, o, norm2, then router, gate, up, down,
shared_gate, shared_up, shared_down (an expert run) or gate, up, down (a
dense run).
"""

from __future__ import annotations

import functools
import math
from collections import OrderedDict
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from attacking_federate_learning_tpu.models.base import MODELS, Model
from attacking_federate_learning_tpu.ops import flash_attention
from attacking_federate_learning_tpu.utils.costs import (
    note_attention, stage_scope
)

INIT_STD = 0.02         # every matrix but the embedding
# The embedding's: with N(0, 0.02) rows the residual stream behind the first
# global layer without positions is one common direction (the mean value
# vector), every token of a context then selects the same six experts and a
# held expert sees all of them or none, by seed: unlike a trained router,
# which is balanced.  Unit rows keep the token in charge of its routing
# (PERF.md section 6, PR 36: the chip's readings of both).
EMBED_STD = 1.0
LOSS_ROWS = 1024        # rows of one head + cross-entropy chunk
# what a rematerialized layer keeps: the attention output and, on the fused
# path, its log-sum-exp, so that the backward recomputes no attention
SAVED = ("attention_out", "attention_lse")
_NEG = -1e30            # a masked score: exp() of it is exactly 0 in f32
# The sigmoid router's selection bias (DeepSeek-V3's
# e_score_correction_bias): a trained model's is learnt by an update rule
# of its trainer, not by the gradient, and no value is published, so it is
# a fixed buffer drawn from this seed, outside the wire.  Nonzero, so that a
# selection it moves is exercised: at N(0, 0.05), selecting without it moves
# over half the tokens' log-probabilities by tenths at the published widths
# (PERF.md section 6, the latent-attention cell).
BIAS_SEED, BIAS_STD = 0, 0.05
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


class SeqSizes(NamedTuple):
    vocab: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_layout: Tuple[int, ...]      # per layer: 1 rotates q and k
    window_layout: Tuple[int, ...]    # per layer: 1 is a window layer
    window: int
    rope_theta: float
    eps: float
    experts: int                      # the router's outputs (published)
    experts_held: Tuple[int, ...]     # ids of the experts this chip holds
    top_k: int
    expert_width: int
    query_block: int = 512
    # latent attention where kv_rank > 0: the latent's width, and the q.k
    # part with positions (one key head shared by all); head_dim is then
    # the q.k part without positions and the value width
    kv_rank: int = 0
    rope_dim: int = 0
    rope_interleave: bool = False     # rotate adjacent pairs, not halves
    router: str = "softmax"           # or "sigmoid" (the second form)
    routed_scale: float = 1.0
    activation: str = "relu"          # of every expert and dense layer
    shared_width: int = 0             # the shared experts' (0: none)
    dense_layers: int = 0             # leading dense layers
    dense_width: int = 0

    @property
    def layers(self):
        return len(self.rope_layout)

    @property
    def runs(self):
        """[(rope, windowed, dense, count)]: maximal runs of consecutive
        layers of one kind, in layer order."""
        out = []
        for i, kind in enumerate(zip(self.rope_layout, self.window_layout)):
            kind += (int(i < self.dense_layers),)
            if out and out[-1][:3] == kind:
                out[-1] = kind + (out[-1][3] + 1,)
            else:
                out.append(kind + (1,))
        return out


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _layer_params(s: SeqSizes, dense, normal, keys):
    """One layer's pieces in wire order, drawn from ``keys`` in order."""
    H, D, held = s.hidden, s.head_dim, len(s.experts_held)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    p = OrderedDict(norm1=ones(H))
    if s.kv_rank:
        p["q"] = normal(next(keys), (H, s.heads * (D + s.rope_dim)))
        p["kv_a"] = normal(next(keys), (H, s.kv_rank + s.rope_dim))
        p["kv_norm"] = ones(s.kv_rank)
        p["kv_b"] = normal(next(keys), (s.kv_rank, s.heads * 2 * D))
    else:
        p["q"] = normal(next(keys), (H, s.heads * D))
        p["k"] = normal(next(keys), (H, s.kv_heads * D))
        p["v"] = normal(next(keys), (H, s.kv_heads * D))
    p["o"] = normal(next(keys), (s.heads * D, H))
    if s.router == "softmax":
        p["router"] = normal(next(keys), (H, s.experts))
    p["norm2"] = ones(H)
    if dense:
        p["gate"] = normal(next(keys), (H, s.dense_width))
        p["up"] = normal(next(keys), (H, s.dense_width))
        p["down"] = normal(next(keys), (s.dense_width, H))
        return p
    if s.router == "sigmoid":
        p["router"] = normal(next(keys), (H, s.experts))
    p["gate"] = normal(next(keys), (held, H, s.expert_width))
    p["up"] = normal(next(keys), (held, H, s.expert_width))
    p["down"] = normal(next(keys), (held, s.expert_width, H))
    if s.shared_width:
        p["shared_gate"] = normal(next(keys), (H, s.shared_width))
        p["shared_up"] = normal(next(keys), (H, s.shared_width))
        p["shared_down"] = normal(next(keys), (s.shared_width, H))
    return p


def init_params(s: SeqSizes, key):
    def normal(k, shape):
        return INIT_STD * jax.random.normal(k, shape, jnp.float32)

    # at most 11 matrices a layer (8 in the first form, whose draws are
    # those of a split into 2 + 8 a layer)
    keys = iter(jax.random.split(
        key, 2 + (11 if s.kv_rank else 8) * s.layers))
    params = OrderedDict([("embed", normal(next(keys), (s.vocab, s.hidden))
                           * (EMBED_STD / INIT_STD))])
    for r, (_, _, dense, count) in enumerate(s.runs):
        layers = [_layer_params(s, dense, normal, keys)
                  for _ in range(count)]
        # a run's layers are one stacked leaf a piece: the run is a scan
        params[f"run{r}"] = OrderedDict(
            (name, jnp.stack([layer[name] for layer in layers]))
            for name in layers[0])
    params["norm"] = jnp.ones((s.hidden,), jnp.float32)
    params["head"] = normal(next(keys), (s.hidden, s.vocab))
    return params


# --------------------------------------------------------------------------
# norm, positions
# --------------------------------------------------------------------------

def rms_norm(g, u, eps):
    return u * lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * g


def rope_tables(length, head_dim, theta):
    """(cos, sin), each (length, head_dim): the rotate-half layout."""
    inv = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                    / head_dim)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """x (B, L, heads, head_dim) rotated by its position."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def deinterleave(x):
    """Adjacent pairs (x0, x1), (x2, x3), ... laid out as halves (x0, x2,
    ...; x1, x3, ...), so that :func:`rotate` turns each pair: RoPE over
    adjacent pairs, in an order that q and k share, which no q.k changes."""
    return x.reshape(*x.shape[:-1], -1, 2).swapaxes(-1, -2).reshape(x.shape)


# --------------------------------------------------------------------------
# attention in query blocks
# --------------------------------------------------------------------------

KEY_CLASSES = 4     # query blocks a class of key spans covers


def _attend_block(q, k, v, start, lo, window):
    """One block of queries against a span of keys.  q (B, G, R, Q, D) at
    positions start.., k / v (B, G, K, D) at positions lo.. (positions
    below 0 are padding); ``window`` None on a global layer."""
    scores = jnp.einsum("bgrqd,bgkd->bgrqk", q, k) / math.sqrt(q.shape[-1])
    qi = start + lax.broadcasted_iota(jnp.int32, scores.shape[-2:], 0)
    kj = lo + lax.broadcasted_iota(jnp.int32, scores.shape[-2:], 1)
    seen = (kj <= qi) & (kj >= 0)
    if window is not None:
        seen = seen & (qi - kj < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, _NEG), axis=-1)
    return jnp.einsum("bgrqk,bgkd->bgrqd", probs, v)


def key_spans(length, window, block):
    """[(keys a block reads, [block starts])]: the query blocks grouped by
    the span of keys they read.  A block at ``start`` has to see the keys
    from ``start - window + 1`` (0 on a global layer) to its own last row;
    spans are rounded up to ``KEY_CLASSES`` blocks and capped at ``window +
    block`` (the context on a global layer), so that a layer is a handful
    of uniform loops, not one program a block.  A window layer at 8,192 /
    4,096 / 512 reads 2,048, 4,096 and 4,608 keys: 31.5 M pairs a head for
    the 25.2 M visible (a span of the whole context would be 67.1 M)."""
    step = KEY_CLASSES * block
    cap = length if window is None else min(length, -(-window // block)
                                            * block + block)
    spans = {}
    for start in range(0, length, block):
        stop = start + block
        lo = 0 if window is None else max(
            0, (start - window + 1) // block * block)
        keys = min(-(-(stop - lo) // step) * step, cap)
        spans.setdefault(keys, []).append(start)
    return sorted(spans.items())


def _on_tpu():
    return jax.default_backend() == "tpu"


def takes_kernel(length, v_dim, block):
    """Whether attention runs as the fused kernels: on a TPU, with values
    of whole lanes and a context of whole blocks (tiles of ``block``)."""
    return _on_tpu() and flash_attention.fits(length, v_dim, block)


def _note_path(length, window, layers, s: SeqSizes):
    """A run of layers' attention path in the cost log."""
    record = {"path": "blocked", "layers": layers, "tokens": length,
              "window": window, "qk_dim": s.head_dim + s.rope_dim,
              "v_dim": s.head_dim}
    if takes_kernel(length, s.head_dim, s.query_block):
        heads = s.heads // s.kv_heads
        record.update(
            path="fused", pairs_ratio=(
                flash_attention.computed_pairs(length, window, s.query_block)
                / flash_attention.visible_pairs(length, window)),
            groups_per_step=flash_attention.groups_per_step(heads,
                                                            s.kv_heads),
            grid_steps=flash_attention.grid_steps(
                length, window, s.query_block, heads, s.kv_heads))
    note_attention(record)


def attention(q, k, v, window, block):
    """Causal grouped-query attention, (B, L, heads, D) x (B, L, kv_heads,
    D) x (B, L, kv_heads, Dv) -> (B, L, heads * Dv); ``window`` None or the
    look-back of a window layer.  On a TPU, with values of whole lanes and a
    context of whole blocks, the fused kernels of ``ops/flash_attention.py``
    in tiles of ``block`` x ``block``; elsewhere query blocks over key
    spans.  Sub-stage ``attention`` of the stage ledger, forward and
    backward."""
    B, L, H, D = q.shape
    G, Dv = k.shape[2], v.shape[-1]
    if L % block:
        raise ValueError(f"a context of {L} tokens is not whole query "
                         f"blocks of {block}")
    with stage_scope("attention"):
        if takes_kernel(L, Dv, block):
            return flash_attention.attention(q, k, v, window, block)
        q = q.reshape(B, L // block, block, G, H // G, D).transpose(
            1, 0, 3, 4, 2, 5)                       # (blocks, B, G, R, Q, D)
        # keys in front of the context, so that every block of a class
        # reads a span of one length (masked where the position is < 0)
        pad = (KEY_CLASSES - 1) * block
        k = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (pad, 0),
                                              (0, 0)))
        v = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (pad, 0),
                                              (0, 0)))
        out = []
        for keys, starts in key_spans(L, window, block):

            @jax.checkpoint
            def one(q, start, keys=keys):
                lo = start + block - keys           # may be < 0: padding
                return _attend_block(
                    q, lax.dynamic_slice_in_dim(k, lo + pad, keys, axis=2),
                    lax.dynamic_slice_in_dim(v, lo + pad, keys, axis=2),
                    start, lo, window)

            first = starts[0] // block
            out.append(lax.map(
                lambda xs: one(*xs),
                (q[first:first + len(starts)],
                 jnp.asarray(starts, jnp.int32))))
        out = jnp.concatenate(out, axis=0)         # (blocks, B, G, R, Q, Dv)
        return out.transpose(1, 0, 4, 2, 3, 5).reshape(B, L, H * Dv)


# --------------------------------------------------------------------------
# the expert layer: this chip's share
# --------------------------------------------------------------------------

def _dispatch_impl(m, plan):
    """Rows of m (T, H) in sorted-pair order for one chunk: (C, H)."""
    tok, valid, _, _ = plan
    return jnp.where(valid[:, None], m[tok], 0.0)


def _combine_impl(o, plan):
    """Sum over a token's slots of its rows of o (C, H): (T, H)."""
    _, _, rel, inc = plan
    total = None
    for s in range(rel.shape[1]):
        part = jnp.where(inc[:, s, None], o[rel[:, s]], 0.0)
        total = part if total is None else total + part
    return total


@jax.custom_vjp
def _dispatch(m, plan):
    return _dispatch_impl(m, plan)


@jax.custom_vjp
def _combine(o, plan):
    return _combine_impl(o, plan)


# The two are linear and each other's transpose: a token's row goes to the
# sorted positions of its held pairs, and comes back as their sum.  Written
# as custom rules so that both directions gather (a gather's own transpose
# is a scatter-add, which the TPU walks row by row).
_dispatch.defvjp(lambda m, plan: (_dispatch_impl(m, plan), plan),
                 lambda plan, dx: (_combine_impl(dx, plan), None))
_combine.defvjp(lambda o, plan: (_combine_impl(o, plan), plan),
                lambda plan, dy: (_dispatch_impl(dy, plan), None))


def _pair_weights_impl(w, slot, plan):
    tok, valid, _, _ = plan
    return jnp.where(valid, w[tok, slot], 0.0)


@jax.custom_vjp
def _pair_weights(w, slot, plan):
    """The routing weight of each sorted pair of a chunk: (C,)."""
    return _pair_weights_impl(w, slot, plan)


def _pair_weights_bwd(plan, dwc):
    _, _, rel, inc = plan
    return jnp.where(inc, dwc[rel], 0.0), None, None


_pair_weights.defvjp(
    lambda w, slot, plan: (_pair_weights_impl(w, slot, plan), plan),
    _pair_weights_bwd)


def selection_bias(s: SeqSizes):
    """The sigmoid router's selection bias, (expert layers, experts) f32:
    N(0, ``BIAS_STD``) from ``BIAS_SEED``, layer by layer."""
    rng = np.random.default_rng(BIAS_SEED)
    return (BIAS_STD * rng.standard_normal(
        (s.layers - s.dense_layers, s.experts))).astype(np.float32)


def route(r, s: SeqSizes, bias=None):
    """Router logits (T, experts) -> (weights (T, k), local (T, k)): per
    slot the routing weight and the index of the selected expert among
    those held here (``len(experts_held)`` for an expert on another chip).
    ``softmax``: the top k logits, weights their softmax.  ``sigmoid``: the
    top k of ``sigmoid(r) + bias`` (experts,), weights the selected
    sigmoids alone, over their sum, times ``routed_scale``."""
    held = len(s.experts_held)
    local_of = np.full((s.experts,), held, np.int32)
    local_of[list(s.experts_held)] = np.arange(held)
    if s.router == "softmax":
        top, idx = lax.top_k(r, s.top_k)
        return jax.nn.softmax(top, axis=-1), jnp.asarray(local_of)[idx]
    sig = jax.nn.sigmoid(r)
    _, idx = lax.top_k(sig + bias, s.top_k)
    # the selected sigmoids by a masked sum, whose transpose is a select
    # (a gather's would be a scatter-add)
    picked = idx[..., None] == lax.broadcasted_iota(
        jnp.int32, (1, 1, s.experts), 2)
    top = jnp.sum(jnp.where(picked, sig[:, None, :], 0.0), axis=-1)
    w = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return w * s.routed_scale, jnp.asarray(local_of)[idx]


def routing_counts(local, held):
    """Tokens routed to each held expert, (held,) int32."""
    return jnp.sum(local.reshape(-1, 1) == jnp.arange(held)[None, :],
                   axis=0, dtype=jnp.int32)


def _pair_plan(local, held):
    """The (token, slot) pairs sorted by held expert (absent ones last):
    what every chunk's plan is cut from."""
    T, k = local.shape
    flat = local.reshape(-1)                        # pair = t * k + slot
    order = jnp.argsort(flat, stable=True)
    pos = jnp.argsort(order).reshape(T, k).astype(jnp.int32)
    ends = jnp.cumsum(routing_counts(local, held))
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    return order, pos, starts, ends, local < held


def _expert_chunk(c, sorted_pairs, act, gate, up, down, m, w):
    """Chunk c (traced) of the sorted pairs, T of them, through the held
    experts: rows in by :func:`_dispatch`, three grouped products with
    ``act`` on the gate, the routing weights, rows out by
    :func:`_combine`: (T, H)."""
    order, pos, starts, ends, is_held = sorted_pairs
    T, k = pos.shape
    base = c * T
    pairs = lax.dynamic_slice_in_dim(order, base, T)
    rel = pos - base
    plan = ((pairs // k).astype(jnp.int32),
            base + jnp.arange(T) < ends[-1],
            jnp.clip(rel, 0, T - 1),
            is_held & (rel >= 0) & (rel < T))
    groups = (jnp.clip(ends - base, 0, T)
              - jnp.clip(starts - base, 0, T)).astype(jnp.int32)
    x = _dispatch(m, plan)
    h = (ACTIVATIONS[act](lax.ragged_dot(x, gate, groups))
         * lax.ragged_dot(x, up, groups))
    o = lax.ragged_dot(h, down, groups)
    wc = _pair_weights(w, (pairs % k).astype(jnp.int32), plan)
    # rows past the chunk's groups are not written by the grouped
    # product: masked here, and in _dispatch on the way back
    return _combine(jnp.where(plan[1][:, None], o * wc[:, None], 0.0), plan)


def _chunks(sorted_pairs):
    """Chunks of T pairs that hold a pair routed to a held expert."""
    T = sorted_pairs[1].shape[0]
    return (sorted_pairs[3][-1] + T - 1) // T


def _held_experts_impl(gate, up, down, m, w, local, act):
    with stage_scope("experts"):
        sorted_pairs = _pair_plan(local, gate.shape[0])
        return lax.fori_loop(
            0, _chunks(sorted_pairs),
            lambda c, y: y + _expert_chunk(c, sorted_pairs, act, gate, up,
                                           down, m, w),
            jnp.zeros_like(m))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _held_experts(gate, up, down, m, w, local, act):
    """The weighted outputs of the held experts for tokens m (T, H) with
    routing weights w (T, k) and held-expert indices ``local`` (T, k).
    The sorted pairs are walked in chunks of T, only as many as hold a
    routed pair (one at even routing): a loop of traced length, so forward
    and backward are written out, each chunk's backward by ``jax.vjp`` of
    the chunk and the gradients summed in the loop's carry (a scan of
    ``lax.cond`` keeps a copy of the experts' weights a chunk)."""
    return _held_experts_impl(gate, up, down, m, w, local, act)


def _held_experts_bwd(act, res, dy):
    gate, up, down, m, w, local = res
    with stage_scope("experts"):
        sorted_pairs = _pair_plan(local, gate.shape[0])

        def chunk_grads(c, acc):
            _, pull = jax.vjp(functools.partial(
                _expert_chunk, c, sorted_pairs, act), gate, up, down, m, w)
            return jax.tree.map(jnp.add, acc, pull(dy))

        grads = lax.fori_loop(
            0, _chunks(sorted_pairs), chunk_grads,
            jax.tree.map(jnp.zeros_like, (gate, up, down, m, w)))
    return (*grads, None)


_held_experts.defvjp(
    lambda *args: (_held_experts_impl(*args), args[:-1]), _held_experts_bwd)


def experts(p, m, r, s: SeqSizes, bias=None):
    """This chip's part of the expert block's output for tokens m (T, H)
    with router logits r (T, experts).  Sub-stage ``experts``."""
    with stage_scope("experts"):
        w, local = route(r, s, bias)
    return _held_experts(p["gate"], p["up"], p["down"], m, w, local,
                         s.activation)


def glu(m, gate, up, down, act):
    """``(act(m @ gate) * (m @ up)) @ down``: a dense layer's feed-forward
    and the shared experts'."""
    return (ACTIVATIONS[act](m @ gate) * (m @ up)) @ down


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def latent_qkv(p, a, rope, s: SeqSizes):
    """Latent attention's q, k (B, L, heads, head_dim + rope_dim) and v
    (B, L, heads, head_dim) from the normed input a.  Sub-stage ``latent``:
    the latent's projections and norm, the rotation and the broadcast of
    the parts with positions (q's projection is the layer's own)."""
    B, L, _ = a.shape
    D, R = s.head_dim, s.rope_dim
    q = (a @ p["q"]).reshape(B, L, s.heads, D + R)
    with stage_scope("latent"):
        ckr = a @ p["kv_a"]
        c = rms_norm(p["kv_norm"], ckr[..., :s.kv_rank], s.eps)
        kv = (c @ p["kv_b"]).reshape(B, L, s.heads, 2 * D)
        q_r, k_r = q[..., D:], ckr[..., None, s.kv_rank:]
        if s.rope_interleave:
            q_r, k_r = deinterleave(q_r), deinterleave(k_r)
        if rope:
            cos, sin = rope_tables(L, R, s.rope_theta)
            q_r, k_r = rotate(q_r, cos, sin), rotate(k_r, cos, sin)
        q = jnp.concatenate([q[..., :D], q_r], axis=-1)
        k = jnp.concatenate(
            [kv[..., :D], jnp.broadcast_to(k_r, (B, L, s.heads, R))], axis=-1)
    return q, k, kv[..., D:]


def _layer(p, h, rope, windowed, s: SeqSizes, dense=False, bias=None):
    B, L, _ = h.shape
    a = rms_norm(p["norm1"], h, s.eps)
    if s.router == "softmax":       # read in front of attention
        r = a @ p["router"]
    if s.kv_rank:
        q, k, v = latent_qkv(p, a, rope, s)
    else:
        q = (a @ p["q"]).reshape(B, L, s.heads, s.head_dim)
        k = (a @ p["k"]).reshape(B, L, s.kv_heads, s.head_dim)
        v = (a @ p["v"]).reshape(B, L, s.kv_heads, s.head_dim)
        if rope:
            cos, sin = rope_tables(L, s.head_dim, s.rope_theta)
            q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    ao = attention(q, k, v, s.window if windowed else None, s.query_block)
    h = h + checkpoint_name(ao, "attention_out") @ p["o"]
    m = rms_norm(p["norm2"], h, s.eps)
    if dense:
        return h + glu(m, p["gate"], p["up"], p["down"], s.activation)
    if s.router == "sigmoid":
        r = m @ p["router"]
    y = experts(p, m.reshape(B * L, -1), r.reshape(B * L, -1), s, bias)
    if s.shared_width:
        with stage_scope("shared_experts"):
            y = y + glu(m, p["shared_gate"], p["shared_up"],
                        p["shared_down"], s.activation).reshape(y.shape)
    return h + y.reshape(h.shape)


def trunk(params, x, s: SeqSizes):
    """Token ids (B, L) -> the final residual stream (B, L, hidden)."""
    h = params["embed"][x]
    keep = jax.checkpoint_policies.save_only_these_names(*SAVED)
    bias = selection_bias(s) if s.router == "sigmoid" else None
    first = 0                                   # the run's first layer
    for r, (rope, windowed, dense, count) in enumerate(s.runs):
        _note_path(x.shape[1], s.window if windowed else None, count, s)
        layer = jax.checkpoint(functools.partial(
            _layer, rope=bool(rope), windowed=bool(windowed),
            dense=bool(dense), s=s), policy=keep)
        at = first - s.dense_layers
        run_bias = (None if bias is None or dense
                    else jnp.asarray(bias[at:at + count]))
        h, _ = lax.scan(lambda h, xs: (layer(xs[0], h, bias=xs[1]), None),
                        h, (params[f"run{r}"], run_bias))
        first += count
    return h


def apply(params, x, s: SeqSizes):
    """(B, L) token ids -> (B, L, vocab) log-probabilities of the next
    token."""
    h = rms_norm(params["norm"], trunk(params, x, s), s.eps)
    return jax.nn.log_softmax(h @ params["head"], axis=-1)


def loss(params, x, y, s: SeqSizes):
    """Mean next-token cross-entropy of contexts x (B, L) against y (B,
    L), head and loss in chunks of ``LOSS_ROWS`` rows so that the logits
    of a whole context never exist: what ``nll_loss(apply(params, x), y)``
    computes."""
    h = trunk(params, x, s).reshape(-1, s.hidden)
    y = y.reshape(-1)

    @jax.checkpoint
    def rows(h, y):
        logits = rms_norm(params["norm"], h, s.eps) @ params["head"]
        # the target's logit by a masked sum, which fuses into the pass
        # over the logits (a gather's transpose would be a scatter-add)
        at = lax.broadcasted_iota(jnp.int32, logits.shape, 1) == y[:, None]
        picked = jnp.sum(jnp.where(at, logits, 0.0), axis=-1)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    total = sum(rows(h[lo:lo + LOSS_ROWS], y[lo:lo + LOSS_ROWS])
                for lo in range(0, h.shape[0], LOSS_ROWS))
    return total / h.shape[0]


def make_sequence_model(name: str, s: SeqSizes) -> Model:
    assert len(s.rope_layout) == len(s.window_layout)
    assert s.heads % s.kv_heads == 0 and s.head_dim % 2 == 0
    assert s.router in ("softmax", "sigmoid") and s.activation in ACTIVATIONS
    assert not s.kv_rank or (s.kv_heads == s.heads and s.rope_dim % 2 == 0)
    return Model(name=name, init=functools.partial(init_params, s),
                 apply=functools.partial(apply, s=s),
                 input_shape=(), num_classes=s.vocab,
                 loss=functools.partial(loss, s=s), sizes=s)


# One chip's share (member 0) of an 8-way expert-parallel deployment of
# SmallThinker-21BA3B-Instruct: one whole period of its layer pattern,
# experts 0-7 of 64, an eighth of the vocabulary, every width as
# published (perfbench/configs/smallthinker_21b_a3b_ep8.json has the cut).
SMALLTHINKER_EP8 = SeqSizes(
    vocab=18_992, hidden=2_560, heads=28, kv_heads=4, head_dim=128,
    rope_layout=(0, 1, 1, 1), window_layout=(0, 1, 1, 1), window=4_096,
    rope_theta=1.5e6, eps=1e-6, experts=64, experts_held=tuple(range(8)),
    top_k=6, expert_width=768)

# The same layer at a size the CPU tests and a smoke run can afford.
SEQ_TINY = SeqSizes(
    vocab=96, hidden=64, heads=4, kv_heads=2, head_dim=16,
    rope_layout=(0, 1, 1, 1), window_layout=(0, 1, 1, 1), window=8,
    rope_theta=1.5e6, eps=1e-6, experts=8, experts_held=(0, 1),
    top_k=2, expert_width=32, query_block=8)


# One chip's share (member 0) of a 16-way expert-parallel deployment of
# kanana-2-30b-a3b-instruct-2601 (DeepSeek-V3 form, no q compression): the
# leading dense layer and four expert layers, experts 0-7 of 128, an eighth
# of the vocabulary, every width as published
# (perfbench/configs/kanana_2_30b_a3b_ep16.json has the cut).
KANANA_EP16 = SeqSizes(
    vocab=16_032, hidden=2_048, heads=32, kv_heads=32, head_dim=128,
    rope_layout=(1,) * 5, window_layout=(0,) * 5, window=0,
    rope_theta=1e6, eps=1e-6, experts=128, experts_held=tuple(range(8)),
    top_k=6, expert_width=768, kv_rank=512, rope_dim=64,
    rope_interleave=True, router="sigmoid", routed_scale=2.448,
    activation="silu", shared_width=1_536, dense_layers=1,
    dense_width=6_144)

# The second form at a size the CPU tests and a smoke run can afford.
MLA_TINY = SeqSizes(
    vocab=96, hidden=64, heads=4, kv_heads=4, head_dim=16,
    rope_layout=(1,) * 4, window_layout=(0,) * 4, window=0,
    rope_theta=1e6, eps=1e-6, experts=8, experts_held=(0, 1), top_k=2,
    expert_width=32, query_block=8, kv_rank=32, rope_dim=8,
    rope_interleave=True, router="sigmoid", routed_scale=2.448,
    activation="silu", shared_width=32, dense_layers=1, dense_width=96)


@MODELS.register("smallthinker_21b_a3b_ep8")
def smallthinker_21b_a3b_ep8() -> Model:
    return make_sequence_model("smallthinker_21b_a3b_ep8", SMALLTHINKER_EP8)


@MODELS.register("seq_tiny")
def seq_tiny() -> Model:
    return make_sequence_model("seq_tiny", SEQ_TINY)


@MODELS.register("kanana_2_30b_a3b_ep16")
def kanana_2_30b_a3b_ep16() -> Model:
    return make_sequence_model("kanana_2_30b_a3b_ep16", KANANA_EP16)


@MODELS.register("mla_tiny")
def mla_tiny() -> Model:
    return make_sequence_model("mla_tiny", MLA_TINY)
