"""Plain reference of ``kanana_2_30b_a3b_ep16``: one chip's share (member 0
of 16) of kanana-2-30b-a3b-instruct-2601's layers, written from the
published description (DeepSeek-V3's form; configs/kanana_2_30b_a3b_ep16.json
has the source, the cut and what the config leaves open).  ``jax.numpy``,
f32, from the flat wire vector; no cache, no scan over clients, no
grouped products: every held expert runs densely over
every token and the routing weight, zero where it is not selected, scales
it.  Its departures from plain are for memory alone: attention walks the
queries in blocks of ``ROWS`` rows against ALL keys, so that no (heads, L,
L) tensor has to fit beside the live state, and each layer and each block
is checkpointed, so that its gradient keeps no block's scores.

Per layer, rms(u) = u / sqrt(mean(u^2) + eps) * g:

- a = rms1(h); q = a @ W_q, 32 heads of 128 + 64, split into q_n and q_r.
- [c, k_r] = a @ W_kva (512 + 64); c = rms_kv(c); [k_n, v] = c @ W_kvb,
  32 heads of 128 + 128.  k_r is ONE head, seen by all 32.
- q_r and k_r rotate over adjacent pairs (rope_interleave): pair i,
  (x[2i], x[2i+1]), turns by position * theta^(-2i / 64), theta 1e6.
- scores (q_n . k_n + q_r . k_r) / sqrt(192), causal, softmax in f32, times
  v, heads joined, @ W_o; h += that.
- m = rms2(h).  Layer 0 (dense): h += (silu(m @ W_gate) * (m @ W_up)) @
  W_down, width 6,144.  Layers 1-4: s = sigmoid(m @ W_r) over 128 experts;
  the 6 largest of s + b are selected (b: the selection bias, below); the
  weights are the selected s over their sum (+1e-20) times 2.448; h += the
  weighted SwiGLU outputs (width 768) of the selected experts HELD HERE
  (ids 0-7) + the shared experts' SwiGLU (width 1,536).
- log_softmax(rms_f(h) @ W_head): the head's logits over the 16,032-id
  slice, normalized as the program's ``model.apply`` returns them.

b, layer by layer: 0.05 * numpy ``default_rng(0).standard_normal((4,
128))`` (models/sequence.py draws the same buffer; it is not on the wire).

Wire order: embed (V, H); then per run of consecutive layers of one kind
(layer 0, then layers 1-4) each piece stacked over the run's layers: norm1
(H), q (H, 32 * 192), kv_a (H, 576), kv_norm (512), kv_b (512, 32 * 256),
o (32 * 128, H), norm2 (H); the dense run gate (H, 6144), up (H, 6144),
down (6144, H); an expert run router (H, 128), gate (8, H, 768), up (8, H,
768), down (8, 768, H), shared_gate (H, 1536), shared_up (H, 1536),
shared_down (1536, H); then norm (H); head (H, V).
"""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.kernels.attention import visible_pairs

with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json") as _f:
    CONFIG = json.load(_f)

ROWS = 256      # query rows of one attention block
BIAS_STD = 0.05


def sizes_of(config):
    """The sizes the forward needs, from a configuration file's keys."""
    return {
        "vocab": config["vocab_size"], "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"], "rank": config["kv_lora_rank"],
        "layers": config["num_hidden_layers"],
        "dense": config["first_k_dense_replace"],
        "dense_width": config["intermediate_size"],
        "theta": float(config["rope_theta"]), "eps": config["rms_norm_eps"],
        "experts": config["n_routed_experts_published"],
        "held": list(range(config["n_routed_experts"])),
        "top_k": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": (config["n_shared_experts"]
                         * config["moe_intermediate_size"]),
        "scale": config["routed_scaling_factor"]}


SIZES = sizes_of(CONFIG)


def sizes_from_model(m):
    """The same sizes from a program model's ``sizes``: for the CPU tests'
    tiny models.  The chip's check of the published configuration reads its
    file (:data:`SIZES`), never the program."""
    return {
        "vocab": m.vocab, "hidden": m.hidden, "heads": m.heads,
        "nope": m.head_dim, "rope": m.rope_dim, "v": m.head_dim,
        "rank": m.kv_rank, "layers": m.layers, "dense": m.dense_layers,
        "dense_width": m.dense_width, "theta": float(m.rope_theta),
        "eps": m.eps, "experts": m.experts, "held": list(m.experts_held),
        "top_k": m.top_k, "expert_width": m.expert_width,
        "shared_width": m.shared_width, "scale": m.routed_scale}


def runs_of(s):
    """[(first layer, layers, dense)]: the runs of consecutive layers of one
    kind, in wire order."""
    runs = [(0, s["dense"], True),
            (s["dense"], s["layers"] - s["dense"], False)]
    return [run for run in runs if run[1]]


def shapes_of(s):
    """[(name, shape)] of the wire vector's pieces, in wire order; a run's
    piece is ``<first layer of its run>.<piece>``, stacked."""
    H, heads, held = s["hidden"], s["heads"], len(s["held"])
    out = [("embed", (s["vocab"], H))]
    for first, n, dense in runs_of(s):
        out += [(f"{first}.norm1", (n, H)),
                (f"{first}.q", (n, H, heads * (s["nope"] + s["rope"]))),
                (f"{first}.kv_a", (n, H, s["rank"] + s["rope"])),
                (f"{first}.kv_norm", (n, s["rank"])),
                (f"{first}.kv_b", (n, s["rank"], heads * (s["nope"] + s["v"]))),
                (f"{first}.o", (n, heads * s["v"], H)),
                (f"{first}.norm2", (n, H))]
        if dense:
            W = s["dense_width"]
            out += [(f"{first}.gate", (n, H, W)), (f"{first}.up", (n, H, W)),
                    (f"{first}.down", (n, W, H))]
            continue
        W, S = s["expert_width"], s["shared_width"]
        out += [(f"{first}.router", (n, H, s["experts"])),
                (f"{first}.gate", (n, held, H, W)),
                (f"{first}.up", (n, held, H, W)),
                (f"{first}.down", (n, held, W, H)),
                (f"{first}.shared_gate", (n, H, S)),
                (f"{first}.shared_up", (n, H, S)),
                (f"{first}.shared_down", (n, S, H))]
    return out + [("norm", (H,)), ("head", (H, s["vocab"]))]


def wire_dim(s):
    return sum(int(np.prod(shape)) for _, shape in shapes_of(s))


WIRE_DIM = wire_dim(SIZES)


def unpack(w, s):
    """{name: array}, a run's pieces under ``<first layer>.<piece>``,
    stacked over its layers as the wire holds them."""
    p, at = {}, 0
    for name, shape in shapes_of(s):
        size = int(np.prod(shape))
        p[name] = w[at:at + size].reshape(shape)
        at += size
    if at != w.shape[0]:
        raise ValueError(f"wire vector of {w.shape[0]}, expected {at}")
    return p


def selection_bias(s):
    """(expert layers, experts): the router's selection bias."""
    return BIAS_STD * np.random.default_rng(0).standard_normal(
        (s["layers"] - s["dense"], s["experts"])).astype(np.float64)


def rms(u, g, eps):
    return u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * g


def rope_pairs(x, theta):
    """x (B, L, heads, R) with pair i = (x[2i], x[2i+1]) turned by the angle
    position * theta^(-2i / R), in place."""
    L, R = x.shape[1], x.shape[3]
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def attend(q_n, q_r, k_n, k_r, v):
    """q_n / k_n (B, L, heads, nope), q_r (B, L, heads, rope), k_r (B, L,
    rope), v (B, L, heads, dv) -> (B, L, heads * dv), causal.  The query
    blocks are a ``lax.map`` of a checkpointed block, so the gradient keeps
    no block's scores: each is computed again in the backward."""
    B, L, H, dv = v.shape
    rows = math.gcd(L, ROWS)
    scale = (q_n.shape[-1] + q_r.shape[-1]) ** -0.5
    kj = jnp.arange(L)[None, :]

    @jax.checkpoint
    def block(lo, q_n, q_r, k_n, k_r, v):
        qi = lo + jnp.arange(rows)[:, None]
        scores = (jnp.einsum("bqhd,bkhd->bhqk",
                             jax.lax.dynamic_slice_in_dim(q_n, lo, rows, 1),
                             k_n)
                  + jnp.einsum("bqhd,bkd->bhqk",
                               jax.lax.dynamic_slice_in_dim(q_r, lo, rows, 1),
                               k_r))
        scores = jnp.where(kj <= qi, scores.astype(jnp.float32) * scale,
                           -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(lambda lo: block(lo, q_n, q_r, k_n, k_r, v),
                      jnp.arange(0, L, rows))
    return jnp.moveaxis(out, 0, 1).reshape(B, L, H * dv)


def swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def expert_block(p, m, bias, s, held=None):
    """One layer's expert block for m (B, L, H), ``p`` the layer's pieces:
    the weighted outputs of the selected experts among ``held`` (default:
    those held here), dense over every token, plus the shared experts'."""
    held = s["held"] if held is None else held
    scores = jax.nn.sigmoid((m @ p["router"]).astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias, s["top_k"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    weights = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    weights = weights * s["scale"]
    y = swiglu(m, p["shared_gate"], p["shared_up"], p["shared_down"])
    for e in held:
        local = s["held"].index(e)
        share = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        out = swiglu(m, p["gate"][local], p["up"][local], p["down"][local])
        y = y + share[..., None].astype(m.dtype) * out
    return y


def layer(h, p, bias, dense, s):
    """One layer on h (B, L, H), ``p`` its pieces; ``bias`` its selection
    bias (None in a dense layer)."""
    B, L, _ = h.shape
    heads, nope = s["heads"], s["nope"]
    a = rms(h, p["norm1"], s["eps"])
    q = (a @ p["q"]).reshape(B, L, heads, nope + s["rope"])
    ckr = a @ p["kv_a"]
    c = rms(ckr[..., :s["rank"]], p["kv_norm"], s["eps"])
    kv = (c @ p["kv_b"]).reshape(B, L, heads, nope + s["v"])
    q_r = rope_pairs(q[..., nope:], s["theta"])
    k_r = rope_pairs(ckr[..., None, s["rank"]:], s["theta"])[:, :, 0]
    h = h + attend(q[..., :nope], q_r, kv[..., :nope], k_r,
                   kv[..., nope:]) @ p["o"]
    m = rms(h, p["norm2"], s["eps"])
    if dense:
        return h + swiglu(m, p["gate"], p["up"], p["down"])
    return h + expert_block(p, m, bias, s)


def forward_pieces(p, x, s, bias):
    """Token ids x (B, L) -> (B, L, V) log-probabilities of the next token,
    from the unpacked pieces ``p``.  Each run of layers is a ``lax.scan``
    of a checkpointed layer (the gradient keeps a layer's input alone and
    computes the rest again)."""
    bias = jnp.asarray(bias, jnp.float32)
    h = p["embed"][x]
    for first, n, dense in runs_of(s):
        stacked = {k.split(".")[1]: v for k, v in p.items()
                   if k.startswith(f"{first}.")}
        rows = (None if dense else
                bias[first - s["dense"]:first - s["dense"] + n])

        @jax.checkpoint
        def body(h, xs, dense=dense):
            return layer(h, xs[0], xs[1], dense, s), None

        h, _ = jax.lax.scan(body, h, (stacked, rows))
    logits = rms(h, p["norm"], s["eps"]) @ p["head"]
    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)


def pieces(w, s, dtype=jnp.float32):
    return {k: v.astype(dtype) for k, v in unpack(w, s).items()}


def forward(w, x, s=None, dtype=jnp.float32, bias=None):
    """Token ids x (B, L) -> (B, L, V) log-probabilities of the next token.
    ``dtype`` and ``bias`` are the controls' handles: the whole forward in a
    lower precision, and another selection bias (expert layers, experts)
    than :func:`selection_bias`."""
    s = SIZES if s is None else s
    return forward_pieces(pieces(w, s, dtype), x, s,
                          selection_bias(s) if bias is None else bias)


def token_loss(logp, y):
    """Mean next-token cross-entropy: position t against y[:, t]."""
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


def loss(w, x, y, s=None):
    return token_loss(forward(w, x, s), y)


# --- what decides ``correct`` -------------------------------------------------
# Two comparisons at the cell's length, outside the window, on the judged
# state (the harness has placed it in ``exp.state``).
#
# The forward.  CHECK_CONTEXTS contexts of the test set, program against
# reference, one context at a time, reduced on the device.  Per token, the
# largest gap between the two log-probability rows.  Top-6 selection is
# discontinuous, the program's matmuls run one bf16 pass and the
# reference's six, so for some tokens a selection flips (another expert is
# taken, and the weights of every selected one move with the sum they are
# normalized by) and that token's row moves by tenths whatever the
# precision.  An element-wise limit (modelcheck.logits_check) is therefore
# set by those tokens and cannot tell a lower precision from the program;
# the median token has no flip and reads the forward's rounding alone.
#
# - ``token_gap_median``, the median of the per-token gaps, within
#   TOKEN_GAP_LIMIT.  It lies between two chip readings (PERF.md section 6;
#   tests/perfbench/chip_mla_controls.py reads them): the program reads
#   0.01649 on fresh weights, 0.01660 after 15 rounds and 0.01662 where the
#   cell judged (round 15 of its window); the reference in bfloat16
#   0.03375 / 0.03494, NOT correct.  The limit is the geometric mean of
#   0.01662 and 0.03375.  A router that selects without its bias reads
#   0.265 (NOT correct).
# - ``largest_gap``, reported and not judged (limit None): set by the
#   flips, the program reads 0.49-0.84, the bf16 reference 0.51-0.86 and
#   the router without its bias 0.77-0.93, so no reading tells a fault
#   apart by it.
#
# The training step.  One silo's wire row of the judged state's round, as
# the timed program's client step makes it (``exp._compute_grads_impl``,
# the scanned step into the bf16 wire), against ``jax.grad`` of the
# reference's loss on the context that silo trained on, under "highest".
# Per piece of the wire and layer, the relative norm |row - g| / |g|; a
# row left at zero (a state no client moves) reads 1 everywhere.  The
# routed pieces (an expert layer's router and held experts) are judged
# apart: the ~1 % of tokens whose selection flips move them by tenths of
# their norm, and every other piece by the one-pass rounding alone.
#
# - ``grad_gap``, over the whole row, within GRAD_GAP_LIMIT;
# - ``grad_piece_gap``, the largest piece that is not routed, within
#   GRAD_PIECE_GAP_LIMIT;
# - ``grad_routed_gap``, the largest routed piece, within
#   GRAD_ROUTED_GAP_LIMIT.
#
# Chip readings (PERF.md section 6, chip_mla_controls.py) of the three,
# fresh weights / 15 rounds: the program 0.0115 / 0.0137, 0.0163 / 0.0177,
# 0.135 / 0.108 (the cell's own runs 0.0148-0.0149, 0.0188-0.0200,
# 0.113-0.137); the reference in bfloat16 0.0181 / 0.0195, 0.0268 /
# 0.0238, 0.166 / 0.164 (the forward's limit is the one a lower precision
# fails); the loss over half the context 0.639 / 0.665, 0.659 / 0.671,
# 0.664 / 0.673; another context 1.22 / 1.27, 1.48 / 1.46, 1.31 / 1.27;
# the scores at 1/sqrt(256), the padded width's scale, 0.016 / 0.018,
# 0.115 / 0.110, 0.165 / 0.117; the scores without the part with
# positions 0.043 / 0.046, 0.573 / 0.565, 0.266 / 0.225.  Each limit
# leaves the program's highest reading room of 2.5 times or more and fails
# the faults: the whole row those on the loss, the pieces that are not
# routed the scores' scale and positions, the routed ones half a context.
CHECK_CONTEXTS = 2
TOKEN_GAP_LIMIT = 0.0237
GRAD_GAP_LIMIT = 0.2
GRAD_PIECE_GAP_LIMIT = 0.05
GRAD_ROUTED_GAP_LIMIT = 0.4
ROUTED = ("router", "gate", "up", "down")


def token_gaps(exp, reference_apply, weights, dataset, seed):
    """Per-token largest |program - reference| log-probability gap on
    CHECK_CONTEXTS contexts drawn from the test set by ``seed``: the
    program's forward (``exp.model.apply`` on the unravelled ``weights``,
    jitted, as ``exp.evaluate`` runs it) against ``reference_apply(w, x)``
    under "highest" matmul precision.  (gaps, finite) or (None, why)."""
    test_x = np.asarray(dataset.test_x)
    picks = np.sort(np.random.default_rng(seed).choice(
        len(test_x), size=min(CHECK_CONTEXTS, len(test_x)), replace=False))
    w = jnp.asarray(weights)
    program = jax.jit(lambda w, x: exp.model.apply(exp.flat.unravel(w), x))
    reference = jax.jit(reference_apply)

    @jax.jit
    def reduce(got, want):
        return (jnp.max(jnp.abs(got - want), axis=-1).reshape(-1),
                jnp.isfinite(got).all() & jnp.isfinite(want).all())

    gaps, finite = [], True
    for i in picks:
        x = jnp.asarray(test_x[i:i + 1])
        got = program(w, x)
        with jax.default_matmul_precision("highest"):
            want = reference(w, x)
        if got.shape != want.shape:
            return None, (f"program gives {got.shape}, the reference "
                          f"{want.shape}")
        per_token, fin = reduce(got, want)
        gaps.append(np.asarray(per_token, np.float64))
        finite = finite and bool(fin)
    return np.concatenate(gaps), finite


def judge(gaps, finite):
    """The verdict on per-token gaps, each number beside its limit."""
    q50, q90, q99 = (float(q) for q in np.nan_to_num(
        np.quantile(gaps, [0.5, 0.9, 0.99]), nan=1e30))
    largest = float(np.nan_to_num(gaps.max(), nan=1e30))
    return {"ok": finite and q50 <= TOKEN_GAP_LIMIT, "finite": finite,
            "tokens": int(gaps.size), "token_gap_q90": q90,
            "token_gap_q99": q99,
            "compared": {"token_gap_median": [q50, TOKEN_GAP_LIMIT],
                         "largest_gap": [largest, None]}}


@functools.lru_cache(maxsize=1)
def _rows_fn(exp):
    def rows(state, data, row):
        t = state.round
        part = exp._participants(data, t)
        xs, ys = exp._gather_batches(data, t, part)
        xs, ys = exp._split_local_steps(data, xs, ys, part, t)
        grads = exp._compute_grads_impl(state, t, data=data)
        return grads[row], xs[row, 0], ys[row, 0]

    return jax.jit(rows)


def program_row(exp, row):
    """(g, x, y): wire row ``row`` of the round ``exp.state`` is at, from
    the timed program's client step, and the context (x, y) that silo
    trained on, gathered as that step gathers it."""
    return _rows_fn(exp)(exp.state, exp.data, row)


@functools.lru_cache(maxsize=4)
def _gaps_fn(sizes_json, dtype):
    s = json.loads(sizes_json)

    def loss_of(p, x, y, weights):
        logp = forward_pieces(p, x, s, selection_bias(s))
        taken = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
        return -jnp.sum(taken * weights) / jnp.sum(weights)

    def gaps(w, g, x, y, weights):
        want = jax.grad(loss_of)(pieces(w, s, dtype), x, y, weights)
        got = unpack(g, s)
        return {k: (layer_sums(jnp.square(got[k].astype(jnp.float32)
                                          - want[k].astype(jnp.float32)),
                               k),
                    layer_sums(jnp.square(want[k].astype(jnp.float32)), k))
                for k in want}

    def layer_sums(a, k):
        # a run's stacked piece: one sum a layer
        return jnp.sum(a.reshape(a.shape[0], -1), axis=1) if "." in k \
            else jnp.sum(a)[None]

    return jax.jit(gaps)


def grad_gaps(w, g, x, y, s, dtype=jnp.float32, weights=None):
    """{piece: (|g - grad|^2, |grad|^2)}: the row ``g`` against ``grad``,
    the gradient at the wire vector ``w`` of the reference's loss on the
    context (x, y) (:func:`loss`: the mean over its tokens), by piece of
    the wire and layer (``<layer>.<piece>``).  ``dtype`` and ``weights``
    (B, L) are the controls' handles: the reference in a lower precision,
    and the loss as a weighted mean over the tokens."""
    weights = jnp.ones(y.shape, jnp.float32) if weights is None else weights
    gaps = _gaps_fn(json.dumps(s, sort_keys=True), dtype)
    with jax.default_matmul_precision("highest"):
        out = jax.device_get(gaps(w, g, x, y, weights))
    per_piece = {}
    for k, (d, n) in out.items():
        first, _, piece = k.rpartition(".")
        for j, (dj, nj) in enumerate(zip(d, n)):
            name = f"{int(first) + j}.{piece}" if first else piece
            per_piece[name] = (float(dj), float(nj))
    return per_piece


def judge_grads(per_piece, s):
    """The verdict on :func:`grad_gaps`' sums, each number beside its
    limit; the largest pieces are named."""
    def rel(d, n):
        return math.sqrt(d / n) if n > 0 else (0.0 if d == 0 else math.inf)

    def routed(name):
        layer, _, piece = name.rpartition(".")
        return bool(layer) and int(layer) >= s["dense"] and piece in ROUTED

    gap = {k: float(np.nan_to_num(rel(d, n), nan=1e30))
           for k, (d, n) in per_piece.items()}
    whole = float(np.nan_to_num(rel(sum(d for d, _ in per_piece.values()),
                                    sum(n for _, n in per_piece.values())),
                                nan=1e30))
    piece = max((k for k in gap if not routed(k)), key=gap.get)
    expert = max((k for k in gap if routed(k)), key=gap.get)
    compared = {"grad_gap": [whole, GRAD_GAP_LIMIT],
                "grad_piece_gap": [gap[piece], GRAD_PIECE_GAP_LIMIT],
                "grad_routed_gap": [gap[expert], GRAD_ROUTED_GAP_LIMIT]}
    return {"ok": all(v <= limit for v, limit in compared.values()),
            "grad_piece": piece, "grad_routed_piece": expert,
            "compared": compared}


def sizes_for(exp):
    """The published sizes from this configuration's file for the
    published model; a test's tiny model gives its own."""
    return (SIZES if exp.cfg.model == CONFIG["model"]
            else sizes_from_model(exp.model.sizes))


def check(exp, weights, dataset, seed):
    s = sizes_for(exp)
    gaps, finite = token_gaps(exp, lambda w, x: forward(w, x, s), weights,
                              dataset, seed)
    if gaps is None:
        return {"ok": False, "why": finite}
    verdict = judge(gaps, finite)
    row = seed % int(exp.m)
    g, x, y = program_row(exp, row)
    grads = judge_grads(grad_gaps(exp.state.weights, g, x, y, s), s)
    del g
    verdict["compared"].update(grads.pop("compared"))
    verdict.update(forward_ok=verdict["ok"], train_ok=grads.pop("ok"),
                   grad_row=row, **grads)
    verdict["ok"] = verdict["forward_ok"] and verdict["train_ok"]
    return verdict


# --- work counts ----------------------------------------------------------------

def train_flops_per_sample(length=None, s=None):
    """Forward + backward FLOPs of one context (2 a multiply-add, backward
    twice the forward; recomputation not counted): the projections, the
    latent attention core over the causal pairs (q.k at nope + rope, p.v at
    v), the dense layer, the router, the held experts over the tokens even
    routing sends them, the shared experts, the head."""
    s = SIZES if s is None else s
    L = CONFIG["seq_len"] if length is None else length
    H, heads, nope, rope, v = (s["hidden"], s["heads"], s["nope"],
                               s["rope"], s["v"])
    per_token = H * (heads * (nope + rope) + s["rank"] + rope + heads * v)
    per_token += s["rank"] * heads * (nope + v)
    routed = L * s["top_k"] * len(s["held"]) / s["experts"]
    macs = 0.0
    for i in range(s["layers"]):
        macs += L * per_token + visible_pairs(L) * heads * (nope + rope + v)
        if i < s["dense"]:
            macs += L * 3 * H * s["dense_width"]
        else:
            macs += L * H * (s["experts"] + 3 * s["shared_width"])
            macs += routed * 3 * H * s["expert_width"]
    macs += L * H * s["vocab"]
    return 3 * 2 * macs


def shapes(exp):
    """Sizes of this configuration's own kernels for their ``ops_bytes``
    (perfbench/kernels/): a round's client steps."""
    s = SIZES
    contexts = int(exp.m) * int(exp.cfg.batch_size) * int(exp.cfg.local_steps)
    return {"mla": {
        "contexts": contexts, "length": int(exp.dataset.train_x.shape[1]),
        "layers": s["layers"], "heads": s["heads"], "nope": s["nope"],
        "rope": s["rope"], "v_dim": s["v"]}}
