"""The fused attention kernels (ops/flash_attention.py) in Pallas interpret
mode on the CPU: against the blocked path of ``models/sequence.py`` forward
and backward, at the window's edge, several kv groups a grid step against
one, the tiles and grid steps they compute, which path a model takes (the
cost log) and what a rematerialized layer keeps.  Their compile for the
chip is in tests/test_gather_layout.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu.models import sequence as S
from attacking_federate_learning_tpu.ops import flash_attention as F
from attacking_federate_learning_tpu.utils import costs

# a context of four tiles, heads of one lane width, 4 heads over 2 kv heads
L, D, TILE = 512, 128, 128
HEADS, KV = 4, 2


def operands(seed, batch=2):
    """q, k, v and an output cotangent, each value one a bf16 holds, so
    that rounding the products' operands moves nothing on either side."""
    ks = jax.random.split(jax.random.key(seed), 4)
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return (bf(jax.random.normal(ks[0], (batch, L, HEADS, D))),
            bf(jax.random.normal(ks[1], (batch, L, KV, D))),
            bf(jax.random.normal(ks[2], (batch, L, KV, D))),
            jax.random.normal(ks[3], (batch, L, HEADS * D)))


def rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def fused(q, k, v, window):
    return F.attention(q, k, v, window, TILE, interpret=True)


def blocked(q, k, v, window):
    return S.attention(q, k, v, window, TILE)       # the CPU's path


@pytest.mark.parametrize("window", [256, None], ids=["window", "global"])
@pytest.mark.parametrize("mxu,rtol", [(jnp.float32, 1e-5),
                                      (jnp.bfloat16, 2e-2)],
                         ids=["f32-products", "bf16-products"])
def test_the_kernels_are_the_blocked_path_forward_and_backward(
        window, mxu, rtol, monkeypatch):
    """With the products' operands in f32 the two differ by the order of
    f32 accumulation; in bf16, as shipped, by one rounding of p and dS."""
    monkeypatch.setattr(F, "MXU", mxu)
    q, k, v, w = operands(1)
    got, want = fused(q, k, v, window), blocked(q, k, v, window)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert rel(got, want) < rtol
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a, window) * w),
                      argnums=(0, 1, 2))(q, k, v) for f in (fused, blocked)]
    for a, b in zip(*grads):
        assert a.dtype == jnp.float32 and a.shape == b.shape
        assert rel(a, b) < rtol


@pytest.mark.parametrize("window,changes", [(256, False), (None, True)],
                         ids=["window", "global"])
def test_a_key_one_past_the_window_changes_nothing(window, changes):
    """The last query's first key out of sight lies in the key tile the
    window's edge masks (tile 1 for tile 3); one nearer is seen."""
    q, k, v, _ = operands(2, batch=1)
    query, back = L - 1, L - 1 - 256
    row = lambda k, v: fused(q, k, v, window)[0, query]
    base = row(k, v)
    moved = row(k.at[0, back].add(1.0), v.at[0, back].add(1.0))
    assert bool(jnp.array_equal(base, moved)) != changes
    near = row(k.at[0, back + 1].add(1.0), v.at[0, back + 1].add(1.0))
    assert not bool(jnp.array_equal(base, near))


def test_the_tiles_computed_at_the_cells_shape():
    """8,192 tokens in tiles of 512: a window layer of 4,096 computes 9
    key tiles a query tile (the first and last masked), 28.3 M pairs a
    head for 25.2 M visible; the global layer 35.7 M for 33.6 M."""
    assert F.back(8192, 4096, 512) == 8 and F.back(8192, None, 512) == 15
    assert F.computed_pairs(8192, 4096, 512) == 28_311_552
    assert F.computed_pairs(8192, None, 512) == 35_651_584
    assert F.visible_pairs(8192, 4096) == 25_167_872
    assert F.visible_pairs(8192, None) == 33_558_528
    assert F.fits(8192, 128, 512)
    assert not F.fits(24, 16, 8) and not F.fits(8192, 128, 384)


@pytest.mark.parametrize("heads,kv,qk,window", [
    (8, 8, 192, None), (8, 8, 192, 256), (8, 4, 128, None)],
    ids=["one-head-a-group", "one-head-a-group-window", "two-heads-a-group"])
def test_groups_a_step_together_are_one_group_a_step_bit_for_bit(
        heads, kv, qk, window, monkeypatch):
    """Several kv groups a grid step (latent attention's one query head a
    group, q.k 192 padded to 256 against values of 128; two heads a group)
    give every head the same products in the same key-tile order as one
    group a step: the output and dq, dk, dv are equal to the bit, with the
    products' operands in bf16 as shipped."""
    ks = jax.random.split(jax.random.key(heads * kv + qk), 4)
    q = jax.random.normal(ks[0], (1, L, heads, qk))
    k = jax.random.normal(ks[1], (1, L, kv, qk))
    v = jax.random.normal(ks[2], (1, L, kv, D))
    w = jax.random.normal(ks[3], (1, L, heads * D))
    R = heads // kv

    def run():
        grad = jax.grad(lambda *a: jnp.sum(fused(*a, window) * w),
                        argnums=(0, 1, 2))
        grids = {name: grid[1] for name, grid
                 in _kernels(jax.make_jaxpr(grad)(q, k, v).jaxpr)}
        return fused(q, k, v, window), grad(q, k, v), grids

    h = F.groups_per_step(R, kv)
    assert h > 1 and h * R <= F.HEADS_PER_STEP
    out, grads, grids = run()
    assert grids == dict.fromkeys(("flash_fwd", "flash_dq", "flash_dkv"),
                                  kv // h)
    monkeypatch.setattr(F, "HEADS_PER_STEP", R)     # one group a step
    out1, grads1, grids1 = run()
    assert set(grids1.values()) == {kv}
    np.testing.assert_array_equal(out, out1)
    for a, b in zip(grads, grads1):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("heads,groups,want", [
    (1, 32, 8), (7, 4, 1), (1, 12, 6), (2, 12, 4), (3, 4, 2), (1, 7, 7),
    (16, 2, 1), (1, 1, 1)])
def test_a_step_carries_the_most_groups_that_divide_within_the_cap(
        heads, groups, want, monkeypatch):
    """The largest divisor of the kv groups whose query heads stay within
    the cap of 8, and one group where even two exceed it (SmallThinker's 7
    heads a group, 4 groups)."""
    monkeypatch.setattr(F, "HEADS_PER_STEP", 8)
    assert F.groups_per_step(heads, groups) == want


def test_the_grid_steps_at_the_cells_shapes():
    """A layer and context of 8,192 in tiles of 512: latent attention's 32
    groups of one head take 32,768 steps one group a step, 32,768 / cap
    carried together; SmallThinker's 4 groups of 7 (one a step) 4,096 on
    the global layer and 2,304 on a window layer of 4,096."""
    cap = F.HEADS_PER_STEP
    assert F.groups_per_step(1, 32) == cap
    assert F.grid_steps(8192, None, 512, 1, 32) == 32_768 // cap
    assert F.grid_steps(8192, None, 512, 7, 4) == 4 * 4 * 16 * 16
    assert F.grid_steps(8192, 4096, 512, 7, 4) == 4 * 4 * 16 * 9


def _new_records(run):
    before = len(costs.attention_log())
    run()
    return costs.attention_log()[before:]


def test_the_published_sizes_take_the_kernels_and_the_tiny_model_does_not(
        monkeypatch):
    """Read from the cost log as the model is traced (no array is made):
    on a TPU the published sizes take the fused path in all four layers,
    the tiny model (heads of 16) the blocked one; off the TPU both are
    blocked."""
    def trace(s, length):
        model = S.make_sequence_model("t", s)
        params = jax.eval_shape(model.init, jax.random.key(0))
        x = jax.ShapeDtypeStruct((1, length), jnp.int32)
        return _new_records(lambda: jax.eval_shape(model.loss, params, x, x))

    monkeypatch.setattr(S, "_on_tpu", lambda: True)
    big = trace(S.SMALLTHINKER_EP8, 8192)
    assert [(r["path"], r["layers"], r["window"]) for r in big] == [
        ("fused", 1, None), ("fused", 3, 4096)]
    assert sum(r["layers"] for r in big) == 4
    assert big[0]["pairs_ratio"] == pytest.approx(35_651_584 / 33_558_528)
    assert big[1]["pairs_ratio"] == pytest.approx(28_311_552 / 25_167_872)
    tiny = trace(S.SEQ_TINY, 24)
    assert [r["path"] for r in tiny] == ["blocked", "blocked"]
    monkeypatch.setattr(S, "_on_tpu", lambda: False)
    assert {r["path"] for r in trace(S.SMALLTHINKER_EP8, 8192)} == {
        "blocked"}


# the tiny layer with heads of one lane width and a context of two tiles
WIDE = S.SEQ_TINY._replace(head_dim=128, query_block=128, window=128)


def _kernels(jaxpr):
    """(name, grid) of every ``pallas_call`` in a jaxpr and every jaxpr
    inside it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"],
                          eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernels(sub)
    return found


def _forward_kernels(jaxpr):
    """``flash_fwd`` calls in a jaxpr and every jaxpr inside it."""
    return sum(name == "flash_fwd" for name, _ in _kernels(jaxpr))


@pytest.mark.parametrize("saved,kernels", [
    (S.SAVED, 2), (("attention_out",), 4)],
    ids=["lse-kept", "lse-dropped"])
def test_a_rematerialized_layer_runs_the_forward_kernel_once(
        saved, kernels, monkeypatch):
    """The loss's gradient through both runs of layers (global, and the
    window scan) holds one forward kernel a run when the log-sum-exp is
    kept by name, and runs it again in the backward when it is not."""
    monkeypatch.setattr(S, "_on_tpu", lambda: True)
    monkeypatch.setattr(S, "SAVED", saved)
    model = S.make_sequence_model("t", WIDE)
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (1, 2 * WIDE.query_block), 0,
                           WIDE.vocab)
    jaxpr = jax.make_jaxpr(jax.grad(model.loss))(params, x, x).jaxpr
    assert _forward_kernels(jaxpr) == kernels


def test_the_fused_model_is_the_blocked_one(monkeypatch):
    """The tiny wide model's loss and gradient on both paths, products in
    f32 so that only accumulation order parts them; the compiled loss
    carries its attention records in the compile log."""
    monkeypatch.setattr(F, "MXU", jnp.float32)
    model = S.make_sequence_model("t", WIDE)
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (2, 2 * WIDE.query_block), 0,
                           WIDE.vocab)
    costs.install_cache_counters()
    got = {}
    for on in (False, True):
        monkeypatch.setattr(S, "_on_tpu", lambda on=on: on)
        # what earlier traces that compiled nothing left for the next one
        monkeypatch.setattr(costs._CacheCounters, "pending", [])
        log0 = len(costs.compile_log())
        got[on] = jax.jit(jax.value_and_grad(model.loss))(params, x, x)
        noted = [c["attention"] for c in costs.compile_log()[log0:]
                 if "attention" in c]
        assert [[r["path"] for r in n] for n in noted] == [
            ["fused" if on else "blocked"] * 2]
    (l0, g0), (l1, g1) = got[False], got[True]
    assert float(l1) == pytest.approx(float(l0), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("qk,dv,heads,kv", [
    (192, 128, 2, 2), (64, 128, 4, 2), (128, 256, 2, 1)],
    ids=["latent", "narrow-qk", "wide-v"])
@pytest.mark.parametrize("window", [256, None], ids=["window", "global"])
def test_a_qk_width_apart_from_the_value_width_is_the_blocked_path(
        qk, dv, heads, kv, window, monkeypatch):
    """q.k of a width that is no whole number of lanes (latent attention's
    192, zero-padded to 256 inside) or narrower than one, against values of
    another: forward and every gradient as the blocked path gives them,
    products in f32 so that only accumulation order parts the two."""
    monkeypatch.setattr(F, "MXU", jnp.float32)
    ks = jax.random.split(jax.random.key(qk + dv), 4)
    q = jax.random.normal(ks[0], (2, L, heads, qk))
    k = jax.random.normal(ks[1], (2, L, kv, qk))
    v = jax.random.normal(ks[2], (2, L, kv, dv))
    w = jax.random.normal(ks[3], (2, L, heads * dv))
    got, want = fused(q, k, v, window), blocked(q, k, v, window)
    assert got.shape == want.shape == (2, L, heads * dv)
    assert rel(got, want) < 1e-5
    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a, window) * w),
                      argnums=(0, 1, 2))(q, k, v) for f in (fused, blocked)]
    for a, b in zip(*grads):
        assert a.shape == b.shape and a.dtype == jnp.float32
        assert rel(a, b) < 1e-4


def test_the_latent_model_takes_the_kernels_at_its_widths(monkeypatch):
    """On a TPU the published latent-attention sizes take the fused path in
    both runs (the dense layer, the expert layers), and the record carries
    the q.k width apart from the value width."""
    monkeypatch.setattr(S, "_on_tpu", lambda: True)
    model = S.make_sequence_model("t", S.KANANA_EP16)
    params = jax.eval_shape(model.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    got = _new_records(lambda: jax.eval_shape(model.loss, params, x, x))
    assert [(r["path"], r["layers"], r["qk_dim"], r["v_dim"])
            for r in got] == [("fused", 1, 192, 128), ("fused", 4, 192, 128)]
    assert got[0]["pairs_ratio"] == pytest.approx(35_651_584 / 33_558_528)


# the tiny latent model with values of one lane width (q.k of 128 + 64)
WIDE_MLA = S.MLA_TINY._replace(head_dim=128, rope_dim=64, query_block=128)


def test_the_record_counts_the_groups_a_grid_step_carries(monkeypatch):
    """Traced for a TPU, the latent model's records carry its kv groups a
    step (the cap at the published 32 heads, all 4 of the tiny wide
    model's) and the kernels' grid steps a layer and context;
    SmallThinker's one group a step."""
    monkeypatch.setattr(S, "_on_tpu", lambda: True)

    def trace(s, length):
        model = S.make_sequence_model("t", s)
        params = jax.eval_shape(model.init, jax.random.key(0))
        x = jax.ShapeDtypeStruct((1, length), jnp.int32)
        return [(r["groups_per_step"], r["grid_steps"]) for r in
                _new_records(lambda: jax.eval_shape(model.loss, params, x,
                                                    x))]

    cap = F.HEADS_PER_STEP
    assert trace(S.KANANA_EP16, 8192) == [(cap, 32_768 // cap)] * 2
    assert trace(WIDE_MLA, 256) == [(4, 4 * 1 * 2 * 2)] * 2
    assert trace(S.SMALLTHINKER_EP8, 8192) == [(1, 4_096), (1, 2_304)]


def test_the_fused_latent_model_is_the_blocked_one(monkeypatch):
    """The tiny wide latent model's loss and gradient on both paths,
    products in f32."""
    monkeypatch.setattr(F, "MXU", jnp.float32)
    model = S.make_sequence_model("t", WIDE_MLA)
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (1, 2 * WIDE_MLA.query_block),
                           0, WIDE_MLA.vocab)
    got = {}
    for on in (False, True):
        monkeypatch.setattr(S, "_on_tpu", lambda on=on: on)
        got[on] = jax.jit(jax.value_and_grad(model.loss))(params, x, x)
    (l0, g0), (l1, g1) = got[False], got[True]
    assert float(l1) == pytest.approx(float(l0), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
