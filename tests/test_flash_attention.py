"""The fused attention kernels (ops/flash_attention.py) in Pallas interpret
mode on the CPU: against the blocked path of ``models/sequence.py`` forward
and backward, at the window's edge, the tiles they compute, which path a
model takes (the cost log) and what a rematerialized layer keeps.  Their
compile for the chip is in tests/test_gather_layout.py."""

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


def _forward_kernels(jaxpr):
    """``flash_fwd`` calls in a jaxpr and every jaxpr inside it."""
    count = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == "flash_fwd"):
            count += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _forward_kernels(sub)
    return count


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
