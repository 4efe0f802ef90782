"""The sequence client model (models/sequence.py) at a tiny size on the CPU:
against its plain reference (perfbench/configs/smallthinker_21b_a3b_ep8.py
at the same sizes), the shares of the expert layer against the uncut layer,
the two kinds of attention, the router's input, token data through the
engine as integers, the scanned client step against the vmapped one, and a
bf16 wire through the benchmark's Krum check."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import make_attacker
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core import client, engine
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.models import sequence as S
from attacking_federate_learning_tpu.models.base import get_model
from attacking_federate_learning_tpu.utils import costs
from attacking_federate_learning_tpu.utils.flatten import make_flattener

ref = importlib.import_module("perfbench.configs.smallthinker_21b_a3b_ep8")

# hidden 64, 4 / 2 heads x 16, 8 experts of width 32 with 2 a token, window
# 8, L = 24, layouts [0, 1, 1, 1]; every expert held, so that a share can be
# cut from it
WHOLE = S.SEQ_TINY._replace(experts_held=tuple(range(8)))
L = 24


def ref_sizes(s):
    return ref.sizes_from_model(s)


def build(s, seed=1):
    model = S.make_sequence_model("t", s)
    params = model.init(jax.random.key(seed))
    return model, params, make_flattener(params)


def tokens(seed, s, batch=2):
    return jax.random.randint(jax.random.key(seed), (batch, L), 0, s.vocab)


@pytest.mark.parametrize("held", [(0, 1), (3,), tuple(range(8))],
                         ids=["two", "one", "all"])
@pytest.mark.parametrize("seed", [1, 2])
def test_program_log_probs_match_the_reference(held, seed):
    s = S.SEQ_TINY._replace(experts_held=held)
    model, params, flat = build(s, seed)
    x = tokens(seed + 10, s)
    got = model.apply(params, x)
    want = ref.forward(flat.ravel(params), x, ref_sizes(s))
    assert got.shape == (2, L, s.vocab)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_loss_and_wire_row_match_grad_of_the_reference_loss(seed):
    s = S.SEQ_TINY
    model, params, flat = build(s, seed)
    w = flat.ravel(params)
    xs = tokens(seed, s, 3).reshape(3, 1, 1, L)
    ys = tokens(seed + 5, s, 3).reshape(3, 1, 1, L)
    assert float(model.loss(params, xs[0, 0], ys[0, 0])) == pytest.approx(
        float(ref.loss(w, xs[0, 0], ys[0, 0], ref_sizes(s))), rel=1e-6)
    want = jnp.stack([jax.grad(ref.loss)(w, xs[i, 0], ys[i, 0], ref_sizes(s))
                      for i in range(3)])
    update = client.make_client_update_fn(model, flat,
                                          scan_dtype=jnp.float32)
    wire = jax.jit(update)(w, xs, ys, 0.1, 0.1)
    np.testing.assert_allclose(wire, want, atol=1e-6)


def test_the_model_loss_is_nll_of_apply():
    from attacking_federate_learning_tpu.models.layers import nll_loss

    model, params, _ = build(S.SEQ_TINY)
    x, y = tokens(3, S.SEQ_TINY), tokens(4, S.SEQ_TINY)
    assert float(model.loss(params, x, y)) == pytest.approx(
        float(nll_loss(model.apply(params, x), y)), rel=1e-6)


@pytest.mark.parametrize("seed", [1, 2])
def test_the_eight_shares_add_up_to_the_uncut_layer(seed):
    """What every chip of the deployment adds for a token, summed over the
    chips, is the whole expert block's output: experts 0..7 one a share,
    against the layer that holds all eight and against the reference."""
    s = WHOLE
    _, params, flat = build(s, seed)
    p = jax.tree.map(lambda leaf: leaf[0], params["run1"])
    k1, k2 = jax.random.split(jax.random.key(seed))
    m = jax.random.normal(k1, (2 * L, s.hidden))
    r = jax.random.normal(k2, (2 * L, s.experts))
    whole = S.experts(p, m, r, s)
    shares = []
    for e in range(s.experts):
        pe = dict(p, **{n: p[n][e:e + 1] for n in ("gate", "up", "down")})
        shares.append(S.experts(pe, m, r, s._replace(experts_held=(e,))))
    np.testing.assert_allclose(sum(shares), whole, atol=1e-6)
    assert all(float(jnp.abs(share).max()) > 0 for share in shares)
    plain = ref.expert_block(
        {"1." + n: p[n] for n in ("gate", "up", "down")}, 1, m, r,
        ref_sizes(s))
    np.testing.assert_allclose(whole, plain, atol=1e-6)


@pytest.mark.parametrize("length,window,block", [
    (64, None, 4), (64, 8, 4), (64, 20, 4), (48, 16, 8), (32, 64, 8)])
def test_attention_in_key_spans_is_the_plain_attention(length, window, block):
    """Several classes of key spans (the tiny model has one): global, a
    window shorter than a class, one that is no whole number of blocks,
    one longer than the context."""
    spans = S.key_spans(length, window, block)
    assert sorted(s for _, starts in spans for s in starts) == list(
        range(0, length, block))
    keys = jax.random.split(jax.random.key(length), 3)
    q = jax.random.normal(keys[0], (2, length, 4, 16))
    k = jax.random.normal(keys[1], (2, length, 2, 16))
    v = jax.random.normal(keys[2], (2, length, 2, 16))
    got = S.attention(q, k, v, window, block)
    np.testing.assert_allclose(got, ref.attend(q, k, v, window), atol=1e-5)
    # and its gradient, through the blocks' rematerialization
    g1 = jax.grad(lambda *a: jnp.sum(S.attention(*a, window, block) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(ref.attend(*a, window) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("windowed,changes", [(True, False), (False, True)],
                         ids=["window", "global"])
def test_a_token_past_the_window_reaches_only_a_global_layer(windowed,
                                                             changes):
    s = S.SEQ_TINY
    _, params, _ = build(s)
    p = jax.tree.map(lambda leaf: leaf[0], params["run1"])
    h = jax.random.normal(jax.random.key(5), (1, L, s.hidden))
    layer = jax.jit(lambda h: S._layer(p, h, rope=windowed,
                                       windowed=windowed, s=s))
    query, back = L - 1, L - 1 - s.window      # the first key out of sight
    moved = h.at[0, back].add(1.0)
    a, b = layer(h)[0, query], layer(moved)[0, query]
    assert bool(jnp.array_equal(a, b)) != changes
    # one step nearer is inside the window of either kind
    near = layer(h.at[0, back + 1].add(1.0))[0, query]
    assert not bool(jnp.array_equal(a, near))


def test_the_router_reads_the_attention_blocks_input():
    s = S.SEQ_TINY
    model, params, flat = build(s)
    x, w = tokens(7, s), flat.ravel(params)
    got = model.apply(params, x)
    right = ref.forward(w, x, ref_sizes(s))
    wrong = ref.forward(w, x, ref_sizes(s), router_input="experts")
    assert float(jnp.abs(got - right).max()) < 2e-5
    assert float(jnp.abs(got - wrong).max()) > 1e-4


def test_a_wrong_window_shows_in_the_reference():
    s = S.SEQ_TINY
    model, params, flat = build(s)
    x, w = tokens(8, s), flat.ravel(params)
    wrong = ref.forward(w, x, ref_sizes(s), window=s.window - 1)
    assert float(jnp.abs(model.apply(params, x) - wrong).max()) > 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_scanned_client_step_is_the_vmapped_one_bit_for_bit(dtype):
    model = get_model("mnist_mlp")
    params = model.init(jax.random.key(0))
    flat = make_flattener(params)
    w = flat.ravel(params)
    xs = jax.random.normal(jax.random.key(1), (6, 1, 16, 784))
    ys = jax.random.randint(jax.random.key(2), (6, 1, 16), 0, 10)
    vmapped = jax.jit(client.make_client_update_fn(model, flat))(
        w, xs, ys, 0.1, 0.1)
    scanned = jax.jit(client.make_client_update_fn(
        model, flat, scan_dtype=dtype))(w, xs, ys, 0.1, 0.1)
    assert scanned.dtype == dtype
    assert bool(jnp.array_equal(scanned, vmapped.astype(dtype)))


@pytest.mark.parametrize("n,d,dtype,limit,fits", [
    (10_240, 79_510, "float32", 16.9e9, True),      # the MLP cell
    (256, 117_706, "float32", 16.9e9, True),        # the CNN cell
    (8, 370_547_200, "bfloat16", 16.9e9, False),    # the sequence cell
    (8, 370_547_200, "bfloat16", None, True),       # no limit reported
])
def test_the_cohort_is_scanned_only_where_it_does_not_fit(n, d, dtype, limit,
                                                          fits):
    assert client.cohort_fits(n, d, dtype, limit) is fits


def token_experiment(monkeypatch=None, **over):
    cfg = ExperimentConfig(**{**dict(
        dataset=C.SYNTH_TOKENS_TINY, seq_len=L, users_count=8, mal_prop=0.25,
        batch_size=1, defense="Krum", num_std=1.5, synth_train=32,
        synth_test=4, epochs=6, learning_rate=0.01), **over})
    if monkeypatch is not None:     # the chip's answer for a wide cohort
        monkeypatch.setattr(engine, "cohort_fits", lambda *a: False)
    ds = load_dataset(cfg.dataset, cfg.data_dir, 0, synth_train=32,
                      synth_test=4, seq_len=L)
    return FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                               dataset=ds)


def test_token_data_is_seeded_integer_and_learnable_in_form():
    a = load_dataset(C.SYNTH_TOKENS_TINY, seed=3, synth_train=16,
                     synth_test=4, seq_len=L)
    b = load_dataset(C.SYNTH_TOKENS_TINY, seed=3, synth_train=16,
                     synth_test=4, seq_len=L)
    assert a.train_x.dtype == np.int32 and a.train_y.dtype == np.int32
    assert a.train_x.shape == (16, L) and a.test_y.shape == (4, L)
    np.testing.assert_array_equal(a.train_x, b.train_x)
    assert 0 <= a.train_x.min() and a.train_x.max() < a.num_classes == 96
    # the label is the next token
    np.testing.assert_array_equal(a.train_x[:, 1:], a.train_y[:, :-1])
    # the low ids are the frequent ones (Zipf-like)
    assert (a.train_x < 8).mean() > (a.train_x >= 88).mean() * 2


def test_ids_stay_integers_through_placement_gather_and_eval():
    exp = token_experiment()
    assert exp._scan_clients        # a sequence model is never vmapped
    assert exp.data.train_x.dtype == jnp.int32
    assert exp.data.train_x.shape == (32, L)
    xs, ys = jax.jit(lambda t: exp._gather_batches(exp.data, t))(0)
    assert xs.dtype == jnp.int32 and ys.dtype == jnp.int32
    assert xs.shape == (8, 1, L) and ys.shape == (8, 1, L)
    host = np.asarray(exp.dataset.train_x)
    assert all((host == np.asarray(row)).all(axis=1).any()
               for row in xs[:, 0])
    loss, correct = exp.evaluate(exp.state.weights)
    # four test contexts, one a batch: the sum of their mean token losses
    # over four, near ln(96) at init; a count of tokens
    assert float(loss) == pytest.approx(np.log(96), rel=0.05)
    assert 0 <= int(correct) <= exp.dataset.test_y.size == 4 * L


def test_a_long_dataset_is_cropped_to_seq_len_and_a_short_one_refused():
    long = load_dataset(C.SYNTH_TOKENS_TINY, seed=0, synth_train=32,
                        synth_test=4, seq_len=2 * L)
    cfg = ExperimentConfig(dataset=C.SYNTH_TOKENS_TINY, seq_len=L,
                           users_count=4, batch_size=1, synth_train=32,
                           synth_test=4)
    exp = FederatedExperiment(cfg, dataset=long)
    assert exp.dataset.train_x.shape == (32, L)
    np.testing.assert_array_equal(exp.dataset.train_y, long.train_y[:, :L])
    with pytest.raises(ValueError, match="seq_len"):
        FederatedExperiment(dataclasses.replace(cfg, seq_len=4 * L),
                            dataset=long)
    with pytest.raises(ValueError, match="seq_len"):
        ExperimentConfig(dataset=C.SYNTH_MNIST, seq_len=L)


@pytest.mark.parametrize("grad_dtype", ["float32", "bfloat16"])
def test_a_scanned_span_runs_and_its_wire_passes_the_krum_check(
        grad_dtype, monkeypatch):
    """The round on its normal path with the cohort scanned into a wire of
    either dtype, and that wire through the benchmark's own
    ``wire_matrix_fn`` and ``defenses/krum.py:check`` (the f64 reference
    reads a bf16 matrix block by block as it reads an f32 one)."""
    from perfbench import run
    from perfbench.defenses import krum

    exp = token_experiment(monkeypatch, grad_dtype=grad_dtype)
    before = np.asarray(exp.state.weights).copy()
    exp.run_span(0, 3)
    after = np.asarray(exp.state.weights)
    assert np.isfinite(after).all() and not (after == before).all()
    G = run.wire_matrix_fn(exp)(exp.state)
    assert G.dtype == jnp.dtype(grad_dtype) and G.shape == (8, exp.flat.dim)
    assert bool(jnp.array_equal(G[0], G[1]))        # the two colluders
    agg = run.defense_fn(exp)(G)
    verdict = krum.check(G, exp.m, exp.m_mal, np.asarray(agg), seed=3)
    assert verdict["ok"], verdict
    assert not krum.check(G, exp.m, exp.m_mal,
                          np.asarray(G[2]) * 2, seed=3)["ok"]


def test_bf16_wire_statistics_come_out_f32():
    from attacking_federate_learning_tpu.attacks.base import cohort_stats

    rows = jax.random.normal(jax.random.key(0), (2, 4096)).astype(
        jnp.bfloat16)
    mean, std = cohort_stats(rows)
    assert mean.dtype == std.dtype == jnp.float32
    want = np.asarray(rows, np.float64)
    np.testing.assert_allclose(mean, want.mean(0), atol=1e-6)
    np.testing.assert_allclose(std, want.std(0), atol=1e-6)


def test_a_wide_gram_sums_column_blocks_to_the_same_distances():
    from attacking_federate_learning_tpu.ops import distances

    G = jax.random.normal(jax.random.key(0), (8, 5000)).astype(jnp.bfloat16)
    whole = distances.cross_sq_distances(G, G)
    blocked = distances._wide_sq_distances(G, width=1024)   # 4 blocks + tail
    np.testing.assert_allclose(blocked, whole, rtol=1e-5, atol=0.1)
    want = ((np.asarray(G, np.float64)[:, None]
             - np.asarray(G, np.float64)[None]) ** 2).sum(-1)
    np.testing.assert_allclose(blocked, want, rtol=1e-4, atol=0.1)


def _span_text(exp):
    return exp._fused_span.lower(
        exp.data, exp.state, jnp.asarray(0, jnp.int32),
        jnp.asarray(2, jnp.int32)).compile().as_text()


def test_scopes_are_metadata_only_and_book_forward_and_backward(
        monkeypatch):
    """``attention`` and ``experts`` change no instruction (one
    ``hlo_fingerprint`` with scopes on and off), and the benchmark's
    booking finds them as plain path components on forward,
    rematerialized and backward operations alike."""
    from conftest import metadata_in_cache_key
    from perfbench.tracereduce import hlo_scope_paths, innermost

    def text(enabled):
        prev = costs.set_stage_scopes(enabled)
        try:
            return _span_text(token_experiment(monkeypatch))
        finally:
            costs.set_stage_scopes(prev)

    with metadata_in_cache_key():
        on, off = text(True), text(False)
    assert costs.hlo_fingerprint(on) == costs.hlo_fingerprint(off)
    scopes = frozenset(costs.STAGES) | frozenset(costs.SUBSTAGES)
    booked = {}
    for path in hlo_scope_paths(on).values():
        scope = innermost(path, scopes)
        if scope in ("attention", "experts"):
            booked.setdefault(scope, set()).add(
                "backward" if "transpose(" in path else "forward")
    assert booked == {"attention": {"forward", "backward"},
                      "experts": {"forward", "backward"}}, booked
    assert "/attention/" not in off and "/experts/" not in off


def test_the_published_share_has_the_wire_its_configuration_states():
    """Shapes only (nothing of 370 M parameters is made here): the
    program's pieces, in the reference's order, add up to ``WIRE_DIM``."""
    model = get_model("smallthinker_21b_a3b_ep8")
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert make_flattener(shapes).dim == ref.WIRE_DIM == 370_547_200
    assert [tuple(leaf.shape) for leaf in jax.tree.leaves(shapes)] == [
        shape for _, shape in ref.shapes_of(ref.SIZES)]
    assert model.sizes.runs == [(0, 0, 1), (1, 1, 3)]
