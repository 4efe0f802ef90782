"""The latent-attention client model (models/sequence.py, second form: the
DeepSeek-V3 layer of kanana_2_30b_a3b_ep16) at a tiny size on the CPU:
against its plain reference (perfbench/configs/kanana_2_30b_a3b_ep16.py at
the same sizes) forward and backward, the sigmoid router's selection and
weights, the shares of the expert layer against the uncut layer, the two
new scopes, the token data over the model's slice, and a round on the normal
path."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import make_attacker
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core import client
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.models import sequence as S
from attacking_federate_learning_tpu.models.base import get_model
from attacking_federate_learning_tpu.utils import costs
from attacking_federate_learning_tpu.utils.flatten import make_flattener

ref = importlib.import_module("perfbench.configs.kanana_2_30b_a3b_ep16")

# hidden 64, 4 heads of 16 + 8 with positions, a latent of 32, one dense
# layer (96 wide) then three expert layers: 8 experts of 32 with 2 a token,
# shared experts of 32, L = 24
L = 24


def build(s, seed=1):
    model = S.make_sequence_model("t", s)
    params = model.init(jax.random.key(seed))
    return model, params, make_flattener(params)


def tokens(seed, s, batch=2):
    return jax.random.randint(jax.random.key(seed), (batch, L), 0, s.vocab)


@pytest.mark.parametrize("held", [(0, 1), (5,), tuple(range(8))],
                         ids=["two", "one", "all"])
@pytest.mark.parametrize("seed", [1, 2])
def test_program_log_probs_match_the_reference(held, seed):
    s = S.MLA_TINY._replace(experts_held=held)
    model, params, flat = build(s, seed)
    x = tokens(seed + 10, s)
    got = model.apply(params, x)
    want = ref.forward(flat.ravel(params), x, ref.sizes_from_model(s))
    assert got.shape == (2, L, s.vocab)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_loss_and_wire_row_match_grad_of_the_reference_loss(seed):
    """The MLA block's gradients (latent, rotation, the shared key head)
    with the rest of the layer: the wire rows of the scanned client step
    against ``jax.grad`` of the reference's loss."""
    s = S.MLA_TINY
    model, params, flat = build(s, seed)
    w = flat.ravel(params)
    sizes = ref.sizes_from_model(s)
    xs = tokens(seed, s, 3).reshape(3, 1, 1, L)
    ys = tokens(seed + 5, s, 3).reshape(3, 1, 1, L)
    assert float(model.loss(params, xs[0, 0], ys[0, 0])) == pytest.approx(
        float(ref.loss(w, xs[0, 0], ys[0, 0], sizes)), rel=1e-6)
    want = jnp.stack([jax.grad(ref.loss)(w, xs[i, 0], ys[i, 0], sizes)
                      for i in range(3)])
    update = client.make_client_update_fn(model, flat,
                                          scan_dtype=jnp.float32)
    wire = jax.jit(update)(w, xs, ys, 0.1, 0.1)
    np.testing.assert_allclose(wire, want, atol=1e-6)


def test_the_key_part_with_positions_is_one_head_every_query_head_sees():
    """k_r is one head, the same in every head's key: moving the part of
    W_kva that makes it moves every head's keys with positions and leaves
    the parts without them."""
    s = S.MLA_TINY
    _, params, _ = build(s)
    p = jax.tree.map(lambda leaf: leaf[0], params["run1"])
    a = jax.random.normal(jax.random.key(3), (1, L, s.hidden))
    q, k, v = S.latent_qkv(p, a, True, s)
    assert q.shape == k.shape == (1, L, s.heads, s.head_dim + s.rope_dim)
    assert v.shape == (1, L, s.heads, s.head_dim)
    rope = k[..., s.head_dim:]
    assert bool(jnp.all(rope == rope[:, :, :1]))
    bumped = dict(p, kv_a=p["kv_a"].at[:, s.kv_rank:].add(1.0))
    _, k2, _ = S.latent_qkv(bumped, a, True, s)
    moved = k2[..., s.head_dim:] != rope
    assert bool(jnp.all(jnp.any(moved, axis=(0, 1, 3))))
    assert bool(jnp.array_equal(k2[..., :s.head_dim], k[..., :s.head_dim]))


def test_interleaved_rope_turns_adjacent_pairs():
    """The program's de-interleave + rotate-half gives the scores of the
    reference's rotation of adjacent pairs (the layouts differ by one
    permutation that q and k share)."""
    R = 8
    q, k = jax.random.normal(jax.random.key(0), (2, 1, L, 3, R))
    cos, sin = S.rope_tables(L, R, 1e6)
    rq = S.rotate(S.deinterleave(q), cos, sin)
    rk = S.rotate(S.deinterleave(k), cos, sin)
    got = jnp.einsum("bqhd,bkhd->bhqk", rq, rk)
    want = jnp.einsum("bqhd,bkhd->bhqk", ref.rope_pairs(q, 1e6),
                      ref.rope_pairs(k, 1e6))
    np.testing.assert_allclose(got, want, atol=1e-5)
    # position 0 is not turned, and a pair keeps its length
    np.testing.assert_allclose(ref.rope_pairs(q, 1e6)[:, 0], q[:, 0])
    assert not np.allclose(ref.rope_pairs(q, 1e6)[:, 1:], q[:, 1:])


@pytest.mark.parametrize("seed", [0, 1])
def test_the_bias_selects_and_the_sigmoid_alone_weighs(seed):
    """The top k of sigmoid(r) + b are selected; the weights are those
    sigmoids over their sum times routed_scale, whatever b is; with b the
    selection differs from the one without it for some tokens."""
    s = S.MLA_TINY._replace(experts_held=tuple(range(8)))
    r = jax.random.normal(jax.random.key(seed), (256, s.experts))
    bias = jnp.asarray(S.selection_bias(s)[0])
    w, local = S.route(r, s, bias)
    sig = np.asarray(jax.nn.sigmoid(r), np.float64)
    want_idx = np.argsort(-(sig + np.asarray(bias)), axis=1)[:, :s.top_k]
    assert (np.sort(np.asarray(local), 1) == np.sort(want_idx, 1)).all()
    top = np.take_along_axis(sig, np.asarray(local), 1)
    np.testing.assert_allclose(
        w, top / top.sum(1, keepdims=True) * s.routed_scale, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(1), s.routed_scale,
                               rtol=1e-6)
    _, plain = S.route(r, s, jnp.zeros_like(bias))
    moved = (np.sort(np.asarray(plain), 1) != np.sort(np.asarray(local), 1))
    assert 0 < moved.any(1).mean() < 1
    # an absent expert's slot carries the index past the held ones
    _, part = S.route(r, s._replace(experts_held=(0, 1)), bias)
    assert set(np.unique(np.asarray(part))) <= {0, 1, 2}


def test_the_bias_is_one_fixed_buffer_outside_the_wire():
    s = S.MLA_TINY
    b = S.selection_bias(s)
    assert b.shape == (s.layers - s.dense_layers, s.experts)
    assert b.dtype == np.float32 and np.abs(b).min() > 0
    np.testing.assert_array_equal(b, S.selection_bias(s))
    np.testing.assert_array_equal(
        b, ref.selection_bias(ref.sizes_from_model(s)).astype(np.float32))
    shapes = jax.eval_shape(get_model("mla_tiny").init, jax.random.key(0))
    assert "bias" not in str(jax.tree_util.tree_structure(shapes))


@pytest.mark.parametrize("seed", [1, 2])
def test_the_sixteen_shares_add_up_to_the_uncut_layer(seed):
    """Sixteen chips each holding one of 16 experts: what each adds for a
    token, summed over the chips, with the shared experts (which every chip
    computes alike) counted once, is the uncut reference's expert block."""
    s = S.MLA_TINY._replace(experts=16, experts_held=tuple(range(16)))
    _, params, _ = build(s, seed)
    p = jax.tree.map(lambda leaf: leaf[0], params["run1"])
    m = jax.random.normal(jax.random.key(seed), (2 * L, s.hidden))
    r = m @ p["router"]
    bias = jnp.asarray(S.selection_bias(s)[0])
    shares = []
    for e in range(s.experts):
        pe = dict(p, **{n: p[n][e:e + 1] for n in ("gate", "up", "down")})
        shares.append(S.experts(pe, m, r, s._replace(experts_held=(e,)),
                                bias))
    shared = S.glu(m, p["shared_gate"], p["shared_up"], p["shared_down"],
                   s.activation)
    plain = ref.expert_block(p, m[None], bias, ref.sizes_from_model(s))[0]
    np.testing.assert_allclose(sum(shares) + shared, plain, atol=1e-5)
    assert sum(float(jnp.abs(share).max()) > 0 for share in shares) >= 8


def test_a_dense_layer_comes_first_and_routes_nothing():
    s = S.MLA_TINY
    assert s.runs == [(1, 0, 1, 1), (1, 0, 0, 3)]
    _, params, _ = build(s)
    assert "router" not in params["run0"]
    assert params["run0"]["gate"].shape == (1, s.hidden, s.dense_width)
    assert params["run1"]["shared_gate"].shape == (3, s.hidden,
                                                   s.shared_width)


def test_the_published_share_has_the_wire_its_configuration_states():
    """Shapes only (nothing of 425 M parameters is made here): the
    program's pieces, in the reference's order, add up to ``WIRE_DIM``."""
    model = get_model("kanana_2_30b_a3b_ep16")
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert make_flattener(shapes).dim == ref.WIRE_DIM == 424_960_512
    assert [tuple(leaf.shape) for leaf in jax.tree.leaves(shapes)] == [
        shape for _, shape in ref.shapes_of(ref.SIZES)]
    assert ref.sizes_from_model(model.sizes) == ref.SIZES


def test_synth_tokens_draws_from_the_models_slice():
    cfg = ExperimentConfig(dataset=C.SYNTH_TOKENS_16032,
                           model="kanana_2_30b_a3b_ep16")
    assert C.token_vocab(cfg.dataset) == 16_032
    for other in (C.SYNTH_TOKENS, C.SYNTH_TOKENS_TINY):
        with pytest.raises(ValueError, match="expects tokens_16032"):
            ExperimentConfig(dataset=other, model="kanana_2_30b_a3b_ep16")
    with pytest.raises(ValueError, match="expects tokens_18992"):
        ExperimentConfig(dataset=C.SYNTH_TOKENS_16032,
                         model="smallthinker_21b_a3b_ep8")
    ds = load_dataset(cfg.dataset, seed=0, synth_train=2, synth_test=1,
                      seq_len=4096)
    assert ds.num_classes == 16_032 and ds.train_x.shape == (2, 4096)
    assert ds.train_x.max() < 16_032 and ds.train_x.max() > 12_000


def test_smallthinkers_tokens_are_byte_identical():
    """SYNTH_TOKENS at its default length for SmallThinker's config: the
    digest of the arrays the tree before the second slice drew."""
    import hashlib

    ds = load_dataset(C.SYNTH_TOKENS, seed=0, synth_train=2, synth_test=1)
    digest = hashlib.sha256(b"".join(
        np.ascontiguousarray(a).tobytes() for a in
        (ds.train_x, ds.train_y, ds.test_x, ds.test_y))).hexdigest()
    assert ds.num_classes == 18_992 and ds.train_x.shape == (2, 8192)
    assert digest == ("9b0d5cb973938f07d5d111ecb9ee20b6"
                      "45028e54e17ca87564d0d1792bbf6e0c")


def mla_experiment(**over):
    cfg = ExperimentConfig(**{**dict(
        dataset=C.SYNTH_TOKENS_TINY, model="mla_tiny", seq_len=L,
        users_count=6, mal_prop=0.2, batch_size=1, defense="Krum",
        num_std=1.5, synth_train=24, synth_test=4, epochs=6,
        learning_rate=0.01, grad_dtype="bfloat16"), **over})
    ds = load_dataset(cfg.dataset, cfg.data_dir, 0, synth_train=24,
                      synth_test=4, seq_len=cfg.seq_len)
    return FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                               dataset=ds)


def test_a_round_runs_on_the_normal_path():
    """cli's configuration through the engine: the scanned client step
    into a bf16 wire, Krum against ALIE, the server step."""
    exp = mla_experiment()
    assert exp._scan_clients and exp.m == 6 and exp.m_mal == 1
    before = np.asarray(exp.state.weights).copy()
    exp.run_span(0, 3)
    after = np.asarray(exp.state.weights)
    assert np.isfinite(after).all() and not (after == before).all()
    loss, _ = exp.evaluate(exp.state.weights)
    assert float(loss) == pytest.approx(np.log(96), rel=0.1)


def test_the_latent_and_shared_experts_scopes_book_forward_and_backward():
    """``latent`` and ``shared_experts`` change no instruction (one
    ``hlo_fingerprint`` with scopes on and off), and the benchmark's booking
    finds each as a plain path component, forward and backward alike, not
    rendered as ``jvp(name)``."""
    from conftest import metadata_in_cache_key
    from perfbench.tracereduce import hlo_scope_paths, innermost

    def text(enabled):
        prev = costs.set_stage_scopes(enabled)
        try:
            exp = mla_experiment()
            return exp._fused_span.lower(
                exp.data, exp.state, jnp.asarray(0, jnp.int32),
                jnp.asarray(2, jnp.int32)).compile().as_text()
        finally:
            costs.set_stage_scopes(prev)

    with metadata_in_cache_key():
        on, off = text(True), text(False)
    assert costs.hlo_fingerprint(on) == costs.hlo_fingerprint(off)
    scopes = frozenset(costs.STAGES) | frozenset(costs.SUBSTAGES)
    booked = {}
    for scope_path in hlo_scope_paths(on).values():
        scope = innermost(scope_path, scopes)
        booked.setdefault(scope, set()).add(
            "backward" if "transpose(" in scope_path else "forward")
    for scope in ("latent", "shared_experts", "attention", "experts"):
        assert booked.get(scope) == {"forward", "backward"}, (scope, booked)
    assert "/latent/" not in off and "/shared_experts/" not in off
