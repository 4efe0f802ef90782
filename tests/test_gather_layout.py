"""The batch gather reads whole sample rows (PERF.md section 6, PR 26).

The device-resident training set is stored (N, F), one sample a row, and
``FederatedExperiment._gather_batches`` restores the sample shape after
the gather.  That is a storage change only: for every round t, cohort
row i and position j the batch must still be
``train_x[shards[i, (t*kB + j) % L]]`` bit for bit — whatever the
partition, the participation, the number of local steps, the shard
length against kB, and the engine (flat cohort or hierarchical
megabatch).  One parametrised test per property; the reference is host
NumPy on the dataset as loaded, never the engine's own arrays.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import make_attacker
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.data.partition import (
    make_shards, round_batch_indices
)
from attacking_federate_learning_tpu.utils import costs

_DS = {}


def _dataset(name, n_train):
    if (name, n_train) not in _DS:
        _DS[name, n_train] = load_dataset(name, seed=0, synth_train=n_train,
                                          synth_test=32)
    return _DS[name, n_train]


def _experiment(n_train=96, dataset=C.SYNTH_MNIST, **kw):
    kw.setdefault("users_count", 16)
    kw.setdefault("mal_prop", 0.25)
    kw.setdefault("batch_size", 8)
    kw.setdefault("epochs", 4)
    kw.setdefault("defense", "Krum")
    cfg = ExperimentConfig(dataset=dataset, synth_train=n_train,
                           synth_test=32, **kw)
    ds = _dataset(dataset, n_train)
    exp = FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                              dataset=ds)
    return exp, ds


def _spy(exp):
    """Record, from inside the traced round program, what every call of
    the gather was asked for and returned, and what the client step was
    then fed.  Both are looked up on the instance at trace time, so the
    flat engine and the hierarchical builder's closure both see these."""
    seen = {"gather": [], "client": []}
    gather, update = exp._gather_batches, exp._client_update

    def keep(kind):
        return lambda *a: seen[kind].append([np.asarray(v) for v in a])

    def spy_gather(data, t, participants=None):
        xs, ys = gather(data, t, participants)
        ids = (jnp.arange(exp.n) if participants is None else participants)
        jax.debug.callback(keep("gather"), t, ids, xs, ys)
        return xs, ys

    def spy_update(w, xs, ys, lr_train, lr_report):
        jax.debug.callback(keep("client"), xs, ys)
        return update(w, xs, ys, lr_train, lr_report)

    exp._gather_batches, exp._client_update = spy_gather, spy_update
    return seen


def _reference(exp, ds, t, ids):
    """Host NumPy: the rows of the set as loaded, (len(ids), kB, ...)."""
    cfg = exp.cfg
    shards = make_shards(cfg.partition, ds.train_y, cfg.users_count,
                         cfg.seed, cfg.dirichlet_alpha)
    kB = cfg.batch_size * cfg.local_steps
    idx = np.asarray(round_batch_indices(shards[ids], int(t), kB))
    offs = (int(t) * kB + np.arange(kB)) % shards.shape[1]
    np.testing.assert_array_equal(idx, shards[ids][:, offs])
    return ds.train_x[idx], ds.train_y[idx]


def _assert_styled(exp, got, rows, ids):
    """What the client step was fed: the rows themselves, or under
    'femnist_style' row i through client ids[i]'s a*x + b (to an ulp:
    the compiled program may fuse the multiply-add; a row under another
    client's style is off by far more)."""
    if exp.data.style is None:
        np.testing.assert_array_equal(got, rows)
    else:
        want = np.asarray(exp._apply_style(exp.data, jnp.asarray(rows),
                                           jnp.asarray(ids)))
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


# (n_train, n, B): L = ceil(n_train / n) against kB = local_steps * B.
SHAPES = {
    "L6_lt_kB": (96, 16, 8),          # the MLP cell's regime: L=6 < kB
    "L7_no_divisor": (100, 16, 8),    # L=7 divides neither 8 nor 24
    "L64_gt_kB": (1024, 16, 8),       # the CNN cell's regime: L > kB
}


@pytest.mark.parametrize("engine", ["flat", "hierarchical"])
@pytest.mark.parametrize("local_steps", [1, 3])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("partition", ["iid", "dirichlet", "femnist_style"])
def test_batch_is_the_sets_rows_bit_for_bit(partition, shape, local_steps,
                                            engine):
    n_train, n, B = SHAPES[shape]
    kw = dict(users_count=n, batch_size=B, partition=partition,
              local_steps=local_steps)
    if engine == "hierarchical":
        kw.update(aggregation="hierarchical", megabatch=4)
    exp, ds = _experiment(n_train, **kw)
    seen = _spy(exp)
    rounds = [0, 1, 5]          # 5*kB >= 40 wraps every shard length here
    for t in rounds:
        exp.run_round(t)
    jax.effects_barrier()
    per_round = n // 4 if engine == "hierarchical" else 1
    assert len(seen["gather"]) == len(seen["client"]) == (
        len(rounds) * per_round)
    rows_seen = {t: [] for t in rounds}
    for (t, ids, xs, ys), (cx, cy) in zip(seen["gather"], seen["client"]):
        ref_x, ref_y = _reference(exp, ds, t, ids)
        assert xs.dtype == np.float32 and xs.shape == ref_x.shape
        np.testing.assert_array_equal(xs, ref_x)
        np.testing.assert_array_equal(ys, ref_y)
        # ... and style + the local-step split keep every row in place.
        m = len(ids)
        _assert_styled(exp, cx, ref_x.reshape(
            (m, local_steps, B) + ref_x.shape[2:]), ids)
        np.testing.assert_array_equal(
            cy, ref_y.reshape(m, local_steps, B))
        rows_seen[int(t)].extend(ids.tolist())
    for t in rounds:            # every client delivered once a round
        assert sorted(rows_seen[t]) == list(range(n))


@pytest.mark.parametrize("partition", ["iid", "dirichlet", "femnist_style"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_partial_participation_gathers_the_cohorts_rows(shape, partition):
    n_train, n, B = SHAPES[shape]
    exp, ds = _experiment(n_train, users_count=n, batch_size=B,
                          partition=partition, participation=0.5)
    seen = _spy(exp)
    for t in (0, 3, 7):
        exp.run_round(t)
    jax.effects_barrier()
    assert len(seen["gather"]) == 3
    for (t, ids, xs, ys), (cx, _) in zip(seen["gather"], seen["client"]):
        np.testing.assert_array_equal(
            ids, np.asarray(exp._participants(exp.data, jnp.asarray(t))))
        assert len(ids) == exp.m < n
        ref_x, ref_y = _reference(exp, ds, t, ids)
        np.testing.assert_array_equal(xs, ref_x)
        np.testing.assert_array_equal(ys, ref_y)
        _assert_styled(exp, cx[:, 0], ref_x, ids)


def test_image_samples_get_their_shape_back_after_the_gather():
    """(N, C, H, W) sets: the augmentation and the convolution need the
    sample shape, which the (N, F) storage restores after the gather."""
    exp, ds = _experiment(64, dataset=C.SYNTH_CIFAR10, users_count=4,
                          batch_size=4, model="cifar10_cnn",
                          data_augment=True)
    assert ds.train_x.shape[1:] == (3, 32, 32)
    xs, ys = exp._gather_batches(exp.data, jnp.asarray(2, jnp.int32))
    ref_x, ref_y = _reference(exp, ds, 2, np.arange(4))
    assert xs.shape == (4, 4, 3, 32, 32)
    np.testing.assert_array_equal(np.asarray(xs), ref_x)
    np.testing.assert_array_equal(np.asarray(ys), ref_y)
    exp.run_round(0)            # reflect_crop_flip accepts what it gets
    assert np.isfinite(np.asarray(exp.state.weights)).all()


def _parent_gather(exp, ds):
    """The formulation before PR 26: the set placed (N, ...) as loaded,
    gathered in its sample shape."""
    x4d, y = jnp.asarray(ds.train_x), jnp.asarray(ds.train_y)

    def gather(data, t, participants=None):
        shards = (data.shards if participants is None
                  else data.shards[participants])
        idx = round_batch_indices(
            shards, t, exp.cfg.batch_size * exp.cfg.local_steps)
        return x4d[idx], y[idx]

    return gather


@pytest.mark.parametrize("kw", [
    dict(),
    dict(participation=0.5, partition="femnist_style"),
    dict(aggregation="hierarchical", megabatch=4, local_steps=3),
], ids=["flat", "flat_partial_styled", "hierarchical_k3"])
def test_three_rounds_equal_the_parent_formulation(kw):
    """Krum + ALIE, three rounds as one span: final weights equal those
    of the same run gathering from the (N, ...) set."""
    weights = []
    for parent in (False, True):
        exp, ds = _experiment(100, **kw)
        if parent:
            exp._gather_batches = _parent_gather(exp, ds)
        exp.run_span(0, 3)
        weights.append(np.asarray(exp.state.weights))
    assert np.isfinite(weights[0]).all()
    np.testing.assert_array_equal(weights[0], weights[1])


@pytest.mark.parametrize("partition", ["iid", "dirichlet", "femnist_style"])
def test_one_f32_row_store_on_the_device(partition):
    """Every partition gets the same storage — f32 rows, (N, F), feature
    axis minor — and it is the only copy of the set on the device: one
    leaf of the round programs' operand pytree, no attribute beside it."""
    exp, ds = _experiment(96, partition=partition)
    n_train, feat = len(ds.train_x), int(np.prod(ds.train_x.shape[1:]))
    assert exp.data.train_x.shape == (n_train, feat)
    assert exp.data.train_x.dtype == jnp.float32 == ds.train_x.dtype
    np.testing.assert_array_equal(np.asarray(exp.data.train_x),
                                  ds.train_x.reshape(n_train, feat))
    held = [k for k, v in vars(exp).items()
            for leaf in jax.tree.leaves(v)
            if isinstance(leaf, jax.Array) and leaf.size == n_train * feat]
    assert held == ["data"]


# --- what the chip's compiler makes of it (no chip needed) -----------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """An executable for a described chip cannot be read back from the
    persistent cache without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()


def _gather_result_layouts(hlo_text):
    """(shape, minor_to_major) of every fusion / instruction, in any
    computation (the span's gather sits in the round loop's body), that
    gathers floating-point rows."""
    gathering = set(re.findall(
        r"^%?([\w.\-]+) \([^\n]*\{\n(?:(?!^\}).*\n)*?"
        r"[^\n]*= (?:f32|bf16)\[[\d,]*\]\S* gather\(", hlo_text, re.M))
    out = []
    for m in re.finditer(
            r"= (?:f32|bf16)\[([\d,]*)\]\{([\d,]*)[^ ]* "
            r"(?:fusion\([^\n]*calls=%([\w.\-]+)|gather\()", hlo_text):
        if m.group(3) in gathering:
            out.append(([int(v) for v in m.group(1).split(",")],
                        [int(v) for v in m.group(2).split(",")]))
    return out


def _described(exp, one_chip, n_train):
    """``exp``'s round operands and state as shapes on the described
    chip, the set and the shards at the MLP cell's sizes.  Every array is
    given the layout a device buffer has at run time — row-major, which
    is what ``jnp.asarray`` places — because for a described chip the
    compiler would otherwise choose the parameters' layouts itself (it
    takes the set column-major and pays a transposing copy a span)."""
    from jax.experimental.layout import Format, Layout

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=Format(
            Layout(tuple(range(len(shape)))), one_chip))

    n, feat = exp.n, exp.data.train_x.shape[1]
    data = exp.data._replace(
        train_x=arg((n_train, feat), jnp.float32),
        train_y=arg((n_train,), jnp.int32),
        shards=arg((n, -(-n_train // n)), jnp.int32))
    state = jax.tree.map(lambda a: arg(a.shape, a.dtype), exp.state)
    return arg, data, state


def _assert_whole_rows(text, feat):
    layouts = _gather_result_layouts(text)
    assert layouts, "no floating-point gather in the compiled program"
    for shape, minor_to_major in layouts:
        assert shape[-1] == feat, (shape, minor_to_major)
        assert minor_to_major[0] == len(shape) - 1, (shape, minor_to_major)


def test_v5e_compiles_the_gather_to_whole_rows(one_chip):
    """The v5e compiler, given the round's gather + client step at the
    MLP cell's widths (n cut to 1,024) with the set as an ARGUMENT in the
    runtime's row-major layout, writes the gathered batch with the
    FEATURE axis minor.  Gathered in sample shape it wrote
    f32[n*B,28,28]{0,2,1} — the sample axis minor, one element at a
    time (ledger, PR 25: fusion.71, 2.1 s of a 13.7 s window)."""
    n, B, n_train = 1024, 32, 60000
    exp, _ = _experiment(2 * n, users_count=n, batch_size=B, mal_prop=0.0,
                         defense="NoDefense")
    arg, data, state = _described(exp, one_chip, n_train)

    def deliver(data, state, t):
        return exp._compute_grads_impl(state, t, data=data)

    with _no_persistent_cache():
        text = jax.jit(deliver).lower(
            data, state, arg((), jnp.int32)).compile().as_text()
    _assert_whole_rows(text, data.train_x.shape[1])


def test_v5e_compiles_the_span_with_the_set_as_an_operand(one_chip):
    """The whole span (Krum + ALIE, the MLP cell's widths, n cut to
    1,024) as ``run_span`` dispatches it, the set, the shards and the
    state its entry parameters (PERF.md section 6, PR 34).  The gather in
    the round loop's body still moves whole rows, and the text carries
    no constant of a megabyte.  What the compiler writes of the set's
    shape is ONE ``convert`` to bf16 in the entry computation, before the
    loop: the first layer's default-precision rounding, moved through
    the gather (so the rows gathered are bf16, as they were) and out of
    the loop — the closure form folded that same convert into its 188 MB
    constant at compile time.  No layout copy, no transpose, nothing
    set-shaped computed inside the loop (the compiler may move the bf16
    set between memory spaces there: ``copy-start`` / ``copy-done``)."""
    n, B, n_train = 1024, 32, 60000
    exp, _ = _experiment(2 * n, users_count=n, batch_size=B, mal_prop=0.24,
                         defense="Krum", num_std=1.5)
    arg, data, state = _described(exp, one_chip, n_train)
    feat = data.train_x.shape[1]
    with _no_persistent_cache():
        text = exp._fused_span.lower(
            data, state, arg((), jnp.int32),
            arg((), jnp.int32)).compile().as_text()
    _assert_whole_rows(text, feat)
    assert " while(" in text
    entry_at = text.index("\nENTRY ")
    written = [(m.start() > entry_at, m.group(1)) for m in re.finditer(
        r"= (?:f32|bf16)\[%d,%d\]\S* ([\w\-]+)\(" % (n_train, feat), text)
        if m.group(1) not in ("parameter", "get-tuple-element")]
    moves = {"copy-start", "copy-done"}
    assert [w for w in written if w[1] not in moves] == [(True, "convert")], \
        written
    big = [m.group(0)[:160] for m in re.finditer(
               r"= \w+\[([\d,]+)\][^\n]* constant\(", text)
           if np.prod([int(v) for v in m.group(1).split(",")]) * 2 > 2 ** 20]
    assert not big, big


def test_v5e_compiles_the_gram_panels_without_copying_the_wire_matrix(
        one_chip):
    """Krum's Gram at the MLP cell's shape (n = 10,240, d = 79,510, f32):
    the v5e compiler feeds each panel's convolution from row slices of G
    in place.  A materialised ``G[i*b:]`` would be up to 3.26 GB beside a
    3.26 GB matrix; what the block triangle may add is one (n, n) f32
    buffer, 0.42 GB (PERF.md section 6, PR 31).  The compiler's own FLOP
    count is the work witness at the real size: 55 of 100 blocks."""
    from attacking_federate_learning_tpu.ops import distances

    n, d = 10240, 79510
    G = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    with _no_persistent_cache():
        compiled = jax.jit(distances.pairwise_distances).lower(G).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    copies = [line.strip()[:160] for line in entry.splitlines()
              if re.search(r"= (?:f32|bf16)\[\d+,%d\]" % d, line)
              and " parameter(" not in line]
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 4 * n * n
    blocks = n // distances.GRAM_BLOCK_ROWS
    assert blocks >= 2, "the MLP cell's cohort no longer takes the panels"
    share = (blocks + 1) / (2 * blocks)
    assert compiled.cost_analysis()["flops"] < (share + 0.02) * 2 * n * n * d


def test_v5e_compiles_krum_scores_without_a_sort(one_chip):
    """Krum's score evaluator at the MLP cell's shape (n = 10,240,
    f = 2,457): the v5e compiler's program has no ``sort`` (the parent's
    stable sort of f32[10240,10240] carried an s32 payload of the same
    shape, 100.9 ms a round, and the evaluator alone held 838,893,056
    bytes of temporaries; PERF.md section 6, PR 33) and holds no (n, n)
    value beside D: every pass rebuilds the key inside its reduce
    fusion, so the temporaries stay under one such buffer (0 today)."""
    from attacking_federate_learning_tpu.defenses import kernels

    n, f = 10240, 2457
    assert n >= kernels.KRUM_SELECT_MIN_ROWS, \
        "the MLP cell's cohort no longer takes the selection"
    D = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    with _no_persistent_cache():
        compiled = jax.jit(
            lambda D: kernels._krum_scores(D, n, f)).lower(D).compile()
    text = compiled.as_text()
    assert not re.search(r"\bsort\(", text)
    assert " while(" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n * n
    # Outside the fused computations (whose instructions live in
    # registers) nothing is (n, n) but D itself, as an entry parameter and
    # as the loop's invariant operand.
    written = [line.strip()[:160] for line in text.splitlines()
               if re.search(r"= \w+\[%d,%d\]" % (n, n), line)
               and " fusion(" in line]
    assert not written, written


def test_v5e_compiles_the_conv_client_step_without_relu_at_conv_size(
        one_chip):
    """The client step of ``cifar10_cnn`` at the CNN cell's own shape
    (n = 256, batch 128), where conv-1's output f32[256,128,16,30,30] is
    1.89 GB and every pass over it is ~4.6 ms of a memory-bound step
    (at n = 8 it fits the fast memory and nothing shows).  With ReLU
    behind the pool (``layers.relu_max_pool2d``; PERF.md section 6, PR
    35) the v5e compiler writes four values of that size — the
    convolution, its relayout for the pool, the bias add, the pool's
    ``select-and-scatter`` — where the reference order, compiled beside
    it, also writes ReLU's backward (``compare_select_fusion``, bf16)
    and a relayout of the scattered gradient for the masked bias
    gradient, and reads the activation a third time to pack ReLU's mask:
    39.0 GB of bytes accessed against 29.7."""
    from attacking_federate_learning_tpu.core.client import (
        make_client_grad_fn
    )
    from attacking_federate_learning_tpu.models import get_model
    from attacking_federate_learning_tpu.utils.flatten import make_flattener
    from test_conv_block import old_order

    n, B = 256, 128
    model = get_model("cifar10_cnn")
    flat = make_flattener(model.init(jax.random.key(0)))
    operands = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in (((flat.dim,), jnp.float32),
                                     ((n, B) + model.input_shape, jnp.float32),
                                     ((n, B), jnp.int32))]
    conv1 = n * B * 16 * 30 * 30

    def compiled(net):
        with _no_persistent_cache():
            c = jax.jit(make_client_grad_fn(net, flat)).lower(
                *operands).compile()
        text = c.as_text()
        # name = result type (a tuple's every shape counts) opcode(
        written = [
            m.group(1) for m in re.finditer(
                r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(",
                text[text.index("\nENTRY "):], re.M)
            if m.group(3) not in ("bitcast", "parameter")
            and any(np.prod([int(v) for v in shape.split(",")]) == conv1
                    for shape in re.findall(r"\w+\[([\d,]+)\]", m.group(2)))]
        return written, c.cost_analysis()["bytes accessed"]

    written, moved = compiled(model)
    assert 1 <= len(written) <= 4, written
    assert not [w for w in written if w.startswith("compare_select_fusion")]
    was_written, was_moved = compiled(old_order("cifar10_cnn"))
    assert len(was_written) > len(written), (was_written, written)
    assert moved < 0.85 * was_moved, (moved, was_moved)


def test_v5e_compiles_the_fused_attention_at_the_cells_widths(one_chip):
    """The kernels of ``ops/flash_attention.py`` at the sequence cell's
    shapes (8,192 tokens, 28 heads over 4 kv heads of 128, tiles of 512),
    forward and backward, window and global: Mosaic builds each (the fast
    memory it asks for fits), the gradient holds the forward, dq and dk/dv
    kernels once each, every operand and result at their boundary is f32
    (only the products' operands are rounded, inside), and no score-sized
    temporary is left: 264 MB a query block on the blocked path."""
    from attacking_federate_learning_tpu.ops import flash_attention as F

    B, L, H, G, D = 1, 8192, 28, 4, 128

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    q, k, v, w = (arg((B, L, H, D)), arg((B, L, G, D)), arg((B, L, G, D)),
                  arg((B, L, H * D)))
    for window in (None, 4096):
        grad = jax.grad(lambda q, k, v, w: jnp.sum(F.attention(
            q, k, v, window, 512, interpret=False) * w), argnums=(0, 1, 2))
        with _no_persistent_cache():
            compiled = jax.jit(grad).lower(q, k, v, w).compile()
        calls = re.findall(r"%(?:jvp_)?(flash_[a-z]+)\S* = (.*?) custom-call\(.*"
                           r"custom_call_target=\"tpu_custom_call\"",
                           compiled.as_text())
        assert sorted(c[0] for c in calls) == [
            "flash_dkv", "flash_dq", "flash_fwd"], calls
        for name, result in calls:
            assert re.findall(r"\b(\w+)\[", result) and set(
                re.findall(r"\b(\w+)\[", result)) == {"f32"}, (name, result)
        assert compiled.memory_analysis().temp_size_in_bytes < 200e6


def _smallthinker_client_program(length, sharding=None):
    """SmallThinker's scanned client step (8 silos into a bf16 wire, the
    sequence cell's program less the defense) lowered from shapes."""
    from attacking_federate_learning_tpu.core import client
    from attacking_federate_learning_tpu.models.base import get_model
    from attacking_federate_learning_tpu.utils.flatten import make_flattener

    model = get_model("smallthinker_21b_a3b_ep8")
    flat = make_flattener(jax.eval_shape(model.init, jax.random.key(0)))
    step = client.make_scanned_client_grad_fn(model, flat, jnp.bfloat16)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return jax.jit(step).lower(arg((flat.dim,), jnp.float32),
                               arg((8, 1, length), jnp.int32),
                               arg((8, 1, length), jnp.int32))


def test_smallthinkers_client_program_is_the_one_before_latent_attention(
        one_chip, monkeypatch):
    """The latent-attention model shares the sequence model's code, the
    attention kernels and the expert block with SmallThinker; SmallThinker's
    program is what it was before the second form came (the digests were
    read on that tree): as lowered at the cell's length, and as the v5e
    compiler builds it at 1,024 tokens with the TPU's paths (fused
    attention, the wire's select)."""
    from attacking_federate_learning_tpu.models import sequence as S
    from attacking_federate_learning_tpu.utils import flatten

    lowered = _smallthinker_client_program(8192).as_text(dialect="hlo")
    assert costs.hlo_fingerprint(lowered) == (
        "2cbcda6e1b5d2576285ed2cf7c6bd698"
        "97032fb5abbc7706c2d8e085cf1f6faf")
    monkeypatch.setattr(S, "_on_tpu", lambda: True)
    monkeypatch.setattr(flatten, "_platform", lambda: "tpu")
    with _no_persistent_cache():
        compiled = _smallthinker_client_program(1024, one_chip).compile()
    assert costs.hlo_fingerprint(compiled.as_text()) == (
        "dec7c83409f59771a4fdfa64c4148c7b"
        "33ca297f3a03b7b1eaa6878d13d93861")


def test_v5e_compiles_the_fused_latent_attention_at_the_cells_widths(
        one_chip):
    """The kernels at the latent-attention cell's shapes (8,192 tokens, 32
    heads, q.k of 192 padded to 256 lanes, values of 128, tiles of 512),
    forward and backward, each grid step carrying the cap's kv groups of
    one head: Mosaic builds each (the wider blocks' fast memory fits),
    every operand and result at their boundary is f32, and the temporaries
    are the padded q and k and their gradients (4 x 268 MB) and no more: a
    query tile's scores alone would be 537 MB, the whole (queries, keys)
    tensor 8.6 GB."""
    from attacking_federate_learning_tpu.ops import flash_attention as F

    B, L, H = 1, 8192, 32

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    q, k, v, w = (arg((B, L, H, 192)), arg((B, L, H, 192)),
                  arg((B, L, H, 128)), arg((B, L, H * 128)))
    grad = jax.grad(lambda q, k, v, w: jnp.sum(F.attention(
        q, k, v, None, 512, interpret=False) * w), argnums=(0, 1, 2))
    assert F.groups_per_step(1, H) == F.HEADS_PER_STEP > 1
    grids = [e.params["grid_mapping"].grid
             for e in jax.jit(grad).trace(q, k, v, w).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    steps = H // F.HEADS_PER_STEP
    assert sorted(grids) == [(B, steps, 16, 16), (B, steps, 16, 16),
                             (B, steps, 16, 32)], grids
    with _no_persistent_cache():
        compiled = jax.jit(grad).lower(q, k, v, w).compile()
    calls = re.findall(r"%(?:jvp_)?(flash_[a-z]+)\S* = (.*?) custom-call\(.*"
                       r"custom_call_target=\"tpu_custom_call\"",
                       compiled.as_text())
    assert sorted(c[0] for c in calls) == [
        "flash_dkv", "flash_dq", "flash_fwd"], calls
    for name, result in calls:
        assert set(re.findall(r"\b(\w+)\[", result)) == {"f32"}, (name,
                                                                 result)
    padded = B * L * H * 256 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * padded + 2**20
