"""How a scanned client step writes its rows into the (n, d) wire:
``utils/flatten.py:wire_path`` picks an elementwise select where every tile
of the matrix holds every row (a TPU, n <= 8) and an in-place update
elsewhere.  Both give the same bits; the select path is steered here by the
backend the module reads, and the last test compiles it for a described
v5e."""

import contextlib
import os
import re

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
from attacking_federate_learning_tpu.models.base import Model, get_model
from attacking_federate_learning_tpu.utils import costs, flatten
from attacking_federate_learning_tpu.utils.flatten import make_flattener

DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def on(monkeypatch, platform):
    """The backend ``utils/flatten.py`` picks its path from."""
    monkeypatch.setattr(flatten, "_platform", lambda: platform)


@pytest.mark.parametrize("platform,rows,path", [
    ("tpu", 8, "tiles"),        # the sequence cell: one band of tiles
    ("tpu", 1, "tiles"),
    ("tpu", 9, "plain"),        # two bands: an update moves its own
    ("tpu", 256, "plain"),      # the CNN cell
    ("tpu", 10_240, "plain"),   # the MLP cell
    ("cpu", 8, "plain"),        # rows are whole on the CPU
    ("gpu", 8, "plain"),
])
def test_the_path_reads_the_backend_and_the_rows(platform, rows, path,
                                                 monkeypatch):
    on(monkeypatch, platform)
    assert flatten.wire_path(rows) == path


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES.keys())
@pytest.mark.parametrize("row", [0, 3, 7])
def test_the_select_writes_exactly_the_row_an_update_writes(row, dtype,
                                                            monkeypatch):
    wire = jax.random.normal(jax.random.key(0), (8, 384)).astype(dtype)
    tree = {"a": jax.random.normal(jax.random.key(1), (2, 128)),
            "b": jax.random.normal(jax.random.key(2), (128,))}
    got = {}
    for platform in ("cpu", "tpu"):
        on(monkeypatch, platform)
        got[platform] = jax.jit(lambda *a: flatten.write_row(*a))(
            wire, row, tree)
    want = wire.at[row].set(flatten.ravel(tree).astype(dtype))
    for platform in ("cpu", "tpu"):
        assert got[platform].dtype == dtype
        assert bool(jnp.array_equal(got[platform], want)), platform


def _mlp_wire(dtype):
    model = get_model("mnist_mlp")
    params = model.init(jax.random.key(0))
    flat = make_flattener(params)
    xs = jax.random.normal(jax.random.key(1), (6, 1, 16, 784))
    ys = jax.random.randint(jax.random.key(2), (6, 1, 16), 0, 10)
    step = client.make_client_update_fn(model, flat, scan_dtype=dtype)
    return jax.jit(step)(flat.ravel(params), xs, ys, 0.1, 0.1)


def _seq_wire(dtype):
    model = S.make_sequence_model("seq_tiny", S.SEQ_TINY)
    params = model.init(jax.random.key(0))
    flat = make_flattener(params)
    xs = jax.random.randint(jax.random.key(1), (4, 1, 1, 24), 0,
                            S.SEQ_TINY.vocab)
    step = client.make_client_update_fn(model, flat, scan_dtype=dtype)
    return jax.jit(step)(flat.ravel(params), xs, jnp.roll(xs, -1, -1), 0.1,
                         0.1)


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES.keys())
@pytest.mark.parametrize("wire_of", [_mlp_wire, _seq_wire],
                         ids=["mnist_mlp", "seq_tiny"])
def test_the_scanned_wire_is_the_same_bits_on_both_paths(wire_of, dtype,
                                                         monkeypatch):
    on(monkeypatch, "cpu")
    plain = wire_of(dtype)
    on(monkeypatch, "tpu")
    tiles = wire_of(dtype)
    assert tiles.dtype == plain.dtype == dtype
    assert bool(jnp.array_equal(tiles, plain))
    assert bool(jnp.any(plain != 0, axis=1).all())     # every row written


def token_experiment(grad_dtype):
    cfg = ExperimentConfig(
        dataset=C.SYNTH_TOKENS_TINY, seq_len=24, users_count=8,
        mal_prop=0.25, batch_size=1, defense="Krum", num_std=1.5,
        synth_train=32, synth_test=4, epochs=6, learning_rate=0.01,
        grad_dtype=grad_dtype)
    ds = load_dataset(cfg.dataset, cfg.data_dir, 0, synth_train=32,
                      synth_test=4, seq_len=cfg.seq_len)
    return FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                               dataset=ds)


@pytest.mark.parametrize("grad_dtype", ["float32", "bfloat16"])
def test_a_round_crafts_the_same_rows_and_krum_picks_the_same_one(
        grad_dtype, monkeypatch):
    """One round of the tiny sequence cell (n = 8, f = 2, Krum, ALIE) on
    both paths: the post-attack wire, the crafted rows, Krum's pick and
    the next weights are the same bits."""
    from perfbench import run

    got = {}
    for platform in ("cpu", "tpu"):
        on(monkeypatch, platform)
        exp = token_experiment(grad_dtype)
        G = run.wire_matrix_fn(exp)(exp.state)
        agg = run.defense_fn(exp)(G)
        exp.run_span(0, 1)
        got[platform] = (np.asarray(G.astype(jnp.float32)),
                         np.asarray(agg), np.asarray(exp.state.weights))
    (g0, a0, w0), (g1, a1, w1) = got["cpu"], got["tpu"]
    np.testing.assert_array_equal(g1, g0)
    np.testing.assert_array_equal(g1[0], g1[1])             # the colluders
    assert not (g1[0] == g1[2]).all()
    np.testing.assert_array_equal(a1, a0)
    picked = [i for i in range(8) if (g1[i] == a1).all()]
    assert len(picked) >= 1
    np.testing.assert_array_equal(w1, w0)


@pytest.mark.parametrize("platform,path", [("cpu", "plain"),
                                           ("tpu", "tiles")])
def test_the_compile_record_carries_the_wire(platform, path, monkeypatch):
    """The scanned step's compile in ``compile_log()`` carries the rows,
    width, dtype, layout and path it was traced with."""
    costs.install_cache_counters()
    on(monkeypatch, platform)
    monkeypatch.setattr(costs._CacheCounters, "pending_wire", [])
    model = get_model("mnist_mlp")
    params = model.init(jax.random.key(0))
    flat = make_flattener(params)
    step = client.make_scanned_client_grad_fn(model, flat, jnp.bfloat16)
    log0 = len(costs.compile_log())
    jax.jit(step).lower(flat.ravel(params),
                        jnp.zeros((5, 3, 784)), jnp.zeros((5, 3), jnp.int32)
                        ).compile()
    noted = [c["wire"] for c in costs.compile_log()[log0:] if "wire" in c]
    assert noted == [[{"rows": 5, "cols": flat.dim, "dtype": "bfloat16",
                       "layout": "default", "path": path}]]


def test_a_vmapped_cohort_notes_no_wire(monkeypatch):
    costs.install_cache_counters()
    monkeypatch.setattr(costs._CacheCounters, "pending_wire", [])
    model = get_model("mnist_mlp")
    params = model.init(jax.random.key(0))
    flat = make_flattener(params)
    step = client.make_client_grad_fn(model, flat)
    jax.jit(step).lower(flat.ravel(params), jnp.zeros((5, 3, 784)),
                        jnp.zeros((5, 3), jnp.int32)).compile()
    assert costs._CacheCounters.pending_wire == []
    assert all("wire" not in c for c in costs.compile_log()[-1:])


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


def _wide_model(leaves):
    """A model whose parameters are ``leaves`` (rows, 128) f32 matrices
    and whose loss is linear in them: a client step of the wire's width
    that compiles in seconds."""
    def init(key):
        return {f"w{i}": jnp.zeros((r, 128)) for i, r in enumerate(leaves)}

    def loss(params, x, y):
        return sum(jnp.sum(w * (jnp.sum(x) + i)) for i, w in
                   enumerate(jax.tree.leaves(params))) + jnp.sum(y)

    return Model("wide", init, None, (4,), 2, loss=loss)


def test_v5e_writes_the_wide_wire_with_no_whole_wire_copy(one_chip,
                                                          monkeypatch):
    """The scanned step at n = 8, d = 2^27 + 2^20 in bf16 for a described
    v5e on the select path: the optimized HLO copies or transposes nothing
    the wire's size, the temporaries stay under one wire, and the padded
    one-row copy of the update path is gone."""
    from jax.experimental.layout import Format, Layout

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    n, leaves = 8, [2 ** 13, 2 ** 20, 2 ** 7]
    model = _wide_model(leaves)
    flat = make_flattener(jax.eval_shape(model.init, jax.random.key(0)))
    d = flat.dim
    assert d == 128 * sum(leaves) >= 2 ** 27

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=Format(
            Layout(tuple(range(len(shape)))), one_chip))

    args = (arg((d,), jnp.float32), arg((n, 1, 4), jnp.float32),
            arg((n, 1), jnp.int32))
    texts = {}
    for platform in ("cpu", "tpu"):
        on(monkeypatch, platform)
        step = client.make_scanned_client_grad_fn(model, flat, jnp.bfloat16)
        with _no_persistent_cache():
            compiled = jax.jit(step).lower(*args).compile()
        texts[platform] = compiled.as_text()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < n * d * 2, (platform, temp)
    wide = re.compile(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(")
    for m in wide.finditer(texts["tpu"]):
        size = int(np.prod([int(v) for v in m.group(1).split(",")]))
        assert size < n * d, m.group(0)
    padded_row = f"bf16[1,{d}]"
    assert padded_row in texts["cpu"]          # the update's one-row form
    assert padded_row not in texts["tpu"]
    assert "dynamic-update-slice(" not in texts["tpu"]
