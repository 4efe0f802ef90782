"""Krum's Gram computes each pair of clients once (ISSUE 31).

From two blocks of ``ops/distances.GRAM_BLOCK_ROWS`` rows up,
``pairwise_sq_distances`` multiplies only the upper block triangle of
``G G^T`` and reads the lower one transposed.  These tests hold that path
to the one-dot form (values, symmetry, the defenses' picks), pin the rule
that chooses (the static n; below it the program is the single dot's),
witness the saved work in XLA's own FLOP count, and check that every
(n, n)- or panel-shaped instruction still books to the ``gram`` scope the
benchmark's ``gram_ms`` / ``gram_roofline`` join on.
"""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import metadata_in_cache_key
from attacking_federate_learning_tpu.defenses import kernels as K
from attacking_federate_learning_tpu.defenses import oracle as O
from attacking_federate_learning_tpu.ops import distances as D
from attacking_federate_learning_tpu.utils import costs


SHAPES = [(48, 16), (64, 32), (33, 16), (19, 8)]    # (n, block); two ragged
DTYPES = [jnp.float32, jnp.bfloat16]


def rows(n, d, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((n, d)).astype(np.float32), dtype)


@pytest.fixture
def block16(monkeypatch):
    """The block lowered to 16 rows, and a count of the traces that took
    the block-triangle path (a jit cache hit would show as 0)."""
    taken = []
    inner = D._symmetric_gram

    def spy(G, precision, block):
        taken.append((G.shape[0], block))
        return inner(G, precision, block)

    monkeypatch.setattr(D, "GRAM_BLOCK_ROWS", 16)
    monkeypatch.setattr(D, "_symmetric_gram", spy)
    return taken


# --- (a) the same numbers --------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES, ids=lambda t: t.__name__)
@pytest.mark.parametrize("n,block", SHAPES)
def test_block_triangle_matches_the_single_dot(n, block, dtype):
    G = rows(n, 200, seed=n + block, dtype=dtype)
    one = np.asarray(D.cross_sq_distances(G, G))
    got = np.asarray(D._self_sq_distances(G, block=block))
    off = ~np.eye(n, dtype=bool)
    # Same operand dtype and precision: only the f32 accumulation order of
    # a panel's dot may differ from the full dot's.
    np.testing.assert_allclose(got[off], one[off], rtol=1e-6, atol=0)
    np.testing.assert_array_equal(got, got.T)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda t: t.__name__)
@pytest.mark.parametrize("n,block", SHAPES)
def test_pairwise_distances_symmetric_with_zero_diagonal(n, block, dtype,
                                                         monkeypatch):
    monkeypatch.setattr(D, "GRAM_BLOCK_ROWS", block)
    G = rows(n, 200, seed=3 * n, dtype=dtype)
    got = np.asarray(D.pairwise_distances(G))
    want = np.sqrt(np.asarray(D.cross_sq_distances(G, G)))
    off = ~np.eye(n, dtype=bool)
    np.testing.assert_allclose(got[off], want[off], rtol=1e-6, atol=0)
    np.testing.assert_array_equal(got, got.T)
    np.testing.assert_array_equal(np.diag(got), np.zeros(n, np.float32))


def test_explicit_precision_reaches_every_panel():
    """``precision=`` is the caller's: each panel's dot carries it, as the
    single dot does."""
    G = jax.ShapeDtypeStruct((48, 64), jnp.float32)
    for precision, word in ((jax.lax.Precision.HIGHEST, "HIGHEST"),
                            (jax.lax.Precision.DEFAULT, "DEFAULT")):
        text = jax.jit(lambda g: D._self_sq_distances(
            g, precision, block=16)).lower(G).as_text()
        dots = [line for line in text.splitlines() if "dot_general" in line]
        assert len(dots) == 3
        assert all(line.count(word) == 2 for line in dots), dots


# --- (b) the rule that chooses ---------------------------------------------

def _dots(n, d=8):
    text = jax.jit(D.pairwise_distances).lower(
        jax.ShapeDtypeStruct((n, d), jnp.float32)).as_text()
    return text.count("dot_general")


@pytest.mark.parametrize("n,dots", [
    (256, 1),                                   # the CNN cell's cohort
    (512, 1),                                   # the BASELINE cells' largest
    (2 * D.GRAM_BLOCK_ROWS - 1, 1),
    (2 * D.GRAM_BLOCK_ROWS, 2),
    (3 * D.GRAM_BLOCK_ROWS + 8, 4),             # ragged: a shorter last panel
])
def test_block_count_is_a_function_of_the_static_n(n, dots):
    assert _dots(n) == dots


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("spec", [("clients", None), (None, "model"), ()],
                         ids=["rows", "columns", "replicated"])
def test_a_cohort_on_a_mesh_keeps_the_single_dot(block16, spec):
    """Row slices of a row-sharded G make GSPMD reshard every panel (the
    v5e compiler, four chips, n = 10,240: 11.7 GB of temporaries a chip
    against 3.3, 26 all-gathers of a panel's rows; PERF.md §6, PR 31).
    The operand's type carries its mesh, so the rule can see it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from attacking_federate_learning_tpu.parallel.mesh import make_plan

    mesh = make_plan((4, 2)).mesh
    G = jax.device_put(rows(64, 40, seed=5), NamedSharding(mesh, P(*spec)))
    text = jax.jit(D.pairwise_distances).lower(G).as_text()
    assert text.count("dot_general") == 1 and block16 == []
    np.testing.assert_allclose(
        np.asarray(jax.jit(D.pairwise_distances)(G)),
        np.asarray(D.pairwise_distances(rows(64, 40, seed=5))),
        rtol=1e-5, atol=1e-5)
    assert block16 == [(64, 16)]                # the one-device call


def test_below_two_blocks_the_program_is_the_single_dots():
    """The pin that keeps the CNN cell's bits (n = 256, a federation that
    is chaotic in the last bit): the compiled program of
    ``pairwise_distances`` is, instruction for instruction, sqrt and
    zero_diagonal over ``cross_sq_distances(G, G)``."""
    G = jax.ShapeDtypeStruct((256, 1024), jnp.float32)

    def pairwise_distances(G):          # the parent's spelling, unscoped
        return D.zero_diagonal(jnp.sqrt(D.cross_sq_distances(G, G)))

    got = jax.jit(D.pairwise_distances).lower(G).compile().as_text()
    want = jax.jit(pairwise_distances).lower(G).compile().as_text()
    assert costs.hlo_fingerprint(got) == costs.hlo_fingerprint(want)


# --- (c) the work witness ---------------------------------------------------

def test_eight_blocks_cost_at_most_062_of_the_single_dots_flops():
    """36 of 64 blocks = 0.5625 of the products, plus the epilogue; XLA's
    own count on the CPU (a count, not a time)."""
    G = jax.ShapeDtypeStruct((128, 512), jnp.float32)

    def flops(block):
        fn = jax.jit(lambda g: D._self_sq_distances(g, block=block))
        return fn.lower(G).compile().cost_analysis()["flops"]

    assert flops(16) <= 0.62 * flops(128)


# --- (d) the defenses pick what the oracle picks -----------------------------

def adversarial(n=64, d=37, seed=0):
    """The rows of test_host_krum_adversarial_magnitudes_and_ties at four
    blocks: huge rows in two blocks, an exact tie pair across two."""
    G = np.random.default_rng(seed).standard_normal((n, d)).astype(np.float32)
    G[0] = 1e6
    G[50] = -1e6
    G[37] = G[3]
    return G


@pytest.mark.parametrize("f", [5, 15])
def test_blocked_krum_select_matches_oracle(block16, f):
    G = adversarial()
    want = O.np_krum_select(G.astype(np.float64), 64, f)
    got = int(K.krum_select(jnp.asarray(G), 64, f, distance_impl="xla"))
    assert block16 == [(64, 16)]
    np.testing.assert_array_equal(G[got], G[want])     # a tie: the same row


@pytest.mark.parametrize("dead", [(7,), (3, 20, 41, 63)])
def test_blocked_masked_krum_matches_oracle(block16, dead):
    G, f = adversarial(seed=1), 5
    alive = np.ones(64, bool)
    alive[list(dead)] = False
    Gz = np.where(alive[:, None], G, 0.0).astype(np.float32)
    want = O.np_krum_select(Gz.astype(np.float64), int(alive.sum()), f,
                            alive=alive)
    got = int(K.krum_select(jnp.asarray(Gz), 64, f, distance_impl="xla",
                            mask=jnp.asarray(alive)))
    assert block16 == [(64, 16)]
    np.testing.assert_array_equal(Gz[got], Gz[want])


@pytest.mark.parametrize("f", [5, 15])
def test_blocked_bulyan_matches_oracle(block16, f):
    G = adversarial(seed=2)
    want = O.np_bulyan(G.astype(np.float64), 64, f)
    got = np.asarray(K.bulyan(jnp.asarray(G), 64, f, distance_impl="xla"))
    assert block16 == [(64, 16)]
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-4)


def test_blocked_krum_under_jit_is_the_eager_pick(block16):
    G = jnp.asarray(adversarial(seed=4))
    eager = int(K.krum_select(G, 64, 5, distance_impl="xla"))
    jitted = int(jax.jit(lambda g: K.krum_select(
        g, 64, 5, distance_impl="xla"))(G))
    assert block16 == [(64, 16)] * 2
    assert jitted == eager


# --- (e) gram_ms still reads the whole kernel --------------------------------

_SHAPED = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = f32\[(\d+),(\d+)\]")


@pytest.mark.parametrize("n", [64, 57])
def test_every_panel_and_square_instruction_books_to_gram(block16, n):
    """The benchmark books a device operation to the innermost SUBSTAGES
    scope on its instruction's ``op_name`` path; an (n, n) or panel-shaped
    instruction under another scope (or none) would leave ``gram_ms``."""
    from perfbench.tracereduce import hlo_scope_paths, innermost

    def defense(g):                     # as the round program enters it
        with costs.stage_scope("tier1_aggregate"):
            return D.pairwise_distances(g)

    with metadata_in_cache_key():
        text = jax.jit(defense).lower(
            jax.ShapeDtypeStruct((n, 40), jnp.float32)).compile().as_text()
    assert block16 == [(n, 16)]
    paths = hlo_scope_paths(text)
    widths = {n - lo for lo in range(0, n, 16)}
    seen = 0
    for line in text.splitlines():
        m = _SHAPED.match(line)
        if not m or int(m.group(3)) not in widths or " parameter(" in line:
            continue
        seen += 1
        assert innermost(paths[m.group(1)], costs.SUBSTAGES) == "gram", line
    assert seen >= len(widths)          # a dot a panel, at the least
