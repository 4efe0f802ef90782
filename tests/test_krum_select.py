"""Krum scores its rows without sorting them (ISSUE 33).

From ``defenses/kernels.KRUM_SELECT_MIN_ROWS`` rows up, the exact evaluator
of ``_krum_scores`` finds each row's k-th smallest distance by bisection on
the f32 bit pattern and adds the entries below it in one masked sum
(``_select_scores``).  These tests hold that path to the sort it replaces
and to the f64 oracle (ties, masks, short rows), pin the rule that chooses
(the static n; below it the program is the sort's), and check what the
benchmark relies on: no sort in the compiled program, nothing (n, n) carried
through the loop but D, and every (n, n) instruction, the while body's
included, under the ``select`` scope that ``select_ms`` joins on.
"""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import metadata_in_cache_key
from attacking_federate_learning_tpu.defenses import kernels as K
from attacking_federate_learning_tpu.defenses import oracle as O
from attacking_federate_learning_tpu.ops import distances as Dist
from attacking_federate_learning_tpu.utils import costs
from test_attack_cohorts import _cohort, _degenerate_pair_band, _same_pick

BIG = 1 << 30


@pytest.fixture
def taken(monkeypatch):
    """The n of every trace that took the selection (a jit cache hit would
    show as none)."""
    seen = []
    inner = K._select_scores

    def spy(D, k, alive):
        seen.append(D.shape[0])
        return inner(D, k, alive)

    monkeypatch.setattr(K, "_select_scores", spy)
    return seen


@pytest.fixture
def select8(taken, monkeypatch):
    """The threshold lowered to 8 rows, and the spy's list."""
    monkeypatch.setattr(K, "KRUM_SELECT_MIN_ROWS", 8)
    return taken


def sorted_scores(D, n, f, monkeypatch, **kw):
    """The parent's evaluator on the same D: the threshold out of reach."""
    with monkeypatch.context() as m:
        m.setattr(K, "KRUM_SELECT_MIN_ROWS", BIG)
        return np.asarray(K._krum_scores(jnp.asarray(D), n, f, **kw))


# --- the distance matrices ---------------------------------------------------

def gaussian(n=48):
    G = np.random.default_rng(n).standard_normal((n, 40)).astype(np.float32)
    return np.asarray(Dist.pairwise_distances(jnp.asarray(G)))


def alie(n=64, f=15):
    """Identical colluders: f rows with the same distances, zeros among
    themselves by Gram cancellation (noise, not exact zeros)."""
    return np.asarray(Dist.pairwise_distances(
        jnp.asarray(_cohort(n, 300, f, "alie"))))


def all_equal(n=24):
    """Every distance an exact 0: every entry ties at t = 0."""
    return np.zeros((n, n), np.float32)


def lattice(n=40):
    """Points on an integer line: each row holds every distance twice or
    more, so the k-th place always sits inside a run of equal values."""
    x = (np.arange(n) % 7).astype(np.float32)
    return np.abs(x[:, None] - x[None, :])


def two_levels(n=32):
    """Rows of two values only, 1 and 3: k straddles the step."""
    D = np.where((np.arange(n)[:, None] + np.arange(n)[None, :]) % 3 == 0,
                 np.float32(1), np.float32(3)).astype(np.float32)
    np.fill_diagonal(D, 0.0)
    return D


MATRICES = {"gaussian": gaussian, "alie": alie, "all_equal": all_equal,
            "lattice": lattice, "two_levels": two_levels}


def oracle_scores(D, n, f, alive=None, paper_scoring=False):
    return O.np_krum_scores(np.zeros((D.shape[0], 1)), n, f, alive=alive,
                            D=D.astype(np.float64),
                            paper_scoring=paper_scoring)


# --- (a) the same sums --------------------------------------------------------

@pytest.mark.parametrize("paper_scoring", [False, True],
                         ids=["reference", "paper"])
@pytest.mark.parametrize("keep", ["n-f", "1", "n-1"])
@pytest.mark.parametrize("name", sorted(MATRICES))
def test_selection_adds_what_the_sort_adds(select8, monkeypatch, name, keep,
                                           paper_scoring):
    D = MATRICES[name]()
    n = D.shape[0]
    f = {"n-f": n // 4, "1": n - 1, "n-1": 1}[keep]
    if paper_scoring:
        f -= 2 if keep == "1" else 0        # k = n - f - 2 stays >= 1
        f = max(f, 0)
    got = np.asarray(K._krum_scores(jnp.asarray(D), n, f,
                                    paper_scoring=paper_scoring))
    assert select8 == [n]
    srt = sorted_scores(D, n, f, monkeypatch, paper_scoring=paper_scoring)
    want = oracle_scores(D, n, f, paper_scoring=paper_scoring)
    np.testing.assert_allclose(got, srt, rtol=2e-6, atol=0)
    np.testing.assert_allclose(got, want, rtol=5e-6, atol=0)
    if name in ("all_equal", "lattice", "two_levels"):
        # Small integers add exactly in any order: ties at the threshold
        # are counted, not approximated.
        np.testing.assert_array_equal(got, srt)


@pytest.mark.parametrize("bits", [1, 2, 3, 4])
@pytest.mark.parametrize("name", ["alie", "lattice"])
def test_every_pass_width_finds_the_same_threshold(select8, monkeypatch,
                                                   name, bits):
    """``_SELECT_BITS`` is a tuning constant: 31 is divisible by none of
    2, 3, 4, so the last pass re-decides bits and must not move t."""
    monkeypatch.setattr(K, "_SELECT_BITS", bits)
    D = MATRICES[name]()
    n = D.shape[0]
    got = np.asarray(K._krum_scores(jnp.asarray(D), n, n // 4))
    np.testing.assert_allclose(
        got, sorted_scores(D, n, n // 4, monkeypatch), rtol=2e-6, atol=0)


def test_negative_zero_and_nan_order_as_the_sort_orders_them(select8,
                                                             monkeypatch):
    """The key clears the sign bit: -0.0 is a zero, and a NaN (a client
    that sent one) lands past +inf, as ``jnp.sort`` places it, and is
    never added."""
    D = gaussian(16).copy()
    D[2, 5] = D[5, 2] = -0.0
    D[3, :] = D[:, 3] = np.nan
    D[7, 9] = -np.nan
    got = np.asarray(K._krum_scores(jnp.asarray(D), 16, 4))
    np.testing.assert_allclose(got, sorted_scores(D, 16, 4, monkeypatch),
                               rtol=2e-6, atol=0)
    assert np.isfinite(got).all()


# --- (b) masks, a traced k, short rows ----------------------------------------

@pytest.mark.parametrize("name", ["gaussian", "alie", "lattice"])
def test_masked_selection_with_a_traced_k(select8, monkeypatch, name):
    D = MATRICES[name]()
    n, f = D.shape[0], 5
    alive = np.random.default_rng(n).random(n) > 0.25

    @jax.jit
    def masked(D, alive):
        return K._krum_scores(D, jnp.sum(alive), f, alive=alive)

    got = np.asarray(masked(jnp.asarray(D), jnp.asarray(alive)))
    assert select8 == [n]
    srt = sorted_scores(D, int(alive.sum()), f, monkeypatch,
                        alive=jnp.asarray(alive))
    want = oracle_scores(D, int(alive.sum()), f, alive=alive)
    assert np.isinf(got[~alive]).all() and np.isfinite(got[alive]).all()
    np.testing.assert_allclose(got[alive], srt[alive], rtol=2e-6, atol=0)
    np.testing.assert_allclose(got[alive], want[alive], rtol=5e-6, atol=0)


@pytest.mark.parametrize("k_over", [0, 1, 7])
def test_a_row_with_fewer_than_k_finite_entries_sums_them(select8,
                                                          monkeypatch,
                                                          k_over):
    """k >= the n - 1 entries a row has (k_over = 0: k = n, the sketch's
    failing case) and a pool a mask has thinned: the finite entries, never
    the +inf of the holes and never NaN."""
    D = gaussian(32)
    n = 32
    alive = np.ones(n, bool)
    alive[[1, 4, 9, 30]] = False
    for mask, entries in ((None, n - 1), (alive, int(alive.sum()) - 1)):
        kw = {} if mask is None else {"alive": jnp.asarray(mask)}
        got = np.asarray(K._krum_scores(jnp.asarray(D), n + k_over, 0, **kw))
        keep = np.ones(n, bool) if mask is None else mask
        assert np.isfinite(got[keep]).all()
        Dk = D[np.ix_(keep, keep)].astype(np.float64)
        np.testing.assert_allclose(got[keep], Dk.sum(axis=1), rtol=5e-6)
        np.testing.assert_allclose(
            got[keep], sorted_scores(D, n + k_over, 0, monkeypatch,
                                     **kw)[keep], rtol=2e-6, atol=0)
        assert entries < n + k_over


def test_no_positive_k_scores_zero(select8, monkeypatch):
    """Bulyan's shrinking pool can ask for k <= 0: the empty sum."""
    D = gaussian(16)
    for n_minus_f in (0, -3):
        got = np.asarray(K._krum_scores(jnp.asarray(D), n_minus_f + 4, 4))
        np.testing.assert_array_equal(got, np.zeros(16, np.float32))
        np.testing.assert_array_equal(
            got, sorted_scores(D, n_minus_f + 4, 4, monkeypatch))


# --- (c) the rule that chooses --------------------------------------------------

@pytest.mark.parametrize("n,selects", [
    (256, False),                               # the CNN cell's cohort
    (512, False),                               # the BASELINE cells' largest
    (K.KRUM_SELECT_MIN_ROWS - 1, False),
    (K.KRUM_SELECT_MIN_ROWS, True),
    (K.KRUM_SELECT_MIN_ROWS + 7, True),
])
def test_the_evaluator_is_a_function_of_the_static_n(taken, n, selects):
    text = jax.jit(lambda D: K._krum_scores(D, n, n // 4)).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32)).as_text()
    assert taken == ([n] if selects else [])
    assert ("stablehlo.sort" in text) is (not selects)
    assert ("stablehlo.while" in text) is selects


def test_the_threshold_keeps_the_cnn_cell_on_the_sort():
    assert K.KRUM_SELECT_MIN_ROWS >= 512


def test_topk_falls_back_to_the_same_evaluator(select8):
    """'sort' names the exact evaluator; 'topk''s ``lax.cond`` fallback
    calls it, whichever form n gives it."""
    text = jax.jit(lambda D: K._krum_scores(D, 64, 15, method="topk")).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).as_text()
    assert select8 == [64]
    assert "stablehlo.sort" not in text


def test_another_dtype_keeps_the_sort(select8):
    """The key is the f32 bit pattern: a D of another width is sorted."""
    D = jnp.asarray(gaussian(16), jnp.bfloat16)
    K._krum_scores(D, 16, 4)
    assert select8 == []


def test_below_the_threshold_the_program_is_the_parents():
    """The pin that keeps the CNN cell's bits (n = 256, a federation that
    is chaotic in the last bit): the program of ``_krum_scores`` is,
    instruction for instruction, the parent's ``D + diag(inf)``, stable
    sort and masked prefix sum."""
    n, f = 256, 61

    def parent(D, alive=None):
        pool = n if alive is None else jnp.sum(alive)   # the caller's
        Dm = D + jnp.diag(jnp.full((n,), jnp.inf, D.dtype))
        if alive is not None:
            row_dead = jnp.where(alive, 0.0, jnp.inf)
            Dm = Dm + row_dead[None, :] + row_dead[:, None]
        k = pool - f
        srt = jnp.sort(Dm, axis=1)
        prefix = (jnp.arange(n) < k) & jnp.isfinite(srt)
        scores = jnp.sum(jnp.where(prefix, srt, 0.0), axis=1)
        return scores if alive is None else jnp.where(alive, scores, jnp.inf)

    def ours(D, alive=None):
        return K._krum_scores(D, n if alive is None else jnp.sum(alive), f,
                              alive=alive)

    parent.__name__ = ours.__name__ = "scores"      # the module's name
    D = jax.ShapeDtypeStruct((n, n), jnp.float32)
    alive = jax.ShapeDtypeStruct((n,), jnp.bool_)
    for args in ((D,), (D, alive)):
        got, want = (jax.jit(fn).lower(*args).compile().as_text()
                     for fn in (ours, parent))
        assert costs.hlo_fingerprint(got) == costs.hlo_fingerprint(want)


# --- (d) what the selecting program holds ---------------------------------------

def test_no_sort_and_only_D_crosses_the_loop(select8):
    """What the program asks for, whatever compiler takes it: no sort, and
    the while carries (n,) vectors beside the D it reads; the key and the
    holes are rebuilt inside every pass (the v5e compile in
    test_gather_layout.py shows them fused: 0 bytes of temporaries)."""
    n = 64
    alive = jax.ShapeDtypeStruct((n,), jnp.bool_)
    D = jax.ShapeDtypeStruct((n, n), jnp.float32)
    lowered = jax.jit(
        lambda D, a: K._krum_scores(D, jnp.sum(a), 15, alive=a)).lower(
            D, alive)
    text = lowered.as_text()
    assert select8 == [n] and "stablehlo.sort" not in text
    head = text[text.index("stablehlo.while"):]
    carried = re.findall(r"tensor<%dx%dx(\w+)>" % (n, n),
                         head[:head.index("\n")])
    assert carried == ["f32"], carried          # D, loop-invariant
    compiled = lowered.compile().as_text()
    assert not re.search(r"\bsort\(", compiled)


# --- (e) select_ms still reads the whole stage ----------------------------------

_SHAPED = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \w+\[(\d+),(\d+)\]")


@pytest.mark.parametrize("masked", [False, True], ids=["flat", "masked"])
def test_every_square_instruction_books_to_select(select8, masked):
    """The benchmark books a device operation to the innermost SUBSTAGES
    scope on its instruction's ``op_name`` path; an (n, n) instruction of
    the while body under no scope would read as ``unattributed`` and
    ``select_ms`` falsely low."""
    from perfbench.tracereduce import hlo_scope_paths, innermost

    n = 56

    def defense(D, alive):              # as the round program enters it
        with costs.stage_scope("tier1_aggregate"):
            if masked:
                return K._krum_scores(D, jnp.sum(alive), 13, alive=alive)
            return K._krum_scores(D, n, 13)

    with metadata_in_cache_key():
        text = jax.jit(defense).lower(
            jax.ShapeDtypeStruct((n, n), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.bool_)).compile().as_text()
    assert select8 == [n]
    paths = hlo_scope_paths(text)
    seen = in_body = 0
    for line in text.splitlines():
        m = _SHAPED.match(line)
        if (not m or (int(m.group(2)), int(m.group(3))) != (n, n)
                or " parameter(" in line or " get-tuple-element(" in line):
            continue
        seen += 1
        in_body += "/while/body/" in paths[m.group(1)]
        assert innermost(paths[m.group(1)], costs.SUBSTAGES) == "select", line
    assert seen >= 3 and in_body >= 1


# --- (f) Krum picks what the oracle picks ----------------------------------------

_PICKS = [(64, 300, 15, "alie"), (48, 200, 11, "none"),
          (32, 512, 8, "backdoor"), (24, 100, 6, "signflip")]


@pytest.mark.parametrize("n,d,f,attack", _PICKS,
                         ids=[f"{c[0]}-{c[3]}" for c in _PICKS])
def test_krum_pick_is_the_oracles_eager_and_jitted(select8, n, d, f, attack):
    G = _cohort(n, d, f, attack)
    scores64 = O.np_krum_scores(G.astype(np.float64), n, f)
    want = int(np.argmin(scores64))
    eager = int(K.krum_select(jnp.asarray(G), n, f, distance_impl="xla"))
    jitted = int(jax.jit(lambda g: K.krum_select(
        g, n, f, distance_impl="xla"))(jnp.asarray(G)))
    assert select8 == [n, n]
    assert jitted == eager
    assert _same_pick(G, eager, want, scores64, _degenerate_pair_band(G, f))


def test_masked_krum_pick_is_the_survivors_oracle_pick(select8):
    n, f = 64, 15
    G = _cohort(n, 300, f, "alie")
    alive = np.random.default_rng(3).random(n) > 0.25
    keep = np.flatnonzero(alive)
    scores = O.np_krum_scores(G[keep].astype(np.float64), len(keep), f)
    scores64 = np.full(n, np.inf)
    scores64[keep] = scores
    Gz = jnp.asarray(np.where(alive[:, None], G, 0.0).astype(np.float32))
    got = int(K.krum_select(Gz, n, f, mask=jnp.asarray(alive)))
    assert select8 == [n] and alive[got]
    assert _same_pick(G, got, int(keep[np.argmin(scores)]), scores64,
                      _degenerate_pair_band(G, f))


def test_bulyan_telemetry_scores_take_the_selection(select8):
    """``_bulyan_diag`` scores the first pool through ``_krum_scores``;
    Bulyan's own selection loop keeps its argsort."""
    n, f = 32, 5
    G = jnp.asarray(_cohort(n, 100, f, "alie"))
    agg, diag = K.bulyan(G, n, f, distance_impl="xla", telemetry=True)
    assert select8 == [n]
    want = O.np_krum_scores(np.asarray(G, np.float64), n, f)
    np.testing.assert_allclose(np.asarray(diag["scores"]), want, rtol=5e-6,
                               atol=_degenerate_pair_band(np.asarray(G), f))


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("spec", [("clients", None), (None, "model")],
                         ids=["rows", "columns"])
def test_a_sharded_D_selects_the_same_scores(select8, monkeypatch, spec):
    """The passes are element-wise plus a reduction along one axis, which
    GSPMD partitions; the rule reads n only."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from attacking_federate_learning_tpu.parallel.mesh import make_plan

    D = alie()
    n = D.shape[0]
    mesh = make_plan((4, 2)).mesh
    Ds = jax.device_put(jnp.asarray(D), NamedSharding(mesh, P(*spec)))
    got = np.asarray(jax.jit(lambda D: K._krum_scores(D, n, 15))(Ds))
    assert select8 == [n]
    np.testing.assert_allclose(got, sorted_scores(D, n, 15, monkeypatch),
                               rtol=2e-6, atol=0)
