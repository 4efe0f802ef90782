"""The checks that decide ``correct``, on the CPU: the two forms of a
configuration's model check (perfbench/modelcheck.py), Krum's host
reference read in column blocks (perfbench/defenses/krum.py) against the
whole-matrix form it replaced, and how many (n, d) arrays the runner keeps
alive.  Times are not looked at."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from perfbench import modelcheck, run
from perfbench.defenses import krum

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


# --- the count form --------------------------------------------------------

def _experiment(name, shape, seed=3, test_size=96, own_labels=False):
    """What a check reads of a live experiment: the program's model, its
    flattener, its jitted eval over a small random test set (labelled at
    random, or with the model's own predictions), weights."""
    import jax

    from attacking_federate_learning_tpu.core.evaluate import make_eval_fn
    from attacking_federate_learning_tpu.models.base import get_model
    from attacking_federate_learning_tpu.utils.flatten import make_flattener

    model = get_model(name)
    params = model.init(jax.random.key(seed))
    flat = make_flattener(params)
    rng = np.random.default_rng(seed)
    dataset = types.SimpleNamespace(
        test_x=rng.standard_normal((test_size,) + shape).astype(np.float32),
        test_y=rng.integers(0, 10, test_size).astype(np.int32))
    if own_labels:
        dataset.test_y = np.asarray(
            model.apply(params, dataset.test_x)).argmax(axis=1).astype(
                np.int32)
    exp = types.SimpleNamespace(
        model=model, flat=flat,
        state=types.SimpleNamespace(weights=flat.ravel(params)),
        evaluate=make_eval_fn(model, flat, dataset.test_x, dataset.test_y,
                              32))
    return exp, dataset


@pytest.mark.parametrize("name,shape", [("mnist_mlp", (784,)),
                                        ("cifar10_cnn", (3, 32, 32))])
def test_count_check_prints_the_numbers_the_runner_used_to(name, shape):
    exp, dataset = _experiment(name, shape)
    reference = importlib.import_module("perfbench.configs." + name)
    weights = np.asarray(exp.state.weights)
    got = reference.check(exp, weights, dataset, seed=1)
    # PR 24's run.py:333-344, word for word
    _, correct_dev = exp.evaluate(exp.state.weights)
    predicted = np.argmax(
        reference.logits(weights, np.asarray(dataset.test_x)), axis=1)
    correct_ref = int((predicted == np.asarray(dataset.test_y)).sum())
    assert {k: got[k] for k in ("reference_correct", "test_size",
                                "device_correct")} == {
        "reference_correct": correct_ref, "test_size": len(dataset.test_y),
        "device_correct": int(correct_dev)}
    assert got["ok"] == (abs(correct_ref - int(correct_dev))
                         <= 0.005 * len(dataset.test_y))
    assert got["ok"] and modelcheck.EVAL_COUNT_RTOL == 0.005
    assert got["compared"] == {"eval_count_gap": [
        abs(correct_ref - int(correct_dev)), 0.005 * len(dataset.test_y)]}
    json.dumps(got)
    assert reference.train_flops_per_sample() > 0


def test_count_check_fails_on_a_wrong_reference():
    exp, dataset = _experiment("mnist_mlp", (784,), own_labels=True)
    reference = importlib.import_module("perfbench.configs.mnist_mlp")
    weights = np.asarray(exp.state.weights)
    assert modelcheck.count_check(exp, reference.logits, weights,
                                  dataset)["ok"]
    no_bias = lambda w, x: reference.logits(       # noqa: E731
        np.concatenate([w[:78400], 0 * w[78400:78500], w[78500:]]), x) * -1.0
    assert not modelcheck.count_check(exp, no_bias, weights, dataset)["ok"]


# --- the logits form ----------------------------------------------------------
# f32 accumulation of 784 + 100 terms of O(1) against f32 at "highest" on
# the same inputs: gaps of ~1e-6; bf16 matmul passes give ~1e-2.  1e-4
# separates the two with room on both sides.
ATOL = RTOL = 1e-4


def _plain_mlp(w, x):
    """jax.numpy f32 plain forward of MnistNet: what ``model.apply`` returns
    (log-probabilities), from the flat wire vector."""
    import jax
    import jax.numpy as jnp

    W1, b1 = w[:78400].reshape(100, 784), w[78400:78500]
    W2, b2 = w[78500:79500].reshape(10, 100), w[79500:]
    h = jnp.maximum(x.reshape(len(x), -1) @ W1.T + b1, 0.0)
    return jax.nn.log_softmax(h @ W2.T + b2, axis=-1)


def _with_apply(exp, apply):
    return types.SimpleNamespace(
        model=types.SimpleNamespace(apply=apply), flat=exp.flat,
        state=exp.state)


def _bf16_apply(exp):
    """The program's forward with bf16 matmul operands (the CPU ignores
    ``default_matmul_precision``, so the operands are rounded by hand)."""
    import jax
    import jax.numpy as jnp

    def rounded(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    def apply(params, x):
        return exp.model.apply(jax.tree_util.tree_map(rounded, params),
                               rounded(x))
    return apply


def _dropped_term_apply(exp):
    def apply(params, x):       # the first layer's bias left out
        leaves = exp.flat.ravel(params)
        return exp.model.apply(exp.flat.unravel(
            leaves.at[78400:78500].set(0.0)), x)
    return apply


@pytest.mark.parametrize("fault,ok", [(None, True), ("bf16_matmuls", False),
                                      ("dropped_term", False)])
def test_logits_check_passes_as_stated_and_fails_a_lower_precision(fault, ok):
    exp, dataset = _experiment("mnist_mlp", (784,), seed=11)
    if fault == "bf16_matmuls":
        exp = _with_apply(exp, _bf16_apply(exp))
    elif fault == "dropped_term":
        exp = _with_apply(exp, _dropped_term_apply(exp))
    weights = np.asarray(exp.state.weights)
    got = modelcheck.logits_check(exp, _plain_mlp, weights, dataset, seed=5,
                                  count=50, block=16, atol=ATOL, rtol=RTOL)
    assert got["ok"] is ok and got["inputs"] == 50 and got["finite"]
    value, limit = got["compared"]["logit_gap_over_limit"]
    assert limit == 1.0 and (value <= 1.0) is ok
    if ok:
        assert value < 0.3          # room under the limit
    else:
        assert value > 3.0          # and over it
    json.dumps(got)


def test_logits_check_is_seeded_and_refuses_a_shape_or_a_nan():
    exp, dataset = _experiment("mnist_mlp", (784,), seed=11)
    weights = np.asarray(exp.state.weights)
    args = dict(count=20, block=8, atol=ATOL, rtol=RTOL)
    a = modelcheck.logits_check(exp, _plain_mlp, weights, dataset, 5, **args)
    b = modelcheck.logits_check(exp, _plain_mlp, weights, dataset, 5, **args)
    c = modelcheck.logits_check(exp, _plain_mlp, weights, dataset, 6, **args)
    # another seed draws other inputs (the worst element's reference value)
    assert a == b and a["ok"] and c["ok"] and a["reference"] != c["reference"]
    wrong = lambda w, x: _plain_mlp(w, x)[:, :5]         # noqa: E731
    assert not modelcheck.logits_check(exp, wrong, weights, dataset, 5,
                                       **args)["ok"]
    bad = weights.copy()
    bad[0] = np.nan
    got = modelcheck.logits_check(exp, _plain_mlp, bad, dataset, 5, **args)
    assert not got["ok"] and not got["finite"]


# --- Krum in column blocks ------------------------------------------------------

def _whole_matrix_check(G, n, f, agg, seed=0):
    """PR 24's ``krum.check`` and ``scores``: the whole matrix on the host,
    widened to f64 at once.  Kept here as the reference of the blocked form."""
    def scores(G, rows, k):
        n = G.shape[0]
        S = G[rows].astype(np.float64)
        sq_s = np.einsum("nd,nd->n", S, S)
        D2 = np.empty((len(rows), n))
        for lo in range(0, n, 1024):
            B = G[lo:lo + 1024].astype(np.float64)
            sq_b = np.einsum("nd,nd->n", B, B)
            D2[:, lo:lo + 1024] = (sq_s[:, None] + sq_b[None, :]
                                   - 2.0 * (S @ B.T))
        D = np.sqrt(np.maximum(D2, 0.0))
        D[np.arange(len(rows)), rows] = np.inf
        return np.partition(D, k - 1, axis=1)[:, :k].sum(axis=1)

    winners = np.flatnonzero(G[:, 0] == agg[0])
    winners = winners[(G[winners] == agg[None, :]).all(axis=1)]
    if winners.size == 0:
        return {"ok": False, "why": "the aggregate is not an input row"}
    got, k = int(winners[0]), n - f
    if n <= krum.FULL_CHECK_ROWS:
        rows, mode = np.arange(n), "all_rows"
    else:
        rng = np.random.default_rng(seed)
        rows = np.unique(np.append(
            rng.choice(n, krum.SAMPLED_ROWS, replace=False), got))
        mode = f"sample_of_{len(rows)}_rows"
    s = scores(G, rows, k)
    best = int(rows[np.argmin(s)])
    s_got, s_best = float(s[rows == got][0]), float(s.min())
    gap = (s_got - s_best) / s_best
    verdict = ("exact_index" if best == got else
               "same_row" if best in winners else
               "tie_band" if gap <= krum.TIE_RTOL else "wrong_row")
    return {"ok": verdict != "wrong_row", "verdict": verdict, "mode": mode,
            "device_winner": got, "reference_winner": best,
            "identical_winner_rows": int(winners.size),
            "relative_score_gap": gap}


def _matrix(n=40, d=301, f=10, seed=0):
    G = np.random.default_rng(seed).standard_normal((n, d)).astype(
        np.float32)
    G[:f] = 3.0 * G[0]              # colluders send one row, far out
    return G, n, f


def _krum_cases():
    G, n, f = _matrix()
    want = int(np.argmin(krum.scores(G, np.arange(n), n - f)))
    worst = int(np.argmax(krum.scores(G, np.arange(n), n - f)))
    tie = G.copy()                  # the winner, sent by two clients
    other = (want + 1) % n if (want + 1) % n >= f else f
    tie[other] = tie[want]
    return {"winner": (G, G[want]), "wrong_row": (G, G[worst]),
            "tie": (tie, tie[other]), "colluders_row": (G, G[0]),
            "not_a_row": (G, G[want] + 1.0)}


@pytest.mark.parametrize("sampled", [False, True])
@pytest.mark.parametrize("case", ["winner", "wrong_row", "tie",
                                  "colluders_row", "not_a_row"])
def test_blocked_krum_check_is_the_whole_matrix_check(case, sampled,
                                                      monkeypatch):
    import jax.numpy as jnp

    # 7 columns of 40 rows to a block: 301 = 43 blocks, the last ragged
    monkeypatch.setattr(krum, "BLOCK_BYTES", 7 * 4 * 40)
    if sampled:
        monkeypatch.setattr(krum, "FULL_CHECK_ROWS", 8)
        monkeypatch.setattr(krum, "SAMPLED_ROWS", 16)
    G, agg = _krum_cases()[case]
    n, f = G.shape[0], 10
    assert [lo for lo, _ in krum.column_blocks(G)] == list(range(0, 301, 7))
    want = _whole_matrix_check(G, n, f, agg, seed=5)
    for matrix in (G, jnp.asarray(G)):       # a host and a device array
        got = krum.check(matrix, n, f, agg, seed=5)
        compared = got.pop("compared")
        gap = got.pop("relative_score_gap", None)
        want_gap = dict(want).pop("relative_score_gap", None)
        assert got == {k: v for k, v in want.items()
                       if k != "relative_score_gap"}
        if want_gap is None:
            assert gap is None
            assert compared == {"aggregate_not_an_input_row": [1, 0]}
        else:
            # the same f64 sums in another order: the distance of two
            # identical rows is the root of a rounding error, ~1e-6 on
            # scores of ~1e3, in either form
            assert gap == pytest.approx(want_gap, rel=1e-6, abs=1e-9)
            assert compared["defense_score_gap"] == [gap, krum.TIE_RTOL]
            assert got["ok"] == (gap <= krum.TIE_RTOL)
    assert want["ok"] == (case in ("winner", "tie"))
    if case == "tie":
        assert want["identical_winner_rows"] == 2
    if case == "colluders_row" and not sampled:
        assert want["identical_winner_rows"] == 10


def test_krum_reads_no_whole_matrix(monkeypatch):
    """No block the host pulls is wider than BLOCK_BYTES allows, and
    nothing under perfbench/ converts or widens an (n, d) array whole."""
    monkeypatch.setattr(krum, "BLOCK_BYTES", 64 * 4 * 40)
    G, n, f = _matrix()
    widths = [block.shape for _, block in krum.column_blocks(G)]
    assert max(w for _, w in widths) == 64 and sum(
        w for _, w in widths) == 301
    for root, _, files in os.walk(run.HERE):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as fh:
                    text = fh.read()
                assert "np.asarray(G)" not in text, name
                assert "G.astype(" not in text, name


# --- one wire matrix at a time ------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_at_most_one_wire_matrix_is_alive_after_each_phase(trace,
                                                           monkeypatch):
    import jax

    cell = run.load_cell("tiny_cpu", TINY)
    n, d = 16, 79_510
    seen, say = [], run.say

    def counting_say(*parts):
        alive = sum(1 for a in jax.live_arrays() if a.shape == (n, d))
        seen.append((parts[0], alive))
        say(*parts)

    monkeypatch.setattr(run, "say", counting_say)
    res = run.measure(cell, 2**31 + 77, 0.5, bool(trace))
    assert res["correct"] is True
    phases = [p for p, _ in seen]
    for phase in ("defense_check", "memory", "model_check", "checks",
                  "intervals") + (("spans", "span_hlo_text") if trace
                                  else ()):
        assert phase in phases
    by_phase = {}
    for phase, alive in seen:
        by_phase.setdefault(phase, []).append(alive)
    # the one matrix of the defense span and check while they use it;
    # none once it is released, and never two
    assert by_phase["defense_check"] == [1]
    if trace:
        assert by_phase["spans"] == [1]
    after = phases.index("defense_check") + 1
    assert all(alive == 0 for _, alive in seen[after:]), seen[after:]
    before = phases.index("spans") if trace else phases.index(
        "defense_check")
    assert all(alive == 0 for _, alive in seen[:before]), seen[:before]
    assert max(alive for _, alive in seen) <= 1
