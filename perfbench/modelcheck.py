"""The two forms of a configuration's model check.  ``configs/<name>.py``
provides ``check(exp, weights, dataset, seed) -> {"ok": bool, ...}`` and
writes it with one of these; the runner prints the dict on the
``model_check`` line and takes ``ok`` for ``eval_agrees_with_reference``.
``compared`` in the dict is ``{name: [value, limit]}``: each number the
verdict rests on beside its limit.

``exp`` is the live experiment after the window: the checks read only
``exp.evaluate``, ``exp.model.apply``, ``exp.flat.unravel`` and
``exp.state.weights``; ``weights`` is the same (d,) vector on the host.
"""

import numpy as np

# The program evaluates at the TPU's default matmul precision (bf16 passes)
# and the plain reference in f64, so a test sample whose top two logits lie
# within bf16 rounding may flip: half a percent of the test set covers that.
# A wrong layout, a dropped bias or another activation moves tens of percent.
EVAL_COUNT_RTOL = 0.005


def count_check(exp, logits, weights, dataset):
    """A classifier's form: the program's count of correct test samples on
    the final weights against the count the plain reference ``logits(w,
    x)`` (NumPy, f64, the whole test set) gives."""
    _, correct_dev = exp.evaluate(exp.state.weights)
    predicted = np.argmax(logits(weights, np.asarray(dataset.test_x)), axis=1)
    correct_ref = int((predicted == np.asarray(dataset.test_y)).sum())
    test_size = len(dataset.test_y)
    gap, limit = abs(correct_ref - int(correct_dev)), EVAL_COUNT_RTOL * test_size
    return {"ok": gap <= limit, "reference_correct": correct_ref,
            "test_size": test_size, "device_correct": int(correct_dev),
            "compared": {"eval_count_gap": [gap, limit]}}


def logits_check(exp, reference_apply, weights, dataset, seed, count, block,
                 atol, rtol):
    """A sequence model's form: logits against logits.  ``count`` inputs
    drawn from the test set by ``seed``, at the shape the timed path
    evaluates; the program's own forward (``exp.model.apply`` on
    ``exp.flat.unravel(weights)``, jitted, as ``exp.evaluate`` runs it)
    against ``reference_apply(w, x)``: the configuration's plain forward in
    ``jax.numpy`` f32, which returns what ``model.apply`` returns and is run
    here under ``jax.default_matmul_precision("highest")`` in blocks of
    ``block`` inputs, so that it fits beside the live state (it may run on
    the chip).  Every output element has to lie within ``atol + rtol *
    |reference|``; the configuration writes both with the reason for each,
    tight enough that the forward in the next lower precision fails.
    ``gap_over_limit`` is the worst element's gap over its own limit."""
    import jax
    import jax.numpy as jnp

    test_x = np.asarray(dataset.test_x)
    picks = np.random.default_rng(seed).choice(
        len(test_x), size=min(count, len(test_x)), replace=False)
    w = jnp.asarray(weights)
    program = jax.jit(lambda w, x: exp.model.apply(exp.flat.unravel(w), x))
    reference = jax.jit(reference_apply)
    worst = {"gap_over_limit": 0.0, "abs_gap": 0.0, "reference": 0.0}
    finite = True
    for lo in range(0, len(picks), block):
        x = jnp.asarray(test_x[np.sort(picks[lo:lo + block])])
        got = np.asarray(program(w, x), np.float64)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference(w, x), np.float64)
        if got.shape != want.shape:
            return {"ok": False, "why": f"program gives {got.shape}, the "
                                        f"reference {want.shape}"}
        finite = finite and bool(np.isfinite(got).all()
                                 and np.isfinite(want).all())
        over = np.nan_to_num(np.abs(got - want) / (atol + rtol * np.abs(want)),
                             nan=1e30, posinf=1e30)
        at = np.unravel_index(np.argmax(over), over.shape)
        if over[at] >= worst["gap_over_limit"]:
            worst = {"gap_over_limit": float(over[at]),
                     "abs_gap": float(abs(got[at] - want[at])),
                     "reference": float(want[at])}
    ok = finite and worst["gap_over_limit"] <= 1.0
    return dict(worst, ok=ok, finite=finite, inputs=int(len(picks)),
                atol=atol, rtol=rtol,
                compared={"logit_gap_over_limit":
                          [worst["gap_over_limit"], 1.0]})
