"""Controls of the latent-attention cell's ``correct`` at the cell's own
size, on one TPU chip (never collected by pytest: no ``test_`` prefix):

    python3 tests/perfbench/chip_mla_controls.py [--seed N] [--rounds N]

On freshly initialized weights of ``kanana_2_30b_a3b_ep16`` and, with
``--rounds N``, again after N rounds of the cell's own federation (the
state the benchmark judges is a trained one) it runs the configuration's own
comparison (``configs/kanana_2_30b_a3b_ep16.py:token_gaps`` and ``judge``,
what ``check`` calls) of the program against

- ``reference``: the reference as written.  Has to pass.
- ``bfloat16``: the reference computed in bfloat16, parameters and
  activations, the nearest precision below the configuration's.  Has to fail.
- ``no_selection_bias``: the reference whose router selects without the
  selection bias.  Has to fail.

and the training comparison (``program_row``, ``grad_gaps`` and
``judge_grads``) of one silo's wire row against the gradient of

- ``reference``: the reference's loss as written.  Has to pass.
- ``bfloat16``: the reference computed in bfloat16.  Reported, not judged:
  the forward's limit is the one a lower precision fails.
- ``zero_row``: the reference, against a row left at zero (a state no
  client moves).  Has to fail.
- ``half_context``: the loss over the first half of the context's tokens
  (a silo that trains on half its batch).  Has to fail.
- ``another_context``: the loss on another training context than the one
  the silo drew.  Has to fail.
- ``scale_of_256``: the scores scaled by 1/sqrt(256), the padded q.k
  width's, not 1/sqrt(192) (q's pieces scaled by sqrt(192 / 256)).  Has
  to fail.
- ``no_rope_part``: the scores without the part with positions (q's rope
  columns zeroed).  Has to fail.

Prints one JSON line a reading (with the per-token gap's quantiles and each
piece's relative gap, from which the limits are set) and exits non-zero if
a control lands on the wrong side."""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import chip_sequence_controls  # noqa: E402  (puts the repo on sys.path)

CELL = "kanana_krum_alie_l8192"


def controls(exp, dataset, seed, label):
    """One JSON line a control on ``exp``'s current weights; True where
    every control landed on its side."""
    import jax.numpy as jnp

    from perfbench.configs import kanana_2_30b_a3b_ep16 as ref

    sizes = ref.sizes_for(exp)
    forward = functools.partial(ref.forward, s=sizes)
    no_bias = np.zeros_like(ref.selection_bias(sizes))
    wanted = [("reference", forward, True),
              ("bfloat16", functools.partial(forward, dtype=jnp.bfloat16),
               False),
              ("no_selection_bias", functools.partial(forward, bias=no_bias),
               False)]
    good = True
    for name, apply, want_ok in wanted:
        t0 = time.perf_counter()
        gaps, finite = ref.token_gaps(exp, apply, exp.state.weights, dataset,
                                      seed)
        got = ref.judge(gaps, finite)
        got["token_gap_quantiles"] = {
            str(q): float(np.quantile(gaps, q))
            for q in (0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0)}
        print(json.dumps(dict(got, control=name, state=label,
                              want_ok=want_ok,
                              seconds=time.perf_counter() - t0)), flush=True)
        good = good and got["ok"] == want_ok
    return good


def ref_module():
    from perfbench.configs import kanana_2_30b_a3b_ep16 as ref
    return ref


def scaled_q(w, s, keep_nope, rope_by):
    """``w`` with each run's q piece's columns scaled: the part without
    positions by ``keep_nope``, the part with them by ``rope_by``."""
    import jax.numpy as jnp

    at = 0
    for name, shape in ref_module().shapes_of(s):
        size = int(np.prod(shape))
        if name.endswith(".q"):
            scale = jnp.concatenate([
                jnp.full(s["nope"], keep_nope, jnp.float32),
                jnp.full(s["rope"], rope_by, jnp.float32)])
            piece = w[at:at + size].reshape(shape[:-1] + (s["heads"], -1))
            w = w.at[at:at + size].set((piece * scale).reshape(-1))
        at += size
    return w


def train_controls(exp, dataset, seed, label):
    """One JSON line a training control on ``exp``'s current state; True
    where every judged control landed on its side."""
    import jax.numpy as jnp

    ref = ref_module()
    s = ref.sizes_for(exp)
    row = seed % int(exp.m)
    g, x, y = ref.program_row(exp, row)
    w = exp.state.weights
    other = int(np.argmax([not np.array_equal(c, np.asarray(x[0]))
                           for c in np.asarray(dataset.train_x[:2])]))
    x2 = jnp.asarray(dataset.train_x[other:other + 1])
    y2 = jnp.asarray(dataset.train_y[other:other + 1])
    r = (192 / 256) ** 0.5
    half = (jnp.arange(y.shape[-1]) < y.shape[-1] // 2)[None].astype(
        jnp.float32)
    wanted = [
        ("reference", lambda: ref.grad_gaps(w, g, x, y, s), True),
        ("bfloat16", lambda: ref.grad_gaps(w, g, x, y, s, jnp.bfloat16),
         None),
        ("zero_row", lambda: ref.grad_gaps(w, jnp.zeros_like(g), x, y, s),
         False),
        ("half_context", lambda: ref.grad_gaps(w, g, x, y, s, weights=half),
         False),
        ("another_context", lambda: ref.grad_gaps(w, g, x2, y2, s), False),
        ("scale_of_256", lambda: ref.grad_gaps(scaled_q(w, s, r, r), g, x,
                                               y, s), False),
        ("no_rope_part", lambda: ref.grad_gaps(scaled_q(w, s, 1.0, 0.0), g,
                                               x, y, s), False)]
    good = True
    for name, read, want_ok in wanted:
        t0 = time.perf_counter()
        per_piece = read()
        got = ref.judge_grads(per_piece, s)
        got["pieces"] = {k: (d / n) ** 0.5 if n else None
                         for k, (d, n) in per_piece.items()}
        print(json.dumps(dict(got, control=f"train_{name}", state=label,
                              row=row, want_ok=want_ok,
                              seconds=time.perf_counter() - t0)), flush=True)
        good = good and want_ok in (None, got["ok"])
    return good


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=2147490123)
    p.add_argument("--rounds", type=int, default=0,
                   help="rounds of the cell's federation to train before "
                        "the second reading (0: fresh weights only)")
    p.add_argument("--only", choices=["forward", "train"], default=None,
                   help="one of the two comparisons' controls")
    a = p.parse_args()

    from attacking_federate_learning_tpu.utils.backend import (
        enable_compile_cache, require_tpu
    )

    require_tpu("chip_mla_controls")
    enable_compile_cache()
    exp, dataset = chip_sequence_controls.build(a.seed, cell=CELL)

    def read(label):
        good = True
        if a.only != "train":
            good = controls(exp, dataset, a.seed, label) and good
        if a.only != "forward":
            good = train_controls(exp, dataset, a.seed, label) and good
        return good

    good = read("round_0")
    if a.rounds:
        exp.run_span(0, a.rounds)
        good = read(f"round_{a.rounds}") and good
    sys.exit(0 if good else 1)


if __name__ == "__main__":
    main()
