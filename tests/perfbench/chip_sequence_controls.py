"""Controls of the sequence cell's ``correct`` at the cell's own size, on the
chip (never collected by pytest: no ``test_`` prefix; run through the chip
tool):

    python3 tests/perfbench/chip_sequence_controls.py [--seed N] [--rounds N]
    python3 tests/perfbench/chip_sequence_controls.py --gram-only

On freshly initialized weights of ``smallthinker_21b_a3b_ep8`` (or, with
``--rounds N``, after N rounds of the cell's own federation: the state the
benchmark judges is a trained one) it runs the configuration's own comparison
(``configs/smallthinker_21b_a3b_ep8.py:compare``, what ``check`` calls) of
the program against

- ``reference``: the reference as written.  Has to pass.
- ``router_reads_rms2``: the reference whose router reads rms2's output.
  Has to fail.
- ``bfloat16``: the reference computed in bfloat16, parameters and
  activations, the nearest precision below the configuration's.  Has to fail.
- ``window_4095`` (only with ``--window``): one key of 4,096 less a query;
  reported, not judged: at one bf16 pass a matmul the program's own rounding
  is larger than what that key moves.

Then (without ``--skip-gram``) the self-Gram of a random bf16 (8, d) matrix
of correlated rows two ways, as one dot over all d columns and summed over column blocks
(ops/distances.py), each against a float64 host Gram read in column blocks as
``defenses/krum.py`` reads it: the largest relative error of a Krum score, to
be held against ``TIE_RTOL``.  Prints one JSON line a reading and exits
non-zero if a control lands on the wrong side."""

import argparse
import functools
import gc
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "smallthinker_krum_alie_l8192"


def build(seed, extra_argv=(), cell=CELL, root=None):
    """The cell's experiment as ``run.py`` builds it (``extra_argv`` after
    the cell's own flags: argparse keeps the last of a repeated flag;
    ``cell`` / ``root``: the CPU tests' tiny cell)."""
    from attacking_federate_learning_tpu import cli
    from attacking_federate_learning_tpu.attacks import make_attacker
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset
    from perfbench import run

    cell = run.load_cell(cell, *([root] if root else []))
    argv = run.cell_argv(cell, seed, tempfile.mkdtemp(
        prefix="chip_sequence_controls_")) + list(extra_argv)
    cfg = cli.config_from_args(cli.build_parser().parse_args(argv))
    dataset = load_dataset(cfg.dataset, cfg.data_dir,
                           int(cell["config_file"]["dataset_seed"]),
                           synth_train=cfg.synth_train,
                           synth_test=cfg.synth_test)
    exp = FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=dataset),
                              dataset=dataset)
    return exp, dataset


def controls(exp, dataset, seed, label, window=False):
    """One JSON line a control on ``exp``'s current weights; True where
    every control landed on its side."""
    import jax.numpy as jnp

    from perfbench.configs import smallthinker_21b_a3b_ep8 as ref

    sizes = ref.sizes_for(exp)
    forward = functools.partial(ref.forward, s=sizes)
    wanted = [("reference", forward, True),
              ("router_reads_rms2", functools.partial(
                  forward, router_input="experts"), False),
              ("bfloat16", functools.partial(
                  forward, dtype=jnp.bfloat16), False)]
    if window:
        wanted.append(("window_4095", functools.partial(
            forward, window=sizes["window"] - 1), None))
    good = True
    for name, apply, want_ok in wanted:
        t0 = time.perf_counter()
        got = ref.compare(exp, apply, exp.state.weights, dataset, seed)
        print(json.dumps(dict(got, control=name, state=label, want_ok=want_ok,
                              seconds=time.perf_counter() - t0)), flush=True)
        if want_ok is not None and got["ok"] != want_ok:
            good = False
    return good


def gram_readings(n, f, d, seed, reference=True):
    """The wide self-Gram as one dot and as the column-block sum: the
    largest relative gap of a Krum score between the two and, with
    ``reference``, of each against float64."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from attacking_federate_learning_tpu.ops import distances
    from perfbench.defenses import krum

    @jax.jit
    def matrix(key):
        # Rows as a round's are: a common part as large as a row's own (the
        # clients descend one loss, cosine 0.5), so every Gram entry is a
        # long sum of mostly positive products, and two colluders send one
        # row.
        own, common = jax.random.split(key)
        G = 1e-3 * (jax.random.normal(own, (n, d), jnp.bfloat16)
                    + jax.random.normal(common, (1, d), jnp.bfloat16))
        row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        return jnp.where(row == 1, G[0][None, :], G)

    off = ~np.eye(n, dtype=bool)
    off[0, 1] = off[1, 0] = False       # the colluders' own distance: 0

    def score(M):
        return np.sort(np.where(off, M, np.inf), 1)[:, :n - f - 1].sum(1)

    def gap(a, b):
        return float((np.abs(score(a) - score(b)) / score(b)).max())

    G = matrix(jax.random.key(seed % 2**31))
    forms = {"one_dot": lambda G: distances.cross_sq_distances(G, G),
             "column_blocks": distances._wide_sq_distances}
    D = {name: np.sqrt(np.asarray(jax.jit(fn)(G), np.float64))
         for name, fn in forms.items()}
    out = {"control": "wide_gram", "tie_rtol": krum.TIE_RTOL,
           "one_dot_against_column_blocks": gap(D["one_dot"],
                                                D["column_blocks"])}
    if reference:
        sq, cross = np.zeros(n), np.zeros((n, n))
        for _, block in krum.column_blocks(G):
            B = block.astype(np.float64)
            sq += np.einsum("nd,nd->n", B, B)
            cross += B @ B.T
        want = np.sqrt(np.maximum(sq[:, None] + sq[None] - 2 * cross, 0))
        for name in forms:
            out[name + "_against_float64"] = gap(D[name], want)
    print(json.dumps(out), flush=True)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=2147490123)
    p.add_argument("--skip-gram", action="store_true")
    p.add_argument("--gram-only", action="store_true",
                   help="the wide Gram's readings alone, at the cell's n, f "
                        "and d")
    p.add_argument("--window", action="store_true",
                   help="also read the window-of-4,095 control")
    p.add_argument("--rounds", type=int, default=0,
                   help="rounds of the cell's federation to train before "
                        "the controls (0: freshly initialized weights)")
    a = p.parse_args()

    from attacking_federate_learning_tpu.utils.backend import (
        enable_compile_cache, require_tpu
    )

    require_tpu("chip_sequence_controls")
    enable_compile_cache()
    if a.gram_only:
        from perfbench.configs import smallthinker_21b_a3b_ep8 as ref

        gram_readings(8, 2, ref.WIRE_DIM, a.seed)
        return
    exp, dataset = build(a.seed)
    if a.rounds:
        exp.run_span(0, a.rounds)
    good = controls(exp, dataset, a.seed, f"round_{a.rounds}", a.window)
    if not a.skip_gram:
        n, f, d = exp.m, exp.m_mal, exp.flat.dim
        exp.state = None            # the matrix needs the state's room
        del exp
        gc.collect()
        gram_readings(n, f, d, a.seed)
    sys.exit(0 if good else 1)


if __name__ == "__main__":
    main()
