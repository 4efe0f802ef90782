"""First proof that the system starts on the chip: one process, the CLI's
own main path at the full width of the reference's model, checked by the
repo's own means.  ``python chip_smoke.py`` exits 0 only on a TPU.

Legs, all in THIS process (a chip belongs to one process; no child is
started):

1. main path — ``cli.main`` with ``--backend tpu``: ``mnist_mlp``
   (784-100-10, wire d = 79,510), flat Krum vs ALIE, n = 1,024 clients,
   24 % malicious, batch 64, ``SYNTH_MNIST`` named explicitly, 25 rounds
   (six evals), every other knob — the seed too — at the CLI's default.
   Passes when the run completes, the final weights are finite and of
   the wire shape, and the last two evals hold 90 % (models run at TPU
   default matmul precision, so this is a behavioural check, not bit
   parity with CPU).  The margin is thin on purpose: at default
   precision the default seed flips one Krum winner at round 5 against
   the f32 run and settles at 90.35 % where f32 reaches 100 % (PERF.md
   §6) — the trajectory a precision change would move, so it is the one
   to watch;
2. winner trail — the same CLI for 11 rounds with ``--round-stats``,
   printing Krum's winner round by round (a second short run, because
   the flag routes the defense through select + gather and leg 1 must
   stay the default program);
3. oracle — Krum's winner on the device-produced (n, d) matrix of one
   round against ``defenses/oracle.py`` on the host: exact index, or an
   ``adjudicate()`` verdict that the two selected rows are the same
   (colluders send bit-identical rows).

Any exception is a non-zero exit.  Without a TPU the first leg exits
non-zero with one line naming the backend it found, and nothing below is
printed.  The readings printed before the last line (compile seconds,
rounds/s, cache hits) are smoke readings of one run, not benchmark
metrics.  The last line of stdout is the result object.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

ROUNDS = 25
TRAIL_ROUNDS = 11
CLIENTS = 1024
MAL_PROP = 0.24
WIRE_DIM = 79_510
TARGET_ACCURACY = 90.0


def cli_argv(rounds, log_dir):
    return ["--backend", "tpu", "-s", "SYNTH_MNIST", "-d", "Krum",
            "-n", str(CLIENTS), "-m", str(MAL_PROP), "-c", "64",
            "-e", str(rounds), "--no-checkpoint", "--log-dir", log_dir,
            "--run-dir", os.path.join(log_dir, "runs")]


def events(log_dir, kind):
    (path,) = glob.glob(os.path.join(log_dir, "*.jsonl"))
    with open(path) as f:
        return [e for e in map(json.loads, f) if e.get("kind") == kind]


def main_path(log_dir):
    """Leg 1: the entry point a user calls, in-process."""
    from attacking_federate_learning_tpu import cli

    argv = cli_argv(ROUNDS, log_dir)
    t0 = time.perf_counter()
    result = cli.main(argv)
    wall = time.perf_counter() - t0

    weights = np.asarray(result["final_weights"])
    assert weights.shape == (WIRE_DIM,), weights.shape
    assert np.isfinite(weights).all(), "non-finite final weights"
    accuracies = result["accuracies"]
    assert len(accuracies) >= 2, result["epochs"]
    assert min(accuracies[-2:]) >= TARGET_ACCURACY, accuracies

    # Steady state from the run's own event log: the first eval closes
    # the span that compiled; every later span reuses that program.
    evals = events(log_dir, "eval")
    first, last = evals[0], evals[-1]
    return argv, {
        "wall_s": round(wall, 2),
        "first_eval_at_s": first["t"],
        "steady_rounds_per_s": round(
            (last["round"] - first["round"]) / (last["t"] - first["t"]), 2),
        "accuracies": [round(a, 2) for a in accuracies],
    }


def winner_trail(log_dir):
    """Leg 2: which client Krum picked, round by round, and how often it
    was a colluder — where a precision change shows first."""
    from attacking_federate_learning_tpu import cli

    result = cli.main(cli_argv(TRAIL_ROUNDS, log_dir) + ["--round-stats"])
    rounds = events(log_dir, "round")
    assert [r["round"] for r in rounds] == list(range(TRAIL_ROUNDS))
    return {
        "krum_selected": [int(r["krum_selected"]) for r in rounds],
        "malicious_selected": int(sum(r["malicious_selected"]
                                      for r in rounds)),
        "accuracies": [round(a, 2) for a in result["accuracies"]],
    }


def oracle_leg(argv):
    """Leg 3: the device's Krum winner vs the NumPy oracle on one
    round's post-attack gradient matrix (the tests' own construction,
    tests/test_engine.py)."""
    import jax

    from attacking_federate_learning_tpu import cli
    from attacking_federate_learning_tpu.attacks import make_attacker
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset
    from attacking_federate_learning_tpu.defenses.kernels import krum_select
    from attacking_federate_learning_tpu.defenses.oracle import (
        np_krum_select
    )
    from attacking_federate_learning_tpu.utils.numerics import adjudicate

    cfg = cli.config_from_args(cli.build_parser().parse_args(argv))
    ds = load_dataset(cfg.dataset, cfg.data_dir, cfg.seed,
                      synth_train=cfg.synth_train, synth_test=cfg.synth_test)
    exp = FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                              dataset=ds)

    @jax.jit
    def wire_matrix(state):
        grads = exp._compute_grads_impl(state, 0)
        return exp.attacker.apply(grads, exp.m_mal, exp._ctx_for(state, 0))

    G = wire_matrix(exp.state)
    n, f = exp.m, exp.m_mal
    got = int(jax.jit(krum_select, static_argnums=(1, 2))(G, n, f))

    G_host = np.asarray(G)
    assert G_host.shape == (CLIENTS, WIRE_DIM) and np.isfinite(G_host).all()
    # The oracle's own distance builder is O(n^2 d) memory; hand it the
    # same distances from an f64 Gram instead (exact at these sizes).
    G64 = G_host.astype(np.float64)
    sq = np.einsum("nd,nd->n", G64, G64)
    D = np.sqrt(np.maximum(sq[:, None] + sq[None, :] - 2.0 * (G64 @ G64.T),
                           0.0))
    want = np_krum_select(G64, n, f, D=D)
    verdict = ("exact_index" if got == want else
               adjudicate(G_host[got], G_host[want], G64[want])["verdict"])
    assert verdict in ("exact_index", "exact", "tie_band"), (got, want,
                                                             verdict)
    return {"device_winner": got, "oracle_winner": want, "verdict": verdict,
            "malicious_rows": f}


def main():
    import jax

    from attacking_federate_learning_tpu.utils.backend import require_tpu
    from attacking_federate_learning_tpu.utils.costs import (
        cache_counts, compile_log
    )

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as log_dir:
        argv, run = main_path(log_dir)
        device = require_tpu("chip_smoke.py")
        print(f"[smoke] jax {jax.__version__} on {device['platform']} "
              f"({device['device_kind']} x{device['count']})", flush=True)
        trail = winner_trail(os.path.join(log_dir, "trail"))
    oracle = oracle_leg(argv)

    compiles = compile_log()
    span = [c for c in compiles if "span" in (c["name"] or "")]
    print("[smoke] readings (one run; not benchmark metrics): " + json.dumps({
        "main_path": run, "winner_trail": trail, "oracle": oracle,
        "compile_s_total": round(sum(c["compile_s"] for c in compiles), 2),
        "compile_span": span,
        "compile_slowest": sorted(compiles, key=lambda c: -c["compile_s"])[:5],
        "compile_cache": cache_counts(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "claim": None}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["count"]}}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
