#!/usr/bin/env python
"""Supervisor crash-matrix smoke: seeded kill x dispatch mode x defense.

For every cell of {fused span, staged per-round, faulted span} x two
distance defenses, a supervised run (tools/supervisor.py) is preempted
at a random-but-SEEDED round (the FL_PREEMPT_AT_ROUND injection seam —
deterministic, so a failing cell replays exactly), resumed by the
supervisor, and then audited:

1. the supervisor exits clean (0) with bounded attempts — exactly one
   preempt resume, zero retry-budget charges;
2. the per-run journal covers every round and eval exactly once across
   the two attempts (utils/lifecycle.py:RunJournal.verify — the
   supervisor's --verify-journal enforces it in-band, and the matrix
   re-audits out-of-band);
3. the supervisor's own lifecycle event stream validates against the
   v3 schema and records the expected transitions.

The 'staged' cells run the real staged dispatch (pattern backdoor +
--backdoor-staged: per-round host boundaries, the reference's nan-guard
seam), so the preempt/resume contract is exercised on both sides of
the fused/staged split; the 'faulted' cells thread the straggler ring
buffer through the kill (Checkpointer ``extra``).

Usage:
    python tools/crash_matrix.py                 # full matrix
    python tools/crash_matrix.py --seed 7 --epochs 6

Exit status 0 when every cell passes, 1 otherwise.  CPU-pinned (this
must never race a TPU capture); CI-wired via tools/smoke.sh and
tests/test_supervisor.py.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"         # children inherit: CPU only

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from attacking_federate_learning_tpu.utils.lifecycle import (  # noqa: E402
    RunJournal
)
from attacking_federate_learning_tpu.utils.metrics import (  # noqa: E402
    iter_events
)

MODES = {
    # mode -> extra child flags (the dispatch-path axis)
    "fused": [],
    "staged": ["-b", "pattern", "--backdoor-staged"],
    "faulted": ["--fault-dropout", "0.2", "--fault-straggler", "0.1"],
}


def _load_supervisor():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "supervisor.py")
    spec = importlib.util.spec_from_file_location("supervisor", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(sup, mode, defense, kill_round, epochs, workdir):
    """One supervised preempt/resume cycle; returns a list of problem
    strings (empty = cell passed)."""
    cell = f"{mode}_{defense}"
    run_dir = os.path.join(workdir, cell, "runs")
    log_dir = os.path.join(workdir, cell, "logs")
    run_id = f"crash_{cell}"
    events = os.path.join(log_dir, "supervisor.jsonl")
    child = ["--backend", "cpu", "-s", "SYNTH_MNIST", "-e", str(epochs),
             "-c", "16", "--synth-train", "256", "--synth-test", "64",
             "-d", defense, "--run-dir", run_dir, "--log-dir", log_dir,
             ] + MODES[mode]
    rc = sup.main(["--inject-preempt-round", str(kill_round),
                   "--verify-journal", "--checkpoint-every", "2",
                   "--max-retries", "2", "--run-id", run_id,
                   "--events", events, "--"] + child)
    problems = []
    if rc != 0:
        problems.append(f"supervisor exit {rc} (want 0)")
    journal = RunJournal(run_dir, run_id)
    problems += journal.verify(epochs=epochs, test_step=5)
    man = journal.read_manifest() or {}
    if man.get("status") != "done":
        problems.append(f"manifest status {man.get('status')!r} "
                        f"(want 'done')")
    if man.get("attempt") != 2:
        problems.append(f"attempts {man.get('attempt')} (want exactly 2: "
                        f"one preempt + one resume)")
    # The supervisor's own stream: v3-valid, expected transitions only.
    sup_events = list(iter_events(events))
    phases = [e["phase"] for e in sup_events]
    if phases.count("retry") != 1:
        problems.append(f"supervisor retries {phases.count('retry')} "
                        f"(want exactly 1, the preempt resume)")
    retries = [e for e in sup_events if e["phase"] == "retry"]
    if retries and retries[0].get("failure") != "preempted":
        problems.append(f"retry classified {retries[0].get('failure')!r} "
                        f"(want 'preempted')")
    if "supervise_done" not in phases:
        problems.append("no supervise_done transition")
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Supervised preempt/resume crash matrix "
                    "(seeded kill round x dispatch mode x defense).")
    p.add_argument("--seed", default=0, type=int,
                   help="kill-round seed (deterministic replay)")
    p.add_argument("--epochs", default=6, type=int)
    p.add_argument("--modes", default="fused,staged,faulted")
    p.add_argument("--defenses", default="Krum,TrimmedMean")
    p.add_argument("--workdir", default=None,
                   help="cell run/log root (default: a temp dir)")
    args = p.parse_args(argv)

    import numpy as np

    rng = np.random.default_rng(args.seed)
    sup = _load_supervisor()
    workdir = args.workdir or tempfile.mkdtemp(prefix="crash_matrix_")
    failed = 0
    for mode in args.modes.split(","):
        for defense in args.defenses.split(","):
            # Seeded-but-random kill point strictly inside the run, so
            # the preempt boundary is never the trivial first/last one.
            kill_round = int(rng.integers(1, args.epochs - 1))
            problems = run_cell(sup, mode, defense, kill_round,
                                args.epochs, workdir)
            tag = f"{mode:8s} {defense:12s} kill@{kill_round}"
            if problems:
                failed += 1
                print(f"FAIL {tag}")
                for msg in problems:
                    print(f"     - {msg}")
            else:
                print(f"ok   {tag}")
    print(json.dumps({"crash_matrix": "FAIL" if failed else "ok",
                      "cells_failed": failed, "seed": args.seed,
                      "workdir": workdir}))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
