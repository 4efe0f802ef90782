#!/usr/bin/env python
"""Stage-ledger capture: profiled flat + hierarchical rounds with the
ISSUE-15 stage scopes live.

The stage set (utils/costs.py:STAGES — deliver → quarantine →
protect → tier1_aggregate → tier2_aggregate → apply) is threaded
through the engines as ``jax.named_scope`` annotations.  This tool is
the capture leg for that instrument:

- static: per-stage FLOP/byte attribution of the compiled flat and
  hierarchical round programs (utils/costs.py:stage_attribution) plus
  the per-seam wire ledger — the numbers the perf gate's --stageproof
  pins on CPU, re-derived on the live backend;
- profiled: one short span of real rounds per topology under
  ``jax.profiler.trace`` — because the scopes are named_scope
  annotations, the device profile's op breakdown carries the same
  stage tokens, so the trace in ``--trace-dir`` is attributable to the
  stage set by name.

``--rehearse`` pins the CPU backend first: same steps, same JSON
lines, profiler trace included.  Without it the live device set is
used (one process per chip).

Prints one JSON line per cell (flat, hier) on stdout; diagnostics on
stderr.  A cell failure banks an ``error`` record, the remaining cells
still run, and the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


CELLS = {
    "flat": dict(defense="Krum"),
    "hier": dict(defense="Krum", aggregation="hierarchical",
                 users_count=64, mal_prop=0.25, megabatch=8,
                 tier2_defense="Krum"),
}


def run_cell(tag: str, overrides: dict, rounds: int,
             trace_root: str | None) -> dict:
    import jax
    import jax.numpy as jnp

    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.attacks import DriftAttack
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset
    from attacking_federate_learning_tpu.utils.costs import (
        compiled_cost_facts, stage_attribution, stage_scopes_enabled
    )

    base = dict(
        dataset=C.SYNTH_MNIST, users_count=16, mal_prop=0.25,
        batch_size=16, epochs=max(rounds, 2), test_step=max(rounds, 2),
        seed=0, synth_train=512, synth_test=64)
    base.update(overrides)
    cfg = ExperimentConfig(**base)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=base["synth_train"],
                      synth_test=64)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.5), dataset=ds)
    rec = {"tool": "stage_profile", "cell": tag,
           "platform": jax.devices()[0].platform,
           "n_devices": len(jax.devices()),
           "stage_scopes_enabled": stage_scopes_enabled(),
           "defense": cfg.defense, "aggregation": cfg.aggregation,
           "cohort": exp.m, "d": exp.flat.dim}

    t0 = time.perf_counter()
    compiled = exp._fused_round.lower(
        exp.state, jnp.asarray(0, jnp.int32)).compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 2)
    facts = compiled_cost_facts(compiled)
    att = stage_attribution(compiled.as_text(), facts)
    rec["coverage"] = {k: round(v, 4) for k, v in att["coverage"].items()}
    rec["stage_flops"] = {s: v["flops"] for s, v in att["stages"].items()}
    rec["stage_bytes"] = {s: v["bytes_accessed"]
                          for s, v in att["stages"].items()}
    rec["unattributed_flops"] = att["unattributed"]["flops"]
    rec["wire"] = exp.wire_ledger()
    if cfg.aggregation == "hierarchical":
        # The PR-12 identity the --stageproof gate pins statically,
        # restated on the live backend's compiled program.
        S = exp._placement.num_shards
        rec["tier1_to_tier2_S_d_4"] = S * exp.flat.dim * 4

    trace_dir = None
    if trace_root:
        trace_dir = os.path.join(trace_root, tag)
        os.makedirs(trace_dir, exist_ok=True)
    ctx = (jax.profiler.trace(trace_dir) if trace_dir
           else contextlib.nullcontext())
    t0 = time.perf_counter()
    with ctx:
        for t in range(rounds):
            exp.run_round(t)
        jax.block_until_ready(exp.state.weights)
    rec["rounds"] = rounds
    rec["wall_s"] = round(time.perf_counter() - t0, 3)
    rec["trace_dir"] = trace_dir

    if trace_dir:
        # ISSUE 16 cross-check: book the capture just taken against
        # the SAME compiled program the static attribution above
        # priced (utils/walls.py — instruction-name join).  The two
        # ledgers must tell one story: booked partition exact, booked
        # op time inside the host wall, and every stage the static
        # side attributes flops to either appears in the booking or is
        # explicitly absent (a capture missing op events — xprof flag
        # unset — reports walls_verdict='no-op-events' loudly instead
        # of a vacuous pass).
        from attacking_federate_learning_tpu.utils import walls
        wrec = walls.book_trace(trace_dir, compiled.as_text(),
                                name=tag,
                                platform=rec["platform"],
                                rounds=rounds)
        if wrec is None:
            rec["walls_verdict"] = "no-trace-file"
        elif wrec.coverage.get("op_events", 0) == 0:
            rec["walls_verdict"] = "no-op-events"
        else:
            wrec.check()                         # exact partition
            rec["walls"] = {
                "stages": {s: round(v, 3)
                           for s, v in wrec.stages.items()},
                "unattributed_us": round(wrec.unattributed_us, 3),
                "substages": {s: round(v, 3)
                              for s, v in wrec.substages.items()},
                "op_time_fraction":
                    wrec.coverage.get("op_time_fraction"),
            }
            booked_s = wrec.total_us / 1e6
            problems = []
            if booked_s > rec["wall_s"] * 1.05:
                problems.append(
                    f"booked {booked_s:.3f}s exceeds host wall "
                    f"{rec['wall_s']:.3f}s")
            for s, fl in rec["stage_flops"].items():
                if fl > 0 and s not in wrec.stages:
                    problems.append(f"stage {s} carries modeled flops "
                                    f"but booked no wall time")
            rec["walls_verdict"] = ("ok" if not problems
                                    else "; ".join(problems))
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Profiled flat + hier rounds with stage scopes "
                    "live; per-stage static attribution + wire ledger")
    ap.add_argument("--rehearse", action="store_true",
                    help="pin the CPU backend")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--trace-dir", default="logs/stage_profile_trace",
                    help="jax.profiler trace root ('' disables)")
    args = ap.parse_args(argv)

    if args.rehearse:
        from attacking_federate_learning_tpu.utils.backend import (
            select_platform
        )

        select_platform("cpu")

    # Op-level trace events need the xprof flag before this process's
    # FIRST compile (XLA parses XLA_FLAGS once); without it the
    # booking cross-check reports walls_verdict='no-op-events'.
    from attacking_federate_learning_tpu.utils.profiling import (
        ensure_op_profiling
    )
    ensure_op_profiling()

    failed = False
    t_start = time.perf_counter()
    for tag, overrides in CELLS.items():
        t_cell = time.perf_counter()
        try:
            rec = run_cell(tag, overrides, args.rounds,
                           args.trace_dir or None)
        except Exception as e:       # noqa: BLE001 — bank the error,
            # keep the remaining cells, exit non-zero below
            rec = {"tool": "stage_profile", "cell": tag, "error":
                   f"{type(e).__name__}: {e}"}
            failed = True
        print(json.dumps(rec), flush=True)
        # A stalled cell is visible in the log even when an outer
        # timeout kills the tool.
        print(f"[budget] stage_profile.{tag}: "
              f"{time.perf_counter() - t_cell:.1f}s (cum "
              f"{time.perf_counter() - t_start:.1f}s)",
              file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
