#!/bin/bash
# Backdoor persistence triptych at the stable optimizer point
# (VERDICT r4 #2): the round-4 cells ran at the reference's lr 0.1 and
# two of three died in the lr-0.1 dead basin (Krum @ ~r90, Bulyan @
# ~r50), confounding the saturation-phase channel comparison.  The
# lr 0.05 control already converges cleanly and holds through round
# 149 (BASELINE.md round 4) — this re-runs all four cells there.
#
#   bash tools/triptych_lr005.sh
#
# Serial by design (one core); each cell ~40-60 min (the backdoor
# cells pay the per-defense shadow-train compile once, then 150
# rounds).  Logs: logs/triptych005_<cell>.log + the config-keyed JSONL
# the engine writes (lr 0.05 keys distinct files from the r4 runs).
set -u
cd "$(dirname "$0")/.."
mkdir -p logs
# Pin the CPU backend HERE, not in the caller's memory: this is a CPU
# behavioural study and must not land on an accelerator by default.
export JAX_PLATFORMS=cpu
COMMON="-s SYNTH_CIFAR10_HARD -e 150 -n 16 -m 0.2 -c 64 -l 0.05"

run_cell() {  # name, extra args...
  local name=$1; shift
  echo "=== triptych lr0.05 cell: $name ($(date +%T)) ==="
  timeout 7200 python -m attacking_federate_learning_tpu.cli \
    $COMMON "$@" -o "logs/triptych005_${name}.log"
  echo "=== $name done rc=$? ($(date +%T)) ==="
}

# Most-valuable-first: each finished cell is a banked artifact even if
# the round ends mid-script.  Krum carries the "immunity" claim, Bulyan
# the "no re-embed" claim; the control has a round-4 fallback
# (logs/convergence_control_lr005_r4.log, n=12) if time runs out.
run_cell krum_backdoor -d Krum -b pattern
run_cell bulyan_backdoor -d Bulyan -b pattern
run_cell trimmedmean_backdoor -d TrimmedMean -b pattern
# Control matches the triptych cohort (n=16) with no malicious
# clients; argparse takes the last -m, overriding COMMON's 0.2.
run_cell control_noattack -d TrimmedMean -m 0.0
echo "triptych lr0.05 complete"
