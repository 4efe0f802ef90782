"""Krum/Bulyan behavior under femnist_style feature shift vs IID.

The behavioral evidence row for the 'femnist_style' partitioner
(SURVEY §7.2 M4: FEMNIST-style non-IID): with per-client input style
transforms, HONEST clients' gradients acquire systematic structure —
their pairwise distances are no longer exchangeable noise — which is
the condition distance-based defenses are sensitive to.  Label-skew
(Dirichlet) alone never produces this on class-balanced synth data.

Measured: the 30-round selection histogram (distinct winners, top-1
share, malicious picks) and final accuracy, iid vs femnist_style, for
Krum and Bulyan.  Results land in GRID_RESULTS.md.

Instrumentation: this study used to hand-roll its selection histogram
from per-round ``last_round_stats``; it now IS one telemetry run
(cfg.telemetry) — the engine writes per-round 'defense' events + the
end-of-run 'selection_hist' to the run JSONL, and the concentration
numbers come from report.selection_concentration, the same code path as
``python -m attacking_federate_learning_tpu.cli report``.  Bulyan rows
gain a selection-mass concentration (multi-hot masks) the old
Krum-winner instrumentation could not see.

Run (CPU):  JAX_PLATFORMS=cpu \
            python tools/femnist_style_study.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cell(defense, part, strength=0.5, rounds=30, log_dir="logs"):
    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu import report
    from attacking_federate_learning_tpu.attacks import make_attacker
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset
    from attacking_federate_learning_tpu.utils.metrics import RunLogger

    cfg = ExperimentConfig(
        dataset=C.SYNTH_MNIST_HARD, users_count=19, mal_prop=0.2,
        batch_size=64, epochs=rounds, test_step=rounds, defense=defense,
        partition=part, style_strength=strength, telemetry=True,
        log_dir=log_dir)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=8000,
                      synth_test=2000)
    exp = FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                              dataset=ds)
    jsonl_name = f"femnist_study_{defense}_{part}"
    jsonl_path = os.path.join(log_dir, jsonl_name + ".jsonl")
    if os.path.exists(jsonl_path):
        os.remove(jsonl_path)  # RunLogger appends; one study = one log
    with RunLogger(cfg, None, log_dir, jsonl_name=jsonl_name) as logger:
        result = exp.run(logger)

    out = {"defense": defense, "partition": part,
           "final_acc": round(result["accuracies"][-1], 2),
           "jsonl": jsonl_path}
    sel = report.selection_concentration(report.load_events([jsonl_path]))
    if sel:
        out.update(
            distinct_winners=sel["distinct_winners"],
            top1_share=round(sel["top1_share"], 3),
            top1_client=sel["top1_client"],
            malicious_share=sel["malicious_share"],
            histogram=sel["histogram"])
        if "malicious_picks" in sel:
            out["malicious_picks"] = sel["malicious_picks"]
    return out


def main():
    rows = []
    for defense in ("Krum", "Bulyan"):
        for part in ("iid", "femnist_style"):
            row = run_cell(defense, part)
            rows.append(row)
            print(json.dumps(row), flush=True)
    # Cross-row deltas the GRID_RESULTS row quotes.
    k_iid, k_sty = rows[0], rows[1]
    print(json.dumps({
        "summary": "krum_selection_shift",
        "distinct_winners_iid": k_iid.get("distinct_winners"),
        "distinct_winners_style": k_sty.get("distinct_winners"),
        "top1_share_iid": k_iid.get("top1_share"),
        "top1_share_style": k_sty.get("top1_share"),
    }), flush=True)


if __name__ == "__main__":
    main()
