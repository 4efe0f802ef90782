#!/usr/bin/env python
"""Standalone validator for structured run JSONLs.

Checks every line of the given files against the event schema
(attacking_federate_learning_tpu/utils/metrics.py: EVENT_KINDS /
validate_event) so a malformed emitter is caught by CI, not by a reader
weeks later.  No device work (validation is pure Python over parsed
JSON), so it runs in tier-1 time budget on any backend state.

Speaks every supported schema version (v1, plus v2's compile/cost/
heartbeat kinds, plus v3's lifecycle kind — the preempt/resume/retry/
degrade transitions of utils/lifecycle.py — plus v4's cross-run
observatory kinds: 'registry' run-finish stamps, utils/registry.py,
and 'gate' behavioral-drift verdicts, tools/science_gate.py — plus
v5's 'secagg' kind: one secure-aggregation protocol record per round,
protocols/secagg.py — plus v6's hierarchical-forensics kinds:
'shard_selection' per-round tier-1/tier-2 selection records from
hierarchical rounds under --telemetry, core/engine.py, and
'forensics' colluder-localization verdicts, report.py — plus v7's
'async' kind: one asynchronous-round record per round under
aggregation='async', core/async_rounds.py — plus v8's 'campaign'
kind: one campaign-scheduler transition per record — campaign
start/done, cell start/done/failed/skipped verdicts and deadline
checkpoints — written to runs/campaigns/<id>/events.jsonl,
campaigns/scheduler.py — plus v9's observability kinds:
'stage_cost' per-entry per-stage cost attributions and
'wire_bytes' per-seam wire ledgers, both emitted by --cost-report
runs via utils/costs.py:CompileLedger.emit; with telemetry/reporting
off neither kind may appear, the invariant
tests/test_costs.py pins — plus v10's 'wall' kind: measured wall
telemetry from --profile-every runs — source='host' per-span/per-eval
host-clock walls from core/engine.py's fetch boundary, and
source='trace' per-stage booked walls from a jax.profiler capture,
utils/walls.py, whose stages + unattributed_us partition the booked
total exactly — plus v11's 'traffic' kind: one population-traffic
record per round under --traffic-population runs, core/population.py
— arrived/f_eff cohort accounting and the defense-validity watchdog's
ladder action, replayable on host via replay_traffic — plus v12's
'margin' kind: one robustness-margin record per round under --margins
runs, core/engine.py + utils/margins.py — per-row defense decision
margins, the colluder-survival rollups and the attack-side envelope
utilization — plus v13's hierarchical shard-domain 'fault' fields:
the per-shard survivor-count vector (shard_alive), the correlated
shard-DOMAIN accounting (shards_dead / shards_alive) and the
host-planned tier-2 ladder decision (tier2_action), all replayable
from the fault key via core/faults.py:hier_fault_schedule — plus
v14's 'numerics' kind: one numeric-health record per round under
--numerics runs, core/engine.py + utils/numerics.py — per-stage
nonfinite counts, gradient-norm dynamic range, distance-Gram
cancellation depth and the tie-proximity counters banded at k ulp of
the PR 18 margin boundaries, with the nonfinite_total / tie_locked
rollups; the cross-implementation ulp envelopes these counters
explain live in NUMERICS_BASELINE.json, tools/numerics_gate.py).  An
event stamped with a
version this reader does not know is reported as "produced by a newer
writer" — a clear per-line error, never a KeyError — and a newer-only
kind stamped with an older version is flagged as an emitter bug
(utils/metrics.py:validate_event owns both rules via
KIND_MIN_VERSION; the v6-kind-stamped-v5 rule mirrors the v2
precedent).

Usage:
    python tools/check_events.py logs/*.jsonl
    python tools/check_events.py --strict run.jsonl   # free-form lines
                                                      # are errors too
    python tools/check_events.py --stats run.jsonl    # per-kind count +
                                                      # schema-version
                                                      # histogram

Lines that are valid JSON objects WITHOUT a 'kind' field are counted as
legacy/free-form rows and skipped by default (pre-schema logs — e.g. the
grid drivers' summary rows); --strict flags them.  Exit status: 0 when
every file is clean, 1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from attacking_federate_learning_tpu.utils.metrics import (  # noqa: E402
    SCHEMA_VERSION, SUPPORTED_VERSIONS, validate_event
)


def check_file(path, strict=False):
    """Returns (per-kind counts, legacy-row count, [(lineno, error)])."""
    counts: dict = {}
    legacy = 0
    errors = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append((lineno, f"not JSON: {e}"))
                continue
            if not isinstance(rec, dict) or "kind" not in rec:
                legacy += 1
                if strict:
                    errors.append((lineno, "no 'kind' field (free-form "
                                           "row; --strict forbids)"))
                continue
            try:
                validate_event(rec)
            except ValueError as e:
                errors.append((lineno, str(e)))
                continue
            counts[rec["kind"]] = counts.get(rec["kind"], 0) + 1
    return counts, legacy, errors


def file_stats(path):
    """Per-kind stats over one file's typed rows — ``{kind: {"count":
    n, "versions": {v: n}}}`` — without validating (the histogram of a
    malformed file is still informative).  Free-form rows carry no
    kind/version stamp and are excluded; a typed row without a 'v'
    stamp counts under version 1 (the pre-stamp writer)."""
    stats: dict = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not isinstance(rec, dict) or "kind" not in rec:
                continue
            row = stats.setdefault(str(rec["kind"]),
                                   {"count": 0, "versions": {}})
            row["count"] += 1
            v = rec.get("v", 1)
            row["versions"][v] = row["versions"].get(v, 0) + 1
    return stats


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=f"Validate run JSONLs against the event schema "
                    f"(v{min(SUPPORTED_VERSIONS)}-v{max(SUPPORTED_VERSIONS)}"
                    f"; writer stamps v{SCHEMA_VERSION}).")
    p.add_argument("paths", nargs="+", metavar="JSONL")
    p.add_argument("--strict", action="store_true",
                   help="rows without a 'kind' field are errors, not "
                        "legacy free-form lines")
    p.add_argument("--stats", action="store_true",
                   help="also print the per-kind count and "
                        "schema-version histogram for each file")
    args = p.parse_args(argv)

    failed = False
    for path in args.paths:
        counts, legacy, errors = check_file(path, strict=args.strict)
        kinds = "  ".join(f"{k}:{v}" for k, v in sorted(counts.items()))
        tail = f"  (+{legacy} free-form)" if legacy else ""
        if errors:
            failed = True
            print(f"FAIL {path}: {len(errors)} bad line(s)  "
                  f"[{kinds}]{tail}")
            for lineno, msg in errors[:20]:
                print(f"  line {lineno}: {msg}")
            if len(errors) > 20:
                print(f"  ... and {len(errors) - 20} more")
        else:
            print(f"ok   {path}: {sum(counts.values())} events  "
                  f"[{kinds}]{tail}")
        if args.stats:
            stats = file_stats(path)
            print(f"  kind              count  versions")
            for kind in sorted(stats):
                row = stats[kind]
                vs = " ".join(f"v{v}:{n}" for v, n in
                              sorted(row["versions"].items()))
                print(f"    {kind:<15} {row['count']:>6}  {vs}")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
