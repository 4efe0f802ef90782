#!/usr/bin/env bash
# One-liner CI smoke: event-schema validation + fault matrix + crash
# matrix + perf gate (incl. hierarchical memproof + secagg wireproof +
# stage/wire-ledger stageproof) +
# science gate + registry selfcheck + hierarchical-aggregation smoke +
# secure-aggregation smoke + hierarchical-telemetry/forensics smoke +
# asynchronous-rounds smoke + campaign-engine kill/resume smoke +
# measured-walls smoke (profiled run, runs walls, wall gate) +
# population-traffic smoke (churn run, ladder audit, runs traffic) +
# robustness-margins smoke (margin run, v12 audit, runs margins drift).
#
#   bash tools/smoke.sh            # all fourteen, CPU-pinned
#   bash tools/smoke.sh --fast     # skip the fault + crash matrices
#                                  # (the two slowest legs)
#
# Legs (each independently CI-wired through tests/ as well):
#   1. tools/check_events.py over every run JSONL in logs/ (schema
#      v1-v10: round/eval/.../fault, compile/cost/heartbeat, lifecycle,
#      registry/gate, secagg, shard_selection/forensics, async,
#      campaign, stage_cost/wire_bytes, wall) — skipped when logs/ has
#      no .jsonl yet;
#   2. tools/fault_matrix.py — 5-round fault x defense sweep, emitted
#      'fault' events diffed against the host replay of the schedule,
#      plus the dropout x async-buffer leg (async + fault events
#      diffed against core/async_rounds.py:replay_schedule);
#   3. tools/crash_matrix.py — supervised preempt/resume at a seeded
#      round x {fused, staged, faulted} x 2 defenses: bounded retries,
#      exactly-once journal, clean exit (tools/supervisor.py);
#   4. tools/perf_gate.py — deterministic static-HLO perf gate against
#      PERF_BASELINE.json (FLOPs/bytes exact, memory within tolerance);
#   5. tools/science_gate.py — deterministic behavioral-drift gate:
#      pinned SYNTH_MNIST_HARD defense x attack cells against
#      BEHAVIOR_BASELINE.json (exact where bit-deterministic, measured
#      ulp-tie bands elsewhere);
#   6. 'runs selfcheck' — cross-run registry over runs/ (incl. the
#      supervised-run artifacts legs 2-3 leave behind): index refresh
#      idempotence + every entry resolvable (utils/registry.py);
#   7. hierarchical-aggregation smoke — a 5-round journaled
#      hierarchical x {Krum, TrimmedMean} run each (two-tier streaming
#      engine, ops/federated.py), then a journal audit: every round and
#      eval committed exactly once (utils/lifecycle.py RunJournal);
#   8. secure-aggregation smoke — a 5-round journaled --secagg vanilla
#      run with injected dropout (every dropout round must complete as
#      a mask-reconstruction round with the bitwise sum check passing)
#      and a 5-round journaled --secagg groupwise x tier-2 Krum run
#      (protocols/secagg.py), then the same journal audit plus a
#      'secagg'-event audit over the private run logs;
#   9. hierarchical-telemetry forensics smoke — a 5-round journaled
#      hierarchical x Krum run with --telemetry (schema-v6
#      'shard_selection' events), check_events over its private log,
#      'report forensics' exit-0, and a 'runs trace' export (the
#      exporter validates the trace before writing);
#  10. asynchronous-rounds smoke — a journaled 5-round
#      --aggregation async x {Krum, TrimmedMean} run each (FedBuff
#      buffered rounds, core/async_rounds.py), then RunJournal.verify
#      (every round and eval exactly once), check_events over the
#      private logs (v7 'async' events), and an async-event audit:
#      one per round, every delivered round exactly k rows;
#  11. campaign-engine smoke — a journaled 2x2 (defense x attack)
#      campaign on SYNTH_MNIST (campaigns/scheduler.py) with one
#      injected mid-campaign kill (FL_CAMPAIGN_KILL_AFTER_CELLS) +
#      resume: the re-invoke completes only the remaining cells, the
#      campaign journal audits exactly-once, runs/index.jsonl carries
#      zero duplicate run stamps, check_events validates the v8
#      'campaign' event stream, and 'runs campaign <id>' renders the
#      defense x attack table from the registry;
#  12. measured-walls smoke — a journaled 5-round flat x Krum run with
#      --profile-every 1 (schema-v10 'wall' events: host span/eval
#      walls + per-stage trace bookings, utils/walls.py), check_events
#      over its private log, and 'runs walls' exit-0 on the run;
#  13. population-traffic smoke — a journaled 10-round churn run from a
#      deliberately unreliable 16-client population (the cohort
#      routinely under-fills the Krum validity bound, forcing the
#      degradation ladder), check_events over its private log (schema
#      v11 'traffic' events), a replay audit (emitted events must
#      equal core/population.py:replay_traffic exactly, with at least
#      one degraded round), and 'runs traffic <id>' exit-0;
#  14. robustness-margins smoke — two journaled 6-round --margins x
#      Bulyan runs at different seeds (schema-v12 'margin' events:
#      per-row decision margins + colluder-survival rollups,
#      utils/margins.py), check_events --stats over the private logs
#      (v12 kind + per-kind histogram), a margin-event audit (one per
#      round, rollup fields present), 'runs margins <id>' exit-0 on
#      one run, and the cross-run drift render over both;
#  15. faulted-hierarchy smoke (ISSUE 19) — a journaled 6-round
#      hierarchical TrimmedMean run under per-client dropout/corrupt
#      PLUS correlated shard-DOMAIN death (--fault-shard-dropout),
#      check_events --stats over its private log (schema-v13 'fault'
#      events with per-shard survivor vectors), a host-replay audit
#      (emitted events must equal core/faults.py:hier_fault_schedule
#      exactly, tier-2 ladder action included), and 'report' exit-0
#      with the shard-domain fault table rendered.
#
# Exit: nonzero if any leg fails.  Always CPU (the gates' baselines are
# CPU artifacts, and the matrices must not touch the chip).
set -u
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

fail=0

shopt -s nullglob
jsonls=(logs/*.jsonl)
if [ ${#jsonls[@]} -gt 0 ]; then
    echo "== smoke 1/16: check_events (${#jsonls[@]} logs) =="
    python tools/check_events.py "${jsonls[@]}" || fail=1
else
    echo "== smoke 1/16: check_events — no logs/*.jsonl yet, skipped =="
fi

crash_work=""
if [ "${1:-}" != "--fast" ]; then
    echo "== smoke 2/16: fault_matrix =="
    python tools/fault_matrix.py || fail=1
    echo "== smoke 3/16: crash_matrix (supervised preempt/resume) =="
    # Keep the matrix's run stores: leg 6 registry-checks them.
    crash_work="$(mktemp -d -t crash_matrix_XXXXXX)"
    python tools/crash_matrix.py --workdir "$crash_work" || fail=1
else
    echo "== smoke 2/16: fault_matrix — skipped (--fast) =="
    echo "== smoke 3/16: crash_matrix — skipped (--fast) =="
fi

echo "== smoke 4/16: perf_gate (+ memproof + wireproof + shardproof"
echo "   + stageproof) =="
python tools/perf_gate.py --memproof || fail=1

echo "== smoke 5/16: science_gate (behavioral drift) =="
python tools/science_gate.py || fail=1

echo "== smoke 6/16: runs selfcheck (registry) =="
python -m attacking_federate_learning_tpu.cli runs selfcheck || fail=1
if [ -n "$crash_work" ]; then
    # The registry over the crash matrix's preempt/resume artifacts:
    # every supervised cell's run store must index, list and selfcheck
    # (refresh idempotence + resolvability) like any other runs/.
    for d in "$crash_work"/*/runs; do
        [ -d "$d" ] || continue
        echo "-- registry over crash-matrix artifacts: $d --"
        python -m attacking_federate_learning_tpu.cli runs \
            --run-dir "$d" --bench '' --progress '' list || fail=1
        python -m attacking_federate_learning_tpu.cli runs \
            --run-dir "$d" --bench '' --progress '' selfcheck || fail=1
    done
    rm -rf "$crash_work"
fi

echo "== smoke 7/16: hierarchical aggregation (journaled, audited) =="
hier_work="$(mktemp -d -t hier_smoke_XXXXXX)"
for def in Krum TrimmedMean; do
    python -m attacking_federate_learning_tpu.cli \
        -d "$def" -s SYNTH_MNIST -n 12 -m 0.25 -c 16 -e 5 \
        --synth-train 256 --synth-test 64 \
        --aggregation hierarchical --megabatch 4 \
        --journal --run-id "hier_${def}_smoke" --no-checkpoint \
        --log-dir "$hier_work/logs" --run-dir "$hier_work/runs" \
        > /dev/null || fail=1
done
# Journal audit: every round and eval committed exactly once
# (utils/lifecycle.py RunJournal.verify returns [] when clean).
python - "$hier_work/runs" <<'PY' || fail=1
import sys
from attacking_federate_learning_tpu.utils.lifecycle import RunJournal
bad = 0
for rid in ("hier_Krum_smoke", "hier_TrimmedMean_smoke"):
    problems = RunJournal(sys.argv[1], rid).verify(epochs=5, test_step=5)
    status = "ok" if not problems else f"FAIL {problems}"
    print(f"  journal {rid}: {status}")
    bad |= bool(problems)
sys.exit(bad)
PY
rm -rf "$hier_work"

echo "== smoke 8/16: secure aggregation (journaled, audited) =="
sa_work="$(mktemp -d -t secagg_smoke_XXXXXX)"
# vanilla: one dropout-rate high enough that the 5-round seeded run is
# guaranteed (and pinned by the audit below) to include at least one
# mask-reconstruction round.
python -m attacking_federate_learning_tpu.cli \
    -d NoDefense -s SYNTH_MNIST -n 12 -m 0.25 -c 16 -e 5 \
    --synth-train 256 --synth-test 64 \
    --secagg vanilla --fault-dropout 0.25 \
    --journal --run-id secagg_vanilla_smoke --no-checkpoint \
    --log-dir "$sa_work/logs" --run-dir "$sa_work/runs" \
    > /dev/null || fail=1
# groupwise x tier-2 Krum over per-group sums (the NET-SA composition
# with the two-tier tree).
python -m attacking_federate_learning_tpu.cli \
    -d NoDefense --tier2-defense Krum -s SYNTH_MNIST -n 12 -m 0.25 \
    -c 16 -e 5 --synth-train 256 --synth-test 64 \
    --secagg groupwise --aggregation hierarchical --megabatch 4 \
    --journal --run-id secagg_groupwise_smoke --no-checkpoint \
    --log-dir "$sa_work/logs" --run-dir "$sa_work/runs" \
    > /dev/null || fail=1
python - "$sa_work" <<'PY' || fail=1
import json, os, sys
from attacking_federate_learning_tpu.utils.lifecycle import RunJournal
work = sys.argv[1]
bad = 0
for rid in ("secagg_vanilla_smoke", "secagg_groupwise_smoke"):
    problems = RunJournal(os.path.join(work, "runs"), rid).verify(
        epochs=5, test_step=5)
    events = [json.loads(line) for line in
              open(os.path.join(work, "logs", rid + ".jsonl"))]
    sec = [e for e in events if e.get("kind") == "secagg"]
    if len(sec) != 5:
        problems.append(f"{len(sec)} secagg events, want one per round")
    if any(not e.get("sum_check_ok") for e in sec):
        problems.append("bitwise sum check failed")
    if rid == "secagg_vanilla_smoke":
        rec = sum(e.get("recovery", 0) for e in sec)
        masks = sum(e.get("masks_reconstructed", 0) for e in sec)
        if rec < 1 or masks < 1:
            problems.append(f"no dropout-recovery round fired "
                            f"(recovery={rec}, masks={masks})")
    status = "ok" if not problems else f"FAIL {problems}"
    print(f"  secagg {rid}: {status}")
    bad |= bool(problems)
sys.exit(bad)
PY
rm -rf "$sa_work"

echo "== smoke 9/16: hierarchical telemetry + forensics (journaled) =="
fx_work="$(mktemp -d -t hier_tele_smoke_XXXXXX)"
# 5-round journaled hierarchical x Krum run with --telemetry: the run
# must emit one schema-v6 'shard_selection' event per round.
python -m attacking_federate_learning_tpu.cli \
    -d Krum -s SYNTH_MNIST -n 12 -m 0.25 -c 16 -e 5 \
    --synth-train 256 --synth-test 64 \
    --aggregation hierarchical --megabatch 4 --telemetry \
    --journal --run-id hier_tele_smoke --no-checkpoint \
    --log-dir "$fx_work/logs" --run-dir "$fx_work/runs" \
    > /dev/null || fail=1
# Event audit: the private log validates (v6 'shard_selection' events
# included) and carries exactly one per round.
python tools/check_events.py "$fx_work/logs/hier_tele_smoke.jsonl" \
    || fail=1
python - "$fx_work" <<'PY' || fail=1
import json, os, sys
events = [json.loads(line) for line in
          open(os.path.join(sys.argv[1], "logs",
                            "hier_tele_smoke.jsonl"))]
ss = [e for e in events if e.get("kind") == "shard_selection"]
ok = (len(ss) == 5 and all(e.get("v") >= 6 for e in ss)
      and all("tier2_selection_mask" in e for e in ss))
print(f"  shard_selection events: {len(ss)}/5 "
      f"({'ok' if ok else 'FAIL'})")
sys.exit(0 if ok else 1)
PY
# 'report forensics' must produce a verdict (exit 0) on the run log.
python -m attacking_federate_learning_tpu.cli report forensics \
    "$fx_work/logs/hier_tele_smoke.jsonl" || fail=1
# 'runs trace' export over the same run — export_trace validates the
# trace-event JSON (tier-2 forensics track included) before writing.
python -m attacking_federate_learning_tpu.cli runs \
    --run-dir "$fx_work/runs" --bench '' --progress '' \
    trace hier_tele_smoke -o "$fx_work/trace.json" || fail=1
rm -rf "$fx_work"

echo "== smoke 10/16: asynchronous rounds (journaled, audited) =="
as_work="$(mktemp -d -t async_smoke_XXXXXX)"
# 5-round journaled FedBuff runs: k=8 of n=12 aggregated per applied
# round, staleness bound 2, poly weighting, Krum + TrimmedMean.
for def in Krum TrimmedMean; do
    python -m attacking_federate_learning_tpu.cli \
        -d "$def" -s SYNTH_MNIST -n 12 -m 0.25 -c 16 -e 5 \
        --synth-train 256 --synth-test 64 \
        --aggregation async --async-buffer 8 --async-max-staleness 2 \
        --staleness-weight poly \
        --journal --run-id "async_${def}_smoke" --no-checkpoint \
        --log-dir "$as_work/logs" --run-dir "$as_work/runs" \
        > /dev/null || fail=1
    # The private log must validate (v7 'async' events included).
    python tools/check_events.py \
        "$as_work/logs/async_${def}_smoke.jsonl" || fail=1
done
# Journal audit (exactly-once) + async-event audit: one v7 'async'
# event per round, and every delivered round aggregates exactly k.
python - "$as_work" <<'PY' || fail=1
import json, os, sys
from attacking_federate_learning_tpu.utils.lifecycle import RunJournal
work = sys.argv[1]
bad = 0
for rid in ("async_Krum_smoke", "async_TrimmedMean_smoke"):
    problems = RunJournal(os.path.join(work, "runs"), rid).verify(
        epochs=5, test_step=5)
    events = [json.loads(line) for line in
              open(os.path.join(work, "logs", rid + ".jsonl"))]
    av = [e for e in events if e.get("kind") == "async"]
    if len(av) != 5:
        problems.append(f"{len(av)} async events, want one per round")
    if any(e.get("v", 0) < 7 for e in av):
        problems.append("async event stamped below v7")
    if any(int(e.get("delivered", -1)) not in (0, 8) for e in av):
        problems.append("a delivered round did not aggregate "
                        "exactly k=8 rows")
    if not any(int(e.get("delivered", 0)) == 8 for e in av):
        problems.append("no round ever reached the FedBuff trigger")
    status = "ok" if not problems else f"FAIL {problems}"
    print(f"  async {rid}: {status}")
    bad |= bool(problems)
sys.exit(bad)
PY
# Registry-resolved staleness table must render (runs async verb).
python -m attacking_federate_learning_tpu.cli runs \
    --run-dir "$as_work/runs" --bench '' --progress '' \
    async async_Krum_smoke || fail=1
rm -rf "$as_work"

echo "== smoke 11/16: campaign engine (kill + resume, audited) =="
ce_work="$(mktemp -d -t campaign_smoke_XXXXXX)"
cat > "$ce_work/spec.json" <<SPEC
{"name": "smoke",
 "base": {"dataset": "SYNTH_MNIST", "users_count": 12, "mal_prop": 0.25,
          "batch_size": 16, "epochs": 5, "synth_train": 256,
          "synth_test": 64, "backend": "cpu",
          "log_dir": "$ce_work/logs", "run_dir": "$ce_work/runs"},
 "axes": {"defense": ["Krum", "TrimmedMean"],
          "attack": ["none", "alie"]}}
SPEC
# First invocation dies (injected SIGKILL-equivalent) after 2 cells...
FL_CAMPAIGN_KILL_AFTER_CELLS=2 \
python -m attacking_federate_learning_tpu.campaigns "$ce_work/spec.json" \
    --executor inline > /dev/null 2>&1
rc=$?
[ "$rc" -eq 137 ] || { echo "FAIL campaign: expected kill rc 137, got $rc"; fail=1; }
# ...the re-invoke completes only the remaining cells.
python -m attacking_federate_learning_tpu.campaigns "$ce_work/spec.json" \
    --executor inline || fail=1
camp_id="$(ls "$ce_work/runs/campaigns")"
# Exactly-once audits: campaign journal + zero duplicate run stamps.
python - "$ce_work" "$camp_id" <<'PY' || fail=1
import json, os, sys
from attacking_federate_learning_tpu.campaigns import CampaignJournal
work, camp_id = sys.argv[1], sys.argv[2]
j = CampaignJournal(os.path.join(work, "runs"), camp_id)
problems = j.verify()
man = j.read_manifest()
if man["status"] != "done" or man["counts"].get("done") != 4:
    problems.append(f"campaign not done: {man['status']} {man['counts']}")
attempts = [r for r in j.records() if r.get("kind") == "attempt"]
if len(attempts) != 2:
    problems.append(f"{len(attempts)} attempts recorded, want 2")
ids = [json.loads(line)["run_id"]
       for line in open(os.path.join(work, "runs", "index.jsonl"))]
if len(ids) != len(set(ids)):
    problems.append(f"duplicate run stamps in index.jsonl: {ids}")
print("  campaign journal: " + ("ok (exactly-once, resumed)"
                                if not problems else f"FAIL {problems}"))
sys.exit(bool(problems))
PY
# The v8 'campaign' event stream validates...
python tools/check_events.py \
    "$ce_work/runs/campaigns/$camp_id/events.jsonl" || fail=1
# ...and 'runs campaign <id>' renders the defense x attack table from
# the registry (values bit-exact against the per-run manifests).
python -m attacking_federate_learning_tpu.cli runs \
    --run-dir "$ce_work/runs" --bench '' --progress '' \
    campaign "$camp_id" || fail=1
rm -rf "$ce_work"

echo "== smoke 12/16: measured walls (profiled run + wall gate) =="
wl_work="$(mktemp -d -t walls_smoke_XXXXXX)"
# 5-round journaled flat x Krum with every eval interval profiled: the
# engine books each span capture onto the stage set and emits
# schema-v10 'wall' events next to the --cost-report stage_cost twins.
python -m attacking_federate_learning_tpu.cli \
    -d Krum -s SYNTH_MNIST -n 12 -m 0.25 -c 16 -e 5 \
    --synth-train 256 --synth-test 64 \
    --profile-every 1 --cost-report \
    --journal --run-id walls_smoke --no-checkpoint \
    --log-dir "$wl_work/logs" --run-dir "$wl_work/runs" \
    > /dev/null || fail=1
# The private log validates (v10 'wall' events included) and carries
# both wall sources (host span/eval clocks + trace bookings).
python tools/check_events.py "$wl_work/logs/walls_smoke.jsonl" || fail=1
python - "$wl_work" <<'PY' || fail=1
import json, os, sys
events = [json.loads(line) for line in
          open(os.path.join(sys.argv[1], "logs", "walls_smoke.jsonl"))]
wl = [e for e in events if e.get("kind") == "wall"]
src = {e.get("source") for e in wl}
traced = [e for e in wl if e.get("source") == "trace"]
exact = all(
    abs(sum(e["stages"].values()) + e["unattributed_us"]
        - e["wall_s"] * 1e6) <= 1.0 for e in traced)
ok = (bool(wl) and src == {"host", "trace"}
      and all(e.get("v") == 10 for e in wl)
      and all(e["coverage"]["op_events"] > 0 for e in traced) and exact)
print(f"  wall events: {len(wl)} ({len(traced)} trace-booked, "
      f"partition {'exact' if exact else 'BROKEN'}) "
      f"({'ok' if ok else 'FAIL'})")
sys.exit(0 if ok else 1)
PY
# The registry verb renders the measured/modeled tables (exit 0).
python -m attacking_federate_learning_tpu.cli runs \
    --run-dir "$wl_work/runs" --bench '' --progress '' \
    walls walls_smoke || fail=1
rm -rf "$wl_work"

echo "== smoke 13/16: population traffic (churn, ladder, audited) =="
tr_work="$(mktemp -d -t traffic_smoke_XXXXXX)"
# 10-round journaled churn run from an unreliable 16-client population:
# the sampled cohort routinely misses Krum's 2f+3 validity bound, so
# the run only completes by walking the declared degradation ladder
# (remask -> TrimmedMean fallback -> hold), every decision a v11
# 'traffic' event.
python -m attacking_federate_learning_tpu.cli \
    -d Krum -s SYNTH_MNIST -n 12 -m 0.25 -c 16 -e 10 \
    --synth-train 256 --synth-test 64 --seed 1 \
    --traffic-population 16 --traffic-rate 0.6 --traffic-churn-dwell 2 \
    --traffic-fallback TrimmedMean --traffic-seed 5 \
    --journal --run-id traffic_smoke --no-checkpoint \
    --log-dir "$tr_work/logs" --run-dir "$tr_work/runs" \
    > /dev/null || fail=1
# The private log must validate (v11 'traffic' events included).
python tools/check_events.py "$tr_work/logs/traffic_smoke.jsonl" || fail=1
# Journal audit (exactly-once) + the replay audit: the emitted traffic
# events must equal the independent host regeneration of the schedule,
# and the under-fill must actually have forced a degradation step.
python - "$tr_work" <<'PY' || fail=1
import json, os, sys
from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.core.population import replay_traffic
from attacking_federate_learning_tpu.utils.lifecycle import RunJournal
work = sys.argv[1]
problems = RunJournal(os.path.join(work, "runs"), "traffic_smoke").verify(
    epochs=10, test_step=5)
events = [json.loads(line) for line in
          open(os.path.join(work, "logs", "traffic_smoke.jsonl"))]
tr = sorted((e for e in events if e.get("kind") == "traffic"),
            key=lambda e: e["round"])
cfg = C.ExperimentConfig(
    dataset=C.SYNTH_MNIST, users_count=12, mal_prop=0.25, batch_size=16,
    epochs=10, synth_train=256, synth_test=64, seed=1, defense="Krum",
    traffic=C.TrafficConfig(population=16, rate=0.6, churn_dwell=2,
                            fallback_defense="TrimmedMean", seed=5))
want = replay_traffic(cfg, 10)
keys = ("round", "arrived", "f_eff", "cohort", "action", "defense")
if len(tr) != 10:
    problems.append(f"{len(tr)} traffic events, want one per round")
if any(e.get("v", 0) < 11 for e in tr):
    problems.append("traffic event stamped below v11")
if ([tuple(e[k] for k in keys) for e in tr]
        != [tuple(e[k] for k in keys) for e in want]):
    problems.append("emitted traffic events diverge from the host replay")
if not any(e["action"] in ("fallback", "hold") for e in tr):
    problems.append("under-fill never forced a degradation step")
degraded = sum(1 for e in tr if e["action"] != "remask")
status = "ok" if not problems else f"FAIL {problems}"
print(f"  traffic traffic_smoke: {len(tr)} events, "
      f"{degraded} degraded rounds ({status})")
sys.exit(bool(problems))
PY
# Registry-resolved traffic table must render (runs traffic verb).
python -m attacking_federate_learning_tpu.cli runs \
    --run-dir "$tr_work/runs" --bench '' --progress '' \
    traffic traffic_smoke || fail=1
rm -rf "$tr_work"

echo "== smoke 14/16: robustness margins (v12 audit + drift render) =="
mg_work="$(mktemp -d -t margins_smoke_XXXXXX)"
# Two short journaled Bulyan --margins runs at different seeds: the
# in-jit margin observatory emits one schema-v12 'margin' event per
# round (per-row decision margins + colluder-survival rollups).
for seed in 0 1; do
    python -m attacking_federate_learning_tpu.cli \
        -d Bulyan -z 1.5 -s SYNTH_MNIST -n 15 -m 0.2 -c 16 -e 6 \
        --synth-train 256 --synth-test 64 --seed "$seed" \
        --margins \
        --journal --run-id "margins_smoke_$seed" --no-checkpoint \
        --log-dir "$mg_work/logs" --run-dir "$mg_work/runs" \
        > /dev/null || fail=1
    # The private log validates (v12 'margin' events included) and the
    # --stats histogram renders.
    python tools/check_events.py --stats \
        "$mg_work/logs/margins_smoke_$seed.jsonl" || fail=1
done
# Margin-event audit: one per round, rollup fields riding along.
python - "$mg_work" <<'PY' || fail=1
import json, os, sys
bad = 0
for seed in (0, 1):
    events = [json.loads(line) for line in
              open(os.path.join(sys.argv[1], "logs",
                                f"margins_smoke_{seed}.jsonl"))]
    mg = [e for e in events if e.get("kind") == "margin"]
    problems = []
    if len(mg) != 6:
        problems.append(f"{len(mg)} margin events, want one per round")
    if any(e.get("v", 0) < 12 for e in mg):
        problems.append("margin event stamped below v12")
    if any("colluder_margin" not in e or "margin_gap" not in e
           for e in mg):
        problems.append("a margin event is missing its rollups")
    status = "ok" if not problems else f"FAIL {problems}"
    print(f"  margins margins_smoke_{seed}: {len(mg)} events ({status})")
    bad |= bool(problems)
sys.exit(bad)
PY
# Registry-resolved trajectory table (exit 0), then the cross-run
# colluder-margin drift with sign-flip marks over both seeds.
python -m attacking_federate_learning_tpu.cli runs \
    --run-dir "$mg_work/runs" --bench '' --progress '' \
    margins margins_smoke_0 || fail=1
python -m attacking_federate_learning_tpu.cli runs \
    --run-dir "$mg_work/runs" --bench '' --progress '' \
    margins margins_smoke_0 margins_smoke_1 || fail=1
rm -rf "$mg_work"

echo "== smoke 15/16: faulted hierarchy (shard domains, journaled) =="
fh_work="$(mktemp -d -t fault_hier_smoke_XXXXXX)"
# A journaled 6-round two-tier run under BOTH fault granularities:
# per-client dropout/corrupt inside each megabatch plus correlated
# shard-DOMAIN death; the shard-dropout rate is high enough that the
# seeded run includes dead-domain rounds (pinned by the audit below).
python -m attacking_federate_learning_tpu.cli \
    -d TrimmedMean -s SYNTH_MNIST -n 16 -m 0.25 -c 16 -e 6 \
    --synth-train 256 --synth-test 64 --seed 3 \
    --aggregation hierarchical --megabatch 4 \
    --fault-dropout 0.2 --fault-corrupt 0.1 \
    --fault-shard-dropout 0.3 --fault-shard-dropout-dwell 2 \
    --journal --run-id fault_hier_smoke --no-checkpoint \
    --log-dir "$fh_work/logs" --run-dir "$fh_work/runs" \
    > /dev/null || fail=1
# The private log validates (schema-v13 'fault' events with per-shard
# survivor vectors) and the --stats histogram renders.
python tools/check_events.py --stats \
    "$fh_work/logs/fault_hier_smoke.jsonl" || fail=1
# Host-replay audit: every emitted 'fault' event — per-shard
# shard_alive vector and tier-2 ladder action included — must equal
# the independent regeneration from the fault key.
python - "$fh_work" <<'PY' || fail=1
import json, os, sys
from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.core.faults import (
    fault_key, hier_fault_schedule, plan_tier2_actions
)
from attacking_federate_learning_tpu.ops.federated import (
    make_placement, tier2_assumed
)
from attacking_federate_learning_tpu.utils.lifecycle import RunJournal

work = sys.argv[1]
problems = RunJournal(os.path.join(work, "runs"),
                      "fault_hier_smoke").verify(epochs=6, test_step=5)
cfg = C.ExperimentConfig(
    dataset=C.SYNTH_MNIST, users_count=16, mal_prop=0.25, seed=3,
    aggregation="hierarchical", megabatch=4, defense="TrimmedMean",
    faults=C.FaultConfig(dropout=0.2, corrupt=0.1, shard_dropout=0.3,
                         shard_dropout_dwell=2))
place = make_placement(cfg.users_count, cfg.corrupted_count,
                       cfg.megabatch, cfg.mal_placement)
rows = hier_fault_schedule(fault_key(cfg), 0, 6, place, cfg.faults)
plan = plan_tier2_actions(
    [r["shards_alive"] for r in rows], cfg.defense,
    tier2_assumed(cfg.corrupted_count, cfg.megabatch))
events = [json.loads(line) for line in
          open(os.path.join(work, "logs", "fault_hier_smoke.jsonl"))]
flt = sorted((e for e in events if e.get("kind") == "fault"
              and not e.get("rolled_back")),
             key=lambda e: e["round"])
if len(flt) != 6:
    problems.append(f"{len(flt)} fault events, want one per round")
else:
    for got, want, act in zip(flt, rows, plan):
        for k in ("injected_dropout", "injected_corrupt", "quarantined",
                  "shards_dead", "shards_alive"):
            if int(got.get(k, -1)) != want[k]:
                problems.append(
                    f"round {want['round']}: {k} {got.get(k)} != "
                    f"replayed {want[k]}")
        if [int(x) for x in got.get("shard_alive", [])] != \
                want["shard_alive"]:
            problems.append(f"round {want['round']}: shard_alive "
                            f"{got.get('shard_alive')} != "
                            f"{want['shard_alive']}")
        if int(got.get("tier2_action", -1)) != int(act):
            problems.append(f"round {want['round']}: tier2_action "
                            f"{got.get('tier2_action')} != {int(act)}")
    if not any(r["shards_dead"] > 0 for r in rows):
        problems.append("no dead-domain round fired (raise "
                        "--fault-shard-dropout)")
status = "ok" if not problems else f"FAIL {problems}"
print(f"  fault_hier_smoke: {len(flt)} fault events, host replay "
      f"exact ({status})")
sys.exit(bool(problems))
PY
# 'report' must render the shard-domain fault table (exit 0).
python -m attacking_federate_learning_tpu.cli report \
    "$fh_work/logs/fault_hier_smoke.jsonl" || fail=1
rm -rf "$fh_work"

echo "== smoke 16/16: numerics observatory (v14 audit + drift gate) =="
nm_work="$(mktemp -d -t numerics_smoke_XXXXXX)"
# A short journaled --numerics run: the in-jit numeric-health
# observatory emits one schema-v14 'numerics' event per round
# (nonfinite by stage, norm dynamic range, tie proximity at the
# decision boundaries, Gram cancellation depth).
python -m attacking_federate_learning_tpu.cli \
    -d Krum -z 1.5 -s SYNTH_MNIST -n 12 -m 0.2 -c 16 -e 5 \
    --synth-train 256 --synth-test 64 --seed 0 \
    --numerics \
    --journal --run-id numerics_smoke --no-checkpoint \
    --log-dir "$nm_work/logs" --run-dir "$nm_work/runs" \
    > /dev/null || fail=1
# The private log validates (v14 'numerics' events included) and the
# --stats histogram renders.
python tools/check_events.py --stats \
    "$nm_work/logs/numerics_smoke.jsonl" || fail=1
# Numerics-event audit: one per round, stage counters + rollups along.
python - "$nm_work" <<'PY' || fail=1
import json, os, sys
events = [json.loads(line) for line in
          open(os.path.join(sys.argv[1], "logs",
                            "numerics_smoke.jsonl"))]
nm = [e for e in events if e.get("kind") == "numerics"]
problems = []
if len(nm) != 5:
    problems.append(f"{len(nm)} numerics events, want one per round")
if any(e.get("v", 0) < 14 for e in nm):
    problems.append("numerics event stamped below v14")
need = ("nonfinite_pre", "nonfinite_post", "nonfinite_agg",
        "range_log2", "tie_rows", "cancel_bits", "nonfinite_total",
        "tie_locked", "tie_band_ulps")
if any(k not in e for e in nm for k in need):
    problems.append("a numerics event is missing its counters")
if any(e.get("nonfinite_total", -1) != 0 for e in nm):
    problems.append("nonfinite gradients in a healthy seeded run")
status = "ok" if not problems else f"FAIL {problems}"
print(f"  numerics numerics_smoke: {len(nm)} events ({status})")
sys.exit(bool(problems))
PY
# Registry-resolved health-trajectory table must render (runs
# numerics verb, exit 0).
python -m attacking_federate_learning_tpu.cli runs \
    --run-dir "$nm_work/runs" --bench '' --progress '' \
    numerics numerics_smoke || fail=1
# Cross-impl divergence ledger round-trip: regenerate a baseline into
# the temp dir, then gate against it — a fresh ledger must gate clean
# on the same host (the checked-in NUMERICS_BASELINE.json is the
# cross-session pin; tools/numerics_gate.py).
python tools/numerics_gate.py --update \
    --baseline "$nm_work/NUMERICS_BASELINE.json" || fail=1
python tools/numerics_gate.py --strict-env \
    --baseline "$nm_work/NUMERICS_BASELINE.json" || fail=1
rm -rf "$nm_work"

if [ $fail -ne 0 ]; then
    echo "SMOKE FAILED"
else
    echo "smoke clean"
fi
exit $fail
