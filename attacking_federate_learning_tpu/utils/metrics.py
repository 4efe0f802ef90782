"""Structured run metrics, the event schema, and logging.

The reference logs via a print/file tee closure (reference main.py:13-18), a
``locals()`` config dump (main.py:19), accuracy lines every TEST_STEP rounds
(main.py:77-80) and a CSV of the accuracy trajectory whose filename encodes
every hyperparameter (main.py:100).  This module keeps all of those outputs
(tee, config dump, CSV with the same filename schema) and adds what the
reference lacks (SURVEY.md §5): a versioned schema of structured JSONL
events — per-round diagnostics, eval/ASR trajectories, phase timings,
stream stall stats, and the telemetry pipeline's per-round defense/attack
forensics (core/engine.py) — validated at the emitter so malformed events
fail the producing run, not a downstream reader.

Event contract (schema v2): every event is one JSON object per line with a
``kind`` from :data:`EVENT_KINDS`, that kind's required fields, a schema
version ``v`` and a relative timestamp ``t``.  Extra fields are always
allowed (they're how diagnostics grow without a version bump); missing
required fields or unknown kinds are errors.  ``tools/check_events.py`` is
the standalone validator; ``report.py`` is the reader.

Version history: v1 introduced the structured kinds (round/eval/asr/
profile/stream/defense/attack/selection_hist, later fault); v2 adds the
compile-and-cost observatory kinds — ``compile`` (per-entry-point
compile wall time + persistent-cache attribution), ``cost`` (static HLO
FLOPs / bytes-accessed / memory facts, utils/costs.py) and
``heartbeat`` (the RunLogger liveness thread); v3 adds ``lifecycle``
(run-lifecycle transitions — start/resume/preempt/complete from the
engine, retry/degrade/exhausted from tools/supervisor.py;
utils/lifecycle.py); v4 adds the cross-run observatory rollups —
``registry`` (the engine's run-finish stamp that joins the event log to
``runs/index.jsonl``, utils/registry.py) and ``gate`` (one behavioral-
drift verdict per pinned cell, tools/science_gate.py); v5 adds
``secagg`` — one secure-aggregation protocol record per round
(protocols/secagg.py: masks reconstructed, dropout-recovery flag,
bitwise sum-check verdict, per-group sum norms under groupwise); v6
adds the hierarchical forensics kinds — ``shard_selection`` (one
record per hierarchical round under --telemetry: the stacked per-shard
tier-1 diagnostics and the tier-2 cross-shard selection/trim
diagnostics, with the static placement ground truth riding along) and
``forensics`` (the colluder-localization verdict `report forensics`
computes from a run's shard_selection stream); v7 adds ``async`` —
one asynchronous-round record per round under
``aggregation='async'`` (core/async_rounds.py: delivered / pending /
in-flight counts, evictions, supersessions, the delivered staleness
histogram and the weight mass per staleness bucket — emitted with or
without --telemetry, like 'fault'); v8 adds ``campaign`` — one
campaign-scheduler transition per record
(attacking_federate_learning_tpu/campaigns/: campaign start/done,
cell start and the cell's terminal verdict done/failed/skipped/
adopted, deadline checkpoints — written to the campaign's own
``runs/campaigns/<id>/events.jsonl``, never into a run's log by the
engine); v9 adds the stage & wire ledger kinds (utils/costs.py,
emitted by CompileLedger.emit under --cost-report) — ``stage_cost``
(one per compiled entry point: the whole-program FLOPs/bytes/temp
partitioned across the canonical stage set ``deliver →
quarantine → protect → tier1_aggregate → tier2_aggregate → apply``
plus the unattributed residual and the modeled coverage) and
``wire_bytes`` (one per run: bytes-per-round on every protocol seam —
broadcast, client_update, tier1_to_tier2, secagg mask exchange /
recovery, async delivery); v10 adds ``wall`` — the measured-walls
observatory (utils/walls.py, ``--profile-every``): one record per
measured wall, either host-clock span/eval timing at the engine's
eval-boundary fetch (``source='host'``: wall_s, rounds, rounds/s —
no new host callbacks in-jit) or a profiler-trace capture booked
onto the stage set (``source='trace'``: per-stage microseconds
+ unattributed residual summing exactly to wall_s, with op-event
coverage riding along) — the runtime twin of v9's modeled
``stage_cost``; v11 adds ``traffic`` — one population-traffic record
per round under a ``--traffic-population`` run (core/population.py):
the arrived-count / effective-f accounting of the sampled cohort and
the defense-validity watchdog's ladder decision
(action='remask'/'fallback'/'hold', with the cohort pids, f_eff and
the defense actually applied riding along) — host-born from the
PRNG-replayable schedule, so ``replay_traffic`` diffs the emitted
stream against an independent regeneration; v12 adds ``margin`` —
one robustness-margin record per round under ``--margins``
(core/engine.py + utils/margins.py): the defenses' in-jit decision
margins (Krum winner/runner-up gap and per-row signed distance to the
selection threshold, trim-boundary distances and kept-coordinate
fractions, Bulyan per-iteration selection slack) rolled up host-side
into the colluder-survival ledger (colluder_margin /
colluder_selected / colluder_kept_mass), with the attack's envelope
utilization and traffic's f_eff riding along; v13 extends ``fault``
with the hierarchical shard-domain fields (core/faults.py ISSUE 19:
``shard_alive`` — the per-shard survivor-count vector after quarantine
and domain death, ``shards_dead`` / ``shards_alive`` — the correlated
shard-DOMAIN accounting, and ``tier2_action`` — the host-planned
remask/fallback/hold ladder decision at tier-2), all host-replayable
from the fault key (tools/fault_matrix.py diffs them exactly); v14
adds ``numerics`` — one numeric-health record per round under
``--numerics`` (core/engine.py + utils/numerics.py): per-stage
nonfinite counts (pre/post quarantine, post-aggregate), the
gradient-norm dynamic range, the distance-Gram cancellation-depth
estimate, and the tie-proximity counters that band the PR 18 margin
tensors at k ulp of their decision boundary, rolled up host-side into
nonfinite_total / tie_locked (read with ``runs numerics``; the
cross-implementation envelopes live in NUMERICS_BASELINE.json, gated
by tools/numerics_gate.py).
Readers accept every version; older logs simply never carry the newer
kinds, and a newer-only kind stamped with an older version is an
emitter bug, rejected (``KIND_MIN_VERSION``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Optional

import numpy as np


SCHEMA_VERSION = 14
SUPPORTED_VERSIONS = (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14)

# kind -> required fields.  Producers: core/engine.py (round, eval, asr,
# profile, stream, defense, attack, selection_hist via RunLogger).
EVENT_KINDS = {
    # per-round scalar diagnostics (--round-stats)
    "round": {"round"},
    # eval-cadence accuracy line (reference main.py:77-80, structured)
    "eval": {"round", "test_loss", "accuracy", "correct", "test_size"},
    # backdoor attack-success rate at eval cadence
    "asr": {"round", "attack_success_rate"},
    # recorder totals written once at run end: the loop's host phases
    # (interval.*), plus --profile's device-synced round / eval
    "profile": {"phases"},
    # host-stream stall accounting (data/stream.py stall_stats)
    "stream": {"stream_stall_s", "stream_gets"},
    # per-round defense forensics (--telemetry): selection masks/scores,
    # trim/clip/trust diagnostics, per-client norms + cosine-to-mean
    "defense": {"round", "defense"},
    # per-round attack envelope stats (--telemetry): ALIE z/sigma/drift
    # norms, backdoor shadow loss
    "attack": {"round", "attack"},
    # end-of-run selection histogram (the GRID_RESULTS top-1 analysis)
    "selection_hist": {"defense", "counts"},
    # fault-injection / recovery accounting (core/faults.py + the
    # engine's divergence watchdog): per-round injected/quarantined
    # counts, and rollback records (rolled_back, restored_round)
    "fault": {"round"},
    # --- v2: the compile-and-cost observatory (utils/costs.py) ---------
    # per-entry-point compile record: wall time + persistent-cache
    # attribution ('hit'/'miss'/'uncached') + backend platform
    "compile": {"name", "compile_s", "cache"},
    # static HLO facts for the same entry point: exact FLOPs and
    # bytes-accessed (cost_analysis), memory sizes (memory_analysis)
    "cost": {"name", "flops", "bytes_accessed", "peak_bytes"},
    # RunLogger liveness thread: emitted every N seconds so a stalled
    # capture is distinguishable from a long compile by tailing the
    # events file (round / rounds-per-sec EMA ride along when known)
    "heartbeat": {"rss_mb", "last_event_age_s"},
    # --- v3: the run-lifecycle layer (utils/lifecycle.py) --------------
    # one transition of the preemption-safe run lifecycle.  'phase' is
    # the transition name: the engine emits start/resume/preempt/
    # complete (core/engine.py), the supervisor retry/degrade/
    # stall_kill/exhausted/fatal (tools/supervisor.py).  Extra fields
    # (round, attempt, signal, failure class, degradation applied) ride
    # along as diagnostics.
    "lifecycle": {"phase"},
    # --- v4: the cross-run observatory (utils/registry.py) -------------
    # the engine's run-finish registry stamp: the run_id this event log
    # belongs to, with the final-trajectory summary riding along
    # (final/max accuracy, ASR, rounds) — the join key between a log
    # and runs/index.jsonl
    "registry": {"run_id"},
    # one behavioral-drift gate verdict (tools/science_gate.py): the
    # pinned cell's name and its pass/fail/skip status, with the
    # compared metrics as extra fields
    "gate": {"cell", "status"},
    # --- v5: the secure-aggregation protocol layer (protocols/secagg.py)
    # one protocol record per round (emitted with or without
    # --telemetry, like 'fault'): bitwise sum-check verdict
    # (sum_check_ok), dropped-client count, masks reconstructed in the
    # simulated seed-reveal (recovery), and under groupwise the
    # per-group sum norms — the server-visible quantities
    "secagg": {"round"},
    # --- v6: hierarchical forensics (core/engine.py, report.py) ---------
    # one record per hierarchical round under --telemetry: the stacked
    # per-shard tier-1 diagnostics ('shard_*' fields — (S, m) selection
    # masks/scores, kept fractions) and the tier-2 cross-shard
    # diagnostics ('tier2_*' fields — (S,) selection mask/scores over
    # the shard-estimate matrix), plus the static placement ground
    # truth (mal_counts, megabatch) the forensics layer attributes
    # against.  Under groupwise secagg only the tier-2 (group-sum-
    # level) fields appear — per-client rows are not server-visible.
    "shard_selection": {"round", "defense"},
    # the colluder-localization verdict 'report forensics' computes
    # from a run's shard_selection stream (tier-2 rejection
    # attribution: which shards were rejected, when localization
    # stabilized, whether the malicious shards were isolated)
    "forensics": {"verdict"},
    # --- v7: asynchronous buffered rounds (core/async_rounds.py) --------
    # one record per async round (emitted with or without --telemetry,
    # like 'fault'): delivered / pending / in-flight counts, over-stale
    # evictions, supersessions, quarantined non-finite arrivals, the
    # delivered staleness histogram and the per-bucket weight mass —
    # the staleness-rollup raw material ('report' staleness table)
    "async": {"round", "delivered"},
    # --- v8: the campaign scheduler (campaigns/scheduler.py) ------------
    # one scheduler transition: 'phase' is campaign_start/cell_start/
    # cell_done/cell_failed/cell_skipped/deadline/campaign_done, with
    # the cell id, rejection reason, cache hit/miss evidence and
    # summary metrics riding along as diagnostics
    "campaign": {"campaign", "phase"},
    # --- v9: the stage & wire ledger (utils/costs.py) -------------------
    # one per compiled entry point (CompileLedger.emit): the program's
    # actual totals partitioned per canonical stage ('stages': stage ->
    # {flops, bytes_accessed, temp_bytes}), the unattributed residual
    # (partition sums equal the 'cost' event's totals exactly) and the
    # modeled coverage fractions the perf gate's --stageproof bars
    "stage_cost": {"name", "stages", "coverage"},
    # one per run: bytes-per-round on every protocol seam the topology
    # crosses ('seams': seam -> {bytes, ...}; the hierarchical
    # tier1_to_tier2 seam reproduces the measured SPMD all_gather
    # collective_bytes == S·d·4)
    "wire_bytes": {"topology", "seams", "total_bytes"},
    # --- v10: the measured-walls observatory (utils/walls.py) -----------
    # one measured wall per record, emitted under --profile-every.
    # source='host': host-clock timing at the engine's existing eval-
    # boundary fetch (span wall + rounds + rounds/s, eval wall) — cheap,
    # every span.  source='trace': one profiled span per K eval
    # intervals, booked onto the stage set ('stages': stage -> us,
    # plus 'unattributed_us'; the partition sums to wall_s exactly) with
    # op-event 'coverage' riding along — the runtime twin of
    # 'stage_cost', joined by 'name' for measured-vs-modeled ratios
    # ('runs walls').
    "wall": {"name", "source", "wall_s"},
    # --- v11: the population & traffic engine (core/population.py) ------
    # one record per traffic round (emitted with or without --telemetry,
    # like 'fault'): the arrived count of the sampled cohort, the
    # arrived-malicious count f_eff, and the defense-validity watchdog's
    # ladder decision ('action': remask/fallback/hold) with the defense
    # actually applied and the cohort pids riding along — host-born
    # from the PRNG-replayable schedule (replay_traffic diffs the
    # emitted stream against an independent regeneration)
    "traffic": {"round", "arrived", "action"},
    # --- v12: the robustness-margin observatory (utils/margins.py) ------
    # one record per round under --margins: the defense's in-jit
    # decision margins stripped to bare names (selection margins, gap,
    # trim kept fractions / boundary distances, Bulyan slack), the
    # host-side colluder-survival rollups (colluder_margin — the
    # DEFENSE-side worst margin over the malicious rows, <= 0 when a
    # colluder survives selection — colluder_selected, kept-mass
    # splits), the attack's envelope-utilization stats ('attack_*'),
    # the hierarchical per-shard/tier-2 stacks ('shard_margin_*' /
    # 'tier2_margin_*' with their own rollups) and traffic's f_eff
    # when a --traffic-population schedule rides along
    "margin": {"round", "defense"},
    # --- v14: the numerics & determinism observatory (utils/numerics.py)
    # one record per round under --numerics: per-stage nonfinite counts
    # (nonfinite_pre / nonfinite_post / nonfinite_agg), the gradient-
    # norm dynamic range (range_log2), the tie-proximity counters read
    # off the PR 18 margin tensors (tie_rows, banded at tie_band_ulps
    # of the decision boundary's own f32 spacing), the distance-Gram
    # cancellation-depth estimate (cancel_bits), the hierarchical
    # per-shard/tier-2 stacks on the same names ('shard_*'/'tier2_*'),
    # and the host rollups (nonfinite_total, tie_locked)
    "numerics": {"round", "defense"},
}

# Minimum schema version per kind introduced after v1; an event carrying
# one of these but stamped with an older version is an emitter bug (an
# older writer cannot know these kinds).
KIND_MIN_VERSION = {"compile": 2, "cost": 2, "heartbeat": 2,
                    "lifecycle": 3, "registry": 4, "gate": 4,
                    "secagg": 5, "shard_selection": 6, "forensics": 6,
                    "async": 7, "campaign": 8,
                    "stage_cost": 9, "wire_bytes": 9,
                    "wall": 10, "traffic": 11, "margin": 12,
                    "numerics": 14}

# Back-compat alias (pre-v3 spelling used by external readers).
V2_KINDS = {k for k, v in KIND_MIN_VERSION.items() if v == 2}


def validate_event(rec) -> dict:
    """Validate one event against the schema; returns it or raises
    ValueError.  Unknown kinds, unknown schema versions and missing
    required fields are errors; extra fields are not (diagnostics grow
    without a version bump)."""
    if not isinstance(rec, dict):
        raise ValueError(
            f"event must be a JSON object, got {type(rec).__name__}")
    v = rec.get("v", SCHEMA_VERSION)
    if v not in SUPPORTED_VERSIONS:
        # Version first: an event from a NEWER writer may carry kinds
        # this reader has never heard of — "unknown kind" would
        # misdiagnose that as emitter corruption.
        raise ValueError(
            f"unsupported event schema version {v!r} (this reader "
            f"speaks v{min(SUPPORTED_VERSIONS)}..v{max(SUPPORTED_VERSIONS)}"
            f"; a newer writer's logs need a newer reader)")
    kind = rec.get("kind")
    if kind not in EVENT_KINDS:
        raise ValueError(
            f"unknown event kind {kind!r} (schema v{SCHEMA_VERSION}; "
            f"known: {sorted(EVENT_KINDS)})")
    min_v = KIND_MIN_VERSION.get(kind, 1)
    if v < min_v:
        raise ValueError(
            f"{kind!r} events need schema v{min_v}, but this one is "
            f"stamped v{v} (emitter bug: a v{v} writer cannot produce "
            f"this kind)")
    missing = EVENT_KINDS[kind] - rec.keys()
    if missing:
        raise ValueError(
            f"{kind!r} event missing required fields {sorted(missing)}")
    if "round" in EVENT_KINDS[kind] and not isinstance(
            rec["round"], (int, float)):
        raise ValueError(
            f"{kind!r} event field 'round' must be numeric, "
            f"got {rec['round']!r}")
    return rec


def iter_events(path, validate: bool = True, skip_bad: bool = False,
                bad_lines: Optional[list] = None):
    """Yield events from a run JSONL, optionally schema-validated.
    Raises ValueError (with the line number) on a malformed line so a
    reader never silently consumes drifted events — unless ``skip_bad``
    (the cross-run readers: a crash-truncated log's torn tail must not
    make the whole run store unreadable), in which case bad lines are
    skipped and appended to ``bad_lines`` as (lineno, message)."""
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                if skip_bad:
                    if bad_lines is not None:
                        bad_lines.append((lineno, f"not JSON: {e}"))
                    continue
                raise ValueError(f"{path}:{lineno}: not JSON: {e}") from e
            if validate:
                try:
                    validate_event(rec)
                except ValueError as e:
                    if skip_bad:
                        if bad_lines is not None:
                            bad_lines.append((lineno, str(e)))
                        continue
                    raise ValueError(f"{path}:{lineno}: {e}") from e
            yield rec


def _rss_mb() -> float:
    """Resident set size in MB via /proc (no psutil on this image);
    0.0 where /proc is absent — the heartbeat still carries the ages."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
    except (OSError, ValueError, IndexError):
        pass
    return 0.0


class RunLogger:
    """Tee + CSV + structured JSONL sink; a context manager.

    ``with RunLogger(cfg) as logger:`` guarantees the JSONL handle is
    closed and the accuracy CSV is written even when the run raises
    (crash-safe ``close``).  ``finish()`` (CSV + JSONL close) is
    idempotent and leaves the tee handle open so callers can still
    ``print`` a trailing summary line; ``close()`` / ``__exit__`` shut
    everything.

    ``heartbeat_every > 0`` starts a daemon thread that appends a small
    'heartbeat' event (schema v2) every N seconds: last-seen round, a
    rounds/s EMA, resident set size, and the age of the last REAL event
    — so ``tail -f run.jsonl`` distinguishes a stalled run or a hung
    backend (age grows unbounded, rss flat) from a long compile or a
    long fused span (age grows, then one burst of round events).
    Heartbeats never update the last-event clock — they must not mask
    the very stall they exist to expose."""

    def __init__(self, config, output: Optional[str] = None,
                 log_dir: str = "logs", jsonl_name: Optional[str] = None,
                 heartbeat_every: float = 0.0):
        self.config = config
        self.output = output
        self.log_dir = log_dir
        os.makedirs(log_dir, exist_ok=True)  # the reference crashes when
        # logs/ is missing (main.py:100, readme.md:25); we create it.
        base = jsonl_name or config.csv_name().replace(".csv", "")
        self.jsonl_path = os.path.join(log_dir, base + ".jsonl")
        self._jsonl = open(self.jsonl_path, "a")
        # Reference-style tee (main.py:13-18): append semantics, but the
        # handle is opened ONCE and kept — the reference reopened the
        # file on every print.
        self._tee = open(self.output, "a") if self.output else None
        self._finished = False
        self.accuracies: list = []
        self.accuracies_epochs: list = []
        self._t0 = time.time()
        # Heartbeat state (written by record() under the lock, read by
        # the beat thread).  The JSONL handle is shared with the beat
        # thread, so every write serializes through _write_lock.
        self._write_lock = threading.Lock()
        self._last_event_time = time.time()
        self._last_round = None
        self._last_round_time = None
        self._rps_ema = None
        self._hb_stop = None
        self._hb_thread = None
        if heartbeat_every and heartbeat_every > 0:
            self._start_heartbeat(float(heartbeat_every))

    # --- context manager ------------------------------------------------
    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    # --- reference-style tee (main.py:13-18) ---------------------------
    def print(self, s, end="\n"):
        if self._tee is not None:
            self._tee.write(str(s) + end)
            self._tee.flush()  # per-call reopen flushed implicitly
        else:
            print(s, end=end, flush=True)

    def dump_config(self):
        self.print(dataclasses.asdict(self.config))

    # --- heartbeat (schema v2) -----------------------------------------
    def _start_heartbeat(self, every: float):
        self._hb_stop = threading.Event()

        def beat():
            while not self._hb_stop.wait(every):
                if self._finished:
                    return
                try:
                    self.record(**self.heartbeat_fields())
                except ValueError:
                    return      # closed mid-beat; the stop flag races
        self._hb_thread = threading.Thread(
            target=beat, name="runlogger-heartbeat", daemon=True)
        self._hb_thread.start()

    def heartbeat_fields(self) -> dict:
        """One heartbeat payload (also callable without the thread —
        tests and ad-hoc probes)."""
        now = time.time()
        rec = dict(kind="heartbeat",
                   rss_mb=round(_rss_mb(), 1),
                   last_event_age_s=round(now - self._last_event_time, 3))
        if self._last_round is not None:
            rec["round"] = self._last_round
        if self._rps_ema is not None:
            rec["rounds_per_s"] = round(self._rps_ema, 4)
        return rec

    def _note_progress(self, fields):
        """Track round progress for the heartbeat: any event carrying a
        numeric 'round' advances the last-seen round and feeds the
        rounds/s EMA.  Heartbeats themselves are excluded — they must
        not reset the stall clock they measure."""
        if fields.get("kind") == "heartbeat":
            return
        now = time.time()
        self._last_event_time = now
        rnd = fields.get("round")
        if not isinstance(rnd, (int, float)):
            return
        if (self._last_round is not None and rnd > self._last_round
                and now > self._last_round_time):
            rps = (rnd - self._last_round) / (now - self._last_round_time)
            self._rps_ema = (rps if self._rps_ema is None
                             else 0.3 * rps + 0.7 * self._rps_ema)
        if self._last_round is None or rnd >= self._last_round:
            self._last_round = rnd
            self._last_round_time = now

    # --- structured records --------------------------------------------
    def record(self, **fields):
        fields.setdefault("t", round(time.time() - self._t0, 3))
        if "kind" in fields:
            # Validate at the emitter: a malformed event fails the run
            # that produced it, not a later reader.
            fields.setdefault("v", SCHEMA_VERSION)
            validate_event(fields)
        with self._write_lock:
            if self._finished:
                # The beat thread can race finish(); a write to a closed
                # handle would turn a clean shutdown into a crash.
                raise ValueError("record() after finish()")
            self._note_progress(fields)
            self._jsonl.write(json.dumps(fields, default=float) + "\n")
            self._jsonl.flush()

    def record_eval(self, epoch, test_loss, correct, test_size, asr=None,
                    **extra):
        accuracy = 100.0 * float(correct) / test_size
        self.accuracies.append(accuracy)
        self.accuracies_epochs.append(epoch)
        # Line format mirrors reference main.py:77-80.
        self.print("Test set: [{:3d}] Average loss: {:.4f}, "
                   "Accuracy: {}/{} ({:.2f}%)".format(
                       epoch, float(test_loss), int(correct), test_size,
                       accuracy))
        rec = dict(kind="eval", round=epoch, test_loss=float(test_loss),
                   accuracy=accuracy, correct=int(correct),
                   test_size=test_size, **extra)
        if asr is not None:
            rec["attack_success_rate"] = float(asr)
        self.record(**rec)
        return accuracy

    def finish(self):
        """Write the CSV and close the JSONL.  Idempotent; the tee stays
        open (trailing summary prints still tee) until close().  The
        heartbeat thread is stopped first — the JSONL handle it writes
        through is about to close."""
        if self._finished:
            return
        if self._hb_stop is not None:
            self._hb_stop.set()
        with self._write_lock:
            if self._finished:
                return
            self._finished = True
            self._jsonl.close()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2.0)
        if self.accuracies:
            self.print("Max accuracy: {}".format(max(self.accuracies)))
            # CSV with the reference's filename schema (main.py:100).
            np.savetxt(os.path.join(self.log_dir, self.config.csv_name()),
                       np.asarray(self.accuracies), delimiter=",")

    def close(self):
        self.finish()
        if self._tee is not None and not self._tee.closed:
            self._tee.close()
