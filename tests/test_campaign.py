"""Campaign engine (ISSUE 10): spec expansion determinism, the
composition-rejection pre-validation matrix, SIGKILL-mid-campaign
resume with exactly-once accounting, cache-aware ordering, the
deadline seam, and the ``runs campaign`` table render.

The kill/resume leg runs real inline campaigns in SUBPROCESSES (the
injection seams ``FL_CAMPAIGN_KILL_*`` os._exit mid-campaign); a
module-scoped fixture runs the 2x2 campaign once and several tests
audit its artifacts.  The measured grouped-vs-shuffled cache proof is
``slow``-marked (three supervisor-mode campaigns, each cell a child
process — ~70 s) — GRID_RESULTS.md round 10 records a measured run.
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.campaigns import (
    Campaign, CampaignJournal, CampaignSpec, cell_id_for,
    composition_reject_reason, hlo_signature, order_cells
)
from attacking_federate_learning_tpu.campaigns.scheduler import (
    EXIT_DEADLINE, adjacency, trim_cache
)
from attacking_federate_learning_tpu.campaigns.spec import (
    cfg_to_cli_args, verify_cli_round_trip
)
from attacking_federate_learning_tpu.config import ExperimentConfig


def _base(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 12)
    kw.setdefault("mal_prop", 0.25)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 2)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("log_dir", os.path.join(str(tmp_path), "logs"))
    kw.setdefault("run_dir", os.path.join(str(tmp_path), "runs"))
    return kw


class RecordingExecutor:
    """Fake executor: records which cells execute, returns canned
    results, and can advance an injected clock per cell."""

    def __init__(self, clock=None, step=0.0):
        self.cells = []
        self.clock = clock
        self.step = step

    def run(self, cell, camp):
        self.cells.append(cell.cell_id)
        if self.clock is not None:
            self.clock.t += self.step
        return {"state": "done", "rc": 0, "final_accuracy": 50.0,
                "max_accuracy": 50.0, "rounds": cell.cfg.epochs,
                "wall_s": 0.0}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# expansion determinism + identity

def test_spec_expansion_deterministic(tmp_path):
    spec = CampaignSpec(
        name="det", base=_base(tmp_path),
        axes={"defense": ["NoDefense", "Krum"],
              "attack": ["none", "alie"], "seed": [0, 1]})
    a = spec.expand()
    b = spec.expand()
    assert [c.cell_id for c in a] == [c.cell_id for c in b]
    assert [c.group for c in a] == [c.group for c in b]
    assert len(a) == 8 and len({c.cell_id for c in a}) == 8
    # JSON round trip preserves identity and expansion.
    spec2 = CampaignSpec.from_json(spec.to_json())
    assert spec2.campaign_id == spec.campaign_id
    assert [c.cell_id for c in spec2.expand()] == [c.cell_id for c in a]
    # The attack name is part of cell identity: two attacks sharing a
    # config (alie vs signflip) must not share a journal.
    cfg = ExperimentConfig(**_base(tmp_path))
    assert cell_id_for(cfg, "alie") != cell_id_for(cfg, "signflip")
    assert cell_id_for(cfg, "auto") != cell_id_for(cfg, "alie")


def test_spec_duplicate_cells_rejected(tmp_path):
    spec = CampaignSpec(name="dup", base=_base(tmp_path),
                        axes={"defense": ["Krum", "Krum"]})
    with pytest.raises(ValueError, match="duplicate cell id"):
        spec.expand()


def test_hlo_signature_groups(tmp_path):
    """The grouping heuristic measured on this engine: epochs and the
    io/cadence fields are program-inert, seed and the defense are not
    (the training set is baked into the fused span as constants)."""
    cfg = ExperimentConfig(**_base(tmp_path))
    same = dataclasses.replace(cfg, epochs=8, checkpoint_every=5,
                               log_dir="elsewhere")
    assert hlo_signature(cfg) == hlo_signature(same)
    assert hlo_signature(cfg) != hlo_signature(
        dataclasses.replace(cfg, seed=1))
    assert hlo_signature(cfg) != hlo_signature(
        dataclasses.replace(cfg, defense="Krum"))
    assert hlo_signature(cfg, "alie") != hlo_signature(cfg, "signflip")


# ---------------------------------------------------------------------------
# the composition-rejection matrix, pre-validated

# (overrides, attack, message fragment) — every known-invalid combo the
# pre-check must skip.  Spans config-level rejections (ExperimentConfig
# __post_init__) and engine-level ones (the pure init checks).
_INVALID = [
    (dict(defense="Bulyan", users_count=10, mal_prop=0.24), "alie",
     "4*corrupted_count"),
    (dict(defense="Krum", users_count=8, mal_prop=0.5), "alie",
     "2*corrupted_count"),
    (dict(secagg="vanilla", defense="Krum"), "auto",
     "server never sees per-client"),
    (dict(secagg="groupwise", aggregation="flat"), "auto",
     "requires --aggregation hierarchical"),
    (dict(secagg="vanilla", telemetry=True), "auto",
     "nothing per-client OR per-group"),
    (dict(aggregation="hierarchical", megabatch=5, users_count=12),
     "auto", "must divide users_count"),
    # ISSUE 19: hierarchical ⊕ faults is now a VALID composition; the
    # rejections that remain are the real structural ones — correlated
    # shard-domain death needs shard domains to kill, and the straggler
    # ring buffer is a cross-round carry the SPMD client_map can't
    # thread.
    (dict(faults=dict(shard_dropout=0.3), defense="Median"), "auto",
     "shard-DOMAIN"),
    (dict(aggregation="hierarchical", megabatch=4, users_count=32,
          mesh_shape=[8, 1], faults=dict(straggler=0.1),
          defense="TrimmedMean"), "auto", "SPMD client_map"),
    (dict(aggregation="hierarchical", megabatch=4,
          defense="GeoMedian"), "auto", "tier-1 defense"),
    (dict(aggregation="async", async_buffer=0), "auto",
     "--async-buffer >= 1"),
    (dict(aggregation="async", async_buffer=20, users_count=12,
          mal_prop=0.25), "auto", "exceeds the cohort"),
    (dict(aggregation="async", async_buffer=4, defense="TrimmedMean",
          users_count=12, mal_prop=0.25), "auto", "k - f - 1"),
    (dict(backdoor="pattern"), "backdoor_timed",
     "requires aggregation='async'"),
    (dict(faults=dict(dropout=0.2), defense="DnC"), "auto",
     "mask-aware defense"),
    (dict(participation=0.25, users_count=12, mal_prop=0.1), "alie",
     "malicious cohort to 0"),
]


@pytest.mark.parametrize("overrides,attack,fragment", _INVALID)
def test_rejection_matrix_precheck(tmp_path, overrides, attack,
                                   fragment):
    merged = _base(tmp_path, **overrides)
    reason = composition_reject_reason(merged, attack)
    assert reason is not None and fragment in reason, (reason, fragment)


def test_precheck_agrees_with_real_construction(tmp_path):
    """The pre-check must not drift from what the engine actually
    rejects: for engine-level combos, FederatedExperiment construction
    raises the SAME message the pre-check returned."""
    from attacking_federate_learning_tpu.attacks import make_attacker
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset

    cases = [
        dict(defense="Bulyan", users_count=10, mal_prop=0.24),
        dict(faults=dict(shard_dropout=0.3), defense="Median"),
        dict(aggregation="async", async_buffer=20, users_count=12,
             mal_prop=0.25),
    ]
    ds = load_dataset(C.SYNTH_MNIST, seed=0, synth_train=256,
                      synth_test=64)
    for overrides in cases:
        merged = _base(tmp_path, **overrides)
        reason = composition_reject_reason(merged, "alie")
        assert reason
        cfg = ExperimentConfig(**merged)       # config itself is fine
        with pytest.raises(ValueError) as ei:
            FederatedExperiment(
                cfg, attacker=make_attacker(cfg, dataset=ds,
                                            name="alie"), dataset=ds)
        assert str(ei.value) == reason


def test_skipped_cells_never_reach_the_executor(tmp_path):
    spec = CampaignSpec(
        name="rej", base=_base(tmp_path),
        axes={"defense": ["NoDefense", "Bulyan"],
              "attack": ["none", "alie"]})
    rec = RecordingExecutor()
    camp = Campaign(spec, executor=rec, journal_runs=False,
                    persist=False)
    assert camp.run() == 0
    cells = spec.expand()
    skipped = [c for c in cells if c.skip]
    assert {(c.overrides["defense"], c.attack) for c in skipped} == {
        ("Bulyan", "alie")}
    executed = set(rec.cells)
    assert all(c.cell_id not in executed for c in skipped)
    assert len(executed) == 3
    # The skip carried the rejection message into the journal record.
    rec_j = camp.journal.cells[skipped[0].cell_id]
    assert rec_j["state"] == "skipped"
    assert "4*corrupted_count" in rec_j["reason"]


# ---------------------------------------------------------------------------
# ordering

def _cells_two_groups(tmp_path):
    spec = CampaignSpec(
        name="ord", base=_base(tmp_path),
        axes={"defense": ["Krum", "TrimmedMean"],
              "epochs": [2, 4, 6, 8]})
    return spec, spec.expand()


def test_grouped_ordering_is_adjacent_and_deterministic(tmp_path):
    spec, cells = _cells_two_groups(tmp_path)
    assert len({c.group for c in cells}) == 2       # 2 HLO groups
    g = order_cells(cells, "grouped", spec.campaign_id)
    assert adjacency(g) == len(cells) - 2           # fully contiguous
    assert [c.cell_id for c in g] == [
        c.cell_id for c in order_cells(cells, "grouped",
                                       spec.campaign_id)]
    # spec order interleaves the groups (defense is the outer axis...
    # epochs inner, so spec order is already grouped here); shuffled
    # must be deterministic and is the measured control arm.
    s1 = order_cells(cells, "shuffled", spec.campaign_id)
    s2 = order_cells(cells, "shuffled", spec.campaign_id)
    assert [c.cell_id for c in s1] == [c.cell_id for c in s2]
    assert adjacency(s1) <= adjacency(g)


def test_priority_bands_override_grouping(tmp_path):
    spec = CampaignSpec(
        name="prio", base=_base(tmp_path),
        axes={"defense": ["Krum", "TrimmedMean"], "epochs": [2, 4]},
        priorities={"defense=TrimmedMean": 10})
    cells = spec.expand()
    ordered = order_cells(cells, "grouped", spec.campaign_id)
    # The high-priority band runs first, grouping applies inside it.
    assert [c.overrides["defense"] for c in ordered] == [
        "TrimmedMean", "TrimmedMean", "Krum", "Krum"]


def test_trim_cache_evicts_oldest(tmp_path):
    d = tmp_path / "cache"
    os.makedirs(d)
    for i, name in enumerate(["a-cache", "b-cache", "c-cache"]):
        p = d / name
        p.write_bytes(b"x" * 100)
        os.utime(p, (i, i))                    # a oldest, c newest
        (d / (name + "-atime")).write_bytes(b"")
    evicted = trim_cache(str(d), 250)
    assert evicted == 1
    left = {f for f in os.listdir(d) if not f.endswith("-atime")}
    assert left == {"b-cache", "c-cache"}      # a (oldest) evicted
    assert not os.path.exists(d / "a-cache-atime")


# ---------------------------------------------------------------------------
# deadline stop + resume (injected clock, fake executor)

def test_deadline_stop_then_resume(tmp_path):
    spec = CampaignSpec(name="dl", base=_base(tmp_path),
                        axes={"defense": ["NoDefense", "Krum",
                                          "Median", "TrimmedMean"]})
    clock = FakeClock()
    rec = RecordingExecutor(clock=clock, step=10.0)
    camp = Campaign(spec, executor=rec, journal_runs=False,
                    deadline_s=25.0, clock=clock)
    rc = camp.run()
    assert rc == EXIT_DEADLINE
    assert len(rec.cells) == 3          # 0s, 10s, 20s; 30s > deadline
    man = camp.journal.read_manifest()
    assert man["status"] == "deadline"
    pending = [cid for cid, row in man["cells"].items()
               if row["state"] == "pending"]
    assert len(pending) == 1
    # Resume with a fresh deadline: only the remaining cell executes.
    clock2 = FakeClock()
    rec2 = RecordingExecutor(clock=clock2, step=10.0)
    camp2 = Campaign(spec, executor=rec2, journal_runs=False,
                     deadline_s=25.0, clock=clock2)
    assert camp2.run() == 0
    assert rec2.cells == pending
    j = CampaignJournal(camp2.run_dir, spec.campaign_id)
    assert j.verify([c.cell_id for c in spec.expand()]) == []
    assert j.read_manifest()["status"] == "done"
    assert j.attempt == 2


def test_journal_recommit_refused_and_torn_tail_sealed(tmp_path):
    j = CampaignJournal(str(tmp_path), "c1")
    j.start_attempt()
    j.commit_cell("cell_a", "done", rc=0)
    with pytest.raises(ValueError, match="exactly-once"):
        j.commit_cell("cell_a", "failed")
    with pytest.raises(ValueError, match="state must be"):
        j.commit_cell("cell_b", "running")
    j.close()
    # A SIGKILL mid-append leaves a torn tail; the next attempt seals
    # and skips it without losing committed records.
    with open(j.journal_path, "a") as f:
        f.write('{"kind": "cell", "cell": "torn')
    j2 = CampaignJournal(str(tmp_path), "c1")
    assert j2.torn_lines == 1
    assert j2.state_of("cell_a") == "done"
    j2.commit_cell("cell_b", "skipped", reason="x")
    j3 = CampaignJournal(str(tmp_path), "c1")
    assert j3.state_of("cell_b") == "skipped"
    assert j3.verify() == []


# ---------------------------------------------------------------------------
# kill mid-campaign -> resume (real subprocesses, inline executor)

CLI_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _invoke_campaign(spec_path, env=None, expect=0):
    r = subprocess.run(
        [sys.executable, "-m", "attacking_federate_learning_tpu.campaigns",
         str(spec_path), "--executor", "inline"],
        env=env or CLI_ENV, capture_output=True, text=True)
    assert r.returncode == expect, (r.returncode, r.stderr[-2000:])
    return r


@pytest.fixture(scope="module")
def killed_campaign(tmp_path_factory):
    """One real 2x2 campaign, SIGKILLed (os._exit injection) after two
    cells, then resumed to completion; several tests audit it."""
    work = tmp_path_factory.mktemp("campaign_kill")
    base = _base(work)
    spec = dict(name="kr", base=base,
                axes={"defense": ["Krum", "TrimmedMean"],
                      "attack": ["none", "alie"]})
    spec_path = work / "spec.json"
    spec_path.write_text(json.dumps(spec))
    env = dict(CLI_ENV, FL_CAMPAIGN_KILL_AFTER_CELLS="2")
    _invoke_campaign(spec_path, env=env, expect=137)
    # Mid-campaign state: exactly 2 terminal cells, the rest pending.
    camp_id = os.listdir(os.path.join(base["run_dir"], "campaigns"))[0]
    j = CampaignJournal(base["run_dir"], camp_id)
    assert len(j.cells) == 2
    _invoke_campaign(spec_path)
    return {"work": work, "base": base, "camp_id": camp_id,
            "spec": CampaignSpec.from_json(json.dumps(spec))}


def test_kill_resume_exactly_once(killed_campaign):
    base = killed_campaign["base"]
    camp_id = killed_campaign["camp_id"]
    spec = killed_campaign["spec"]
    j = CampaignJournal(base["run_dir"], camp_id)
    expected = [c.cell_id for c in spec.expand()]
    assert j.verify(expected) == []
    man = j.read_manifest()
    assert man["status"] == "done"
    assert man["counts"] == {"done": 4}
    assert j.attempt == 2
    # Commits split across the two attempts — the resume executed only
    # the remaining cells.
    by_attempt = {}
    for rec in j.records():
        if rec.get("kind") == "cell":
            by_attempt.setdefault(rec["attempt"], []).append(rec["cell"])
    assert len(by_attempt[1]) == 2 and len(by_attempt[2]) == 2


def test_kill_resume_zero_duplicate_registry_stamps(killed_campaign):
    base = killed_campaign["base"]
    idx = os.path.join(base["run_dir"], "index.jsonl")
    ids = [json.loads(line)["run_id"] for line in open(idx)]
    assert len(ids) == 4
    assert len(ids) == len(set(ids))


def test_campaign_event_stream_validates_v8(killed_campaign):
    import importlib.util

    from attacking_federate_learning_tpu.utils.metrics import iter_events

    base = killed_campaign["base"]
    camp_id = killed_campaign["camp_id"]
    events_path = os.path.join(base["run_dir"], "campaigns", camp_id,
                               "events.jsonl")
    events = list(iter_events(events_path))       # emitter validation
    assert all(e["kind"] == "campaign" and e["v"] >= 8 for e in events)
    phases = [e["phase"] for e in events]
    assert phases.count("campaign_start") == 2    # two attempts
    assert phases.count("cell_done") == 4
    assert phases.count("campaign_done") == 1     # only the resume ends
    # The standalone validator (CI's view) agrees.
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "check_events.py")
    s = importlib.util.spec_from_file_location("check_events", path)
    ce = importlib.util.module_from_spec(s)
    s.loader.exec_module(ce)
    counts, _, errors = ce.check_file(events_path)
    assert errors == [] and counts == {"campaign": len(events)}


def test_runs_campaign_table_matches_manifests_bit_exactly(
        killed_campaign, capsys):
    """Acceptance: the rendered table's values come from the registry
    and match the per-run manifest values bit-exactly; skipped cells
    show their rejection reason."""
    from attacking_federate_learning_tpu.report import campaign_table
    from attacking_federate_learning_tpu.runs_cli import main as runs_main

    base = killed_campaign["base"]
    camp_id = killed_campaign["camp_id"]
    rc = runs_main(["--run-dir", base["run_dir"], "--bench", "",
                    "--progress", "", "--json", "campaign", camp_id])
    assert rc == 0
    blob = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    table = blob["table"]
    assert table["rows"] == ["Krum", "TrimmedMean"]
    assert table["cols"] == ["none", "alie"]
    for cid, row in blob["manifest"]["cells"].items():
        (rec,) = table["cells"][f"{row['defense']}|{row['attack']}"]
        assert rec["source"] == "registry"
        run_man = json.load(open(os.path.join(
            base["run_dir"], cid, "manifest.json")))
        assert rec["final_accuracy"] == run_man["final_accuracy"]
        assert rec["max_accuracy"] == run_man["max_accuracy"]
    # Human render carries the skip column for a campaign with one.
    spec2 = CampaignSpec(
        name="skiprender", base=killed_campaign["base"],
        axes={"defense": ["Bulyan"], "attack": ["alie"]})
    spec2.base["mal_prop"] = 0.25
    man2 = {"campaign_id": "x", "status": "done",
            "cells": {c.cell_id: {**c.row(), "state": "skipped",
                                  "reason": c.skip}
                      for c in spec2.expand()}}
    t2 = campaign_table(man2, {})
    (rec2,) = t2["cells"]["Bulyan|alie"]
    assert rec2["state"] == "skipped"
    assert "4*corrupted_count" in rec2["reason"]


def test_kill_before_commit_adopts_without_rerun(tmp_path):
    """The harsher kill point: the cell's run FINISHED (journal 'done',
    registry stamped) but the campaign commit never happened.  Resume
    must adopt the finished run instead of re-executing — zero
    duplicate registry stamps is the observable contract."""
    base = _base(tmp_path)
    spec = dict(name="kb", base=base, axes={"defense": ["NoDefense",
                                                        "Krum"]})
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(json.dumps(spec))
    env = dict(CLI_ENV, FL_CAMPAIGN_KILL_BEFORE_COMMIT="1")
    _invoke_campaign(spec_path, env=env, expect=137)
    camp_id = os.listdir(os.path.join(base["run_dir"], "campaigns"))[0]
    j = CampaignJournal(base["run_dir"], camp_id)
    assert j.cells == {}                     # nothing committed...
    idx = os.path.join(base["run_dir"], "index.jsonl")
    assert len(open(idx).readlines()) == 1   # ...but the run stamped
    _invoke_campaign(spec_path)
    j2 = CampaignJournal(base["run_dir"], camp_id)
    assert j2.read_manifest()["counts"] == {"done": 2}
    adopted = [rec for rec in j2.cells.values() if rec.get("adopted")]
    assert len(adopted) == 1                 # the killed cell, adopted
    ids = [json.loads(line)["run_id"] for line in open(idx)]
    assert len(ids) == 2 and len(set(ids)) == 2   # still no duplicates


# ---------------------------------------------------------------------------
# stale-index footgun

def test_runs_list_no_refresh_warns_when_stale(tmp_path, capsys):
    from attacking_federate_learning_tpu.runs_cli import main as runs_main
    from attacking_federate_learning_tpu.utils.registry import RunRegistry

    run_dir = tmp_path / "runs"
    d = run_dir / "r1"
    os.makedirs(d)
    (d / "manifest.json").write_text(json.dumps(
        {"run_id": "r1", "status": "done"}))
    reg = RunRegistry(str(run_dir))
    reg.refresh()
    assert reg.stale_run_ids() == []
    capsys.readouterr()
    # The store moves under the index (backdate the index rather than
    # future-date the manifest, so the refresh below really clears it).
    os.utime(reg.index_path,
             (os.path.getmtime(d / "manifest.json") - 5,) * 2)
    assert reg.stale_run_ids() == ["r1"]
    rc = runs_main(["--run-dir", str(run_dir), "--bench", "",
                    "--progress", "", "list", "--no-refresh"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "WARNING" in out and "stale" in out
    # A refreshing list clears the staleness (and the warning).
    rc = runs_main(["--run-dir", str(run_dir), "--bench", "",
                    "--progress", "", "list"])
    assert rc == 0
    assert "WARNING" not in capsys.readouterr().out
    assert reg.stale_run_ids() == []


# ---------------------------------------------------------------------------
# the CLI round trip (supervisor executor's child surface)

def test_cfg_to_cli_args_round_trip(tmp_path):
    cases = [
        _base(tmp_path),
        _base(tmp_path, defense="Krum", seed=3, partition="dirichlet",
              dirichlet_alpha=0.3, participation=0.5, mal_prop=0.5),
        _base(tmp_path, aggregation="hierarchical", megabatch=4,
              tier2_defense="Krum", mal_placement="concentrated",
              telemetry=True),
        _base(tmp_path, aggregation="async", async_buffer=8,
              staleness_weight="poly", defense="Krum"),
        _base(tmp_path, faults=dict(dropout=0.1, corrupt=0.05,
                                    corrupt_mode="scale"),
              defense="Median", checkpoint_every=2),
        # ISSUE 19: faults ⊕ hierarchical round-trips, shard-domain
        # flags included.
        _base(tmp_path, aggregation="hierarchical", megabatch=4,
              defense="TrimmedMean",
              faults=dict(dropout=0.1, shard_dropout=0.25,
                          shard_dropout_dwell=2)),
        _base(tmp_path, secagg="vanilla", defense="NoDefense",
              backdoor="pattern"),
    ]
    for kw in cases:
        cfg = ExperimentConfig(**kw)
        for attack in ("auto", "alie"):
            from attacking_federate_learning_tpu.campaigns.spec import (
                Cell
            )
            cell = Cell(cell_id=cell_id_for(cfg, attack), overrides=kw,
                        attack=attack, cfg=cfg)
            assert verify_cli_round_trip(cell) is None, kw
    # An inexpressible field fails LOUDLY instead of silently running
    # a drifted config.
    cfg = ExperimentConfig(**_base(tmp_path, test_step=3))
    from attacking_federate_learning_tpu.campaigns.spec import Cell
    cell = Cell(cell_id=cell_id_for(cfg, "auto"), overrides={},
                attack="auto", cfg=cfg)
    problem = verify_cli_round_trip(cell)
    assert problem is not None and "not expressible" in problem


def test_grid_spec_delegation_matches_historical_rows(tmp_path):
    """grid.py is now a campaign wrapper: the summary keeps the
    historical row shape and the skip semantics (tests/test_grid.py
    pins the behavioral contract; this pins the spec plumbing)."""
    from attacking_federate_learning_tpu.grid import grid_spec

    base = ExperimentConfig(**_base(tmp_path))
    spec = grid_spec(base, ["NoDefense", "Krum"], ["none", "alie"])
    cells = spec.expand()
    assert [(c.overrides["defense"], c.attack) for c in cells] == [
        ("NoDefense", "none"), ("NoDefense", "alie"),
        ("Krum", "none"), ("Krum", "alie")]
    # 'none' zeroes the malicious cohort (the historical mapping).
    assert cells[0].cfg.mal_prop == 0.0 and cells[0].cfg.num_std == 0.0
    assert cells[1].cfg.mal_prop == base.mal_prop


# ---------------------------------------------------------------------------
# measured cache-ordering proof (slow: 3 supervisor campaigns, each
# cell a fresh child process — the in-memory compile cache would mask
# eviction inside a single process)

@pytest.mark.slow
def test_cache_ordering_grouped_beats_shuffled_measured(tmp_path):
    def make_spec(arm_dir):
        return dict(
            name="proof",
            base=dict(dataset=C.SYNTH_MNIST, users_count=10,
                      mal_prop=0.2, batch_size=16, synth_train=256,
                      synth_test=64, backend="cpu",
                      log_dir=os.path.join(arm_dir, "logs"),
                      run_dir=os.path.join(arm_dir, "runs")),
            axes={"defense": ["Krum", "TrimmedMean"],
                  "epochs": [5, 10, 15, 20]})

    def run_arm(name, order, budget_mb):
        arm_dir = os.path.join(str(tmp_path), f"{name}_{order}")
        spec_path = os.path.join(str(tmp_path), f"{name}_{order}.json")
        with open(spec_path, "w") as f:
            json.dump(make_spec(arm_dir), f)
        r = subprocess.run(
            [sys.executable, "-m",
             "attacking_federate_learning_tpu.campaigns", spec_path,
             "--executor", "supervisor", "--order", order,
             "--cache-dir", os.path.join(arm_dir, "cache"),
             "--cache-budget-mb", str(budget_mb)],
            env=CLI_ENV, capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-2000:]
        camp_root = os.path.join(arm_dir, "runs", "campaigns")
        (cid,) = os.listdir(camp_root)
        with open(os.path.join(camp_root, cid, "manifest.json")) as f:
            return json.load(f)

    # The two orderings must actually differ (>=8 cells, 2 groups).
    spec = CampaignSpec.from_json(json.dumps(make_spec("x")))
    cells = spec.expand()
    assert len(cells) == 8 and len({c.group for c in cells}) == 2
    g = order_cells(cells, "grouped", spec.campaign_id)
    s = order_cells(cells, "shuffled", spec.campaign_id)
    assert adjacency(s) < adjacency(g)

    # Probe: grouped, unbounded — measures the per-group cache size.
    man_p = run_arm("probe", "grouped", 0.0)
    exec_ids = [c.cell_id for c in g]
    bytes_after = [man_p["cells"][cid]["cache_bytes"]
                   for cid in exec_ids]
    size_a, total = bytes_after[3], bytes_after[-1]
    size_b = total - size_a
    budget_mb = max(size_a, size_b) * 1.15 / 1e6
    assert budget_mb * 1e6 < total      # one group fits, both don't

    man_g = run_arm("meas", "grouped", budget_mb)
    man_s = run_arm("meas", "shuffled", budget_mb)
    # Acceptance: the manifests record a higher persistent-cache hit
    # count under grouped ordering, measured by the PR 3 counters.
    assert man_g["cache"]["hits"] > man_s["cache"]["hits"]
    assert man_g["cache"]["misses"] < man_s["cache"]["misses"]
    per_cell = [man_g["cells"][cid].get("cache_hits", 0)
                for cid in exec_ids]
    assert sum(per_cell) == man_g["cache"]["hits"]
