"""Cross-run observatory (ISSUE 5): the run registry, the ``runs``
CLI (list/show/diff/compare/selfcheck), the schema-v4 kinds, the
checkpoint-layout migration, Perfetto trace export, the behavioral
science gate's diff policy, and report.py over mixed-version logs.

Acceptance contract: the registry indexes journal dirs incrementally
and tolerates torn artifacts; ``runs diff`` on two same-config runs
reports the first divergent round (different seeds) or bit-identity
(identical seeds); trace export of a real run validates against the
Chrome trace-event schema; the science gate's diff names cell+metric
when a constant is perturbed and skips loudly on env mismatch.
"""

import json
import os

import pytest

from attacking_federate_learning_tpu import cli
from attacking_federate_learning_tpu.utils.metrics import validate_event
from attacking_federate_learning_tpu.utils.registry import RunRegistry


# ---------------------------------------------------------------------------
# shared run store: three journaled CLI runs (seed 0, seed 1, and an
# identical-config twin of seed 0 under its own run id)

@pytest.fixture(scope="module")
def store(tmp_path_factory, capfd_disabled=None):
    tmp = tmp_path_factory.mktemp("obs")
    base = ["-s", "SYNTH_MNIST", "-e", "6", "-c", "16",
            "--synth-train", "256", "--synth-test", "64",
            "--log-dir", str(tmp / "logs"), "--run-dir", str(tmp / "runs"),
            "-n", "10", "-m", "0.1", "-d", "Krum",
            "--round-stats", "--journal"]
    cli.main(base)
    cli.main(base + ["--seed", "1"])
    cli.main(base + ["--run-id", "twin"])
    return tmp


def _run_dir(store):
    return str(store / "runs")


def _reg(store):
    return RunRegistry(_run_dir(store))


# ---------------------------------------------------------------------------
# registry core

def test_refresh_indexes_journaled_runs(store, capsys):
    reg = _reg(store)
    summary = reg.refresh()
    ents = {e["run_id"]: e for e in reg.entries()}
    assert summary["entries"] == len(ents) >= 3
    assert "twin" in ents
    s0 = [e for e in ents.values()
          if e["run_id"].startswith("SYNTH_MNIST_Krum_s0")]
    assert len(s0) == 1
    e = s0[0]
    assert e["status"] == "done"
    assert e["rounds_committed"] == 6 and e["evals_committed"] == 2
    assert e["final_accuracy"] > 50.0
    assert e["dataset"] == "SYNTH_MNIST" and e["defense"] == "Krum"
    assert e["event_kinds"]["round"] == 6      # private per-run log
    assert os.path.exists(e["events"])


def test_refresh_is_incremental_and_idempotent(store):
    reg = _reg(store)
    reg.refresh()
    first = reg.entries()
    s2 = reg.refresh()
    assert s2["built"] == 0 and s2["reused"] == len(first)
    assert reg.entries() == first


def test_engine_stamp_makes_run_resolvable_without_refresh(store):
    """core/engine.py appends an index line at run finish, so a
    just-finished run resolves before any rescan."""
    reg = RunRegistry(_run_dir(store))
    e = reg.resolve("twin")
    assert e["status"] == "done"
    assert e["final_accuracy"] > 50.0


def test_registry_event_emitted_and_v4_schema(store):
    ev_path = RunRegistry(_run_dir(store)).resolve("twin")["events"]
    events = [json.loads(x) for x in open(ev_path).read().splitlines()]
    for e in events:
        validate_event(e)
    stamps = [e for e in events if e["kind"] == "registry"]
    assert len(stamps) == 1 and stamps[0]["run_id"] == "twin"
    assert stamps[0]["v"] >= 4
    # v4 rules: the new kinds reject an older stamp, older logs stay
    # valid.
    validate_event({"kind": "gate", "cell": "x", "status": "pass", "v": 4})
    with pytest.raises(ValueError, match="need schema v4"):
        validate_event({"kind": "registry", "run_id": "r", "v": 3})
    validate_event({"kind": "round", "round": 1, "v": 1})


def test_resolve_prefix_tag_filter_and_ambiguity(store):
    reg = _reg(store)
    reg.refresh()
    assert reg.resolve("twin")["run_id"] == "twin"
    assert reg.resolve("SYNTH_MNIST_Krum_s1")["run_id"].startswith(
        "SYNTH_MNIST_Krum_s1_")
    with pytest.raises(ValueError, match="ambiguous"):
        reg.resolve("SYNTH_MNIST_Krum_s")      # s0 and s1 both match
    with pytest.raises(ValueError, match="no run matching"):
        reg.resolve("nonexistent")
    assert [e["run_id"] for e in reg.entries(["seed=1"])] == [
        reg.resolve("SYNTH_MNIST_Krum_s1")["run_id"]]
    reg.tag("twin", "golden")
    assert reg.resolve("golden")["run_id"] == "twin"
    reg.refresh()                               # tag survives a rescan
    assert reg.resolve("golden")["run_id"] == "twin"


def test_torn_artifacts_tolerated(tmp_path):
    """A SIGKILL mid-write leaves a torn manifest/journal/index; the
    registry counts and indexes around it instead of dying."""
    d = tmp_path / "runs" / "torn_run"
    os.makedirs(d)
    with open(d / "journal.jsonl", "w") as f:
        f.write(json.dumps({"kind": "rounds", "start": 0, "end": 4}) + "\n")
        f.write('{"kind": "rounds", "start": 5, "e')       # torn tail
    with open(d / "manifest.json", "w") as f:
        f.write('{"run_id": "torn_run", "status"')          # torn
    reg = RunRegistry(str(tmp_path / "runs"))
    reg.refresh()
    e = reg.resolve("torn_run")
    assert e["journal_high"] == 4
    assert e["torn_lines"] == 1
    assert e["problems"] == ["manifest missing or torn"]
    # A torn INDEX line doesn't take the index down either.
    with open(reg.index_path, "a") as f:
        f.write('{"run_id": "half')
    assert reg.resolve("torn_run")["journal_high"] == 4


# ---------------------------------------------------------------------------
# checkpoint layout: private auto dirs + legacy migration

def test_journaled_autos_live_under_run_id_dir(tmp_path):
    out = cli.main(["-s", "SYNTH_MNIST", "-e", "4", "-c", "16",
                    "--synth-train", "128", "--synth-test", "32",
                    "--log-dir", str(tmp_path / "logs"),
                    "--run-dir", str(tmp_path / "runs"),
                    "-n", "8", "-m", "0.0", "-d", "NoDefense",
                    "--journal", "--run-id", "mine",
                    "--checkpoint-every", "2"])
    assert out["accuracies"]
    autos = [n for n in os.listdir(tmp_path / "runs" / "mine")
             if n.startswith("checkpoint-auto-")]
    assert autos    # private: no collision with runs/<dataset>/
    shared = tmp_path / "runs" / "SYNTH_MNIST"
    if shared.exists():
        assert not [n for n in os.listdir(shared)
                    if n.startswith("checkpoint-auto-")]


def test_refresh_migrates_legacy_auto_checkpoint(tmp_path):
    """Pre-PR-5 layout: the manifest references an auto-checkpoint in
    the shared runs/<dataset>/ dir; one refresh moves it (npz + json
    sidecar) under the owning runs/<run_id>/ and rewrites the
    manifest."""
    runs = tmp_path / "runs"
    legacy = runs / "SYNTH_MNIST"
    owned = runs / "legacy_run"
    os.makedirs(legacy)
    os.makedirs(owned)
    ck = legacy / "checkpoint-auto-00000004.npz"
    ck.write_bytes(b"npz-bytes")
    (legacy / "checkpoint-auto-00000004.json").write_text("{}")
    with open(owned / "manifest.json", "w") as f:
        json.dump({"run_id": "legacy_run", "status": "preempted",
                   "checkpoint": str(ck)}, f)
    reg = RunRegistry(str(runs))
    summary = reg.refresh()
    assert summary["migrated"] == 1
    moved = owned / "checkpoint-auto-00000004.npz"
    assert moved.exists() and not ck.exists()
    assert (owned / "checkpoint-auto-00000004.json").exists()
    assert json.load(open(owned / "manifest.json"))[
        "checkpoint"] == str(moved)
    # One-shot: the next refresh reuses the entry, no re-migration.
    assert reg.refresh()["migrated"] == 0
    assert reg.resolve("legacy_run")["migrated_checkpoint"] == str(moved)


def test_checkpointer_legacy_fallback(tmp_path):
    """A run-id Checkpointer with no private autos yet falls back to
    pre-migration autos in the shared dataset dir for --resume."""
    import numpy as np

    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.utils.checkpoint import (
        Checkpointer
    )

    cfg = ExperimentConfig(dataset="SYNTH_MNIST", users_count=4,
                           batch_size=8, epochs=2, synth_train=64,
                           synth_test=16,
                           run_dir=str(tmp_path / "runs"))
    shared = Checkpointer(cfg)
    from attacking_federate_learning_tpu.core.server import ServerState
    import jax.numpy as jnp

    st = ServerState(weights=jnp.zeros(4), velocity=jnp.zeros(4),
                     round=jnp.asarray(7))
    shared.save_auto(st)
    private = Checkpointer(cfg, auto_dir=str(tmp_path / "runs" / "rid"))
    assert private.latest() is not None
    assert int(np.load(private.latest())["round"]) == 7
    # Once the private dir has its own auto, it wins.
    private.save_auto(ServerState(weights=jnp.ones(4),
                                  velocity=jnp.zeros(4),
                                  round=jnp.asarray(9)))
    assert "rid" in private.latest()
    assert int(np.load(private.latest())["round"]) == 9


# ---------------------------------------------------------------------------
# the runs CLI

def test_runs_list_show_compare_selfcheck(store, capsys):
    rd = _run_dir(store)
    assert cli.main(["runs", "--run-dir", rd, "list"]) == 0
    out = capsys.readouterr().out
    assert "twin" in out and "defense=Krum" in out
    assert cli.main(["runs", "--run-dir", rd, "show", "twin"]) == 0
    out = capsys.readouterr().out
    assert "journal audit: clean" in out
    assert cli.main(["runs", "--run-dir", rd, "compare", "twin",
                     "SYNTH_MNIST_Krum_s1"]) == 0
    out = capsys.readouterr().out
    assert "final_accuracy" in out
    assert cli.main(["runs", "--run-dir", rd, "selfcheck"]) == 0
    out = capsys.readouterr().out
    assert "refresh idempotent" in out
    assert cli.main(["runs", "--run-dir", rd, "show", "nope"]) == 2


def test_runs_diff_reports_first_divergent_round(store, capsys):
    """Same config, different seed: the diff names the first round
    where the per-round records part ways (the acceptance criterion's
    'first divergent round')."""
    rd = _run_dir(store)
    assert cli.main(["runs", "--run-dir", rd, "--json", "diff",
                     "SYNTH_MNIST_Krum_s0", "SYNTH_MNIST_Krum_s1"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["config_deltas"] == {"seed": [0, 1]}
    tr = d["trajectory"]
    assert tr["bit_identical"] is False
    assert tr["divergence_round"] == 0      # seeds differ from init
    assert tr["divergence_fields"]


def test_runs_diff_bit_identity_on_same_seed(store, capsys):
    """Identical config+seed under two run ids: every shared per-round
    record must match to the bit (the determinism witness)."""
    rd = _run_dir(store)
    assert cli.main(["runs", "--run-dir", rd, "--json", "diff",
                     "SYNTH_MNIST_Krum_s0", "twin"]) == 0
    d = json.loads(capsys.readouterr().out)
    assert d.get("config_deltas") == {}
    tr = d["trajectory"]
    assert tr["bit_identical"] is True
    assert tr["divergence_round"] is None
    assert tr["rounds_compared"] == 6


def test_report_run_id_resolution(store, capsys):
    from attacking_federate_learning_tpu import report

    assert report.main(["--run-dir", _run_dir(store),
                        "--run-id", "twin", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    (summary,) = out.values()
    assert summary["accuracy"]["final"] > 50.0


# ---------------------------------------------------------------------------
# trace export

def test_trace_export_validates_against_schema(store, tmp_path):
    from attacking_federate_learning_tpu.utils.trace_export import (
        export_trace, validate_trace
    )

    entry = RunRegistry(_run_dir(store)).resolve("twin")
    out = export_trace(entry["events"], str(tmp_path / "t.json"),
                       name="twin")
    obj = json.load(open(out))
    assert validate_trace(obj) == []
    evs = obj["traceEvents"]
    rounds = [e for e in evs if e["ph"] == "X"
              and e["name"].startswith("round ")]
    assert len(rounds) == 6                 # one span per round
    assert all(e["dur"] >= 1 for e in rounds)
    names = {e["name"] for e in evs}
    assert "eval" in names                  # instants present
    assert any(n.startswith("lifecycle:") for n in names)
    metas = [e for e in evs if e["ph"] == "M"]
    assert any(e["args"]["name"] == "twin" for e in metas)


def test_trace_export_heartbeat_counters_and_compiles():
    from attacking_federate_learning_tpu.utils.trace_export import (
        events_to_trace, validate_trace
    )

    events = [
        {"kind": "compile", "name": "fused_round", "compile_s": 1.5,
         "cache": "miss", "t": 2.0, "v": 2},
        {"kind": "heartbeat", "rss_mb": 512.0, "last_event_age_s": 0.1,
         "rounds_per_s": 3.25, "t": 3.0, "v": 2},
        {"kind": "profile", "phases": {"round": {"total_s": 1.0,
                                                 "count": 5,
                                                 "mean_ms": 200.0}},
         "t": 4.0, "v": 1},
        {"kind": "gate", "cell": "krum_alie05", "status": "pass",
         "t": 5.0, "v": 4},
    ]
    obj = events_to_trace(events, name="synth")
    assert validate_trace(obj) == []
    evs = obj["traceEvents"]
    comp = [e for e in evs if e["name"] == "compile fused_round"]
    assert comp and comp[0]["dur"] == 1_500_000   # 1.5 s in us
    assert comp[0]["ts"] == 500_000               # tail-anchored
    counters = [e for e in evs if e["ph"] == "C"]
    assert {list(e["args"])[0] for e in counters} == {"rss_mb",
                                                      "rounds_per_s"}
    assert [e for e in evs if e["name"] == "round"
            and e["tid"] == 6] or True            # phases track exists
    assert any(e["name"] == "gate" for e in evs)


def test_validate_trace_names_problems():
    from attacking_federate_learning_tpu.utils.trace_export import (
        validate_trace
    )

    assert validate_trace({"nope": []})
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0},   # no dur
        {"name": "", "ph": "i", "pid": 1, "tid": 1, "ts": 1},    # no name
        {"name": "c", "ph": "C", "pid": 1, "tid": 1, "ts": 1,
         "args": {"v": "high"}},                                 # non-num
    ]}
    problems = validate_trace(bad)
    assert len(problems) == 3
    assert any("dur" in p for p in problems)


# ---------------------------------------------------------------------------
# science gate (diff policy; the cell replays are smoke.sh leg 5)

def _load_gate():
    import importlib.util

    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "science_gate.py")
    spec = importlib.util.spec_from_file_location("science_gate", path)
    sg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sg)
    return sg


def test_science_gate_diff_names_cell_and_metric():
    """A perturbed attack/defense constant shows up as a named
    cell.metric drift — exact metrics at any delta, banded metrics only
    beyond their measured ulp-tie envelope."""
    sg = _load_gate()
    baseline = {
        "nodefense_clean": {
            "final_accuracy": {"value": 80.4, "band": 0.0}},
        "krum_alie05": {
            "final_accuracy": {"value": 48.2, "band": 3.0},
            "malicious_share": {"value": 1.0, "band": 0.1}},
    }
    clean = {
        "nodefense_clean": {
            "final_accuracy": {"value": 80.4, "band": 0.0}},
        "krum_alie05": {
            "final_accuracy": {"value": 49.0, "band": 3.0},   # in band
            "malicious_share": {"value": 1.0, "band": 0.1}},
    }
    assert sg.diff(baseline, clean) == []
    # z drifting (say 0.5 -> 0.9) moves the Krum capture cell beyond
    # its band and flips the exact NoDefense cell by a hair: BOTH are
    # named.
    perturbed = {
        "nodefense_clean": {
            "final_accuracy": {"value": 80.5, "band": 0.0}},
        "krum_alie05": {
            "final_accuracy": {"value": 40.1, "band": 3.0},
            "malicious_share": {"value": 0.4, "band": 0.1}},
    }
    problems = sg.diff(baseline, perturbed)
    assert any(p.startswith("nodefense_clean.final_accuracy")
               and "exact-match" in p for p in problems)
    assert any(p.startswith("krum_alie05.final_accuracy") for p in problems)
    assert any(p.startswith("krum_alie05.malicious_share")
               and "band" in p for p in problems)
    # Vanished cells/metrics are drifts, not silence.
    assert sg.diff(baseline, {"nodefense_clean": {}}) != []


def test_science_gate_real_constant_drift_is_named():
    """The real failure mode against the REAL baseline: the ALIE z
    constant drifting 0.5 -> 1.5 (the checked-in krum_alie15 cell's
    measurements presented as krum_alie05) trips every
    selection-concentration metric by far more than its band, each
    named cell.metric."""
    sg = _load_gate()
    base = json.load(open(sg.BASELINE))["cells"]
    problems = sg.diff({"krum_alie05": base["krum_alie05"]},
                       {"krum_alie05": base["krum_alie15"]})
    assert problems
    assert all(p.startswith("krum_alie05.") for p in problems)
    named = {p.split(":")[0] for p in problems}
    assert "krum_alie05.final_accuracy" in named
    assert "krum_alie05.malicious_share" in named


def test_science_gate_env_mismatch_skips_loudly(tmp_path, capsys):
    sg = _load_gate()
    baseline = {"env": {"jax": "9.9.9", "jaxlib": "9.9.9",
                        "platform": "cpu"},
                "rounds": 10, "cells": {}}
    path = tmp_path / "bb.json"
    path.write_text(json.dumps(baseline))
    assert sg.main(["--baseline", str(path),
                    "--cells", "nodefense_clean"]) == 0
    out = capsys.readouterr().out
    assert "SKIP science_gate" in out and "environment mismatch" in out
    assert sg.main(["--baseline", str(path), "--strict-env",
                    "--cells", "nodefense_clean"]) == 1
    out = capsys.readouterr().out
    assert "FAIL science_gate" in out


def test_science_gate_missing_baseline_exit_2(tmp_path):
    sg = _load_gate()
    assert sg.main(["--baseline", str(tmp_path / "none.json")]) == 2


def test_science_gate_checked_in_baseline_shape():
    """The checked-in baseline carries provenance + the pinned cells
    with per-metric bands (the measured-band policy is part of the
    artifact, not just the tool)."""
    sg = _load_gate()
    base = json.load(open(sg.BASELINE))
    assert {"env", "rounds", "generated", "policy", "cells"} <= set(base)
    assert set(base["cells"]) == set(sg.CELLS)
    for cell, metrics in base["cells"].items():
        for m, rec in metrics.items():
            assert {"value", "band"} <= set(rec), (cell, m)
    # The selection-mediated cells carry bands; the clean mean cell is
    # exact.
    assert base["cells"]["nodefense_clean"]["final_accuracy"]["band"] == 0.0
    assert base["cells"]["krum_alie05"]["final_accuracy"]["band"] > 0.0


# ---------------------------------------------------------------------------
# report.py over mixed-version + torn logs (one invocation)

def test_report_mixed_version_and_torn_logs(tmp_path, capsys):
    from attacking_federate_learning_tpu import report

    v1 = tmp_path / "v1.jsonl"
    with open(v1, "w") as f:
        f.write(json.dumps({"kind": "eval", "round": 0, "test_loss": 0.5,
                            "accuracy": 50.0, "correct": 32,
                            "test_size": 64, "v": 1}) + "\n")
        f.write(json.dumps({"kind": "round", "round": 0,
                            "grad_norm_mean": 1.0, "v": 1}) + "\n")
    v3 = tmp_path / "v3.jsonl"
    with open(v3, "w") as f:
        f.write(json.dumps({"kind": "lifecycle", "phase": "start",
                            "attempt": 1, "v": 3}) + "\n")
        f.write(json.dumps({"kind": "heartbeat", "rss_mb": 10.0,
                            "last_event_age_s": 0.5, "v": 2}) + "\n")
        f.write(json.dumps({"kind": "eval", "round": 5, "test_loss": 0.1,
                            "accuracy": 90.0, "correct": 58,
                            "test_size": 64, "v": 3}) + "\n")
    torn = tmp_path / "torn.jsonl"
    with open(torn, "w") as f:
        f.write(json.dumps({"kind": "eval", "round": 0, "test_loss": 0.2,
                            "accuracy": 75.0, "correct": 48,
                            "test_size": 64, "v": 4}) + "\n")
        f.write('{"kind": "eval", "round": 5, "acc')       # SIGKILL here
    rc = report.main([str(v1), str(v3), str(torn), "--skip-bad",
                      "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out[str(v1)]["accuracy"]["final"] == 50.0
    assert out[str(v3)]["lifecycle"]["last_phase"] == "start"
    assert out[str(v3)]["heartbeat"]["beats"] == 1
    assert out[str(torn)]["accuracy"]["final"] == 75.0
    assert out[str(torn)]["bad_lines"] == 1
    # Without --skip-bad the torn log still fails loudly (the default
    # contract is unchanged).
    with pytest.raises(ValueError, match="not JSON"):
        report.main([str(torn)])
    # Human-readable path mentions the skip.
    assert report.main([str(torn), "--skip-bad"]) == 0
    assert "torn/invalid line(s) skipped" in capsys.readouterr().out
