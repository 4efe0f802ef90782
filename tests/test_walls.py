"""Measured-walls observatory (ISSUE 16): utils/walls.py booking,
engine --profile-every wiring, schema-v10 wall events, the runs-walls
verb and the noise-banded wall gate.

Acceptance contract: the trace-to-HLO booking partitions exactly
(stage sums + unattributed == total, same floats) on all three engines
x two defenses over REAL profiler captures; FL_STAGE_SCOPES=0 books
everything to unattributed; profiling off leaves the round program's
HLO fingerprint-identical; ``runs walls`` renders single/diff/--json
and exits 1 on a walls-less run; and a --profile-every run's log
round-trips through validate_event at schema v10.

The real-capture tests run in SUBPROCESSES: op-level CPU trace events
need ``--xla_cpu_enable_xprof_traceme=true`` in XLA_FLAGS before the
process's FIRST compile, and this warm pytest process compiled long
ago (utils/profiling.py:ensure_op_profiling documents the seam).
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.utils import walls
from attacking_federate_learning_tpu.utils.costs import (
    STAGES, hlo_fingerprint, set_stage_scopes
)
from attacking_federate_learning_tpu.utils.metrics import (
    SCHEMA_VERSION, iter_events, validate_event
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _subproc_env():
    """Child env with the xprof op-trace flag live from process start
    (the child's first compile sees it; this process's cannot)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "--xla_cpu_enable_xprof_traceme=true" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_cpu_enable_xprof_traceme=true").strip()
    return env


def _exp(**kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 9)
    kw.setdefault("mal_prop", 0.22)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 4)
    kw.setdefault("test_step", 4)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    cfg = ExperimentConfig(**kw)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    return FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)


# ---------------------------------------------------------------------------
# booking primitives (synthetic, no trace needed)

_HLO = """\
HloModule jit_round
ENTRY main {
  %dot.4 = f32[8,8]{1,0} dot(a, b), metadata={op_name="jit(round)/deliver/tier1_aggregate/dot" source_file="x"}
  %add.1 = f32[8]{0} add(c, d), metadata={op_name="jit(round)/deliver/add"}
  ROOT %mul.2 = f32[8]{0} multiply(e, f)
}
"""


def test_hlo_stage_map_innermost_token_rule():
    m = walls.hlo_stage_map(_HLO)
    # Innermost (LAST) stage token wins, not the outer scope.
    assert m["dot.4"] == "tier1_aggregate"
    assert m["add.1"] == "deliver"
    # ROOT-prefixed instruction parsed; no op_name -> unattributed.
    assert m["mul.2"] is None


def test_book_events_exact_partition_and_coverage():
    stage_map = {"dot.4": "tier1_aggregate", "add.1": "deliver",
                 "mul.2": None}
    events = [
        {"ph": "X", "name": "dot.4", "dur": 100.5},
        {"ph": "X", "name": "dot.4", "dur": 0.25},      # repeats sum
        {"ph": "X", "name": "add.1", "dur": 7.0},
        {"ph": "X", "name": "mul.2", "dur": 3.5},       # unattributed
        {"ph": "X", "name": "TfrtCpuExecutable::Execute", "dur": 900.0},
        {"ph": "X", "name": "some_python_frame", "dur": 50.0},
    ]
    rec = walls.book_events(events, stage_map, name="fused_span")
    assert rec.stages == {"tier1_aggregate": 100.75, "deliver": 7.0}
    assert rec.unattributed_us == 3.5
    # The partition identity: same floats, not a tolerance.
    assert sum(rec.stages.values()) + rec.unattributed_us == rec.total_us
    rec.check()
    cov = rec.coverage
    assert cov["op_events"] == 4
    assert cov["runtime_us"] == 900.0       # classified, never booked
    assert cov["unknown_us"] == 50.0
    assert cov["booked_us"] == 111.25
    assert cov["op_time_fraction"] == pytest.approx(
        111.25 / (111.25 + 50.0), abs=1e-4)


def test_wall_event_validates_at_v10():
    rec = walls.book_events(
        [{"ph": "X", "name": "dot.4", "dur": 10.0}],
        {"dot.4": "tier1_aggregate"}, name="fused_span",
        platform="cpu", rounds=3)
    ev = rec.wall_event()
    ev["v"] = SCHEMA_VERSION
    ev["t"] = 0.0
    assert validate_event(ev) is ev
    # A v10 kind stamped with an older writer version is an emitter bug.
    ev_old = dict(ev, v=9)
    with pytest.raises(ValueError):
        validate_event(ev_old)


def test_measured_vs_modeled_shares_and_ratios():
    wall = {"stages": {"deliver": 300.0, "tier1_aggregate": 100.0},
            "unattributed_us": 0.0}
    cost = {"stages": {"deliver": {"flops": 100.0},
                       "tier1_aggregate": {"flops": 100.0}},
            "unattributed": {"flops": 0.0}}
    out = walls.measured_vs_modeled(wall, cost)
    assert out["deliver"]["measured_share"] == 0.75
    assert out["deliver"]["modeled_share"] == 0.5
    assert out["deliver"]["ratio"] == 1.5
    assert out["tier1_aggregate"]["ratio"] == 0.5
    # A stage with measured time but no modeled mass gets None, not 0.
    wall2 = {"stages": {"protect": 10.0}, "unattributed_us": 0.0}
    out2 = walls.measured_vs_modeled(wall2, cost)
    assert out2["protect"]["ratio"] is None


# ---------------------------------------------------------------------------
# scopes-off + fingerprint invariants (compiled programs, no trace)

def test_scopes_off_span_text_books_all_to_unattributed():
    prev = set_stage_scopes(False)
    try:
        exp = _exp(defense="Krum")
        text = exp._span_hlo_text(2)
    finally:
        set_stage_scopes(prev)
    smap = walls.hlo_stage_map(text)
    assert smap, "span HLO parsed no instructions"
    assert all(v is None for v in smap.values())
    # Booking a synthetic capture over those instructions lands 100%
    # in unattributed — scopes off degrades loudly, never invents.
    names = list(smap)[:5]
    rec = walls.book_events(
        [{"ph": "X", "name": n, "dur": 1.0} for n in names], smap)
    assert rec.stages == {}
    assert rec.unattributed_us == float(len(names))
    rec.check()


def test_profile_every_leaves_hlo_fingerprint_identical():
    off = _exp(defense="Krum", profile_every=0)
    on = _exp(defense="Krum", profile_every=2)
    f_off = hlo_fingerprint(off._span_hlo_text(3))
    f_on = hlo_fingerprint(on._span_hlo_text(3))
    assert f_off == f_on
    t0 = jnp.asarray(0, jnp.int32)
    r_off = off._fused_round.lower(off.state, t0).as_text()
    r_on = on._fused_round.lower(on.state, t0).as_text()
    assert hlo_fingerprint(r_off) == hlo_fingerprint(r_on)


def test_failed_wall_booking_is_counted_not_raised(tmp_path, monkeypatch,
                                                   capsys):
    """A booking that raises (or a capture that left no trace) never
    sinks the run, but it is counted in the run's result and named in
    the exit summary — a chip run can no longer drop every wall event
    without a word."""
    def boom(*a, **k):
        raise RuntimeError("unparseable trace")

    monkeypatch.setattr(walls, "book_trace", boom)
    exp = _exp(defense="Krum", profile_every=1, log_dir=str(tmp_path))
    result = exp.run()
    assert result["wall_booking_failures"] == 2     # spans r0 and r1-3
    out = capsys.readouterr().out
    assert "[walls] booking failed: RuntimeError" in out
    assert "[walls] 2 profiled span(s) produced no wall booking" in out


def test_span_entry_names_match_cost_report_ledger():
    assert _exp(defense="Krum")._span_entry_name() == "fused_span"
    assert _exp(defense="Krum", aggregation="hierarchical",
                users_count=12, mal_prop=0.25,
                megabatch=4)._span_entry_name() == "hier_span"
    assert _exp(defense="Krum", aggregation="async",
                async_buffer=8, users_count=12,
                mal_prop=0.25)._span_entry_name() == "async_span"
    assert _exp(defense="Krum",
                telemetry=True)._span_entry_name() == "tele_span"


# ---------------------------------------------------------------------------
# REAL captures: partition invariant across the three engines (subprocess —
# the xprof flag must precede the child's first compile)

_MATRIX_SCRIPT = r"""
import json, os, sys, tempfile
import jax

sys.path.insert(0, %(repo)r)
from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.utils import walls
from attacking_federate_learning_tpu.utils.profiling import xla_trace

CELLS = []
for defense in ("Krum", "TrimmedMean"):
    CELLS.append(("flat", dict(defense=defense)))
    CELLS.append(("hier", dict(defense=defense,
                               aggregation="hierarchical",
                               users_count=12, mal_prop=0.25,
                               megabatch=4)))
    CELLS.append(("async", dict(defense=defense, aggregation="async",
                                async_buffer=8, users_count=12,
                                mal_prop=0.25)))

ds = load_dataset(C.SYNTH_MNIST, seed=0, synth_train=128, synth_test=64)
for tag, overrides in CELLS:
    base = dict(dataset=C.SYNTH_MNIST, users_count=9, mal_prop=0.22,
                batch_size=16, epochs=4, test_step=4,
                synth_train=128, synth_test=64)
    base.update(overrides)
    exp = FederatedExperiment(ExperimentConfig(**base),
                              attacker=DriftAttack(1.0), dataset=ds)
    exp.run_span(0, 2)                         # warm: compile untraced
    jax.block_until_ready(exp.state.weights)
    td = tempfile.mkdtemp(prefix="wallmat_")
    with xla_trace(td):
        exp.run_span(2, 2)
        jax.block_until_ready(exp.state.weights)
    rec = walls.book_trace(td, exp._span_hlo_text(2),
                           name=exp._span_entry_name(), rounds=2)
    out = {"cell": f"{tag}/{base['defense']}",
           "entry": exp._span_entry_name()}
    if rec is None:
        out["error"] = "no trace file"
    else:
        try:
            rec.check()
        except AssertionError as e:
            out["error"] = str(e)
        out["op_events"] = rec.coverage["op_events"]
        out["stages"] = rec.stages
        out["unattributed_us"] = rec.unattributed_us
        out["exact"] = (sum(rec.stages.values()) + rec.unattributed_us
                        == rec.total_us)
    print(json.dumps(out), flush=True)
"""


def test_partition_exact_on_all_three_engines_real_traces():
    proc = subprocess.run(
        [sys.executable, "-c", _MATRIX_SCRIPT % {"repo": REPO}],
        env=_subproc_env(), capture_output=True, text=True, timeout=540,
        cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    assert len(rows) == 6, proc.stdout
    entries = {r["cell"]: r["entry"] for r in rows}
    assert entries["flat/Krum"] == "fused_span"
    assert entries["hier/Krum"] == "hier_span"
    assert entries["async/Krum"] == "async_span"
    for r in rows:
        assert "error" not in r, r
        assert r["op_events"] > 0, f"{r['cell']}: no op events booked"
        assert r["exact"], f"{r['cell']}: partition not exact"
        # The aggregation stage must carry measured time in every cell
        # (the span executed real defense work under the scope).
        assert r["stages"].get("tier1_aggregate", 0.0) > 0.0, r
        assert set(r["stages"]) <= set(STAGES), r


# ---------------------------------------------------------------------------
# e2e: --profile-every run -> v10 log -> runs walls

@pytest.fixture(scope="module")
def profiled_runs(tmp_path_factory):
    """Three journaled CLI runs in one store: two profiled (a, b) and
    one without --profile-every (for the exit-1 path)."""
    root = tmp_path_factory.mktemp("walls_e2e")
    log_dir, run_dir = str(root / "logs"), str(root / "runs")
    base = ["-s", "SYNTH_MNIST", "-n", "9", "-m", "0.22", "-c", "16",
            "-e", "5", "--synth-train", "128", "--synth-test", "64",
            "--journal", "--no-checkpoint", "--log-dir", log_dir,
            "--run-dir", run_dir]
    runs = [
        ("walls-a", ["-d", "Krum", "--profile-every", "1",
                     "--cost-report"]),
        ("walls-b", ["-d", "Median", "--profile-every", "1",
                     "--cost-report"]),
        ("walls-none", ["-d", "Krum"]),
    ]
    for run_id, extra in runs:
        proc = subprocess.run(
            [sys.executable, "-m", "attacking_federate_learning_tpu.cli",
             *base, *extra, "--run-id", run_id],
            env=_subproc_env(), capture_output=True, text=True,
            timeout=420, cwd=REPO)
        assert proc.returncode == 0, (run_id, proc.stderr[-3000:])
    return log_dir, run_dir


def _runs(run_dir, *argv):
    from attacking_federate_learning_tpu import runs_cli
    return runs_cli.main(["--run-dir", run_dir, *argv])


def test_profiled_run_log_roundtrips_at_v10(profiled_runs):
    log_dir, _ = profiled_runs
    path = os.path.join(log_dir, "walls-a.jsonl")
    events = list(iter_events(path, validate=True))
    wall = [e for e in events if e["kind"] == "wall"]
    # 'wall' arrived at v10 (KIND_MIN_VERSION); records stamp whatever
    # the current schema version is (v11+ after the traffic kind).
    assert wall and all(e["v"] == SCHEMA_VERSION >= 10 for e in wall)
    by_source = {e["source"] for e in wall}
    assert by_source == {"host", "trace"}
    for e in wall:
        if e["source"] != "trace":
            continue
        booked = sum(e["stages"].values()) + e["unattributed_us"]
        # wall_s is rounded to the microsecond, stages to 1e-3 us.
        assert booked == pytest.approx(e["wall_s"] * 1e6, abs=1.0)
        assert e["coverage"]["op_events"] > 0
        assert e["name"] == "fused_span"


def test_runs_walls_single_and_diff(profiled_runs, capsys):
    _, run_dir = profiled_runs
    assert _runs(run_dir, "walls", "walls-a") == 0
    out = capsys.readouterr().out
    assert "entry fused_span" in out
    assert "tier1_aggregate" in out
    assert "host walls:" in out
    assert _runs(run_dir, "walls", "walls-a", "walls-b") == 0
    out = capsys.readouterr().out
    assert "walls diff: walls-a vs walls-b" in out
    assert "rounds/s:" in out


def test_runs_walls_json_and_exit1(profiled_runs, capsys):
    _, run_dir = profiled_runs
    assert _runs(run_dir, "--json", "walls", "walls-a") == 0
    payload = json.loads(capsys.readouterr().out)
    entry = payload["walls-a"]["entries"]["fused_span"]
    assert entry["captures"] >= 1
    assert "vs_modeled" in entry     # the --cost-report twin joined
    assert _runs(run_dir, "walls", "walls-none") == 1
    assert "no wall events" in capsys.readouterr().out


def test_campaign_cells_carry_rounds_per_s(profiled_runs):
    """The registry whitelists the engine's always-on rounds_per_s
    summary stamp (the campaign time column's source)."""
    from attacking_federate_learning_tpu.utils.registry import RunRegistry
    _, run_dir = profiled_runs
    reg = RunRegistry(run_dir)
    reg.refresh()
    ent = reg.resolve("walls-a")
    assert isinstance(ent.get("rounds_per_s"), (int, float))
    assert ent["rounds_per_s"] > 0
