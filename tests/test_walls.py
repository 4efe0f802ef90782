"""Measured-walls observatory (ISSUE 16): utils/walls.py booking,
engine --profile-every wiring, schema-v10 wall events and the
runs-walls verb.

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
from conftest import metadata_in_cache_key

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
    # The persistent cache's key leaves op metadata out: an executable
    # cached before a scope was added would come back without it.
    env["JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"] = "1"
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
# booking primitives (synthetic structures, no capture needed)

_HLO = """\
HloModule jit_round
ENTRY main {
  %dot.4 = f32[8,8]{1,0} dot(a, b), metadata={op_name="jit(round)/deliver/tier1_aggregate/gram/dot" source_file="x"}
  %add.1 = f32[8]{0} add(c, d), metadata={op_name="jit(round)/deliver/gather/add"}
  %while.3 = (f32[8]{0}) while(g), metadata={op_name="jit(round)/while"}
  ROOT %mul.2 = f32[8]{0} multiply(e, f)
}
"""


def _ev(name, start_us, dur_us, **extra):
    return dict(name=name, start_ns=start_us * 1e3, dur_ns=dur_us * 1e3,
                **extra)


def _tpu(ops, spans=()):
    """A capture as walls.load_xplane hands it over: one TPU plane with
    its operations line, and the program's spans on the host plane."""
    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": walls.OPS_LINE, "events": list(ops)}]}]
    if spans:
        planes.append({"name": walls.HOST_PLANE, "lines": [
            {"name": "python", "events": list(spans)}]})
    return {"planes": planes}


def test_hlo_stage_map_innermost_token_rule():
    m = walls.hlo_stage_map(_HLO)
    # Innermost (LAST) stage token wins, not the outer scope; a
    # sub-stage books to the stage around it here.
    assert m["dot.4"] == "tier1_aggregate"
    assert m["add.1"] == "deliver"
    # ROOT-prefixed instruction parsed; no op_name -> unattributed.
    assert m["mul.2"] is None and m["while.3"] is None
    paths = walls.hlo_scope_paths(_HLO)
    assert paths["add.1"] == "jit(round)/deliver/gather/add"
    assert paths["mul.2"] == ""


def test_a_compiler_made_instruction_takes_its_producers_path():
    """XLA's own layout copies and reshapes carry no op_name (10.9 % of
    the CNN cell's device time on the chip): each is named by the
    nearest producer of one of its operands, and the booking says how
    much time was named that way."""
    text = """\
HloModule m
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %neg.1 = f32[8]{0} negate(p)
}
ENTRY main {
  %param.1 = f32[8]{0} parameter(0)
  %conv.1 = f32[8]{0} convolution(%param.1, %param.1), metadata={op_name="jit(f)/deliver/client_step/conv"}
  %copy.2 = f32[8]{0} copy(f32[8]{0} %conv.1)
  %bitcast.3 = f32[8]{0} bitcast(f32[8]{0} %copy.2)
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %param.1, f32[8]{0} %bitcast.3), kind=kLoop, calls=%fused_computation.1
  %copy.5 = f32[8]{0} copy(f32[8]{0} %param.1)
}
"""
    paths = walls.hlo_scope_paths(text)
    want = walls.INHERITED + "jit(f)/deliver/client_step/conv"
    assert paths["copy.2"] == paths["bitcast.3"] == paths["fusion.4"] == want
    assert paths["copy.5"] == "" and paths["param.1"] == ""
    assert walls.scope_stage(want, substages=True) == ("deliver",
                                                       "client_step")
    assert walls.hlo_stage_map(text)["copy.2"] == "deliver"
    rec = walls.book_events(_tpu([
        _ev("%conv.1 = f32[8]{0} convolution(a, b)", 0, 80.0),
        _ev("%copy.2 = f32[8]{0} copy(c)", 80, 15.0),
        _ev("%copy.5 = f32[8]{0} copy(d)", 95, 5.0)]), paths)
    assert rec.stages == {"deliver": 95.0}
    assert rec.substages == {"client_step": 95.0}
    assert rec.unattributed_us == 5.0
    assert rec.coverage["inherited_us"] == 15.0
    assert rec.coverage["unjoined_events"] == 0


@pytest.mark.parametrize("path,want", [
    ("jit(f)/while/body/deliver/gather/gather", ("deliver", "gather")),
    ("jit(f)/deliver/tier1_aggregate/gram/gram/dot",
     ("tier1_aggregate", "gram")),
    ("jit(f)/deliver/gather/apply/add", ("apply", None)),
    ("jit(f)/tier2_aggregate/gram/dot", ("tier2_aggregate", "gram")),
    ("jit(f)/select/sort", ("tier1_aggregate", "select")),
    ("jit(f)/while/body/add", (None, None)),
    # a primitive's own name is not a scope (lax.gather's is "gather")
    ("jit(f)/while/body/gather", (None, None)),
    ("jit(f)/apply", (None, None)),
    ("", (None, None)), (None, (None, None)),
])
def test_scope_stage_names_the_innermost_stage_and_substage(path, want):
    assert walls.scope_stage(path, substages=True) == want
    assert walls.scope_stage(path) == want[0]


def test_book_events_exact_partition_and_coverage():
    paths = walls.hlo_scope_paths(_HLO)
    ops = [
        _ev("%dot.4 = f32[8,8]{1,0} dot(a, b)", 0, 100.5),
        _ev("%dot.4 = f32[8,8]{1,0} dot(a, b)", 101, 0.25),  # repeats sum
        _ev("%add.1 = f32[8]{0} add(c, d)", 102, 7.0),
        _ev("%mul.2 = f32[8]{0} multiply(e, f)", 110, 3.5),  # no scope
        _ev("%fusion.9 = f32[8]{0} fusion(x)", 114, 50.0),   # not in HLO
    ]
    rec = walls.book_events(_tpu(ops), paths, name="fused_span")
    assert rec.stages == {"tier1_aggregate": 100.75, "deliver": 7.0}
    assert rec.substages == {"gram": 100.75, "gather": 7.0}
    assert rec.unattributed_us == 53.5
    # The partition identity: same floats, not a tolerance.
    assert sum(rec.stages.values()) + rec.unattributed_us == rec.total_us
    rec.check()
    cov = rec.coverage
    assert cov["op_events"] == 5
    assert cov["booked_us"] == cov["busy_us"] == 161.25
    assert cov["unjoined_us"] == 50.0 and cov["unjoined_events"] == 1
    assert cov["op_time_fraction"] == pytest.approx(111.25 / 161.25,
                                                    abs=1e-4)
    assert cov["named_fraction"] == pytest.approx(107.75 / 161.25,
                                                  abs=1e-4)
    assert cov["idle_us"] == pytest.approx(164.0 - 161.25)


def test_self_time_under_a_while_books_the_body_once():
    """A scanned span is one ``while`` around its body: the old booking
    summed both (27.8 ms booked against a 14.7 ms round on the chip)."""
    paths = walls.hlo_scope_paths(_HLO)
    ops = [
        _ev("%while.3 = (f32[8]{0}) while(g)", 0, 100.0),
        _ev("%add.1 = f32[8]{0} add(c, d)", 1, 30.0),
        _ev("%dot.4 = f32[8,8]{1,0} dot(a, b)", 31, 60.0),
        _ev("%add.1 = f32[8]{0} add(c, d)", 200, 5.0),    # after the loop
    ]
    rec = walls.book_events(_tpu(ops), paths)
    assert rec.stages == {"deliver": 35.0, "tier1_aggregate": 60.0}
    assert rec.unattributed_us == 10.0      # the while's own 10 us
    assert rec.total_us == rec.coverage["busy_us"] == 105.0
    # a double count cannot pass the record's own check
    rec.stages["deliver"] += 100.0
    with pytest.raises(AssertionError, match="double-counts"):
        rec.check()


def test_scope_from_the_event_wins_and_the_join_is_the_fallback():
    paths = walls.hlo_scope_paths(_HLO)
    ops = [
        # its own scope path: booked by it, whatever the HLO text says
        _ev("%add.1 = f32[8]{0} add(c, d)", 0, 4.0,
            scope="jit(round)/apply/add"),
        # an hlo_op stat names the instruction (CPU thunks)
        _ev("anything", 4, 6.0, hlo_op="add.1"),
        # neither: the instruction name is the head of the event name
        _ev("%dot.4 = f32[8,8]{1,0} dot(a, b)", 10, 8.0),
        _ev("dot.4", 18, 2.0),
    ]
    rec = walls.book_events(_tpu(ops), paths)
    assert rec.stages == {"apply": 4.0, "deliver": 6.0,
                          "tier1_aggregate": 10.0}
    assert rec.coverage["unjoined_events"] == 0
    assert rec.coverage["op_time_fraction"] == 1.0


def test_idle_gap_is_split_over_the_host_spans_that_overlap_it():
    ops = [_ev("%add.1 = f32[8]{0} add(c, d)", 0, 100.0),
           _ev("%add.1 = f32[8]{0} add(c, d)", 1100, 100.0)]
    spans = [
        _ev("interval.wait_device", 50, 100.0),         # 50 us of gap
        _ev("interval.log", 150, 600.0),                # 600 us
        _ev("interval.poll", 800, 250.0),               # 250 us
        _ev("interval.dispatch_span", 1060, 30.0),      # 30 us
        _ev("not.one.of.ours", 750, 50.0),
    ]
    rec = walls.book_events(_tpu(ops, spans),
                            walls.hlo_scope_paths(_HLO))
    assert rec.host_gaps == pytest.approx({
        "interval.wait_device": 50.0, "interval.log": 600.0,
        "interval.poll": 250.0, "interval.dispatch_span": 30.0,
        "unannotated": 70.0})
    assert sum(rec.host_gaps.values()) == pytest.approx(
        rec.coverage["idle_us"]) == pytest.approx(1000.0)
    assert rec.coverage["long_gaps"] == 1
    assert rec.coverage["long_gaps_unannotated"] == 0
    # nested spans: the innermost names the time
    nested = [_ev("setup.experiment", 100, 1000.0),
              _ev("setup.place_data", 300, 200.0)]
    rec = walls.book_events(_tpu(ops, nested),
                            walls.hlo_scope_paths(_HLO))
    assert rec.host_gaps == pytest.approx({"setup.experiment": 800.0,
                                           "setup.place_data": 200.0})
    # no span at all: the gap is reported, unnamed
    rec = walls.book_events(_tpu(ops), {})
    assert rec.host_gaps == {"unannotated": 1000.0}
    assert rec.coverage["long_gaps_unannotated"] == 1


def test_cpu_thunk_lines_are_self_timed_one_thread_at_a_time():
    """On XLA:CPU the thunks run on several threads of the host plane;
    only events with an ``hlo_op`` stat are operations."""
    host = {"name": walls.HOST_PLANE, "lines": [
        {"name": "tf_XLAEigen/1", "events": [
            _ev("while.3", 0, 50.0, hlo_op="while.3"),
            _ev("add.1", 10, 20.0, hlo_op="add.1"),
            _ev("ThunkExecutor::Execute", 0, 60.0)]},
        {"name": "tf_XLAEigen/2", "events": [
            _ev("dot.4", 5, 40.0, hlo_op="dot.4")]},
        {"name": "python", "events": [
            _ev("interval.dispatch_span", 0, 5.0)]},
    ]}
    rec = walls.book_events({"planes": [host]},
                            walls.hlo_scope_paths(_HLO), platform="cpu")
    assert rec.stages == {"deliver": 20.0, "tier1_aggregate": 40.0}
    assert rec.unattributed_us == 30.0
    assert rec.coverage["op_events"] == 3
    assert rec.coverage["busy_us"] == 90.0      # 50 + 40, per thread


def test_recorded_tpu_capture_books_without_a_double_count():
    """A capture recorded on the chip (tests/data/walls_tpu_span.json:
    the first profiled span of the CLI at n = 10,240, Krum vs ALIE,
    event names cut to their heads, each event's scope path from the
    capture's own event metadata): the booking by the events' own scope paths and the
    booking by the join on a text of the same paths agree, the scanned
    span's ``while`` is not counted on top of its body, and the idle
    lead-in and tail are named by the host's spans."""
    with open(os.path.join(REPO, "tests", "data",
                           "walls_tpu_span.json")) as f:
        recorded = json.load(f)
    trace = recorded["trace"]
    own = walls.book_events(trace, {}, name="fused_span", platform="tpu")
    ops = trace["planes"][0]["lines"][0]["events"]
    outer = max(ops, key=lambda e: e["dur_ns"])
    assert outer["name"].startswith("%while")
    assert own.total_us == pytest.approx(outer["dur_ns"] / 1e3, rel=1e-6)
    assert own.total_us <= own.coverage["busy_us"] * (1 + 1e-9)
    assert sum(e["dur_ns"] for e in ops) / 1e3 > 1.9 * own.total_us
    assert own.coverage["named_fraction"] >= 0.9
    assert set(own.substages) >= {"gather", "client_step", "gram",
                                  "select"}
    assert set(own.host_gaps) >= {"interval.dispatch_span",
                                  "interval.wait_span"}
    assert own.coverage["long_gaps_unannotated"] == 0
    # the join: strip the events' own paths, book against the HLO text
    stripped = json.loads(json.dumps(trace))
    for ev in stripped["planes"][0]["lines"][0]["events"]:
        ev.pop("scope", None)
    joined = walls.book_events(stripped,
                               walls.hlo_scope_paths(recorded["hlo"]),
                               name="fused_span", platform="tpu")
    assert joined.stages == own.stages
    assert joined.substages == own.substages
    # (operations the compiler made carry no path in the capture, so
    # none in the text made of it: 2.4 % of the time, reported)
    assert (joined.coverage["unjoined_events"]
            == own.coverage["unjoined_events"]
            == sum("scope" not in ev for ev in ops))
    assert walls.book_events(stripped, {}).stages == {}


def test_wall_event_validates_at_v10():
    rec = walls.book_events(
        _tpu([_ev("%dot.4 = f32[8,8] dot(a, b)", 0, 10.0)],
             [_ev("interval.dispatch_span", -5, 5.0)]),
        walls.hlo_scope_paths(_HLO), name="fused_span",
        platform="cpu", rounds=3)
    ev = rec.wall_event()
    assert ev["stages"] == {"tier1_aggregate": 10.0}
    assert ev["substages"] == {"gram": 10.0}
    assert ev["host_gaps"] == {"interval.dispatch_span": 5.0}
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
        with metadata_in_cache_key():
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
        _tpu([_ev(f"%{n} = f32[] op()", i, 1.0)
              for i, n in enumerate(names)]),
        walls.hlo_scope_paths(text))
    assert rec.stages == {} and rec.substages == {}
    assert rec.unattributed_us == float(len(names))
    assert rec.coverage["unjoined_events"] == 0
    rec.check()


def test_profile_every_leaves_hlo_fingerprint_identical():
    off = _exp(defense="Krum", profile_every=0)
    on = _exp(defense="Krum", profile_every=2)
    with metadata_in_cache_key():
        f_off = hlo_fingerprint(off._span_hlo_text(3))
        f_on = hlo_fingerprint(on._span_hlo_text(3))
    assert f_off == f_on
    t0 = jnp.asarray(0, jnp.int32)
    r_off = off._fused_round.lower(off.data, off.state, t0).as_text()
    r_on = on._fused_round.lower(on.data, on.state, t0).as_text()
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
        out["substages"] = rec.substages
        out["coverage"] = rec.coverage
        out["host_gaps"] = rec.host_gaps
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
        # self time: nothing is counted twice, and the scopes name it
        cov = r["coverage"]
        assert cov["booked_us"] <= cov["busy_us"] * (1 + 1e-9), r
        assert cov["op_time_fraction"] >= 0.99, r
        # (how much: a toy span on XLA:CPU is mostly the loop's own
        # overhead and the share moves with the box's load; the 90 % bar
        # is the chip's, on the recorded capture above and in PERF.md
        # section 5)
        assert cov["named_fraction"] > 0.0, r
        assert {"gather", "client_step", "craft"} <= set(r["substages"]), r
        if r["cell"].endswith("Krum"):
            assert {"gram", "select"} <= set(r["substages"]), r
        # the capture's idle time is named by the host's spans
        assert "interval.dispatch_span" in r["host_gaps"], r


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
    assert "sub-stages: " in out and "gram " in out
    assert "device idle, by host span: " in out
    assert "interval.dispatch_span " in out
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
