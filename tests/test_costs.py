"""Compile-and-cost observatory: static HLO accounting (utils/costs.py),
the engine's cost_report, schema-v2 events (compile/cost/heartbeat),
the RunLogger heartbeat thread, and the deterministic perf gate
(tools/perf_gate.py).

Acceptance contract (ISSUE 3): the gate passes against a freshly
generated baseline on CPU, fails loudly (nonzero exit, named metric)
when a defense kernel's FLOPs are inflated, cost/compile/heartbeat
events round-trip through check_events, and running the cost report
leaves the round program's HLO byte-identical.
"""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import metadata_in_cache_key

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu import report
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import (
    ExperimentConfig, FaultConfig
)
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.utils import costs
from attacking_federate_learning_tpu.utils.metrics import (
    RunLogger, SCHEMA_VERSION, validate_event
)


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 9)
    kw.setdefault("mal_prop", 0.22)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 4)
    kw.setdefault("test_step", 4)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("log_dir", str(tmp_path))
    return ExperimentConfig(**kw)


def _exp(cfg, **kw):
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    kw.setdefault("attacker", DriftAttack(1.0))
    return FederatedExperiment(cfg, dataset=ds, **kw)


# ---------------------------------------------------------------------------
# utils/costs.py primitives

def test_analyze_lowered_facts_present_and_deterministic():
    """cost_analysis/memory_analysis land in the record, and two
    analyses of the same program agree exactly (the determinism the
    perf gate stands on)."""
    fn = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((32, 64), jnp.float32)
    a = costs.analyze_lowered("gram", fn.lower(x))
    b = costs.analyze_lowered("gram", fn.lower(x))
    assert a.flops > 0 and a.bytes_accessed > 0
    assert a.argument_bytes == 32 * 64 * 4
    assert a.peak_bytes >= a.argument_bytes
    assert a.gate_facts() == b.gate_facts()
    # Event payloads validate against schema v2.
    validate_event({**a.cost_event(), "v": SCHEMA_VERSION})
    validate_event({**a.compile_event(), "v": SCHEMA_VERSION})


def test_cost_scales_with_problem_size():
    """More clients -> more distance FLOPs: the facts are real numbers,
    not placeholders (the O(n^2 d) Krum story becomes measurable)."""
    from attacking_federate_learning_tpu.defenses.kernels import krum

    d = 512
    recs = {}
    for n in (8, 16):
        G = jnp.zeros((n, d), jnp.float32)
        fn = jax.jit(krum, static_argnums=(1, 2))
        recs[n] = costs.analyze_lowered(f"krum{n}", fn.lower(G, n, 2))
    assert recs[16].flops > 2.5 * recs[8].flops


def test_cache_counters_install_idempotent():
    costs.install_cache_counters()
    costs.install_cache_counters()
    counts = costs.cache_counts()
    assert set(counts) == {"hits", "misses"}
    assert counts["hits"] >= 0 and counts["misses"] >= 0


# ---------------------------------------------------------------------------
# engine.cost_report

def test_cost_report_fused_entries_and_events(tmp_path):
    cfg = _cfg(tmp_path, defense="Krum")
    exp = _exp(cfg)
    with RunLogger(cfg, None, str(tmp_path), jsonl_name="cr") as logger:
        ledger = exp.cost_report(logger)
    assert not ledger.errors
    names = [r.name for r in ledger.records]
    assert names == ["fused_round", "fused_span", "defense_Krum", "eval"]
    for rec in ledger.records:
        assert rec.flops > 0, rec.name
        assert rec.peak_bytes > 0, rec.name
        assert rec.cache in ("hit", "miss", "uncached")
    # The defense kernel is strictly cheaper than the round containing it.
    by = {r.name: r for r in ledger.records}
    assert by["defense_Krum"].flops < by["fused_round"].flops
    with open(logger.jsonl_path) as f:
        evs = [json.loads(line) for line in f]
    assert sum(e["kind"] == "compile" for e in evs) == 4
    assert sum(e["kind"] == "cost" for e in evs) == 4
    # ISSUE 15: every analyzed entry carries its stage attribution, and
    # the run carries exactly one per-seam wire ledger.
    assert sum(e["kind"] == "stage_cost" for e in evs) == 4
    assert sum(e["kind"] == "wire_bytes" for e in evs) == 1
    for e in evs:
        validate_event(e)


def test_cost_report_mode_specific_entries(tmp_path):
    # Telemetry adds the tele_span program.
    exp = _exp(_cfg(tmp_path, defense="Krum", telemetry=True))
    names = [r.name for r in exp.cost_report().records]
    assert "tele_span" in names
    # Faults swap the span for the fault span.
    exp = _exp(_cfg(tmp_path, defense="Median",
                    faults=FaultConfig(dropout=0.2)))
    names = [r.name for r in exp.cost_report().records]
    assert "fault_span" in names and "fused_span" not in names
    # The staged path (backdoor_fused=False) analyzes its stages; on the
    # CPU backend a Krum/Bulyan aggregate runs eagerly (host BLAS), so
    # only compute_grads has a compiled program — use TrimmedMean, whose
    # aggregate stays jitted.
    cfg = _cfg(tmp_path, users_count=8, mal_prop=0.25, defense="TrimmedMean",
               backdoor="pattern", backdoor_fused=False, synth_train=512)
    from attacking_federate_learning_tpu.attacks import make_attacker

    ds = load_dataset(cfg.dataset, seed=0, synth_train=512, synth_test=64)
    exp = FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                              dataset=ds)
    ledger = exp.cost_report()
    names = [r.name for r in ledger.records]
    assert "compute_grads" in names and "aggregate" in names
    assert not ledger.errors


def test_cost_report_leaves_round_hlo_byte_identical(tmp_path):
    """Acceptance: the observatory is an observer — running it must not
    change the compiled round program (same pin methodology as the
    telemetry/fault bit-identity tests)."""
    ds = load_dataset(C.SYNTH_MNIST, seed=0, synth_train=256, synth_test=64)

    def lowered_text(run_report):
        cfg = _cfg(tmp_path, defense="Krum")
        exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=ds)
        if run_report:
            exp.cost_report()
        return exp._fused_round.lower(
            exp.data, exp.state, jnp.asarray(0, jnp.int32)).as_text()

    assert lowered_text(False) == lowered_text(True)


# ---------------------------------------------------------------------------
# heartbeat

def test_heartbeat_thread_emits_and_stops(tmp_path):
    cfg = _cfg(tmp_path)
    with RunLogger(cfg, None, str(tmp_path), jsonl_name="hb",
                   heartbeat_every=0.05) as logger:
        logger.record(kind="round", round=0)
        time.sleep(0.18)
        logger.record(kind="round", round=3)
        time.sleep(0.12)
        path = logger.jsonl_path
    # Thread stopped: no writes after close.
    time.sleep(0.15)
    with open(path) as f:
        evs = [json.loads(line) for line in f]
    beats = [e for e in evs if e["kind"] == "heartbeat"]
    assert len(beats) >= 3
    for e in beats:
        validate_event(e)
        assert e["rss_mb"] > 0 and e["last_event_age_s"] >= 0
    # Round progress rides along once seen; the EMA appears after two
    # distinct rounds.
    assert beats[-1]["round"] == 3
    assert any("rounds_per_s" in e for e in beats)
    # The age tracks REAL events only — a beat never resets the clock:
    # ages grow monotonically between the two round events.
    stall = [e["last_event_age_s"] for e in beats if e["t"] < 0.18]
    assert stall == sorted(stall)
    with pytest.raises(ValueError, match="finish"):
        logger.record(kind="round", round=4)


def test_heartbeat_off_by_default(tmp_path):
    cfg = _cfg(tmp_path)
    with RunLogger(cfg, None, str(tmp_path), jsonl_name="nohb") as logger:
        assert logger._hb_thread is None
        logger.record(kind="round", round=0)
        path = logger.jsonl_path
    with open(path) as f:
        assert all(json.loads(line)["kind"] != "heartbeat" for line in f)


# ---------------------------------------------------------------------------
# schema v2 / check_events

def _load_tool(name):
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_v2_kinds_and_version_rules():
    validate_event({"kind": "compile", "name": "x", "compile_s": 0.1,
                    "cache": "hit", "v": 2})
    validate_event({"kind": "cost", "name": "x", "flops": 1.0,
                    "bytes_accessed": 2.0, "peak_bytes": 3, "v": 2})
    validate_event({"kind": "heartbeat", "rss_mb": 1.0,
                    "last_event_age_s": 0.0, "v": 2})
    # v1 events stay valid (old logs readable by the new reader).
    validate_event({"kind": "round", "round": 1, "v": 1})
    # A v2-only kind stamped v1 is an emitter bug.
    with pytest.raises(ValueError, match="need schema v2"):
        validate_event({"kind": "heartbeat", "rss_mb": 1.0,
                        "last_event_age_s": 0.0, "v": 1})
    # Unknown versions name the version, not the kind (a newer writer's
    # kinds are unknowable here).
    with pytest.raises(ValueError, match="newer writer"):
        validate_event({"kind": "from_the_future", "v": 99})


def test_check_events_handles_v2_and_unknown_versions(tmp_path):
    ce = _load_tool("check_events")
    path = os.path.join(str(tmp_path), "v2.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "compile", "name": "a",
                            "compile_s": 0.5, "cache": "miss",
                            "v": 2}) + "\n")
        f.write(json.dumps({"kind": "cost", "name": "a", "flops": 1.0,
                            "bytes_accessed": 1.0, "peak_bytes": 1,
                            "v": 2}) + "\n")
        f.write(json.dumps({"kind": "heartbeat", "rss_mb": 5.0,
                            "last_event_age_s": 0.1, "v": 2}) + "\n")
    counts, legacy, errors = ce.check_file(path)
    assert not errors
    assert counts == {"compile": 1, "cost": 1, "heartbeat": 1}
    assert ce.main([path]) == 0
    from attacking_federate_learning_tpu.utils.metrics import (
        SUPPORTED_VERSIONS
    )

    bad = os.path.join(str(tmp_path), "future.jsonl")
    with open(bad, "w") as f:
        # One past the newest supported version — stays "the future"
        # across schema bumps instead of hard-coding a constant.
        f.write(json.dumps({"kind": "quantum_trace",
                            "v": max(SUPPORTED_VERSIONS) + 1}) + "\n")
    counts, legacy, errors = ce.check_file(bad)
    assert len(errors) == 1 and "newer writer" in errors[0][1]
    assert ce.main([bad]) == 1


# ---------------------------------------------------------------------------
# report: compile & cost table

def test_report_compile_cost_table(tmp_path, capsys):
    from attacking_federate_learning_tpu import cli

    cfg = _cfg(tmp_path, defense="Krum")
    exp = _exp(cfg)
    with RunLogger(cfg, None, str(tmp_path), jsonl_name="cctab") as logger:
        exp.cost_report(logger)
        logger.record(**logger.heartbeat_fields())
        path = logger.jsonl_path
    capsys.readouterr()
    assert cli.main(["report", "--json", path]) == 0
    out = json.loads(capsys.readouterr().out)[path]
    cc = out["compile_cost"]
    assert {r["name"] for r in cc["entries"]} == {
        "fused_round", "fused_span", "defense_Krum", "eval"}
    for r in cc["entries"]:
        assert r["flops"] > 0 and r["peak_bytes"] > 0
    assert out["heartbeat"]["beats"] == 1
    assert cli.main(["report", path]) == 0
    text = capsys.readouterr().out
    assert "compile & cost" in text and "defense_Krum" in text


# ---------------------------------------------------------------------------
# tools/perf_gate.py (satellite: CI smoke next to fault_matrix)

def test_perf_gate_roundtrip_and_inflation_detection(tmp_path, capsys):
    """Acceptance: the gate passes against a freshly generated baseline,
    and an artificially inflated defense-kernel FLOP count fails with a
    nonzero exit naming the metric."""
    pg = _load_tool("perf_gate")
    baseline = os.path.join(str(tmp_path), "base.json")
    # One distance cell keeps the test inside CI budget (the compiles
    # are persistent-cache-warmed after the first run).
    argv = ["--baseline", baseline, "--cells", "krum"]
    assert pg.main(argv + ["--update"]) == 0
    assert pg.main(argv) == 0
    capsys.readouterr()

    with open(baseline) as f:
        doc = json.load(f)
    doc["cells"]["krum"]["defense_Krum"]["flops"] *= 2
    with open(baseline, "w") as f:
        json.dump(doc, f)
    assert pg.main(argv) == 1
    out = capsys.readouterr().out
    assert "krum.defense_Krum.flops" in out


def test_perf_gate_env_mismatch_skips_unless_strict(tmp_path, capsys):
    pg = _load_tool("perf_gate")
    baseline = os.path.join(str(tmp_path), "base.json")
    argv = ["--baseline", baseline, "--cells", "nodefense"]
    assert pg.main(argv + ["--update"]) == 0
    with open(baseline) as f:
        doc = json.load(f)
    doc["env"]["jax"] = "9.9.9"
    with open(baseline, "w") as f:
        json.dump(doc, f)
    capsys.readouterr()
    assert pg.main(argv) == 0
    assert "SKIP" in capsys.readouterr().out
    assert pg.main(argv + ["--strict-env"]) == 1


def test_perf_gate_missing_baseline_is_exit_2(tmp_path):
    pg = _load_tool("perf_gate")
    assert pg.main(["--baseline",
                    os.path.join(str(tmp_path), "nope.json")]) == 2


def test_checked_in_baseline_matches_this_environment():
    """The repo's PERF_BASELINE.json was generated on this box; the
    gate must treat it as comparable (env match) — otherwise every CI
    run silently skips and the gate is dead weight."""
    pg = _load_tool("perf_gate")
    if not os.path.exists(pg.BASELINE):
        pytest.skip("no checked-in baseline")
    with open(pg.BASELINE) as f:
        doc = json.load(f)
    assert doc["env"] == pg.environment()
    # And the cheapest cell actually gates clean against it.
    assert pg.main(["--cells", "nodefense"]) == 0


# ---------------------------------------------------------------------------
# stage & wire ledger (ISSUE 15)

def _round_compiled(exp):
    """Lower + compile the engine's round entry (the program
    --stageproof gates; signature varies by topology)."""
    t0 = jnp.asarray(0, jnp.int32)
    if exp._async is not None:
        return exp._fused_round.lower(
            exp.data, exp.state, t0, exp._async_state, None).compile()
    if exp.faults is not None:
        return exp._fused_round.lower(
            exp.data, exp.state, t0, exp._fault_state, None).compile()
    return exp._fused_round.lower(exp.data, exp.state, t0).compile()


# Topology overrides per defense family.  Bulyan's 4f+3 validity bound
# needs wider cohorts: n=11/f=2 flat (the perf-gate pinned base), the
# gate's hier_bulyan shape for two-tier (megabatch >= 4*f1+3), and a
# full-cohort buffer under async (k=11 >= 4f+3).
_TOPO = {
    "flat": dict(),
    "hierarchical": dict(aggregation="hierarchical", users_count=12,
                         mal_prop=0.25, megabatch=4),
    "async": dict(aggregation="async", async_buffer=8),
}
_TOPO_BULYAN = {
    "flat": dict(users_count=11, mal_prop=0.2),
    "hierarchical": dict(aggregation="hierarchical", users_count=24,
                         mal_prop=0.125, megabatch=8,
                         tier2_defense="TrimmedMean"),
    "async": dict(aggregation="async", users_count=11, mal_prop=0.2,
                  async_buffer=11),
}


@pytest.mark.parametrize("topology", ["flat", "hierarchical", "async"])
@pytest.mark.parametrize("defense",
                         ["Krum", "TrimmedMean", "Bulyan", "Median"])
def test_stage_attribution_partitions_round(tmp_path, defense, topology):
    """Acceptance (ISSUE 15): on every tier-1 defense x topology the
    stage partition sums to XLA's own whole-program totals exactly,
    coverage clears the --stageproof bar, and the stages that must be
    populated are (tier2_aggregate appears on the two-tier topology
    and ONLY there)."""
    import math

    over = (_TOPO_BULYAN if defense == "Bulyan" else _TOPO)[topology]
    exp = _exp(_cfg(tmp_path, defense=defense, **over))
    compiled = _round_compiled(exp)
    facts = costs.compiled_cost_facts(compiled)
    att = costs.stage_attribution(compiled.as_text(), facts)
    for metric in ("flops", "bytes_accessed", "temp_bytes"):
        parts = [att["stages"][s][metric] for s in costs.STAGES]
        parts.append(att["unattributed"][metric])
        assert math.isclose(math.fsum(parts), facts[metric],
                            rel_tol=1e-9, abs_tol=1e-6), metric
    assert att["coverage"]["flops"] >= 0.95
    assert att["stages"]["deliver"]["flops"] > 0
    assert att["stages"]["tier1_aggregate"]["flops"] > 0
    assert att["stages"]["apply"]["flops"] > 0
    if topology == "hierarchical":
        assert att["stages"]["tier2_aggregate"]["flops"] > 0
    else:
        assert att["stages"]["tier2_aggregate"]["flops"] == 0


def test_stage_scopes_are_metadata_only(tmp_path):
    """Scopes off must leave the compiled program identical up to
    metadata: the canonicalized fingerprint matches, while the
    annotated text itself differs (the scopes ARE there) -- the stages
    and the sub-stages under ``deliver`` and ``tier1_aggregate``."""
    ds = load_dataset(C.SYNTH_MNIST, seed=0, synth_train=256,
                      synth_test=64)

    def compiled_text(on):
        prev = costs.set_stage_scopes(on)
        try:
            cfg = _cfg(tmp_path, defense="Krum")
            exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                      dataset=ds)
            return _round_compiled(exp).as_text()
        finally:
            costs.set_stage_scopes(prev)

    with metadata_in_cache_key():
        on, off = compiled_text(True), compiled_text(False)
    assert costs.hlo_fingerprint(on) == costs.hlo_fingerprint(off)
    assert costs.canonical_hlo(on) == costs.canonical_hlo(off)
    # every round has these; "attention", "experts", "latent" and
    # "shared_experts" only a sequence model's (tests/test_sequence_model.py
    # and tests/test_mla_model.py hold them to the same proof)
    scopes = costs.STAGES[:1] + costs.STAGES[3:4] + tuple(
        s for s in costs.SUBSTAGES if s not in (
            "attention", "experts", "latent", "shared_experts"))
    assert set(scopes) >= {"deliver", "tier1_aggregate", "gather",
                           "client_step", "craft", "gram", "select"}
    for token in scopes:
        assert f"/{token}/" in on, token
        assert f"/{token}/" not in off, token
    assert "StackFrames" in on and "StackFrames" not in (
        costs.canonical_hlo(on))


def test_wire_ledger_seam_math():
    """Pure seam pricing: every seam the topology crosses, nothing it
    doesn't, totals additive, and the hierarchical seam is the PR 12
    S*d*4 collective identity."""
    flat = costs.wire_ledger(cohort=16, dim=100)
    assert set(flat["seams"]) == {"broadcast", "client_update"}
    assert flat["seams"]["broadcast"]["bytes"] == 16 * 100 * 4
    assert flat["total_bytes"] == 2 * 16 * 100 * 4

    hier = costs.wire_ledger(cohort=64, dim=79510,
                             topology="hierarchical", num_shards=8,
                             megabatch=8, spmd_parts=4)
    assert hier["seams"]["tier1_to_tier2"]["bytes"] == 8 * 79510 * 4
    assert hier["seams"]["tier1_to_tier2"]["collective"] is True

    sa = costs.wire_ledger(cohort=12, dim=100, secagg="vanilla",
                           dropped=2)
    assert sa["seams"]["secagg_mask_exchange"]["bytes"] == 66 * 32
    assert sa["seams"]["secagg_recovery"]["bytes"] == 2 * 11 * 32
    gw = costs.wire_ledger(cohort=12, dim=100, secagg="groupwise",
                           topology="hierarchical", num_shards=3,
                           megabatch=4)
    assert gw["seams"]["secagg_mask_exchange"]["bytes"] == 3 * 6 * 32

    asy = costs.wire_ledger(cohort=12, dim=100, topology="async",
                            async_buffer=8)
    assert asy["seams"]["async_delivery"]["bytes"] == 8 * 100 * 4
    for led in (flat, hier, sa, gw, asy):
        assert led["total_bytes"] == sum(
            s["bytes"] for s in led["seams"].values())


def test_engine_wire_ledger_matches_topology(tmp_path):
    """FederatedExperiment.wire_ledger() fills the seam parameters from
    the live engine: hierarchical carries the S*d*4 seam sized by ITS
    placement."""
    exp = _exp(_cfg(tmp_path, defense="Krum", aggregation="hierarchical",
                    users_count=12, mal_prop=0.25, megabatch=4))
    led = exp.wire_ledger()
    S = exp._placement.num_shards
    assert led["seams"]["tier1_to_tier2"]["bytes"] == S * exp.flat.dim * 4
    assert led["seams"]["broadcast"]["bytes"] == exp.m * exp.flat.dim * 4


def test_v9_kinds_and_version_rules():
    validate_event({"kind": "stage_cost", "name": "fused_round",
                    "stages": {"deliver": {"flops": 1.0}},
                    "unattributed": {"flops": 0.0},
                    "coverage": {"flops": 0.99}, "v": 9})
    validate_event({"kind": "wire_bytes", "topology": "flat",
                    "seams": {"broadcast": {"bytes": 4}},
                    "total_bytes": 4, "v": 9})
    # A v9-only kind stamped v8 is an emitter bug.
    with pytest.raises(ValueError, match="need schema v9"):
        validate_event({"kind": "wire_bytes", "topology": "flat",
                        "seams": {}, "total_bytes": 0, "v": 8})


def test_no_reporting_means_no_ledger_events(tmp_path):
    """The telemetry-off invariant: without --cost-report nothing emits
    stage_cost/wire_bytes (cost_report without a logger writes no file;
    a plain logged run carries neither kind)."""
    cfg = _cfg(tmp_path, defense="Krum")
    exp = _exp(cfg)
    ledger = exp.cost_report()         # no logger: analysis only
    assert ledger.wire is not None     # the facts exist...
    with RunLogger(cfg, None, str(tmp_path), jsonl_name="plain") as lg:
        lg.record(kind="round", round=0)
        path = lg.jsonl_path
    with open(path) as f:              # ...but never reached the log
        kinds = {json.loads(line)["kind"] for line in f}
    assert "stage_cost" not in kinds and "wire_bytes" not in kinds


# ---------------------------------------------------------------------------
# runs attribution (registry verb over the banked v9 events)

@pytest.fixture(scope="module")
def attr_store(tmp_path_factory):
    from attacking_federate_learning_tpu import cli

    tmp = tmp_path_factory.mktemp("attr")
    base = ["-s", "SYNTH_MNIST", "-e", "4", "-c", "16", "-n", "9",
            "-m", "0.22", "--synth-train", "256", "--synth-test", "64",
            "--log-dir", str(tmp / "logs"), "--run-dir", str(tmp / "runs"),
            "--journal", "--no-checkpoint"]
    cli.main(base + ["-d", "Krum", "--cost-report", "--run-id", "attrA"])
    cli.main(base + ["-d", "TrimmedMean", "--cost-report",
                     "--run-id", "attrB"])
    cli.main(base + ["-d", "Krum", "--run-id", "plain"])
    return tmp


def _runs(store, *verb):
    from attacking_federate_learning_tpu import cli

    return cli.main(["runs", "--run-dir", str(store / "runs"),
                     "--bench", "", "--progress", ""] + list(verb))


def test_runs_attribution_single_and_diff(attr_store, capsys):
    assert _runs(attr_store, "attribution", "attrA") == 0
    out = capsys.readouterr().out
    assert "tier1_aggregate" in out and "broadcast" in out
    assert "coverage" in out
    assert _runs(attr_store, "attribution", "attrA", "attrB") == 0
    out = capsys.readouterr().out
    assert "attrA" in out and "attrB" in out
    assert "tier1_aggregate" in out


def test_runs_attribution_json(attr_store, capsys):
    assert _runs(attr_store, "--json", "attribution", "attrA") == 0
    out = capsys.readouterr().out
    # The registry refresh banner precedes the payload; parse from the
    # first JSON line.
    doc = json.loads(out[out.index("{"):])
    att = doc["attrA"]
    assert "fused_round" in att["stages"]
    assert att["wire"]["total_bytes"] > 0


def test_runs_attribution_without_events_exits_1(attr_store, capsys):
    assert _runs(attr_store, "attribution", "plain") == 1
    assert "--cost-report" in capsys.readouterr().out


def test_cost_report_run_log_validates(attr_store):
    """The --cost-report run's private log round-trips check_events
    (v9 kinds included), and the plain run carries neither kind."""
    ce = _load_tool("check_events")
    counts, _, errors = ce.check_file(
        str(attr_store / "logs" / "attrA.jsonl"))
    assert not errors
    assert counts["stage_cost"] >= 4 and counts["wire_bytes"] == 1
    counts, _, errors = ce.check_file(
        str(attr_store / "logs" / "plain.jsonl"))
    assert not errors
    assert "stage_cost" not in counts and "wire_bytes" not in counts


# ---------------------------------------------------------------------------
# bench embedding (the RESULT fields, not a full bench run)

def test_bench_result_embeds_env_and_cache(tmp_path):
    """bench.py's emitted JSON carries env attribution and cache counts
    (satellite).  Emulated: emit_result_json on a seeded RESULT — a
    full bench run is minutes, the contract is the field set."""
    import bench

    bench.RESULT.clear()
    try:
        bench.RESULT.update(metric="x", value=1.0, env={"jax": "0.0"})
        import contextlib
        import io
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            bench.emit_result_json()
        rec = json.loads(buf.getvalue())
        assert rec["env"] == {"jax": "0.0"}
        assert set(rec["compile_cache"]) == {"hits", "misses"}
    finally:
        bench.RESULT.clear()


def test_a_kernels_body_is_compared_without_its_source_locations():
    """A Pallas kernel's serialized body carries the file and line of every
    operation in it: the canonical text hashes it printed without them, so
    the same kernel traced from another line or checkout compares equal; a
    body that is not MLIR is left as it is."""
    import base64

    def text(module):
        body = base64.b64encode(module.encode()).decode()
        return ('%c = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target='
                '"tpu_custom_call", backend_config={"custom_call_config":'
                '{"body":"' + body + '"}}')

    here = 'module {\n} loc("/a/flash_attention.py":10:4)'
    there = 'module {\n} loc("/b/flash_attention.py":99:4)'
    assert costs.canonical_hlo(text(here)) == costs.canonical_hlo(text(there))
    assert "flash_attention" not in costs.canonical_hlo(text(here))
    assert costs.canonical_hlo(text("not mlir")) == costs.canonical_hlo(
        text("not mlir")) != costs.canonical_hlo(text("not mlir either"))
