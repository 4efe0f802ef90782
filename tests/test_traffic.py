"""Population & traffic engine (ISSUE 17).

Acceptance contract: the legacy ``--participation`` draw routes through
core/population.py bit-compatibly; the traffic schedule is a pure
function of (TrafficConfig, seed, round) — deterministic across process
restarts, replayable on host, resume-exact; the registry never
materializes a population-sized tensor (structural O(1) pin + no dim-P
shape in the lowered span HLO); a forced validity-bound violation
completes through the declared degradation ladder with every decision
emitted as a v11 'traffic' event that diffs clean against
``replay_traffic``; and a SIGTERM-preempted traffic run resumes
bit-for-bit.
"""

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import (
    ExperimentConfig, TrafficConfig
)
from attacking_federate_learning_tpu.core import population as P
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.utils.checkpoint import Checkpointer
from attacking_federate_learning_tpu.utils.metrics import RunLogger


def _tcfg(**kw):
    kw.setdefault("population", 256)
    return TrafficConfig(**kw)


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 12)
    kw.setdefault("mal_prop", 0.2)
    kw.setdefault("batch_size", 16)
    kw.setdefault("epochs", 10)
    kw.setdefault("test_step", 5)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("defense", "Krum")
    kw.setdefault("log_dir", str(tmp_path / "logs"))
    kw.setdefault("run_dir", str(tmp_path / "runs"))
    return ExperimentConfig(**kw)


def _run(cfg, name, checkpointer=None):
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name=name) as logger:
        exp.run(logger, checkpointer=checkpointer)
    with open(logger.jsonl_path) as f:
        events = [json.loads(line) for line in f]
    return exp, events


def _traffic_events(events):
    return [e for e in events if e.get("kind") == "traffic"]


EVENT_KEYS = ("round", "arrived", "f_eff", "cohort", "action", "defense")


def _payload(e):
    return tuple(e[k] for k in EVENT_KEYS)


# ---------------------------------------------------------------------------
# satellite 1: the legacy --participation draw, relocated verbatim

def test_legacy_cohort_bit_compat():
    """population.legacy_cohort IS the pre-population inline draw from
    engine._participants — pinned against the original formula so the
    relocation can never drift (every pre-PR partial-participation
    trajectory depends on these exact ids)."""
    key = jax.random.key(1234)
    n, f, m, m_mal = 20, 4, 10, 2
    for t in (0, 3, 17):
        k1, k2 = jax.random.split(jax.random.fold_in(key, t))
        mal = jax.random.choice(k1, f, (m_mal,), replace=False)
        hon = f + jax.random.choice(k2, n - f, (m - m_mal,),
                                    replace=False)
        want = jnp.concatenate([mal, hon]).astype(jnp.int32)
        got = P.legacy_cohort(key, t, n, f, m, m_mal)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_engine_participants_route_through_population(tmp_path):
    """engine._participants delegates to population.legacy_cohort with
    the engine's own participation key (the single code path both the
    traced round and the streaming prefetcher share)."""
    cfg = _cfg(tmp_path, participation=0.5)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    for t in (0, 2, 7):
        want = P.legacy_cohort(exp.data.part_key, t, exp.n, exp.f, exp.m,
                               exp.m_mal)
        np.testing.assert_array_equal(
            np.asarray(exp._participants(exp.data, t)), np.asarray(want))


# ---------------------------------------------------------------------------
# the defense-validity watchdog (host, schedule time)

def test_plan_action_ladder_bounds():
    """The declared ladder order on the published validity bounds:
    remask while m_eff >= bound(defense), else fallback while m_eff >=
    bound(fallback), else hold.  f is the kernel's STATIC corrupted
    count — the masked kernels trim f rows whatever arrived."""
    # Krum f=3: 2f+3 = 9; TrimmedMean fallback: 2f+1 = 7.
    pa = P.plan_action
    assert pa("Krum", "TrimmedMean", 9, 3, 1) == P.TRAFFIC_REMASK
    assert pa("Krum", "TrimmedMean", 8, 3, 1) == P.TRAFFIC_FALLBACK
    assert pa("Krum", "TrimmedMean", 7, 3, 1) == P.TRAFFIC_FALLBACK
    assert pa("Krum", "TrimmedMean", 6, 3, 1) == P.TRAFFIC_HOLD
    # Bulyan f=1: 4f+3 = 7; Median fallback: 2f+1 = 3.
    assert pa("Bulyan", "Median", 7, 1, 1) == P.TRAFFIC_REMASK
    assert pa("Bulyan", "Median", 6, 1, 1) == P.TRAFFIC_FALLBACK
    assert pa("Bulyan", "Median", 2, 1, 1) == P.TRAFFIC_HOLD
    # min_cohort floors every rung, including NoDefense.
    assert pa("NoDefense", "NoDefense", 3, 0, 1) == P.TRAFFIC_REMASK
    assert pa("NoDefense", "NoDefense", 3, 0, 4) == P.TRAFFIC_HOLD
    assert pa("Krum", "TrimmedMean", 8, 3, 8) == P.TRAFFIC_FALLBACK


def test_sybil_burst_window_and_fixed_average_f():
    """With the burst knob on, colluders arrive ONLY inside the window,
    boosted by period/width so their AVERAGE arrival mass matches the
    uniform profile — participation becomes an attack axis at fixed
    average f."""
    t = _tcfg(population=10_000, rate=0.2, reliability_lo=1.0,
              reliability_hi=1.0, churn_dwell=1, sybil_burst_period=4,
              sybil_burst_width=1)
    reg = P.PopulationRegistry(t, n=10, f=5, seed=3)
    pids = np.arange(2000)                 # colluders: pids < F = 5000
    per_round = [reg.available(pids, tt).mean() for tt in range(8)]
    for tt, frac in enumerate(per_round):
        if tt % 4 == 0:
            assert frac > 0.5              # in-window: boosted ~0.8
        else:
            assert frac == 0.0             # outside: silent
    avg = float(np.mean(per_round))
    # Uniform profile would arrive at rate*reliability = 0.2 per round.
    assert abs(avg - 0.2) < 0.05
    # The honest population is untouched by the sybil knob.
    hon = reg.available(reg.F + pids, 1).mean()
    assert abs(hon - 0.2) < 0.05


# ---------------------------------------------------------------------------
# the registry: lazy, deterministic, structurally O(1) in P

def test_registry_lazy_deterministic_million_clients():
    """P = 1,000,000 clients: the registry object holds scalars only
    (no attribute scales with P), per-client state is a pure function
    of (seed, pid), and two same-seed registries sample identical
    cohorts while different seeds diverge."""
    t = _tcfg(population=1_000_000)
    a = P.PopulationRegistry(t, n=16, f=3, seed=11)
    b = P.PopulationRegistry(t, n=16, f=3, seed=11)
    c = P.PopulationRegistry(t, n=16, f=3, seed=12)
    # Structural O(1): nothing on the object is population-sized.
    for reg in (a, b, c):
        for name, val in vars(reg).items():
            if isinstance(val, np.ndarray):
                assert val.size < 1024, (name, val.size)
    assert a.F == round(1_000_000 * 3 / 16)   # population mirrors f/n
    pids = np.array([0, a.F - 1, 999_999, a.F])
    sa, sb = a.client_state(pids), b.client_state(pids)
    for k in sa:
        np.testing.assert_array_equal(np.asarray(sa[k]),
                                      np.asarray(sb[k]))
    assert sa["malicious"].tolist() == [True, True, False, False]
    # Shard archetypes respect the rows-[0, f) attack invariant.
    assert (sa["shard"][sa["malicious"]] < 3).all()
    assert (sa["shard"][~sa["malicious"]] >= 3).all()
    for tt in (0, 5):
        ids_a, arr_a, p_a = a.sample_cohort(tt, 16, 3)
        ids_b, arr_b, p_b = b.sample_cohort(tt, 16, 3)
        np.testing.assert_array_equal(ids_a, ids_b)
        np.testing.assert_array_equal(arr_a, arr_b)
        np.testing.assert_array_equal(p_a, p_b)
        assert ids_a.shape == (16,) and arr_a.dtype == bool
    assert not np.array_equal(a.sample_cohort(0, 16, 3)[2],
                              c.sample_cohort(0, 16, 3)[2])


def test_schedule_deterministic_across_process_restart(tmp_path):
    """The whole span schedule (ids, arrivals, ladder actions) hashes
    identically when regenerated in a FRESH interpreter — the property
    that makes preempt/resume and host replay exact with no carried
    traffic state."""
    code = (
        "import hashlib, numpy as np\n"
        "from attacking_federate_learning_tpu.config import TrafficConfig\n"
        "from attacking_federate_learning_tpu.core import population as P\n"
        "t = TrafficConfig(population=500, rate=0.6, diurnal_amp=0.3,\n"
        "                  churn_dwell=3, sybil_burst_period=5)\n"
        "reg = P.PopulationRegistry(t, n=12, f=2, seed=7)\n"
        "s = P.traffic_schedule(reg, 0, 12, 12, 2, 'Krum', 'Median', 1)\n"
        "h = hashlib.sha256()\n"
        "for arr in (s.shard_ids, s.arrived.astype(np.int8), s.action):\n"
        "    h.update(np.ascontiguousarray(arr).tobytes())\n"
        "print(h.hexdigest())\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = [subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, check=True,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
           .stdout.strip() for _ in range(2)]
    assert out[0] == out[1]
    # And it matches THIS process's regeneration.
    import hashlib as _hl
    t = TrafficConfig(population=500, rate=0.6, diurnal_amp=0.3,
                      churn_dwell=3, sybil_burst_period=5)
    reg = P.PopulationRegistry(t, n=12, f=2, seed=7)
    s = P.traffic_schedule(reg, 0, 12, 12, 2, "Krum", "Median", 1)
    h = _hl.sha256()
    for arr in (s.shard_ids, s.arrived.astype(np.int8), s.action):
        h.update(np.ascontiguousarray(arr).tobytes())
    assert h.hexdigest() == out[0]


# ---------------------------------------------------------------------------
# the flat engine under traffic: events, ladder, HLO structure

def test_traffic_events_match_replay(tmp_path):
    """A 10-round churn run emits one v11 'traffic' event per round
    whose payload diffs IDENTICAL against the independent host
    regeneration (population.replay_traffic) — the fault_matrix-style
    replay audit."""
    cfg = _cfg(tmp_path, traffic=_tcfg(population=96, rate=0.7,
                                       churn_dwell=2, seed=9))
    exp, events = _run(cfg, "traffic_replay")
    got = sorted(_traffic_events(events), key=lambda e: e["round"])
    assert len(got) == 10
    want = P.replay_traffic(cfg, cfg.epochs)
    assert [_payload(e) for e in got] == [_payload(e) for e in want]
    assert all(e["v"] >= 11 for e in got)


def test_forced_underfill_completes_via_ladder(tmp_path):
    """Acceptance: a run whose cohort persistently under-fills the Krum
    validity bound COMPLETES (no raise) by walking the declared ladder,
    every decision is emitted and replay-exact, and a hold round is a
    true no-op (an all-hold schedule freezes the weights bit-for-bit)."""
    # Unreliable tiny population: arrivals routinely miss 2f+3.
    cfg = _cfg(tmp_path, epochs=8, traffic=_tcfg(
        population=16, rate=0.35, reliability_lo=0.3, reliability_hi=0.6,
        churn_dwell=2, fallback_defense="TrimmedMean", seed=5))
    exp, events = _run(cfg, "traffic_underfill")
    got = sorted(_traffic_events(events), key=lambda e: e["round"])
    assert len(got) == 8
    want = P.replay_traffic(cfg, cfg.epochs)
    assert [_payload(e) for e in got] == [_payload(e) for e in want]
    acts = {e["action"] for e in got}
    assert acts & {"fallback", "hold"}, acts   # the bound WAS violated
    # Degraded rounds aggregate with the defense the event names.
    for e in got:
        assert e["defense"] == {"remask": "Krum",
                                "fallback": "TrimmedMean",
                                "hold": "none"}[e["action"]]
    # All-hold schedule: min_cohort above the cohort size means no
    # round can ever satisfy the floor -> weights frozen bit-for-bit.
    cfg2 = _cfg(tmp_path, epochs=4, test_step=10, traffic=_tcfg(
        population=32, min_cohort=64))
    ds = load_dataset(cfg2.dataset, seed=0, synth_train=cfg2.synth_train,
                      synth_test=cfg2.synth_test)
    exp2 = FederatedExperiment(cfg2, attacker=DriftAttack(1.0),
                               dataset=ds)
    w0 = np.array(exp2.state.weights, copy=True)
    with RunLogger(cfg2, None, cfg2.log_dir,
                   jsonl_name="traffic_allhold") as logger:
        exp2.run(logger)
    np.testing.assert_array_equal(np.asarray(exp2.state.weights), w0)
    assert all(e["action"] == "hold"
               for e in P.replay_traffic(cfg2, cfg2.epochs))


def test_no_population_tensor_in_program(tmp_path):
    """Structural memory pin (the perf_gate --memproof analogue): with
    P = 1,000,000 registered clients the lowered traffic-span HLO
    carries cohort-sized operands only — no dimension anywhere in the
    program scales with P, and the schedule plan stays host-side
    numpy."""
    cfg = _cfg(tmp_path, traffic=_tcfg(population=1_000_000, seed=3))
    ds = load_dataset(cfg.dataset, seed=0, synth_train=cfg.synth_train,
                      synth_test=cfg.synth_test)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    assert exp._span_entry_name() == "traffic_span"
    hlo = exp._span_hlo_text(4)
    assert "1000000" not in hlo            # no dim-P shape compiled
    assert f"{4},{exp.m}" in hlo.replace(" ", "") or "4,12" in hlo
    sched = exp._traffic_plan(0, 4)
    assert sched.shard_ids.shape == (4, exp.m)
    assert (sched.shard_ids < exp.n).all()
    # Traffic OFF: the engine builds none of the machinery (the
    # byte-identity of the compiled programs is pinned end to end by
    # tools/perf_gate.py stageproof against PERF_BASELINE).
    cfg_off = _cfg(tmp_path)
    exp_off = FederatedExperiment(cfg_off, attacker=DriftAttack(1.0),
                                  dataset=ds)
    assert exp_off.traffic is None and exp_off.registry is None
    assert exp_off._traffic_span is None
    assert exp_off._span_entry_name() == "fused_span"


# ---------------------------------------------------------------------------
# preempt/resume: the stateless schedule makes resume free

def test_sigterm_preempt_resume_bit_for_bit_traffic(tmp_path):
    """SIGTERM at an arbitrary round under traffic: the restarted run
    finishes with final weights bit-for-bit equal to the uninterrupted
    run, the journal audits clean, and the stitched event stream
    carries every round's traffic event exactly once — possible only
    because the schedule is pure in (config, t) with NO carried state."""
    from attacking_federate_learning_tpu.utils.lifecycle import (
        GracefulShutdown, Preempted, RunJournal
    )

    kill_round = int(np.random.default_rng(17).integers(1, 9))
    tr = _tcfg(population=96, rate=0.7, churn_dwell=2, seed=9)

    def cfg_for(run_dir):
        return _cfg(tmp_path, traffic=tr, checkpoint_every=3,
                    run_dir=str(tmp_path / run_dir))

    cfg_ref = cfg_for("runs_ref")
    ds = load_dataset(cfg_ref.dataset, seed=0,
                      synth_train=cfg_ref.synth_train,
                      synth_test=cfg_ref.synth_test)
    full = FederatedExperiment(cfg_ref, attacker=DriftAttack(1.0),
                               dataset=ds)
    with RunLogger(cfg_ref, None, cfg_ref.log_dir,
                   jsonl_name="traf_full") as logger:
        full.run(logger, checkpointer=Checkpointer(cfg_ref))
    w_full = np.array(full.state.weights, copy=True)

    cfg = cfg_for("runs_sup")
    ck = Checkpointer(cfg)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="traf_sup") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "traf"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    resumed = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=ds)
    state, extra = ck.resume(ck.latest(), with_extra=True)
    resumed.state = state
    resumed.restore_fault_state(extra)
    with RunLogger(cfg, None, cfg.log_dir, jsonl_name="traf_sup") as logger:
        resumed.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "traf"))

    np.testing.assert_array_equal(np.asarray(resumed.state.weights),
                                  w_full)
    assert RunJournal(cfg.run_dir, "traf").verify(
        epochs=10, test_step=5) == []
    # Exactly-once traffic events across the two attempts, replay-exact.
    with open(os.path.join(cfg.log_dir, "traf_sup.jsonl")) as f:
        ev = [json.loads(line) for line in f]
    got = sorted(_traffic_events(ev), key=lambda e: e["round"])
    assert [e["round"] for e in got] == list(range(10))
    want = P.replay_traffic(cfg, cfg.epochs)
    assert [_payload(e) for e in got] == [_payload(e) for e in want]


# ---------------------------------------------------------------------------
# async latency profile + hierarchical slot resampling

def test_async_latency_profile_deterministic():
    """The heavy-tail delay draw is pure in (key, t), lands inside the
    delivery ring, and the per-client scales come off the lazy
    registry — same config, same scales."""
    cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, users_count=12,
                           mal_prop=0.2,
                           traffic=_tcfg(population=200, latency_scale=2.0,
                                         latency_tail=1.2, seed=4))
    scales, tail = P.async_latency_for_cfg(cfg, 12)
    scales2, _ = P.async_latency_for_cfg(cfg, 12)
    np.testing.assert_array_equal(np.asarray(scales),
                                  np.asarray(scales2))
    assert scales.shape == (12,) and (np.asarray(scales) > 0).all()
    assert tail == 1.2
    key = jax.random.key(0)
    for t in (0, 3):
        d1 = np.asarray(P.traffic_delays(key, t, scales, tail, 6))
        d2 = np.asarray(P.traffic_delays(key, t, scales, tail, 6))
        np.testing.assert_array_equal(d1, d2)
        assert d1.dtype == np.int32
        assert (d1 >= 0).all() and (d1 <= 5).all()
    assert not np.array_equal(
        np.asarray(P.traffic_delays(key, 0, scales, tail, 6)),
        np.asarray(P.traffic_delays(key, 1, scales, tail, 6)))


def test_hier_resample_slots_deterministic_and_invariant():
    """Per-megabatch slot resampling: pure in (key, t, ids[0]),
    malicious slots draw archetypes from [0, f), honest from [f, n) —
    the per-megabatch mirror of the rows-[0, c_mal) invariant."""
    key = jax.random.key(2)
    ids = jnp.arange(100, 108, dtype=jnp.int32)
    a = np.asarray(P.resample_slots(key, 4, ids, 2, 3, 16))
    b = np.asarray(P.resample_slots(key, 4, ids, 2, 3, 16))
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.int32 and a.shape == (8,)
    assert (a[:2] < 3).all() and (a[2:] >= 3).all() and (a < 16).all()
    c = np.asarray(P.resample_slots(key, 5, ids, 2, 3, 16))
    assert not np.array_equal(a, c)


# ---------------------------------------------------------------------------
# loud rejections (campaigns/spec.py pre-validates the same way)

def test_check_traffic_support_rejections(tmp_path):
    def make(**kw):
        kw.setdefault("traffic", _tcfg())
        return _cfg(tmp_path, **kw)

    with pytest.raises(ValueError, match="cover the cohort"):
        P.check_traffic_support(make(traffic=_tcfg(population=4)))
    with pytest.raises(ValueError, match="secagg"):
        P.check_traffic_support(make(secagg="vanilla",
                                     defense="TrimmedMean"))
    with pytest.raises(ValueError, match="host_stream|device"):
        P.check_traffic_support(make(data_placement="host_stream"))
    with pytest.raises(ValueError, match="mask-aware"):
        P.check_traffic_support(make(defense="GeoMedian"))
    with pytest.raises(ValueError, match="fallback"):
        P.check_traffic_support(
            make(traffic=_tcfg(fallback_defense="GeoMedian")))
    with pytest.raises(ValueError, match="host"):
        P.check_traffic_support(make(trimmed_mean_impl="host",
                                     defense="TrimmedMean"))
    with pytest.raises(ValueError, match="shard_map|SPMD|clients"):
        P.check_traffic_support(make(aggregation="hierarchical",
                                     megabatch=4, mesh_shape=(2, 1)))
    # The staged backdoor path has no arrival seam.
    with pytest.raises(ValueError, match="fused backdoor"):
        P.check_traffic_support(make(backdoor="pattern",
                                     backdoor_fused=False))
