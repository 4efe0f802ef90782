"""SPMD hierarchical tier-1 on the 8-virtual-device mesh (ISSUE 12).

Acceptance contract: with a multi-device mesh ``clients`` axis the
hierarchical round runs as one shard_map program (each device scans
its own megabatches, tier-2 reads one explicit estimate all_gather)
and reproduces the sequential scan path inside the measured ulp band —
for every tier-1 defense, both placements (concentrated exercises the
group-padding schedule), masked (faulted) and weighted (async-style)
kernel variants, and telemetry; a shard count not divisible by the
clients axis is rejected loudly (engine, schedule and campaign
pre-check agreeing on the message); the compiled per-device program
holds no full (n, d)/(S, m, d) tensor and its collective traffic is
the O(S·d) gather; and a SIGTERM-preempted sharded run resumes
bit-for-bit (same harness as test_hierarchy.py's lifecycle test).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.defenses.kernels import (
    TIER2_DEFENSES, bulyan, krum, trimmed_mean
)
from attacking_federate_learning_tpu.defenses.median import median
from attacking_federate_learning_tpu.ops.federated import (
    make_placement, spmd_schedule, tier1_assumed, tier2_assumed,
    two_tier_aggregate
)
from attacking_federate_learning_tpu.parallel.mesh import make_plan
from attacking_federate_learning_tpu.utils.checkpoint import Checkpointer
from attacking_federate_learning_tpu.utils.metrics import RunLogger

needs_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                             reason="needs 8 (virtual) devices")

_DS = {}


def _dataset(name=C.SYNTH_MNIST):
    if name not in _DS:
        _DS[name] = load_dataset(name, seed=0, synth_train=256,
                                 synth_test=64)
    return _DS[name]


def _cfg(tmp_path, **kw):
    kw.setdefault("dataset", C.SYNTH_MNIST)
    kw.setdefault("users_count", 32)
    kw.setdefault("mal_prop", 0.25)
    kw.setdefault("batch_size", 8)
    kw.setdefault("epochs", 2)
    kw.setdefault("synth_train", 256)
    kw.setdefault("synth_test", 64)
    kw.setdefault("aggregation", "hierarchical")
    kw.setdefault("megabatch", 4)
    kw.setdefault("log_dir", str(tmp_path / "logs"))
    kw.setdefault("run_dir", str(tmp_path / "runs"))
    return ExperimentConfig(**kw)


def _run(tmp_path, shardings, rounds=2, **kw):
    cfg = _cfg(tmp_path, **kw)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(cfg.num_std),
                              dataset=_dataset(), shardings=shardings)
    for t in range(rounds):
        exp.run_round(t)
    return exp, np.asarray(exp.state.weights)


# ---------------------------------------------------------------------------
# schedule invariants (pure host — no devices needed)

@pytest.mark.parametrize("mal_placement", ["spread", "concentrated"])
@pytest.mark.parametrize("n,f,m,parts", [
    (32, 8, 4, 8), (32, 8, 4, 4), (64, 15, 4, 8), (48, 5, 4, 6),
])
def test_spmd_schedule_invariants(n, f, m, parts, mal_placement):
    """Every megabatch is scheduled exactly once in gathered order,
    padding is bounded by < parts duplicate rows per group, and the
    per-group grids deal device-contiguous slices of the placement."""
    pl = make_placement(n, f, m, mal_placement)
    sched = spmd_schedule(pl, parts)
    S = pl.num_shards
    assert sorted(np.unique(sched.select)) == sorted(sched.select)
    assert sched.padded_shards >= S
    assert sched.padded_shards < S + parts * len(pl.groups)
    # Reconstruct the device-major gathered order and check select
    # lands every true megabatch on a row holding ITS client ids.
    k_per = [g.shape[0] // parts for g in sched.grids]
    gathered = []
    for q in range(parts):
        for gi, grid in enumerate(sched.grids):
            k = k_per[gi]
            gathered.extend(grid[q * k:(q + 1) * k].tolist())
    for sid in range(S):
        assert gathered[sched.select[sid]] == pl.grid[sid].tolist()
    # Static counts match the placement groups 1:1.
    assert sched.counts == tuple(c for c, _ in pl.groups)


def test_spmd_schedule_rejects_indivisible_shard_count():
    """S % clients axis != 0 is a loud error naming the knobs — never
    silent replication (ISSUE 12 satellite)."""
    pl = make_placement(24, 5, 4, "spread")        # S = 6
    with pytest.raises(ValueError, match="--megabatch"):
        spmd_schedule(pl, 8)
    with pytest.raises(ValueError, match="mesh clients"):
        spmd_schedule(pl, 4)
    # Divisible counts pass whatever the group layout.
    for parts in (1, 2, 3, 6):
        assert spmd_schedule(pl, parts).parts == parts


@needs_8
def test_engine_rejects_indivisible_shard_count_loudly(tmp_path):
    """The engine init (and the campaign pre-check, via the same
    function) rejects mesh ⊕ hierarchical when S is not divisible by
    the clients axis — message names the flags, cells become skips."""
    from attacking_federate_learning_tpu.campaigns.spec import (
        composition_reject_reason
    )

    with pytest.raises(ValueError, match="--mesh-shape"):
        FederatedExperiment(
            _cfg(tmp_path, users_count=24, megabatch=4,
                 mesh_shape=(8, 1)),
            attacker=DriftAttack(1.5), dataset=_dataset())
    overrides = dict(
        dataset=C.SYNTH_MNIST, users_count=24, mal_prop=0.25,
        batch_size=8, epochs=2, aggregation="hierarchical",
        megabatch=4, mesh_shape=[8, 1], synth_train=256, synth_test=64)
    reason = composition_reject_reason(overrides)
    assert reason is not None and "--megabatch" in reason
    assert "clients axis=8" in reason
    # The same cell on a compatible mesh pre-validates clean.
    overrides["mesh_shape"] = [2, 1]
    assert composition_reject_reason(overrides) is None


def test_config_validates_mesh_shape_and_normalizes():
    cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, mesh_shape=[4, 2])
    assert cfg.mesh_shape == (4, 2)                 # list -> tuple
    for bad in ((0, 1), (4,), (2, 1, 1), ("4", "2")):
        with pytest.raises(ValueError, match="mesh_shape"):
            ExperimentConfig(dataset=C.SYNTH_MNIST, mesh_shape=bad)


# ---------------------------------------------------------------------------
# engine parity: sharded == unsharded per defense / placement / mesh

_T2 = {"Krum": "Krum", "TrimmedMean": "TrimmedMean",
       "Median": "Median", "Bulyan": "TrimmedMean"}


@needs_8
@pytest.mark.parametrize("defense", sorted(_T2))
def test_spmd_round_matches_scan_per_defense(tmp_path, defense):
    kw = dict(defense=defense, tier2_defense=_T2[defense])
    if defense == "Bulyan":
        kw.update(users_count=64, megabatch=8, mal_prop=0.125)
    exp_ref, w_ref = _run(tmp_path, None, **kw)
    exp_spmd, w_spmd = _run(tmp_path, make_plan((8, 1)), **kw)
    assert exp_spmd._hier_spmd and not exp_ref._hier_spmd
    np.testing.assert_allclose(w_spmd, w_ref, atol=2e-5, rtol=1e-5)


@needs_8
@pytest.mark.parametrize("mesh_shape", [(4, 2), (2, 4)])
def test_spmd_round_matches_scan_across_mesh_shapes(tmp_path,
                                                    mesh_shape):
    """Model-axis sharding composes: the SPMD client_map replicates
    over the model axis, the server update stays model-sharded."""
    exp_ref, w_ref = _run(tmp_path, None)
    _, w_spmd = _run(tmp_path, make_plan(mesh_shape))
    np.testing.assert_allclose(w_spmd, w_ref, atol=2e-5, rtol=1e-5)


@needs_8
def test_spmd_round_matches_scan_concentrated_padding(tmp_path):
    """Concentrated placement leaves uneven groups (2 full + 6 empty
    over a 4-way axis): the padded schedule must not change a bit."""
    kw = dict(mal_placement="concentrated")
    _, w_ref = _run(tmp_path, None, **kw)
    exp, w_spmd = _run(tmp_path, make_plan((4, 2)), **kw)
    sched = spmd_schedule(exp._placement, 4)
    assert sched.padded_shards > exp._placement.num_shards  # real padding
    np.testing.assert_allclose(w_spmd, w_ref, atol=2e-5, rtol=1e-5)


@needs_8
def test_spmd_telemetry_matches_scan(tmp_path):
    """The stacked per-shard diagnostics and tier-2 selection record
    ride the same gather+reorder as the estimates — telemetry under
    SPMD is the scan path's telemetry, leaf for leaf."""
    kw = dict(telemetry=True)
    exp_ref, w_ref = _run(tmp_path, None, **kw)
    exp_spmd, w_spmd = _run(tmp_path, make_plan((8, 1)), **kw)
    np.testing.assert_allclose(w_spmd, w_ref, atol=2e-5, rtol=1e-5)
    ref_t, spmd_t = (exp_ref.last_round_telemetry,
                     exp_spmd.last_round_telemetry)
    assert sorted(ref_t) == sorted(spmd_t)
    for k in ref_t:
        np.testing.assert_allclose(np.asarray(spmd_t[k]),
                                   np.asarray(ref_t[k]),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"telemetry leaf {k}")


# ---------------------------------------------------------------------------
# kernel-level parity: masked (faulted) and weighted (async-style)

_T1 = {"Krum": krum, "TrimmedMean": trimmed_mean, "Bulyan": bulyan,
       "Median": median}


@needs_8
@pytest.mark.parametrize("name", sorted(_T1))
@pytest.mark.parametrize("variant", ["masked", "weighted"])
def test_two_tier_spmd_masked_weighted_parity(name, variant):
    """two_tier_aggregate under the SPMD plan == the sequential path,
    with the quarantine mask (faulted rows) and the staleness-weight
    seam threaded through the per-shard tier-1 kernels."""
    n, m, f = 32, 8, 3
    pl = make_placement(n, f, m, "spread")
    f1 = tier1_assumed(f, pl.num_shards)
    f2 = max(tier2_assumed(f, m), 1)
    rng = np.random.default_rng(11)
    G = jnp.asarray(rng.standard_normal((n, 40)).astype(np.float32))
    mask = jnp.asarray(rng.random(n) > 0.25)
    weights = (jnp.asarray((1.0 / np.sqrt(
        1.0 + rng.integers(0, 3, n))).astype(np.float32))
        if variant == "weighted" else None)
    t1, t2 = _T1[name], TIER2_DEFENSES[_T2[name]]
    plan = make_plan((4, 2))

    ref = two_tier_aggregate(G, pl, t1, t2, f1, f2, mask=mask,
                             weights=weights)
    got = two_tier_aggregate(G, pl, t1, t2, f1, f2, mask=mask,
                             weights=weights, plan=plan)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-7, rtol=1e-6)


def test_two_tier_weights_require_mask():
    pl = make_placement(16, 2, 4, "spread")
    G = jnp.zeros((16, 8), jnp.float32)
    with pytest.raises(ValueError, match="weights= requires mask="):
        two_tier_aggregate(G, pl, krum, TIER2_DEFENSES["Krum"], 1, 1,
                           weights=jnp.ones(16))


# ---------------------------------------------------------------------------
# structural facts: collectives + placement invariants under sharding

@needs_8
def test_spmd_hlo_truly_sharded_and_collective_pinned(tmp_path):
    """The compiled per-device hier round holds no full (n, d) /
    (S, m, d) / (n, n) tensor, and its only collective is the estimate
    all_gather at exactly S*d*4 bytes (uniform spread groups, 1-way
    model axis)."""
    from attacking_federate_learning_tpu.utils.costs import (
        collective_hlo_bytes, compiled_cost_facts
    )

    cfg = _cfg(tmp_path, users_count=64, megabatch=4)   # S=16, f=16
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.5),
                              dataset=_dataset(),
                              shardings=make_plan((8, 1)))
    compiled = exp._fused_round.lower(
        exp.data, exp.state, jnp.asarray(0, jnp.int32), None).compile()
    text = compiled.as_text()
    d, S = exp.flat.dim, 16
    for shape in (f"f32[64,{d}]", f"bf16[64,{d}]", f"f32[16,4,{d}]",
                  "f32[64,64]"):
        assert shape not in text, f"{shape} rematerialized"
    facts = compiled_cost_facts(compiled)
    assert facts["collective_bytes"] == S * d * 4
    per_op = collective_hlo_bytes(text)["per_op"]
    assert set(per_op) == {"all-gather"}


@needs_8
def test_one_device_clients_axis_keeps_scan_path(tmp_path):
    """A (1, 1) mesh must route through the sequential scan: no SPMD
    flag, no collective in the compiled program, and cost facts equal
    to the no-mesh scan path exactly (the shardproof (a) leg)."""
    from attacking_federate_learning_tpu.utils.costs import (
        compiled_cost_facts
    )

    def facts(shardings):
        exp = FederatedExperiment(
            _cfg(tmp_path), attacker=DriftAttack(1.5),
            dataset=_dataset(), shardings=shardings)
        return exp, compiled_cost_facts(exp._fused_round.lower(
            exp.data, exp.state, jnp.asarray(0, jnp.int32), None).compile())

    plan1 = make_plan((1, 1), devices=jax.devices()[:1])
    exp1, f1 = facts(plan1)
    exp0, f0 = facts(None)
    assert not exp1._hier_spmd
    assert f1["collective_bytes"] == 0
    for k in ("flops", "bytes_accessed", "argument_bytes",
              "output_bytes", "temp_bytes"):
        assert f1[k] == f0[k], k


def test_collective_hlo_bytes_parser():
    from attacking_federate_learning_tpu.utils.costs import (
        collective_hlo_bytes
    )

    text = """
  %ag = f32[16,100]{1,0} all-gather(f32[2,100]{1,0} %x), dimensions={0}
  %ar = bf16[8]{0} all-reduce(bf16[8]{0} %y), to_apply=%sum
  %cp.1 = f32[4,4]{1,0} collective-permute-start(f32[4,4]{1,0} %z)
  %done = f32[4,4]{1,0} collective-permute-done(%cp.1)
  %plain = f32[9,9]{1,0} add(f32[9,9]{1,0} %a, f32[9,9]{1,0} %b)
"""
    out = collective_hlo_bytes(text)
    assert out["per_op"]["all-gather"] == 16 * 100 * 4
    assert out["per_op"]["all-reduce"] == 8 * 2
    assert out["per_op"]["collective-permute"] == 4 * 4 * 4
    assert out["total"] == sum(out["per_op"].values())
    assert collective_hlo_bytes("%r = f32[4] add(%a, %b)")["total"] == 0


# ---------------------------------------------------------------------------
# campaign surface: mesh knobs stamped, invalid meshes become skips

def test_campaign_cells_stamp_mesh_knobs_and_skip_bad_mesh():
    from attacking_federate_learning_tpu.campaigns.spec import (
        CampaignSpec
    )

    spec = CampaignSpec(
        name="spmd",
        base=dict(dataset=C.SYNTH_MNIST, users_count=32, mal_prop=0.25,
                  batch_size=8, epochs=2, aggregation="hierarchical",
                  megabatch=4, synth_train=256, synth_test=64),
        axes={"mesh_shape": [[2, 1], [8, 1], [5, 1]]})
    cells = spec.expand()
    assert [c.skip is None for c in cells] == [True, True, False]
    assert "--megabatch" in cells[2].skip        # S=8 % 5 != 0
    for c in cells:
        row = c.row()
        assert row["megabatch"] == 4
        assert row["mal_placement"] == "spread"
        assert isinstance(row["mesh_shape"], list)
    assert cells[1].row()["mesh_shape"] == [8, 1]
    assert json.dumps([c.row() for c in cells])  # JSONL-stable


# ---------------------------------------------------------------------------
# lifecycle: SIGTERM preempt -> resume bit-for-bit on a sharded mesh

@needs_8
def test_spmd_preempt_resume_bit_for_bit(tmp_path):
    """Same harness as test_hierarchy.py's lifecycle test, on the
    (8, 1) mesh: a gracefully preempted SPMD hierarchical run resumes
    to final weights bit-for-bit equal to the uninterrupted run."""
    from attacking_federate_learning_tpu.utils.lifecycle import (
        GracefulShutdown, Preempted, RunJournal
    )

    ds = _dataset()
    kill_round = 3

    def cfg_for(run_dir):
        return _cfg(tmp_path, defense="Krum", epochs=6, test_step=3,
                    checkpoint_every=2, mesh_shape=(8, 1),
                    run_dir=str(tmp_path / run_dir))

    cfg_ref = cfg_for("runs_ref")
    full = FederatedExperiment(cfg_ref, attacker=DriftAttack(1.0),
                               dataset=ds)
    assert full._hier_spmd
    with RunLogger(cfg_ref, None, cfg_ref.log_dir,
                   jsonl_name="spmd_full") as logger:
        full.run(logger, checkpointer=Checkpointer(cfg_ref))
    w_full = np.array(full.state.weights, copy=True)
    v_full = np.array(full.state.velocity, copy=True)

    cfg = cfg_for("runs_sup")
    ck = Checkpointer(cfg)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.0), dataset=ds)
    with RunLogger(cfg, None, cfg.log_dir,
                   jsonl_name="spmd_sup") as logger:
        with pytest.raises(Preempted):
            exp.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "spmd"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    resumed = FederatedExperiment(cfg, attacker=DriftAttack(1.0),
                                  dataset=ds)
    state, _extra = ck.resume(ck.latest(), with_extra=True)
    resumed.state = state
    with RunLogger(cfg, None, cfg.log_dir,
                   jsonl_name="spmd_sup") as logger:
        resumed.run(logger, checkpointer=ck,
                    journal=RunJournal(cfg.run_dir, "spmd"),
                    shutdown=GracefulShutdown(
                        preempt_at_round=kill_round))

    np.testing.assert_array_equal(np.asarray(resumed.state.weights),
                                  w_full)
    np.testing.assert_array_equal(np.asarray(resumed.state.velocity),
                                  v_full)
    assert RunJournal(cfg.run_dir, "spmd").verify(
        epochs=6, test_step=3) == []
    with open(os.path.join(cfg.log_dir, "spmd_sup.jsonl")) as f:
        events = [json.loads(line) for line in f]
    evals = [e["round"] for e in events if e["kind"] == "eval"]
    assert evals == sorted(set(evals))
