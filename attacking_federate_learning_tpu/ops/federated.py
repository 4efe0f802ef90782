"""Explicit federated primitives: broadcast / client-map / shard-reduce.

The flat engine materializes the full (n, d) gradient matrix every round
and (for Krum/Bulyan) an (n, n) distance matrix on top — the O(n·d) /
O(n²·d) memory wall that caps the client axis around n≈10k (at n=1M the
gradient matrix alone is ~300 TB).  DrJAX (arXiv 2403.07128) shows that
federated computations decompose into three primitives that compose
with sharding and scan; this module is that decomposition for the round
engine's client axis:

- :func:`broadcast` — server state to every client.  In jax this is
  free (closure capture + XLA replication), so the primitive is an
  annotation hook: under a MeshPlan it pins the replicated layout.
- :func:`client_map` — apply a per-megabatch function over the client
  axis as a ``lax.scan`` of static-size *megabatches* (m ≪ n clients at
  a time).  Only one megabatch's gradients are ever live; XLA reuses
  the loop carry buffers across iterations, so the round's peak memory
  scales with m·d, not n·d (pinned by tools/perf_gate.py memproof).
- :func:`shard_reduce` — the cross-shard reduction over the (n/m, d)
  shard-estimate matrix (tier-2 of the two-tier robust aggregation,
  defenses/kernels.py shard_* entries).

The megabatch *placement* (which client ids land in which megabatch,
and where the colluding malicious rows [0, f) sit) is a host-side pure
function of the config (:func:`make_placement`).  Placement is a real
Byzantine surface, not a systems detail (NET-SA, arXiv 2501.01187):
colluders *concentrated* in one shard overwhelm its tier-1 estimator
but present tier-2 with a single outlier estimate; *spread* colluders
stay under every shard's tier-1 tolerance but tint every estimate.
``config.mal_placement`` selects the scenario; GRID_RESULTS.md banks
the measured flip.

Attack-seam semantics under client_map (the documented change behind
``aggregation='hierarchical'``): ``Attack.craft`` runs once per
megabatch and sees only that megabatch's malicious rows — cohort
statistics (ALIE's mean/std envelope) are per-megabatch, not global.
Scan shapes must be static, so megabatches are grouped by their
malicious-row count and one scan runs per distinct count (≤ 3 groups:
full/partial/zero under 'concentrated', hi/lo under 'spread').

SPMD tier-1 (ISSUE 12): with a MeshPlan whose ``clients`` axis holds
more than one device, ``client_map`` stops being a sequential scan and
becomes one ``shard_map`` program over the clients axis: each device
scans ONLY its own megabatches locally (one megabatch's intermediates
live per device — the O(m·d) contract survives per shard), and the
stacked per-device outputs meet in one explicit tiled ``all_gather``
— O(S·d) bytes on the wire — so tier-2 reads a replicated, ordered
(S, d) estimate matrix with no GSPMD resharding seam (the
"involuntary full rematerialization" warning the MULTICHIP dryruns
logged came from exactly that seam).  :func:`spmd_schedule` is the
host-side plan: S must divide by the clients axis (rejected loudly —
silent replication would defeat the sharding), and a placement group
whose megabatch count does not divide evenly is padded with DUPLICATE
megabatches (bounded: < clients-axis extra rows per group, dropped
after the gather by the ``select`` index) so every device runs the
same static program without changing any estimate.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class Placement(NamedTuple):
    """Host-side megabatch layout: a pure function of the config.

    ``grid[s]`` lists megabatch s's client ids, malicious ids first
    (the per-megabatch mirror of the engine's rows-[0, f) attack
    invariant); ``mal_counts[s]`` is that static count.  ``groups``
    pairs each distinct malicious count with the megabatch ids that
    share it — one ``lax.scan`` per group keeps every shape static.
    """

    grid: np.ndarray                       # (S, m) int32 client ids
    mal_counts: Tuple[int, ...]            # per-megabatch malicious rows
    groups: Tuple[Tuple[int, Tuple[int, ...]], ...]
    megabatch: int                         # m
    num_shards: int                        # S = n / m


def tier1_assumed(f: int, num_shards: int) -> int:
    """Default per-shard corrupted bound the tier-1 estimator assumes:
    the server doesn't know the placement, so it budgets for the
    evenly-spread worst case, ceil(f / S)."""
    return -(-f // num_shards) if f > 0 else 0


def tier2_assumed(f: int, megabatch: int) -> int:
    """Default corrupted-shard bound for tier-2: the number of shards
    the f colluders could fill outright, ceil(f / m) (capped below by 1
    whenever any colluder exists — one partially-filled shard can still
    carry a poisoned estimate)."""
    return -(-f // megabatch) if f > 0 else 0


def make_placement(n: int, f: int, megabatch: int,
                   mal_placement: str = "spread") -> Placement:
    """Assign the n clients (malicious = ids [0, f)) to n/m megabatches.

    'spread' deals malicious ids round-robin across megabatches
    (counts differ by at most one); 'concentrated' packs them into the
    fewest megabatches (the colluders-own-a-shard scenario).  Honest
    ids fill the remaining slots in id order.  Deterministic — no RNG:
    the placement is part of the run's identity.
    """
    if megabatch < 1 or n % megabatch:
        raise ValueError(
            f"megabatch must divide users_count (n={n}, m={megabatch})")
    if mal_placement not in ("spread", "concentrated"):
        raise ValueError(f"mal_placement must be 'spread' or "
                         f"'concentrated', got {mal_placement!r}")
    m, S = megabatch, n // megabatch
    shards: list = [[] for _ in range(S)]
    for k in range(f):
        shards[k % S if mal_placement == "spread" else k // m].append(k)
    counts = tuple(len(s) for s in shards)
    honest = iter(range(f, n))
    for rows in shards:
        while len(rows) < m:
            rows.append(next(honest))
    grouped: dict = {}
    for sid, c in enumerate(counts):
        grouped.setdefault(c, []).append(sid)
    groups = tuple((c, tuple(sids)) for c, sids in grouped.items())
    return Placement(grid=np.asarray(shards, np.int32), mal_counts=counts,
                     groups=groups, megabatch=m, num_shards=S)


class SpmdSchedule(NamedTuple):
    """Host-side SPMD plan for :func:`client_map` over the mesh
    ``clients`` axis: one padded id grid per placement group (shape
    ``(k_g * parts, m)`` — device q owns rows ``[q*k_g, (q+1)*k_g)``),
    the group's static malicious counts, and ``select`` — for each
    megabatch id, the row it lands on in the device-major
    ``all_gather`` order (also the dedup: padded duplicate rows are
    simply never selected)."""

    grids: Tuple[np.ndarray, ...]      # per group: (k_g*parts, m) ids
    counts: Tuple[int, ...]            # per group static malicious rows
    select: np.ndarray                 # (S,) gathered-row index per shard
    parts: int                         # mesh clients-axis size
    padded_shards: int                 # total scheduled rows (>= S)
    sids: Tuple[np.ndarray, ...] = ()  # per group: (k_g*parts,) shard ids


def spmd_schedule(placement: Placement, parts: int) -> SpmdSchedule:
    """Deal the placement's megabatches across the mesh clients axis.

    ``parts`` is the clients-axis device count.  The shard count S must
    be divisible by it — anything else would silently replicate work
    (the exact failure mode the SPMD mapping exists to retire), so it
    is rejected loudly with the knobs named.  WITHIN a group a
    non-divisible megabatch count is legal: the group is padded with
    duplicates of its first megabatch (< parts extra rows per group,
    pure redundant compute whose outputs ``select`` drops), because
    every device must run the same static per-group scan."""
    S = placement.num_shards
    if parts < 1:
        raise ValueError(f"mesh clients axis must be >= 1, got {parts}")
    if S % parts:
        raise ValueError(
            f"hierarchical SPMD tier-1 needs the megabatch count "
            f"S = users_count/megabatch divisible by the mesh clients "
            f"axis (S={S}, clients axis={parts}): pick --megabatch / "
            f"--mesh-shape so S % clients == 0 — silently replicating "
            f"megabatches across devices would defeat the sharding")
    grids, counts, per_dev, sid_rows = [], [], [], []
    for count, sids in placement.groups:
        k = -(-len(sids) // parts)
        padded = list(sids) + [sids[0]] * (k * parts - len(sids))
        grids.append(placement.grid[padded])
        counts.append(count)
        per_dev.append(k)
        sid_rows.append(np.asarray(padded, np.int32))
    k_sum = sum(per_dev)
    select = np.empty(S, np.int64)
    for gi, (_, sids) in enumerate(placement.groups):
        k, off = per_dev[gi], sum(per_dev[:gi])
        for r, sid in enumerate(sids):
            q, j = divmod(r, k)
            select[sid] = q * k_sum + off + j
    return SpmdSchedule(grids=tuple(grids), counts=tuple(counts),
                        select=select, parts=parts,
                        padded_shards=k_sum * parts,
                        sids=tuple(sid_rows))


def check_hier_support(cfg):
    """Fail fast on configs the hierarchical topology cannot honor
    (engine init AND campaigns/spec.py pre-validation — both call this
    exact function, so the pre-check message and the construction
    message cannot drift).  Pure: no jax op, no model."""
    from attacking_federate_learning_tpu.defenses.kernels import (
        TIER2_DEFENSES
    )

    if cfg.participation < 1.0:
        raise ValueError(
            "hierarchical aggregation requires full participation "
            "(placement assigns every client to a megabatch)")
    if cfg.data_placement != "device":
        raise ValueError(
            "hierarchical aggregation requires "
            "data_placement='device' (the scanned round gathers "
            "each megabatch's batch on device)")
    if cfg.backdoor and not cfg.backdoor_fused:
        raise ValueError(
            "hierarchical aggregation needs the fused backdoor "
            "path (drop --backdoor-staged)")
    if cfg.defense not in TIER2_DEFENSES:
        raise ValueError(
            f"hierarchical tier-1 defense must be one of "
            f"{sorted(TIER2_DEFENSES)} (the mask-aware kernel "
            f"set), got {cfg.defense!r}")
    if cfg.distance_impl in ("ring", "allgather", "host"):
        raise ValueError(
            f"hierarchical aggregation supports distance_impl in "
            f"auto/xla (got {cfg.distance_impl!r}): the "
            f"per-megabatch distance pass must stay inside the "
            f"scanned program")
    for knob in ("trimmed_mean_impl", "median_impl",
                 "bulyan_selection_impl", "bulyan_trim_impl"):
        if getattr(cfg, knob) == "host":
            raise ValueError(
                f"hierarchical aggregation requires a device-"
                f"resident {knob} ('xla'; got 'host' — "
                f"a host kernel would pure_callback once per "
                f"megabatch per scan step)")


def _client_map_spmd(shard_fn, placement: Placement, plan, *args,
                     with_sid=False):
    """One true SPMD program for the megabatch axis: a ``shard_map``
    over the mesh ``clients`` axis in which each device runs the
    group scans over ITS megabatch rows only, then one explicit tiled
    ``all_gather`` per output leaf — O(S · leaf_row_bytes) collective
    traffic — hands every device the full device-major stack, and the
    host-computed ``select`` gather restores megabatch order (and
    drops padding duplicates).  Output pytree: identical structure,
    shapes and (ulp-band) values to the sequential scan path."""
    import functools

    from attacking_federate_learning_tpu.parallel.mesh import CLIENTS
    from jax.sharding import PartitionSpec as P

    sched = spmd_schedule(placement, plan.mesh.shape[CLIENTS])
    grids = tuple(jnp.asarray(g) for g in sched.grids)
    sid_ops = (tuple(jnp.asarray(s) for s in sched.sids) if with_sid
               else ())
    in_specs = (tuple(P(CLIENTS, None) for _ in grids)
                + tuple(P(CLIENTS) for _ in sid_ops))

    @functools.partial(
        jax.shard_map, mesh=plan.mesh, in_specs=in_specs,
        out_specs=P(), check_vma=False)
    def run(*operands):
        dev_grids = operands[:len(grids)]
        dev_sids = operands[len(grids):]
        pieces = []
        for gi, (count, grid) in enumerate(zip(sched.counts,
                                               dev_grids)):
            if with_sid:
                # shard ids ride the scan beside the id grid so the
                # per-shard fault stream replays exactly (ISSUE 19)
                def body(carry, x, _c=count):
                    sid, ids = x
                    return carry, shard_fn(sid, ids, _c, *args)

                xs = (dev_sids[gi], grid)
            else:
                def body(carry, ids, _c=count):
                    return carry, shard_fn(ids, _c, *args)

                xs = grid
            _, stacked = lax.scan(
                body, lax.pcast(jnp.zeros((), jnp.int32), CLIENTS,
                                to="varying"), xs)
            pieces.append(stacked)
        local = (pieces[0] if len(pieces) == 1
                 else jax.tree_util.tree_map(
                     lambda *xs: jnp.concatenate(xs, axis=0), *pieces))
        return jax.tree_util.tree_map(
            lambda x: lax.all_gather(x, CLIENTS, tiled=True), local)

    out = run(*grids, *sid_ops)
    sel = jnp.asarray(sched.select)
    return jax.tree_util.tree_map(lambda a: a[sel], out)


def broadcast(value, plan=None):
    """Server -> clients broadcast.  Functionally the identity (the
    scanned client_map closes over the value and XLA replicates it);
    under a MeshPlan it additionally pins the replicated layout so the
    broadcast operand never picks up a stray sharding from its
    producer."""
    if plan is None:
        return value
    from jax.sharding import PartitionSpec as P

    return lax.with_sharding_constraint(value, plan.sharding(P()))


def client_map(shard_fn, placement: Placement, *args, plan=None,
               with_sid=False):
    """Stream ``shard_fn`` over the client axis, one megabatch at a time.

    ``shard_fn(ids, mal_count, *args) -> pytree`` receives a traced
    (m,) int32 id vector and its megabatch's STATIC malicious-row
    count; ``*args`` are broadcast operands (server state, round
    index).  Returns the per-megabatch pytrees stacked along a leading
    shard axis, in megabatch order — the (n/m, ...) shard-estimate
    matrix.  One ``lax.scan`` per placement group (distinct malicious
    count), so only one megabatch's intermediates are live at a time.

    ``with_sid=True`` threads each megabatch's SHARD id through the
    scan — ``shard_fn(sid, ids, mal_count, *args)`` — so a per-shard
    PRNG stream (the ISSUE 19 fault draw, keyed ``fold_in(fold_in(key,
    t), sid)``) replays identically on the host regardless of group
    order or SPMD padding.  Off by default: the False path traces the
    exact pre-ISSUE-19 program (HLO byte-identity of faults-off runs).

    ``plan``: a MeshPlan whose ``clients`` axis holds > 1 device
    switches to the SPMD mapping (:func:`_client_map_spmd`) — devices
    scan their own megabatches concurrently and meet in one explicit
    all_gather.  ``None`` (or a 1-device clients axis) is the
    sequential scan, byte-for-byte the pre-SPMD program.
    """
    if plan is not None:
        from attacking_federate_learning_tpu.parallel.mesh import CLIENTS

        if plan.mesh.shape[CLIENTS] > 1:
            return _client_map_spmd(shard_fn, placement, plan, *args,
                                    with_sid=with_sid)
    pieces, order = [], []
    for count, sids in placement.groups:
        grid = jnp.asarray(placement.grid[list(sids)])

        if with_sid:
            def body(carry, x, _c=count):
                sid, ids = x
                return carry, shard_fn(sid, ids, _c, *args)

            xs = (jnp.asarray(list(sids), jnp.int32), grid)
        else:
            def body(carry, ids, _c=count):
                return carry, shard_fn(ids, _c, *args)

            xs = grid
        _, stacked = lax.scan(body, jnp.zeros((), jnp.int32), xs)
        pieces.append(stacked)
        order.extend(sids)
    out = (pieces[0] if len(pieces) == 1
           else jax.tree_util.tree_map(
               lambda *xs: jnp.concatenate(xs, axis=0), *pieces))
    if order == sorted(order):
        return out
    inv = jnp.asarray(np.argsort(np.asarray(order)))
    return jax.tree_util.tree_map(lambda a: a[inv], out)


def shard_reduce(tier2_fn, estimates, num_shards: int,
                 corrupted_shards: int, alive_counts=None, plan=None,
                 **kw):
    """Cross-shard (tier-2) robust reduction over the (n/m, d)
    shard-estimate matrix.

    ``tier2_fn`` is a defenses/kernels.py ``shard_*`` entry (or any
    ``(G, n, f, alive_counts=None) -> (d,)`` reducer);
    ``alive_counts`` (S,) carries each shard's effective cohort from
    the fault masks — a fully-dead shard's estimate is excluded.
    Under a MeshPlan the estimate matrix is constrained to the
    clients-axis layout first so the reduction's collectives are
    explicit.  ``telemetry=True`` (forwarded through ``**kw`` to the
    shard_* entry) additionally returns the tier-2 diagnostics pytree
    — (S,)-shaped selection masks/scores over the SHARD axis, the
    which-estimates-were-rejected record the forensics layer
    attributes colluder placement from (report.py).

    Stage ledger (utils/costs.py): the reduction — resharding
    constraint included — is the ``tier2_aggregate`` stage, whatever
    ``tier2_fn`` the caller passes (the engine's dispatch wrap covers
    its own; raw kernels from tests/bench get it here)."""
    from attacking_federate_learning_tpu.utils.costs import stage_scope

    with stage_scope("tier2_aggregate"):
        estimates = estimates.astype(jnp.float32)
        if plan is not None:
            estimates = plan.constrain_estimates(estimates)
        return tier2_fn(estimates, num_shards, corrupted_shards,
                        alive_counts=alive_counts, **kw)


def two_tier_aggregate(users_grads, placement: Placement, tier1_fn,
                       tier2_fn, tier1_corrupted: int,
                       tier2_corrupted: int, mask=None, weights=None,
                       plan=None, telemetry=False):
    """Reference two-tier aggregation over a MATERIALIZED (n, d) matrix.

    The engine's hierarchical round never builds this matrix (gradients
    are computed inside client_map); this helper exists for the places
    that already hold one — kernel-level tests (each tier-1 estimate
    must bit-match the flat kernel on that shard's rows) and the
    aggregation-only benchmarks.  ``mask`` (n,) is the quarantine seam:
    each megabatch's tier-1 runs mask-aware over its rows and tier-2
    receives the per-shard alive counts.

    ``telemetry=True`` (trace-time, like the kernels' flag) returns
    ``(agg, tier1_diag, tier2_diag)``: ``tier1_diag`` is the flat
    kernel's diagnostics pytree stacked along a leading shard axis —
    each row is BY CONSTRUCTION the flat kernel's telemetry on that
    shard's sub-matrix, the bit-match contract the engine's
    shard_selection events inherit — and ``tier2_diag`` is the
    shard_* entry's (S,)-shaped selection record.

    ``weights`` (n,) threads each megabatch's rows through the
    kernels' staleness-weight seam (requires ``mask`` — the kernels
    reject weights without a delivered-cohort mask); ``plan`` with a
    multi-device clients axis runs the SPMD client_map (the estimates
    come back replicated from the explicit all_gather, so the tier-2
    resharding constraint is skipped — there is nothing to reshard).
    """
    m = placement.megabatch
    if weights is not None and mask is None:
        from attacking_federate_learning_tpu.defenses.kernels import (
            check_weight_seam
        )

        check_weight_seam(mask, weights)   # raises, naming the seam

    def shard_fn(ids, _c, G, gmask, gw):
        rows = G[ids]
        if gmask is None:
            if not telemetry:
                return tier1_fn(rows, m,
                                tier1_corrupted).astype(jnp.float32)
            est, diag = tier1_fn(rows, m, tier1_corrupted,
                                 telemetry=True)
            return est.astype(jnp.float32), diag
        sm = gmask[ids]
        kw = {} if gw is None else {"weights": gw[ids]}
        if not telemetry:
            est = tier1_fn(rows, m, tier1_corrupted, mask=sm, **kw)
            return est.astype(jnp.float32), jnp.sum(sm).astype(jnp.int32)
        est, diag = tier1_fn(rows, m, tier1_corrupted, mask=sm,
                             telemetry=True, **kw)
        return (est.astype(jnp.float32), jnp.sum(sm).astype(jnp.int32),
                diag)

    out = client_map(shard_fn, placement, users_grads, mask, weights,
                     plan=plan)
    spmd = False
    if plan is not None:
        from attacking_federate_learning_tpu.parallel.mesh import CLIENTS

        spmd = plan.mesh.shape[CLIENTS] > 1
    tier2_plan = None if spmd else plan
    t1_diag = None
    if mask is None:
        if telemetry:
            estimates, t1_diag = out
            alive = None
        else:
            estimates, alive = out, None
    elif telemetry:
        estimates, alive, t1_diag = out
    else:
        estimates, alive = out
    if not telemetry:
        return shard_reduce(tier2_fn, estimates, placement.num_shards,
                            tier2_corrupted, alive_counts=alive,
                            plan=tier2_plan)
    agg, t2_diag = shard_reduce(tier2_fn, estimates,
                                placement.num_shards, tier2_corrupted,
                                alive_counts=alive, plan=tier2_plan,
                                telemetry=True)
    return agg, t1_diag, t2_diag


# Megabatch sizing helper for callers that only know n (bench, docs):
# the largest power-of-two megabatch ≤ cap that divides n.
def auto_megabatch(n: int, cap: int = 512) -> Optional[int]:
    for m in (2 ** k for k in range(int(math.log2(max(cap, 1))), -1, -1)):
        if m <= cap and n % m == 0 and n // m >= 2:
            return m
    return None
