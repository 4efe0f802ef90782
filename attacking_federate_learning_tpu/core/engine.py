"""Experiment engine: the jitted round loop.

The reference's round is four host-side phases over one process
(reference main.py:64-71): dispatch_weights (N sequential client steps),
attacker.attack, collect_gradients, defend+update.  Here a round is:

    grads = vmap(grad(loss))(w, batches)      # all clients at once
    grads = attack.apply(grads, f, ctx)       # first-f-rows overwrite
    state = momentum_update(state, defense(grads, n, f))

For fusable attacks (none / ALIE / the baselines, and the backdoor by
default — its shadow train is itself pure jitted jax) the whole round is one
jitted function of ``(state, round_index)`` — batch gathers included — so
steady-state rounds are a single device program; ``backdoor_fused=False``
restores the reference's staged seam (main.py:66-71) with its per-round
host nan guard.

Evaluation, checkpointing and logging stay on the host at TEST_STEP cadence
(reference main.py:73-95).

Telemetry (cfg.telemetry): each round's defense diagnostics
(defenses/kernels.py telemetry seam), attack envelope stats
(attacks/base.py:envelope_stats) and per-client population stats ride out
of the jitted round as AUXILIARY OUTPUTS — fixed-shape device pytrees, no
host callbacks inside the jit.  When rounds fuse into spans, a
``lax.scan`` stacks the per-round pytrees along a leading round axis and
the host fetches the whole stack once per eval interval
(``_tele_span``); the per-round dispatch modes fetch per round.  Events
land in the run JSONL as 'defense'/'attack' records plus one end-of-run
'selection_hist' (utils/metrics.py schema).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from attacking_federate_learning_tpu.attacks.base import (
    Attack, AttackContext, NoAttack
)
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.client import (
    cohort_fits, make_client_update_fn, make_loss_fn
)
from attacking_federate_learning_tpu.core.evaluate import make_eval_fn
from attacking_federate_learning_tpu.core.server import (
    ServerState, faded_learning_rate, init_server_state, momentum_update
)
from attacking_federate_learning_tpu.data.augment import (
    augment_key, reflect_crop_flip
)
from attacking_federate_learning_tpu.data.datasets import (
    crop_contexts, load_dataset
)
from attacking_federate_learning_tpu.data.partition import (
    make_shards, round_batch_indices
)
from attacking_federate_learning_tpu.defenses import (
    DEFENSES, check_defense_args
)
from attacking_federate_learning_tpu.defenses.dnc import sketch_key
from attacking_federate_learning_tpu.defenses.kernels import stage_wrapped
from attacking_federate_learning_tpu.models.base import get_model
from attacking_federate_learning_tpu.utils.costs import stage_scope
from attacking_federate_learning_tpu.utils.flatten import make_flattener
from attacking_federate_learning_tpu.utils.metrics import RunLogger
from attacking_federate_learning_tpu.utils.numerics import (
    nonfinite_count, norm_dynamic_range
)
from attacking_federate_learning_tpu.utils.profiling import (
    RECORDER, span as host_span
)


def _jsonable(v):
    """Host telemetry leaf -> JSON value: 0-d arrays to float, vectors
    to lists, matrices — the hierarchical (S, m) per-shard stacks — to
    nested lists (the event schema stores fixed-shape arrays inline)."""
    a = np.asarray(v)
    if a.ndim == 0:
        return float(a)
    if a.ndim == 1:
        return [float(x) for x in a]
    return a.astype(float).tolist()


class RoundData(NamedTuple):
    """What a round reads and never writes: the device-resident training
    set, the client -> sample shards and every seed-derived array.  Built
    once in ``FederatedExperiment.__init__`` (``self.data``, beside
    ``self.state``) and passed as the first, never donated, operand of
    every jitted round program — nothing here is closed over, so the
    lowered program is the same text for every seed and every set of one
    shape, and its compile-cache entry holds code only (PERF.md section
    6, PR 34).  A field is None — an empty pytree, no operand — where
    the configuration has no such array."""
    train_x: Any = None      # (N, F) f32 rows; None under host_stream
    train_y: Any = None      # (N,) int32
    shards: Any = None       # (n, L) int32 client -> sample ids
    part_key: Any = None     # the --participation cohort draw
    style: Any = None        # femnist_style (a, b), each (n,)
    augment_key: Any = None  # data/augment.py:augment_key
    fault_key: Any = None    # core/faults.py:fault_key
    secagg_key: Any = None   # protocols/secagg.py:secagg_key
    traffic_key: Any = None  # core/population.py:traffic_key
    async_key: Any = None    # core/async_rounds.py:async_key
    latency: Any = None      # async traffic: (m,) latency scales
    meta: Any = None         # FLTrust's trusted pool (meta_x, meta_y)
    defense_key: Any = None  # defenses/dnc.py:sketch_key
    attack: Any = None       # Attack.operands()


class FederatedExperiment:
    @host_span("setup.experiment")
    def __init__(self, cfg: ExperimentConfig, attacker: Optional[Attack] = None,
                 dataset=None, shardings=None):
        self.cfg = cfg
        self.attacker = attacker or NoAttack()
        self.dataset = crop_contexts(
            dataset or load_dataset(cfg.dataset, cfg.data_dir, cfg.seed,
                                    seq_len=cfg.seq_len), cfg.seq_len)
        self.model = get_model(cfg.model)
        self.n = cfg.users_count
        self.f = cfg.corrupted_count
        # Per-round cohort (config.participation): STATIC sizes — round(p·f)
        # malicious + honest remainder — with random identities per round,
        # so jit shapes never change and the rows-[0, m_mal) attack
        # invariant holds.  p=1 degenerates to the reference's
        # everyone-every-round cohort.
        if cfg.participation < 1.0:
            self.m = max(1, int(round(cfg.participation * self.n)))
            self.m_mal = min(int(round(cfg.participation * self.f)), self.m)
            if self.f > 0 and self.m_mal == 0:
                raise ValueError(
                    f"participation={cfg.participation} rounds the "
                    f"malicious cohort to 0 while f={self.f} — the attack "
                    f"would silently never run (static cohorts); raise "
                    f"participation or set mal_prop=0 explicitly")
            if self.m - self.m_mal > self.n - self.f:
                raise ValueError(
                    f"cohort needs {self.m - self.m_mal} honest clients "
                    f"but only {self.n - self.f} exist "
                    f"(n={self.n}, f={self.f}, "
                    f"participation={cfg.participation})")
        else:
            self.m, self.m_mal = self.n, self.f
        # Secure-aggregation protocol layer (protocols/secagg.py;
        # cfg.secagg): 'off' is the reference fiction and leaves the
        # compiled round byte-identical (pinned).  The structural
        # incompatibilities are rejected at config construction
        # (config.py); the one engine-level fact — a non-fusable
        # attacker handed in programmatically — is checked here.
        if cfg.secagg != "off":
            from attacking_federate_learning_tpu.protocols.secagg import (
                secagg_key
            )
            if not getattr(self.attacker, "fusable", True):
                raise ValueError(
                    "--secagg masks inside the fused round program and "
                    "needs a fusable attack (drop --backdoor-staged)")
            self._secagg = cfg.secagg
            self._secagg_key = secagg_key(cfg)
        else:
            self._secagg = None
        # Mesh plan first: the hierarchical init below decides between
        # the sequential megabatch scan and the SPMD client_map from
        # the clients-axis size (ISSUE 12), so the plan must exist
        # before the topology is planned.
        if shardings is None and cfg.mesh_shape is not None:
            from attacking_federate_learning_tpu.parallel.mesh import make_plan
            shardings = make_plan(tuple(cfg.mesh_shape))
        self.shardings = shardings  # parallel.MeshPlan or None (single device)
        # The defense only ever sees the round cohort (flat), one
        # megabatch / the shard-estimate matrix (hierarchical), or the
        # delivered sub-cohort (async).
        self._async = None
        self._hier_spmd = False
        if cfg.aggregation == "hierarchical":
            self._init_hierarchical()
        elif cfg.aggregation == "async":
            self._init_async()
        else:
            self._placement = None
            check_defense_args(cfg.defense, self.m, self.m_mal)
        if (getattr(self.attacker, "timed", False)
                and cfg.aggregation != "async"):
            raise ValueError(
                "a timed attack (attacks/backdoor.py "
                "TimedBackdoorAttack) games the async arrival schedule; "
                "it requires aggregation='async' — under synchronous "
                "topologies there is no arrival time to game")
        # Fault-injection subsystem (core/faults.py): None is the
        # zero-fault reference path — no fault state, no mask threading,
        # the compiled round program is bit-identical to the
        # pre-fault-subsystem one.
        if cfg.faults is not None and cfg.faults.enabled:
            from attacking_federate_learning_tpu.core.faults import (
                check_fault_support, fault_key
            )
            check_fault_support(cfg)
            self.faults = cfg.faults
            self._fault_key = fault_key(cfg)
        else:
            self.faults = None
        # Population & traffic engine (core/population.py): None is the
        # resident-cohort reference path — no registry, no schedule, no
        # arrival mask; the compiled round program is bit-identical to
        # the pre-population one.  The registry is LAZY: it holds
        # scalars only, so engine memory scales with the cohort m
        # however large cfg.traffic.population grows.
        if cfg.traffic is not None and cfg.traffic.enabled:
            from attacking_federate_learning_tpu.core.population import (
                PopulationRegistry, check_traffic_support, traffic_key
            )
            check_traffic_support(cfg)
            self.traffic = cfg.traffic
            self.registry = PopulationRegistry(cfg.traffic, self.n,
                                               self.f, cfg.seed)
            self._traffic_key = traffic_key(cfg)
            self._traffic_events = {}
            if cfg.aggregation not in ("hierarchical", "async"):
                # Ladder step 2: the bounds-valid fallback kernel,
                # ledgered as tier-1 like the configured defense.
                self._traffic_fallback_fn = stage_wrapped(
                    DEFENSES[cfg.traffic.fallback_defense],
                    "tier1_aggregate")
        else:
            self.traffic = None
            self.registry = None
        # Set by the flat _build_round_fns traffic branch only; its
        # None-ness is the run_span/run_round dispatch sentinel (hier
        # traffic is in-program slot resampling, no schedule operands).
        self._traffic_span = None
        self._krum_select_fn = None  # set for Krum (selection telemetry)
        self.last_round_telemetry = None   # cfg.telemetry, per-round modes
        self.last_span_telemetry = None    # cfg.telemetry, fused spans
        self.defense_fn = DEFENSES[cfg.defense]
        if cfg.defense in ("Krum", "Bulyan"):
            self.defense_fn = self._wire_distance_defense(self.defense_fn)
        elif cfg.defense in ("TrimmedMean", "Median"):
            # Opt-in kernel routing (defenses/kernels.py:trimmed_mean
            # explains why the host kernel is not auto-dispatched).
            impl = (cfg.trimmed_mean_impl if cfg.defense == "TrimmedMean"
                    else cfg.median_impl)
            if impl != "xla":
                self.defense_fn = functools.partial(
                    self.defense_fn, impl=impl)
        elif cfg.defense == "DnC":
            # DnC's constants are config surface (the most constant-
            # sensitive defense), and its sketch keys flow from the
            # experiment seed so repeat runs with different seeds draw
            # different coordinate subsets (defenses/dnc.py); the round
            # programs pass the same key as an operand
            # (RoundData.defense_key, _aggregate_impl).
            self.defense_fn = functools.partial(
                self.defense_fn, n_iters=cfg.dnc_iters,
                sketch_dim=cfg.dnc_sketch_dim,
                filter_frac=cfg.dnc_filter_frac, seed=cfg.seed)
            self.defense_fn.needs_round = True  # partial drops attributes
        elif cfg.defense == "GeoMedian":
            # Weiszfeld constants are config surface like the DnC knobs.
            self.defense_fn = functools.partial(
                self.defense_fn, iters=cfg.geomed_iters,
                eps=cfg.geomed_eps)
        elif cfg.defense == "CenteredClip":
            self.defense_fn = functools.partial(
                self.defense_fn, tau=cfg.cclip_tau,
                iters=cfg.cclip_iters)
        # Stage ledger (utils/costs.py): every op the tier-1 kernel
        # traces carries 'tier1_aggregate' metadata whatever the call
        # site (fused round, hier shard_fn, standalone cost entries).
        self.defense_fn = stage_wrapped(self.defense_fn,
                                        "tier1_aggregate")

        with host_span("setup.model_init"):
            key = jax.random.key(cfg.seed)
            k_init, self.key_run = jax.random.split(key)
            params0 = self.model.init(k_init)
            self.flat = make_flattener(params0)
            self.state = init_server_state(self.flat.ravel(params0))
            if self.faults is not None and self._async is None:
                # Async rounds model stragglers as extra arrival delay
                # inside their own buffers (core/async_rounds.py) — the
                # sync fault ring never exists there.
                from attacking_federate_learning_tpu.core.faults import (
                    init_fault_state, init_hier_fault_state
                )
                if self._placement is not None:
                    # Hier ring: one (m, d) slab per shard per delay slot
                    # (same total bytes as the flat full-participation
                    # ring; empty pytree when stragglers are off).
                    self._fault_state = init_hier_fault_state(
                        self.faults, self._placement.num_shards,
                        self._placement.megabatch, self.flat.dim)
                else:
                    self._fault_state = init_fault_state(
                        self.faults, self.m, self.flat.dim)
            else:
                self._fault_state = None
            if self._async is not None:
                from attacking_federate_learning_tpu.core.async_rounds import (
                    init_async_state
                )
                self._async_state = init_async_state(self._async, self.m,
                                                     self.flat.dim)
            else:
                self._async_state = None

        with host_span("setup.partition"):
            shards = make_shards(cfg.partition, self.dataset.train_y, self.n,
                                 cfg.seed, cfg.dirichlet_alpha)
        self._streaming = cfg.data_placement == "host_stream"
        self._shards_host = shards    # collect_metadata reads the host's
        train_x = train_y = dev_shards = None
        with host_span("setup.place_data"):
            if self._streaming:
                # Beyond-HBM mode (SURVEY.md §7.3 #5): the training set stays
                # in host RAM; per-round batches are host-gathered and
                # double-buffered onto the device (data/stream.py).
                from attacking_federate_learning_tpu.data.stream import (
                    HostStream
                )
                self.stream = HostStream(self.dataset.train_x,
                                         self.dataset.train_y, shards,
                                         cfg.batch_size * cfg.local_steps,
                                         plan=shardings, n_rounds=cfg.epochs,
                                         participants_fn=(
                                             self._participants_host),
                                         cohort_rows=self.m,
                                         prefetch=cfg.stream_prefetch,
                                         workers=cfg.stream_workers)
                if shardings is not None:
                    self.state = shardings.place_state(self.state)
            else:
                dev_shards = jnp.asarray(shards)
                # Row-contiguous storage: one sample = one (F,) row, the
                # feature axis minor, so the batch gather moves whole
                # rows (_gather_batches restores the sample shape).  A
                # zero-copy view of the host array.
                train_x = jnp.asarray(self.dataset.train_x.reshape(
                    len(self.dataset.train_x), -1))
                train_y = jnp.asarray(self.dataset.train_y)
                if shardings is not None:
                    dev_shards, train_x, train_y, self.state = (
                        shardings.place(dev_shards, train_x,
                                        train_y, self.state,
                                        replicate_shards=self._hier_spmd))

        # FEMNIST-style feature shift (SURVEY §7.2 M4): each client sees
        # the shared pool through its own affine transform a_i*x + b_i
        # (data/partition.py client_style_params).  Raw-data consumers,
        # deliberately: the global test set stays untransformed
        # (accuracy is measured on the common distribution) and the
        # backdoor attacker's shadow train reads the raw dataset (the
        # attacker controls its own pipeline).  Styled consumers: the
        # training batches below AND the metadata pool (collect_metadata
        # applies each contributor's transform — those samples model the
        # client's own view).
        if cfg.partition == "femnist_style":
            from attacking_federate_learning_tpu.data.partition import (
                client_style_params
            )
            a_sty, b_sty = client_style_params(self.n, cfg.style_strength,
                                               cfg.seed)
            self._style = (jnp.asarray(a_sty), jnp.asarray(b_sty))
        else:
            self._style = None

        # Reference parity: augmentation is part of the CIFAR100 train
        # pipeline only (reference data_sets.py:157-166); image-shaped
        # data required (the MNIST wire is flat).
        self._augment = (cfg.data_augment if cfg.data_augment is not None
                         else cfg.dataset == "CIFAR100")
        if self._augment and np.ndim(self.dataset.train_x) != 4:
            raise ValueError(
                f"data_augment needs (N, C, H, W) images, got "
                f"shape {np.shape(self.dataset.train_x)} for {cfg.dataset}")
        self._grad_dtype = jnp.dtype(cfg.grad_dtype)
        # A flat cohort on one device whose f32 gradients do not fit
        # beside the wire is scanned client by client into the wire
        # (core/client.py); read from n, d and the device, no option.  A
        # sequence model's client step (its own loss: grouped products
        # and loops of traced length) cannot be vmapped at all.
        limit = (jax.local_devices()[0].memory_stats() or {}).get(
            "bytes_limit")
        one_device = cfg.aggregation == "flat" and shardings is None
        if self.model.loss is not None and not one_device:
            raise ValueError(
                f"model {cfg.model!r} steps its clients one at a time "
                f"(models/sequence.py): flat aggregation on one device "
                f"only, no mesh")
        self._scan_clients = one_device and (
            self.model.loss is not None
            or not cohort_fits(self.m, self.flat.dim, self._grad_dtype,
                               limit))
        self._client_update = make_client_update_fn(
            self.model, self.flat, cfg.local_steps, remat=cfg.remat,
            scan_dtype=self._grad_dtype if self._scan_clients else None)
        self._needs_server_grad = getattr(self.defense_fn,
                                          "needs_server_grad", False)
        self.metadata = (self.collect_metadata()
                         if (cfg.collect_metadata
                             or self._needs_server_grad) else None)
        # Everything a round program reads and never writes, as ONE
        # operand pytree (RoundData): the set and the shards placed
        # above, and each subsystem's seed-derived arrays.  The host
        # planners (_fault_plan, the traffic registry) keep their own
        # handles on the same keys.
        latency = None
        if self._async is not None and self.traffic is not None:
            # Async traffic = latency-profile delivery: per-cohort-slot
            # heavy-tail Pareto scales (materialized lazily from the
            # population registry, never a (P,) tensor) replace the
            # uniform 0..D arrival draw inside the ring
            # (core/async_rounds.py:draw_delays).  The (m,) scales are
            # the seed's, the tail is a config constant.
            from attacking_federate_learning_tpu.core.population import (
                async_latency_for_cfg
            )
            latency, self._latency_tail = async_latency_for_cfg(cfg, self.m)
        self.data = RoundData(
            train_x=train_x, train_y=train_y, shards=dev_shards,
            part_key=(jax.random.key(cfg.seed ^ 0x9A47)
                      if cfg.participation < 1.0 else None),
            style=self._style,
            augment_key=augment_key(cfg.seed) if self._augment else None,
            fault_key=self._fault_key if self.faults is not None else None,
            secagg_key=(self._secagg_key if self._secagg is not None
                        else None),
            traffic_key=(self._traffic_key if self.traffic is not None
                         else None),
            async_key=(self._async_key if self._async is not None
                       else None),
            latency=latency,
            # Validation-data defense (FLTrust): the server's own gradient
            # on the trusted metadata pool provides the trust anchor.
            meta=((jnp.asarray(self.metadata[0]),
                   jnp.asarray(self.metadata[1]))
                  if self._needs_server_grad else None),
            defense_key=(sketch_key(cfg.seed) if cfg.defense == "DnC"
                         else None),
            attack=self.attacker.operands())
        with host_span("setup.build_round_fns"):
            self._build_round_fns()
            self.evaluate = make_eval_fn(
                self.model, self.flat, self.dataset.test_x,
                self.dataset.test_y, cfg.batch_size)

    # ------------------------------------------------------------------
    def _init_hierarchical(self):
        """Validate + plan the two-tier streaming round (ISSUE 6 /
        ROADMAP item 1; ops/federated.py, ARCHITECTURE.md "Hierarchical
        aggregation").

        The client axis lives inside a scanned device program, so every
        feature that needs the materialized (n, d) matrix — or a host
        hop per round — is rejected here rather than failing deep in a
        trace: partial participation (cohort sampling composes with
        placement in a follow-up), host streaming (one round per
        program by design), and the opt-in host kernels (a
        pure_callback per megabatch per scan step would marshal more
        than it saves).  Telemetry and round-stats are SUPPORTED
        (ISSUE 8): per-shard tier-1 diagnostics ride the scan as
        stacked fixed-shape pytrees — (S, m)-shaped, never
        (n,)-shaped, so the O(m·d) memory contract survives — and the
        tier-2 kernels emit their (S,)-shaped shard-selection record
        ('shard_selection' events, schema v6).  Fault injection is
        SUPPORTED (ISSUE 19): the per-client draw becomes a per-shard
        (m,) quarantine mask inside the scan step (mask-aware tier-1
        kernels unchanged), the straggler ring grows a shard axis
        ((delay, S, m, d) — sequential scan only,
        core/faults.py:check_fault_support rejects straggler ⊕ SPMD),
        and the correlated shard-DOMAIN axis (--fault-shard-dropout)
        kills whole megabatches at once, excluded at tier-2 via the
        alive_counts seam with a host-planned remask → fallback →
        hold ladder on the surviving-shard count."""
        cfg = self.cfg
        from attacking_federate_learning_tpu.defenses.kernels import (
            TIER2_DEFENSES, check_tier2_args
        )
        from attacking_federate_learning_tpu.ops.federated import (
            check_hier_support, make_placement, tier1_assumed,
            tier2_assumed
        )

        check_hier_support(cfg)

        self._placement = make_placement(self.n, self.f, cfg.megabatch,
                                         cfg.mal_placement)
        # SPMD tier-1 (ISSUE 12): a mesh whose clients axis holds > 1
        # device maps the megabatch axis onto it — each device scans
        # its own megabatches, tier-2 reads one explicit all_gather.
        # The schedule is validated NOW (S % clients axis, loudly)
        # rather than deep in a trace; a 1-device clients axis keeps
        # the sequential scan, byte-identical HLO included.
        if self.shardings is not None:
            from attacking_federate_learning_tpu.ops.federated import (
                spmd_schedule
            )
            from attacking_federate_learning_tpu.parallel.mesh import (
                CLIENTS
            )

            parts = self.shardings.mesh.shape[CLIENTS]
            if parts > 1:
                spmd_schedule(self._placement, parts)
                self._hier_spmd = True
        S = self._placement.num_shards
        self._tier1_f = (cfg.tier1_corrupted
                         if cfg.tier1_corrupted is not None
                         else tier1_assumed(self.f, S))
        self._tier2_f = (cfg.tier2_corrupted
                         if cfg.tier2_corrupted is not None
                         else tier2_assumed(self.f, cfg.megabatch))
        self._tier2_name = cfg.tier2_defense or cfg.defense
        # Same validity bounds per tier that the flat path checks once.
        check_tier2_args(cfg.defense, cfg.megabatch, self._tier1_f)
        check_tier2_args(self._tier2_name, S, self._tier2_f)
        # Stage ledger: the tier-2 shard reduction carries its own
        # ledger stage, distinct from the per-shard tier-1 kernel.
        self._tier2_fn = stage_wrapped(TIER2_DEFENSES[self._tier2_name],
                                       "tier2_aggregate")

    # ------------------------------------------------------------------
    def _init_async(self):
        """Validate + plan the FedBuff-style buffered round (ISSUE 9 /
        ROADMAP item 4; core/async_rounds.py, ARCHITECTURE.md
        "Asynchronous rounds").

        Arrival, buffering and staleness weighting all live inside the
        fused round program, so everything that needs a host hop per
        round — or a defense without the mask/weight seam — is
        rejected here, loudly, rather than failing deep in a trace:
        staged attacks, host kernels, partial participation (the ring
        and pending pool are indexed by cohort row), host streaming.
        secagg ⊕ async is structurally rejected at config time
        (vanilla requires flat, groupwise requires hierarchical).
        Faults COMPOSE: dropout = the update is never submitted,
        straggler = extra arrival delay, corrupt = damage in flight
        (core/async_rounds.py:draw_delays)."""
        cfg = self.cfg
        from attacking_federate_learning_tpu.core.async_rounds import (
            AsyncSpec, async_key, check_async_support
        )

        check_async_support(cfg)
        if not getattr(self.attacker, "fusable", True):
            raise ValueError(
                "--aggregation async needs a fusable attack: delivery, "
                "staleness weighting and the attack seam live inside "
                "the fused round program")
        self._placement = None
        if cfg.async_buffer > self.m:
            raise ValueError(
                f"--async-buffer {cfg.async_buffer} exceeds the cohort "
                f"(m={self.m}): the FedBuff trigger would never fire — "
                f"the pending pool holds at most one update per client")
        # A delivered async round aggregates EXACTLY k rows (the
        # FedBuff trigger), so the defense validity bounds apply at
        # n=k with the full f colluders assumed delivered — the
        # worst-case cohort a timed attack can arrange.
        try:
            check_defense_args(cfg.defense, cfg.async_buffer, self.m_mal)
        except ValueError as e:
            raise ValueError(
                f"--aggregation async aggregates exactly "
                f"k=--async-buffer rows per applied round, so the "
                f"defense bound applies at n=k: {e}") from e
        if (cfg.defense == "TrimmedMean"
                and cfg.async_buffer - self.m_mal - 1 < 1):
            raise ValueError(
                f"--aggregation async TrimmedMean keeps "
                f"k - f - 1 rows per applied round; got "
                f"k={cfg.async_buffer}, f={self.m_mal} — raise "
                f"--async-buffer")
        self._async = AsyncSpec(
            buffer=cfg.async_buffer,
            max_staleness=cfg.async_max_staleness,
            weighting=cfg.staleness_weight,
            timed=bool(getattr(self.attacker, "timed", False)))
        self._async_key = async_key(cfg)

    # ------------------------------------------------------------------
    def _wire_distance_defense(self, fn):
        """Bind scoring/distance-engine knobs onto a Krum/Bulyan kernel.

        'auto' stays UNRESOLVED in the wired partial: the kernels resolve
        it per call (defenses/kernels.py:resolve_distance_impl) — 'xla'
        for traced operands (a host round-trip inside the fused round
        program would pay a pure_callback marshal of the whole (n, d)
        matrix every round), and host BLAS for eager CPU-backend calls,
        which is exactly what the staged path's eager aggregation feeds
        it (_build_round_fns).  'ring'/'allgather' precompute the
        distance matrix with the blockwise shard_map kernels
        (parallel/distances.py) over the clients mesh axis and hand it
        to the kernel via its ``D=`` seam."""
        from attacking_federate_learning_tpu.defenses.kernels import (
            krum_select
        )

        cfg = self.cfg
        kw = {"method": cfg.krum_scoring_method}
        if cfg.krum_paper_scoring:
            kw["paper_scoring"] = True
        if cfg.distance_dtype != "float32":
            kw["distance_dtype"] = cfg.distance_dtype
        if cfg.defense == "Bulyan":
            if cfg.bulyan_batch_select != 1:
                kw["batch_select"] = cfg.bulyan_batch_select
            if cfg.bulyan_selection_impl != "xla":
                # 'host': hybrid exact selection — device distances, one
                # (n, n) D marshal, native host selection, device
                # trim-mean.
                kw["selection_impl"] = cfg.bulyan_selection_impl
            if cfg.bulyan_trim_impl != "xla":
                kw["trim_impl"] = cfg.bulyan_trim_impl
        impl = cfg.distance_impl
        if impl in ("ring", "allgather"):
            if self.shardings is None:
                raise ValueError(
                    f"distance_impl={impl!r} needs a device mesh — set "
                    f"mesh_shape (parallel/distances.py kernels are "
                    f"shard_map programs over the clients axis)")
            from attacking_federate_learning_tpu.parallel.distances import (
                pairwise_distances_allgather, pairwise_distances_ring
            )
            from attacking_federate_learning_tpu.parallel.mesh import CLIENTS
            dist_fn = {"ring": pairwise_distances_ring,
                       "allgather": pairwise_distances_allgather}[impl]
            mesh = self.shardings.mesh
            p = mesh.shape[CLIENTS]
            if self.m % p != 0:
                # shard_map's P('clients', None) in_spec needs even rows —
                # the kernels see the round cohort (m), not the population
                # (unlike the xla path, where GSPMD pads unevenly).
                raise ValueError(
                    f"distance_impl={impl!r} needs the round cohort "
                    f"divisible by the clients mesh axis (m={self.m}, "
                    f"axis={p})")

            # Blockwise tiles share cross_sq_distances, so bf16 operands
            # ride the MXU inside the shard_map too (f32 accumulation).
            dist_dtype = jnp.dtype(cfg.distance_dtype)

            def with_blockwise_D(grads, n, f, _fn=fn, **extra):
                extra.pop("distance_dtype", None)  # D is precomputed
                D = dist_fn(grads.astype(dist_dtype), mesh)
                return _fn(grads, n, f, D=D, **extra)

            if cfg.defense == "Krum":
                self._krum_select_fn = functools.partial(
                    with_blockwise_D, _fn=krum_select, **kw)
            return functools.partial(with_blockwise_D, **kw)
        kw["distance_impl"] = impl
        if cfg.defense == "Krum":
            # Selection telemetry shares the defense's exact knobs, so the
            # reported winner IS the aggregated client (round_diagnostics).
            self._krum_select_fn = functools.partial(krum_select, **kw)
        return functools.partial(fn, **kw)

    # ------------------------------------------------------------------
    def collect_metadata(self):
        """Metadata subsystem (reference C12, SURVEY.md §2 — vestigial
        there): every client contributes a stratified ~metadata_fraction
        sample of its first batch (reference user.py:63-66,
        train_test_split(test_size=0.11, stratify=y)); the server
        concatenates them (server.py:62-77).  Returns (meta_x, meta_y) —
        the validation pool a FLTrust/Zeno-style defense can consume."""
        cfg = self.cfg
        shards = self._shards_host
        xs = np.asarray(self.dataset.train_x)
        ys = np.asarray(self.dataset.train_y)
        rng = np.random.default_rng(cfg.seed + 42)
        meta_x, meta_y = [], []
        for i in range(self.n):
            batch = shards[i, : cfg.batch_size]
            labels = ys[batch]
            take = max(1, int(round(cfg.metadata_fraction * len(batch))))
            # Stratified: sample each label proportionally.
            picked = []
            for c in np.unique(labels):
                pool = batch[labels == c]
                k = max(1, int(round(take * len(pool) / len(batch))))
                picked.extend(rng.choice(pool, size=min(k, len(pool)),
                                         replace=False).tolist())
            picked = np.asarray(picked[:take], np.int64)
            x_i = xs[picked]
            if self._style is not None:
                # Contributed samples are the client's OWN view of the
                # data: under femnist_style they carry that client's
                # a_i*x + b_i transform, exactly like its training
                # inputs — otherwise a FLTrust-style consumer would
                # score honest styled gradients against an unstyled
                # reference distribution no client actually has.
                a, b = self._style
                x_i = np.float32(a[i]) * x_i + np.float32(b[i])
            meta_x.append(x_i)
            meta_y.append(ys[picked])
        return np.concatenate(meta_x), np.concatenate(meta_y)

    def get_metadata(self):
        """Reference server.get_MetaData (server.py:58-59)."""
        return self.metadata

    # ------------------------------------------------------------------
    def _maybe_augment(self, data, xs, t):
        """In-program train-time augmentation where the reference pipeline
        has one (CIFAR100, data/augment.py)."""
        if self._augment:
            xs = reflect_crop_flip(
                xs, jax.random.fold_in(data.augment_key, t))
        return xs

    @staticmethod
    def _apply_style(data, xs, participants):
        """Per-client affine style transform ('femnist_style' partition):
        row i of the cohort batch becomes a_i*xs_i + b_i — one fused
        broadcast multiply-add inside the round program, so the feature
        shift costs nothing extra on device."""
        if data.style is None:
            return xs
        a, b = data.style
        if participants is not None:
            a, b = a[participants], b[participants]
        shape = (xs.shape[0],) + (1,) * (xs.ndim - 1)
        return a.reshape(shape) * xs + b.reshape(shape)

    def _participants(self, data, t):
        """Round-t cohort ids, or None under full participation: the
        first m_mal entries are malicious ids (< f), the rest honest —
        random identities, static counts (config.participation).  The
        draw itself lives in core/population.py:legacy_cohort — the
        population sampler's uniform-reliability compat profile,
        relocated verbatim so it stays bit-compatible with every
        pre-population run (tests/test_traffic.py pins it)."""
        if self.cfg.participation >= 1.0:
            return None
        from attacking_federate_learning_tpu.core.population import (
            legacy_cohort
        )
        return legacy_cohort(data.part_key, t, self.n, self.f, self.m,
                             self.m_mal)

    def _participants_host(self, t):
        """Eager host-side cohort for the streaming prefetcher: jax's RNG
        is platform-invariant, so running the same derivation on the CPU
        backend yields exactly the traced path's ids without queueing a
        tiny program behind the accelerator's in-flight round."""
        if self.cfg.participation >= 1.0:
            return None
        try:
            cpu = jax.devices("cpu")[0]
        except RuntimeError:
            return np.asarray(self._participants(self.data, t))
        with jax.default_device(cpu):
            return np.asarray(self._participants(self.data, t))

    def _gather_batches(self, data, t, participants=None):
        """Round-t minibatches for the round cohort (or the megabatch
        whose client ids are ``participants``): one (m, k*B) row gather
        from the device-resident dataset (replaces the reference's N
        host-side DataLoaders, user.py:52-55); k = local_steps (1 in the
        reference's FedSGD regime).

        The set is stored (N, F), one sample a row, and the sample shape
        comes back only AFTER the gather: gathered as (N, C, H, W) the
        TPU compiler pushes the first matmul's / convolution's operand
        layout — sample axis minor — back through the gather, which then
        moves the batch element by element (228 ms a round at n=10,240
        against 8.6 ms of whole rows; PERF.md section 6, PR 26)."""
        shards = (data.shards if participants is None
                  else data.shards[participants])
        idx = round_batch_indices(
            shards, t, self.cfg.batch_size * self.cfg.local_steps)
        xs = data.train_x[idx].reshape(
            idx.shape + self.dataset.train_x.shape[1:])
        return xs, data.train_y[idx]

    def _split_local_steps(self, data, xs, ys, participants, t):
        """Style, augmentation, then the flat (m, k*B) batch split into
        k local-step minibatches — the tail of the ``gather`` sub-stage,
        shared by the flat cohort and the hierarchical megabatch."""
        xs = self._apply_style(data, xs, participants)
        xs = self._maybe_augment(data, xs, t)
        k, B = self.cfg.local_steps, self.cfg.batch_size
        xs = xs.reshape((xs.shape[0], k, B) + xs.shape[2:])
        return xs, ys.reshape((ys.shape[0], k, B) + ys.shape[2:])

    def _compute_grads_impl(self, state: ServerState, t, batches=None,
                            part=None, data=None):
        """``data``: the round program's RoundData operand; None (a
        caller outside the engine's own programs — the benchmark's
        deliver span) binds ``self.data``.

        batches=None gathers from the device-resident dataset; the
        host-streaming mode (cfg.data_placement='host_stream') passes the
        round's pre-transferred (xs, ys) instead.  ``part`` pre-empts
        the participation draw with explicit (m,) cohort ids — the
        traffic engine's host-sampled shard archetypes (a population
        client materializes as its archetype's data shard + style;
        core/population.py).

        Stage ledger: everything here is the ``deliver`` stage — batch
        delivery (sub-stage ``gather``) + client update (``client_step``),
        the cohort's gradients arriving at tier 1 (utils/costs.py:STAGES
        / SUBSTAGES; metadata-only annotation)."""
        cfg = self.cfg
        if data is None:
            data = self.data
        with stage_scope("deliver"):
            with stage_scope("gather"):
                if batches is None:
                    if part is None:
                        part = self._participants(data, t)
                    xs, ys = self._gather_batches(data, t, part)
                else:
                    xs, ys = batches
                    # The streaming prefetcher derives the identical
                    # cohort ids (platform-invariant RNG,
                    # _participants_host), so re-deriving here keeps the
                    # style rows aligned with the streamed batch.
                    part = (self._participants(data, t)
                            if data.style is not None else None)
                xs, ys = self._split_local_steps(data, xs, ys, part, t)
            # Clients train at the faded lr the server dispatches
            # (reference server.py:50-52; inert at k=1, user.py:80); the
            # pseudo-gradient divides by the lr the server will multiply
            # back in so the FedAvg reduction is exact under the
            # constant-server-lr quirk.
            lr_train = faded_learning_rate(cfg.learning_rate,
                                           cfg.fading_rate, t)
            lr_report = (lr_train if cfg.server_uses_faded_lr
                         else cfg.learning_rate)
            with stage_scope("client_step"):
                grads = self._client_update(state.weights, xs, ys,
                                            lr_train, lr_report)
                grads = grads.astype(self._grad_dtype)  # bf16 halves HBM
            if self.shardings is not None:
                grads = self.shardings.constrain_grads(grads)
        return grads

    def _aggregate_impl(self, data, state: ServerState, grads, t,
                        agg=None, telemetry=False, margins=False,
                        numerics=False, mask=None, weights=None,
                        action=None):
        """``data``: the program's RoundData operand (FLTrust's trusted
        pool and DnC's sketch key live there).
        ``agg`` pre-empts the defense call — the Krum-telemetry round
        computes the selection once and aggregates ``grads[sel]`` rather
        than running the O(n^2 d) distance engine twice.  ``telemetry``
        (static bool) asks the defense for its diagnostics pytree and
        returns ``(new_state, diag)`` instead of ``new_state``.
        ``mask``: the quarantine effective-cohort mask (core/faults.py),
        threaded into the mask-aware defense kernels; None (the
        no-fault path) leaves the defense call byte-identical.
        ``weights``: the async staleness weights riding the same seam
        (core/async_rounds.py; requires ``mask``).
        ``action``: the traffic watchdog's per-round ladder decision
        (core/population.py, () int32).  Both the configured defense and
        the bounds-valid fallback are always computed and jnp.where
        selects — identical pytree either way, and a NaN in the
        unselected branch cannot propagate through the select.  HOLD is
        applied at the state level after the update (FedBuff-style
        no-op, the async empty-delivery pattern)."""
        ddiag = {}
        if agg is None:
            # Stage ledger: the defense kernel (server_grad included —
            # FLTrust's trust anchor is part of the tier-1 decision) is
            # the ``tier1_aggregate`` stage.
            with stage_scope("tier1_aggregate"):
                kw = {}
                if mask is not None:
                    kw["mask"] = mask
                if weights is not None:
                    kw["weights"] = weights
                if getattr(self.defense_fn, "needs_round", False):
                    # Round-seeded defenses (DnC's fresh sketches) — the
                    # same attribute seam FLTrust uses for
                    # needs_server_grad.
                    kw["round"] = t
                    if data.defense_key is not None:
                        kw["key"] = data.defense_key
                if self._needs_server_grad:
                    server_grad = jax.grad(
                        make_loss_fn(self.model, self.flat))(
                        state.weights, *data.meta)
                    kw["server_grad"] = server_grad
                if telemetry:
                    if margins:
                        # Trace-time flag like telemetry itself; only
                        # the margin-bearing kernels accept it (config
                        # gates --margins to exactly those), so the
                        # kwarg is only ever passed when True.
                        kw["margins"] = True
                    if numerics:
                        # Kernel tie/cancellation counters ride the
                        # margin tensors (check_numerics_seam) — the
                        # engine passes margins=True alongside and
                        # filters margin fields back out when
                        # --margins itself is off.
                        kw["numerics"] = True
                    agg, ddiag = self.defense_fn(
                        grads, self.m, self.m_mal, telemetry=True, **kw)
                else:
                    agg = self.defense_fn(grads, self.m, self.m_mal, **kw)
                if action is not None:
                    from attacking_federate_learning_tpu.core.population \
                        import TRAFFIC_FALLBACK
                    fb_kw = {k: kw[k] for k in ("mask", "weights")
                             if k in kw}
                    fb = self._traffic_fallback_fn(
                        grads, self.m, self.m_mal, **fb_kw)
                    agg = jnp.where(action == TRAFFIC_FALLBACK, fb, agg)
        with stage_scope("apply"):
            agg = agg.astype(jnp.float32)
            if self.cfg.server_uses_faded_lr:
                lr = faded_learning_rate(self.cfg.learning_rate,
                                         self.cfg.fading_rate, t)
            else:
                # Reference parity: constant base lr on the server
                # (server.py:89, SURVEY.md §2.4 #7).
                lr = self.cfg.learning_rate
            new_state = momentum_update(state, agg, lr, self.cfg.momentum)
            if action is not None:
                from attacking_federate_learning_tpu.core.population \
                    import TRAFFIC_HOLD
                hold = action == TRAFFIC_HOLD
                new_state = ServerState(
                    weights=jnp.where(hold, state.weights,
                                      new_state.weights),
                    velocity=jnp.where(hold, state.velocity,
                                       new_state.velocity),
                    round=new_state.round)
        if telemetry:
            return new_state, ddiag
        return new_state

    def _build_round_fns(self):
        cfg = self.cfg
        if cfg.aggregation == "hierarchical":
            return self._build_hier_round_fns()
        if cfg.aggregation == "async":
            return self._build_async_round_fns()

        def ctx_for(state, t, data=None):
            # data=None: a caller outside the round programs (the staged
            # host seam, the benchmark's deliver span) gets self.data's.
            return AttackContext(
                original_params=state.weights,
                learning_rate=faded_learning_rate(
                    cfg.learning_rate, cfg.fading_rate, t),
                round=t,
                operands=(self.data if data is None else data).attack)

        self._ctx_for = ctx_for  # single construction site for the seam

        def round_diagnostics(grads, state_after, t, aux=None):
            """Per-round stats (SURVEY.md §5 rebuild item): client gradient
            norm spread, aggregate step norm, faded lr — plus, under Krum,
            which client won selection and whether it was malicious (the
            selection-histogram observability the reference lacks; ``aux``
            carries the selection the defense actually made).  Stage
            ledger: these riders observe the applied update — ``apply``."""
            with stage_scope("apply"):
                norms = jnp.linalg.norm(grads.astype(jnp.float32), axis=1)
                diag = {
                    "grad_norm_mean": jnp.mean(norms),
                    "grad_norm_max": jnp.max(norms),
                    "grad_norm_min": jnp.min(norms),
                    "update_norm": jnp.linalg.norm(state_after.velocity),
                    "faded_lr": faded_learning_rate(cfg.learning_rate,
                                                    cfg.fading_rate, t),
                }
                if aux and "krum_selected" in aux:
                    sel = aux["krum_selected"]
                    diag["krum_selected"] = sel
                    diag["malicious_selected"] = (sel < self.m_mal).astype(
                        jnp.int32)
            return diag

        self._round_diagnostics = round_diagnostics

        # In-program replacement for the reference's host-side shadow-train
        # nan guard (backdoor.py:145-152): track non-finiteness over the
        # crafted rows only (rows [0, f)) — matching the staged path's
        # isfinite check, which is strictly stronger than the reference's
        # isnan — so a diverging *server* update can't be misattributed to
        # the attack.  Skipped when no crafting happens (f == 0 or z == 0,
        # mirroring the reference's early returns, malicious.py:11, :21).
        # Fused spans surface the flag at the next host boundary (the
        # documented detection-latency trade, PARITY.md); --backdoor-staged
        # restores the per-round raise.
        self._check_attack_nan = (
            getattr(self.attacker, "checks_finite", False)
            and self.m_mal > 0
            and getattr(self.attacker, "num_std", 1) != 0)

        # Selection telemetry: compute the Krum winner ONCE and aggregate
        # grads[sel] (krum == grads[krum_select], defenses/kernels.py) —
        # the O(n^2 d) distance engine never runs twice per round.  With
        # full telemetry on, the defense itself returns its selection
        # mask from the same single distance computation, so the
        # pre-emption is unnecessary there.  Under fault injection the
        # pre-emption is off too: the selection depends on the
        # quarantine mask, and only the defense call carries it.
        diag_select = (self._krum_select_fn
                       if (cfg.log_round_stats and not cfg.telemetry
                           and not cfg.margins and not cfg.numerics
                           and self.faults is None
                           and self.traffic is None)
                       else None)

        # Kernel-side numerics (ISSUE 20): the tie/cancellation
        # counters band the margin tensors, so they exist only for the
        # margin-bearing defenses; the engine-level health counters
        # (nonfinite by stage, norm dynamic range) are defense-agnostic
        # and keyed off cfg.numerics alone.
        kernel_num = bool(cfg.numerics and cfg.defense in
                          ("Krum", "TrimmedMean", "Median", "Bulyan"))
        self._kernel_numerics = kernel_num

        def inject_and_quarantine(data, grads, t, fstate):
            """Fault seam (core/faults.py): inject the round-t faults
            into the submitted matrix, then mask/zero what the server
            can detect.  Returns the aggregable matrix, the effective-
            cohort mask, the new fault state and the per-round counts
            (fixed-shape scalars, keyed ``fault_*`` so they ride the
            telemetry plumbing into 'fault' events)."""
            from attacking_federate_learning_tpu.core.faults import (
                apply_faults, quarantine
            )
            with stage_scope("quarantine"):
                submitted, dropped, fstate2, fstats = apply_faults(
                    grads, t, data.fault_key, fstate, self.faults,
                    self.m_mal)
                clean, mask, qstats = quarantine(submitted, dropped)
            return clean, mask, fstate2, {**fstats, **qstats}

        self._inject_and_quarantine = inject_and_quarantine

        def attack_envelope(data, grads, state, t):
            """Pre-attack envelope stats (attacks/base.py seam), keyed
            ``attack_*`` into the telemetry pytree.  Stage ledger:
            observes the delivered/crafted matrix — ``deliver``."""
            with stage_scope("deliver"):
                stats = self.attacker.envelope_stats(
                    grads, self.m_mal, ctx_for(state, t, data))
            return {"attack_" + k: v for k, v in stats.items()}

        def attack_margins(data, pre, post, state, t):
            """Attack-side envelope utilization (attacks/base.py
            margin_stats; cfg.margins): computed on the PRE-attack
            matrix with the POST-attack (crafted) matrix riding along,
            keyed ``margin_attack_*`` so the emitter routes it into the
            'margin' event.  Stage ledger: ``deliver``."""
            with stage_scope("deliver"):
                stats = self.attacker.margin_stats(
                    pre, self.m_mal, ctx_for(state, t, data),
                    crafted=post)
            return {"margin_attack_" + k: v for k, v in stats.items()}

        def finish_telemetry(tele, grads, ddiag):
            """Merge defense diagnostics + population stats into the
            round's telemetry pytree (all fixed-shape device arrays).
            Under margins-without-telemetry only the ``margin_*``
            defense fields ride out (``ddiag`` itself is untouched —
            the krum_selected aux still reads its selection mask).
            Stage ledger: defense forensics — ``tier1_aggregate``."""
            from attacking_federate_learning_tpu.defenses.kernels import (
                population_telemetry
            )
            with stage_scope("tier1_aggregate"):
                for k, v in ddiag.items():
                    # Three-way filter: margin fields ride iff
                    # --margins, num_ fields iff --numerics, the rest
                    # iff full telemetry — so a numerics-only run's
                    # margin carriers (check_numerics_seam forces the
                    # margins kwarg on) are dropped here and DCE'd out
                    # of the trace, and vice versa.
                    if k.startswith("margin_"):
                        if cfg.margins:
                            tele["defense_" + k] = v
                    elif k.startswith("num_"):
                        if cfg.numerics:
                            tele["defense_" + k] = v
                    elif cfg.telemetry:
                        tele["defense_" + k] = v
                if cfg.telemetry:
                    tele.update(population_telemetry(grads))
            return tele

        if self._secagg is not None:
            from attacking_federate_learning_tpu.protocols.secagg import (
                secagg_cohort
            )

            def secagg_step(data, agg_grads, mask, t):
                """Vanilla secure aggregation between the quarantine
                and the (NoDefense-only) aggregation: mask every
                submitted row in the uint32 bitcast domain, then
                recover + verify server-side (protocols/secagg.py).
                The recovered matrix is bit-identical to the clear one
                (dropped rows zeroed either way), so the downstream
                aggregate — and the whole run — is byte-for-byte the
                clear run's; the ``secagg_*`` stats ride the telemetry
                plumbing into per-round 'secagg' events."""
                return secagg_cohort(agg_grads, mask, data.secagg_key, t)

            self._secagg_step = secagg_step

        if getattr(self.attacker, "fusable", True):
            def fused_core(data, state, t, batches=None, fstate=None,
                           traffic=None):
                part = traffic[0] if traffic is not None else None
                grads = self._compute_grads_impl(state, t, batches,
                                                 part=part, data=data)
                tele = (attack_envelope(data, grads, state, t)
                        if cfg.telemetry else {})
                pre_attack = grads if cfg.margins else None
                with stage_scope("deliver"), stage_scope("craft"):
                    # Attack craft happens on the wire: what tier 1
                    # receives IS the crafted matrix.
                    grads = self.attacker.apply(grads, self.m_mal,
                                                ctx_for(state, t, data))
                if cfg.margins:
                    tele = {**tele,
                            **attack_margins(data, pre_attack, grads,
                                             state, t)}
                if cfg.numerics:
                    # Numeric health at the delivery seam: the crafted
                    # wire matrix, before any quarantine can mask a
                    # nonfinite row out of sight (utils/numerics.py).
                    with stage_scope("deliver"):
                        tele = {**tele,
                                "num_nonfinite_pre":
                                    nonfinite_count(grads),
                                "num_range_log2":
                                    norm_dynamic_range(grads)}
                # ``grads`` stays the post-attack, PRE-fault matrix from
                # here on (the nan guard must see what the attacker
                # crafted — a dropout zeroing a malicious row must not
                # hide a shadow-train nan); the defense aggregates the
                # quarantined ``agg_grads``.
                mask, agg_grads = None, grads
                if traffic is not None:
                    # Arrival quarantine: rows whose population client
                    # never arrived this round are zeroed and masked
                    # out of the defense (the same mask-aware seam the
                    # fault quarantine uses, core/population.py).
                    arrived = traffic[1]
                    with stage_scope("quarantine"):
                        agg_grads = jnp.where(
                            arrived[:, None], agg_grads,
                            jnp.zeros_like(agg_grads))
                    mask = arrived
                if self.faults is not None:
                    agg_grads, fmask, fstate, fstats = (
                        inject_and_quarantine(data, agg_grads, t, fstate))
                    mask = fmask if mask is None else (mask & fmask)
                    tele = {**tele, **fstats}
                if self._secagg is not None:
                    agg_grads, sstats = self._secagg_step(
                        data, agg_grads, mask, t)
                    tele = {**tele, **sstats}
                if cfg.numerics:
                    # Post-quarantine: what the defense actually
                    # aggregates (dead rows excluded by the mask).
                    with stage_scope("quarantine"):
                        tele = {**tele, "num_nonfinite_post":
                                nonfinite_count(agg_grads, mask=mask)}
                aux = {}
                act = traffic[2] if traffic is not None else None
                if cfg.telemetry or cfg.margins or kernel_num:
                    new_state, ddiag = self._aggregate_impl(
                        data, state, agg_grads, t, telemetry=True,
                        margins=cfg.margins or kernel_num,
                        numerics=kernel_num, mask=mask, action=act)
                    tele = finish_telemetry(tele, agg_grads, ddiag)
                    if (self._krum_select_fn is not None
                            and "selection_mask" in ddiag):
                        # Krum's mask is one-hot: its argmax IS the
                        # aggregated row (defenses/kernels.py:krum).
                        aux["krum_selected"] = jnp.argmax(
                            ddiag["selection_mask"]).astype(jnp.int32)
                else:
                    agg = None
                    if diag_select is not None:
                        sel = diag_select(grads, self.m, self.m_mal)
                        aux["krum_selected"] = sel
                        agg = grads[sel]
                    new_state = self._aggregate_impl(
                        data, state, agg_grads, t, agg=agg, mask=mask,
                        action=act)
                if cfg.numerics:
                    # Post-apply: a nonfinite velocity is the server
                    # update already poisoned, whatever the cohort
                    # counters said.
                    with stage_scope("apply"):
                        tele = {**tele, "num_nonfinite_agg":
                                nonfinite_count(new_state.velocity)}
                return new_state, grads, aux, tele, fstate

            def crafted_nonfinite(grads):
                with stage_scope("quarantine"):   # the fused nan guard
                    return (~jnp.isfinite(
                        grads[: self.m_mal].astype(jnp.float32))).any()

            if self.traffic is not None:
                def fused(data, state, t, sid, arrived, action,
                          fstate=None):
                    """One traffic round: the host-sampled schedule row
                    (shard ids, arrival mask, ladder action) enters as
                    plain device operands — the compiled program never
                    sees the population, only the (m,) cohort."""
                    new_state, grads, aux, tele, fstate = fused_core(
                        data, state, t, None, fstate,
                        (sid, arrived, action))
                    diag = (round_diagnostics(grads, new_state, t, aux)
                            if cfg.log_round_stats else {})
                    bad = (crafted_nonfinite(grads)
                           if self._check_attack_nan
                           else jnp.asarray(False))
                    return new_state, diag, bad, tele, fstate

                def traffic_span(data, state, t0, count, sids, arrs, acts,
                                 fstate=None):
                    # Traffic span: like fault_span (scan, static count)
                    # but each round consumes its row of the host-
                    # sampled schedule.  The carry threads only the
                    # fault state — the traffic schedule itself is
                    # stateless (pure in (traffic seed, t)), which is
                    # what makes preempt→resume bit-for-bit free.
                    def body(carry, xs):
                        s, bad, fs = carry
                        i, sid, arr, act = xs
                        s2, grads, _, tele, fs = fused_core(
                            data, s, t0 + i, None, fs, (sid, arr, act))
                        if self._check_attack_nan:
                            bad = bad | crafted_nonfinite(grads)
                        return (s2, bad, fs), tele

                    (s, bad, fs), stacked = jax.lax.scan(
                        body, (state, jnp.asarray(False), fstate),
                        (jnp.arange(count), sids, arrs, acts))
                    return s, bad, fs, stacked
            elif self.faults is None:
                def fused(data, state, t, batches=None):
                    new_state, grads, aux, tele, _ = fused_core(
                        data, state, t, batches)
                    diag = (round_diagnostics(grads, new_state, t, aux)
                            if cfg.log_round_stats else {})
                    bad = (crafted_nonfinite(grads)
                           if self._check_attack_nan
                           else jnp.asarray(False))
                    return new_state, diag, bad, tele
            else:
                def fused(data, state, t, fstate, batches=None):
                    new_state, grads, aux, tele, fstate = fused_core(
                        data, state, t, batches, fstate)
                    diag = (round_diagnostics(grads, new_state, t, aux)
                            if cfg.log_round_stats else {})
                    bad = (crafted_nonfinite(grads)
                           if self._check_attack_nan
                           else jnp.asarray(False))
                    return new_state, diag, bad, tele, fstate

            def fused_span(data, state, t0, count):
                # One device program for `count` rounds: steady-state
                # training between evals never returns to the host
                # (the reference makes 3N+2 host->object calls per round,
                # main.py:66-71).  count is a traced operand (fori_loop),
                # so every span length shares one compilation.
                def body(i, carry):
                    s, bad = carry
                    s2, grads, _, _, _ = fused_core(data, s, t0 + i)
                    if self._check_attack_nan:
                        bad = bad | crafted_nonfinite(grads)
                    return s2, bad

                return jax.lax.fori_loop(0, count, body,
                                         (state, jnp.asarray(False)))

            def tele_span(data, state, t0, count):
                # Telemetry span: lax.scan stacks each round's telemetry
                # pytree along a leading round axis, so `count` rounds
                # still run as ONE device program and the host fetches
                # the stack once per eval interval — no callbacks inside
                # the jit.  The stacked output's leading dim forces
                # `count` static (one compilation per distinct span
                # length; the eval cadence yields at most two).
                def body(carry, i):
                    s, bad = carry
                    s2, grads, _, tele, _ = fused_core(data, s, t0 + i)
                    if self._check_attack_nan:
                        bad = bad | crafted_nonfinite(grads)
                    return (s2, bad), tele

                (s, bad), stacked = jax.lax.scan(
                    body, (state, jnp.asarray(False)), jnp.arange(count))
                return s, bad, stacked

            def fault_span(data, state, t0, count, fstate):
                # Fault span: like tele_span (scan, static count, one
                # program per eval/checkpoint interval) but the carry
                # additionally threads the fault state (the straggler
                # ring buffer), and the stacked per-round pytree always
                # carries at least the 'fault_*' counts — fault events
                # are emitted per round whether or not cfg.telemetry.
                def body(carry, i):
                    s, bad, fs = carry
                    s2, grads, _, tele, fs = fused_core(data, s, t0 + i,
                                                        None, fs)
                    if self._check_attack_nan:
                        bad = bad | crafted_nonfinite(grads)
                    return (s2, bad, fs), tele

                (s, bad, fs), stacked = jax.lax.scan(
                    body, (state, jnp.asarray(False), fstate),
                    jnp.arange(count))
                return s, bad, fs, stacked

            donate = self._donate_kw()
            if self.traffic is not None:
                # Traffic paths never donate (the fault-path rationale:
                # stacked-scan outputs + schedule operands add aliasing
                # surface the CPU donation distrust already covers).
                self._fused_round = jax.jit(fused)
                self._traffic_span = jax.jit(traffic_span,
                                             static_argnums=3)
            elif self.faults is None:
                self._fused_round = jax.jit(fused, **donate)
                self._fused_span = jax.jit(fused_span, **donate)
                self._tele_span = jax.jit(tele_span, static_argnums=3,
                                          **donate)
            else:
                # The fault paths never donate (any backend): the fault
                # state rides the carry and the stacked-scan outputs add
                # aliasing surface beyond what _donate_kw's CPU rationale
                # already distrusts.
                self._fused_round = jax.jit(fused)
                self._fault_span = jax.jit(fault_span, static_argnums=3)
            self._staged = False
        else:
            if self.traffic is not None:
                # Config already rejects --backdoor-staged + traffic;
                # this catches a non-fusable attacker handed in
                # programmatically (same seam as the secagg check).
                raise ValueError(
                    "the traffic engine requires a fusable attack (the "
                    "staged host-eager path has no arrival seam)")
            self._compute_grads = jax.jit(
                lambda data, state, t, batches=None:
                self._compute_grads_impl(state, t, batches, data=data))
            # Staged rounds already cross the host boundary every round,
            # so on the CPU backend a Krum/Bulyan aggregation runs EAGERLY:
            # the kernel then sees concrete arrays and 'auto' resolves to
            # the host BLAS engine zero-copy (defenses/host.py) instead of
            # paying XLA:CPU's ~2x gemm penalty inside jit (measured in
            # BASELINE.md).  Everything else keeps the jitted aggregate.
            # (Not under a device mesh: the jitted aggregate preserves the
            # MeshPlan state placement; the eager path would silently
            # un-place state and gather the sharded matrix every round.)
            eager_host_agg = (jax.default_backend() == "cpu"
                              and self.shardings is None
                              and cfg.defense in ("Krum", "Bulyan")
                              and cfg.distance_impl in ("auto", "host")
                              # The host engines have no mask seam
                              # (core/faults.py): under fault injection
                              # the jitted aggregate resolves 'auto' to
                              # 'xla' and threads the quarantine mask.
                              and self.faults is None
                              # Margins (and the numerics counters that
                              # band them) read the on-device scores;
                              # the eager host engines never return
                              # them.
                              and not (cfg.margins or kernel_num))
            self._aggregate = (self._aggregate_impl if eager_host_agg
                               else jax.jit(self._aggregate_impl,
                                            **self._donate_kw()))
            if self.faults is not None:
                # Staged rounds cross the host every round anyway; the
                # fault seam runs as its own small jitted step between
                # the (host) attack craft and the aggregation.
                self._fault_step = jax.jit(inject_and_quarantine)
            if cfg.telemetry or cfg.margins or kernel_num:
                # telemetry is a trace-time (static) flag, so the
                # telemetry aggregate is its own jitted function
                # (margins and the kernel numerics counters ride the
                # same diagnostics pytree).
                agg_tele = functools.partial(self._aggregate_impl,
                                             telemetry=True,
                                             margins=(cfg.margins
                                                      or kernel_num),
                                             numerics=kernel_num)
                self._aggregate_tele = (agg_tele if eager_host_agg
                                        else jax.jit(
                                            agg_tele,
                                            **self._donate_kw()))
            self._staged = True
        self._attack_envelope = attack_envelope
        self._attack_margins = attack_margins
        self._finish_telemetry = finish_telemetry

    # ------------------------------------------------------------------
    def _build_hier_round_fns(self):
        """Two-tier streaming round (cfg.aggregation='hierarchical').

        The round is the three federated primitives of ops/federated.py
        composed inside one jit: ``broadcast`` (the server weights ride
        the scan closure), ``client_map`` (a ``lax.scan`` over
        megabatches of ``cfg.megabatch`` clients — gather that
        megabatch's minibatch, compute its gradients, run the attack
        seam on ITS malicious rows, reduce it to one tier-1 robust
        estimate with the unchanged flat kernel), and ``shard_reduce``
        (the tier-2 shard_* kernel over the (n/m, d) estimate matrix).
        The full (n, d) gradient matrix and the (n, n) distance matrix
        never exist: XLA reuses one megabatch's buffers across scan
        steps, so peak round memory is O(m·d) (tools/perf_gate.py
        ``--memproof`` pins it at the 10k north star).

        ATTACK-SEAM SEMANTICS CHANGE (documented contract of the flag):
        ``Attack.craft`` runs once per megabatch and sees only that
        megabatch's malicious rows — ALIE-style cohort statistics are
        per-megabatch envelopes, and under ``mal_placement='spread'``
        each crafted vector is estimated from ~f/S rows instead of f.
        Augmentation keys are per-round (like the flat path), so crop/
        flip draws repeat across megabatches at equal row positions —
        an accepted, documented deviation (CIFAR100 only).

        Spans fuse exactly like the flat path: ``run_span`` drives the
        same ``_fused_round`` / ``_fused_span`` entry points (cost
        ledger names ``hier_round`` / ``hier_span``), and the nan guard
        ORs each megabatch's crafted-rows isfinite flag."""
        cfg = self.cfg
        from attacking_federate_learning_tpu.ops.federated import (
            client_map, shard_reduce
        )

        place = self._placement
        m = place.megabatch
        f1, f2, S = self._tier1_f, self._tier2_f, place.num_shards
        tier2_fn = self._tier2_fn

        def ctx_for(state, t, data=None):
            return AttackContext(
                original_params=state.weights,
                learning_rate=faded_learning_rate(
                    cfg.learning_rate, cfg.fading_rate, t),
                round=t,
                operands=(self.data if data is None else data).attack)

        self._ctx_for = ctx_for
        if not getattr(self.attacker, "fusable", True):
            raise ValueError(
                "hierarchical aggregation needs a fusable attack: the "
                "client axis lives inside a scanned device program")
        # Same predicate as the flat path (the in-program shadow-train
        # nan guard), evaluated per megabatch over ITS crafted rows.
        self._check_attack_nan = (
            getattr(self.attacker, "checks_finite", False)
            and self.m_mal > 0
            and getattr(self.attacker, "num_std", 1) != 0)

        groupwise = self._secagg == "groupwise"
        if groupwise:
            from attacking_federate_learning_tpu.protocols.secagg import (
                secagg_group
            )
        if groupwise and cfg.telemetry:
            from attacking_federate_learning_tpu.protocols.secagg import (
                group_envelope_stats
            )
        tele_on = cfg.telemetry
        # Margins ride the same diagnostics seam at both tiers
        # (shard_fn asks the tier-1 kernel, hier_core the tier-2 one);
        # groupwise secagg is structurally margin-free (config pins
        # the defense to NoDefense there, which --margins rejects).
        marg_on = cfg.margins
        # Numerics ride the same two-tier seam (ISSUE 20): per-shard
        # kernel tie counters stack into shard_num_*, the tier-2
        # reduction's into tier2_num_*; groupwise secagg pins
        # NoDefense, whose kernels accept-and-ignore the flag.
        num_on = cfg.numerics
        # Per-client gradient norms are observable only in the CLEAR
        # hierarchical modes: under groupwise secagg the server sees
        # group sums, not rows, so the shard norm stack (and the
        # round-stats gradient-norm triple) would read a tensor the
        # threat model says the server never holds.
        want_norms = ((tele_on or cfg.log_round_stats)
                      and not groupwise)
        # Any extra per-shard output switches shard_fn to the dict
        # pytree; with everything off the return structure (and the
        # traced program) is byte-for-byte the pre-telemetry tuple.
        extras = tele_on or cfg.log_round_stats or marg_on or num_on

        def keep_diag(k):
            # The hier twin of the flat engine's three-way telemetry
            # filter: margin fields ride iff --margins, num_ fields
            # iff --numerics, everything else iff full telemetry.
            if k.startswith("margin_"):
                return marg_on
            if k.startswith("num_"):
                return num_on
            return tele_on

        def megabatch_grads(ids, c_mal, data, state, t):
            """Deliver + train + attack for one megabatch — the shared
            front half of the clear and faulted scan steps (a Python
            extraction, not a trace change: the fault seam only ever
            APPENDS ops after it, so the faults=None program is
            byte-identical).  Returns the crafted (m, d) matrix and
            the megabatch's nan flag."""
            if self.traffic is not None:
                # Hier traffic = in-program slot resampling only: each
                # megabatch slot re-draws its population archetype per
                # round (pure in (traffic key, t, shard identity) —
                # core/population.py).  Rounds stay full; the ladder
                # and churn accounting are flat/async-engine features
                # (composition matrix, ARCHITECTURE.md).
                from attacking_federate_learning_tpu.core.population \
                    import resample_slots
                ids = resample_slots(data.traffic_key, t, ids, c_mal,
                                     self.f, self.n)
            with stage_scope("deliver"):
                with stage_scope("gather"):
                    xs, ys = self._gather_batches(data, t, ids)
                    xs, ys = self._split_local_steps(data, xs, ys, ids, t)
                lr_train = faded_learning_rate(cfg.learning_rate,
                                               cfg.fading_rate, t)
                lr_report = (lr_train if cfg.server_uses_faded_lr
                             else cfg.learning_rate)
                with stage_scope("client_step"):
                    grads = self._client_update(state.weights, xs, ys,
                                                lr_train, lr_report)
                    grads = grads.astype(self._grad_dtype)
                if self.shardings is not None and not self._hier_spmd:
                    # Under the SPMD client_map the body is device-local
                    # code inside shard_map — a global sharding
                    # constraint has no meaning there (the megabatch
                    # grid IS the sharded operand).
                    grads = self.shardings.constrain_grads(grads)
                with stage_scope("craft"):
                    grads = self.attacker.apply(grads, c_mal,
                                                ctx_for(state, t, data))
            with stage_scope("quarantine"):   # the fused nan guard
                bad = (
                    (~jnp.isfinite(
                        grads[:c_mal].astype(jnp.float32))).any()
                    if (self._check_attack_nan and c_mal > 0)
                    else jnp.asarray(False))
            return grads, bad

        def shard_fn(ids, c_mal, data, state, t):
            """One megabatch: ids (m,) client ids (malicious first —
            the per-megabatch mirror of the rows-[0, f) invariant),
            c_mal its STATIC malicious count.  Returns the (d,) f32
            tier-1 estimate and the megabatch's nan flag (plus, under
            groupwise secagg, the group's bitwise sum-check verdict).
            With telemetry/round-stats on it returns a dict pytree
            carrying the tier-1 diagnostics (``diag`` — the flat
            kernel's telemetry on THIS shard's sub-matrix, stacked by
            client_map into the (S, ...) shard_selection record) and,
            in the clear modes, the per-row gradient norms."""
            grads, bad = megabatch_grads(ids, c_mal, data, state, t)
            if groupwise:
                # NET-SA composition: the group's rows are secure-
                # aggregated (masks keyed on these GLOBAL client ids,
                # protocols/secagg.py) and the server sees only the
                # group sum — the tier-1 "defense" is the masked mean
                # (cfg.defense is pinned to NoDefense at config time),
                # bit-identical to the clear tier-1 mean, so the
                # tier-2 robust pass over group sums is byte-for-byte
                # the plain hierarchical NoDefense tier's.
                grads, sum_ok = secagg_group(grads, data.secagg_key,
                                             t, ids)
                if not extras:
                    est = self.defense_fn(grads, m, f1)
                    return est.astype(jnp.float32), bad, sum_ok
                out = {"bad": bad, "sum_ok": sum_ok}
                if tele_on:
                    # NoDefense tier-1 (config-enforced under secagg)
                    # has an empty diagnostics pytree — nothing
                    # per-client ever leaves the group.
                    est, diag = self.defense_fn(grads, m, f1,
                                                telemetry=True)
                    out["diag"] = diag
                else:
                    est = self.defense_fn(grads, m, f1)
                out["est"] = est.astype(jnp.float32)
                return out
            if not extras:
                est = self.defense_fn(grads, m, f1)
                return est.astype(jnp.float32), bad
            out = {"bad": bad}
            if tele_on or marg_on or num_on:
                dkw = {}
                if marg_on or num_on:
                    dkw["margins"] = True
                if num_on:
                    dkw["numerics"] = True
                est, diag = self.defense_fn(grads, m, f1,
                                            telemetry=True, **dkw)
                # Margins/numerics-only: the full diagnostics never
                # leave the shard — just the flagged fields (the
                # stacked (S, ...) shard_margin_* / shard_num_*
                # records); a numerics-only run's forced margin
                # carriers are dropped here and DCE'd in-trace.
                diag = {k: v for k, v in diag.items() if keep_diag(k)}
                out["diag"] = diag
            else:
                est = self.defense_fn(grads, m, f1)
            out["est"] = est.astype(jnp.float32)
            if want_norms:
                with stage_scope("deliver"):   # delivered-matrix rider
                    out["norms"] = jnp.linalg.norm(
                        grads.astype(jnp.float32), axis=1)
            return out

        # SPMD: client_map runs the shard_map mapping (each device owns
        # its megabatches, one explicit all_gather of the estimates);
        # the gathered (S, ...) outputs come back REPLICATED, so the
        # tier-2 resharding constraint is skipped — re-annotating a
        # replicated matrix is exactly the GSPMD seam being retired.
        cm_plan = self.shardings if self._hier_spmd else None
        t2_plan = None if self._hier_spmd else self.shardings

        def hier_core(data, state, t):
            tele = {}
            # Outer scope: the megabatch scan's own plumbing (carry
            # writes, estimate stacking) books under tier1_aggregate;
            # the finer scopes inside shard_fn win for everything they
            # annotate (stage_attribution takes the innermost token).
            with stage_scope("tier1_aggregate"):
                out = client_map(shard_fn, place, data, state, t,
                                 plan=cm_plan)
            norms = diag1 = sum_oks = None
            if extras:
                ests, bads = out["est"], out["bad"]
                sum_oks = out.get("sum_ok")
                norms = out.get("norms")        # (S, m) clear modes
                diag1 = out.get("diag")         # stacked tier-1 pytree
            elif groupwise:
                ests, bads, sum_oks = out
            else:
                ests, bads = out
            if groupwise:
                # Per-group sum norms are server-visible under
                # group-wise secagg (each estimate is sum/m): the v5
                # 'secagg' event's observable quantity.  Stage ledger:
                # protocol-side riders — ``protect``.
                with stage_scope("protect"):
                    tele = {
                        "secagg_sum_check_ok":
                            jnp.all(sum_oks > 0).astype(jnp.int32),
                        "secagg_groups": jnp.asarray(S, jnp.int32),
                        "secagg_dropped": jnp.zeros((), jnp.int32),
                        "secagg_masks_reconstructed":
                            jnp.zeros((), jnp.int32),
                        "secagg_recovery": jnp.zeros((), jnp.int32),
                        "secagg_group_sum_norms":
                            jnp.linalg.norm(ests, axis=1) * m,
                    }
                    if tele_on:
                        # Group-sum envelope (protocols/secagg.py): the
                        # population view the server can still compute
                        # when groups, not clients, are the visible
                        # unit.
                        env = group_envelope_stats(ests, m)
                        tele["secagg_group_cos_to_mean"] = (
                            env["group_cos_to_mean"])
            if tele_on or marg_on or num_on:
                if diag1:
                    for dk, dv in diag1.items():
                        tele["shard_" + dk] = dv
                if norms is not None and tele_on:
                    tele["shard_grad_norms"] = norms
                t2kw = {}
                if marg_on or num_on:
                    t2kw["margins"] = True
                if num_on:
                    t2kw["numerics"] = True
                agg, diag2 = shard_reduce(tier2_fn, ests, S, f2,
                                          plan=t2_plan,
                                          telemetry=True, **t2kw)
                with stage_scope("tier2_aggregate"):
                    for dk, dv in diag2.items():
                        if keep_diag(dk):
                            tele["tier2_" + dk] = dv
                    if tele_on:
                        tele["tier2_est_norms"] = jnp.linalg.norm(
                            ests.astype(jnp.float32), axis=1)
                if num_on:
                    # Engine-level health at the tier boundary: the
                    # (S, d) estimate matrix the tier-2 reduction
                    # aggregates (per-shard wire health is in the
                    # stacked shard_num_* fields).
                    with stage_scope("tier2_aggregate"):
                        tele["num_nonfinite_post"] = nonfinite_count(
                            ests)
                        tele["num_range_log2"] = norm_dynamic_range(
                            ests)
            else:
                agg = shard_reduce(tier2_fn, ests, S, f2,
                                   plan=t2_plan)
            new_state = self._aggregate_impl(data, state, None, t, agg=agg)
            if num_on:
                with stage_scope("apply"):
                    tele["num_nonfinite_agg"] = nonfinite_count(
                        new_state.velocity)
            bad = (bads.any() if self._check_attack_nan
                   else jnp.asarray(False))
            diag = {}
            if cfg.log_round_stats:
                # The flat round_diagnostics re-read over what this
                # mode can observe: exact per-client norm stats in the
                # clear modes (the (S, m) stack holds the same n
                # values), group-sum norm stats under groupwise.
                with stage_scope("apply"):
                    diag = {
                        "update_norm": jnp.linalg.norm(
                            new_state.velocity),
                        "faded_lr": faded_learning_rate(
                            cfg.learning_rate, cfg.fading_rate, t),
                    }
                    if norms is not None:
                        diag.update(
                            grad_norm_mean=jnp.mean(norms),
                            grad_norm_max=jnp.max(norms),
                            grad_norm_min=jnp.min(norms))
                    else:
                        gs = jnp.linalg.norm(
                            ests.astype(jnp.float32), axis=1) * m
                        diag.update(
                            group_sum_norm_mean=jnp.mean(gs),
                            group_sum_norm_max=jnp.max(gs),
                            group_sum_norm_min=jnp.min(gs))
            return new_state, diag, bad, tele

        def fused(data, state, t, batches=None):
            # `batches` mirrors the flat signature (run_round always
            # passes it); hierarchical is device-resident-only, so it
            # is always None (validated at init).
            new_state, diag, bad, tele = hier_core(data, state, t)
            return new_state, diag, bad, tele

        def fused_span(data, state, t0, count):
            # Same traced-count fori_loop as the flat span: one
            # compilation covers every span length.
            def body(i, carry):
                s, bad = carry
                s2, _, b, _ = hier_core(data, s, t0 + i)
                if self._check_attack_nan:
                    bad = bad | b
                return s2, bad

            return jax.lax.fori_loop(0, count, body,
                                     (state, jnp.asarray(False)))

        def tele_span(data, state, t0, count):
            # Per-round telemetry pytrees (and groupwise secagg's
            # protocol stats) come back stacked, exactly like the flat
            # engine's telemetry span (static count: one compilation
            # per distinct span length).
            def body(carry, i):
                s, bad = carry
                s2, _, b, tele = hier_core(data, s, t0 + i)
                if self._check_attack_nan:
                    bad = bad | b
                return (s2, bad), tele

            (s, bad), stacked = jax.lax.scan(
                body, (state, jnp.asarray(False)), jnp.arange(count))
            return s, bad, stacked

        if self.faults is not None:
            # Faulted hierarchical round (ISSUE 19): two fault
            # granularities compose inside the same scanned program —
            # per-CLIENT faults become a per-shard (m,) quarantine mask
            # into the unchanged mask-aware tier-1 kernel, and the
            # correlated shard-DOMAIN axis kills whole megabatches at
            # once, excluded at tier-2 through the alive_counts seam.
            # The tier-2 graceful-degradation ladder is the traffic
            # engine's (core/population.py plan_action over the
            # SURVIVING-shard count vs f2): planned on host per round
            # (pure in (fault key, t) — resume regenerates it),
            # selected on device, no data-dependent shapes.
            from attacking_federate_learning_tpu.core.faults import (
                TIER2_FALLBACK, apply_shard_faults, domain_alive_row,
                quarantine
            )
            from attacking_federate_learning_tpu.core.population import (
                TRAFFIC_FALLBACK
            )
            from attacking_federate_learning_tpu.defenses.kernels import (
                TIER2_DEFENSES
            )

            faults = self.faults
            straggler = faults.straggler > 0
            # Ladder step: the masked shard-median fallback kernel
            # (core/faults.py TIER2_FALLBACK — the widest-validity
            # tier-2 kernel, f-free over survivors).
            self._tier2_fallback_fn = stage_wrapped(
                TIER2_DEFENSES[TIER2_FALLBACK], "tier2_aggregate")

            def fault_shard_fn(sid, ids, c_mal, data, state, t, ring):
                """Faulted megabatch step: the clear front half
                (megabatch_grads — byte-identical trace) plus the
                fault seam.  ``sid`` is the shard id threaded by
                client_map(with_sid=True) — the fault draw is pure in
                (fault key, t, sid), so the host schedule
                (core/faults.py hier_fault_schedule) replays every
                count exactly.  ``ring`` is the (delay, S, m, d) stale
                slab (a unit f32 dummy when straggler is off).
                Returns a dict pytree; client_map stacks it (S, ...)"""
                grads, bad = megabatch_grads(ids, c_mal, data, state, t)
                fkey = data.fault_key
                with stage_scope("quarantine"):
                    old = (ring[jnp.mod(t, faults.straggler_delay), sid]
                           if straggler else None)
                    faulted, drop, fstats, fresh = apply_shard_faults(
                        grads, t, sid, fkey, old, faults, c_mal)
                    # Full (S,) domain row indexed at sid: every shard
                    # computes the same row (XLA CSEs the copies under
                    # the sequential scan; under shard_map each device
                    # derives it locally — no cross-shard operand).
                    dom = domain_alive_row(fkey, t, S, faults)[sid]
                out = {"bad": bad}
                for sk, sv in fstats.items():
                    out["f_" + sk] = sv
                if straggler:
                    out["fresh"] = fresh
                if groupwise:
                    # Groupwise secagg ⊕ dropout (config admits only
                    # dropout-style faults here): the dropped members'
                    # pairwise masks are reconstructed over the group's
                    # GLOBAL client ids (recovery_residue), the group
                    # sum excludes them, and the masked NoDefense mean
                    # divides by the survivor count — exactly the clear
                    # quarantine semantics, behind the protocol.
                    qmask = ~drop
                    recovered, sstats = secagg_group(
                        faulted, data.secagg_key, t, ids, alive=qmask)
                    out["secagg"] = sstats
                    with stage_scope("quarantine"):
                        out["f_quarantined"] = (
                            m - jnp.sum(qmask)).astype(jnp.int32)
                    if tele_on:
                        est, diag = self.defense_fn(
                            recovered, m, f1, mask=qmask, telemetry=True)
                        out["diag"] = diag
                    else:
                        est = self.defense_fn(recovered, m, f1,
                                              mask=qmask)
                else:
                    with stage_scope("quarantine"):
                        clean, qmask, qstats = quarantine(faulted, drop)
                    out["f_quarantined"] = qstats["fault_quarantined"]
                    if tele_on or marg_on or num_on:
                        dkw = {}
                        if marg_on or num_on:
                            dkw["margins"] = True
                        if num_on:
                            dkw["numerics"] = True
                        est, diag = self.defense_fn(
                            clean, m, f1, mask=qmask, telemetry=True,
                            **dkw)
                        diag = {k: v for k, v in diag.items()
                                if keep_diag(k)}
                        out["diag"] = diag
                    else:
                        est = self.defense_fn(clean, m, f1, mask=qmask)
                    if want_norms:
                        with stage_scope("deliver"):
                            # Norms of the QUARANTINED matrix — what
                            # the server actually aggregates.
                            out["norms"] = jnp.linalg.norm(
                                clean.astype(jnp.float32), axis=1)
                # Effective cohort: quarantine survivors, zeroed whole
                # when the shard's DOMAIN is dead this round — the
                # tier-2 alive_counts seam excludes alive == 0 shards.
                with stage_scope("quarantine"):
                    out["alive"] = (jnp.sum(qmask)
                                    * dom).astype(jnp.int32)
                out["est"] = est.astype(jnp.float32)
                return out

            def fault_hier_core(data, state, t, action, fstate):
                ring = (fstate["stale"] if straggler
                        else jnp.ones((), jnp.float32))
                with stage_scope("tier1_aggregate"):
                    out = client_map(fault_shard_fn, place, data, state, t,
                                     ring, plan=cm_plan, with_sid=True)
                ests, bads, alive = out["est"], out["bad"], out["alive"]
                fstate2 = fstate
                if straggler:
                    with stage_scope("quarantine"):
                        # One ring write per round, outside the scan:
                        # client_map stacks ``fresh`` (S, m, d) in sid
                        # order — exactly the ring's shard axis.
                        fstate2 = {"stale":
                                   jax.lax.dynamic_update_index_in_dim(
                                       ring, out["fresh"],
                                       jnp.mod(t,
                                               faults.straggler_delay),
                                       0)}
                with stage_scope("quarantine"):
                    dom = domain_alive_row(data.fault_key, t, S, faults)
                    # NaN-safety: a shard with zero aggregable rows has
                    # an undefined tier-1 estimate (0/0 mean); zero it
                    # before tier-2 (whose mask already excludes it) so
                    # nothing non-finite can leak through an unselected
                    # lane.
                    ests = jnp.where(alive[:, None] > 0, ests,
                                     jnp.zeros((), ests.dtype))
                    tele = {
                        "fault_injected_dropout": jnp.sum(
                            out["f_injected_dropout"]).astype(jnp.int32),
                        "fault_injected_straggler": jnp.sum(
                            out["f_injected_straggler"]).astype(
                                jnp.int32),
                        "fault_injected_corrupt": jnp.sum(
                            out["f_injected_corrupt"]).astype(jnp.int32),
                        "fault_quarantined": jnp.sum(
                            out["f_quarantined"]).astype(jnp.int32),
                        "fault_shards_dead": (
                            S - jnp.sum(dom)).astype(jnp.int32),
                        "fault_shard_alive": alive.astype(jnp.int32),
                        "fault_shards_alive": jnp.sum(
                            alive > 0).astype(jnp.int32),
                        "fault_tier2_action": jnp.asarray(action,
                                                          jnp.int32),
                    }
                if groupwise:
                    sa = out["secagg"]
                    with stage_scope("protect"):
                        tele.update({
                            "secagg_sum_check_ok": jnp.all(
                                sa["secagg_sum_check_ok"] > 0).astype(
                                    jnp.int32),
                            "secagg_groups": jnp.asarray(S, jnp.int32),
                            "secagg_dropped": jnp.sum(
                                sa["secagg_dropped"]).astype(jnp.int32),
                            "secagg_masks_reconstructed": jnp.sum(
                                sa["secagg_masks_reconstructed"]
                            ).astype(jnp.int32),
                            "secagg_recovery": jnp.any(
                                sa["secagg_recovery"] > 0).astype(
                                    jnp.int32),
                            "secagg_group_sum_norms":
                                jnp.linalg.norm(ests, axis=1) * m,
                        })
                        if tele_on:
                            env = group_envelope_stats(ests, m)
                            tele["secagg_group_cos_to_mean"] = (
                                env["group_cos_to_mean"])
                norms = out.get("norms")
                if tele_on or marg_on or num_on:
                    diag1 = out.get("diag")
                    if diag1:
                        for dk, dv in diag1.items():
                            tele["shard_" + dk] = dv
                    if norms is not None and tele_on:
                        tele["shard_grad_norms"] = norms
                    t2kw = {}
                    if marg_on or num_on:
                        t2kw["margins"] = True
                    if num_on:
                        t2kw["numerics"] = True
                    agg, diag2 = shard_reduce(tier2_fn, ests, S, f2,
                                              alive_counts=alive,
                                              plan=t2_plan,
                                              telemetry=True, **t2kw)
                    with stage_scope("tier2_aggregate"):
                        for dk, dv in diag2.items():
                            if keep_diag(dk):
                                tele["tier2_" + dk] = dv
                        if tele_on:
                            tele["tier2_est_norms"] = jnp.linalg.norm(
                                ests.astype(jnp.float32), axis=1)
                    if num_on:
                        with stage_scope("tier2_aggregate"):
                            tele["num_nonfinite_post"] = (
                                nonfinite_count(ests))
                            tele["num_range_log2"] = (
                                norm_dynamic_range(ests))
                else:
                    agg = shard_reduce(tier2_fn, ests, S, f2,
                                       alive_counts=alive, plan=t2_plan)
                # Ladder on device: the fallback estimate is always
                # computed (fixed shapes), the host-planned action
                # selects.  Telemetry/margins diagnostics above always
                # read the CONFIGURED tier-2 kernel — under FALLBACK
                # only the aggregate switches (documented,
                # ARCHITECTURE.md "Faults & recovery").
                fb = shard_reduce(self._tier2_fallback_fn, ests, S, f2,
                                  alive_counts=alive, plan=t2_plan)
                agg = jnp.where(action == TRAFFIC_FALLBACK, fb, agg)
                # HOLD rides _aggregate_impl's action seam (state-level
                # jnp.where after the momentum update).
                new_state = self._aggregate_impl(data, state, None, t,
                                                 agg=agg, action=action)
                if num_on:
                    with stage_scope("apply"):
                        tele["num_nonfinite_agg"] = nonfinite_count(
                            new_state.velocity)
                bad = (bads.any() if self._check_attack_nan
                       else jnp.asarray(False))
                diag = {}
                if cfg.log_round_stats:
                    with stage_scope("apply"):
                        diag = {
                            "update_norm": jnp.linalg.norm(
                                new_state.velocity),
                            "faded_lr": faded_learning_rate(
                                cfg.learning_rate, cfg.fading_rate, t),
                        }
                        if norms is not None:
                            diag.update(
                                grad_norm_mean=jnp.mean(norms),
                                grad_norm_max=jnp.max(norms),
                                grad_norm_min=jnp.min(norms))
                        else:
                            gs = jnp.linalg.norm(
                                ests.astype(jnp.float32), axis=1) * m
                            diag.update(
                                group_sum_norm_mean=jnp.mean(gs),
                                group_sum_norm_max=jnp.max(gs),
                                group_sum_norm_min=jnp.min(gs))
                return new_state, diag, bad, tele, fstate2

            def fault_fused(data, state, t, action, fstate, batches=None):
                # `batches` mirrors the flat faulted signature
                # (run_round always passes it); hierarchical is
                # device-resident-only, so it is always None.
                return fault_hier_core(data, state, t, action, fstate)

            def fault_span(data, state, t0, count, fstate, actions):
                # Hier fault span: the flat fault_span's shape (scan,
                # static count, stacked 'fault_*' pytree, fault state
                # in the carry) plus the host-planned (count,) ladder
                # actions as a scanned operand.
                def body(carry, xs):
                    s, bad, fs = carry
                    i, act = xs
                    s2, _, b, tele, fs = fault_hier_core(
                        data, s, t0 + i, act, fs)
                    if self._check_attack_nan:
                        bad = bad | b
                    return (s2, bad, fs), tele

                (s, bad, fs), stacked = jax.lax.scan(
                    body, (state, jnp.asarray(False), fstate),
                    (jnp.arange(count), actions))
                return s, bad, fs, stacked

            # The fault paths never donate (flat rationale: the fault
            # state rides the carry and the stacked-scan outputs add
            # aliasing surface).
            self._fused_round = jax.jit(fault_fused)
            self._fault_span = jax.jit(fault_span, static_argnums=3)
            self._staged = False
            return

        donate = self._donate_kw()
        self._fused_round = jax.jit(fused, **donate)
        self._fused_span = jax.jit(fused_span, **donate)
        if groupwise or cfg.telemetry or cfg.margins or cfg.numerics:
            self._tele_span = jax.jit(tele_span, static_argnums=3,
                                      **donate)
        self._staged = False

    # ------------------------------------------------------------------
    def _build_async_round_fns(self):
        """FedBuff-style buffered round (cfg.aggregation='async';
        core/async_rounds.py, ARCHITECTURE.md "Asynchronous rounds").

        The round is the sync compute pipeline plus the asynchrony
        machinery, all inside one jit: every client computes a FRESH
        update against the current broadcast weights (exactly the flat
        path's ``_compute_grads_impl``), the update is submitted into
        the in-flight ring at its PRNG-drawn arrival slot, round-t
        arrivals merge into the pending pool, and the server consumes
        the first ``async_buffer`` pending updates FIFO — delivered
        rows masked into the mask-aware defense kernels with their
        staleness weights threaded as a fixed-shape ``(m,)`` vector
        through the ``weights=`` seam.

        ATTACK-SEAM SEMANTICS CHANGE (documented contract of the
        flag): ``Attack.craft`` runs at DELIVERY time over the
        delivered matrix — the colluders coordinate at the aggregation
        boundary, their crafting statistics come from the DELIVERED
        malicious sub-cohort (``AttackContext.staleness``,
        attacks/base.py:delivered_cohort_stats), and a ``timed``
        attacker additionally forces its own emission delay to 0.  The
        attacker controls content and emission time; arrival
        timestamps (hence staleness weights) are the server's.

        A round with NO deliveries is a server no-op: weights and
        velocity hold (the round counter still advances) — a real
        async server does nothing until updates arrive.

        Spans always scan (``_async_span``): the stacked per-round
        pytree carries the ``async_*`` counts (and ``fault_*`` under
        composed faults) whether or not cfg.telemetry, exactly like
        the fault span — v7 'async' events are emitted per round.  The
        async state (ring + pending) rides the carry and checkpoints
        through the Checkpointer ``extra=`` seam
        (:meth:`carry_state_host`)."""
        cfg = self.cfg
        from attacking_federate_learning_tpu.core.async_rounds import (
            async_step, staleness_weights
        )
        from attacking_federate_learning_tpu.defenses.kernels import (
            population_telemetry
        )
        # Same predicate as the flat builder (ISSUE 20): kernel
        # tie/cancellation counters exist only for the margin-bearing
        # defenses.
        kernel_num = bool(cfg.numerics and cfg.defense in
                          ("Krum", "TrimmedMean", "Median", "Bulyan"))
        self._kernel_numerics = kernel_num

        spec = self._async
        D = spec.depth

        def ctx_for(state, t, staleness=None, data=None):
            return AttackContext(
                original_params=state.weights,
                learning_rate=faded_learning_rate(
                    cfg.learning_rate, cfg.fading_rate, t),
                round=t, staleness=staleness,
                operands=(self.data if data is None else data).attack)

        self._ctx_for = ctx_for
        # Same predicate as the flat path (the in-program shadow-train
        # nan guard), evaluated over the crafted delivered rows.
        self._check_attack_nan = (
            getattr(self.attacker, "checks_finite", False)
            and self.m_mal > 0
            and getattr(self.attacker, "num_std", 1) != 0)

        def crafted_nonfinite(grads):
            return (~jnp.isfinite(
                grads[: self.m_mal].astype(jnp.float32))).any()

        def async_core(data, state, t, astate):
            grads = self._compute_grads_impl(state, t, data=data)
            # Stage ledger: the delivery ring (submit/merge/evict/
            # deliver) is how updates ARRIVE — ``deliver``.
            with stage_scope("deliver"):
                (delivered_grads, delivered, staleness, astate,
                 stats) = async_step(
                    grads, t, data.async_key, spec, astate, self.m_mal,
                    faults=self.faults, fkey=data.fault_key,
                    latency=(None if data.latency is None
                             else (data.latency, self._latency_tail)))
            ctx = ctx_for(state, t, staleness, data)
            tele = dict(stats)
            if cfg.telemetry:
                with stage_scope("deliver"):
                    env = self.attacker.envelope_stats(delivered_grads,
                                                       self.m_mal, ctx)
                tele.update({"attack_" + k: v for k, v in env.items()})
            with stage_scope("deliver"), stage_scope("craft"):
                # Attack at delivery; undelivered rows [0, f) get
                # overwritten too, so re-mask before aggregation (the
                # quarantine zero convention — distance engines
                # NaN-free).
                crafted = self.attacker.apply(delivered_grads,
                                              self.m_mal, ctx)
            if cfg.margins:
                # Attack margins at the delivery seam: pre-attack =
                # the delivered matrix, crafted = the post-attack one
                # (attacks/base.py margin_stats).
                with stage_scope("deliver"):
                    ms = self.attacker.margin_stats(
                        delivered_grads, self.m_mal, ctx, crafted=crafted)
                tele.update(
                    {"margin_attack_" + k: v for k, v in ms.items()})
            bad = (crafted_nonfinite(crafted)
                   if self._check_attack_nan else jnp.asarray(False))
            if cfg.numerics:
                with stage_scope("deliver"):
                    tele.update(
                        num_nonfinite_pre=nonfinite_count(crafted),
                        num_range_log2=norm_dynamic_range(
                            crafted, mask=delivered))
            with stage_scope("quarantine"):
                agg_grads = jnp.where(delivered[:, None], crafted, 0.0)
            if cfg.numerics:
                with stage_scope("quarantine"):
                    tele["num_nonfinite_post"] = nonfinite_count(
                        agg_grads, mask=delivered)
            with stage_scope("deliver"):
                weights = staleness_weights(staleness, delivered,
                                            spec.weighting)
                # Weight mass by staleness bucket — the science surface
                # ('async' events; weighting='none' reports unit
                # weights).
                w_eff = (weights if weights is not None
                         else jnp.where(delivered, 1.0, 0.0))
                bucket = staleness[None, :] == jnp.arange(D)[:, None]
                tele["async_weight_mass"] = jnp.sum(
                    bucket * w_eff[None, :], axis=1).astype(jnp.float32)
            if cfg.telemetry or cfg.margins or kernel_num:
                upd, ddiag = self._aggregate_impl(
                    data, state, agg_grads, t, telemetry=True,
                    margins=cfg.margins or kernel_num,
                    numerics=kernel_num, mask=delivered,
                    weights=weights)
                with stage_scope("tier1_aggregate"):
                    for dk, dv in ddiag.items():
                        # Same three-way filter as the flat engine's
                        # finish_telemetry (margin_ iff --margins,
                        # num_ iff --numerics, rest iff telemetry).
                        if dk.startswith("margin_"):
                            if cfg.margins:
                                tele["defense_" + dk] = dv
                        elif dk.startswith("num_"):
                            if cfg.numerics:
                                tele["defense_" + dk] = dv
                        elif cfg.telemetry:
                            tele["defense_" + dk] = dv
                    if cfg.telemetry:
                        tele.update(population_telemetry(agg_grads))
            else:
                upd = self._aggregate_impl(data, state, agg_grads, t,
                                           mask=delivered,
                                           weights=weights)
            with stage_scope("apply"):
                # Empty delivery = server no-op (weights/velocity hold,
                # the round counter still advances).
                any_del = jnp.any(delivered)
                new_state = ServerState(
                    weights=jnp.where(any_del, upd.weights,
                                      state.weights),
                    velocity=jnp.where(any_del, upd.velocity,
                                       state.velocity),
                    round=upd.round)
            if cfg.numerics:
                with stage_scope("apply"):
                    tele["num_nonfinite_agg"] = nonfinite_count(
                        new_state.velocity)
            diag = {}
            if cfg.log_round_stats:
                # Norm stats over the COMPUTED cohort (what clients
                # submitted this round — comparable to the flat
                # fields); the delivered view lives in async_* stats.
                with stage_scope("apply"):
                    norms = jnp.linalg.norm(grads.astype(jnp.float32),
                                            axis=1)
                    diag = {
                        "grad_norm_mean": jnp.mean(norms),
                        "grad_norm_max": jnp.max(norms),
                        "grad_norm_min": jnp.min(norms),
                        "update_norm": jnp.linalg.norm(
                            new_state.velocity),
                        "faded_lr": faded_learning_rate(
                            cfg.learning_rate, cfg.fading_rate, t),
                    }
            return new_state, diag, bad, tele, astate

        def fused(data, state, t, astate, batches=None):
            # `batches` mirrors the flat faulted signature (run_round
            # always passes it); async is device-resident-only, so it
            # is always None (validated at init).
            return async_core(data, state, t, astate)

        def async_span(data, state, t0, count, astate):
            # Always a scan (static count): the stacked per-round
            # pytree carries the async_* counts with or without
            # telemetry — 'async' events are per-round, like 'fault'.
            def body(carry, i):
                s, bad, a = carry
                s2, _, b, tele, a = async_core(data, s, t0 + i, a)
                if self._check_attack_nan:
                    bad = bad | b
                return (s2, bad, a), tele

            (s, bad, a), stacked = jax.lax.scan(
                body, (state, jnp.asarray(False), astate),
                jnp.arange(count))
            return s, bad, a, stacked

        # Like the fault paths, async never donates: the buffer state
        # rides the carry and the stacked-scan outputs add aliasing
        # surface beyond what _donate_kw's CPU rationale distrusts.
        self._fused_round = jax.jit(fused)
        self._async_span = jax.jit(async_span, static_argnums=3)
        self._staged = False

    # ------------------------------------------------------------------
    def wire_ledger(self):
        """Per-seam wire ledger for THIS engine's topology
        (utils/costs.py:wire_ledger): the bytes each logical network
        seam moves per round, derived statically from the config — no
        execution, no HLO.  Seams that the topology doesn't exercise
        carry 0 bytes, so one schema covers flat, hierarchical and
        async runs (and their secagg compositions) uniformly.

        The hierarchical tier1_to_tier2 seam doubles as the SPMD
        cross-check: under a >1-device clients axis it equals the
        measured all_gather ``collective_bytes`` that
        tools/perf_gate.py --shardproof pins to S*d*4 (ISSUE 12)."""
        cfg = self.cfg
        spmd_parts = 1
        num_shards = None
        if cfg.aggregation == "hierarchical":
            num_shards = self._placement.num_shards
            if self._hier_spmd:
                from attacking_federate_learning_tpu.parallel.mesh import (
                    CLIENTS
                )
                spmd_parts = int(self.shardings.mesh.shape[CLIENTS])
        dropped = 0
        if cfg.secagg != "off" and self.faults is not None:
            # Expected mask-reconstruction load: the dropout fault rate
            # over the cohort (secagg only composes with dropout faults,
            # config.py enforces).
            dropped = int(round(self.faults.dropout * self.m))
        from attacking_federate_learning_tpu.utils.costs import wire_ledger
        return wire_ledger(
            cohort=self.m,
            dim=self.flat.dim,
            grad_bytes=self._grad_dtype.itemsize,
            topology=cfg.aggregation,
            num_shards=num_shards,
            megabatch=cfg.megabatch if num_shards is not None else None,
            spmd_parts=spmd_parts,
            secagg=cfg.secagg,
            dropped=dropped,
            async_buffer=(cfg.async_buffer
                          if cfg.aggregation == "async" else None),
        )

    # ------------------------------------------------------------------
    def cost_report(self, logger=None, span: Optional[int] = None):
        """Static compile-and-cost facts for every jitted entry point
        this engine built (utils/costs.py): each is lowered and
        compiled ONCE — AOT, no execution — and its deterministic HLO
        facts (cost_analysis FLOPs / bytes-accessed, memory_analysis
        buffer sizes) plus compile wall time and persistent-cache
        attribution are collected into a CompileLedger.  With a
        ``logger``, one 'compile' + one 'cost' event (schema v2) lands
        per entry point; tools/perf_gate.py diffs the same facts
        against PERF_BASELINE.json.

        The report is an observer: it never touches the round
        functions themselves (their HLO is pinned byte-identical with
        the report on or off — tests/test_costs.py), and the compiles
        it pays are exactly the ones the run would pay anyway, warmed
        through the persistent cache.

        ``span``: the span length to analyze for the static-length span
        programs (default: the eval interval, the length the run
        compiles first)."""
        import jax

        from attacking_federate_learning_tpu.utils.costs import (
            CompileLedger
        )

        cfg = self.cfg
        ledger = CompileLedger()
        t0 = jnp.asarray(0, jnp.int32)
        span_len = int(span or max(1, min(cfg.test_step, cfg.epochs)))
        d = self.flat.dim
        if self._streaming:
            # Streamed rounds take the round batch as an argument;
            # abstract shapes suffice for lowering.
            kB = cfg.batch_size * cfg.local_steps
            batches = (jax.ShapeDtypeStruct(
                           (self.m, kB) + self.dataset.train_x.shape[1:],
                           jnp.float32),
                       jax.ShapeDtypeStruct((self.m, kB), jnp.int32))
        else:
            batches = None

        entries = []
        # Hierarchical engines expose the same two jitted entry points
        # under their own ledger names — the perf gate pins hier_round's
        # peak-proxy bytes to the megabatch, not the cohort.
        hier = cfg.aggregation == "hierarchical"
        round_name, span_name = (("hier_round", "hier_span") if hier
                                 else ("fused_round", "fused_span"))
        if not self._staged:
            if self._async is not None:
                # Async engines expose their two jitted entry points
                # under their own ledger names (the buffer state rides
                # the signatures).
                entries.append(("async_round", lambda: self._fused_round
                                .lower(self.data, self.state, t0,
                                       self._async_state, batches)))
                entries.append(
                    ("async_span", lambda: self._async_span.lower(
                        self.data, self.state, t0, span_len,
                        self._async_state)))
            elif self.traffic is not None:
                # Traffic engines expose their two jitted entry points
                # under their own ledger names; the schedule operands
                # are abstract (m,)-shaped rows — the lowered program
                # proves memory scales with the cohort, never the
                # population (tests/test_traffic.py pins this).
                sid_sds = jax.ShapeDtypeStruct((self.m,), jnp.int32)
                arr_sds = jax.ShapeDtypeStruct((self.m,), jnp.bool_)
                act_sds = jax.ShapeDtypeStruct((), jnp.int32)
                entries.append(("traffic_round", lambda:
                                self._fused_round.lower(
                                    self.data, self.state, t0, sid_sds,
                                    arr_sds, act_sds, self._fault_state)))
                sids_sds = jax.ShapeDtypeStruct((span_len, self.m),
                                                jnp.int32)
                arrs_sds = jax.ShapeDtypeStruct((span_len, self.m),
                                                jnp.bool_)
                acts_sds = jax.ShapeDtypeStruct((span_len,), jnp.int32)
                entries.append(("traffic_span", lambda:
                                self._traffic_span.lower(
                                    self.data, self.state, t0, span_len,
                                    sids_sds, arrs_sds, acts_sds,
                                    self._fault_state)))
            elif self.faults is None:
                entries.append((round_name, lambda: self._fused_round
                                .lower(self.data, self.state, t0, batches)))
                if not self._streaming:
                    # Span length is a traced operand: one compilation
                    # covers every span, so one analysis does too.
                    entries.append(
                        (span_name, lambda: self._fused_span.lower(
                            self.data, self.state, t0,
                            jnp.asarray(span_len, jnp.int32))))
                    if cfg.telemetry or cfg.margins or cfg.numerics:
                        # Hierarchical engines ledger their telemetry
                        # span under their own name so the perf gate
                        # can pin the hier-tele cost cells separately
                        # (margins and numerics ride the same span
                        # entry point).
                        entries.append(
                            ("hier_tele_span" if hier else "tele_span",
                             lambda: self._tele_span.lower(
                                 self.data, self.state, t0, span_len)))
            else:
                entries.append(("fused_round", lambda: self._fused_round
                                .lower(self.data, self.state, t0,
                                       self._fault_state, batches)))
                entries.append(
                    ("fault_span", lambda: self._fault_span.lower(
                        self.data, self.state, t0, span_len,
                        self._fault_state)))
        else:
            entries.append(("compute_grads", lambda: self._compute_grads
                            .lower(self.data, self.state, t0, batches)))
            grads_sds = jax.ShapeDtypeStruct((self.m, d), self._grad_dtype)
            if hasattr(self._aggregate, "lower"):
                # The staged CPU Krum/Bulyan aggregation runs EAGERLY
                # (host BLAS) — nothing compiled to analyze there.
                entries.append(("aggregate", lambda: self._aggregate.lower(
                    self.data, self.state, grads_sds, t0)))
            if ((cfg.telemetry or cfg.margins
                    or getattr(self, "_kernel_numerics", False))
                    and hasattr(self._aggregate_tele, "lower")):
                entries.append(
                    ("aggregate_tele", lambda: self._aggregate_tele.lower(
                        self.data, self.state, grads_sds, t0)))

        # The wired defense kernel in isolation: the per-cell
        # defense-cost row of the attack x defense grid (ALIE vs Bulyan
        # cells differ by orders of magnitude in O(n^2 d) kernel cost —
        # this is where that becomes a recorded number).
        kw = {}
        if getattr(self.defense_fn, "needs_round", False):
            kw["round"] = t0
        if self._needs_server_grad:
            kw["server_grad"] = jax.ShapeDtypeStruct((d,), jnp.float32)
        # Hierarchical: the tier-1 kernel only ever sees one (m, d)
        # megabatch with the assumed per-shard bound; tier-2 gets its
        # own ledger row over the (S, d) estimate matrix.
        du_n, du_f = ((self._placement.megabatch, self._tier1_f) if hier
                      else (self.m, self.m_mal))
        grads_sds = jax.ShapeDtypeStruct((du_n, d), self._grad_dtype)
        defense_fn = self.defense_fn

        def defense_lowered():
            jitted = jax.jit(lambda G, **kws: defense_fn(
                G, du_n, du_f, **kws))
            return jitted.lower(grads_sds, **kw)

        entries.append((f"defense_{cfg.defense}", defense_lowered))
        if hier:
            S = self._placement.num_shards
            est_sds = jax.ShapeDtypeStruct((S, d), jnp.float32)
            tier2_fn, f2 = self._tier2_fn, self._tier2_f

            def tier2_lowered():
                jitted = jax.jit(lambda E: tier2_fn(E, S, f2))
                return jitted.lower(est_sds)

            entries.append((f"tier2_{self._tier2_name}", tier2_lowered))
        entries.append(("eval", lambda: self.evaluate.lower(
            jax.ShapeDtypeStruct((d,), jnp.float32))))

        for name, thunk in entries:
            try:
                ledger.analyze(name, thunk())
            except Exception as e:        # noqa: BLE001 — one entry
                # failing to lower must not lose the rest of the table
                ledger.errors.append((name, f"{type(e).__name__}: {e}"))
        # Wire ledger rides the same report: one versioned wire_bytes
        # event per cost_report, next to the per-entry stage_cost rows.
        try:
            ledger.wire = self.wire_ledger()
        except Exception:             # noqa: BLE001 — observability
            ledger.wire = None        # must never sink a run
        if logger is not None:
            ledger.emit(logger)
        self.cost_ledger = ledger
        return ledger

    # ------------------------------------------------------------------
    @staticmethod
    def _donate_kw():
        """Server-state donation policy: donate on accelerators (HBM
        reuse matters there), never on the CPU backend.  This box's
        jaxlib honors CPU donation with full input/output buffer
        aliasing, and the combination with zero-copy ``np.asarray``
        views has produced dangling reads and flaky heap corruption
        (segfaults/aborts mid-test-suite, clobbered snapshot restores —
        the seed's recoverable-state failure).  A (d,)-state copy per
        round is noise on CPU; correctness isn't."""
        if jax.default_backend() == "cpu":
            return {}
        return {"donate_argnums": 1}    # argument 0 is the RoundData

    @staticmethod
    def _host_copy(tree):
        """Owned host snapshot of a device pytree.  ``np.asarray`` on a
        CPU-backend jax array can be a zero-copy VIEW of the device
        buffer; snapshots taken before a donating call must own their
        memory or the donation clobbers them."""
        return jax.tree.map(lambda a: np.array(a, copy=True), tree)

    def carry_state_host(self):
        """Host copy of the engine's cross-round carry state for the
        Checkpointer ``extra=`` seam: the async ring + pending pool
        (six ``async_*``-keyed arrays — f32 buffers, bool occupancy
        masks, int32 birth counters) under aggregation='async', or the
        straggler ring buffer (``stale``) under sync fault injection.
        None when the engine carries nothing beyond the ServerState."""
        if self._async is not None and self._async_state:
            host = self._host_copy(self._async_state)
            return {"async_" + k: v for k, v in host.items()}
        if self.faults is None or not self._fault_state:
            return None
        return self._host_copy(self._fault_state)

    def restore_carry_state(self, extra):
        """Re-install checkpointed carry state (the fault ring buffer
        or the async buffers) after a resume (cli.py --resume /
        Checkpointer ``extra``) so a resumed run continues
        bit-for-bit.  Dtypes are restored per array (npz round-trips
        bool occupancy and int32 birth counters faithfully, but a
        foreign writer may widen — coerce to the engine's layout)."""
        if not extra:
            return
        if self._async is not None:
            if any(k.startswith("async_") for k in extra):
                ref = self._async_state
                self._async_state = {
                    k: jnp.asarray(extra["async_" + k]).astype(v.dtype)
                    for k, v in ref.items()}
            return
        if self.faults is not None and "stale" in extra:
            self._fault_state = {"stale": jnp.asarray(extra["stale"])}

    def restore_fault_state(self, extra):
        """Back-compat alias (pre-async spelling; cli.py --resume and
        older callers)."""
        self.restore_carry_state(extra)

    def fault_state_host(self):
        """Back-compat alias for :meth:`carry_state_host` (pre-async
        spelling — it now also returns the async buffers)."""
        return self.carry_state_host()

    def _diverged(self) -> bool:
        """Divergence watchdog predicate, evaluated at span boundaries
        (host side, one fetch): non-finite server weights, or a weight
        norm beyond FaultConfig.watchdog_norm — the signature of
        unquarantinable garbage (e.g. bit-scaled finite rows) making it
        through aggregation."""
        w = np.asarray(self.state.weights)
        if not np.isfinite(w).all():
            return True
        return float(np.linalg.norm(w)) > self.faults.watchdog_norm

    def _rollback(self, logger, epoch, checkpointer):
        """Roll the engine back to the last good auto-checkpointed state
        instead of aborting.  Emits a 'fault' event, re-persists the
        restored state as an on-failure auto-checkpoint, and raises
        FloatingPointError only once max_rollbacks is exhausted (state
        still restored first, so catch-and-continue callers hold a
        finite state)."""
        self._rollbacks += 1
        st, fs = self._last_good
        restored_round = int(st.round)
        logger.record(kind="fault", round=int(epoch), rolled_back=1,
                      restored_round=restored_round,
                      rollbacks_total=self._rollbacks)
        logger.print(
            f"!! server state diverged after round {epoch}; rolling "
            f"back to round {restored_round} "
            f"(rollback {self._rollbacks}/{self.faults.max_rollbacks})")
        self.state = (self.shardings.place_state(st)
                      if self.shardings is not None
                      else jax.tree.map(jnp.asarray, st))
        if fs is not None:
            # fs is the carry_state_host() form (async_* keys or the
            # fault ring), so the restore path is shared with --resume.
            self.restore_carry_state(fs)
        if checkpointer is not None:
            # On-failure checkpoint: persist the state we rolled back
            # to, so an external --resume lands on the same round.
            checkpointer.save_auto(self.state, extra=fs)
        if self._rollbacks > self.faults.max_rollbacks:
            raise FloatingPointError(
                f"server state diverged after round {epoch} and "
                f"exhausted {self.faults.max_rollbacks} rollbacks "
                f"(restored to round {restored_round})")

    def _raise_if_attack_nan(self, bad):
        """Host side of the crafted-rows nan flag — reference-equivalent
        guard, not message parity: the reference raises
        ``Exception('Got nan dist loss')`` / ``Exception('Got nan loss')``
        (backdoor.py:145-152); this raises FloatingPointError with one
        message for both, and checks isfinite (strictly stronger than the
        reference's isnan)."""
        if self._check_attack_nan and bool(bad):
            raise FloatingPointError("Got nan in backdoor shadow training")

    # --- measured walls (utils/walls.py; cfg.profile_every) -----------
    def _span_entry_name(self) -> str:
        """The ledger name of the span program run_span dispatches —
        the same name cost_report records its stage_cost under, so the
        measured 'wall' event joins the modeled row by name."""
        hier = self.cfg.aggregation == "hierarchical"
        if self._async is not None:
            return "async_span"
        if self.traffic is not None and not hier:
            return "traffic_span"
        if self.faults is not None:
            return "fault_span"
        if (self.cfg.telemetry or self.cfg.margins or self.cfg.numerics
                or self._secagg is not None):
            return "hier_tele_span" if hier else "tele_span"
        return "hier_span" if hier else "fused_span"

    def _span_hlo_text(self, count: int) -> str:
        """Compiled HLO text of the span program for ``count`` rounds —
        the static side of the walls join (instruction name -> stage
        token).  AOT lower+compile, exactly the program run_span's jit
        call builds (warm through the persistent cache); memoized per
        (entry, count) since the scanned spans specialize on length."""
        name = self._span_entry_name()
        key = (name, 1 if name == "fused_span" else int(count))
        cache = getattr(self, "_wall_hlo_cache", None)
        if cache is None:
            cache = self._wall_hlo_cache = {}
        if key not in cache:
            t0 = jnp.asarray(0, jnp.int32)
            if self._async is not None:
                low = self._async_span.lower(
                    self.data, self.state, t0, int(count), self._async_state)
            elif self.traffic is not None and name == "traffic_span":
                c = int(count)
                low = self._traffic_span.lower(
                    self.data, self.state, t0, c,
                    jax.ShapeDtypeStruct((c, self.m), jnp.int32),
                    jax.ShapeDtypeStruct((c, self.m), jnp.bool_),
                    jax.ShapeDtypeStruct((c,), jnp.int32),
                    self._fault_state)
            elif self.faults is not None:
                if self._placement is not None:
                    low = self._fault_span.lower(
                        self.data, self.state, t0, int(count),
                        self._fault_state,
                        jax.ShapeDtypeStruct((int(count),), jnp.int32))
                else:
                    low = self._fault_span.lower(
                        self.data, self.state, t0, int(count),
                        self._fault_state)
            elif (self.cfg.telemetry or self.cfg.margins
                    or self.cfg.numerics or self._secagg is not None):
                low = self._tele_span.lower(self.data, self.state, t0,
                                            int(count))
            else:
                # Span length is a traced operand: one compilation
                # covers every span length, so one text does too.
                low = self._fused_span.lower(
                    self.data, self.state, t0, jnp.asarray(count, jnp.int32))
            cache[key] = low.compile().as_text()
        return cache[key]

    def _book_span_walls(self, logger, trace_dir: str, count: int):
        """Book one profiled span capture onto the stage set and
        emit the schema-v10 'wall' event (source='trace').  Returns the
        WallRecord, or None when booking failed or the capture left no
        trace — walls observability must never sink the run it
        measures, so both are counted (``wall_booking_failures``) and
        reported in the run's exit summary instead of raised."""
        from attacking_federate_learning_tpu.utils.walls import (
            book_trace
        )

        try:
            rec = book_trace(
                trace_dir, self._span_hlo_text(count),
                name=self._span_entry_name(),
                platform=jax.devices()[0].platform, rounds=count)
        except Exception as e:          # noqa: BLE001 — observability
            logger.print(f"[walls] booking failed: "
                         f"{type(e).__name__}: {e}")
            rec = None
        if rec is None:
            self.wall_booking_failures += 1
        elif logger is not None:
            logger.record(**rec.wall_event())
        return rec

    def _traffic_plan(self, start: int, count: int):
        """Host-sampled traffic schedule for rounds [start, start+count):
        cohort shard ids, arrival masks and ladder actions (one device
        operand row per round), plus the v11 'traffic' events the run
        loop emits at the next journal-fresh boundary.  Pure in the
        traffic seed and the round index (core/population.py), so a
        resumed run regenerates the identical schedule — no carry
        state."""
        from attacking_federate_learning_tpu.core.population import (
            traffic_schedule
        )
        return traffic_schedule(
            self.registry, start, count, self.m, self.m_mal,
            self.cfg.defense, self.traffic.fallback_defense,
            self.traffic.min_cohort)

    def _fault_plan(self, start: int, count: int):
        """Host-planned tier-2 ladder actions for the faulted
        hierarchical rounds [start, start+count): replay the fault
        schedule (core/faults.py hier_fault_schedule — pure in the
        fault key and the round index, so a resumed run regenerates
        the identical plan), then run the traffic engine's
        plan_action on each round's SURVIVING-shard count vs the
        tier-2 kernel's validity bound (f2).  Returns a (count,)
        int32 np array of TRAFFIC_* codes — one scanned device
        operand row per round."""
        from attacking_federate_learning_tpu.core.faults import (
            hier_fault_schedule, plan_tier2_actions
        )
        rows = hier_fault_schedule(self._fault_key, start, count,
                                   self._placement, self.faults)
        return plan_tier2_actions([r["shards_alive"] for r in rows],
                                  self._tier2_name, self._tier2_f)

    def run_span(self, start: int, count: int) -> ServerState:
        """Run ``count`` rounds [start, start+count) as one scanned device
        program when the attack is fusable; falls back to per-round calls
        otherwise (staged attacks need host crafting; round diagnostics
        need every intermediate gradient matrix; host-streamed data feeds
        one round's batch per program, overlapped with the previous
        round's compute).  Under cfg.telemetry the span still runs as one
        program — per-round telemetry pytrees come back STACKED
        (``_tele_span``) and land in ``self.last_span_telemetry`` as
        ``(start, stacked_pytree)`` for the caller to fetch once."""
        if count <= 0:
            return self.state
        if self._staged or self.cfg.log_round_stats or self._streaming:
            for t in range(start, start + count):
                self.run_round(t)
        else:
            self.last_round_stats = None
            self.last_span_telemetry = None
            pre_span = pre_fstate = pre_astate = None
            if self._check_attack_nan:
                # The span donates self.state, so when the in-program nan
                # flag fires the post-nan state is all a caller would have
                # left — unlike the staged/reference path, where the raise
                # leaves the last good round behind.  A host snapshot of
                # the pre-span state (~2 vectors of d) keeps catch-and-
                # continue callers (benchmarks.py) recoverable.
                # np.array(copy=True), NOT np.asarray: asarray can be a
                # zero-copy view of the very buffer the span donates,
                # and a clobbered snapshot restores garbage.
                with host_span("interval.checkpoint"):
                    pre_span = self._host_copy(self.state)
                    if self._fault_state is not None:
                        pre_fstate = self._host_copy(self._fault_state)
                    if self._async_state is not None:
                        pre_astate = self._host_copy(self._async_state)
            with host_span("interval.dispatch_span"):
                if self._async is not None:
                    # Async spans always scan: the stacked per-round pytree
                    # carries the 'async_*' counts (v7 'async' events are
                    # per-round, telemetry on or off) and the buffer state
                    # rides the carry.
                    (self.state, bad, self._async_state, stacked) = (
                        self._async_span(self.data, self.state,
                                         jnp.asarray(start, jnp.int32),
                                         int(count), self._async_state))
                    self.last_span_telemetry = (int(start), stacked)
                elif self._traffic_span is not None:
                    # Traffic spans always scan: the host samples the span's
                    # schedule (stateless, pure in (traffic seed, t)) and
                    # each round consumes its row; the watchdog's ladder
                    # decisions land as per-round v11 'traffic' events at
                    # the next host boundary.  Composed faults thread their
                    # state through the same carry.
                    sched = self._traffic_plan(int(start), int(count))
                    self._traffic_events.update(
                        {e["round"]: e for e in sched.events})
                    (self.state, bad, self._fault_state, stacked) = (
                        self._traffic_span(
                            self.data, self.state,
                            jnp.asarray(start, jnp.int32),
                            int(count), jnp.asarray(sched.shard_ids),
                            jnp.asarray(sched.arrived),
                            jnp.asarray(sched.action), self._fault_state))
                    # Without telemetry/faults the stacked pytree is empty —
                    # nothing for the emission loop to fetch.
                    self.last_span_telemetry = (
                        (int(start), stacked)
                        if jax.tree_util.tree_leaves(stacked) else None)
                elif self.faults is not None:
                    # Fault spans always scan (the stacked per-round pytree
                    # carries the 'fault_*' counts even without telemetry).
                    # Hierarchical fault spans additionally consume the
                    # host-planned tier-2 ladder actions (one row per
                    # round; _fault_plan is pure in (fault key, t)).
                    if self._placement is not None:
                        acts = self._fault_plan(int(start), int(count))
                        self.state, bad, self._fault_state, stacked = (
                            self._fault_span(self.data, self.state,
                                             jnp.asarray(start, jnp.int32),
                                             int(count), self._fault_state,
                                             jnp.asarray(acts)))
                    else:
                        self.state, bad, self._fault_state, stacked = (
                            self._fault_span(self.data, self.state,
                                             jnp.asarray(start, jnp.int32),
                                             int(count), self._fault_state))
                    self.last_span_telemetry = (int(start), stacked)
                elif (self.cfg.telemetry or self.cfg.margins
                        or self.cfg.numerics or self._secagg is not None):
                    # secagg, margins and numerics ride the telemetry span
                    # too: their per-round stats (sum-check verdicts /
                    # margin fields / numeric-health counters) must come
                    # back stacked even with cfg.telemetry off, exactly
                    # like the fault counts do under faults.
                    self.state, bad, stacked = self._tele_span(
                        self.data, self.state,
                        jnp.asarray(start, jnp.int32), int(count))
                    self.last_span_telemetry = (int(start), stacked)
                else:
                    self.state, bad = self._fused_span(
                        self.data, self.state, jnp.asarray(start, jnp.int32),
                        jnp.asarray(count, jnp.int32))
            if self._check_attack_nan:
                # the one host sync of a span whose attack can
                # craft a nan: the wait for the device is here
                with host_span("interval.wait_span"):
                    bad = bool(bad)
                if bad:
                    self.state = (self.shardings.place_state(pre_span)
                                  if self.shardings is not None
                                  else jax.tree.map(jnp.asarray, pre_span))
                    if pre_fstate is not None:
                        self._fault_state = jax.tree.map(jnp.asarray,
                                                         pre_fstate)
                    if pre_astate is not None:
                        self._async_state = jax.tree.map(jnp.asarray,
                                                         pre_astate)
                    self._raise_if_attack_nan(bad)
        return self.state

    def run_round(self, t: int) -> ServerState:
        batches = self.stream.get(int(t)) if self._streaming else None
        t_host = int(t)
        t = jnp.asarray(t, jnp.int32)
        self.last_round_stats = None
        self.last_round_telemetry = None
        if not self._staged:
            if self._async is not None:
                (self.state, diag, bad, tele,
                 self._async_state) = self._fused_round(
                    self.data, self.state, t, self._async_state, batches)
            elif self._traffic_span is not None:
                sched = self._traffic_plan(t_host, 1)
                self._traffic_events.update(
                    {e["round"]: e for e in sched.events})
                (self.state, diag, bad, tele,
                 self._fault_state) = self._fused_round(
                    self.data, self.state, t, jnp.asarray(sched.shard_ids[0]),
                    jnp.asarray(sched.arrived[0]),
                    jnp.asarray(sched.action[0]), self._fault_state)
            elif self.faults is not None:
                if self._placement is not None:
                    act = self._fault_plan(t_host, 1)[0]
                    (self.state, diag, bad, tele,
                     self._fault_state) = self._fused_round(
                        self.data, self.state, t, jnp.asarray(act, jnp.int32),
                        self._fault_state, batches)
                else:
                    (self.state, diag, bad, tele,
                     self._fault_state) = self._fused_round(
                        self.data, self.state, t, self._fault_state, batches)
            else:
                self.state, diag, bad, tele = self._fused_round(
                    self.data, self.state, t, batches)
            if diag:
                self.last_round_stats = diag
            if tele:
                self.last_round_telemetry = tele
            self._raise_if_attack_nan(bad)
        else:
            grads = self._compute_grads(self.data, self.state, t, batches)
            tele = (self._attack_envelope(self.data, grads, self.state, t)
                    if self.cfg.telemetry else {})
            pre_attack = grads if self.cfg.margins else None
            grads = self.attacker.apply(grads, self.m_mal,
                                        self._ctx_for(self.state, t))
            if self.cfg.margins:
                tele = {**tele, **self._attack_margins(
                    self.data, pre_attack, grads, self.state, t)}
            if self.cfg.numerics:
                # Staged twin of the fused engine counters (eager —
                # the staged path crosses the host every round anyway).
                tele = {**tele,
                        "num_nonfinite_pre": nonfinite_count(grads),
                        "num_range_log2": norm_dynamic_range(grads)}
            mask = None
            if self.faults is not None:
                grads, mask, self._fault_state, fstats = self._fault_step(
                    self.data, grads, t, self._fault_state)
                tele = {**tele, **fstats}
            if self.cfg.numerics:
                tele = {**tele, "num_nonfinite_post":
                        nonfinite_count(grads, mask=mask)}
            aux = {}
            if (self.cfg.telemetry or self.cfg.margins
                    or getattr(self, "_kernel_numerics", False)):
                # The defense returns its own diagnostics (single
                # distance computation; the Krum mask marks the
                # aggregated row by construction).
                self.state, ddiag = self._aggregate_tele(
                    self.data, self.state, grads, t, mask=mask)
                tele = self._finish_telemetry(tele, grads, ddiag)
                if (self._krum_select_fn is not None
                        and "selection_mask" in ddiag):
                    aux["krum_selected"] = jnp.argmax(
                        ddiag["selection_mask"]).astype(jnp.int32)
                self.last_round_telemetry = tele
            else:
                agg = None
                if (self.cfg.log_round_stats
                        and self._krum_select_fn is not None
                        and self.faults is None):
                    # Eager selection (same knobs as the defense),
                    # aggregate the selected row directly — single
                    # distance computation, same as the fused path.
                    sel = self._krum_select_fn(grads, self.m, self.m_mal)
                    aux["krum_selected"] = sel
                    agg = grads[sel]
                self.state = self._aggregate(self.data, self.state, grads,
                                             t, agg, mask=mask)
                if tele:
                    self.last_round_telemetry = tele
            if self.cfg.numerics:
                tele = {**tele, "num_nonfinite_agg":
                        nonfinite_count(self.state.velocity)}
                self.last_round_telemetry = tele
            if self.cfg.log_round_stats:
                self.last_round_stats = self._round_diagnostics(
                    grads, self.state, t, aux)
        return self.state

    def _shard_static_fields(self):
        """The placement ground truth every 'shard_selection' event
        carries (host-side statics): which defenses ran per tier, the
        megabatch size, and each shard's malicious-row count — what
        the forensics layer (report.py) attributes tier-2 rejections
        against.  Shared with tools/science_gate.py so the gate's
        replayed cells see exactly what a logged run records."""
        pl = self._placement
        return {"defense": self.cfg.defense,
                "tier2_defense": self._tier2_name,
                "megabatch": pl.megabatch,
                "mal_counts": list(pl.mal_counts),
                "mal_placement": self.cfg.mal_placement,
                "tier1_corrupted": self._tier1_f,
                "tier2_corrupted": self._tier2_f}

    def _emit_round_telemetry(self, logger, t, tele):
        """Write one round's telemetry (host values) as 'defense' and
        'attack' events (cfg.telemetry), its 'fault_*' counts as a
        'fault' event, its 'secagg_*' protocol stats as a 'secagg'
        event (both emitted with or without telemetry), its margin
        fields as one schema-v12 'margin' event (cfg.margins — also
        with or without telemetry), its numeric-health counters as one
        schema-v14 'numerics' event (cfg.numerics — likewise
        independent of telemetry), and — for hierarchical rounds —
        its 'shard_*'/'tier2_*' stacks as one schema-v6
        'shard_selection' event; track Krum winners for the
        end-of-run selection histogram."""
        defense_fields, attack_fields = {}, {}
        fault_fields, secagg_fields, shard_fields = {}, {}, {}
        async_fields = {}
        margin_fields, margin_attack, hier_margin = {}, {}, {}
        numerics_fields = {}
        for k, v in tele.items():
            val = _jsonable(v)
            # Margin/numerics prefixes are checked FIRST:
            # 'defense_margin_*' / 'shard_margin_*' / 'tier2_margin_*'
            # (and the num_ twins) would otherwise be swallowed by the
            # defense/shard branches below.
            if k.startswith("defense_margin_"):
                margin_fields[k[len("defense_"):]] = val
            elif k.startswith("margin_attack_"):
                margin_attack[k[len("margin_attack_"):]] = val
            elif k.startswith(("shard_margin_", "tier2_margin_")):
                hier_margin[k] = val
            elif k.startswith("defense_num_"):
                # Kernel tie/cancellation counters: 'defense_num_x'
                # lands as bare 'x' in the v14 'numerics' event.
                numerics_fields[k[len("defense_num_"):]] = val
            elif k.startswith(("shard_num_", "tier2_num_")):
                # Hier stacks keep their tier prefix, drop 'num_':
                # 'shard_num_tie_rows' -> 'shard_tie_rows'.
                tier, rest = k.split("num_", 1)
                numerics_fields[tier + rest] = val
            elif k.startswith("num_"):
                # Engine-level health counters.
                numerics_fields[k[len("num_"):]] = val
            elif k.startswith("attack_"):
                attack_fields[k[len("attack_"):]] = val
            elif k.startswith("async_"):
                # v7 'async' record: scalar counts land as ints, the
                # staleness histogram / weight-mass vectors as lists.
                async_fields[k[len("async_"):]] = (
                    int(val) if isinstance(val, float)
                    and float(val).is_integer() else val)
            elif k.startswith("fault_"):
                # Scalar counts land as ints; the hierarchical
                # per-shard survivor vector ('fault_shard_alive',
                # (S,)) as an int list.
                fault_fields[k[len("fault_"):]] = (
                    [int(x) for x in val] if isinstance(val, list)
                    else int(val))
            elif k.startswith("secagg_"):
                # Scalar counts/flags land as ints, the groupwise
                # sum-norm vector as a float list.
                secagg_fields[k[len("secagg_"):]] = (
                    int(val) if isinstance(val, float)
                    and float(val).is_integer() else val)
            elif k.startswith(("shard_", "tier2_")):
                # Hierarchical forensics stacks keep their tier prefix
                # — 'shard_selection_mask' (S, m) and
                # 'tier2_selection_mask' (S,) are different axes of
                # the same round and land in one event.
                shard_fields[k] = val
            elif k.startswith("defense_"):
                defense_fields[k[len("defense_"):]] = val
            else:
                defense_fields[k] = val  # population stats
        if fault_fields:
            logger.record(kind="fault", round=int(t), **fault_fields)
        if async_fields:
            logger.record(kind="async", round=int(t), **async_fields)
        if secagg_fields:
            logger.record(kind="secagg", round=int(t), **secagg_fields)
        if self.cfg.margins and (margin_fields or margin_attack
                                 or hier_margin):
            # One schema-v12 'margin' event per round: the bare defense
            # margin fields + the colluder-survival rollups
            # (utils/margins.py), the attack's envelope utilization
            # ('attack_*'), the hierarchical stacks with their own
            # rollups, and — when a traffic schedule rides along — the
            # round's effective-f (the traffic event itself is popped
            # AFTER this emission in both run loops, so the join reads
            # it in place).
            from attacking_federate_learning_tpu.utils.margins import (
                margin_rollups, hier_margin_rollups, tier2_margin_rollups
            )
            ev = dict(margin_fields)
            ev.update(margin_rollups(margin_fields, self.m_mal))
            for mk, mv in margin_attack.items():
                ev["attack_" + mk] = mv
            if hier_margin:
                ev.update(hier_margin)
                shard_stacks = {k[len("shard_"):]: v
                                for k, v in hier_margin.items()
                                if k.startswith("shard_margin_")}
                tier2_fields = {k[len("tier2_"):]: v
                                for k, v in hier_margin.items()
                                if k.startswith("tier2_margin_")}
                if shard_stacks:
                    mal_counts = list(self._placement.mal_counts)
                    for rk, rv in hier_margin_rollups(
                            shard_stacks, mal_counts).items():
                        ev["shard_" + rk] = rv
                if tier2_fields:
                    colluder_shards = [c > 0 for c in
                                       self._placement.mal_counts]
                    for rk, rv in tier2_margin_rollups(
                            tier2_fields, colluder_shards).items():
                        ev["tier2_" + rk] = rv
            if self.traffic is not None:
                tr = self._traffic_events.get(int(t))
                if tr is not None and "f_eff" in tr:
                    ev["f_eff"] = int(tr["f_eff"])
            logger.record(kind="margin", round=int(t),
                          defense=self.cfg.defense,
                          malicious_count=self.m_mal, **ev)
        if self.cfg.numerics and numerics_fields:
            # One schema-v14 'numerics' event per round: engine-level
            # health counters (nonfinite by stage, norm dynamic range),
            # the kernel tie/cancellation counters (flat or as hier
            # shard_/tier2_ stacks), and the host rollups
            # (utils/numerics.py — nonfinite_total, tie_locked), all
            # stamped with the tie band they were measured at.
            from attacking_federate_learning_tpu.utils.numerics import (
                TIE_BAND_ULPS, numerics_rollups
            )
            nev = dict(numerics_fields)
            nev.update(numerics_rollups(numerics_fields))
            logger.record(kind="numerics", round=int(t),
                          defense=self.cfg.defense,
                          tie_band_ulps=TIE_BAND_ULPS, **nev)
        if not self.cfg.telemetry:
            return
        if shard_fields:
            logger.record(kind="shard_selection", round=int(t),
                          **self._shard_static_fields(), **shard_fields)
        if defense_fields:
            logger.record(kind="defense", round=int(t),
                          defense=self.cfg.defense,
                          malicious_count=self.m_mal, **defense_fields)
        if attack_fields:
            logger.record(kind="attack", round=int(t),
                          attack=self.attacker.name, **attack_fields)
        mask = defense_fields.get("selection_mask")
        if mask is not None and self._krum_select_fn is not None:
            # Krum: one-hot mask -> winner id for the selection histogram.
            self._telemetry_winners.append(
                int(max(range(len(mask)), key=mask.__getitem__)))

    def _emit_selection_hist(self, logger):
        """End-of-run 'selection_hist' event: the GRID_RESULTS top-1-
        share analysis, emitted by the engine instead of hand-rolled
        drivers (tools/femnist_style_study.py pre-telemetry)."""
        import collections

        wins = self._telemetry_winners
        if not wins:
            return
        counts = collections.Counter(wins)
        top1_client, top1 = counts.most_common(1)[0]
        logger.record(
            kind="selection_hist", defense=self.cfg.defense,
            counts={str(k): v for k, v in sorted(counts.items())},
            rounds=len(wins), distinct_winners=len(counts),
            top1_share=round(top1 / len(wins), 4),
            top1_client=top1_client,
            malicious_picks=sum(1 for w in wins if w < self.m_mal))

    def run(self, logger: Optional[RunLogger] = None,
            checkpointer=None, timer=None, journal=None,
            shutdown=None) -> dict:
        """Full experiment loop (reference main.py:64-95).

        ``timer``: an optional utils.profiling.PhaseTimer; per-phase
        wall-clock (round / eval, device-synchronized) is accumulated and
        written as a structured record at the end (the reference's only
        timing artifact is one timestamp, main.py:97).

        ``journal``: an optional utils.lifecycle.RunJournal — rounds and
        evals are committed at host boundaries with exactly-once
        semantics across restarts, and per-round event emission is
        gated by the journal's high-water mark so a resumed run never
        re-emits what a previous attempt already recorded.  None (the
        default) leaves every pre-lifecycle caller untouched.

        ``shutdown``: an optional utils.lifecycle.GracefulShutdown; its
        request flag is polled at each span boundary — when set, the
        engine auto-checkpoints, records a 'lifecycle' preempt event,
        marks the journal 'preempted' and raises
        utils.lifecycle.Preempted (the CLI maps it to exit code 75).

        Logger ownership: a logger the engine creates itself is managed
        with ``with`` (crash-safe close — JSONL handle closed, accuracy
        CSV written even if the loop raises); a caller-provided logger is
        ``finish()``ed on success as before, and the caller's own
        ``with`` (cli.py) covers the crash path."""
        import contextlib

        cfg = self.cfg
        own_logger = logger is None
        logger = logger or RunLogger(cfg, cfg.output, cfg.log_dir)
        test_size = self.dataset.test_y.size    # samples, or tokens
        self._telemetry_winners = []
        self.wall_booking_failures = 0

        def phase(name, sync=None):
            if timer is None:
                return contextlib.nullcontext()
            return timer.phase(name,
                               sync_on=sync or (lambda: self.state.weights))

        with contextlib.ExitStack() as stack:
            if own_logger:
                stack.enter_context(logger)
            return self._run_body(logger, checkpointer, timer, phase,
                                  test_size, journal, shutdown)

    def _preempt(self, logger, checkpointer, epoch, journal, shutdown):
        """Honor a graceful-shutdown request at a span boundary: persist
        an auto-checkpoint (creating a Checkpointer if the caller runs
        without one — a preempt that loses the run would defeat the
        point), flush a 'lifecycle' preempt event, mark the journal and
        raise Preempted (utils/lifecycle.py)."""
        from attacking_federate_learning_tpu.utils.checkpoint import (
            Checkpointer
        )
        from attacking_federate_learning_tpu.utils.lifecycle import (
            EXIT_PREEMPTED, Preempted
        )

        ck = checkpointer or Checkpointer(
            self.cfg,
            auto_dir=journal.dir if journal is not None else None)
        path = ck.save_auto(self.state, extra=self.fault_state_host())
        source = shutdown.source or "signal"
        logger.record(kind="lifecycle", phase="preempt", round=int(epoch),
                      source=source, checkpoint=path,
                      attempt=journal.attempt if journal is not None else 1)
        logger.print(f"!! preempted ({source}) after round {epoch}; "
                     f"state checkpointed to {path}; "
                     f"exiting {EXIT_PREEMPTED} (resumable)")
        if journal is not None:
            journal.finish("preempted", EXIT_PREEMPTED, checkpoint=path)
            journal.close()
        raise Preempted(epoch, source)

    def _run_body(self, logger, checkpointer, timer, phase, test_size,
                  journal=None, shutdown=None):
        cfg = self.cfg
        if cfg.backdoor:
            # Pre-training accuracy line (reference main.py:45-51).
            loss0, correct0 = self.evaluate(self.state.weights)
            logger.print(
                "\nBEFORE: Test set. Average loss: {:.4f}, Accuracy: {}/{} "
                "({:.2f}%)".format(float(loss0), int(correct0), test_size,
                                   100.0 * float(correct0) / test_size))
        else:
            logger.print("\nStarting Training...")

        # Resume-aware: a restored ServerState carries its round counter
        # (utils/checkpoint.py), so the loop continues where it stopped.
        # When the attack is fusable and no per-round observability is
        # requested, all rounds between eval points run as ONE scanned
        # device program (run_span); eval cadence is identical either way.
        use_spans = (not self._staged and not cfg.log_round_stats
                     and timer is None and not self._streaming)
        ckpt_every = cfg.checkpoint_every
        watchdog_on = self.faults is not None and self.faults.watchdog
        self._rollbacks = 0
        if watchdog_on or ckpt_every:
            # Last-good snapshot: the rollback target until the first
            # auto-checkpoint boundary replaces it.
            self._last_good = (self._host_copy(self.state),
                               self.fault_state_host())
        epoch = int(self.state.round)
        start_epoch = epoch
        last_asr = None
        if journal is not None:
            attempt = journal.start_attempt(epoch)
            phase_name = ("start" if attempt == 1 and epoch == 0
                          else "resume")
            logger.record(kind="lifecycle", phase=phase_name,
                          round=epoch, attempt=attempt,
                          replay_high=journal.high)
            if phase_name == "resume":
                logger.print(
                    f"[lifecycle] attempt {attempt} resumes at round "
                    f"{epoch} (journal high-water {journal.high}: "
                    f"replayed rounds/evals are not re-recorded)")

        def fresh(t):
            # Exactly-once event emission across restarts: a round at or
            # below the journal's high-water mark was already recorded
            # by the attempt that committed it (deterministic replay
            # recomputes the identical values — re-emitting would
            # double-count them downstream).
            return journal is None or journal.fresh_round(t)

        # Measured-walls observatory (cfg.profile_every > 0, span paths
        # only — the per-round paths already carry --profile's
        # PhaseTimer): every span is timed on the host clock at its
        # existing boundary, and every K-th eval interval additionally
        # runs under a profiler capture booked onto the stage set
        # (utils/walls.py).  Off (the default), none of this executes —
        # no extra syncs, no events, and the compiled programs are
        # pinned byte-identical either way (tests/test_walls.py).
        prof_k = int(cfg.profile_every or 0)
        walls_interval = 0
        loop_t0 = time.perf_counter()
        # Host phases of the loop (``interval.*``) go to the process-wide
        # recorder (utils/profiling.py): clock pairs only, no sync and no
        # per-interval write; this run's totals leave once, in the
        # 'profile' event at its end.
        spans_before = RECORDER.snapshot()

        while epoch < cfg.epochs:
            if use_spans:
                # Advance to the next eval boundary in one device
                # program; auto-checkpoint boundaries clip the span too
                # (a span must not run past its own checkpoint cadence).
                if epoch % cfg.test_step == 0:
                    boundary = epoch
                else:
                    boundary = min((epoch // cfg.test_step + 1)
                                   * cfg.test_step, cfg.epochs - 1)
                if ckpt_every:
                    # Same boundary quirk as the eval cadence above: at
                    # a checkpoint epoch the span is one round, so the
                    # save below runs right after it.
                    boundary = min(boundary,
                                   epoch if epoch % ckpt_every == 0
                                   else (epoch // ckpt_every + 1)
                                   * ckpt_every)
                count = boundary - epoch + 1
                if prof_k > 0:
                    from attacking_federate_learning_tpu.utils import (
                        profiling as _prof
                    )

                    profiled = walls_interval % prof_k == 0
                    walls_interval += 1
                    trace_dir = (os.path.join(logger.log_dir,
                                              "walltrace", f"r{epoch}")
                                 if profiled else None)
                    t_span = time.perf_counter()
                    with _prof.xla_trace(trace_dir):
                        self.run_span(epoch, count)
                        # The sync the host wall needs; the span paths
                        # fetch at this boundary anyway, so nothing new
                        # crosses in-jit.
                        with host_span("interval.wait_span"):
                            jax.block_until_ready(self.state.weights)
                    span_wall = time.perf_counter() - t_span
                    logger.record(
                        kind="wall", source="host",
                        name=self._span_entry_name(), round=int(epoch),
                        rounds=int(count), wall_s=round(span_wall, 6),
                        rounds_per_s=(round(count / span_wall, 4)
                                      if span_wall > 0 else 0.0))
                    if trace_dir is not None:
                        self._book_span_walls(logger, trace_dir, count)
                else:
                    self.run_span(epoch, count)
                if ((cfg.telemetry or cfg.margins or cfg.numerics
                        or self.faults is not None
                        or self._secagg is not None
                        or self._async is not None)
                        and self.last_span_telemetry is not None):
                    # ONE host fetch per eval interval: the whole stacked
                    # telemetry pytree comes over at the eval boundary.
                    with host_span("interval.fetch_telemetry"):
                        t0, stacked = self.last_span_telemetry
                        host = jax.tree.map(np.asarray, stacked)
                        for i in range(boundary - epoch + 1):
                            if fresh(t0 + i):
                                self._emit_round_telemetry(
                                    logger, t0 + i,
                                    jax.tree.map(lambda a: a[i], host))
                        self.last_span_telemetry = None
                if self.traffic is not None and self._traffic_events:
                    # Traffic events are host-born (the schedule knows
                    # arrivals and ladder actions before the device
                    # runs) — emitted at the same exactly-once boundary
                    # as the fetched telemetry.
                    with host_span("interval.traffic_events"):
                        for tt in range(epoch, boundary + 1):
                            ev = self._traffic_events.pop(tt, None)
                            if ev is not None and fresh(tt):
                                logger.record(kind="traffic", **ev)
                if journal is not None:
                    with host_span("interval.journal"):
                        journal.commit_rounds(epoch, boundary)
                epoch = boundary
            else:
                with phase("round"):
                    self.run_round(epoch)
                if (cfg.log_round_stats and fresh(epoch)
                        and self.last_round_stats is not None):
                    logger.record(kind="round", round=epoch,
                                  **{k: float(v) for k, v in
                                     self.last_round_stats.items()})
                if ((cfg.telemetry or cfg.margins or cfg.numerics
                        or self.faults is not None
                        or self._secagg is not None
                        or self._async is not None)
                        and fresh(epoch)
                        and self.last_round_telemetry is not None):
                    self._emit_round_telemetry(
                        logger, epoch,
                        jax.tree.map(np.asarray,
                                     self.last_round_telemetry))
                if self.traffic is not None and self._traffic_events:
                    ev = self._traffic_events.pop(epoch, None)
                    if ev is not None and fresh(epoch):
                        logger.record(kind="traffic", **ev)
                if journal is not None:
                    journal.commit_rounds(epoch, epoch)

            if watchdog_on and self._diverged():
                # Graceful degradation: restore the last good state and
                # re-run from there instead of aborting (bounded by
                # max_rollbacks); the eval below never sees the
                # diverged weights.
                self._rollback(logger, epoch, checkpointer)
                epoch = int(self.state.round)
                continue

            if ((epoch % cfg.test_step == 0 or epoch == cfg.epochs - 1)
                    and (journal is None or journal.fresh_eval(epoch))):
                # Replayed evals (journal) are skipped entirely: eval is
                # pure observation of the deterministically-recomputed
                # state, so re-running it would only duplicate 'eval'
                # events and waste the resumed attempt's time.
                # The lambda reads `correct` after the block assigns it, so
                # the timer blocks on the eval outputs, not stale state.
                t_eval = time.perf_counter()
                with host_span("interval.dispatch_eval"), \
                        phase("eval", lambda: correct):
                    test_loss, correct = self.evaluate(self.state.weights)
                # record_eval's first statement converts the outputs, so
                # this block adds no sync: it only separates waiting for
                # the device (the span and the eval) from logging.
                with host_span("interval.wait_device"):
                    jax.block_until_ready((test_loss, correct))
                if prof_k > 0:
                    # Host eval wall (source='host').
                    logger.record(kind="wall", source="host",
                                  name="eval", round=int(epoch),
                                  wall_s=round(
                                      time.perf_counter() - t_eval, 6))
                with host_span("interval.log"):
                    accuracy = logger.record_eval(epoch, test_loss,
                                                  correct, test_size)
                if (accuracy > cfg.checkpoint_acc_threshold
                        and checkpointer is not None):
                    # Carry state rides EVERY checkpoint (not just the
                    # autos): --resume picks the newest by round, and a
                    # best-accuracy save that tied an auto would
                    # otherwise silently drop the async buffers / fault
                    # ring on resume.
                    with host_span("interval.checkpoint"):
                        checkpointer.save(self.state, accuracy,
                                          extra=self.carry_state_host())
                if cfg.backdoor and hasattr(self.attacker, "test_asr"):
                    # Post-aggregation backdoor check, printed after the
                    # accuracy line as in the reference (main.py:91-95).
                    with host_span("interval.log"):
                        asr = self.attacker.test_asr(
                            self.state.weights, logger=logger, tag="POST")
                        last_asr = float(asr)
                        logger.record(kind="asr", round=epoch,
                                      attack_success_rate=last_asr)
                if journal is not None:
                    with host_span("interval.journal"):
                        journal.commit_eval(epoch)
            if ckpt_every and epoch % ckpt_every == 0:
                # Periodic auto-checkpoint (atomic + rotated,
                # utils/checkpoint.py) — the watchdog above has already
                # certified this state, so it also becomes the new
                # in-memory last-good rollback target.
                with host_span("interval.checkpoint"):
                    self._last_good = (self._host_copy(self.state),
                                       self.fault_state_host())
                    if checkpointer is not None:
                        checkpointer.save_auto(self.state,
                                               extra=self._last_good[1])
            if shutdown is not None:
                with host_span("interval.poll"):
                    preempt = shutdown.should_preempt(start_epoch, epoch)
                if preempt:
                    # Span boundary = the only place a checkpoint is
                    # coherent (state.round == epoch + 1, fault ring
                    # buffer at the matching phase); a signal that landed
                    # mid-span waited here.
                    self._preempt(logger, checkpointer, epoch, journal,
                                  shutdown)
            epoch += 1

        if self.cfg.telemetry:
            self._emit_selection_hist(logger)
        phases = RECORDER.summary(since=spans_before)
        if timer is not None:
            phases.update(timer.summary())
        logger.record(kind="profile", phases=phases)
        if self._streaming:
            # Did the host gather/transfer sit on the round path?
            # (VERDICT r2 #3's stream-stall measurement; near-zero stall
            # per get means the prefetch pipeline kept up.)
            logger.record(kind="stream", **self.stream.stall_stats())
        if journal is not None:
            logger.record(kind="lifecycle", phase="complete",
                          round=int(self.state.round) - 1,
                          attempt=journal.attempt)
            # Registry stamp (PR 5, utils/registry.py): the manifest
            # becomes the run's queryable summary — trajectory
            # endpoints, the event-log join path, and the full config
            # (what 'runs diff' reads for config deltas) — and one
            # index line is appended so the finished run is resolvable
            # without a rescan.  A v4 'registry' event mirrors the
            # stamp into the event log itself.
            import dataclasses as _dc

            from attacking_federate_learning_tpu.utils.lifecycle import (
                run_id_for
            )
            from attacking_federate_learning_tpu.utils.registry import (
                RunRegistry
            )

            summary = {"events": os.path.abspath(logger.jsonl_path)}
            # Headline wall summary (always-on, sync-free: total loop
            # wall over committed rounds) — the campaign table's time
            # column reads this off the registry entry.
            rounds_done = int(self.state.round) - start_epoch
            loop_wall = time.perf_counter() - loop_t0
            if rounds_done > 0 and loop_wall > 0:
                summary["rounds_per_s"] = round(rounds_done / loop_wall,
                                                4)
            if logger.accuracies:
                summary["final_accuracy"] = round(
                    float(logger.accuracies[-1]), 4)
                summary["max_accuracy"] = round(
                    float(max(logger.accuracies)), 4)
            if last_asr is not None:
                summary["final_asr"] = round(last_asr, 4)
            logger.record(kind="registry", run_id=journal.run_id,
                          rounds=int(self.state.round), **summary)
            journal.finish("done",
                           config=_dc.asdict(cfg),
                           config_hash=run_id_for(cfg).rsplit("_", 1)[-1],
                           **summary)
            journal.close()
            try:
                reg = RunRegistry(cfg.run_dir)
                reg.stamp(reg._entry_for_run(journal.run_id,
                                             migrate=False))
            except OSError as e:       # an unwritable index must not
                logger.print(f"[registry] stamp failed: {e}")  # fail a
                #                                           finished run
        if self.wall_booking_failures:
            logger.print(f"[walls] {self.wall_booking_failures} profiled "
                         f"span(s) produced no wall booking")
        logger.finish()
        return {"accuracies": logger.accuracies,
                "epochs": logger.accuracies_epochs,
                "final_weights": self.state.weights,
                "wall_booking_failures": self.wall_booking_failures}
