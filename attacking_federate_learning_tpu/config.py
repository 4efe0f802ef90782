"""Experiment configuration.

One dataclass surfaces every knob of the reference, including the constants
hardcoded after argparse in reference main.py:138-149 (momentum 0.9,
mal_epochs 5, alpha 4, per-dataset fading_rate) and defaults buried in
signatures (reference main.py:12 batch_size=83 vs CLI default 128;
backdoor.py:14 BackdoorAttack(batch_size=200, learning_rate=0.1)).

Reference-behavior parity quirks (SURVEY.md §2.4) are explicit flags with the
reference behavior as the default, so a run is reproducible against the
reference while the paper-faithful behavior stays one flag away.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


MNIST = "MNIST"
CIFAR10 = "CIFAR10"
CIFAR100 = "CIFAR100"
SYNTH_MNIST = "SYNTH_MNIST"      # MNIST-shaped deterministic synthetic data
SYNTH_CIFAR10 = "SYNTH_CIFAR10"  # CIFAR10-shaped deterministic synthetic data
SYNTH_MNIST_HARD = "SYNTH_MNIST_HARD"  # low-SNR variant for behavioral tests
SYNTH_CIFAR10_HARD = "SYNTH_CIFAR10_HARD"  # low-SNR CIFAR-shaped variant
# Seeded synthetic token contexts for the sequence models (data/datasets.py
# make_synthetic_tokens): ids from a vocabulary slice of 18,992 / of 96.
SYNTH_TOKENS = "SYNTH_TOKENS"
SYNTH_TOKENS_TINY = "SYNTH_TOKENS_TINY"
TOKEN_DATASETS = (SYNTH_TOKENS, SYNTH_TOKENS_TINY)

# Per-dataset LR fading constants, reference main.py:144-149.
FADING_RATES = {CIFAR10: 2000.0, MNIST: 10000.0, CIFAR100: 1500.0,
                SYNTH_MNIST: 10000.0, SYNTH_CIFAR10: 2000.0,
                SYNTH_MNIST_HARD: 10000.0, SYNTH_CIFAR10_HARD: 2000.0}


@dataclasses.dataclass
class FaultConfig:
    """Deterministic client-side fault model (core/faults.py).

    Every rate is a per-client, per-round probability drawn from a PRNG
    keyed on ``(seed, round)`` — the schedule is a pure function of the
    config, so two runs (or a run and its resumed half) inject byte-
    identical faults, and a host-side replay of the draw reproduces the
    exact injected counts (tools/fault_matrix.py validates emitted
    'fault' events against that replay).

    Fault kinds (applied to the SUBMITTED update matrix, after the
    attack seam — the attack owns rows [0, f); corruption is restricted
    to honest rows so the two threat models never alias):

    - ``dropout``: the client returns no update this round.  Its row is
      zeroed and excluded from aggregation via the quarantine mask.
    - ``straggler``: the client submits the gradient it computed
      ``straggler_delay`` rounds ago (carried in a fixed-shape ring
      buffer inside the fused round program).  Stale updates are valid
      — they are aggregated, not quarantined.
    - ``corrupt``: an honest client's row is damaged in flight —
      ``'nan'``/``'inf'`` make it non-finite (caught and quarantined
      pre-aggregation), ``'scale'`` multiplies it by ``corrupt_scale``
      (finite garbage: what the robust aggregation itself — or, failing
      that, the divergence watchdog — must absorb).
    - ``shard_dropout``: the correlated shard-DOMAIN axis
      (hierarchical aggregation only): each megabatch/device domain
      draws a per-round death onset with this probability and stays
      dead for ``shard_dropout_dwell`` consecutive rounds — a whole
      megabatch vanishes at once (rack/device loss), its tier-1
      estimate is excluded from tier-2 through the ``alive_counts``
      seam, and the tier-2 defense-validity watchdog degrades through
      the remask → bounds-valid-fallback → hold ladder
      (core/population.py ordering) when too few shards survive.

    The watchdog fields govern server-side graceful degradation
    (core/engine.py): at span boundaries a non-finite or norm-exploded
    server state triggers a rollback to the last good auto-checkpoint
    (cfg.checkpoint_every) instead of an abort, at most
    ``max_rollbacks`` times.
    """

    dropout: float = 0.0
    straggler: float = 0.0
    corrupt: float = 0.0
    shard_dropout: float = 0.0   # correlated shard-domain death rate
    shard_dropout_dwell: int = 1  # rounds a dead domain stays dead
    straggler_delay: int = 1     # rounds of staleness (ring-buffer depth)
    corrupt_mode: str = "nan"    # 'nan' | 'inf' | 'scale'
    corrupt_scale: float = 1e30  # multiplier for corrupt_mode='scale'
    watchdog: bool = True        # divergence watchdog + rollback
    watchdog_norm: float = 1e8   # ||weights|| explosion threshold
    max_rollbacks: int = 3       # rollback attempts before aborting
    seed: Optional[int] = None   # None -> derived from the experiment seed

    def __post_init__(self):
        for name in ("dropout", "straggler", "corrupt", "shard_dropout"):
            v = getattr(self, name)
            if not (0.0 <= v < 1.0):
                raise ValueError(
                    f"fault {name} rate must be in [0, 1), got {v}")
        if self.shard_dropout_dwell < 1:
            raise ValueError(
                f"shard_dropout_dwell must be >= 1, got "
                f"{self.shard_dropout_dwell}")
        if self.straggler_delay < 1:
            raise ValueError(
                f"straggler_delay must be >= 1, got {self.straggler_delay}")
        if self.corrupt_mode not in ("nan", "inf", "scale"):
            raise ValueError(
                f"corrupt_mode must be 'nan', 'inf' or 'scale', "
                f"got {self.corrupt_mode!r}")
        if self.watchdog_norm <= 0:
            raise ValueError(
                f"watchdog_norm must be > 0, got {self.watchdog_norm}")
        if self.max_rollbacks < 0:
            raise ValueError(
                f"max_rollbacks must be >= 0, got {self.max_rollbacks}")

    @property
    def enabled(self) -> bool:
        return (self.dropout > 0 or self.straggler > 0
                or self.corrupt > 0 or self.shard_dropout > 0)


@dataclasses.dataclass
class TrafficConfig:
    """Population & traffic model (core/population.py).

    ``population`` > 0 turns the subsystem on: each round's cohort is
    sampled from a registry of P clients whose per-client persistent
    state (data-shard archetype, femnist-style transform, reliability,
    churn dwell, latency profile) is derived lazily from counter-based
    PRNG streams — never materialized as a (P,)-sized tensor.  The
    arrival process is a diurnal-modulated base rate with per-client
    blockwise on/off churn (correlated dropout episodes of ~churn_dwell
    rounds) and heavy-tail (Pareto ``latency_tail``) straggler
    latencies feeding the async delivery ring.  The schedule is a pure
    function of ``(TrafficConfig, seed, round)``: replayable on host
    (population.replay_traffic), resume-exact with no carried state.

    The sybil burst window makes participation an attack axis: with
    ``sybil_burst_period`` > 0 colluders arrive only in the first
    ``sybil_burst_width`` rounds of each period, boosted by
    period/width so the AVERAGE arrived-colluder mass matches the
    uniform profile (fixed average f).

    Robustness half: when churn under-fills a round, the
    defense-validity watchdog degrades through a declared ladder —
    re-mask the configured defense to the arrived sub-cohort while its
    bound holds (Krum m_eff >= 2f+3, Bulyan >= 4f+3), else run
    ``fallback_defense``, else hold the round as a no-op — each
    decision a versioned 'traffic' event (schema v11), never a crash
    or a silent invalid aggregate.
    """

    population: int = 0          # P registered clients; 0 = disabled
    rate: float = 0.9            # base per-round arrival probability scale
    diurnal_amp: float = 0.0     # rate modulation amplitude in [0, 1]
    diurnal_period: int = 24     # rounds per diurnal cycle
    reliability_lo: float = 0.6  # per-client reliability spread
    reliability_hi: float = 0.95
    churn_dwell: int = 4         # mean on/off episode length (rounds)
    latency_scale: float = 1.0   # async delay scale (rounds)
    latency_tail: float = 1.5    # Pareto tail index (smaller = heavier)
    sybil_burst_period: int = 0  # 0 = colluders arrive like honest clients
    sybil_burst_width: int = 1   # rounds of each period colluders arrive in
    fallback_defense: str = "Median"  # ladder step 2 kernel
    min_cohort: int = 1          # hold below this many arrivals regardless
    seed: Optional[int] = None   # None -> derived from the experiment seed

    def __post_init__(self):
        if self.population < 0:
            raise ValueError(
                f"traffic population must be >= 0, got {self.population}")
        if self.rate <= 0:
            raise ValueError(f"traffic rate must be > 0, got {self.rate}")
        if not (0.0 <= self.diurnal_amp <= 1.0):
            raise ValueError(
                f"diurnal_amp must be in [0, 1], got {self.diurnal_amp}")
        if self.diurnal_period < 1:
            raise ValueError(
                f"diurnal_period must be >= 1, got {self.diurnal_period}")
        if not (0.0 < self.reliability_lo <= self.reliability_hi <= 1.0):
            raise ValueError(
                f"need 0 < reliability_lo <= reliability_hi <= 1, got "
                f"{self.reliability_lo}/{self.reliability_hi}")
        if self.churn_dwell < 1:
            raise ValueError(
                f"churn_dwell must be >= 1, got {self.churn_dwell}")
        if self.latency_scale <= 0 or self.latency_tail <= 0:
            raise ValueError(
                f"latency_scale and latency_tail must be > 0, got "
                f"{self.latency_scale}/{self.latency_tail}")
        if self.sybil_burst_period < 0:
            raise ValueError(
                f"sybil_burst_period must be >= 0, got "
                f"{self.sybil_burst_period}")
        if self.sybil_burst_period > 0 and not (
                1 <= self.sybil_burst_width <= self.sybil_burst_period):
            raise ValueError(
                f"sybil_burst_width must be in [1, period="
                f"{self.sybil_burst_period}], got {self.sybil_burst_width}")
        if self.fallback_defense not in ("Median", "TrimmedMean",
                                         "NoDefense"):
            raise ValueError(
                f"fallback_defense must be 'Median', 'TrimmedMean' or "
                f"'NoDefense' (the bounds-valid ladder kernels), got "
                f"{self.fallback_defense!r}")
        if self.min_cohort < 1:
            raise ValueError(
                f"min_cohort must be >= 1, got {self.min_cohort}")

    @property
    def enabled(self) -> bool:
        return self.population > 0


@dataclasses.dataclass
class ExperimentConfig:
    # --- topology -------------------------------------------------------
    users_count: int = 10            # reference main.py:118
    mal_prop: float = 0.24           # reference main.py:106
    dataset: str = MNIST             # reference main.py:114
    model: Optional[str] = None      # default: dataset's canonical model

    # --- optimization ---------------------------------------------------
    learning_rate: float = 0.1       # server base lr, reference main.py:127
    fading_rate: Optional[float] = None  # None -> FADING_RATES[dataset]
    momentum: float = 0.9            # reference main.py:138
    batch_size: int = 128            # reference main.py:121
    epochs: int = 300                # rounds, reference main.py:124
    # FedAvg-style local SGD steps per round (beyond-reference; the
    # reference is strictly FedSGD — its client optimizer never steps,
    # user.py:80).  k > 1 clients run k local steps at the faded lr and
    # report (w0 - w_k) divided by the lr the SERVER will multiply back
    # in, so the FedAvg-as-FedSGD reduction is exact
    # (core/client.py:make_client_update_fn).
    local_steps: int = 1

    # --- attack ---------------------------------------------------------
    # ALIE z, reference main.py:109.  'auto' (beyond-reference) resolves
    # at construction to the ALIE paper's z_max via attacks/alie.py:
    # paper_z(n, f), so every consumer (and the CSV name schema) sees
    # the numeric value.
    num_std: "float | str" = 1.5
    backdoor: object = False         # False | 'pattern' | int sample index
    alpha: float = 4.0               # anchor-loss weight, reference main.py:142
    mal_epochs: int = 5              # shadow-net epochs, reference main.py:139
    mal_batch_size: int = 200        # reference backdoor.py:14
    mal_learning_rate: float = 0.1   # shadow SGD lr, reference backdoor.py:132
    mal_weight_decay: float = 1e-4   # reference backdoor.py:132
    # (the reference's shadow-SGD momentum is inert — fresh optimizer per
    # batch, backdoor.py:132 — so it is not a knob here)
    # Fuse the (pure, jitted) shadow-train + clip pipeline into the round
    # program so backdoor rounds run without a per-round host hop; False
    # restores the staged path with the reference's per-round nan guard
    # (backdoor.py:145-152) — fused mode tracks an in-program isnan flag
    # over the crafted rows, raised at the next host boundary.
    backdoor_fused: bool = True

    # --- defense --------------------------------------------------------
    defense: str = "NoDefense"       # reference main.py:112

    # --- hierarchical (two-tier) aggregation ----------------------------
    # 'flat' (the default) is the reference path: one (n, d) gradient
    # matrix, one defense call.  'hierarchical' streams the client axis
    # through lax.scan megabatches of static size `megabatch` (m ≪ n):
    # per-megabatch tier-1 robust estimates (the same mask-aware kernels,
    # `defense` above), then a tier-2 robust reduction over the (n/m, d)
    # estimate matrix (defenses/kernels.py shard_* entries) — the full
    # (n, d) and (n, n) arrays never exist (ops/federated.py;
    # ARCHITECTURE.md "Hierarchical aggregation").  The flat path's
    # compiled HLO is byte-identical with these knobs at any value
    # (tests/test_hierarchy.py pins it).
    aggregation: str = "flat"        # 'flat' | 'hierarchical' | 'async'
    # Megabatch (tier-1 shard) size m; must divide users_count with at
    # least 2 shards.  Peak round memory scales with m·d, not n·d.
    megabatch: int = 0
    # Tier-2 reducer over shard estimates; None = same family as
    # `defense`.  Restricted to the mask-aware kernel set.
    tier2_defense: Optional[str] = None
    # Colluder placement across megabatches — a genuine Byzantine
    # surface, not an implementation detail (ops/federated.py):
    # 'spread' deals the malicious ids [0, f) round-robin over shards,
    # 'concentrated' packs them into the fewest shards.
    mal_placement: str = "spread"
    # Assumed corrupted bounds per tier; None derives the spread-worst-
    # case defaults ceil(f/S) and ceil(f/m) (ops/federated.py
    # tier1_assumed/tier2_assumed).  Explicit values let experiments
    # probe mismatched-assumption regimes (and keep Bulyan's
    # 4f+3 validity satisfiable at small shard counts).
    tier1_corrupted: Optional[int] = None
    tier2_corrupted: Optional[int] = None

    # --- asynchronous buffered rounds (core/async_rounds.py) ------------
    # 'async' is the third engine topology: every client still computes
    # a fresh update each round, but it ARRIVES a PRNG-drawn number of
    # rounds later; the server consumes the first `async_buffer`
    # pending arrivals per round FIFO (FedBuff-style), weighting each
    # delivered row's contribution by its staleness through the
    # mask-aware kernels' `weights=` seam.  All three knobs are inert
    # (ignored, like `megabatch` under flat) unless
    # aggregation='async'; the flat/hierarchical HLO is byte-identical
    # at any value (tests/test_async.py pins it).
    # k: pending updates aggregated per round (FIFO; required >= 1
    # under aggregation='async').
    async_buffer: int = 0
    # Eviction bound: a pending update older than this many rounds is
    # discarded (masked), never aggregated; arrival delays draw
    # uniformly from [0, max_staleness] (ring depth = max_staleness+1).
    async_max_staleness: int = 2
    # Contribution discount by staleness s (core/async_rounds.py):
    # 'none' = 1 (pure first-k), 'poly' = 1/sqrt(1+s) (the FedBuff
    # paper's discount), 'const' = 0.5 for any stale row.
    staleness_weight: str = "none"

    # --- evaluation / io ------------------------------------------------
    test_step: int = 5               # reference main.py:58
    # Measured-walls observatory (utils/walls.py): 0 = off; K > 0 times
    # every span/eval on the host clock at the existing eval-boundary
    # fetch (schema-v10 'wall' events, source='host') and captures one
    # profiler trace per K eval intervals, booked onto the stage set
    # (source='trace'), on whatever backend the run is on
    # (utils/profiling.py:xla_trace); the compiled round programs are
    # pinned byte-identical with this on or off.
    profile_every: int = 0
    checkpoint_acc_threshold: float = 70.0  # reference main.py:84
    output: Optional[str] = None     # tee file, reference main.py:13-18
    log_dir: str = "logs"
    run_dir: str = "runs"
    data_dir: str = "data"           # raw MNIST idx / CIFAR pickle location

    # --- determinism ----------------------------------------------------
    # The reference seeds only the metadata split (random_state=42,
    # user.py:65); everything else (init, shard permutation) is implicit.
    # Here every random choice flows from this seed (SURVEY.md §2.4 #13).
    seed: int = 0

    # --- synthetic dataset sizing (SYNTH_* / air-gapped fallbacks) ------
    # Part of the config (not a CLI side-channel) so checkpoints record
    # them and --resume rebuilds the identical dataset.
    synth_train: int = 10000
    synth_test: int = 2000
    # Tokens a context of a token dataset has (TOKEN_DATASETS); None:
    # the dataset's own length (data/datasets.py TOKEN_SEQ_LEN: 8,192,
    # 24 for the tiny one).
    seq_len: Optional[int] = None

    # --- data partition -------------------------------------------------
    # 'iid' (DistributedSampler-equivalent, reference user.py:49-54) |
    # 'dirichlet' (label skew) | 'femnist_style' (per-client affine
    # input transform over IID shards — the feature-shift axis of
    # SURVEY §7.2 M4's "FEMNIST"; data/partition.py
    # client_style_params).
    partition: str = "iid"
    dirichlet_alpha: float = 0.5
    style_strength: float = 0.25     # 'femnist_style' contrast/brightness
                                     # spread; 0 degenerates to IID

    # --- per-round client participation (beyond-reference) -------------
    # Fraction of clients sampled each round (the reference uses every
    # client every round).  Cohort sizes are STATIC — round(p*f) malicious
    # + the honest remainder — with random identities per round, so jit
    # shapes never change and the rows-[0, f_round) attack invariant
    # holds (core/engine.py:_participants).
    participation: float = 1.0

    # --- train-time augmentation ---------------------------------------
    # Reference parity: only the CIFAR100 train pipeline augments
    # (reflect-pad 4 + RandomCrop(32) + RandomHorizontalFlip, reference
    # data_sets.py:157-166); None follows that rule, True/False overrides.
    data_augment: Optional[bool] = None

    # --- backend / parallelism -----------------------------------------
    backend: str = "auto"            # 'auto' | 'cpu' | 'tpu'
    # 'device' keeps the whole training set in HBM (MNIST/CIFAR fit);
    # 'host_stream' keeps it in host RAM and double-buffers each round's
    # (n, B) batch onto the device (data/stream.py — the beyond-HBM /
    # FEMNIST-scale mode, SURVEY.md §7.3 #5).  Streaming feeds one round
    # per device program, so eval-to-eval span fusion is off in that mode.
    data_placement: str = "device"
    # host_stream pipeline tuning (data/stream.py): how many rounds of
    # batches stay in flight, and whether gather+transfer run on a
    # background thread (workers=1) so the host gather overlaps device
    # compute instead of sitting on the round path.  Defaults reproduce
    # the single-slot async-put double buffer.
    stream_prefetch: int = 1
    stream_workers: int = 0
    mesh_shape: Optional[tuple] = None  # (clients_devices, model_devices);
                                        # None -> all devices on client axis
    grad_dtype: str = "float32"      # dtype of the (n, d) gradient matrix;
                                     # 'bfloat16' halves HBM at large n
                                     # (distances still accumulate in f32)
    # jax.checkpoint the client loss: backward recomputes activations
    # instead of storing (n, B, activations) — the HBM/FLOPs trade for
    # WRN-scale models or very large cohorts (core/client.py).
    remat: bool = False

    # --- reference-parity quirk flags (SURVEY.md §2.4) ------------------
    # Server momentum step uses the *constant* base lr, not the faded lr
    # (reference server.py:89 — the faded lr reaches only the clients'
    # never-stepped optimizers and the attacker's arithmetic).
    server_uses_faded_lr: bool = False
    # Krum scores sum the n-f smallest distances (reference defences.py:26,
    # 33-34) rather than the paper's n-f-2.
    krum_paper_scoring: bool = False
    # Score evaluation strategy: 'sort' (default — the exact evaluator,
    # oracle-verified and cancellation-free under arbitrary attacker
    # magnitudes; from kernels.KRUM_SELECT_MIN_ROWS rows up it selects
    # each row's k-th smallest distance instead of ordering the row,
    # the same sum, chosen by the static n), 'topk'
    # (complement subtraction — cheaper at large n / small f; carries a
    # runtime cancellation guard that re-evaluates via 'sort'
    # whenever the subtraction would lose precision, so it is safe under
    # adversarial magnitudes too — kernels.py:_krum_scores), or 'auto'
    # (pick by shape).  The round-1 CPU bench regression attributed to
    # 'sort' was actually the XLA:CPU gemm — see distance_impl below.
    krum_scoring_method: str = "sort"
    # Distance engine for Krum/Bulyan (defenses/kernels.py):
    #   'auto'      xla inside the engine's traced round programs (a host
    #               round-trip there would cost more than it saves —
    #               core/engine.py:_wire_distance_defense); host BLAS for
    #               eager CPU-backend kernel calls (the bench fallback)
    #   'xla'       Gram matmul + epilogue (ops/distances.py)
    #   'host'      NumPy/BLAS (defenses/host.py; pure_callback in-jit)
    #   'ring'      blockwise ppermute schedule over the clients mesh axis
    #   'allgather' one all_gather + per-device tiles
    # (ring/allgather require a device mesh, parallel/distances.py).
    distance_impl: str = "auto"
    # Distance computation dtype (defenses/kernels.py:_distances_for):
    # 'bfloat16' casts the (n, d) operand for the DISTANCE computation
    # only — the Gram matmul rides the MXU at native bf16 throughput
    # (vs the multi-pass f32 HIGHEST emulation) with f32 accumulation
    # and f32 norms; training numerics are untouched.  An explicit,
    # flagged deviation for the 10k north-star regime; 'float32' (the
    # default) is reference-parity.  Ignored by the 'host' engine.
    distance_dtype: str = "float32"
    # Bulyan selection batching (defenses/kernels.py:bulyan): q>1 is an
    # explicit, flagged relaxation of the reference's strictly sequential
    # selection for the large-n regime — each trip selects the q
    # lowest-scoring clients against the same scores, re-scoring between
    # trips (ceil(set_size/q) trips instead of set_size).  1 = the
    # reference's exact semantics (the default, like every quirk flag).
    bulyan_batch_select: int = 1
    # Bulyan selection engine (defenses/kernels.py:bulyan): 'xla' (the
    # traced fixed-trip loop — reference-exact, compiles into the fused
    # round program), 'host' — the HYBRID exact path for the
    # accelerator at large n: distances stay on the MXU, the (n, n) D
    # ships to the host once for the native O(n^2) incremental
    # selection, and the gather + trimmed mean run back on the device.
    # Opt-in (not auto): host ties resolve by the native comparator
    # (ulp-band only — tests/test_native.py), and the pure_callback
    # marshal is only worth it when set_size sequential XLA trips cost
    # more than one D transfer (the 10k north-star regime).
    bulyan_selection_impl: str = "xla"
    # Bulyan's final trimmed-mean tail: 'xla' (default, bit-stable with
    # the traced path) or 'host' (native column-blocked kernel — the
    # CPU-backend 10k opt-in; at full scale the XLA:CPU stable argsort
    # over the (n-2f, d) selection dominates the whole hybrid).  Same
    # opt-in standard and ulps caveat as trimmed_mean_impl.
    bulyan_trim_impl: str = "xla"
    # Attack statistics over the malicious cohort only (reference
    # malicious.py:14-19), matching the ALIE threat model.

    # --- beyond-reference attack/defense knobs --------------------------
    # Perturbation direction for the min-max/min-sum attacks
    # (attacks/minmax.py): cohort negative std ('std', the NDSS'21 paper's
    # best performer), -sign(mean) ('sign'), or negative unit mean ('unit').
    attack_direction: str = "std"
    # DnC spectral defense constants (defenses/dnc.py) — the most
    # constant-sensitive defense, so its knobs live in the config like
    # every other quirk flag.  Sketch keys derive from (seed, round, iter),
    # so repeat runs with different seeds draw different coordinate
    # subsets (the paper's random-subsampling assumption).
    dnc_iters: int = 5
    dnc_sketch_dim: int = 2048
    dnc_filter_frac: float = 1.5
    # GeoMedian smoothed-Weiszfeld constants (defenses/geomed.py) — same
    # config-surface standard as the DnC knobs above.
    geomed_iters: int = 10
    geomed_eps: float = 1e-6
    # CenteredClip constants (defenses/centeredclip.py, ICML'21): clip
    # radius and fixed re-centering trips.
    cclip_tau: float = 10.0
    cclip_iters: int = 5
    # Coordinate-wise kernels: 'xla' (default — keeps staged/fused
    # rounds on the same kernel, preserving bit-identity) or 'host'
    # (opt-in: the native column-blocked kernels, ~minutes -> ~25 s at
    # the 10k scale on the CPU backend; defenses/kernels.py:trimmed_mean,
    # defenses/median.py).
    trimmed_mean_impl: str = "xla"
    median_impl: str = "xla"

    # --- metadata subsystem (reference C12, vestigial there) ------------
    collect_metadata: bool = False
    metadata_fraction: float = 0.11  # reference user.py:65 test_size=0.11

    # --- faults & recovery (core/faults.py; ARCHITECTURE.md) ------------
    # None (the default) is the zero-fault reference path: the compiled
    # round program is bit-identical to the pre-fault-subsystem one.  A
    # FaultConfig (or an equivalent dict, coerced below) with any rate
    # > 0 turns on in-jit deterministic fault injection + the
    # pre-aggregation quarantine mask + the divergence watchdog.
    faults: Optional[FaultConfig] = None
    # --- population & traffic (core/population.py; ARCHITECTURE.md) -----
    # None (the default) is the resident-cohort reference path: every
    # compiled round program is bit-identical to the pre-population one.
    # A TrafficConfig (or an equivalent dict, coerced below) with
    # population > 0 samples each round's cohort from the lazy client
    # registry, injects correlated churn + the defense-validity
    # degradation ladder (flat), draws async arrival delay from the
    # latency profile (async), and resamples megabatch slots per round
    # (hierarchical).
    traffic: Optional["TrafficConfig"] = None
    # Auto-checkpoint cadence in rounds (0 = off): the engine writes a
    # rotated, atomically-replaced checkpoint-auto-<round>.npz every N
    # rounds (utils/checkpoint.py) — the rollback target for the
    # watchdog and the --resume target after a kill.
    checkpoint_every: int = 0

    # --- secure aggregation (protocols/secagg.py; ARCHITECTURE.md) ------
    # Server-visibility mode for client updates:
    #   'off'       the reference fiction — the server sees every row in
    #               the clear (byte-identical HLO to the pre-protocol
    #               engine, pinned by PERF_BASELINE + tests/test_secagg.py)
    #   'vanilla'   Bonawitz-style pairwise-masked sums inside the fused
    #               round: per-pair counter-based PRNG masks in the
    #               uint32 bitcast domain (bit-exact cancellation), the
    #               server sees only the masked wire + the recovered
    #               sum.  Robust per-client defenses CANNOT run (no
    #               rows to defend over) — NoDefense is required, and a
    #               --fault-dropout round becomes a mask-reconstruction
    #               round (simulated seed-reveal, exact sum recovery).
    #   'groupwise' NET-SA-style group-wise secagg composed with
    #               aggregation='hierarchical': each megabatch's sum is
    #               secure-aggregated (masks within the group, keyed on
    #               global client ids) and the server sees per-GROUP
    #               sums — tier-2 robust kernels (--tier2-defense) run
    #               over the (n/m, d) group-sum matrix.
    secagg: str = "off"

    # --- observability --------------------------------------------------
    # Per-round structured diagnostics (gradient-norm stats, aggregate
    # norm, faded lr) written to the JSONL log.  The reference logs only
    # eval-time accuracy (SURVEY.md §5).
    log_round_stats: bool = False
    # Aggregation forensics (utils/metrics.py event schema): defenses
    # return their fixed-shape diagnostics pytrees (Krum/Bulyan selection
    # masks + scores, trim fractions, clip counts, FLTrust trust scores;
    # defenses/kernels.py telemetry seam), attacks their envelope stats
    # (ALIE z-bounds, backdoor shadow loss; attacks/base.py
    # envelope_stats), plus per-client norms and cosine-to-mean — all
    # carried as auxiliary outputs of the jitted round, stacked across
    # rounds and fetched once per eval interval (NO host callbacks
    # inside the jit), then written as 'defense'/'attack'/
    # 'selection_hist' events.  Under aggregation='hierarchical' the
    # same flag threads the stacked per-shard tier-1 diagnostics and
    # the tier-2 shard-selection record out of the scanned round as
    # 'shard_selection' events (schema v6; read with 'report
    # forensics'); under --secagg groupwise only the tier-2
    # (group-sum-level) view appears — per-client rows are not
    # server-visible there.  Off by default: the compiled round
    # program is bit-identical to the pre-telemetry one.
    telemetry: bool = False
    # Robustness-margin observatory (utils/margins.py; ISSUE 18): the
    # defenses additionally return their DECISION MARGINS — Krum's
    # winner/runner-up gap and every row's signed distance to the
    # selection threshold, the trim kernels' per-coordinate boundary
    # distance and kept-coordinate fractions, Bulyan's per-iteration
    # selection slack — as fixed-shape fields riding the same telemetry
    # diagnostics pytree (no host callbacks in-jit), and attacks their
    # envelope utilization (attacks/base.py margin_stats).  The engine
    # rolls them up host-side into one 'margin' event per round (schema
    # v12): the colluder-survival ledger ('runs margins' renders the
    # trajectories).  Requires a margin-bearing defense (Krum /
    # TrimmedMean / Median / Bulyan) on the on-device score path —
    # host-marshalled impls never materialize the scores the margins
    # are read from.  Off by default: the compiled round program is
    # bit-identical to the margins-less one (PERF_BASELINE pins this).
    margins: bool = False
    # Numerics & determinism observatory (utils/numerics.py; ISSUE 20):
    # in-jit numeric health counters — per-stage nonfinite counts
    # (post-attack wire / post-quarantine / applied update), the
    # gradient-norm dynamic range, the distance-Gram cancellation-depth
    # estimate, and tie-proximity counters that band the PR 18 margin
    # tensors at k ulp of their decision boundary (no new O(n^2 d)
    # reductions) — emitted as one schema-v14 'numerics' event per
    # round ('runs numerics' renders the health trajectories).  Works
    # with any defense (the stage counters are defense-free); on a
    # margin-bearing defense the kernels additionally report tie_rows /
    # cancel_bits, which needs the same on-device score path --margins
    # does.  Off by default: the compiled round program is bit-identical
    # to the numerics-less one (PERF_BASELINE pins this).
    numerics: bool = False

    def __post_init__(self):
        if self.model is not None and self.model in MODEL_FAMILY:
            want = DATASET_FAMILY.get(self.dataset)
            if want is not None and MODEL_FAMILY[self.model] != want:
                raise ValueError(
                    f"model {self.model!r} expects {MODEL_FAMILY[self.model]}"
                    f"-shaped inputs but dataset {self.dataset!r} is "
                    f"{want}-shaped")
        if self.seq_len is not None and (
                self.dataset not in TOKEN_DATASETS or self.seq_len < 2):
            raise ValueError(
                f"seq_len={self.seq_len!r} needs a token dataset "
                f"{TOKEN_DATASETS} and at least 2 tokens a context")
        if self.grad_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"grad_dtype must be 'float32' or 'bfloat16', got "
                f"{self.grad_dtype!r}")
        if self.krum_scoring_method not in ("sort", "topk", "auto"):
            raise ValueError(
                f"krum_scoring_method must be 'sort', 'topk' or 'auto', "
                f"got {self.krum_scoring_method!r}")
        if self.distance_impl not in ("auto", "xla", "host", "ring",
                                      "allgather"):
            raise ValueError(
                f"distance_impl must be one of auto/xla/host/ring/"
                f"allgather, got {self.distance_impl!r}")
        if self.distance_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"distance_dtype must be 'float32' or 'bfloat16', "
                f"got {self.distance_dtype!r}")
        if self.data_placement not in ("device", "host_stream"):
            raise ValueError(
                f"data_placement must be 'device' or 'host_stream', "
                f"got {self.data_placement!r}")
        if self.stream_prefetch < 1 or self.stream_workers not in (0, 1):
            raise ValueError(
                f"stream_prefetch must be >= 1 and stream_workers 0 or 1, "
                f"got {self.stream_prefetch}/{self.stream_workers}")
        if self.mesh_shape is not None:
            # Normalized to a tuple so a JSON campaign spec's list and
            # the CLI's tuple hash to the same run/cell identity.
            ms = tuple(self.mesh_shape)
            if len(ms) != 2 or any(
                    not isinstance(x, int) or x < 1 for x in ms):
                raise ValueError(
                    f"mesh_shape must be two positive ints "
                    f"(clients_devices, model_devices), "
                    f"got {self.mesh_shape!r}")
            self.mesh_shape = ms
        if self.bulyan_batch_select < 1:
            raise ValueError(
                f"bulyan_batch_select must be >= 1, got "
                f"{self.bulyan_batch_select}")
        if self.bulyan_selection_impl not in ("xla", "host"):
            raise ValueError(
                f"bulyan_selection_impl must be 'xla' or 'host', "
                f"got {self.bulyan_selection_impl!r}")
        if self.bulyan_trim_impl not in ("xla", "host"):
            raise ValueError(
                f"bulyan_trim_impl must be 'xla' or 'host', "
                f"got {self.bulyan_trim_impl!r}")
        if self.attack_direction not in ("std", "sign", "unit"):
            raise ValueError(
                f"attack_direction must be 'std', 'sign' or 'unit', "
                f"got {self.attack_direction!r}")
        if self.dnc_iters < 1 or self.dnc_sketch_dim < 1:
            raise ValueError(
                f"dnc_iters/dnc_sketch_dim must be >= 1, got "
                f"{self.dnc_iters}/{self.dnc_sketch_dim}")
        if self.dnc_filter_frac <= 0:
            raise ValueError(
                f"dnc_filter_frac must be > 0, got {self.dnc_filter_frac}")
        if self.cclip_iters < 1 or self.cclip_tau <= 0:
            raise ValueError(
                f"cclip_iters must be >= 1 and cclip_tau > 0, got "
                f"{self.cclip_iters}/{self.cclip_tau}")
        if self.geomed_iters < 1 or self.geomed_eps <= 0:
            raise ValueError(
                f"geomed_iters must be >= 1 and geomed_eps > 0, got "
                f"{self.geomed_iters}/{self.geomed_eps}")
        if self.trimmed_mean_impl not in ("xla", "host"):
            raise ValueError(
                f"trimmed_mean_impl must be 'xla' or 'host', "
                f"got {self.trimmed_mean_impl!r}")
        if self.median_impl not in ("xla", "host"):
            raise ValueError(
                f"median_impl must be 'xla' or 'host', "
                f"got {self.median_impl!r}")
        if self.aggregation not in ("flat", "hierarchical", "async"):
            raise ValueError(
                f"aggregation must be 'flat', 'hierarchical' or "
                f"'async', got {self.aggregation!r}")
        if self.staleness_weight not in ("none", "poly", "const"):
            raise ValueError(
                f"staleness_weight must be 'none', 'poly' or 'const', "
                f"got {self.staleness_weight!r}")
        if self.async_buffer < 0 or self.async_max_staleness < 0:
            raise ValueError(
                f"async_buffer/async_max_staleness must be >= 0, got "
                f"{self.async_buffer}/{self.async_max_staleness}")
        if self.aggregation == "async" and self.async_buffer < 1:
            raise ValueError(
                "--aggregation async needs --async-buffer >= 1 (k, the "
                "pending updates aggregated per round — FedBuff's "
                "buffer size; core/async_rounds.py)")
        if self.mal_placement not in ("spread", "concentrated"):
            raise ValueError(
                f"mal_placement must be 'spread' or 'concentrated', "
                f"got {self.mal_placement!r}")
        if self.megabatch < 0:
            raise ValueError(f"megabatch must be >= 0, got {self.megabatch}")
        _TIER2 = ("NoDefense", "Krum", "TrimmedMean", "Bulyan", "Median")
        if self.tier2_defense is not None and self.tier2_defense not in _TIER2:
            raise ValueError(
                f"tier2_defense must be one of {_TIER2}, "
                f"got {self.tier2_defense!r}")
        for name in ("tier1_corrupted", "tier2_corrupted"):
            v = getattr(self, name)
            if v is not None and v < 0:
                raise ValueError(f"{name} must be >= 0, got {v}")
        if self.aggregation == "hierarchical":
            if self.megabatch < 1:
                raise ValueError(
                    "hierarchical aggregation needs megabatch >= 1 "
                    "(the tier-1 shard size; --megabatch)")
            if self.users_count % self.megabatch:
                raise ValueError(
                    f"megabatch must divide users_count "
                    f"({self.users_count} % {self.megabatch} != 0)")
            if self.users_count // self.megabatch < 2:
                raise ValueError(
                    f"hierarchical aggregation needs >= 2 shards "
                    f"(n={self.users_count}, m={self.megabatch})")
        if isinstance(self.faults, dict):
            # Checkpoint-JSON round trips and kwargs-style callers hand
            # a plain dict; coerce so every consumer sees a FaultConfig.
            self.faults = FaultConfig(**self.faults)
        if isinstance(self.traffic, dict):
            # Same coercion seam as faults: journal/checkpoint JSON and
            # campaign specs hand plain dicts.
            self.traffic = TrafficConfig(**self.traffic)
        if self.checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every must be >= 0, got "
                f"{self.checkpoint_every}")
        if self.secagg not in ("off", "vanilla", "groupwise"):
            raise ValueError(
                f"--secagg must be 'off', 'vanilla' or 'groupwise', "
                f"got {self.secagg!r}")
        if self.secagg != "off":
            # Secure aggregation inverts the server's visibility: every
            # feature that reads per-client rows server-side is
            # structurally impossible and rejected here, loudly, with
            # the offending flag named (tests/test_secagg.py pins the
            # message contract).
            if self.defense != "NoDefense":
                hint = ("use --secagg groupwise with --tier2-defense to "
                        "defend over per-group sums"
                        if self.secagg == "vanilla" else
                        "move the robust kernel to --tier2-defense (it "
                        "runs over the per-group sums)")
                raise ValueError(
                    f"--secagg {self.secagg}: defense {self.defense!r} "
                    f"cannot run — the server never sees per-client "
                    f"updates, so there are no rows to defend over; "
                    f"set -d NoDefense ({hint})")
            if self.secagg == "vanilla" and self.aggregation != "flat":
                raise ValueError(
                    "--secagg vanilla masks the whole cohort into one "
                    "sum and requires --aggregation flat; use --secagg "
                    "groupwise for the hierarchical composition")
            if self.secagg == "groupwise" and self.aggregation != (
                    "hierarchical"):
                raise ValueError(
                    "--secagg groupwise exposes per-megabatch sums and "
                    "requires --aggregation hierarchical (+ --megabatch)")
            if self.telemetry and self.secagg == "vanilla":
                raise ValueError(
                    "--telemetry is server-side forensics; under "
                    "--secagg vanilla the server sees only one masked "
                    "cohort sum — there is nothing per-client OR "
                    "per-group to observe (groupwise supports "
                    "--telemetry: tier-2 selection over group sums is "
                    "server-visible)")
            if self.log_round_stats and self.secagg == "vanilla":
                raise ValueError(
                    "--round-stats reads per-client gradient norms "
                    "server-side; under --secagg vanilla the server "
                    "sees no per-client rows (groupwise supports "
                    "--round-stats over the per-group sums)")
            if self.backdoor and not self.backdoor_fused:
                raise ValueError(
                    "--backdoor-staged crafts on the host between "
                    "compute and aggregation; --secagg masks inside "
                    "the fused round program (drop --backdoor-staged)")
            if self.participation < 1.0:
                raise ValueError(
                    "--secagg requires --participation 1.0: pairwise "
                    "masks are keyed on client identity, and partial "
                    "cohorts re-key every row each round")
            if self.grad_dtype != "float32":
                raise ValueError(
                    f"--secagg masks in the uint32 bitcast domain of "
                    f"f32 wire updates; grad_dtype={self.grad_dtype!r} "
                    f"is not maskable (set grad_dtype='float32')")
            if self.faults is not None and (self.faults.straggler > 0
                                            or self.faults.corrupt > 0):
                raise ValueError(
                    "--secagg composes only with --fault-dropout / "
                    "--fault-shard-dropout (dropout is the secure-"
                    "aggregation protocol event: a mask-reconstruction "
                    "round; a dead shard domain drops its whole "
                    "group); --fault-straggler/--fault-corrupt mutate "
                    "the masked wire, which the protocol cannot model "
                    "yet")
        if self.local_steps < 1:
            raise ValueError(
                f"local_steps must be >= 1, got {self.local_steps}")
        _MARGIN_DEFENSES = ("Krum", "TrimmedMean", "Median", "Bulyan")
        if self.margins:
            # Margins are read from the ON-DEVICE score/rank tensors the
            # robust kernels already build; every config that never
            # materializes them is rejected here, loudly, with the
            # offending knob named (tests/test_margins.py pins the
            # message contract).
            if self.defense not in _MARGIN_DEFENSES:
                raise ValueError(
                    f"--margins measures a robust defense's decision "
                    f"margins; defense {self.defense!r} makes no "
                    f"selection/trim decision to measure (use one of "
                    f"{'/'.join(_MARGIN_DEFENSES)})")
        if self.margins or (self.numerics
                            and self.defense in _MARGIN_DEFENSES):
            # The numerics tie-proximity counters reuse those same
            # margin tensors (utils/numerics.py), so --numerics on a
            # margin-bearing defense shares the on-device-impl
            # requirement (on any other defense only the stage
            # counters run and no impl constraint applies).
            flag = "--margins" if self.margins else "--numerics"
            for knob in ("trimmed_mean_impl", "median_impl",
                         "bulyan_trim_impl", "distance_impl",
                         "bulyan_selection_impl"):
                if getattr(self, knob) == "host":
                    raise ValueError(
                        f"{flag} reads the on-device score/rank "
                        f"tensors inside the fused round program; "
                        f"{knob}='host' marshals that stage to a native "
                        f"kernel that returns only its aggregate, never "
                        f"the per-row margins (set {knob} to an "
                        f"on-device impl)")
        if not (0.0 < self.participation <= 1.0):
            raise ValueError(
                f"participation must be in (0, 1], got "
                f"{self.participation}")
        if self.num_std == "auto":
            from attacking_federate_learning_tpu.attacks.alie import paper_z
            self.num_std = paper_z(self.users_count, self.corrupted_count)
        elif (isinstance(self.num_std, bool)
                or not isinstance(self.num_std, (int, float))):
            # bool is an int subclass; num_std=True silently meaning
            # z=1.0 would be a config typo accepted as physics.
            raise ValueError(
                f"num_std must be a number or 'auto', got "
                f"{self.num_std!r}")
        if self.fading_rate is None:
            self.fading_rate = FADING_RATES.get(self.dataset, 10000.0)
        if self.model is None:
            self.model = default_model_for(self.dataset)
        if self.backdoor == "No":
            self.backdoor = False  # reference main.py:135-136
        elif isinstance(self.backdoor, str) and self.backdoor.isdigit():
            # reference main.py:116 leaves '1'|'2'|'3' as strings, which
            # crashes at backdoor.py:34 (str - int); we coerce instead.
            self.backdoor = int(self.backdoor)

    @property
    def corrupted_count(self) -> int:
        # reference main.py:21 / server.py:87
        return int(self.mal_prop * self.users_count)

    def csv_name(self) -> str:
        # Filename schema of reference main.py:100.
        return ("{}_stdev_{}_{}_backdoor-{}_mal_prop_{}_users_{}_alpha_{}_lr_{}"
                ".csv").format(self.dataset, self.num_std, self.defense,
                               self.backdoor, self.mal_prop, self.users_count,
                               self.alpha, self.learning_rate)


# Input-shape families for fail-fast model/dataset validation (a wrong
# pairing otherwise surfaces as a reshape error deep inside the jit trace).
MODEL_FAMILY = {"mnist_mlp": "mnist", "mnist_cnn": "mnist",
                "cifar10_cnn": "cifar", "resnet20": "cifar",
                "wideresnet40_4": "cifar",
                "smallthinker_21b_a3b_ep8": "tokens_18992",
                "seq_tiny": "tokens_96"}
DATASET_FAMILY = {SYNTH_TOKENS: "tokens_18992",
                  SYNTH_TOKENS_TINY: "tokens_96",
                  MNIST: "mnist", SYNTH_MNIST: "mnist",
                  SYNTH_MNIST_HARD: "mnist", CIFAR10: "cifar",
                  SYNTH_CIFAR10: "cifar", SYNTH_CIFAR10_HARD: "cifar",
                  CIFAR100: "cifar"}


def default_model_for(dataset: str) -> str:
    return {
        MNIST: "mnist_mlp", SYNTH_MNIST: "mnist_mlp",
        CIFAR10: "cifar10_cnn", SYNTH_CIFAR10: "cifar10_cnn",
        SYNTH_CIFAR10_HARD: "cifar10_cnn",
        CIFAR100: "wideresnet40_4",
        SYNTH_TOKENS: "smallthinker_21b_a3b_ep8",
        SYNTH_TOKENS_TINY: "seq_tiny",
    }.get(dataset, "mnist_mlp")
