"""Benchmark driver: prints ONE JSON line to stdout.

Headline kernel: Krum robust aggregation — the reference's #1 hotspot, an
O(n^2 d) Python dict of pairwise norms plus a per-user sort
(reference defences.py:16-42).  Here it is the framework's dispatching
kernel (defenses/kernels.py): a Gram matmul (the upper block triangle
at this size, ops/distances.py) + top-k on the TPU MXU.
The baseline is a NumPy/BLAS implementation of the same exact semantics
(defenses/oracle.py math, vectorized Gram form — already far faster than
the reference's Python double loop, so the reported speedup is a *lower*
bound on the advantage over the reference itself) measured on the host's
CPU.

Output: {"metric": "krum_agg_<n>c_wall_ms", "value": <ms>,
         "unit": "ms", "vs_baseline": <cpu_ms / our_ms>, "env": <device>}

Diagnostics (per-impl table, MFU, the 10k-client north-star suite from
BASELINE.md, FL round throughput) go to stderr, with a recap block at
the very end.

This is a device measurement: it runs on the TPU or not at all.  A
backend that is not a TPU is a non-zero exit with one line saying so
(utils/backend.py:require_tpu), and a phase that raises fails the run.

Timing: host clock around ``jax.block_until_ready`` — K back-to-back
dispatches, one wait on the last output (the single device stream
executes in dispatch order, so it bounds all K).

Validity gate: the emitted JSON carries ``valid`` — True only when
every check passed; poisoned (with ``invalid_reasons``) when any implied
throughput exceeds the published bf16 peak of the device
(:data:`PEAKS`), or when two f32 distance engines disagree on the Krum
selection index beyond the measured tie band.  A garbage number can not
be recorded as a headline.
"""

from __future__ import annotations

import json
import sys
import time
from contextlib import contextmanager

import numpy as np


N_CLIENTS = 2048
DIM = 79_510          # MNIST MLP wire dim (reference data_sets.py:13-23)
F_FRAC = 0.24         # reference default mal proportion (main.py:106)
REPEATS = 5
N_NORTH = 10_240      # BASELINE.md north star
HOST_FLOOR_10K_MS = 72_700.0  # XLA:CPU-box host-BLAS floor @ 10,240 (BASELINE.md)

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.  A
# device that is not in the table is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

RECAP: list[str] = []
RESULT: dict = {}
PHASES_DONE: list[str] = []  # names of phases that ran to completion
PHASE_TIMER = None  # utils.profiling.PhaseTimer, set in main() (the module
                    # imports jax, so construction waits for backend setup);
                    # every phase() logs into it and RESULT['phase_timing']
                    # carries the summary.


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise SystemExit(
            f"bench: no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add them with their source")
    return PEAKS[device_kind]


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def recap(msg):
    log(msg)
    RECAP.append(msg)


def emit_result_json():
    from attacking_federate_learning_tpu.utils.costs import cache_counts

    RESULT["compile_cache"] = cache_counts()
    print(json.dumps(RESULT), flush=True)


def mark_invalid(reason):
    """Poison the emitted JSON's validity and say why, loudly."""
    RESULT["valid"] = False
    reasons = RESULT.setdefault("invalid_reasons", [])
    if reason not in reasons:
        reasons.append(reason)
    recap(f"  !! VALIDITY: {reason}")


@contextmanager
def phase(name):
    """Time one bench phase into RESULT['phase_timing'] and record its
    completion (``phases_completed``).  An exception propagates: a
    failed phase fails the run."""
    t0 = time.perf_counter()
    try:
        yield
        PHASES_DONE.append(name)
        RESULT["phases_completed"] = PHASES_DONE
    finally:
        if PHASE_TIMER is not None:
            PHASE_TIMER.totals[name] += time.perf_counter() - t0
            PHASE_TIMER.counts[name] += 1
            RESULT["phase_timing"] = PHASE_TIMER.summary()


def median_ms(fn, repeats=REPEATS):
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(1e3 * (time.perf_counter() - t0))
    return float(np.median(times))


def numpy_krum_ms(G: np.ndarray, f: int) -> float:
    """Reference-semantics Krum (sum of n-f smallest distances, argmin)
    in vectorized NumPy/BLAS — the strongest honest CPU baseline."""

    def run():
        sq = np.einsum("nd,nd->n", G, G)
        d2 = sq[:, None] + sq[None, :] - 2.0 * (G @ G.T)
        np.maximum(d2, 0.0, out=d2)
        D = np.sqrt(d2)
        np.fill_diagonal(D, np.inf)
        k = G.shape[0] - f
        srt = np.sort(D, axis=1)[:, : min(k, G.shape[0] - 1)]
        _ = G[int(np.argmin(srt.sum(axis=1)))]

    return median_ms(run)


def timed_ms(make_out, iters=6, loops=3):
    """Median over ``loops`` of: dispatch ``iters`` back-to-back
    executions and block until the last output is ready (in-order
    device stream => the wait bounds all of them), per iteration.
    Returns ``(ms, last_output)`` so callers that need the output (e.g.
    a selection index) don't pay an extra execution."""
    import jax

    out = jax.block_until_ready(make_out())        # compile + warm
    ts = []
    for _ in range(loops):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = make_out()
        jax.block_until_ready(out)
        ts.append(1e3 * (time.perf_counter() - t0) / iters)
    return float(np.median(ts)), out


def mfu_line(tag, flops, ms, device_kind, to_recap=False):
    """Log the implied throughput against the device's published bf16
    peak; returns the achieved fraction so callers can gate validity —
    a fraction > 1.0 means the measurement is broken, never that the
    kernel is fast.  ``flops`` is the nominal 2·n²·d of one pass and
    ``ms`` a host wall, so for an f32 ``Precision.HIGHEST`` Gram (several
    bf16 MXU passes) this is a validity bound, not a utilization: the
    device's own counters are a trace's to read (ROADMAP S2)."""
    peak = peaks_for(device_kind)["bf16_flops"]
    achieved = flops / (ms * 1e-3)
    frac = achieved / peak
    line = (f"  mfu[{tag}]: nominal {achieved / 1e12:.1f} TFLOP/s by host "
            f"clock = {100 * frac:.1f}% of the {peak / 1e12:.0f} TFLOP/s "
            f"bf16 peak")
    (recap if to_recap else log)(line)
    if frac > 1.0:
        mark_invalid(f"mfu[{tag}] implies {achieved / 1e12:.0f} TFLOP/s "
                     f"> bf16 physical peak — measurement broken")
    return frac


def krum_score_two_ways(G, f, i):
    """One candidate's Krum score — the sum of its n-f smallest
    distances to the others (reference defences.py:16-42 semantics) —
    computed via BOTH distance formulations the engines use: the
    direct-difference form and the Gram form (which cancels
    catastrophically for near-equal rows).  Distances come back to host
    and are summed in float64 (effectively exact for f32 inputs at
    these n), so each returned score isolates the error of its distance
    FORMULATION — the spread between the two is a direct measurement of
    cross-engine score indeterminacy on this data.  Used only to
    adjudicate selection flips."""
    import jax.numpy as jnp

    n = G.shape[0]
    k = min(n - f, n - 1)
    gi = G[i]
    d_diff = jnp.sqrt(jnp.sum((G - gi[None, :]) ** 2, axis=1))
    sq = jnp.sum(G * G, axis=1)
    d2_gram = sq + jnp.sum(gi * gi) - 2.0 * (G @ gi)
    d_gram = jnp.sqrt(jnp.maximum(d2_gram, 0.0))
    out = []
    for dvec in (d_diff, d_gram):
        v = np.asarray(dvec, np.float64)
        v[i] = np.inf
        out.append(float(np.sum(np.sort(v)[:k])))
    return out[0], out[1]


def adjudicate_f32_flip(G, f, indices):
    """Decide whether an f32 cross-engine Krum index flip is a legal tie.

    Two correct f32 engines may legally disagree when the top-2 score
    gap is inside the engines' numeric indeterminacy — different
    summation orders AND different distance formulations (Gram vs
    direct difference; Gram cancellation error can dwarf summation
    noise when rows are close).  The band is therefore measured, not
    guessed: per candidate, the |diff-form − Gram-form| score spread on
    this very data (×4 safety), plus the analytic worst-case f32
    summation term n·(eps/2)·|score|.  A gap inside the band cannot be
    adjudicated by ANY f32 engine — the same ulp-band reality
    tests/test_native.py pins for the native Bulyan comparator.
    Returns ``(is_tie, gap, band)``; gaps above the band are real
    disagreements (correctness unproven — the caller poisons
    validity)."""
    scores = {int(i): krum_score_two_ways(G, f, int(i))
              for i in set(indices)}
    vals = [s for pair in scores.values() for s in pair]
    if not all(np.isfinite(v) for v in vals):
        return False, float("nan"), 0.0
    mids = [0.5 * (a + b) for a, b in scores.values()]
    gap = max(mids) - min(mids)
    spread = max(abs(a - b) for a, b in scores.values())
    band = 4.0 * spread + 0.5 * G.shape[0] * float(
        np.finfo(np.float32).eps) * max(abs(v) for v in vals)
    return gap <= band, gap, band


def gate_f32_disagreement(G, f, group, n):
    """The f32 half of the cross-impl agreement gate, routed through the
    tie adjudicator (ADVICE r4 #1).  f32 engines computing the same math
    MUST agree on any decisive score gap; a flip there means on-chip
    correctness is unproven, so no per-impl number (nor the headline
    that shares the xla engine) may be quoted as valid.  But a near-tied
    score can legally flip between engines (the ulp-band contract
    tests/test_native.py pins) — poisoning a whole run over a
    legitimate tie would waste it, so ties warn instead."""
    is_tie, gap, band = adjudicate_f32_flip(G, f, group.values())
    if is_tie:
        recap(f"  .. f32 flip at n={n} is a legal tie "
              f"(score gap {gap:.6g} <= indeterminacy band "
              f"{band:.6g}); warning only")
    else:
        mark_invalid(
            f"f32 distance impls disagree on the Krum index "
            f"at n={n} (score gap {gap:.6g} > tie band {band:.6g})")


def bench_impl_table(G, f, iters=4):
    """Per-impl diagnostic: the in-program distance engine at this n —
    the XLA Gram in f32 and in the bf16-Gram MXU mode
    (distance_dtype='bfloat16') — with cross-impl Krum selection-index
    agreement among rows of one dtype."""
    import functools

    import jax

    from attacking_federate_learning_tpu.defenses.kernels import krum_select

    n = G.shape[0]
    rows = {}
    idxs = {}
    for impl, ddt in [("xla", None), ("xla", "bfloat16")]:
        label = impl + ("[bf16]" if ddt else "")
        sel_fn = jax.jit(
            functools.partial(krum_select, distance_impl=impl,
                              distance_dtype=ddt),
            static_argnums=(1, 2))
        # krum_select returns the index itself, so the timed loop's last
        # output already holds it — no extra execution.
        ms, out = timed_ms(lambda: sel_fn(G, n, f), iters=iters)
        rows[label] = ms
        idxs[label] = int(out)
        recap(f"  krum impl={label:13s} n={n}: {ms:10.2f} ms  "
              f"(select={idxs[label]})")
    # Cross-impl agreement is checked WITHIN a dtype: on iid gaussian
    # data near-tied Krum scores make an f32-vs-bf16 selection flip
    # legitimate (tests/test_distance_impl.py), so mixing dtypes into
    # one set would false-alarm the parity signal.
    for tag, group in (("f32", {k: v for k, v in idxs.items()
                                if "bf16" not in k}),
                       ("bf16", {k: v for k, v in idxs.items()
                                 if "bf16" in k})):
        if len(set(group.values())) > 1:
            recap(f"  !! {tag} impl DISAGREEMENT at n={n}: {group}")
            if tag == "f32":
                gate_f32_disagreement(G, f, group, n)
        else:
            recap(f"  {tag} impls agree at n={n} "
                  f"(select={next(iter(group.values()))})")
    return rows


def main():
    from attacking_federate_learning_tpu.utils.backend import (
        enable_compile_cache, require_tpu
    )

    stamp = require_tpu("bench.py")
    enable_compile_cache()
    import functools

    import jax
    import jax.numpy as jnp

    global PHASE_TIMER
    from attacking_federate_learning_tpu.utils.profiling import PhaseTimer

    PHASE_TIMER = PhaseTimer()

    from attacking_federate_learning_tpu.defenses.kernels import (
        bulyan, krum, trimmed_mean
    )

    # Every record says which toolchain and device produced it.
    RESULT["env"] = {"jax": jax.__version__, **stamp}
    kind = stamp["device_kind"]
    peaks_for(kind)     # unknown device: fail before measuring anything
    n = N_CLIENTS
    f = int(F_FRAC * n)
    recap(f"device: {stamp['platform']} ({kind} x{stamp['count']}); "
          f"n={n} d={DIM} f={f}")

    rng = np.random.default_rng(0)

    # --- baseline: NumPy/BLAS on host CPU ------------------------------
    # The kernels are data-oblivious (matmul + sort), so the baseline's
    # data need not be bit-identical to the device run's.
    G_host = rng.standard_normal((n, DIM)).astype(np.float32)
    cpu_ms = numpy_krum_ms(G_host, f)
    recap(f"numpy/BLAS krum (host CPU): {cpu_ms:.1f} ms "
          f"(median of {REPEATS})")
    del G_host

    # --- ours: the jitted XLA Gram-matmul path on the chip, with data
    # GENERATED ON DEVICE (no multi-GB host transfer) --------------------
    G = jax.block_until_ready(jax.jit(
        lambda k: jax.random.normal(k, (n, DIM), jnp.float32))(
            jax.random.PRNGKey(0)))
    krum_fn = jax.jit(krum, static_argnums=(1, 2))

    with phase("headline"):
        dev_ms, _ = timed_ms(lambda: krum_fn(G, n, f))
        recap(f"framework krum [xla/jit] ({kind}): {dev_ms:.2f} ms")
        # valid starts True and every gate can only poison it: implied
        # throughput <= bf16 physical peak (mfu_line), f32 impl
        # agreement (bench_impl_table below).
        RESULT.update(
            metric=f"krum_agg_{n}c_wall_ms", value=round(dev_ms, 3),
            unit="ms", vs_baseline=round(cpu_ms / dev_ms, 2), valid=True)
        # Gram matmul dominates: 2 n^2 d FLOPs.
        mfu_line("krum_gram", 2 * n * n * DIM, dev_ms, kind, to_recap=True)
        # Static cost facts for the headline kernel (utils/costs.py):
        # XLA's own FLOP/bytes/memory accounting of the jitted program
        # rides next to the timed wall so a record is interpretable
        # without re-deriving the 2n^2d analytic estimate.  AOT-analyzed;
        # the compile is the one the timed loop already warmed.
        from attacking_federate_learning_tpu.utils.costs import (
            analyze_lowered, wire_ledger
        )
        rec = analyze_lowered("krum_xla", krum_fn.lower(G, n, f))
        RESULT["cost"] = {rec.name: rec.gate_facts()}
        recap(f"  static cost [krum_xla]: flops={rec.flops:.3e} "
              f"bytes={rec.bytes_accessed:.3e} "
              f"peak={rec.peak_bytes / 1e6:.1f} MB")
        # Wire-ledger rollup for the headline cohort: the per-seam
        # protocol bytes the same (n, d) round moves, priced from
        # topology facts alone.
        RESULT["wire"] = wire_ledger(cohort=n, dim=DIM)
        recap(f"  wire ledger [flat n={n}]: "
              f"{RESULT['wire']['total_bytes'] / 1e6:.1f} MB/round "
              f"over {len(RESULT['wire']['seams'])} seams")

    with phase("impl-table"):
        log("per-impl table:")
        bench_impl_table(G, f)
    del G

    # --- north star: 10k clients (BASELINE.md) --------------------------
    f10 = int(F_FRAC * N_NORTH)
    with phase("north-star-data"):
        G10 = jax.block_until_ready(jax.jit(lambda k: jax.random.normal(
            k, (N_NORTH, DIM), jnp.float32))(jax.random.PRNGKey(1)))
    with phase("north-star-krum"):
        ms10, _ = timed_ms(lambda: krum_fn(G10, N_NORTH, f10), iters=3)
        recap(f"north-star: krum @ {N_NORTH} clients, d={DIM}: "
              f"{ms10:.1f} ms (XLA:CPU host-BLAS floor "
              f"{HOST_FLOOR_10K_MS:.0f} ms => "
              f"{HOST_FLOOR_10K_MS / ms10:.0f}x)")
        mfu_line("krum_gram_10k", 2 * N_NORTH * N_NORTH * DIM, ms10, kind,
                 to_recap=True)
        log("per-impl table @ 10k:")
        bench_impl_table(G10, f10, iters=2)
    with phase("north-star-trimmed-mean"):
        tm_fn = jax.jit(trimmed_mean, static_argnums=(1, 2))
        ms_tm, _ = timed_ms(lambda: tm_fn(G10, N_NORTH, f10), iters=2)
        recap(f"north-star: trimmed_mean @ {N_NORTH}: {ms_tm:.1f} ms")
    with phase("north-star-bulyan-hybrid"):
        # The exact-semantics accelerator path at 10k — device Gram on
        # the MXU, ONE (n, n) D marshal (~420 MB) to the native host
        # selection engine, device gather + trim-mean.
        hy_fn = jax.jit(
            functools.partial(bulyan, selection_impl="host"),
            static_argnums=(1, 2))
        ms_hy, _ = timed_ms(lambda: hy_fn(G10, N_NORTH, f10),
                            iters=1, loops=2)
        recap(f"north-star: bulyan[exact, hybrid] @ {N_NORTH}: "
              f"{ms_hy:.1f} ms (incl. the one (n,n) D marshal)")
    with phase("north-star-bulyan-batched"):
        bq_fn = jax.jit(
            functools.partial(bulyan, batch_select=64),
            static_argnums=(1, 2))
        ms_bq, _ = timed_ms(lambda: bq_fn(G10, N_NORTH, f10),
                            iters=1, loops=2)
        recap(f"north-star: bulyan[q=64] @ {N_NORTH}: {ms_bq:.1f} ms")
    # The traced exact selection (q=1, ~5,200 sequential O(n^2) trips) is
    # not a phase here: on the v5e one call did not finish in 20 minutes
    # (PERF.md) and would keep every later phase from running.
    del G10

    # --- multichip hier: SPMD vs scan tier-1 at the north star ----------
    # AOT-only static facts: collective bytes + temp bytes of the SPMD
    # client_map round (megabatch axis sharded over the mesh clients
    # axis, one explicit estimate all_gather) vs the sequential scan
    # round, at the 10,240-client memproof point.  Runs in a child
    # pinned to the CPU platform with 8 virtual devices: this process
    # holds the chip (one process per chip), and the child needs none —
    # it is a deterministic static-HLO fact.  --rehearse overrides the
    # inherited platform in the child before its backend initializes.
    with phase("multichip-hier"):
        import os
        import subprocess

        cmd = [sys.executable,
               os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tools", "multichip_hier.py"),
               "--rehearse", "--aot", "--clients", str(N_NORTH),
               "--megabatch", "512"]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=280,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"))
        if proc.returncode != 0 or not proc.stdout.strip():
            raise RuntimeError(
                f"multichip_hier rc={proc.returncode}: "
                f"{proc.stderr[-400:]}")
        mh = json.loads(proc.stdout.strip().splitlines()[-1])
        RESULT["multichip_hier"] = mh
        recap(f"multichip-hier @ {mh['clients']} (m={mh['megabatch']}, "
              f"S={mh['num_shards']}, {mh['clients_axis']}-way clients "
              f"axis, {mh['platform']} AOT): sharded collective "
              f"{mh['sharded']['collective_bytes'] / 1e6:.1f} MB "
              f"(S*d*4 = "
              f"{mh['collective_bytes_bound_S_d_4'] / 1e6:.1f} MB) "
              f"temp {mh['sharded']['temp_bytes'] / 1e6:.0f} MB vs "
              f"scan temp {mh['scan']['temp_bytes'] / 1e6:.0f} MB, "
              f"0 collective")

    # --- secondary: full FL round throughput (stderr diagnostic) --------
    with phase("fl-throughput"):
        from attacking_federate_learning_tpu.attacks import DriftAttack
        from attacking_federate_learning_tpu.config import ExperimentConfig
        from attacking_federate_learning_tpu.core.engine import (
            FederatedExperiment
        )
        from attacking_federate_learning_tpu.data.datasets import load_dataset
        from attacking_federate_learning_tpu.utils.lifecycle import (
            run_id_for
        )

        for n_clients in (10, 512):
            cfg = ExperimentConfig(
                dataset="SYNTH_MNIST", users_count=n_clients,
                mal_prop=0.24, batch_size=64, epochs=1, defense="Krum")
            # Config-hash identity: the join key between this record
            # and the run registry (utils/registry.py).
            RESULT.setdefault("run_ids", {})[
                f"fl_round_{n_clients}c"] = run_id_for(cfg)
            ds = load_dataset(cfg.dataset, seed=0, synth_train=8192,
                              synth_test=512)
            exp = FederatedExperiment(cfg, attacker=DriftAttack(1.5),
                                      dataset=ds)
            reps = 20
            exp.run_span(0, reps)  # compile the scanned span
            jax.block_until_ready(exp.state.weights)
            t0 = time.perf_counter()
            exp.run_span(reps, reps)  # one device program for all rounds
            jax.block_until_ready(exp.state.weights)
            dt = time.perf_counter() - t0
            rps = reps / dt
            recap(f"fl_rounds_per_sec (Krum+ALIE, {n_clients} clients, "
                  f"mnist-mlp, scanned span): {rps:.1f}")
            # vmapped fwd/bwd of the MLP: ~6 * n * B * d FLOPs per round.
            mfu_line(f"fl_round_{n_clients}c",
                     reps * 6 * n_clients * cfg.batch_size * DIM, 1e3 * dt,
                     kind)

    # --- backdoor rounds/sec: fused vs staged (stderr diagnostic) -------
    with phase("backdoor"):
        from attacking_federate_learning_tpu.attacks import make_attacker

        def backdoor_rps(fused, n_clients=32, reps=10):
            cfg = ExperimentConfig(
                dataset="SYNTH_MNIST", users_count=n_clients, mal_prop=0.25,
                batch_size=32, epochs=1, defense="TrimmedMean",
                backdoor="pattern", backdoor_fused=fused)
            RESULT.setdefault("run_ids", {})[
                f"backdoor_{'fused' if fused else 'staged'}"] = (
                run_id_for(cfg))
            ds = load_dataset(cfg.dataset, seed=0, synth_train=4096,
                              synth_test=256)
            exp = FederatedExperiment(
                cfg, attacker=make_attacker(cfg, dataset=ds), dataset=ds)
            exp.run_span(0, reps)
            jax.block_until_ready(exp.state.weights)
            t0 = time.perf_counter()
            exp.run_span(reps, reps)
            jax.block_until_ready(exp.state.weights)
            return reps / (time.perf_counter() - t0)

        recap(f"backdoor_rounds_per_sec fused={backdoor_rps(True):.2f} "
              f"staged={backdoor_rps(False):.2f} "
              f"(32 clients, pattern trigger, TrimmedMean)")

    # Every recap line already streamed live (recap() echoes as it
    # banks), so the closing block repeats ONLY the essentials — one
    # block, each line once.
    log("=== essentials ===")
    for line in RECAP:
        if ("device:" in line or "framework krum" in line
                or "north-star" in line or "mfu[krum" in line
                or "VALIDITY" in line):
            log(line)

    emit_result_json()


if __name__ == "__main__":
    main()
