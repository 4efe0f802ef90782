#!/usr/bin/env python
"""Pallas defense-kernel micro-bench.

Compiles each ops/pallas_defense.py kernel through Mosaic on the TPU
and times a few executions (host clock around block_until_ready).  One
JSON line per kernel on stdout; chatter on stderr.  It is a device
measurement: without ``--rehearse`` it needs the TPU and exits
non-zero without one.

    python tools/pallas_microbench.py [--n N] [--d D] [--rehearse]

--rehearse: the CPU stub: tiny shapes, interpret forced on, same steps
and the same JSON schema — proves the step mechanics, measures nothing.

On TPU the fused Krum-score kernel runs the balanced large-tile
configuration (bm=bn=512, bk=1024: tile HBM traffic ~n²·d·8/512 bytes)
and the parity check diffs each kernel against its XLA reference at f32
tolerance.  A kernel Mosaic refuses banks an ``error`` row (today: every
sort-based kernel, ops/pallas_defense.py:_sort_kernel_interpret) and
the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--n", type=int, default=2048)
    p.add_argument("--d", type=int, default=79_510)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU stub: tiny shapes, interpret forced on")
    args = p.parse_args(argv)

    from attacking_federate_learning_tpu.utils.backend import (
        enable_compile_cache, require_tpu, select_platform
    )

    if args.rehearse:
        select_platform("cpu")
    else:
        require_tpu("pallas_microbench")
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from attacking_federate_learning_tpu.defenses.kernels import (
        _krum_scores, bulyan, trimmed_mean_of
    )
    from attacking_federate_learning_tpu.ops.distances import (
        pairwise_distances
    )
    from attacking_federate_learning_tpu.ops.pallas_defense import (
        krum_scores_cost, pallas_krum_scores, pallas_median_of,
        pallas_trimmed_mean_of
    )
    from attacking_federate_learning_tpu.utils.costs import (
        compiled_cost_facts
    )

    dev = jax.devices()[0]
    on_accel = not args.rehearse
    interpret = True if args.rehearse else None
    n, d = (64, 1024) if args.rehearse else (args.n, args.d)
    f = int(0.24 * n)
    log(f"pallas_microbench: backend={dev.platform} n={n} d={d} f={f} "
        f"interpret={interpret}")
    G = jax.jit(lambda k: jax.random.normal(k, (n, d), jnp.float32))(
        jax.random.PRNGKey(0))
    jax.block_until_ready(G)

    # Large tiles on real hardware (roofline-balanced at 10k); the CI
    # defaults elsewhere keep small-n interpret coverage cheap.
    tiles = (dict(bm=512, bn=512, bk=1024) if on_accel
             else dict(bm=128, bn=128, bk=512))

    def timed(fn):
        out = jax.block_until_ready(fn())            # compile + warm
        walls = []
        for _ in range(max(1, args.repeats)):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            walls.append(1e3 * (time.perf_counter() - t0))
        return float(np.median(walls)), out

    k_keep = n - f - 1
    cells = [
        ("krum_score_fusion",
         jax.jit(lambda g: pallas_krum_scores(
             g, n, f, interpret=interpret, **tiles)[0]),
         jax.jit(lambda g: _krum_scores(pairwise_distances(g), n, f,
                                        method="sort")),
         krum_scores_cost(n, d, f, **tiles)),
        ("trimmed_mean_tile",
         jax.jit(lambda g: pallas_trimmed_mean_of(
             g, k_keep, interpret=interpret)),
         jax.jit(lambda g: trimmed_mean_of(g, k_keep)), None),
        ("median_tile",
         jax.jit(lambda g: pallas_median_of(g, interpret=interpret)),
         jax.jit(lambda g: jnp.median(g, axis=0)), None),
    ]
    if n <= 2048 or args.rehearse:
        # The exact on-device Bulyan route (selection loop is O(n) trips
        # of O(n²)); bounded to sizes where one execution fits a step.
        cells.append((
            "bulyan_pallas_route",
            jax.jit(lambda g: bulyan(g, n, f, selection_impl="pallas",
                                     trim_impl="pallas"),
                    static_argnums=()),
            jax.jit(lambda g: bulyan(g, n, f)), None))

    rc = 0
    for tag, pal_fn, ref_fn, declared in cells:
        row = {"kernel": tag, "n": n, "d": d, "f": f,
               "platform": dev.platform, "device_kind": dev.device_kind,
               "mosaic": on_accel,
               "tiles": tiles if tag == "krum_score_fusion" else None}
        try:
            t0 = time.perf_counter()
            lowered = pal_fn.lower(G)
            compiled = lowered.compile()
            row["compile_s"] = round(time.perf_counter() - t0, 2)
            row["cost"] = {k: v for k, v in
                           compiled_cost_facts(compiled).items()
                           if k in ("flops", "bytes_accessed",
                                    "temp_bytes")}
            if declared:
                row["declared"] = declared
            wall, out = timed(lambda: pal_fn(G))
            row["wall_ms"] = round(wall, 2)
            ref_wall, ref_out = timed(lambda: ref_fn(G))
            row["xla_wall_ms"] = round(ref_wall, 2)
            got, want = np.asarray(out), np.asarray(ref_out)
            denom = np.maximum(np.abs(want), 1e-6)
            row["max_rel_err"] = float(np.max(np.abs(got - want) / denom))
            row["parity_ok"] = bool(row["max_rel_err"] < 5e-3)
            if not row["parity_ok"]:
                rc = 1
        except Exception as e:      # noqa: BLE001 — a Mosaic refusal
            # is evidence to bank: record it, exit non-zero below
            row["error"] = f"{type(e).__name__}: {e}"
            rc = 1
        log(f"  {tag}: " + (f"{row.get('wall_ms')} ms (xla "
                            f"{row.get('xla_wall_ms')} ms), rel "
                            f"{row.get('max_rel_err'):.2e}"
                            if "wall_ms" in row
                            else row.get("error", "?")))
        print(json.dumps(row), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
