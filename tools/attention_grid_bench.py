"""Time the fused attention kernels at several kv groups a grid step, on
the chip, and compare their results with one group a step.

The latent-attention cell's shape by default: one context of 8,192, 32
heads of one query head a kv group, q.k of 192 (padded to 256 inside),
values of 128, causal, tiles of 512.  For each ``h`` of ``--groups`` the
kernels of ``ops/flash_attention.py`` are built with ``HEADS_PER_STEP``
set so that a step carries ``h`` groups, then timed: the forward alone,
and the forward with the backward (output, dq, dk, dv for one output
cotangent), each the median of ``--repeats`` runs after a warm-up.  Every
``h`` is compared with ``h = 1``: whether the output and the three
gradients are equal to the bit, and the largest absolute difference.

    python tools/attention_grid_bench.py [--groups 1 2 4 8] [--out F]

prints the report as JSON on its last line (and writes it to ``F``).
Exits 1 without a TPU; ``--rehearse`` runs the kernels interpreted on the
CPU (use a tiny ``--length`` and ``--tile``), and its times are the
interpreter's.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from attacking_federate_learning_tpu.ops import (  # noqa: E402
    flash_attention as F
)

QK_DIM, V_DIM = 192, 128        # latent attention's q.k and value widths


def median_ms(fn, args, repeats):
    jax.block_until_ready(fn(*args))                  # compile and warm up
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--length", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--tile", type=int, default=512)
    ap.add_argument("--groups", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", help="also write the report here")
    a = ap.parse_args(argv)
    if jax.default_backend() != "tpu" and not a.rehearse:
        print(f"attention_grid_bench: no TPU (backend "
              f"{jax.default_backend()})", file=sys.stderr)
        return 1

    ks = jax.random.split(jax.random.key(0), 4)
    shape = (1, a.length, a.heads)
    q = jax.random.normal(ks[0], shape + (QK_DIM,))
    k = jax.random.normal(ks[1], shape + (QK_DIM,))
    v = jax.random.normal(ks[2], shape + (V_DIM,))
    w = jax.random.normal(ks[3], (1, a.length, a.heads * V_DIM))

    def build(h):
        """Fresh functions, so that jit traces them with ``h`` groups a
        step (a function traced before is not traced again)."""
        F.HEADS_PER_STEP = h                # one query head a kv group
        assert F.groups_per_step(1, a.heads) == h, h

        def forward(q, k, v):
            return F.attention(q, k, v, None, a.tile)

        def both(q, k, v, w):
            out, vjp = jax.vjp(forward, q, k, v)
            return (out,) + vjp(w)

        grids = {e.params["name"]: e.params["grid_mapping"].grid
                 for e in jax.jit(both).trace(q, k, v, w).jaxpr.eqns
                 if e.primitive.name == "pallas_call"}
        assert all(g[1] == a.heads // h for g in grids.values()), grids
        return jax.jit(forward), jax.jit(both), grids

    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform,
                         "device_kind": dev.device_kind},
              "shape": {"length": a.length, "heads": a.heads,
                        "qk_dim": QK_DIM, "v_dim": V_DIM,
                        "tile": a.tile},
              "runs": []}
    base = None
    for h in a.groups:
        forward, both, grids = build(h)
        got = jax.block_until_ready(both(q, k, v, w))
        run = {"groups_per_step": h, "grids": grids,
               "grid_steps": F.grid_steps(a.length, None, a.tile, 1,
                                          a.heads),
               "forward_ms": median_ms(forward, (q, k, v), a.repeats),
               "forward_backward_ms": median_ms(both, (q, k, v, w),
                                                a.repeats)}
        if base is None:
            base = got
        run["bit_equal_to_first"] = [bool(jnp.array_equal(x, y))
                                     for x, y in zip(got, base)]
        run["max_abs_diff_to_first"] = [float(jnp.abs(x - y).max())
                                        for x, y in zip(got, base)]
        report["runs"].append(run)
        print(json.dumps(run), flush=True)
    text = json.dumps(report)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
