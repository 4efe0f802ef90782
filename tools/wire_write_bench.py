"""Time the row writes of a scanned client step into a wide wire, on the
chip.

One program a variant: a ``lax.scan`` of n client steps, each casting an
f32 gradient (four leaves of the sequence cell's width, d = 370,547,200)
to the wire's dtype and writing it into its row of an (n, d) wire, then
Krum's column-block Gram (``ops/distances.py:_wide_sq_distances``) of the
result.  Variants:

- ``plain``: ``dynamic_update_slice`` of the row (the write before
  ``utils/flatten.py:wire_path``);
- ``constrained_row`` / ``constrained_pair``: ``plain`` with the carry
  under ``with_layout_constraint`` of a one-row or a row-pair tiling;
- ``tiles``: ``utils/flatten.py:write_row`` as the round runs it on a TPU.

For each: the medians of the timed runs (ms a client's row made and
written, ms the Gram; ``rows_only_ms`` is a client's row made and summed,
not written), the compiled temporaries, and the whole-wire copies or
transposes in the optimized HLO.  A variant whose
optimized HLO is ``plain``'s is not run again.

    python tools/wire_write_bench.py [--rows 8] [--dim 370547200] [--out F]

prints the report as JSON on its last line (and writes it to ``F``).
Exits 1 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from attacking_federate_learning_tpu.ops.distances import (  # noqa: E402
    _wide_sq_distances
)
from attacking_federate_learning_tpu.utils.costs import (  # noqa: E402
    canonical_hlo
)
from attacking_federate_learning_tpu.utils.flatten import (  # noqa: E402
    ravel, write_row
)

TILINGS = {"constrained_row": ((1, 128),),
           "constrained_pair": ((2, 128), (2, 1))}


def leaf_sizes(dim):
    """Four leaves of whole lane vectors summing to ``dim``: the cell's
    embedding and head, a norm, the layers."""
    table = 18992 * 2560
    return [table, table, 2560, dim - 2 * table - 2560]


gram = jax.jit(_wide_sq_distances)


def make_clients(variant, n, dim, dtype):
    """The jitted scan of ``n`` client writes of ``variant``."""
    def constrain(wire):
        if variant in TILINGS:
            return with_layout_constraint(
                wire, Layout((0, 1), tiling=TILINGS[variant]))
        return wire

    def clients(srcs, scales):
        def one_client(wire, batch):
            i, c = batch
            grads = [s * c for s in srcs]
            if variant == "tiles":
                return write_row(wire, i, grads), None
            row = ravel([g.astype(dtype) for g in grads])
            return constrain(jax.lax.dynamic_update_slice(
                wire, row[None, :], (i, 0))), None

        wire, _ = jax.lax.scan(one_client, constrain(
            jnp.zeros((n, dim), dtype)), (jnp.arange(n), scales))
        return wire

    return jax.jit(clients)


@jax.jit
def rows_only(srcs, scales):
    """The clients' rows made and summed, none written: what every
    variant pays before its writes."""
    def one_client(acc, c):
        row = ravel([(s * c).astype(jnp.bfloat16) for s in srcs])
        return acc + jnp.sum(row.astype(jnp.float32)), None

    return jax.lax.scan(one_client, jnp.float32(0), scales)[0]


def whole_wire_copies(hlo, n, dim):
    """Copies and transposes in the optimized HLO whose result holds as
    many elements as the wire."""
    out = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* (copy|transpose)\(", hlo):
        size = 1
        for v in m.group(1).split(","):
            size *= int(v) if v else 1
        if size >= n * dim:
            out.append(m.group(0))
    return out


def timed(fn, *args, reps):
    times = []
    for _ in range(reps):           # the first of a jitted ``fn`` compiles
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t) * 1e3)
    return statistics.median(times)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--dim", type=int, default=370_547_200)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", help="also write the report here")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"wire_write_bench: no TPU (backend {jax.default_backend()})",
              file=sys.stderr)
        return 1
    n, dim, dtype = args.rows, args.dim, jnp.dtype(args.dtype)
    keys = jax.random.split(jax.random.key(0), 4)
    srcs = [jax.random.normal(k, (size // 128, 128), jnp.float32)
            for k, size in zip(keys, leaf_sizes(dim))]
    scales = jnp.arange(1, n + 1, dtype=jnp.float32)
    device = jax.devices()[0]
    report = {"device": {"platform": device.platform,
                         "device_kind": device.device_kind},
              "rows": n, "dim": dim, "dtype": dtype.name,
              "rows_only_ms": timed(rows_only, srcs, scales,
                                    reps=args.reps) / n,
              "variants": {}}
    programs = {}
    for variant in ("plain", "constrained_row", "constrained_pair",
                    "tiles"):
        compiled = make_clients(variant, n, dim, dtype).lower(
            srcs, scales).compile()
        hlo = canonical_hlo(compiled.as_text())
        row = {"temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
               "whole_wire_copies": whole_wire_copies(hlo, n, dim)}
        same = [v for v, text in programs.items() if text == hlo]
        programs[variant] = hlo
        if same:
            row["same_program_as"] = same[0]
        else:
            jax.block_until_ready(compiled(srcs, scales))
            row["client_ms"] = timed(compiled, srcs, scales,
                                     reps=args.reps) / n
            wire = compiled(srcs, scales)
            row["gram_ms"] = timed(gram, wire, reps=args.reps + 1)
            del wire
        report["variants"][variant] = row
        print(variant, json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
