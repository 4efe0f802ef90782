#!/usr/bin/env python
"""Multi-chip hierarchical tier-1: parity + static traffic facts.

The SPMD client_map (ISSUE 12, ops/federated.py:_client_map_spmd) maps
the megabatch axis onto the mesh ``clients`` axis — each device scans
its own megabatches, tier-2 reads one explicit all_gather.  This tool
is the bench leg for that mapping:

- ``--aot``: compile-only facts at the given scale — temp bytes and
  collective bytes for the SHARDED round vs the sequential SCAN round,
  the ``sharded vs scan tier-1`` record bench.py's ``multichip-hier``
  phase stamps into its JSON.  Deterministic static-HLO facts
  (utils/costs.py), no execution, no TPU needed.
- default (execute): run a short sharded span AND its unsharded twin,
  assert parity inside the ulp band, and report walls — the real
  multi-chip round on a four-chip host.

``--rehearse`` pins CPU + 8 virtual devices before backend init (the
same lazily-read XLA_FLAGS seam as __graft_entry__.py) so the whole
step runs without a chip.  Without it the live device set is used, and
a device set that cannot form a multi-device clients axis is a
non-zero exit: asked for real chips and finding one must not pass.

Always prints exactly one JSON line on stdout; diagnostics on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _force_rehearse_env(n_devices: int = 8) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags
            + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0),
            f"--xla_force_host_platform_device_count={n_devices}")
    from attacking_federate_learning_tpu.utils.backend import (
        select_platform
    )

    select_platform("cpu")


def _clients_axis(num_shards: int, n_devices: int) -> int:
    """Largest divisor of the shard count that fits the device set —
    the mesh shape the S % clients == 0 contract admits."""
    for p in range(min(num_shards, n_devices), 0, -1):
        if num_shards % p == 0:
            return p
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="SPMD hierarchical tier-1 parity + traffic facts")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU + 8 virtual devices (no chip needed)")
    ap.add_argument("--aot", action="store_true",
                    help="compile-only: temp/collective byte facts for "
                         "sharded vs scan, no execution")
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--megabatch", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args(argv)

    if args.rehearse:
        _force_rehearse_env()
    import jax

    rec = {"tool": "multichip_hier", "rehearse": bool(args.rehearse),
           "aot": bool(args.aot), "clients": args.clients,
           "megabatch": args.megabatch}
    n_dev = len(jax.devices())
    rec["n_devices"] = n_dev
    rec["platform"] = jax.devices()[0].platform
    S = args.clients // args.megabatch
    parts = _clients_axis(S, n_dev)
    rec["num_shards"], rec["clients_axis"] = S, parts
    if parts < 2:
        rec["error"] = (f"no multi-device clients axis: {n_dev} "
                        f"device(s), S={S}")
        print(json.dumps(rec))
        return 1

    import jax.numpy as jnp
    import numpy as np

    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.attacks import DriftAttack
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset
    from attacking_federate_learning_tpu.parallel.mesh import make_plan
    from attacking_federate_learning_tpu.utils.costs import (
        compiled_cost_facts
    )

    n, m = args.clients, args.megabatch
    cfg = ExperimentConfig(
        dataset=C.SYNTH_MNIST, users_count=n, mal_prop=0.24,
        batch_size=1, epochs=max(args.rounds, 2), test_step=2, seed=0,
        synth_train=n, synth_test=64, defense="Krum",
        aggregation="hierarchical", megabatch=m, tier2_defense="Krum")
    ds = load_dataset(cfg.dataset, seed=0, synth_train=n, synth_test=64)

    def build(shardings):
        return FederatedExperiment(cfg, attacker=DriftAttack(1.5),
                                   dataset=ds, shardings=shardings)

    plan = make_plan((parts, 1), devices=jax.devices()[:parts])
    exp_spmd = build(plan)
    assert exp_spmd._hier_spmd, "mesh did not engage the SPMD path"
    d = exp_spmd.flat.dim
    rec["d"] = d

    for tag, exp in (("sharded", exp_spmd), ("scan", build(None))):
        t0 = time.perf_counter()
        facts = compiled_cost_facts(
            exp._fused_round.lower(exp.data, exp.state,
                                   jnp.asarray(0, jnp.int32),
                                   None).compile())
        rec[tag] = {"compile_s": round(time.perf_counter() - t0, 2),
                    "temp_bytes": int(facts["temp_bytes"]),
                    "collective_bytes": int(facts["collective_bytes"]),
                    "flops": facts["flops"]}
        if not args.aot:
            t0 = time.perf_counter()
            for t in range(args.rounds):
                exp.run_round(t)
            jax.block_until_ready(exp.state.weights)
            rec[tag]["rounds"] = args.rounds
            rec[tag]["wall_s"] = round(time.perf_counter() - t0, 3)
            rec[tag]["weights"] = exp.state.weights
    rec["collective_bytes_bound_S_d_4"] = S * d * 4
    if not args.aot:
        w_s = np.asarray(rec["sharded"].pop("weights"))
        w_r = np.asarray(rec["scan"].pop("weights"))
        rec["max_abs_diff"] = float(np.max(np.abs(w_s - w_r)))
        rec["parity_ok"] = bool(
            rec["max_abs_diff"] <= 2e-5 + 2e-5 * float(
                np.max(np.abs(w_r))))
    print(json.dumps(rec))
    return 0 if rec.get("parity_ok", True) else 1


if __name__ == "__main__":
    raise SystemExit(main())
