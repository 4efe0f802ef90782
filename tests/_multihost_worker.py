"""Worker for the 2-process multihost exercise (run by test_multihost).

Each process contributes 4 virtual CPU devices; after
``multihost.initialize`` the global mesh spans 8 devices across the two
processes, and the blockwise ring distance kernel's ``ppermute`` hops cross
the process boundary over the distributed runtime — the DCN path of
SURVEY.md §2.3, on localhost.

Usage: python _multihost_worker.py <coord_addr> <num_procs> <proc_id> <out>
"""

import os
import sys

# Must be set before jax backend init (conftest isn't in play here).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def main():
    coord, num_procs, proc_id, out_path = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])

    from attacking_federate_learning_tpu.parallel import multihost

    assert multihost.initialize(coordinator_address=coord,
                                num_processes=num_procs,
                                process_id=proc_id) is True
    assert jax.process_count() == num_procs
    assert jax.device_count() == 4 * num_procs          # global devices
    assert len(jax.local_devices()) == 4

    from attacking_federate_learning_tpu.defenses.kernels import krum
    from attacking_federate_learning_tpu.parallel.distances import (
        pairwise_distances_ring
    )
    from attacking_federate_learning_tpu.parallel.mesh import (
        CLIENTS, make_mesh
    )

    mesh = make_mesh((jax.device_count(), 1))

    # Same full matrix on both processes (same seed); each contributes its
    # process-local rows to the globally sharded array.
    n, d, f = 16, 256, 3
    G_full = np.random.default_rng(0).standard_normal((n, d)).astype(
        np.float32)
    sharding = NamedSharding(mesh, P(CLIENTS, None))
    G = jax.make_array_from_process_local_data(sharding, G_full[
        proc_id * (n // num_procs):(proc_id + 1) * (n // num_procs)])
    assert not G.is_fully_addressable   # genuinely spans both processes

    @jax.jit
    def agg(G):
        D = pairwise_distances_ring(G, mesh, axis=CLIENTS)
        out = krum(G, n, f, D=D)
        # Replicate so every process holds the full aggregate.
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P()))

    out = agg(G)
    result = np.asarray(out.addressable_data(0))
    if multihost.is_primary():
        np.savez(out_path, agg=result, G=G_full)
    # Clean shutdown so the coordinator exits 0.
    jax.distributed.shutdown()
    print("WORKER_OK", flush=True)


if __name__ == "__main__":
    main()
