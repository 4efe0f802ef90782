"""Multi-host wrapper: single-host no-op semantics, plus a real 2-process
exercise of ``jax.distributed.initialize`` over localhost (VERDICT item #8:
the only module whose happy path had never executed)."""

import os
import pathlib
import socket
import subprocess
import sys

import numpy as np
import pytest

from attacking_federate_learning_tpu.parallel import multihost


def test_single_host_is_noop(monkeypatch):
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    assert multihost.initialize() is False


def test_is_primary_single_host():
    assert multihost.is_primary() is True


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_ring_round(tmp_path):
    """Two real processes join one distributed runtime; the global mesh
    spans both; the ring distance kernel's ppermute hops cross the process
    boundary; the Krum aggregate must match the single-process kernel.

    Infra flakiness (port races, slow coordinator) skips; a wrong answer
    fails."""
    worker = pathlib.Path(__file__).parent / "_multihost_worker.py"
    coord = f"127.0.0.1:{_free_port()}"
    out_path = tmp_path / "result.npz"
    repo_root = worker.parent.parent
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           # Script-mode python puts tests/ (not the repo root) on
           # sys.path; prepend the root so the package imports.
           "PYTHONPATH": f"{repo_root}:{os.environ.get('PYTHONPATH', '')}"}
    env.pop("XLA_FLAGS", None)  # worker sets its own device count
    procs = [subprocess.Popen(
        [sys.executable, str(worker), coord, "2", str(i), str(out_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=str(worker.parent.parent))
        for i in range(2)]
    try:
        outs = [p.communicate(timeout=300)[0] for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("2-process runtime timed out (infra)")
    failing = [(p, o) for p, o in zip(procs, outs) if p.returncode != 0]
    if failing:
        # Capability skip, distinct from flake-skip: this box's jaxlib
        # (0.4.37) cannot run multiprocess collectives on the CPU
        # backend at all ("Multiprocess computations aren't implemented
        # on the CPU backend") — the test needs either a newer jaxlib
        # or real multi-host devices.  A permanent local gap, not a
        # wrong answer; the kernel itself is still covered by the
        # 8-virtual-device single-process ring/allgather parity tests
        # (tests/test_parallel.py, tests/test_distance_impl.py).
        cap = "Multiprocess computations aren't implemented"
        if all(cap in o for _, o in failing):
            pytest.skip("jaxlib CPU backend lacks multiprocess "
                        "collectives on this box (capability gap, "
                        "see ARCHITECTURE.md 'Known local failures')")
        # Skip only when every failing process's OWN output shows an
        # infra signature; a genuine assertion in one worker must fail
        # even if its peer finished cleanly.
        infra = ("UNAVAILABLE", "DEADLINE", "failed to connect",
                 "Connection re", "Barrier timed out")
        if all(any(sig in o for sig in infra) for _, o in failing):
            pytest.skip("distributed infra flake:\n"
                        + "\n---\n".join(o[-1000:] for _, o in failing))
        raise AssertionError("worker failed:\n"
                             + "\n---\n".join(o[-4000:] for _, o in failing))
    assert all("WORKER_OK" in o for o in outs)

    data = np.load(out_path)
    # Single-process reference: same kernel, same inputs, local mesh.
    from attacking_federate_learning_tpu.defenses.kernels import krum
    import jax.numpy as jnp

    want = np.asarray(krum(jnp.asarray(data["G"]), 16, 3))
    np.testing.assert_allclose(data["agg"], want, atol=2e-5, rtol=1e-5)
