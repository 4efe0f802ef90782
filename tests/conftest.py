"""Test harness: CPU-only jax with 8 virtual devices so every shard_map /
pjit path runs in CI without a TPU (SURVEY.md §4(e)).  Tests are
CPU-only; the checks that need the chip live in chip_smoke.py."""

import contextlib
import os

# Environment setup must precede backend initialization (XLA_FLAGS is
# read lazily at CPU-client creation; children inherit the platform).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# Persistent compile cache: the suite compiles dozens of kernel variants —
# caching cuts re-runs from minutes to seconds.  Same rule as
# utils/backend.py:enable_compile_cache: an externally-set directory wins
# verbatim, otherwise the fixed <checkout>/.jax_cache.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(os.path.dirname(os.path.dirname(
                          os.path.abspath(__file__))), ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402

# jax reads the platform and cache env vars at import time only, and
# something may have imported jax before this file ran — so apply them
# to the live config explicitly (backend init is lazy, so this is in
# time as long as no test has touched a jax op).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs",
                  float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: outside the tier-1 budget (tier-1 runs -m 'not slow'); "
        "e.g. the measured campaign cache-ordering proof, which spawns "
        "a child process per cell")


@contextlib.contextmanager
def metadata_in_cache_key():
    """The persistent cache's key leaves op metadata out, so of two
    programs that differ in scopes alone the second loads the first's
    executable, metadata and all.  A test that compares their compiled
    texts compiles both under this."""
    name = "jax_compilation_cache_include_metadata_in_key"
    prev = getattr(jax.config, name)
    jax.config.update(name, True)
    try:
        yield
    finally:
        jax.config.update(name, prev)


@pytest.fixture(scope="session")
def hard_ds():
    """Shared low-SNR behavioral dataset (generated once per session)."""
    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.data.datasets import load_dataset

    return load_dataset(C.SYNTH_MNIST_HARD, seed=0, synth_train=8000,
                        synth_test=2000)


def hard_final_accuracy(ds, defense, attack, mal_prop, rounds=30):
    """Run the standard behavioral config and return final test accuracy."""
    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.config import ExperimentConfig
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )

    cfg = ExperimentConfig(dataset=C.SYNTH_MNIST_HARD, users_count=19,
                           mal_prop=mal_prop, batch_size=64, epochs=rounds,
                           defense=defense)
    exp = FederatedExperiment(cfg, attacker=attack, dataset=ds)
    for t in range(rounds):
        exp.run_round(t)
    _, correct = exp.evaluate(exp.state.weights)
    return 100.0 * float(correct) / len(ds.test_y)
