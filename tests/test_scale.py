"""Large-client-count paths in miniature: the 10k-client north star's code
shape (BASELINE.md) exercised at n=1024 on the 8-virtual-device CPU mesh —
client-sharded gradient matrix, bf16 storage, Gram-matmul distances at
n^2 = 1M entries, complement-top-k scoring, fused span."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import DriftAttack
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.defenses import DEFENSES
from attacking_federate_learning_tpu.parallel.mesh import make_plan


needs_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                             reason="needs 8 (virtual) devices")


@needs_8
# slow tier: the 1024-client sharded span is the second most
# expensive tier-1 case (~100 s on a 1-core box); the n=2048
# sharded-vs-sort parity below keeps the scale contract in tier-1.
@pytest.mark.slow
def test_1024_client_sharded_round_with_krum():
    cfg = ExperimentConfig(dataset=C.SYNTH_MNIST, users_count=1024,
                           mal_prop=0.1, batch_size=4, epochs=1,
                           defense="Krum", grad_dtype="bfloat16",
                           synth_train=4096, synth_test=64)
    ds = load_dataset(cfg.dataset, seed=0, synth_train=4096, synth_test=64)
    exp = FederatedExperiment(cfg, attacker=DriftAttack(1.5), dataset=ds,
                              shardings=make_plan((8, 1)))
    state = exp.run_span(0, 2)
    assert int(state.round) == 2
    assert bool(np.isfinite(np.asarray(state.weights)).all())


@needs_8
def test_2048_client_krum_topk_sharded_matches_sort():
    """At n=2048 the distance matrix is 4M entries; the sharded top-k
    scoring must agree with the sort path.  n is two blocks of
    ops/distances.GRAM_BLOCK_ROWS, where one device would take the Gram's
    block triangle; G lives on a mesh here, so both calls keep the single
    dot (tests/test_gram_blocks.py pins that rule)."""
    rng = np.random.default_rng(0)
    G = jnp.asarray(rng.standard_normal((2048, 64)).astype(np.float32))
    from jax.sharding import NamedSharding, PartitionSpec as P
    plan = make_plan((8, 1))
    Gs = jax.device_put(G, NamedSharding(plan.mesh, P("clients", None)))
    a = np.asarray(jax.jit(DEFENSES["Krum"], static_argnums=(1, 2),
                           static_argnames=("method",))(
        Gs, 2048, 204, method="sort"))
    b = np.asarray(jax.jit(DEFENSES["Krum"], static_argnums=(1, 2),
                           static_argnames=("method",))(
        Gs, 2048, 204, method="topk"))
    np.testing.assert_allclose(a, b, atol=1e-4)
