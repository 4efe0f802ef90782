"""Behavioral fidelity: the directional outcomes of the implemented papers
(SURVEY.md §6) reproduced on deterministic synthetic data.

ALIE (NeurIPS'19, via reference malicious.py): with ~21% attackers the
mean-shift attack defeats plain averaging and — at an appropriate z —
Krum, while TrimmedMean and Bulyan degrade already at the reference's
default z=1.5.  The backdoor (reference backdoor.py) embeds its trigger via
shadow training and hides inside the clip envelope.

Margins are generous (tens of accuracy points) and every run is seeded, so
these are regression tests, not statistical flakes.  Measured values at
authoring time (30 rounds, n=19, f=4, SYNTH_MNIST_HARD):

    defense      clean   alie z=1.5   alie z=0.5
    NoDefense    99.7%      92.2%        15.2%
    Krum         99.5%      99.2%        20.8%
    TrimmedMean  81.0%      50.3%        99.7%
    Bulyan       82.0%      10.8%        33.4%
"""

import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import (
    DriftAttack, NoAttack, make_attacker
)
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset


# hard_ds fixture and the shared runner live in conftest.py.
from conftest import hard_final_accuracy as final_accuracy  # noqa: E402


def test_alie_defeats_plain_averaging(hard_ds):
    clean = final_accuracy(hard_ds, "NoDefense", NoAttack(), 0.0)
    attacked = final_accuracy(hard_ds, "NoDefense", DriftAttack(0.5), 0.21)
    assert clean > 90.0
    assert attacked < clean - 40.0


def test_alie_circumvents_krum_at_moderate_z(hard_ds):
    """The ALIE mechanism against Krum: a crafted vector close enough to
    the cohort mean gets *selected* and drifts the model."""
    clean = final_accuracy(hard_ds, "Krum", NoAttack(), 0.0)
    attacked = final_accuracy(hard_ds, "Krum", DriftAttack(0.5), 0.21)
    assert clean > 90.0
    assert attacked < clean - 40.0


def test_krum_survives_oversized_z(hard_ds):
    """At the reference's default z=1.5 the crafted vector is too far out
    to be Krum-selected on this data, so Krum keeps accuracy — the
    documented flip side of the fixed-z quirk (SURVEY.md §2.4 #3)."""
    attacked = final_accuracy(hard_ds, "Krum", DriftAttack(1.5), 0.21)
    assert attacked > 90.0


def test_alie_degrades_trimmed_mean_at_default_z(hard_ds):
    clean = final_accuracy(hard_ds, "TrimmedMean", NoAttack(), 0.0)
    attacked = final_accuracy(hard_ds, "TrimmedMean", DriftAttack(1.5), 0.21)
    assert attacked < clean - 15.0


def test_alie_degrades_bulyan_at_default_z(hard_ds):
    clean = final_accuracy(hard_ds, "Bulyan", NoAttack(), 0.0)
    attacked = final_accuracy(hard_ds, "Bulyan", DriftAttack(1.5), 0.21)
    assert attacked < clean - 40.0


# ---------------------------------------------------------------------------
# backdoor mechanism
# ---------------------------------------------------------------------------

def test_backdoor_shadow_training_embeds_trigger():
    """With the clip released (huge z), the re-expressed gradient encodes
    shadow-net parameters whose poison accuracy is 100% (reference
    backdoor.py:108-159 pipeline)."""
    import jax

    from attacking_federate_learning_tpu.models import get_model
    from attacking_federate_learning_tpu.utils.flatten import make_flattener

    cfg = ExperimentConfig(dataset="SYNTH_MNIST", users_count=10,
                           mal_prop=0.24, batch_size=64, epochs=1,
                           defense="NoDefense", num_std=1e6,
                           backdoor="pattern", mal_epochs=5,
                           mal_batch_size=100)
    ds = load_dataset("SYNTH_MNIST", seed=0, synth_train=4000,
                      synth_test=1000)
    atk = make_attacker(cfg, dataset=ds)
    model = get_model("mnist_mlp")
    flat = make_flattener(model.init(jax.random.key(1)))
    w = flat.ravel(model.init(jax.random.key(1)))

    rng = np.random.default_rng(0)
    mal_grads = jnp.asarray(
        rng.standard_normal((2, flat.dim)).astype(np.float32) * 0.01)
    mean = mal_grads.mean(0)
    lr = jnp.asarray(0.1)
    crafted = atk._craft(mal_grads, w, lr, atk.operands())
    # Invert the gradient re-expression (backdoor.py:59-60) to recover the
    # shadow-trained parameters; unclipped because z is huge.
    start = w - lr * mean
    mal_params = start - lr * crafted - lr * mean
    _, correct = atk._poison_metrics(mal_params, atk.operands())
    assert float(correct) == atk.poison_count  # 100% trigger accuracy


def test_backdoor_crafted_grads_respect_clip_envelope():
    """With finite z the crafted vector must lie in [mean-z*sigma,
    mean+z*sigma] (reference backdoor.py:62-63) — the defense-evasion
    property."""
    cfg = ExperimentConfig(dataset="SYNTH_MNIST", users_count=10,
                           mal_prop=0.24, batch_size=64, epochs=1,
                           defense="NoDefense", num_std=1.5,
                           backdoor="pattern", mal_epochs=2,
                           mal_batch_size=100)
    ds = load_dataset("SYNTH_MNIST", seed=0, synth_train=2000,
                      synth_test=500)
    atk = make_attacker(cfg, dataset=ds)
    import jax

    from attacking_federate_learning_tpu.models import get_model
    from attacking_federate_learning_tpu.utils.flatten import make_flattener

    model = get_model("mnist_mlp")
    flat = make_flattener(model.init(jax.random.key(2)))
    w = flat.ravel(model.init(jax.random.key(2)))
    rng = np.random.default_rng(1)
    mal_grads = jnp.asarray(
        rng.standard_normal((3, flat.dim)).astype(np.float32) * 0.01)
    crafted = np.asarray(atk._craft(mal_grads, w, jnp.asarray(0.1),
                                    atk.operands()))
    mean = np.asarray(mal_grads.mean(0))
    sigma = np.asarray(mal_grads.std(0))
    assert (crafted <= mean + 1.5 * sigma + 1e-6).all()
    assert (crafted >= mean - 1.5 * sigma - 1e-6).all()
