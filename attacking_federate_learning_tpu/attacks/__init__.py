from attacking_federate_learning_tpu.attacks.base import (  # noqa: F401
    Attack, AttackContext, NoAttack, cohort_stats
)
from attacking_federate_learning_tpu.attacks.alie import DriftAttack  # noqa: F401
from attacking_federate_learning_tpu.utils.plugins import Registry
from attacking_federate_learning_tpu.utils.profiling import span

# Factories with the uniform signature (cfg, dataset) -> Attack, so new
# attacks plug in the way new defenses do (the reference hardwires its two
# attacks at main.py:44-54).
ATTACKS = Registry("attack")
ATTACKS.register("none", lambda cfg, dataset=None: NoAttack())
ATTACKS.register("alie", lambda cfg, dataset=None: DriftAttack(cfg.num_std))


def _make_backdoor(cfg, dataset=None):
    from attacking_federate_learning_tpu.attacks.backdoor import (
        BackdoorAttack
    )
    return BackdoorAttack(cfg, dataset=dataset)


def _make_backdoor_timed(cfg, dataset=None):
    from attacking_federate_learning_tpu.attacks.backdoor import (
        TimedBackdoorAttack
    )
    return TimedBackdoorAttack(cfg, dataset=dataset)


ATTACKS.register("backdoor", _make_backdoor)
ATTACKS.register("backdoor_timed", _make_backdoor_timed)

from attacking_federate_learning_tpu.attacks.baselines import (  # noqa: E402
    GaussianNoiseAttack, SignFlipAttack
)

ATTACKS.register("signflip",
                 lambda cfg, dataset=None: SignFlipAttack(cfg.num_std))
ATTACKS.register("noise",
                 lambda cfg, dataset=None: GaussianNoiseAttack(
                     cfg.num_std, seed=cfg.seed))

from attacking_federate_learning_tpu.attacks.minmax import (  # noqa: E402
    MinMaxAttack, MinSumAttack
)

ATTACKS.register("minmax",
                 lambda cfg, dataset=None: MinMaxAttack(
                     cfg.num_std, direction=cfg.attack_direction))
ATTACKS.register("minsum",
                 lambda cfg, dataset=None: MinSumAttack(
                     cfg.num_std, direction=cfg.attack_direction))


@span("setup.attacker")
def make_attacker(cfg, dataset=None, name=None):
    """Attack selection mirroring reference main.py:44-54: a backdoor option
    picks BackdoorAttack, otherwise ALIE DriftAttack."""
    if name is None:
        name = "backdoor" if cfg.backdoor else "alie"
    return ATTACKS[name](cfg, dataset=dataset)
