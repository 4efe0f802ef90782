"""Attack framework.

The reference's attack seam is ``Attack.attack(mal_users)`` called once per
round between client compute and gradient collection (reference main.py:66-68,
malicious.py:10-27): it computes the mean and population std of the malicious
cohort's *honest* gradients, asks the subclass for one crafted vector, and
overwrites every malicious client's gradient with that same vector
(malicious.py:26-27).

Here the seam is functional: ``craft(mal_grads (m, d), ctx) -> (d,)``
produces the crafted vector and the engine broadcasts it into the first f
rows of the (n, d) gradient matrix (malicious clients are the first f ids,
reference main.py:28).  ``ctx`` carries what the reference stashes on user 0
(user.py:84-86): the round's broadcast weights and the faded learning rate.

``num_std == 0`` disables crafting and leaves the honest gradients in place
(reference malicious.py:21-22).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


class AttackContext(NamedTuple):
    original_params: jax.Array   # (d,) weights broadcast this round
    learning_rate: jax.Array     # faded lr (reference server.py:50-52)
    round: jax.Array = 0         # () int32 round index (rng derivation)
    # Asynchronous rounds only (core/async_rounds.py): the (m,) int32
    # per-row staleness view of the DELIVERED cohort — t - birth on
    # delivered rows, -1 on undelivered ones.  None under the
    # synchronous topologies, where every row is fresh by construction.
    # The attack seam runs at DELIVERY time in async mode, so crafting
    # statistics must come from the delivered sub-cohort
    # (:func:`delivered_cohort_stats`) — the aggregation never sees the
    # rest.
    staleness: Optional[jax.Array] = None
    # The attacker's own arrays (:meth:`Attack.operands`) as the round
    # program received them: the engine passes them in as operands and
    # hands them back here, so a jitted round does not close over a
    # poison set or a seed's key.  None (a caller with no engine): the
    # attack reads its own attributes.
    operands: Any = None


def cohort_stats(mal_grads):
    """Mean and population std over the malicious cohort
    (reference malicious.py:18-19: np.var ** 0.5, i.e. ddof=0).  A bf16
    wire's rows go in as they are and the statistics come out (d,) f32:
    the upcast fuses into the reductions, no f32 copy of the rows."""
    mal_grads = mal_grads.astype(jnp.float32)
    mean = jnp.mean(mal_grads, axis=0)
    stdev = jnp.sqrt(jnp.var(mal_grads, axis=0))
    return mean, stdev


def masked_cohort_stats(mal_grads, delivered):
    """Mean and population std over the DELIVERED malicious rows only
    (``delivered`` (f,) bool) — fixed shapes, traced delivered count.
    With every row delivered this computes exactly
    :func:`cohort_stats` up to summation order (mean-of-all vs
    sum/count are the same reduction here: sum over the full axis
    divided by the full count)."""
    mal_grads = mal_grads.astype(jnp.float32)
    e = jnp.maximum(jnp.sum(delivered), 1)
    mean = jnp.sum(jnp.where(delivered[:, None], mal_grads, 0.0),
                   axis=0) / e
    var = jnp.sum(jnp.where(delivered[:, None],
                            (mal_grads - mean[None, :]) ** 2, 0.0),
                  axis=0) / e
    return mean, jnp.sqrt(var)


def delivered_cohort_stats(mal_grads, ctx):
    """The crafting statistics an attack seam should use: the classic
    full-cohort stats under the synchronous topologies, the
    delivered-sub-cohort stats in async mode (``ctx.staleness >= 0``
    marks delivery) — how ALIE "recalibrates its envelope to the
    delivered cohort" (ISSUE 9)."""
    if ctx is None or ctx.staleness is None:
        return cohort_stats(mal_grads)
    f = mal_grads.shape[0]
    return masked_cohort_stats(mal_grads, ctx.staleness[:f] >= 0)


class Attack:
    """Base class; subclasses implement ``craft``."""

    name = "none"

    def __init__(self, num_std: float):
        self.num_std = num_std

    def operands(self):
        """The arrays this attack reads and never writes — a poison
        set, a key derived from the seed — as one pytree, or None.  The
        engine makes them operands of its round programs
        (core/engine.py RoundData.attack) and returns them through
        ``AttackContext.operands``."""
        return None

    def _operands_from(self, ctx):
        """``ctx.operands`` where an engine bound them, else our own."""
        if ctx is not None and ctx.operands is not None:
            return ctx.operands
        return self.operands()

    def craft(self, mal_grads, ctx: AttackContext):
        """(m, d) honest malicious-cohort grads -> (d,) crafted vector."""
        raise NotImplementedError

    def apply(self, users_grads, corrupted_count: int,
              ctx: Optional[AttackContext] = None):
        """Full seam: returns users_grads with the first f rows replaced.

        No-ops when there are no malicious users (reference malicious.py:11)
        or num_std == 0 (malicious.py:21).
        """
        f = corrupted_count
        if f == 0 or self.num_std == 0:
            return users_grads
        crafted = self.craft(users_grads[:f], ctx)
        return users_grads.at[:f].set(
            crafted[None, :].astype(users_grads.dtype))

    def envelope_stats(self, users_grads, corrupted_count: int,
                       ctx: Optional[AttackContext] = None) -> dict:
        """Telemetry seam (core/engine.py, cfg.telemetry): fixed-shape,
        device-side stats of the attack's crafting envelope, computed on
        the PRE-attack gradient matrix — the same honest malicious-cohort
        view ``craft`` derives its statistics from.  Must stay pure jax
        (it runs inside the fused round program; no host callbacks).
        Default: nothing to report."""
        return {}

    def margin_stats(self, users_grads, corrupted_count: int,
                     ctx: Optional[AttackContext] = None,
                     crafted=None) -> dict:
        """Margin-observatory seam (core/engine.py, cfg.margins; ISSUE
        18): fixed-shape, device-side ENVELOPE-UTILIZATION margins —
        how much of the defense-evading envelope the attack actually
        spends (the attack-side complement of the defenses' decision
        margins, utils/margins.py).  ``users_grads`` is the PRE-attack
        matrix (the honest view ``craft`` derives its statistics
        from); ``crafted`` is the POST-attack matrix, for attacks
        whose utilization is a property of the delivered rows (the
        backdoor's clip saturation).  Must stay pure jax (it runs
        inside the fused round program; no host callbacks).  Default:
        nothing to report."""
        return {}


class NoAttack(Attack):
    name = "none"

    def __init__(self):
        super().__init__(num_std=0.0)

    def apply(self, users_grads, corrupted_count, ctx=None):
        return users_grads
