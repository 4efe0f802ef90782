"""Coordinate-wise median defense (Yin et al., ICML'18 — the companion
estimator to the trimmed mean the reference implements at
defences.py:44-52; the reference itself ships only the trimmed variant).

One jnp.median along the client axis: robust to up to half the clients per
coordinate, no selection state, fully shardable over the model axis.
"""

from __future__ import annotations

import jax.numpy as jnp

from attacking_federate_learning_tpu.defenses.kernels import DEFENSES


@DEFENSES.register("Median")
def median(users_grads, users_count, corrupted_count, impl="xla",
           telemetry=False, mask=None, weights=None, margins=False,
           numerics=False):
    """``impl='host'`` (opt-in, config ``median_impl``) routes to the
    native column-blocked kernel (native/bulyan_select.cpp:fl_median) —
    same rationale and same non-auto-dispatch rule as
    kernels.py:trimmed_mean.

    ``telemetry=True`` additionally returns ``{'dist_to_agg': (n,)}`` —
    each client's L2 distance to the aggregated median vector, the
    outlier view a coordinate-wise estimator admits (both impls: the
    distance is computed from the returned aggregate).

    ``mask`` (the quarantine seam, core/faults.py): the median of the
    alive rows only (kernels.py:masked_median — fixed shapes, traced
    alive count).

    ``weights`` (the staleness seam, core/async_rounds.py — requires
    ``mask``): the weighted lower median, the value where cumulative
    weight crosses half the mass (kernels.py:masked_median).

    ``margins=True`` (requires ``telemetry=True``; ISSUE 18)
    additionally returns ``margin_kept_frac``/``margin_boundary_dist``
    (utils/margins.py:median_pick_margins) — each row's pick mass
    from the exact rank membership of the median (so the picked values
    reconstruct the aggregate) and its inside-positive proximity to
    the rank-derived median.  Pure-XLA rank ops; the off-device host
    kernel raises.

    ``numerics=True`` (requires ``margins=True``; ISSUE 20)
    additionally returns ``num_tie_rows`` () int32 — boundary
    distances within TIE_BAND_ULPS ulp of the median pick, banded at
    the input's largest finite magnitude (utils/numerics.py)."""
    from attacking_federate_learning_tpu.defenses.kernels import (
        check_margin_seam, check_numerics_seam, check_weight_seam,
        masked_median
    )
    check_weight_seam(mask, weights)
    check_margin_seam(margins, telemetry)
    check_numerics_seam(numerics, margins)
    if margins and impl == "host":
        raise ValueError(
            "Median margins need the on-device ranks; impl='host' "
            "returns only the aggregate (defenses/host.py)")

    def margin_fields():
        from attacking_federate_learning_tpu.utils.margins import (
            median_pick_margins
        )
        mf = median_pick_margins(users_grads, mask=mask, weights=weights)
        if numerics:
            from attacking_federate_learning_tpu.utils.numerics import (
                max_finite_abs, tie_proximity
            )
            key = users_grads if mask is None else jnp.where(
                mask[:, None], users_grads, jnp.inf)
            mf["num_tie_rows"] = tie_proximity(
                mf["margin_boundary_dist"], max_finite_abs(key))
        return mf

    if mask is not None:
        if impl == "host":
            raise ValueError(
                "mask-aware Median has no host kernel "
                "(defenses/host.py is maskless); use impl='xla'")
        agg = masked_median(users_grads, mask, weights=weights)
        if not telemetry:
            return agg
        G = users_grads.astype(jnp.float32)
        dist = jnp.linalg.norm(G - agg.astype(jnp.float32)[None, :],
                               axis=1)
        diag = {"dist_to_agg": dist}
        if margins:
            diag.update(margin_fields())
        return agg, diag
    if impl == "host":
        from attacking_federate_learning_tpu.defenses.host import (
            host_median
        )
        from attacking_federate_learning_tpu.defenses.kernels import (
            host_coordwise
        )
        agg = host_coordwise(host_median, users_grads)
    else:
        agg = jnp.median(users_grads, axis=0)
    if not telemetry:
        return agg
    G = users_grads.astype(jnp.float32)
    dist = jnp.linalg.norm(G - agg.astype(jnp.float32)[None, :], axis=1)
    diag = {"dist_to_agg": dist}
    if margins:
        diag.update(margin_fields())
    return agg, diag
