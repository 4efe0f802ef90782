"""Batched client computation.

The reference runs N sequential ``User.step`` calls per round, each loading
the broadcast weights into a private net copy and doing one minibatch
forward/backward with no local optimizer step (reference server.py:54-56,
user.py:83-92).  Here the entire client population is one call:

    grads = vmap(grad(loss))(broadcast_weights, client_xs, client_ys)

over stacked per-client batches, returning the (n, d) flat gradient matrix
directly in wire format.  Under pjit the client axis shards across devices
(parallel/), which is the TPU-native form of the reference's simulated data
parallelism (SURVEY.md §2.2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from attacking_federate_learning_tpu.models.base import Model
from attacking_federate_learning_tpu.models.layers import nll_loss
from attacking_federate_learning_tpu.utils.flatten import (
    FlatParams, write_row
)


def make_loss_fn(model: Model, flat: FlatParams, remat: bool = False):
    """Mean-NLL loss on flat wire-format weights (reference user.py:36,
    :77-79: log_softmax head + NLLLoss).

    ``remat=True`` wraps the loss in ``jax.checkpoint`` so the backward
    pass recomputes activations instead of storing them — the standard
    HBM/FLOPs trade for big models (WRN-40-4) or big client cohorts,
    where the vmapped (n, B, activations) footprint dominates memory.
    """

    params_loss = make_params_loss_fn(model)

    def loss_fn(flat_w, x, y):
        return params_loss(flat.unravel(flat_w), x, y)

    return jax.checkpoint(loss_fn) if remat else loss_fn


def make_params_loss_fn(model: Model):
    """The same loss on the params pytree: the model's own where it has
    one (a sequence model's, chunked over rows), else ``nll_loss`` of
    ``apply``."""
    if model.loss is not None:
        return model.loss
    return lambda params, x, y: nll_loss(model.apply(params, x), y)


def cohort_fits(n: int, d: int, wire_dtype, device_bytes) -> bool:
    """Do the cohort's f32 gradients (what ``vmap(grad)`` makes, n x d x 4
    bytes) fit on the device beside the (n, d) wire in ``wire_dtype`` and
    the server's weights and momentum?  ``device_bytes`` None (a backend
    that reports no limit): they do."""
    if device_bytes is None:
        return True
    need = n * d * (4 + jnp.dtype(wire_dtype).itemsize) + 2 * 4 * d
    return need <= device_bytes


def make_scanned_client_grad_fn(model: Model, flat: FlatParams, wire_dtype):
    """(d,), (n, B, ...), (n, B) -> (n, d) in ``wire_dtype``, one client
    at a time: a ``lax.scan`` over clients whose body takes one client's
    gradient as a pytree and writes it, cast leaf by leaf, into that
    client's row of the wire (utils/flatten.py:write_row).  For a cohort
    whose f32 gradients do not fit beside the wire (:func:`cohort_fits`):
    one client's gradient is alive at a time, and no f32 row is
    concatenated.  The weights are unravelled once, outside the scan."""
    grad_fn = jax.grad(make_params_loss_fn(model))

    def clients_grads(flat_w, xs, ys):
        params = flat.unravel(flat_w)

        def one_client(wire, batch):
            i, x, y = batch
            return write_row(wire, i, grad_fn(params, x, y)), None

        n = xs.shape[0]
        wire, _ = jax.lax.scan(
            one_client, jnp.zeros((n, flat.dim), wire_dtype),
            (jnp.arange(n), xs, ys))
        return wire

    return clients_grads


def make_client_grad_fn(model: Model, flat: FlatParams, remat: bool = False):
    """(d,), (n, B, ...), (n, B) -> (n, d) per-client gradients."""
    grad_fn = jax.grad(make_loss_fn(model, flat, remat))

    def clients_grads(flat_w, xs, ys):
        return jax.vmap(grad_fn, in_axes=(None, 0, 0))(flat_w, xs, ys)

    return clients_grads


def make_client_update_fn(model: Model, flat: FlatParams,
                          local_steps: int = 1, remat: bool = False,
                          scan_dtype=None):
    """FedAvg-style local training (beyond-reference: the reference is
    strictly FedSGD — one minibatch gradient, never a local optimizer
    step, user.py:80).

    With ``local_steps == 1`` this IS :func:`make_client_grad_fn` (exact
    reference semantics, lr-independent).  With k > 1 each client runs k
    plain-SGD steps at the dispatched (faded) ``lr_train`` and reports the
    pseudo-gradient ``(w0 - w_k) / lr_report``, where ``lr_report`` is the
    lr the *server* will multiply back in — the FedAvg-as-FedSGD reduction
    is exact only when the divisor matches the server's multiplier (which,
    reference quirk, is the constant base lr while clients fade,
    reference server.py:89 vs :50-52).

    ``scan_dtype``: the wire's dtype where the cohort is to be scanned
    client by client (:func:`make_scanned_client_grad_fn`; the engine
    asks for it where :func:`cohort_fits` says no), None for the vmapped
    form.

    Signature: (d,), (n, k, B, ...), (n, k, B), lr_train, lr_report
    -> (n, d).
    """
    if scan_dtype is not None and local_steps != 1:
        raise ValueError(
            "a cohort too wide for vmap(grad) is scanned client by "
            "client, which is built for local_steps=1 only")
    if local_steps == 1:
        base = (make_client_grad_fn(model, flat, remat)
                if scan_dtype is None else
                make_scanned_client_grad_fn(model, flat, scan_dtype))

        def clients_update(flat_w, xs, ys, lr_train, lr_report):
            # Squeeze the k=1 step axis; lrs are unused (parity: the
            # reference's client optimizer never steps).
            return base(flat_w, xs[:, 0], ys[:, 0])

        return clients_update

    grad_fn = jax.grad(make_loss_fn(model, flat, remat))

    def one_client(flat_w, xs, ys, lr_train, lr_report):
        def step(w, batch):
            x, y = batch
            return w - lr_train * grad_fn(w, x, y), None

        wk, _ = jax.lax.scan(step, flat_w, (xs, ys))
        return (flat_w - wk) / lr_report

    def clients_update(flat_w, xs, ys, lr_train, lr_report):
        return jax.vmap(one_client, in_axes=(None, 0, 0, None, None))(
            flat_w, xs, ys, lr_train, lr_report)

    return clients_update
