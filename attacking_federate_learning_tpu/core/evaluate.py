"""Jitted test-set evaluation.

Reproduces the reference metric exactly (reference server.py:92-112): the
reported "average loss" is the *sum of per-batch mean NLLs* divided by the
test-set size — a quirk of ``test_loss += loss.item()`` with mean-reduction
batches (server.py:104-110) — plus the argmax-correct count.  The test set is
padded to a whole number of batches with a validity mask so the scan has
static shapes; masked per-batch means match the reference's short final
batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from attacking_federate_learning_tpu.models.base import Model
from attacking_federate_learning_tpu.utils.flatten import FlatParams


def pad_to_batches(x, y, batch_size):
    n = x.shape[0]
    n_batches = -(-n // batch_size)
    pad = n_batches * batch_size - n
    mask = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
    xp = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
    yp = np.concatenate([y, np.zeros((pad,) + y.shape[1:], y.dtype)])
    shape = (n_batches, batch_size)
    return (xp.reshape(shape + x.shape[1:]), yp.reshape(shape + y.shape[1:]),
            mask.reshape(shape))


def masked_nll_metrics(apply_fn, params, bx, by, bm):
    """Scan batched (nb, B, ...) data: returns (sum of per-batch masked-mean
    NLLs, masked correct count) — the reference's exact eval arithmetic
    (server.py:104-110), shared by server eval and the backdoor ASR check
    (backdoor.py:89-94).  A sequence model's (B, L, V) log-probs against
    (B, L) next tokens give the same two over tokens: the batch's mean
    token loss and the count of tokens predicted."""

    def batch_metrics(carry, batch):
        x, y, m = batch
        logp = apply_fn(params, x)
        per_ex = -jnp.take_along_axis(logp, y[..., None],
                                      axis=-1).squeeze(-1)
        count = jnp.sum(m)
        if per_ex.ndim > 1:             # tokens: (B, L) under a (B,) mask
            m = m.reshape(m.shape + (1,) * (per_ex.ndim - 1))
            count = count * (per_ex.size // m.size)
        batch_mean = jnp.sum(per_ex * m) / jnp.maximum(count, 1.0)
        correct = jnp.sum((jnp.argmax(logp, axis=-1) == y) * m)
        loss_sum, correct_sum = carry
        return (loss_sum + batch_mean, correct_sum + correct), None

    (loss_sum, correct_sum), _ = jax.lax.scan(
        batch_metrics, (jnp.zeros(()), jnp.zeros(())), (bx, by, bm))
    return loss_sum, correct_sum


def make_eval_fn(model: Model, flat: FlatParams, test_x, test_y, batch_size):
    """Returns jitted (flat_w) -> (test_loss, correct) on the full test set."""
    bx, by, bm = (jnp.asarray(a)
                  for a in pad_to_batches(test_x, test_y, batch_size))
    n_test = test_x.shape[0]

    @functools.partial(jax.jit, donate_argnums=())
    def evaluate(flat_w):
        params = flat.unravel(flat_w)
        loss_sum, correct_sum = masked_nll_metrics(model.apply, params,
                                                   bx, by, bm)
        return loss_sum / n_test, correct_sum

    return evaluate
