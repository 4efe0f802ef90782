"""Clipped backdoor attack.

Reproduces the reference ``BackdoorAttack`` pipeline (reference
backdoor.py:13-159), restructured as pure jitted functions:

1. Project where honest descent would land this round:
   ``start = original_params - faded_lr * grads_mean`` (backdoor.py:54).
2. Fine-tune a shadow net from ``start`` on poisoned data — trigger pattern
   with target class 0, or a single sample relabeled (y+1)%5
   (backdoor.py:80-83, :128-131) — with the anchor loss
   ``NLL + alpha * sum_tensors MSE(p, p_start)`` (backdoor.py:140-148),
   skipping training entirely when the backdoor already classifies at 100%
   (backdoor.py:114-116).
3. Re-express the desired parameters as a gradient:
   ``new_grads = (start - (mal_params + lr*mean)) / lr`` (backdoor.py:59-60).
4. Launder it through the ALIE envelope: clip into
   ``[mean - z*sigma, mean + z*sigma]`` (backdoor.py:62-63) — the clipping is
   what defeats the statistical defenses.

Reference quirks preserved: the shadow optimizer is constructed fresh every
batch (backdoor.py:132), making its momentum inert — the effective update is
plain SGD with lr 0.1 and weight decay 1e-4, which is what the jitted
training loop implements; nan guards raise (backdoor.py:145-152).

Deviation (documented): reference 'sample k' mode indexes a shuffled
permutation via DistributedSampler rank k-1 (backdoor.py:33-34) and is
broken from the CLI (argparse leaves k a string, SURVEY.md §2.4 #10); here
'sample k' poisons training image k-1 directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from attacking_federate_learning_tpu.attacks.base import (
    Attack, cohort_stats, masked_cohort_stats
)
from attacking_federate_learning_tpu.core.evaluate import (
    masked_nll_metrics, pad_to_batches
)
from attacking_federate_learning_tpu.data import triggers
from attacking_federate_learning_tpu.models.base import get_model
from attacking_federate_learning_tpu.models.layers import nll_loss
from attacking_federate_learning_tpu.utils.flatten import make_flattener


class BackdoorAttack(Attack):
    name = "backdoor"
    # The engine checks aggregated weights for finiteness after fused
    # rounds/spans — the in-program replacement for the reference's
    # host-side nan raise (backdoor.py:145-152), see craft() below.
    checks_finite = True

    def __init__(self, cfg, dataset, model=None, flat=None, rng=None):
        super().__init__(cfg.num_std)
        self.cfg = cfg
        # The whole pipeline (shadow train included) is pure jitted jax,
        # so the round can fuse it (cfg.backdoor_fused, default).  Staged
        # mode retains the reference's exact per-round host nan guard.
        self.fusable = bool(getattr(cfg, "backdoor_fused", True))
        self.backdoor = cfg.backdoor
        self.alpha = cfg.alpha
        self.model = model or get_model(cfg.model)
        if flat is None:
            flat = make_flattener(self.model.init(jax.random.key(cfg.seed)))
        self.flat = flat
        self._build_poison_set(dataset, rng or np.random.default_rng(cfg.seed))
        self._build_fns()

    # ------------------------------------------------------------------
    def _build_poison_set(self, dataset, rng):
        B = self.cfg.mal_batch_size
        x, y = dataset.train_x, dataset.train_y
        if self.backdoor == "pattern":
            # A random 1/u strided shard, u = len/batch/10 (reference
            # backdoor.py:37-42) — about 10 batches of mal_batch_size.
            u = max(1, len(x) // B // 10)
            perm = rng.permutation(len(x))
            shard = perm[int(rng.integers(u))::u]
            px = jnp.asarray(x[shard])
            px = triggers.add_pattern(px)
            py = jnp.asarray(y[shard])
        else:
            # 'sample k': the single training image k-1 (see module
            # docstring on the reference's broken indexing).
            k = int(self.backdoor) - 1
            px = jnp.asarray(x[k: k + 1])
            py = jnp.asarray(y[k: k + 1])
        py = triggers.backdoor_targets(py, self.backdoor)

        # Pad to whole batches with a validity mask (static shapes; shared
        # helper with the server eval path).
        n = px.shape[0]
        bx, by, bm = pad_to_batches(np.asarray(px), np.asarray(py),
                                    min(B, n))
        self.poison_x = jnp.asarray(bx)
        self.poison_y = jnp.asarray(by)
        self.poison_mask = jnp.asarray(bm)
        self.poison_count = float(n)

    def operands(self):
        """The poison set (batched x, y and validity mask): drawn from
        the seed, so the jitted functions below take it as an argument
        rather than closing over it."""
        return self.poison_x, self.poison_y, self.poison_mask

    # ------------------------------------------------------------------
    def _build_fns(self):
        model, flat, cfg = self.model, self.flat, self.cfg
        alpha = self.alpha
        n_steps = cfg.mal_epochs * self.poison_x.shape[0]
        lr, wd = cfg.mal_learning_rate, cfg.mal_weight_decay

        def poison_metrics(flat_w, poison):
            """(loss, correct) over the poisoned set (reference
            backdoor.py:67-102; test_loader is the train loader,
            backdoor.py:43; loss is the sum of per-batch mean NLLs divided
            by the set size, matching backdoor.py:89, :93)."""
            params = flat.unravel(flat_w)
            loss_sum, correct = masked_nll_metrics(model.apply, params,
                                                   *poison)
            return loss_sum / self.poison_count, correct

        def poison_accuracy(flat_w, poison):
            _, correct = poison_metrics(flat_w, poison)
            return 100.0 * correct / self.poison_count

        def shadow_loss(params, anchor, x, y, m):
            logp = model.apply(params, x)
            per_ex = -jnp.take_along_axis(logp, y[:, None], axis=1).squeeze(1)
            cls = jnp.sum(per_ex * m) / jnp.maximum(jnp.sum(m), 1.0)
            # Anchor: sum over parameter tensors of per-tensor mean MSE
            # (torch MSELoss summed across parameters, backdoor.py:142-144).
            dist = sum(jnp.mean((p - a) ** 2)
                       for p, a in zip(jax.tree_util.tree_leaves(params),
                                       jax.tree_util.tree_leaves(anchor)))
            return cls + alpha * dist

        grad_fn = jax.grad(shadow_loss)

        def train_shadow(start_flat, poison):
            anchor = flat.unravel(start_flat)
            px, py, pm = poison

            def do_train(w0):
                def step(params, i):
                    b = i % px.shape[0]
                    g = grad_fn(params, anchor, px[b], py[b], pm[b])
                    # Fresh-optimizer-per-batch quirk: momentum buffer is
                    # always zero, so the update is SGD + weight decay
                    # (reference backdoor.py:132, SURVEY.md §2.4 #9).
                    params = jax.tree_util.tree_map(
                        lambda p, gi: p - lr * (gi + wd * p), params, g)
                    return params, None

                params, _ = jax.lax.scan(step, flat.unravel(w0),
                                         jnp.arange(n_steps))
                return flat.ravel(params)

            # Early-out when the backdoor already fires at 100%
            # (reference backdoor.py:114-116).
            return jax.lax.cond(
                poison_accuracy(start_flat, poison) >= 100.0,
                lambda w: w, do_train, start_flat)

        def craft(mal_grads, original_params, learning_rate, poison,
                  delivered=None):
            # ``delivered`` (async rounds, core/async_rounds.py): the
            # clip envelope and the descent projection come from the
            # DELIVERED malicious rows only — the server never
            # aggregates the rest, so laundering against the full
            # cohort would clip into an envelope nobody measures.
            if delivered is None:
                mean, stdev = cohort_stats(mal_grads)
            else:
                mean, stdev = masked_cohort_stats(mal_grads, delivered)
            start = original_params - learning_rate * mean
            mal_params = train_shadow(start, poison)
            new_params = mal_params + learning_rate * mean
            new_grads = (start - new_params) / learning_rate
            return jnp.clip(new_grads,
                            mean - self.num_std * stdev,
                            mean + self.num_std * stdev)

        self._craft = jax.jit(craft)
        self._poison_metrics = jax.jit(poison_metrics)

    # ------------------------------------------------------------------
    def craft(self, mal_grads, ctx):
        if ctx is not None and ctx.staleness is not None:
            f = mal_grads.shape[0]
            out = self._craft(mal_grads, ctx.original_params,
                              ctx.learning_rate, self._operands_from(ctx),
                              ctx.staleness[:f] >= 0)
        else:
            out = self._craft(mal_grads, ctx.original_params,
                              ctx.learning_rate, self._operands_from(ctx))
        if not isinstance(out, jax.core.Tracer):
            # Staged/eager path: the reference's per-round host nan guard
            # (backdoor.py:145-152).  Inside a fused round program the
            # engine checks the aggregated weights instead (checks_finite).
            if not bool(jnp.isfinite(out).all()):
                raise FloatingPointError(
                    "Got nan in backdoor shadow training")
        return out

    def envelope_stats(self, users_grads, corrupted_count, ctx=None):
        """Telemetry: the ALIE clip envelope the crafted gradient is
        laundered through (``||z*sigma||`` halfwidth) plus the shadow
        objective's state — poison-set loss/accuracy of the CURRENT
        global weights (when did the backdoor embed?).  Pure jitted jax,
        so the fused round program carries it without a host hop."""
        f = corrupted_count
        if f == 0 or self.num_std == 0:
            return {}
        if ctx is not None and ctx.staleness is not None:
            _, stdev = masked_cohort_stats(users_grads[:f],
                                           ctx.staleness[:f] >= 0)
        else:
            _, stdev = cohort_stats(users_grads[:f])
        loss, correct = self._poison_metrics(ctx.original_params,
                                             self._operands_from(ctx))
        return {"z": jnp.asarray(self.num_std, jnp.float32),
                "clip_halfwidth_norm": jnp.asarray(
                    self.num_std, jnp.float32) * jnp.linalg.norm(stdev),
                "shadow_loss": loss,
                "poison_acc": 100.0 * correct / self.poison_count}

    def margin_stats(self, users_grads, corrupted_count, ctx=None,
                     crafted=None):
        """Boost headroom (cfg.margins, ISSUE 18): how hard the crafted
        rows press against the ALIE clip envelope they were laundered
        through.  ``clip_saturation`` — the fraction of malicious
        coordinates pinned at a clip boundary (1.0 means the shadow
        objective wanted more than the envelope allows everywhere);
        ``boost_headroom`` — the mean remaining distance to the nearer
        clip edge, normalized by the envelope halfwidth (0 = at the
        boundary, 1 = at the honest mean).  Measured on the POST-attack
        rows against the PRE-attack envelope — no shadow-train
        re-run."""
        f = corrupted_count
        if f == 0 or self.num_std == 0 or crafted is None:
            return {}
        if ctx is not None and ctx.staleness is not None:
            mean, stdev = masked_cohort_stats(users_grads[:f],
                                              ctx.staleness[:f] >= 0)
        else:
            mean, stdev = cohort_stats(users_grads[:f])
        half = jnp.asarray(self.num_std, jnp.float32) * stdev
        lo, hi = mean - half, mean + half
        rows = crafted[:f]
        sat = jnp.mean(((rows <= lo[None, :]) | (rows >= hi[None, :]))
                       .astype(jnp.float32))
        head = jnp.minimum(hi[None, :] - rows, rows - lo[None, :])
        return {"clip_saturation": sat,
                "boost_headroom": jnp.mean(
                    head / jnp.maximum(half[None, :], 1e-12))}

    def test_asr(self, flat_w, logger=None, tag="POST"):
        """Attack success rate of the *server* weights on the poisoned set
        (reference main.py:91-95 + backdoor.py:67-102); log line format
        matches reference backdoor.py:97-101."""
        loss, correct = self._poison_metrics(jnp.asarray(flat_w),
                                             self.operands())
        acc = 100.0 * float(correct) / self.poison_count
        if logger is not None:
            logger.print(
                "##Test malicious net: [{}] Average loss: {:.4f}, "
                "Accuracy: {}/{} ({:.2f}%)".format(
                    tag, float(loss), int(correct), self.poison_count, acc))
        return acc


class TimedBackdoorAttack(BackdoorAttack):
    """The async timing-channel backdoor (ISSUE 9): identical crafting
    pipeline, but the attacker GAMES THE ARRIVAL SCHEDULE — its rows
    always emit with delay 0 (``timed``, read by
    core/async_rounds.py:draw_delays), so every delivered malicious row
    is fresh: full staleness weight, and a clip envelope computed
    against whatever stale honest rows share its bus.  The price is
    FIFO priority — freshest-born rows board the k-bus last — so the
    timing channel is a measured trade, not a free win (GRID_RESULTS
    round-9).  The attacker controls content and emission time only;
    arrival timestamps (hence weights) are the server's.

    Only meaningful under ``aggregation='async'`` — the engine and CLI
    reject it elsewhere (there is no arrival time to game)."""

    name = "backdoor_timed"
    timed = True
