"""The round programs take what they read as operands (PERF.md section 6,
PR 34).

``FederatedExperiment.data`` (core/engine.py ``RoundData``) holds the
device-resident training set, the client -> sample shards and every array
derived from ``--seed``; each jitted round program receives it as its first
argument and closes over none of it.  Three consequences, one parametrised
test each, over the flat, hierarchical and async builders under Krum + ALIE
and under a backdoor:

- the lowered span of two experiments that differ only in ``--seed`` is one
  program (one ``hlo_fingerprint``) and carries no constant of the size of
  anything in ``data``;
- a persistent compile cache that holds one seed's span serves the next
  seed's: the second experiment warms up without a span miss;
- the trajectory is that of the closure form (the set and the seed's arrays
  baked into the program as constants, which is what every PR before this
  one compiled) to the bit.
"""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attacking_federate_learning_tpu import config as C
from attacking_federate_learning_tpu.attacks import make_attacker
from attacking_federate_learning_tpu.config import ExperimentConfig
from attacking_federate_learning_tpu.core.engine import FederatedExperiment
from attacking_federate_learning_tpu.data.datasets import load_dataset
from attacking_federate_learning_tpu.utils import costs

N_TRAIN = 640           # 640 x 784 f32 = 2.0 MB: over the 1 MiB of the claim
BUILDERS = {
    "flat": dict(users_count=16),
    "hierarchical": dict(users_count=32, aggregation="hierarchical",
                         megabatch=4),
    "async": dict(users_count=16, aggregation="async", async_buffer=8,
                  async_max_staleness=2),
}
TRAFFIC = {
    # the two cells' federation (perfbench/traffic/krum_alie_*.json)
    "krum_alie": dict(defense="Krum", mal_prop=0.24, num_std=1.5),
    # mal_batch_size 32: 640 / 32 / 10 = 2 strides of 320 samples each,
    # so every seed's poison set has one shape
    "backdoor": dict(defense="TrimmedMean", mal_prop=0.2, backdoor="pattern",
                     mal_batch_size=32, mal_epochs=1),
}
CASES = [(b, t) for b in BUILDERS for t in TRAFFIC]
IDS = [f"{b}-{t}" for b, t in CASES]
# The CNN cell's federation at small n, for the trajectory pin.
CNN = dict(dataset=C.SYNTH_CIFAR10_HARD, model="cifar10_cnn", users_count=8,
           batch_size=4, **TRAFFIC["krum_alie"])

_DS = {}


def _experiment(builder, traffic, seed, **over):
    kw = dict(dataset=C.SYNTH_MNIST, batch_size=8, epochs=8,
              **BUILDERS[builder], **TRAFFIC[traffic])
    kw.update(over)
    cfg = ExperimentConfig(synth_train=N_TRAIN, synth_test=32, seed=seed,
                           **kw)
    if cfg.dataset not in _DS:
        # one public dataset whatever the run's seed (perfbench/run.py)
        _DS[cfg.dataset] = load_dataset(cfg.dataset, seed=0,
                                        synth_train=N_TRAIN, synth_test=32)
    ds = _DS[cfg.dataset]
    return FederatedExperiment(cfg, attacker=make_attacker(cfg, dataset=ds),
                               dataset=ds)


def _span_args(exp, count):
    """(jitted span, its arguments after ``data``, the argument numbers
    that are static once ``data`` is bound) as ``run_span`` dispatches."""
    t0 = jnp.asarray(0, jnp.int32)
    if exp._async is not None:
        return exp._async_span, (exp.state, t0, count,
                                 exp._async_state), (2,)
    return exp._fused_span, (exp.state, t0,
                             jnp.asarray(count, jnp.int32)), ()


def _lowered_hlo(exp, count=3):
    span, args, _ = _span_args(exp, count)
    return span.lower(exp.data, *args).as_text(dialect="hlo")


def _constant_bytes(hlo_text):
    """Bytes of every constant in an HLO text, largest first."""
    sizes = []
    for m in re.finditer(r"= (\w+?)(\d*)\[([\d,]*)\][^\n]*? constant\(",
                         hlo_text):
        elems = int(np.prod([int(v) for v in m.group(3).split(",") if v]))
        sizes.append(elems * max(1, int(m.group(2) or 8) // 8))
    return sorted(sizes, reverse=True)


@pytest.mark.parametrize("builder,traffic", CASES, ids=IDS)
def test_two_seeds_lower_to_one_span(builder, traffic):
    a, b = (_experiment(builder, traffic, seed) for seed in (3, 2147495301))
    # the seeds do differ in what the span reads ...
    assert not np.array_equal(np.asarray(a.data.shards),
                              np.asarray(b.data.shards))
    text_a, text_b = _lowered_hlo(a), _lowered_hlo(b)
    # ... and the program does not see it
    assert costs.hlo_fingerprint(text_a) == costs.hlo_fingerprint(text_b)
    # Nothing of data's size is baked in: the smallest thing a seed draws
    # here is a key (8 bytes), the set is 2 MB; a builder's own constants
    # (iotas, the placement grid, masks) stay under a kilobyte.
    leaves = [leaf.nbytes for leaf in jax.tree.leaves(a.data)
              if hasattr(leaf, "nbytes")]
    assert max(leaves) > 2 ** 20
    consts = _constant_bytes(text_a)
    assert not consts or consts[0] < 1024, consts[:5]


@contextlib.contextmanager
def _persistent_cache_at(path):
    """The persistent compile cache at ``path`` and open to programs of any
    compile time (a tiny CPU compile is under the program's 0.5 s), for
    the block; the suite's own cache after it."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prev = [getattr(jax.config, n) for n in names]
    for n, v in zip(names, (str(path), 0.0, 0)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for n, v in zip(names, prev):
            jax.config.update(n, v)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("builder,traffic", CASES, ids=IDS)
def test_second_seed_warms_up_from_the_first_seeds_cache(builder, traffic,
                                                         tmp_path):
    costs.install_cache_counters()

    def warm_up(seed):
        log0, counts0 = len(costs.compile_log()), costs.cache_counts()
        exp = _experiment(builder, traffic, seed)
        exp.run_span(0, 3)
        jax.block_until_ready(exp.state.weights)
        spans = [c for c in costs.compile_log()[log0:]
                 if "span" in (c["name"] or "")]
        misses = costs.cache_counts()["misses"] - counts0["misses"]
        return spans, misses

    with _persistent_cache_at(tmp_path):
        first, _ = warm_up(3)
        assert [c["cache"] for c in first] == ["miss"], first
        second, misses = warm_up(2147495301)
    # the second seed's span came out of the cache, and so did every
    # other program of its warm-up
    assert [c["cache"] for c in second] == ["hit"], second
    assert misses == 0


def _closure_form(exp, count):
    """The span with ``exp.data`` bound at trace time: the set, the shards
    and the seed's arrays become constants of the program, as they were
    before they were operands."""
    span, args, static = _span_args(exp, count)
    closed = jax.jit(functools.partial(span.__wrapped__, exp.data),
                     static_argnums=static)
    return closed, args


@pytest.mark.parametrize("kw", [
    dict(builder="flat", traffic="krum_alie"),
    dict(builder="flat", traffic="krum_alie", **CNN),
    dict(builder="hierarchical", traffic="krum_alie"),
    dict(builder="async", traffic="krum_alie"),
    dict(builder="flat", traffic="backdoor"),
    dict(builder="flat", traffic="krum_alie", participation=0.5,
         partition="femnist_style"),
], ids=["mlp_cell", "cnn_cell", "hierarchical", "async", "backdoor",
        "partial_styled"])
def test_rounds_equal_the_closure_form_to_the_bit(kw):
    """k rounds as one span: the state after them is the same 32-bit words
    whether the program read ``data`` as operands or as constants."""
    k = 4
    exp = _experiment(seed=7, **kw)
    closed, args = _closure_form(exp, k)
    want = closed(*args)[0]
    exp.run_span(0, k)
    for got, ref in zip(jax.tree.leaves(exp.state), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(ref)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert int(exp.state.round) == k
