"""Model abstraction: a pair of pure functions plus shape metadata.

A model is ``init(key) -> params`` and ``apply(params, x) -> log_probs``
((batch, classes) for a classifier, (batch, length, vocabulary) for a
sequence model: the next token's at every position).
Params are ordered dicts in torch ``.parameters()`` order so the flat wire
vector (utils/flatten.py) matches the reference's byte layout.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

from attacking_federate_learning_tpu.utils.plugins import Registry


class Model(NamedTuple):
    name: str
    init: Callable            # (key) -> params pytree
    apply: Callable           # (params, x) -> (batch, classes) log-probs
    input_shape: Tuple[int, ...]   # per-example, e.g. (784,) or (3, 32, 32)
    num_classes: int
    # A sequence model's own training loss, (params, x, y) -> scalar: what
    # ``nll_loss(apply(params, x), y)`` computes, without the whole
    # (length, vocabulary) output alive (models/sequence.py); such a model
    # is stepped client by client, never vmapped over the cohort.  None:
    # the client step takes ``nll_loss`` of ``apply``.
    loss: Optional[Callable] = None
    sizes: Any = None              # the sizes a model was built from


MODELS = Registry("model")


def get_model(name: str) -> Model:
    return MODELS[name]()
