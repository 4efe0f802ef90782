"""Device self time under one of the program's named scopes, from the
traced window: milliseconds a round.

The scopes are the program's own vocabulary (``utils/costs.py``: the
``STAGES`` and the ``SUBSTAGES`` inside them); the booking is the
benchmark's (``tracereduce.scope_times``: the window, the self times and the
join through ``obs["span_hlo_text"]``).  Every operation books to the
innermost scope on its path, so the scopes, ``unattributed`` and
``other_programs`` (the eval) sum to the window's busy time; the whole
table goes on an earlier ``[perfbench] scope_times_ms`` line.  None where
there is no device trace, no span text, or nothing ran under ``scope``.
"""

import json

from perfbench import tracereduce


def scopes():
    """The program's scope names."""
    from attacking_federate_learning_tpu.utils.costs import STAGES, SUBSTAGES

    return frozenset(STAGES) | frozenset(SUBSTAGES)


def booked(obs):
    """``tracereduce.scope_times`` of this run, made once and kept in
    ``obs`` for the other metrics that read it."""
    if "scope_times" not in obs:
        obs["scope_times"] = tracereduce.scope_times(
            obs.get("xplane"), obs.get("span_hlo_text"), scopes())
        got = obs["scope_times"]
        if got is not None and got["periods"]:
            per = got["periods"] * 1e6
            print("[perfbench] scope_times_ms", json.dumps({
                "per": "interval", "periods": got["periods"],
                "busy": got["busy_ns"] / per,
                "scopes": {k: v / per for k, v in got["scopes"].items()}}),
                flush=True)
    return obs["scope_times"]


def read(obs, scope):
    got = booked(obs)
    if got is None or not got["periods"] or not got["scopes"].get(scope):
        return None
    return got["scopes"][scope] / (got["periods"] * obs["test_step"]) / 1e6
