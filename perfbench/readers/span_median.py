"""Median milliseconds of one of the harness's own spans (run.py
``timed_calls``: blocked calls on the live state, after the window)."""


def read(obs, span):
    got = obs["spans"].get(span)
    return None if got is None else got["median_s"] * 1e3
