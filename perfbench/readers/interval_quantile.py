"""A quantile (nearest rank) of the eval-interval times in the window, in
milliseconds.  One interval = test_step rounds + one eval + its host work."""

import math


def read(obs, q):
    marks = obs["marks"]
    samples = sorted((b[1] - a[1]) * 1e3 for a, b in zip(marks, marks[1:]))
    if not samples:
        return None
    return samples[max(0, math.ceil(q * len(samples)) - 1)]
