"""A quantity the runner observed directly (``setup_s``, peak memory)."""


def read(obs, key, scale=1.0):
    value = obs.get(key)
    return None if value is None else value * scale
