"""A field of the reduced device trace (tracereduce.py ``reduce``)."""


def read(obs, field):
    trace = obs.get("trace")
    return None if trace is None else trace.get(field)
