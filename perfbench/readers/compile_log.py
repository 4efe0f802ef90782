"""Compile seconds and persistent-cache misses during set-up, from the
program's own log of backend compiles (utils/costs.py ``compile_log``)."""


def read(obs, field):
    log = obs.get("compile_log_setup")
    if log is None:
        return None
    if field == "compile_s":
        return sum(c["compile_s"] for c in log)
    if field == "cache_misses":
        return sum(1 for c in log if c["cache"] == "miss")
    raise ValueError(f"compile_log: unknown field {field!r}")
