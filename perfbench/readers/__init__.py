"""Metric readers: ``read(obs, **args)`` returns the value, or None when
there is nothing to read (the runner then leaves the metric out)."""
