"""Least work of a configuration's own kernels: ``ops_bytes(**sizes)`` a file,
named by a metric file's ``module`` (readers/substage_roofline.py)."""
