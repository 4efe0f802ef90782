"""The on-chip benchmark: run.py, tracereduce.py and data files (README.md)."""
