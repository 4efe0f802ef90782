"""A kernel's share of its roofline from its own device time: the least
time the chip could take for one round's call -- max(ops / peak FLOP/s,
bytes / peak bytes/s), peaks from peaks.json -- over the device self time a
round under ``scope`` in the traced window (readers/substage.py).  The
metric file's ``args`` name all three: ``scope``; ``module``, whose
``perfbench.<module>.ops_bytes(**sizes)`` counts the work; and ``shape``,
the key of ``obs`` that holds those sizes (``"defense"``: n, d, f; its
``module`` entry is not a size).  So a further kernel is one more metric
file, its ops and bytes, and -- where its sizes are not the defense's -- a
key of ``obs`` that a configuration's or a cell's file fills."""

import importlib

from perfbench.readers import defense_roofline, substage


def read(obs, module, scope, shape):
    peaks, ms = obs.get("peaks"), substage.read(obs, scope)
    if peaks is None or ms is None or shape not in obs:
        return None
    sizes = {k: v for k, v in obs[shape].items() if k != "module"}
    ops, nbytes = importlib.import_module(
        "perfbench." + module).ops_bytes(**sizes)
    return defense_roofline.share("substage_roofline", ops, nbytes, peaks,
                                  ms / 1e3, scope=scope, device_s=ms / 1e3)
