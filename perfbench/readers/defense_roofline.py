"""The defense call's share of its roofline: the least time the chip could
take for one call -- max(ops / peak FLOP/s, bytes / peak bytes/s), ops and
bytes from the defense's own file, peaks from peaks.json -- over the
host-clocked whole call.  A floor on waste, not a kernel's device time."""

import json


def share(label, ops, nbytes, peaks, seconds, **said):
    """100 x least time / ``seconds``; the ops, the bytes and which peak
    bounds them go on a ``[perfbench] <label>`` line."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    print(f"[perfbench] {label}", json.dumps(dict(
        said, ops=ops, bytes=nbytes, least_s=max(t_ops, t_bytes),
        bound="compute" if t_ops >= t_bytes else "memory")), flush=True)
    return 100.0 * max(t_ops, t_bytes) / seconds


def read(obs):
    span, peaks = obs["spans"].get("defense"), obs.get("peaks")
    if span is None or peaks is None:
        return None
    d = obs["defense"]
    ops, nbytes = d["module"].ops_bytes(d["n"], d["d"], d["f"])
    return share("defense_roofline", ops, nbytes, peaks, span["median_s"],
                 call_s=span["median_s"])
