"""The whole round's share of the chip's peak: the FLOPs a round needs --
the configuration's ``train_flops_per_sample()`` (forward + backward,
recomputation not counted) times the samples a round, plus the defense's
``ops_bytes`` FLOPs -- over the window's seconds a round (whole eval
intervals: evals and host seams included) times the bf16 peak.  The bound
on what a faster kernel can claim once its own roofline share goes silent.
Every FLOP is held against the chip's bf16 peak, as ``defense_roofline``
does, although the Gram runs f32 at ``Precision.HIGHEST`` (several bf16
passes): the share is of what the chip has, not of what that precision
could reach."""

import json


def read(obs):
    peaks, marks = obs.get("peaks"), obs.get("marks") or []
    per_sample = getattr(obs["config"]["module"], "train_flops_per_sample",
                         None)
    if peaks is None or per_sample is None or len(marks) < 2:
        return None
    (r0, t0), (r1, t1) = marks[0], marks[-1]
    d = obs["defense"]
    client = per_sample() * obs["config"]["samples_per_round"]
    defense, _ = d["module"].ops_bytes(d["n"], d["d"], d["f"])
    seconds = (t1 - t0) / (r1 - r0)
    print("[perfbench] round_mfu", json.dumps({
        "client_flops": client, "defense_flops": defense,
        "seconds_per_round": seconds}), flush=True)
    return 100.0 * (client + defense) / (seconds * peaks["bf16_flops_per_s"])
