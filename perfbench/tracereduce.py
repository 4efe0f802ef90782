"""From a profiler trace to device numbers: busy union, idle share, the
operations that took most time and the longest idle gaps.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain structure
(device planes only: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``); ``reduce`` works on that
structure alone, so it is checked on a recorded one (testdata/).

A TPU device plane carries a line of whole programs ("XLA Modules") and a
line of operations ("XLA Ops").  The window is whole periods of the
program that takes most of the time (the span) -- from its first start to
its last start -- so every period holds one span, one eval and the host's
work between them.
Busy is the union of the operation intervals inside the window; a gap in
it is named by the programs on either side of it.  Operations nest (a
scanned span is one ``while`` around its body), so an operation's time is
its self time: its duration less its direct children's.
"""

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
NAME_CHARS = 160    # an operation's name is its whole HLO line: keep the head


def load_xplane(path):
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = [{"name": line.name,
                  "events": [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                             for ev in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_profile_dir(trace_dir):
    """The one ``.xplane.pb`` that ``jax.profiler.start_trace(trace_dir)``
    left, or None."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return load_xplane(found[0]) if found else None


def program_name(name):
    """``jit_span(123456789)`` -> ``jit_span``: the fingerprint changes
    with the seed, the name does not."""
    return re.sub(r"\(\d+\)$", "", name)


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return sorted(line["events"], key=lambda e: (e[1], -e[2]))
    return []


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _self_times(events):
    """(name, self_ns) per event of a start-sorted, properly nested list."""
    out, stack = [], []      # stack of [name, end, self_ns]
    for name, a, b in events:
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    out.extend((name, self_ns) for name, _, self_ns in stack)
    return out


def _union(events):
    merged = []
    for _, a, b in events:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _gap_label(a, b, modules):
    mid = (a + b) / 2
    before = after = None
    for name, start, dur in modules:
        if start <= mid < start + dur:
            return "inside " + program_name(name)
        if start + dur <= mid:
            before = name
        elif after is None and start >= mid:
            after = name
    return (f"host between {program_name(before) if before else 'start'}"
            f" and {program_name(after) if after else 'end'}")


def reduce_plane(plane):
    ops, modules = _line(plane, OPS_LINE), _line(plane, MODULES_LINE)
    if not ops:
        return None
    lo, hi = ops[0][1], max(s + d for _, s, d in ops)
    total = {}
    for name, _, dur in modules:
        total[name] = total.get(name, 0) + dur
    longest = max(total, key=total.get, default=None)     # the span
    starts = [s for name, s, _ in modules if name == longest]
    if len(starts) >= 2:
        lo, hi = starts[0], starts[-1]
    ops = _clip(ops, lo, hi)
    merged = _union(ops)
    busy = sum(b - a for a, b in merged)
    by_op = {}
    for name, self_ns in _self_times(ops):
        by_op[name] = by_op.get(name, 0) + self_ns
    by_gap = {}
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            label = _gap_label(a, b, modules)
            by_gap[label] = by_gap.get(label, 0) + (b - a)

    def top(table):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name[:NAME_CHARS], ns / 1e9] for name, ns in ranked]

    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            "periods": max(len(starts) - 1, 0),
            "device_ops": top(by_op), "idle_gaps": top(by_gap)}


def reduce(trace):
    """Busy and window averaged over the device planes that ran anything;
    operations and gaps from the first of them.  None when no device
    operation was traced."""
    if trace is None:
        return None
    planes = sorted((p for p in trace["planes"]
                     if p["name"].startswith(DEVICE_PLANE)),
                    key=lambda p: p["name"])
    per = [r for r in map(reduce_plane, planes) if r and r["busy_s"] > 0]
    if not per:
        return None
    busy = sum(r["busy_s"] for r in per) / len(per)
    window = sum(r["window_s"] for r in per) / len(per)
    return {"busy_s": busy, "window_s": window, "chips": len(per),
            "periods": per[0]["periods"],
            "idle_pct": 100.0 * (1.0 - busy / window),
            "device_ops": per[0]["device_ops"],
            "idle_gaps": per[0]["idle_gaps"]}
