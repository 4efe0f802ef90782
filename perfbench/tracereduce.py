"""From a profiler trace to device numbers: busy union, idle share, the
operations that took most time and the longest idle gaps.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain structure
(device planes only: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``); ``reduce`` and
``scope_times`` work on that structure alone, so they are checked on
recorded ones (testdata/).

A TPU device plane carries a line of whole programs ("XLA Modules") and a
line of operations ("XLA Ops").  The window is whole periods of the
program that takes most of the time (the span) -- from its first start to
its last start -- so every period holds one span, one eval and the host's
work between them.
Busy is the union of the operation intervals inside the window; a gap in
it is named by the programs on either side of it.  Operations nest (a
scanned span is one ``while`` around its body), so an operation's time is
its self time: its duration less its direct children's.

``scope_times`` books the same self times, inside the same window, to the
program's named scopes.  A TPU operation event is named by its whole HLO
line and carries no scope of its own, so the scope comes from a join: the
event name's head is an instruction of the span's compiled HLO text, whose
``op_name`` is the ``jax.named_scope`` path it was traced under.
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
    """(name, start, self_ns) per event of a start-sorted, properly nested
    list."""
    out, stack = [], []      # stack of [name, end, self_ns, start]
    for name, a, b in events:
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            out.append((top[0], top[3], top[2]))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a, a])
    out.extend((name, a, self_ns) for name, _, self_ns, a in stack)
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


def _span_window(plane):
    """(operations clipped to the window, programs, lo, hi, span starts,
    the span's name) of one device plane: the window is whole periods of
    the program that takes most of the time."""
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
    return _clip(ops, lo, hi), modules, lo, hi, starts, longest


def reduce_plane(plane):
    window = _span_window(plane)
    if window is None:
        return None
    ops, modules, lo, hi, starts, _ = window
    merged = _union(ops)
    busy = sum(b - a for a, b in merged)
    by_op = {}
    for name, _, self_ns in _self_times(ops):
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


# --- device self time by named scope ----------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_EVENT_INSTR = re.compile(r"^%?([\w.\-]+)")
UNNAMED = "unattributed"        # in the span, under no named scope
OTHER = "other_programs"        # outside the span (the eval)


def hlo_scope_paths(text):
    """Instruction name -> ``op_name`` scope path of one compiled HLO text.
    An instruction the compiler made itself (a layout copy, a convert
    between two fusions) has no ``op_name``: it takes the path of the
    nearest instruction that produced one of its operands, or ''."""
    own, operands = {}, {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        named = _OP_NAME.search(line)
        own[m.group(1)] = named.group(1) if named else ""
        if not named:
            operands[m.group(1)] = _OPERAND.findall(line[m.end():])

    def producer_path(name, depth=8):
        if own.get(name):
            return own[name]
        for operand in operands.get(name, ()) if depth else ():
            path = producer_path(operand, depth - 1)
            if path:
                return path
        return ""

    return {name: producer_path(name) for name in own}


def innermost(path, scopes):
    """The last component of a scope path that is one of ``scopes``; the
    final component is the primitive's own name (``lax.gather``'s is
    "gather"), never a scope."""
    for token in reversed(path.split("/")[:-1]):
        if token in scopes:
            return token
    return None


def scope_times(trace, hlo_text, scopes):
    """Self time of the device operations inside the window, booked to the
    innermost of ``scopes`` on each operation's path: ``{"periods",
    "busy_ns", "scopes": {scope: ns}}``, averaged over the chips that ran
    anything.  An operation of the span under no scope books to
    ``unattributed``, one outside the span's own intervals (another
    program's instruction names mean nothing in this text) to
    ``other_programs``; every operation lands in one bucket with its self
    time, so the buckets sum to ``busy_ns``.  None when no device operation
    was traced."""
    if trace is None or not hlo_text:
        return None
    paths = hlo_scope_paths(hlo_text)
    per = []
    for plane in sorted((p for p in trace["planes"]
                         if p["name"].startswith(DEVICE_PLANE)),
                        key=lambda p: p["name"]):
        window = _span_window(plane)
        if window is None:
            continue
        ops, modules, _, _, starts, span = window
        inside = [(s, s + d) for name, s, d in modules if name == span]
        booked, at = {}, 0
        for name, a, self_ns in sorted(_self_times(ops),
                                       key=lambda e: e[1]):
            while at < len(inside) and inside[at][1] <= a:
                at += 1
            if at < len(inside) and inside[at][0] <= a:
                instr = _EVENT_INSTR.match(name)
                scope = innermost(paths.get(instr.group(1), "")
                                  if instr else "", scopes) or UNNAMED
            else:
                scope = OTHER
            booked[scope] = booked.get(scope, 0) + self_ns
        busy = sum(b - a for a, b in _union(ops))
        if busy > 0:
            per.append((max(len(starts) - 1, 0), busy, booked))
    if not per:
        return None
    names = sorted({name for _, _, booked in per for name in booked})
    return {"periods": per[0][0], "chips": len(per),
            "busy_ns": sum(busy for _, busy, _ in per) / len(per),
            "scopes": {name: sum(b.get(name, 0) for _, _, b in per)
                       / len(per) for name in names}}
