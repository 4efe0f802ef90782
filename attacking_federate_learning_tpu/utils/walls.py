"""Measured stage walls: book a ``jax.profiler`` capture onto the stage
set (the runtime twin of utils/costs.py:stage_attribution).

PR 15 priced every compiled op statically.  This module measures where
the device's time actually goes: it reads the ``.xplane.pb`` that a
``jax.profiler`` capture writes under
``<dir>/plugins/profile/<ts>/`` with ``jax.profiler.ProfileData``
(nothing but jax) and books every device operation's **self** time --
its duration less its direct children's: a scanned span is one
``while`` around its body, and summing both counts the body twice --
to the innermost token of ``STAGES`` + ``SUBSTAGES`` on the
operation's scope path.  Stage sums + the ``unattributed`` residual
equal the booked total *by construction* (one bucket per op), the
booked total cannot exceed the device's busy time (the union of the op
intervals), and what no scope covers is reported, not hidden.

Where the operations are, and how each finds its scope path (looked at
on this jax, not assumed):

- **TPU**: plane ``/device:TPU:<n>``, line ``XLA Ops``.  An event is
  named by its whole HLO line (``%fusion.85 = bf16[...] fusion(...)``)
  and carries only device offsets as stats; the scope path lives in
  the event *metadata*, which ``ProfileData`` does not hand out.  So
  the path comes from a join: instruction name (the event name's
  head) -> ``op_name`` in the compiled HLO text of the program
  (:func:`hlo_scope_paths`).
- **CPU** (the test rig): plane ``/host:CPU``, one line per worker
  thread; with ``--xla_cpu_enable_xprof_traceme=true`` in ``XLA_FLAGS``
  before the process's FIRST compile (utils/profiling.py
  ``ensure_op_profiling``) each thunk is an event with an ``hlo_op``
  stat.  Same join.  Each thread's line is self-timed on its own.
- An event that does carry a scope path of its own (a ``tf_op`` /
  ``op_name`` stat, or ``scope`` in a recorded structure) is booked by
  that and needs no join.

The host side of the same capture: ``utils/profiling.py`` spans
(``interval.*``, ``setup.*``) are ``TraceAnnotation``s on the
``/host:CPU`` plane, on the device events' time base.  Every gap in
the device's busy time inside the capture is split over the spans that
overlap it (``host_gaps``, innermost span wins, the rest
``unannotated``), so an idle gap is named by what the host was doing,
not by the programs on either side of it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Optional

from attacking_federate_learning_tpu.utils.costs import (
    STAGES, SUBSTAGES, _STAGE_SET
)

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_EVENT_INSTR_RE = re.compile(r"^%?([\w.\-]+)(?:\s*=|$)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
INHERITED = "~producer/"      # prefix of a path taken from an operand

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("interval.", "setup.")     # utils/profiling.py spans
_SCOPE_STATS = ("tf_op", "op_name")     # -> an event's "scope"
LONG_GAP_US = 500.0


@dataclasses.dataclass
class WallRecord:
    """Measured per-stage device time for one entry point / capture.

    ``stages`` maps each canonical stage to booked microseconds of op
    self time; ``unattributed_us`` holds op time whose scope path
    carries no stage token (scopes off, ops of a program whose text was
    not supplied).  ``total_us`` is defined as ``sum(stages.values()) +
    unattributed_us`` -- the partition is exact by construction, which
    :func:`WallRecord.check` re-asserts.  ``substages`` is a finer view
    of the same time (``gather`` is part of ``deliver``), not a further
    term of the sum.  ``host_gaps`` splits the device's idle time inside
    the capture over the host spans that overlap it.  ``coverage``
    reports what the partition rests on: op events, booked against busy
    time, the share joined to a scope path and the share named."""

    name: str
    platform: str = "unknown"
    rounds: Optional[int] = None
    stages: dict = dataclasses.field(default_factory=dict)
    unattributed_us: float = 0.0
    substages: dict = dataclasses.field(default_factory=dict)
    host_gaps: dict = dataclasses.field(default_factory=dict)
    coverage: dict = dataclasses.field(default_factory=dict)
    trace_dir: Optional[str] = None

    @property
    def total_us(self) -> float:
        return sum(self.stages.values()) + self.unattributed_us

    def check(self) -> None:
        """Partition invariant: stage sums + unattributed == total,
        exactly (same floats, same order -- not within a tolerance);
        and no double count: booked <= the capture's busy time."""
        total = sum(self.stages.values()) + self.unattributed_us
        if total != self.total_us:
            raise AssertionError(
                f"wall partition broken for {self.name}: "
                f"{total} != {self.total_us}")
        busy = self.coverage.get("busy_us")
        if busy is not None and total > busy * (1 + 1e-9) + 1e-3:
            raise AssertionError(
                f"wall booking double-counts for {self.name}: booked "
                f"{total} us > busy {busy} us")

    def wall_event(self) -> dict:
        """Schema-v10 'wall' event payload (source='trace')."""
        ev = dict(kind="wall", source="trace", name=self.name,
                  wall_s=round(self.total_us / 1e6, 6),
                  stages={s: round(v, 3)
                          for s, v in self.stages.items()},
                  unattributed_us=round(self.unattributed_us, 3),
                  coverage=self.coverage, platform=self.platform)
        if self.substages:
            ev["substages"] = {s: round(v, 3)
                               for s, v in self.substages.items()}
        if self.host_gaps:
            ev["host_gaps"] = {s: round(v, 3)
                               for s, v in self.host_gaps.items()}
        if self.rounds is not None:
            ev["rounds"] = int(self.rounds)
        if self.trace_dir:
            ev["trace_dir"] = self.trace_dir
        return ev


def hlo_scope_paths(text: str) -> dict:
    """Instruction name -> ``op_name`` scope path for one compiled HLO
    text -- the static side of the trace join.  An instruction the
    compiler made itself (a layout copy, a reshape or convert between
    two fusions: 10.9 % of the device time of the CNN cell) carries no
    ``op_name``; it takes the path of the nearest instruction that
    produced one of its operands, marked :data:`INHERITED` so the
    booking can say how much was named this way, or '' when no producer
    has one."""
    own, operands = {}, {}
    for line in text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        nm = _OPNAME_RE.search(line)
        own[m.group(1)] = nm.group(1) if nm else ""
        if not nm:
            operands[m.group(1)] = _OPERAND_RE.findall(line[m.end():])
    out = dict(own)

    def producer_path(name, depth=8):
        if own.get(name):
            return own[name]
        for operand in operands.get(name, ()) if depth else ():
            path = producer_path(operand, depth - 1)
            if path:
                return path
        return ""

    for name in operands:
        path = producer_path(name)
        if path:
            out[name] = INHERITED + path
    return out


def scope_stage(path: Optional[str], substages: bool = False):
    """The stage a scope path books to: the LAST :data:`STAGES` token
    wins (an outer engine scope must not clobber the finer scopes
    inside) -- stage_attribution's rule, verbatim.  With
    ``substages`` returns ``(stage, sub)``: ``sub`` is the last
    :data:`SUBSTAGES` token inside that stage's scope, and a sub-stage
    with no stage token around it books to its declared parent."""
    stage = sub = None
    # the last component is the primitive's own name, never a scope
    # (and ``lax.gather``'s is "gather")
    for tok in (path or "").split("/")[:-1]:
        if tok in _STAGE_SET:
            stage, sub = tok, None
        elif tok in SUBSTAGES:
            sub = tok
    if stage is None and sub is not None:
        stage = SUBSTAGES[sub]
    return (stage, sub) if substages else stage


def hlo_stage_map(text: str) -> dict:
    """Instruction name -> innermost stage token (or None) for one
    compiled HLO text.  Filters on :data:`STAGES` only: a sub-stage
    books to its parent here."""
    return {name: scope_stage(path)
            for name, path in hlo_scope_paths(text).items()}


def find_xplane(trace_dir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under a ``jax.profiler`` output dir
    (``<dir>/plugins/profile/<timestamp>/<host>.xplane.pb``), or None
    when the capture produced nothing."""
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def load_xplane(path: str) -> dict:
    """The part of a capture the booking reads, as a plain structure
    (what tests/data holds a recorded one of): ``{"planes": [{"name",
    "lines": [{"name", "events": [{"name", "start_ns", "dur_ns",
    "hlo_op"?, "scope"?}]}]}]}``.  Kept: a TPU plane's ``XLA Ops``
    line; on the host plane the thunk events (those with an ``hlo_op``
    stat) and the program's own spans."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not (device or plane.name == HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = []
            for ev in line.events:
                span = not device and ev.name.startswith(SPAN_PREFIXES)
                stats = {} if span else dict(ev.stats)
                if not (device or span or "hlo_op" in stats):
                    continue
                row = {"name": ev.name, "start_ns": float(ev.start_ns),
                       "dur_ns": float(ev.duration_ns)}
                if "hlo_op" in stats:
                    row["hlo_op"] = str(stats["hlo_op"])
                for key in _SCOPE_STATS:
                    if stats.get(key):
                        row["scope"] = str(stats[key])
                        break
                events.append(row)
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _instr_name(ev) -> str:
    if ev.get("hlo_op"):
        return ev["hlo_op"]
    m = _EVENT_INSTR_RE.match(ev["name"])
    return m.group(1) if m else ev["name"]


def _self_times(events):
    """[(event, self_ns)] for the events of one line: duration less
    direct children's (events properly nested, as one thread's or one
    core's are)."""
    out, stack = [], []      # stack of [event, end, self_ns]
    for ev in sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        a, b = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([ev, b, b - a])
    out.extend((ev, self_ns) for ev, _, self_ns in stack)
    return out


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _split_gap(a, b, spans, out):
    """Add gap [a, b) to ``out`` by the innermost (latest-started) host
    span over each piece of it; what no span covers is 'unannotated'.
    Returns the nanoseconds some span covered."""
    over = [(max(s, a), min(e, b), s, name) for name, s, e in spans
            if s < b and e > a]
    cuts = sorted({a, b} | {x for lo, hi, _, _ in over for x in (lo, hi)})
    named = 0.0
    for lo, hi in zip(cuts, cuts[1:]):
        inner = [(s, name) for olo, ohi, s, name in over
                 if olo <= lo and ohi >= hi]
        name = max(inner)[1] if inner else "unannotated"
        out[name] = out.get(name, 0.0) + (hi - lo)
        named += (hi - lo) if inner else 0.0
    return named


def book_events(trace: dict, scope_paths: dict, name: str = "trace",
                platform: str = "unknown",
                rounds: Optional[int] = None,
                trace_dir: Optional[str] = None) -> WallRecord:
    """Book a loaded capture (:func:`load_xplane`'s structure) onto the
    stage set.  Every op event lands in exactly one bucket -- the
    innermost stage of its scope path (its own ``scope``, else
    ``scope_paths[instruction name]``), or ``unattributed`` when there
    is no path or no stage token in it -- with its self time, so the
    partition is exact and nothing is counted twice."""
    stages = {s: 0.0 for s in STAGES}
    substages = {s: 0.0 for s in SUBSTAGES}
    unattributed = unjoined = inherited = busy = idle = 0.0
    op_events = unjoined_events = 0
    spans = sorted(
        (ev["name"], ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
        for plane in trace["planes"] if plane["name"] == HOST_PLANE
        for line in plane["lines"] for ev in line["events"]
        if ev["name"].startswith(SPAN_PREFIXES))
    gaps: dict = {}
    long_gaps = long_gaps_unannotated = 0
    for plane in trace["planes"]:
        device = plane["name"].startswith(DEVICE_PLANE)
        intervals = []
        for line in plane["lines"]:
            ops = [ev for ev in line["events"]
                   if (device and line["name"] == OPS_LINE)
                   or "hlo_op" in ev]
            if not ops:
                continue
            line_iv = [(ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
                       for ev in ops]
            busy += sum(b - a for a, b in _union(line_iv)) / 1e3
            intervals += line_iv
            for ev, self_ns in _self_times(ops):
                op_events += 1
                path = ev.get("scope")
                if path is None:
                    path = scope_paths.get(_instr_name(ev))
                us = self_ns / 1e3
                if path is None:
                    unjoined += us
                    unjoined_events += 1
                stage, sub = scope_stage(path, substages=True)
                if stage is None:
                    unattributed += us
                else:
                    stages[stage] += us
                    if path.startswith(INHERITED):
                        inherited += us
                if sub is not None:
                    substages[sub] += us
        if not intervals:
            continue
        merged = _union(intervals)
        lo = min([merged[0][0]] + [s for _, s, _ in spans])
        hi = max([merged[-1][1]] + [e for _, _, e in spans])
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            idle += (b - a) / 1e3
            named = _split_gap(a, b, spans, gaps)
            if (b - a) / 1e3 > LONG_GAP_US:
                long_gaps += 1
                long_gaps_unannotated += named < (b - a) / 2
    booked = sum(stages.values()) + unattributed
    rec = WallRecord(
        name=name, platform=platform, rounds=rounds,
        stages={s: v for s, v in stages.items() if v > 0.0},
        unattributed_us=unattributed,
        substages={s: v for s, v in substages.items() if v > 0.0},
        host_gaps={s: v / 1e3 for s, v in gaps.items() if v > 0.0},
        trace_dir=trace_dir)
    rec.coverage = {
        "op_events": op_events,
        "booked_us": round(booked, 3),
        # union of the op intervals per line: booked <= busy
        "busy_us": round(busy, 3),
        "idle_us": round(idle, 3),
        # op time no scope path was found for (not in the supplied
        # HLO texts): part of 'unattributed', named here
        "unjoined_us": round(unjoined, 3),
        "unjoined_events": unjoined_events,
        # Fraction of op time joined to a scope path; 0.0 on a capture
        # with no op events (xprof flag unset) -- loud, not wrong.
        "op_time_fraction": round(1.0 - unjoined / booked, 4)
        if booked > 0 else 0.0,
        "named_fraction": round(1.0 - unattributed / booked, 4)
        if booked > 0 else 0.0,
        # part of the named time: ops the compiler made, named by the
        # path of what produced their operand (hlo_scope_paths)
        "inherited_us": round(inherited, 3),
        "substage_fraction": round(
            sum(substages.values()) / booked, 4) if booked > 0 else 0.0,
        "long_gaps": long_gaps,
        "long_gaps_unannotated": long_gaps_unannotated,
    }
    rec.check()
    return rec


def book_trace(trace_dir: str, hlo_texts, name: str = "trace",
               platform: str = "unknown",
               rounds: Optional[int] = None) -> Optional[WallRecord]:
    """Load the newest capture under ``trace_dir`` and book it against
    one HLO text or an iterable of texts (their instruction maps are
    unioned -- a span capture may interleave several executables).
    Returns None when the dir holds no capture, never raises on an
    empty one."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    if isinstance(hlo_texts, str):
        hlo_texts = [hlo_texts]
    scope_paths: dict = {}
    for text in hlo_texts:
        scope_paths.update(hlo_scope_paths(text))
    return book_events(load_xplane(path), scope_paths, name=name,
                       platform=platform, rounds=rounds,
                       trace_dir=trace_dir)


def measured_vs_modeled(wall_rec: dict, stage_cost: dict) -> dict:
    """Per-stage measured-vs-modeled shares for one entry point: joins
    a 'wall' event (source='trace') with its 'stage_cost' twin by
    stage.  Shares are fractions of each record's own attributed total
    (measured us vs modeled flops), so the ratio is scale-free:
    ratio > 1 means the stage costs more wall time than its modeled
    flop share predicts (memory-bound, host-marshal, launch overhead),
    ratio < 1 the reverse.  Stages absent from either side carry None
    ratios instead of fabricated zeros."""
    meas = dict(wall_rec.get("stages") or {})
    meas["unattributed"] = float(wall_rec.get("unattributed_us", 0.0))
    modeled = {s: float((v or {}).get("flops", 0.0))
               for s, v in (stage_cost.get("stages") or {}).items()}
    modeled["unattributed"] = float(
        (stage_cost.get("unattributed") or {}).get("flops", 0.0))
    mt = sum(meas.values())
    ct = sum(modeled.values())
    out = {}
    for stage in tuple(STAGES) + ("unattributed",):
        m_us = float(meas.get(stage, 0.0))
        flops = modeled.get(stage)
        m_share = (m_us / mt) if mt > 0 else 0.0
        c_share = (flops / ct) if (flops is not None and ct > 0) else None
        row = {"measured_us": round(m_us, 3),
               "measured_share": round(m_share, 4),
               "modeled_share": (round(c_share, 4)
                                 if c_share is not None else None)}
        row["ratio"] = (round(m_share / c_share, 3)
                        if c_share else None)
        if m_us > 0 or (c_share or 0) > 0:
            out[stage] = row
    return out
