"""Measured stage walls: book a ``jax.profiler`` trace onto the stage
set (the runtime twin of utils/costs.py:stage_attribution).

PR 15 priced every compiled op statically (modeled FLOPs/bytes split
across the six-stage set).  This module measures where the *wall
clock* actually goes: it parses the Chrome-trace JSON a
``jax.profiler.trace(dir)`` capture writes under
``<dir>/plugins/profile/<ts>/*.trace.json.gz`` and books every op
event's duration to the innermost stage token of that op's ``op_name``
metadata, with the same exact-partition discipline as
``stage_attribution`` — stage sums + the ``unattributed`` residual
equal the booked total *by construction* (one bucket per op, total =
sum of buckets), and coverage is reported instead of hidden.

The join that makes this work on this box (measured, not assumed):

- On the TFRT CPU backend the profiler emits **no** op-level events by
  default — only runtime spans (``TfrtCpuExecutable::Execute``,
  ``PjitFunction(f)``) with empty args.  With
  ``--xla_cpu_enable_xprof_traceme=true`` in ``XLA_FLAGS`` (set before
  the FIRST compile of the process — XLA parses the env once;
  :func:`attacking_federate_learning_tpu.utils.profiling.
  ensure_op_profiling` owns the mechanics) each thunk execution
  appears as one X event **named by its HLO instruction**
  (``dot.4``, ``iota_reduce_fusion``) — with no scope path and no
  args.
- The stage tokens therefore never ride the trace itself; they live in
  the compiled program's ``op_name`` metadata.  Booking is a join:
  instruction name (trace event) -> ``op_name`` (HLO text) -> innermost
  stage token (``stage_attribution``'s rule, verbatim).  On TPU the
  op events carry full metadata already; the same join degrades to a
  name lookup and books identically.

The op universe is defined by the HLO map: an X event whose name is a
known instruction of one of the supplied programs is an op event;
everything else (python tracer rows, threadpool listeners, executable
wrappers) is runtime noise, counted in ``coverage`` but never booked —
so a host-heavy capture cannot smear the device partition.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Optional

from attacking_federate_learning_tpu.utils.costs import STAGES, _STAGE_SET

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')

# Trace-event names that are runtime machinery, never HLO ops; counted
# as runtime (not "unknown") in coverage diagnostics.
_RUNTIME_PREFIXES = ("TfrtCpu", "PjitFunction", "ThreadpoolListener",
                     "ParseArguments", "ThunkExecutor", "$", "Xla",
                     "ExecuteShardedOnLocalDevices", "copy_to_host")


@dataclasses.dataclass
class WallRecord:
    """Measured per-stage wall time for one entry point / capture.

    ``stages`` maps each canonical stage to booked microseconds;
    ``unattributed_us`` holds op time whose ``op_name`` carries no
    stage token (scopes off, XLA-invented fusions with no metadata).
    ``total_us`` is defined as ``sum(stages.values()) +
    unattributed_us`` — the partition is exact by construction, which
    :func:`WallRecord.check` re-asserts.  ``coverage`` reports what the
    partition does NOT cover: trace op events never matched to the
    supplied HLO and the runtime/host share of the capture."""

    name: str
    platform: str = "unknown"
    rounds: Optional[int] = None
    stages: dict = dataclasses.field(default_factory=dict)
    unattributed_us: float = 0.0
    coverage: dict = dataclasses.field(default_factory=dict)
    trace_dir: Optional[str] = None

    @property
    def total_us(self) -> float:
        return sum(self.stages.values()) + self.unattributed_us

    def check(self) -> None:
        """Partition invariant: stage sums + unattributed == total,
        exactly (same floats, same order — not within a tolerance)."""
        total = sum(self.stages.values()) + self.unattributed_us
        if total != self.total_us:
            raise AssertionError(
                f"wall partition broken for {self.name}: "
                f"{total} != {self.total_us}")

    def wall_event(self) -> dict:
        """Schema-v10 'wall' event payload (source='trace')."""
        ev = dict(kind="wall", source="trace", name=self.name,
                  wall_s=round(self.total_us / 1e6, 6),
                  stages={s: round(v, 3)
                          for s, v in self.stages.items()},
                  unattributed_us=round(self.unattributed_us, 3),
                  coverage=self.coverage, platform=self.platform)
        if self.rounds is not None:
            ev["rounds"] = int(self.rounds)
        if self.trace_dir:
            ev["trace_dir"] = self.trace_dir
        return ev


def hlo_stage_map(text: str) -> dict:
    """Instruction name -> innermost stage token (or None) for one
    compiled HLO text — the static side of the trace join.  The token
    rule is stage_attribution's, verbatim: the LAST stage token in
    the ``op_name`` scope path wins (an outer engine scope must not
    clobber the finer scopes inside)."""
    out = {}
    for line in text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        nm = _OPNAME_RE.search(line)
        stage = None
        if nm is not None:
            toks = [t for t in nm.group(1).split("/") if t in _STAGE_SET]
            if toks:
                stage = toks[-1]
        out[m.group(1)] = stage
    return out


def find_trace_file(trace_dir: str) -> Optional[str]:
    """Newest ``*.trace.json.gz`` under a ``jax.profiler.trace`` output
    dir (``<dir>/plugins/profile/<timestamp>/<host>.trace.json.gz``),
    or None when the capture produced nothing."""
    hits = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                     recursive=True)
    hits += glob.glob(os.path.join(trace_dir, "**", "*.trace.json"),
                      recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def load_trace_events(path: str) -> list:
    """The X (complete) events of one Chrome-trace JSON (.gz or
    plain)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        obj = json.load(f)
    return [e for e in obj.get("traceEvents", [])
            if isinstance(e, dict) and e.get("ph") == "X"]


def book_events(events, stage_map: dict, name: str = "trace",
                platform: str = "unknown",
                rounds: Optional[int] = None,
                trace_dir: Optional[str] = None) -> WallRecord:
    """Book trace X events onto the stage set via the instruction
    name -> stage join.  Every op event (name present in ``stage_map``)
    lands in exactly one bucket — its innermost stage, or
    ``unattributed`` when its ``op_name`` carries no stage token —
    so the partition is exact by construction.  Non-op events are
    classified (runtime machinery vs unknown) and reported in
    coverage, never booked."""
    stages = {s: 0.0 for s in STAGES}
    unattributed = 0.0
    op_events = 0
    runtime_us = 0.0
    unknown_us = 0.0
    unknown_events = 0
    for e in events:
        nm = e.get("name")
        dur = float(e.get("dur", 0.0) or 0.0)
        if not isinstance(nm, str):
            continue
        if nm in stage_map:
            op_events += 1
            stage = stage_map[nm]
            if stage is None:
                unattributed += dur
            else:
                stages[stage] += dur
        elif nm.startswith(_RUNTIME_PREFIXES) or "::" in nm:
            runtime_us += dur
        else:
            unknown_events += 1
            unknown_us += dur
    booked = sum(stages.values()) + unattributed
    rec = WallRecord(
        name=name, platform=platform, rounds=rounds,
        stages={s: v for s, v in stages.items() if v > 0.0},
        unattributed_us=unattributed, trace_dir=trace_dir)
    rec.coverage = {
        "op_events": op_events,
        "trace_events": len(events),
        "booked_us": round(booked, 3),
        "runtime_us": round(runtime_us, 3),
        "unknown_us": round(unknown_us, 3),
        "unknown_events": unknown_events,
        # Fraction of non-runtime X-event time the partition explains;
        # 0.0 on a capture with no op events (xprof flag unset) —
        # loud, not wrong.
        "op_time_fraction": round(
            booked / (booked + unknown_us), 4)
        if (booked + unknown_us) > 0 else 0.0,
    }
    rec.check()
    return rec


def book_trace(trace_dir: str, hlo_texts, name: str = "trace",
               platform: str = "unknown",
               rounds: Optional[int] = None) -> Optional[WallRecord]:
    """Parse the newest capture under ``trace_dir`` and book it against
    one HLO text or an iterable of texts (their instruction maps are
    unioned — a span capture may interleave several executables).
    Returns None when the dir holds no trace, never raises on an
    empty capture."""
    path = find_trace_file(trace_dir)
    if path is None:
        return None
    if isinstance(hlo_texts, str):
        hlo_texts = [hlo_texts]
    stage_map: dict = {}
    for text in hlo_texts:
        stage_map.update(hlo_stage_map(text))
    events = load_trace_events(path)
    return book_events(events, stage_map, name=name, platform=platform,
                       rounds=rounds, trace_dir=trace_dir)


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
