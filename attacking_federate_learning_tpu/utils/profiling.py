"""Tracing / profiling hooks.

The reference's only timing artifact is a wall-clock timestamp printed at run
end (reference main.py:97; SURVEY.md §5 "tracing: absent").  Here the host
side of a run is a vocabulary of named spans kept by one recorder
(:class:`PhaseTimer`, :data:`RECORDER`): the phases of building an
experiment (``setup.*``) and of every eval interval of the round loop
(``interval.*``, core/engine.py ``_run_body``), each a clock pair and a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler capture
shows them on the device trace's time base.  The device side of the
vocabulary is utils/costs.py ``stage_scope``; utils/walls.py books a
capture onto both.

``xla_trace`` is the one capture wrapper — the measured-walls layer
(utils/walls.py, ``--profile-every``) and ``--trace-dir`` both run
through it, and a capture that is asked for is taken on every backend.
``ensure_op_profiling`` arms the XLA flag that makes CPU captures
carry per-op events at all.
"""

from __future__ import annotations

import collections
import contextlib
import os
import time
from typing import Optional

import jax

# The TFRT CPU runtime only emits per-op TraceMe annotations (one X
# event per thunk, named by HLO instruction) when this debug flag is
# set; without it a CPU capture carries runtime spans only and every
# wall books to 'unattributed'.
OP_TRACE_FLAG = "--xla_cpu_enable_xprof_traceme=true"


def ensure_op_profiling() -> bool:
    """Arm per-op CPU trace events by appending :data:`OP_TRACE_FLAG`
    to ``XLA_FLAGS``.  XLA parses the env variable ONCE, at the first
    compilation of the process — so this must run before anything is
    compiled (cli.py calls it at --profile-every setup, tools set it at
    main() entry).  Returns True when the
    flag is present afterwards; callers that might be late (a warm
    pytest process) still get a valid, fully-unattributed booking, not
    a crash."""
    flags = os.environ.get("XLA_FLAGS", "")
    if OP_TRACE_FLAG not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + OP_TRACE_FLAG).strip()
    return True


class PhaseTimer:
    """The one host-side span recorder.

    ``span(name)`` is a ``jax.profiler.TraceAnnotation`` -- so inside any
    profiler capture the name sits on the host plane of the same trace as
    the device's operations -- and a ``time.perf_counter()`` pair, added
    to the per-name totals/counts and appended to a bounded ring of
    ``(name, start, end)``.  Nothing is synchronized unless ``sync_on``
    is given.  With no profiler running a span costs two clock reads and
    a no-op annotation.

    :data:`RECORDER` is the process-wide instance the program's own
    spans go to (``interval.*`` in core/engine.py ``_run_body`` /
    ``run_span``, ``setup.*`` where an experiment is built).  A caller
    may still hand ``run(timer=PhaseTimer())`` an instance of its own
    (``--profile``): that forces the per-round path and device-synced
    ``round`` / ``eval`` phases, as before."""

    def __init__(self, ring: int = 16384):
        self.totals = collections.defaultdict(float)
        self.counts = collections.defaultdict(int)
        self.ring = collections.deque(maxlen=ring)

    @contextlib.contextmanager
    def span(self, name: str, sync_on=None):
        """``sync_on``: array (or zero-arg callable returning one, evaluated
        after the block so it can reference freshly produced state) to
        block on before stopping the clock.  The span is accounted even
        when the block or the sync target raises — the wall-clock was
        spent either way."""
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                try:
                    if sync_on is not None:
                        jax.block_until_ready(sync_on() if callable(sync_on)
                                              else sync_on)
                finally:
                    t1 = time.perf_counter()
                    self.totals[name] += t1 - t0
                    self.counts[name] += 1
                    self.ring.append((name, t0, t1))

    phase = span    # the name --profile's callers know it by

    def snapshot(self) -> dict:
        """``{"spans": [(name, start, end), ...] by start time (a parent
        before its children), "totals": {name: seconds}, "counts"}``.
        ``spans`` holds the newest ``ring`` spans; the totals cover every
        span since the recorder was made."""
        return {"spans": sorted(self.ring, key=lambda s: (s[1], -s[2])),
                "totals": dict(self.totals), "counts": dict(self.counts)}

    def summary(self, since: Optional[dict] = None) -> dict:
        """Per-name totals, or only what was added after the
        :meth:`snapshot` passed as ``since``."""
        base_t = since["totals"] if since else {}
        base_c = since["counts"] if since else {}
        out = {}
        for name in self.totals:
            count = self.counts[name] - base_c.get(name, 0)
            if count <= 0:
                continue
            total = self.totals[name] - base_t.get(name, 0.0)
            out[name] = {"total_s": round(total, 4), "count": count,
                         "mean_ms": round(1e3 * total / count, 3)}
        return out


RECORDER = PhaseTimer()
# A span on the process-wide recorder: ``with span(name):``, or
# ``@span(name)`` over a function (a contextmanager's object decorates).
span = RECORDER.span


@contextlib.contextmanager
def xla_trace(log_dir: Optional[str]):
    """Capture a jax.profiler trace if log_dir is given, else no-op."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
