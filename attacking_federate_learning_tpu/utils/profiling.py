"""Tracing / profiling hooks.

The reference's only timing artifact is a wall-clock timestamp printed at run
end (reference main.py:97; SURVEY.md §5 "tracing: absent").  Here every round
phase (grads / attack / aggregate / eval) can be timed with a context-manager
stopwatch that blocks on device completion, and a full XLA trace can be
captured with ``jax.profiler`` around any region for TensorBoard/Perfetto.

``xla_trace`` is the one capture wrapper — the measured-walls layer
(utils/walls.py, ``--profile-every``) and ``--trace-dir`` both run
through it, and a capture that is asked for is taken on every backend.
``ensure_op_profiling`` arms the XLA flag that makes CPU captures
carry per-op events at all.
"""

from __future__ import annotations

import contextlib
import os
import time
from collections import defaultdict
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
    """Accumulates per-phase wall-clock, device-synchronized."""

    def __init__(self):
        self.totals = defaultdict(float)
        self.counts = defaultdict(int)

    @contextlib.contextmanager
    def phase(self, name: str, sync_on=None):
        """``sync_on``: array (or zero-arg callable returning one, evaluated
        after the block so it can reference freshly produced state) to
        block on before stopping the clock.  The phase is accounted even
        when the block or the sync target raises — the wall-clock was
        spent either way."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            try:
                if sync_on is not None:
                    jax.block_until_ready(sync_on() if callable(sync_on)
                                          else sync_on)
            finally:
                dt = time.perf_counter() - t0
                self.totals[name] += dt
                self.counts[name] += 1

    def summary(self) -> dict:
        return {name: {"total_s": round(self.totals[name], 4),
                       "count": self.counts[name],
                       "mean_ms": round(1e3 * self.totals[name]
                                        / max(self.counts[name], 1), 3)}
                for name in self.totals}


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
