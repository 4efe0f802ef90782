"""Host spans and counters the program keeps itself, read by importing its
recorder -- as ``compile_log`` reads ``utils/costs.py:compile_log()``.

What this reader needs of ``obs``: ``marks`` only, ``(round, seconds)`` per
interval boundary of the window on ``time.perf_counter()``.  The program's
recorder (``utils/profiling.py:RECORDER``, a ring of ``(name, start, end)``
on the same clock) and ``utils/costs.py:trace_lower_log()`` are process-wide
and always on, so nothing is handed over: a span that ended before
``marks[0]`` is set-up, one between ``marks[0]`` and ``marks[-1]`` is the
window's.  A program without the recorder (an older commit), an empty
recorder or a window without the spans a reading needs gives None, and the
metric is left out of the line.

Readings (``what``):

- ``host_seam_ms``: median over the window's intervals of the time from
  the end of ``interval.wait_device`` (the device has finished the span
  and the eval) to the end of the next ``interval.dispatch_span`` (the
  next span is enqueued): logging, the poll of ``shutdown=`` -- in a
  benchmark run the harness's own ``Window`` -- and argument conversion.
  The device has nothing to run meanwhile.
- ``host_log_ms``: median ``interval.log``.
- ``setup_data_s``: ``setup.dataset`` + ``setup.place_data`` of the
  experiment the window ran.
- ``setup_build_s``: ``setup.attacker`` + ``setup.experiment`` less
  ``setup.place_data``.
- ``trace_lower_s``: seconds of set-up inside a jaxpr trace or a
  jaxpr-to-MLIR lowering: the union of the log's intervals (a nested jit
  traces inside its caller's trace, so a plain sum would count it twice).
"""

import json
import statistics


def recorded():
    """(spans by start time, trace/lower log) from the program, or None."""
    try:
        from attacking_federate_learning_tpu.utils.costs import (
            trace_lower_log
        )
        from attacking_federate_learning_tpu.utils.profiling import RECORDER
    except ImportError:
        return None
    return RECORDER.snapshot()["spans"], trace_lower_log()


def say(what, table):
    print("[perfbench]", what, json.dumps(table), flush=True)


def _median(values):
    return statistics.median(values) if values else None


def interval_medians(marks, spans=None):
    """Median milliseconds of every ``interval.*`` phase recorded between
    the first and the last mark, by name; {} without the recorder."""
    if spans is None:
        got = recorded()
        if got is None or len(marks) < 2:
            return {}
        spans = got[0]
    lo, hi = marks[0][1], marks[-1][1]
    by_name = {}
    for name, a, b in spans:
        if a >= lo and b <= hi and name.startswith("interval."):
            by_name.setdefault(name, []).append((b - a) * 1e3)
    return {name: _median(v) for name, v in sorted(by_name.items())}


def _setup_spans(spans, before):
    """The spans of the last experiment built before ``before``: its
    ``setup.experiment`` and children, and the last ``setup.dataset`` /
    ``setup.attacker`` that ended before it began."""
    built = [s for s in spans if s[0] == "setup.experiment"
             and s[2] <= before]
    if not built:
        return None
    _, lo, hi = built[-1]
    out = {"setup.experiment": hi - lo}
    for name, a, b in spans:
        if name.startswith("setup.") and lo <= a and b <= hi \
                and name != "setup.experiment":
            out[name] = out.get(name, 0.0) + (b - a)
    begin = lo
    for name in ("setup.dataset", "setup.attacker"):
        earlier = [s for s in spans if s[0] == name and s[2] <= lo]
        if earlier:
            out[name] = earlier[-1][2] - earlier[-1][1]
            begin = min(begin, earlier[-1][1])
    out["begin"], out["end"] = begin, hi
    return out


def _union_s(intervals):
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total, end = total + (b - a), b
        elif b > end:
            total, end = total + (b - end), b
    return total


def read(obs, what):
    got, marks = recorded(), obs.get("marks") or []
    if got is None or len(marks) < 2:
        return None
    spans, lowerings = got
    lo, hi = marks[0][1], marks[-1][1]
    if what in ("host_seam_ms", "host_log_ms"):
        inside = [s for s in spans if s[1] >= lo and s[2] <= hi]
        if what == "host_log_ms":
            return _median([(b - a) * 1e3 for name, a, b in inside
                            if name == "interval.log"])
        # the whole split goes on an earlier line, as defense_roofline's
        # ops and bytes do: every interval.* phase the window recorded
        say("interval_spans_median_ms", interval_medians(marks, spans))
        seams, waited = [], None
        for name, a, b in inside:
            if name == "interval.wait_device":
                waited = b
            elif name == "interval.dispatch_span" and waited is not None:
                seams.append((b - waited) * 1e3)
                waited = None
        return _median(seams)
    setup = _setup_spans(spans, lo)
    if setup is None:
        return None
    if what == "setup_data_s":
        if "setup.dataset" not in setup:
            return None
        return setup["setup.dataset"] + setup.get("setup.place_data", 0.0)
    if what == "setup_build_s":
        # the whole of set-up on one earlier line: what ran before
        # load_dataset (imports, backend), each span, and the warm-up
        # intervals (trace, lower, compile and two intervals' work)
        split = {k[len("setup."):] + "_s": v for k, v in setup.items()
                 if k.startswith("setup.")}
        split["warm_up_s"] = lo - setup["end"]
        if obs.get("setup_s") is not None:
            split["setup_s"] = obs["setup_s"]
            split["before_dataset_s"] = obs["setup_s"] - (lo - setup["begin"])
        say("setup_split", split)
        return (setup.get("setup.attacker", 0.0) + setup["setup.experiment"]
                - setup.get("setup.place_data", 0.0))
    if what == "trace_lower_s":
        during = [(e["t"] - e["secs"], e["t"]) for e in lowerings
                  if setup["begin"] <= e["t"] <= lo]
        return _union_s(during) if during else None
    raise ValueError(f"program_span: unknown reading {what!r}")
