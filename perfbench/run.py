"""The benchmark's one runner.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one ``FederatedExperiment.run()`` call on a config built by
the CLI's own parser from the cell's data files.  Everything that belongs
to one configuration, one traffic mix, one cell or one metric is a file
found by name (README.md); nothing here names a cell.

The only seam into the program is the ``shutdown=`` object ``run()``
polls at every eval-interval boundary (core/engine.py ``_run_body``):
``Window.should_preempt`` reads the benchmark's own clock there, opens
the measured window after the warm-up intervals, starts and stops the
profiler in a traced run, and ends the run when ``--seconds`` have
passed.  The last line of stdout is the result object; everything else
(the program's own log, accuracy, sample counts, the compile log, the
ops and bytes behind a roofline share) goes on earlier lines.
"""

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WARM_INTERVALS = 2      # the interval that compiles, plus one more
EPOCHS = 10_000_000     # never reached: the window's close ends the run
TRACE_SECONDS = 3.0     # a traced window: this long and TRACE_INTERVALS
TRACE_INTERVALS = 3     # whole intervals, whichever is later
SPAN_BATCH_S = 0.25     # a host-clocked span covers at least this long
SPAN_BATCHES = 5


def say(*parts):
    print("[perfbench]", *parts, flush=True)


# --- data files ----------------------------------------------------------

def load_json(kind, name, root=HERE):
    with open(os.path.join(root, kind, name + ".json")) as f:
        return json.load(f)


def load_cell(name, root=HERE):
    """A cell = its own small file + its configuration + its traffic."""
    cell = dict(load_json("workloads", name, root), name=name)
    cell["config_file"] = load_json("configs", cell["config"], root)
    cell["traffic_file"] = load_json("traffic", cell["traffic"], root)
    return cell


def metric_files(root=HERE):
    out = []
    for path in sorted(glob.glob(os.path.join(root, "metrics", "*.json"))):
        with open(path) as f:
            out.append(dict(json.load(f),
                            name=os.path.basename(path)[:-len(".json")]))
    return out


def metrics_for(cell_name, kind, root=HERE):
    return [m for m in metric_files(root) if m["kind"] == kind
            and cell_name in m.get("workloads", [cell_name])]


def peaks_for(device_kind, root=HERE):
    with open(os.path.join(root, "peaks.json")) as f:
        table = json.load(f)["device_kind"]
    if device_kind not in table:
        raise SystemExit(f"perfbench: no published peaks for device_kind "
                         f"{device_kind!r} (known: {sorted(table)}); add "
                         f"them to peaks.json with their source")
    return table[device_kind]


def cell_argv(cell, seed, log_dir):
    """The flags a user would type: configuration, then federation, then
    what a run of any cell needs (seed, an unreachable round count and
    somewhere temporary to write)."""
    return (list(cell["config_file"]["argv"])
            + list(cell["traffic_file"]["argv"])
            + ["--seed", str(seed), "-e", str(EPOCHS), "--log-dir", log_dir,
               "--run-dir", os.path.join(log_dir, "runs")])


# --- the window ----------------------------------------------------------

class Window:
    """``shutdown=`` for ``FederatedExperiment.run``: called once per eval
    interval, after the eval's blocking fetch and its log line."""

    def __init__(self, seconds, trace_dir=None, on_open=None):
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.on_open = on_open
        self.source = None          # read by the engine's preempt record
        self.marks = []             # (round, perf_counter) per boundary
        self.open_at = None         # index into marks
        self.setup_end = None
        self.tracing = False

    def should_preempt(self, start_round, round_):
        now = time.perf_counter()
        self.marks.append((int(round_), now))
        if len(self.marks) < WARM_INTERVALS:
            return False
        if self.open_at is None:
            self.setup_end = now
            if self.on_open is not None:
                self.on_open()
            if self.trace_dir is not None:
                import jax

                # device events only: the Python and host tracers slow
                # the host, which is what the idle share is about
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=options)
                self.tracing = True
            # the window opens once the profiler (if any) is running
            self.marks[-1] = (int(round_), time.perf_counter())
            self.open_at = len(self.marks) - 1
            return False
        intervals = len(self.marks) - 1 - self.open_at
        elapsed = now - self.marks[self.open_at][1]
        if self.trace_dir is not None:
            done = (elapsed >= min(self.seconds, TRACE_SECONDS)
                    and intervals >= TRACE_INTERVALS)
        else:
            done = elapsed >= self.seconds
        if done:
            self.stop_trace()
            self.source = "perfbench_window_closed"
        return done

    def stop_trace(self):
        if self.tracing:
            import jax

            self.tracing = False
            jax.profiler.stop_trace()

    def window_marks(self):
        return [] if self.open_at is None else self.marks[self.open_at:]


# --- spans taken by the harness, outside the window -------------------------

def timed_calls(fn, *args):
    """Median seconds per call of ``fn(*args)``, each call ended by
    ``block_until_ready``; calls are timed in batches of at least
    SPAN_BATCH_S so the host clock's half millisecond is under 0.2 %."""
    import jax

    jax.block_until_ready(fn(*args))            # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    single = max(time.perf_counter() - t0, 1e-6)
    reps = max(1, math.ceil(SPAN_BATCH_S / single))
    means = []
    for _ in range(SPAN_BATCHES):
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        means.append((time.perf_counter() - t0) / reps)
    means.sort()
    return {"median_s": means[len(means) // 2], "batches": means,
            "calls_per_batch": reps}


def interval_summary(marks):
    """The window's eval intervals on one line, in every run, so that two
    runs of one cell can be told apart by more than their rate: a run that
    is slower throughout moves the median, a stall moves only the maximum;
    the program's host phases (medians, where it records them) say whether
    the host or the device took the difference.  Nothing is read inside the
    window for this but the marks it has anyway."""
    from perfbench.readers import program_span

    ms = sorted((b[1] - a[1]) * 1e3 for a, b in zip(marks, marks[1:]))
    if not ms:
        return {}
    return {"count": len(ms), "min_ms": ms[0], "p50_ms": ms[len(ms) // 2],
            "max_ms": ms[-1], "mean_ms": sum(ms) / len(ms),
            "host_phases_median_ms": program_span.interval_medians(marks)}


def say_memory(phase):
    """Device bytes (now and the process's peak) and the host's peak
    resident size after a phase of the checks and spans."""
    import resource

    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    say("memory", json.dumps({
        "after": phase, "bytes_in_use": stats.get("bytes_in_use"),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "peak_bytes_reserved": stats.get("peak_bytes_reserved"),
        "host_peak_rss_bytes":
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}))


def wire_matrix_fn(exp):
    """One round's post-attack (n, d) matrix from the live state: batch
    gather + client step + attack craft (chip_smoke.py ``oracle_leg``)."""
    import jax

    @jax.jit
    def wire_matrix(state):
        grads = exp._compute_grads_impl(state, state.round)
        return exp.attacker.apply(grads, exp.m_mal,
                                  exp._ctx_for(state, state.round))

    return wire_matrix


def defense_fn(exp):
    import jax

    return jax.jit(lambda G: exp.defense_fn(G, exp.m, exp.m_mal))


# --- one run ------------------------------------------------------------------

def measure(cell, seed, seconds, trace, t_start=None, root=HERE):
    """Run one cell once and return the result object (a dict)."""
    import jax
    import numpy as np

    from attacking_federate_learning_tpu import cli
    from attacking_federate_learning_tpu.attacks import make_attacker
    from attacking_federate_learning_tpu.core.engine import (
        FederatedExperiment
    )
    from attacking_federate_learning_tpu.data.datasets import load_dataset
    from attacking_federate_learning_tpu.utils.backend import device_stamp
    from attacking_federate_learning_tpu.utils.checkpoint import Checkpointer
    from attacking_federate_learning_tpu.utils.costs import (
        cache_counts, compile_log, install_cache_counters
    )
    from attacking_federate_learning_tpu.utils.lifecycle import Preempted
    from attacking_federate_learning_tpu.utils.metrics import RunLogger

    t_start = time.perf_counter() if t_start is None else t_start
    install_cache_counters()
    stamp = device_stamp()
    config = cell["config_file"]
    obs = {"trace": None, "spans": {}}

    with tempfile.TemporaryDirectory(prefix="perfbench_") as tmp:
        argv = cell_argv(cell, seed, tmp)
        say("argv", json.dumps(argv))
        args = cli.build_parser().parse_args(argv)
        cfg = cli.config_from_args(args)
        trace_dir = os.path.join(tmp, "trace") if trace else None

        def on_open():
            obs["compile_log_setup"] = compile_log()
            obs["cache_counts_setup"] = cache_counts()

        window = Window(seconds, trace_dir, on_open)
        with RunLogger(cfg, cfg.output, cfg.log_dir) as logger:
            logger.dump_config()
            logger.print({"device": stamp})
            # The public dataset does not change with a run's seed, so
            # neither does its stand-in (cli.main seeds it from --seed).
            dataset = load_dataset(cfg.dataset, cfg.data_dir,
                                   int(config["dataset_seed"]),
                                   synth_train=cfg.synth_train,
                                   synth_test=cfg.synth_test)
            attacker = make_attacker(
                cfg, dataset=dataset,
                name=None if args.attack == "auto" else args.attack)
            exp = FederatedExperiment(cfg, attacker=attacker,
                                      dataset=dataset)
            try:
                exp.run(logger, shutdown=window,
                        checkpointer=None if args.no_checkpoint
                        else Checkpointer(cfg))
                raise RuntimeError("run() ended before the window closed")
            except Preempted:
                pass
            finally:
                window.stop_trace()
        with open(logger.jsonl_path) as f:
            events = [json.loads(line) for line in f]
        if trace:
            from perfbench import tracereduce

            obs["xplane"] = tracereduce.load_profile_dir(trace_dir)
            obs["trace"] = tracereduce.reduce(obs["xplane"])

    marks = window.window_marks()
    obs["marks"] = marks
    obs["setup_s"] = window.setup_end - t_start
    compiles_in_window = compile_log()[len(obs["compile_log_setup"]):]
    # Peak on the fullest chip.  This runtime books live buffers under
    # ``bytes_in_use`` and a loaded program's temporaries under
    # ``bytes_reserved`` (a 1 GiB-temp probe moved only the latter, PERF.md
    # section 6), so the chip's peak is the sum of the two peaks.
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    obs["memory_peak_bytes"] = max(
        int(s.get("peak_bytes_in_use", 0))
        + int(s.get("peak_bytes_reserved", 0)) for s in stats)
    say("memory_stats", json.dumps(stats[0]))

    evals = [e for e in events if e.get("kind") == "eval"]
    lo, hi = marks[0][0], marks[-1][0]
    window_evals = [e for e in evals if lo <= e["round"] <= hi]
    attempted = hi - lo
    nonfinite = [e for e in window_evals[1:]
                 if not math.isfinite(e["test_loss"])]
    failed = min(attempted, cfg.test_step * len(nonfinite))
    accuracy = evals[-1]["accuracy"]
    say("window", json.dumps({
        "rounds": attempted, "intervals": len(marks) - 1,
        "seconds": marks[-1][1] - marks[0][1],
        "event_log_rounds_per_s":
            (window_evals[-1]["round"] - window_evals[0]["round"])
            / max(window_evals[-1]["t"] - window_evals[0]["t"], 1e-9),
        "first_eval_accuracy": evals[0]["accuracy"],
        "last_eval_accuracy": accuracy,
        "compiles_in_window": compiles_in_window}))
    say("compile_log_setup", json.dumps(obs["compile_log_setup"]))
    say("cache_counts_setup", json.dumps(obs["cache_counts_setup"]))

    # --- correct, outside the window ---------------------------------
    # Each number compared goes beside its limit (``compared``); the
    # verdicts are ``checks``.
    checks, compared = {}, {}
    weights = np.asarray(exp.state.weights)
    compared["nonfinite_weights"] = [int((~np.isfinite(weights)).sum()), 0]
    compared["compiles_in_window"] = [len(compiles_in_window), 0]
    compared["failed_rounds"] = [failed, 0]
    checks["weights_finite"] = compared["nonfinite_weights"][0] == 0
    checks["no_compile_in_window"] = not compiles_in_window
    checks["no_failed_rounds"] = failed == 0

    # One (n, d) wire matrix at a time.  The deliver span makes one a call
    # and drops it, with no other alive; then exactly one is made for the
    # defense span and the defense check (the span first, straight after
    # the deliver span as it always was, not after the check's half minute
    # of host work) and released before anything else runs.
    wire_matrix = wire_matrix_fn(exp)
    defense = importlib.import_module(
        "perfbench.defenses." + cfg.defense.lower())
    defend = defense_fn(exp)
    if trace:
        obs["spans"]["eval"] = timed_calls(exp.evaluate, exp.state.weights)
        obs["spans"]["deliver"] = timed_calls(wire_matrix, exp.state)
    G = wire_matrix(exp.state)
    agg = defend(G)
    if trace:
        obs["spans"]["defense"] = timed_calls(defend, G)
        say("spans", json.dumps(obs["spans"]))
        say_memory("spans")
    verdict = defense.check(G, exp.m, exp.m_mal, np.asarray(agg), seed=seed)
    say("defense_check", json.dumps(verdict))
    checks["defense_agrees_with_reference"] = bool(verdict["ok"])
    compared.update(verdict.get("compared", {}))
    del G, agg
    say_memory("defense_check")

    reference = importlib.import_module(
        "perfbench.configs." + cell["config"])
    model_verdict = reference.check(exp, weights, dataset, seed)
    say("model_check", json.dumps(model_verdict))
    checks["eval_agrees_with_reference"] = bool(model_verdict["ok"])
    compared.update(model_verdict.get("compared", {}))

    if cell.get("min_accuracy") is not None:
        compared["accuracy_under_floor"] = [
            max(0.0, float(cell["min_accuracy"]) - accuracy), 0]
        checks["accuracy_floor"] = accuracy >= float(cell["min_accuracy"])
    say("checks", json.dumps(checks))
    say_memory("model_check")

    if trace:
        # The span's compiled text, for the join from a device operation to
        # its named scope.  It lowers and compiles: after the compile log
        # of the window was read, outside every timed span.
        t0 = time.perf_counter()
        obs["span_hlo_text"] = exp._span_hlo_text(cfg.test_step)
        say("span_hlo_text", json.dumps({
            "seconds": time.perf_counter() - t0,
            "bytes": len(obs["span_hlo_text"])}))
    obs["defense"] = {"module": defense, "n": int(exp.m),
                      "f": int(exp.m_mal), "d": int(exp.flat.dim)}
    obs["config"] = {"module": reference, "samples_per_round":
                     int(exp.m) * int(cfg.batch_size) * int(cfg.local_steps)}
    obs["peaks"] = peaks_for(stamp["device_kind"], root) \
        if stamp["platform"] == "tpu" else None
    obs["test_step"] = int(cfg.test_step)
    say("intervals", json.dumps(interval_summary(marks)))

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(cell["name"], kind, root):
        reader = importlib.import_module("perfbench.readers." + m["reader"])
        value = reader.read(obs, **m.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": stamp["platform"], "kind": stamp["device_kind"],
              "count": stamp["count"],
              "memory_peak_bytes": obs["memory_peak_bytes"]}
    result = {"correct": all(checks.values()), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and obs["trace"] is not None:
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        result["breakdown"] = {"device_ops": obs["trace"]["device_ops"],
                               "idle_gaps": obs["trace"]["idle_gaps"]}
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, (value, limit) in compared.items()}
    return result


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    cell = load_cell(a.workload)

    from attacking_federate_learning_tpu.utils.backend import (
        enable_compile_cache, require_tpu
    )

    stamp = require_tpu("perfbench")        # exits non-zero, no fallback
    if stamp["count"] < int(cell["chips"]):
        raise SystemExit(f"perfbench: cell {a.workload} needs "
                         f"{cell['chips']} chips, found {stamp['count']}")
    enable_compile_cache()
    result = measure(cell, a.seed, a.seconds, bool(a.trace),
                     t_start=T_START)
    for name, pair in result["compared"].items():
        print("[perfbench] compared", name, json.dumps(pair),
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
