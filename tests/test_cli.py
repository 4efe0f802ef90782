"""CLI-level tests: invoke cli.main([...]) end to end (VERDICT item #7).

Covers the reference-verbatim flag surface (reference main.py:103-153)
including the typo'd ``-dispatch_weightsn`` alias, the backdoor trigger
flag, resume-with-checkpoint, profiling output, and the TPU-era knobs.
"""

import json
import os

import numpy as np
import pytest

from attacking_federate_learning_tpu import cli


def run_cli(tmp_path, extra, epochs=6):
    argv = ["-s", "SYNTH_MNIST", "-e", str(epochs), "-c", "16",
            "--synth-train", "256", "--synth-test", "64",
            "--log-dir", str(tmp_path / "logs"),
            "--run-dir", str(tmp_path / "runs")] + extra
    return argv, cli.main(argv)


def test_reference_verbatim_flags_and_csv(tmp_path):
    # The reference's own spelling, incl. the -dispatch_weightsn typo alias
    # for --users-count (reference main.py:118).
    argv, result = run_cli(tmp_path, ["-dispatch_weightsn", "10",
                                      "-m", "0.1", "-z", "1.5",
                                      "-d", "Krum", "-l", "0.1"])
    assert len(result["accuracies"]) >= 2
    assert result["accuracies"][-1] > 50.0  # synth MNIST converges fast
    # CSV trajectory with the reference's filename schema (main.py:100).
    csvs = os.listdir(tmp_path / "logs")
    assert any(c.startswith("SYNTH_MNIST_stdev_1.5_Krum") and
               c.endswith(".csv") for c in csvs)


def test_backdoor_pattern_flag(tmp_path, capsys):
    _, result = run_cli(tmp_path, ["-b", "pattern", "-n", "8",
                                   "-m", "0.25", "-d", "NoDefense"],
                        epochs=3)
    out = capsys.readouterr().out
    assert "BEFORE" in out            # pre-training line (main.py:45-51)
    assert "malicious net" in out     # ASR lines (backdoor.py:96-101)
    assert len(result["accuracies"]) >= 1


def test_backdoor_sample_index_flag_coerced(tmp_path):
    # Reference leaves '-b 1' as the string '1' and crashes (str - int,
    # backdoor.py:34, SURVEY.md §2.4 #10); we coerce and run.
    _, result = run_cli(tmp_path, ["-b", "1", "-n", "8", "-m", "0.25"],
                        epochs=2)
    assert len(result["accuracies"]) >= 1


def test_resume_roundtrip(tmp_path):
    # First run crosses the checkpoint threshold (synth MNIST hits 100%
    # by round 5), writing runs/<ds>/checkpoint.npz (reference
    # main.py:84-89); the resumed run continues from the saved round.
    run_cli(tmp_path, ["-n", "10", "-m", "0.1", "-d", "NoDefense"],
            epochs=6)
    ckpt = tmp_path / "runs" / "SYNTH_MNIST" / "checkpoint.npz"
    assert ckpt.exists()
    saved_round = int(np.load(ckpt)["round"])
    assert saved_round > 0

    argv, result = run_cli(tmp_path, ["-n", "10", "-m", "0.1",
                                      "-d", "NoDefense", "--resume"],
                           epochs=9)
    # Continued (round counter advanced past the snapshot), still accurate.
    assert result["accuracies"][-1] > 90.0
    assert result["epochs"][-1] == 8


def test_resume_missing_checkpoint_exits(tmp_path):
    with pytest.raises(SystemExit, match="no checkpoint"):
        run_cli(tmp_path, ["--resume"], epochs=2)


def test_profile_flag_writes_phase_timing(tmp_path):
    run_cli(tmp_path, ["-n", "6", "-m", "0.0", "--profile"], epochs=3)
    logs = tmp_path / "logs"
    jsonls = [f for f in os.listdir(logs) if f.endswith(".jsonl")]
    assert jsonls
    records = [json.loads(line)
               for line in (logs / jsonls[0]).read_text().splitlines()]
    prof = [r for r in records if r.get("kind") == "profile"]
    assert prof and "round" in prof[0]["phases"]
    assert prof[0]["phases"]["round"]["total_s"] > 0


def test_round_stats_flag_writes_diagnostics(tmp_path):
    run_cli(tmp_path, ["-n", "6", "-m", "0.0", "--round-stats"], epochs=2)
    logs = tmp_path / "logs"
    jsonls = [f for f in os.listdir(logs) if f.endswith(".jsonl")]
    records = [json.loads(line)
               for line in (logs / jsonls[0]).read_text().splitlines()]
    rounds = [r for r in records if r.get("kind") == "round"]
    assert rounds and "grad_norm_mean" in rounds[0]


def test_distance_impl_and_scoring_flags(tmp_path):
    _, result = run_cli(tmp_path, ["-n", "10", "-m", "0.1", "-d", "Krum",
                                   "--distance-impl", "xla",
                                   "--krum-scoring-method", "topk"],
                        epochs=3)
    assert result["accuracies"][-1] > 0.0


def test_geomed_flags(tmp_path):
    _, result = run_cli(tmp_path, ["-n", "8", "-m", "0.25",
                                   "-d", "GeoMedian",
                                   "--geomed-iters", "3",
                                   "--geomed-eps", "1e-4"],
                        epochs=2)
    assert result["accuracies"][-1] > 0.0


def test_augment_flag_parses(tmp_path):
    _, result = run_cli(tmp_path, ["-n", "4", "-m", "0.0",
                                   "--augment", "off"], epochs=2)
    assert len(result["accuracies"]) >= 1


def test_invalid_choices_error():
    with pytest.raises(SystemExit):
        cli.build_parser().parse_args(["-d", "NotADefense"])
    with pytest.raises(SystemExit):
        cli.build_parser().parse_args(["-s", "NotADataset"])


def test_bulyan_guard_via_cli(tmp_path):
    with pytest.raises(ValueError, match="Bulyan requires"):
        run_cli(tmp_path, ["-n", "10", "-m", "0.24", "-d", "Bulyan"],
                epochs=2)


def test_attack_backdoor_requires_trigger():
    with pytest.raises(SystemExit):
        cli.build_parser()  # parser itself fine
        cli.main(["--attack", "backdoor", "-s", "SYNTH_MNIST", "-e", "1"])


def test_model_override_flag(tmp_path):
    _, result = run_cli(tmp_path, ["-n", "6", "-m", "0.0",
                                   "--model", "mnist_cnn"], epochs=2)
    assert len(result["accuracies"]) >= 1


def test_telemetry_flag_and_report_subcommand(tmp_path, capsys):
    """--telemetry writes schema-valid defense/attack/selection_hist
    events; the report subcommand reads them back."""
    from attacking_federate_learning_tpu.utils.metrics import validate_event

    run_cli(tmp_path, ["-n", "9", "-m", "0.22", "-d", "Krum",
                       "--telemetry"], epochs=4)
    logs = tmp_path / "logs"
    jsonl = [f for f in os.listdir(logs) if f.endswith(".jsonl")][0]
    path = str(logs / jsonl)
    records = [json.loads(line)
               for line in open(path).read().splitlines()]
    for r in records:
        validate_event(r)
    defense = [r for r in records if r["kind"] == "defense"]
    assert len(defense) == 4
    assert all("selection_mask" in r and "client_norms" in r
               for r in defense)
    assert [r for r in records if r["kind"] == "selection_hist"]
    capsys.readouterr()
    from attacking_federate_learning_tpu import cli as cli_mod
    assert cli_mod.main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "selection concentration" in out


def test_crash_still_writes_csv(tmp_path):
    """RunLogger is context-managed in cli.main: a run that raises
    after the logger opens still exits cleanly through __exit__ (here:
    the Bulyan n >= 4f+3 guard), leaving the JSONL artifact behind."""
    with pytest.raises(ValueError, match="Bulyan requires"):
        run_cli(tmp_path, ["-n", "10", "-m", "0.24", "-d", "Bulyan"],
                epochs=2)
    logs = tmp_path / "logs"
    assert [f for f in os.listdir(logs) if f.endswith(".jsonl")]


# ---------------------------------------------------------------------------
# device selection and the compile cache (utils/backend.py)

def test_backend_tpu_without_a_tpu_exits_nonzero(tmp_path):
    """--backend tpu under an inherited JAX_PLATFORMS=cpu must select the
    TPU over the inherited value and, on a box without one, exit
    non-zero with a one-line reason — never train on CPU.  Subprocess:
    the selection happens before backend init, which this process has
    long passed."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "attacking_federate_learning_tpu.cli",
         "--backend", "tpu", "-s", "SYNTH_MNIST", "-e", "2", "-c", "16",
         "--synth-train", "256", "--synth-test", "64",
         "--log-dir", str(tmp_path / "logs"),
         "--run-dir", str(tmp_path / "runs")],
        capture_output=True, text=True, timeout=300, cwd=root,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    reason = [ln for ln in proc.stderr.splitlines()
              if ln.startswith("--backend tpu:")]
    assert len(reason) == 1 and "TPU" in reason[0]
    assert "Test set:" not in proc.stdout       # no round ever ran


def test_config_dump_is_followed_by_the_device_stamp(tmp_path, capsys):
    run_cli(tmp_path, ["-n", "8"], epochs=1)
    out = capsys.readouterr().out
    assert "{'device': {'platform': 'cpu'" in out


def test_compile_cache_dir_resolution(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins verbatim (and no other directory
    is set in code); unset, the cache is the fixed <checkout>/.jax_cache
    with nothing host-derived under it."""
    import jax

    from attacking_federate_learning_tpu.utils import backend

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        backend.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == "/some/dir"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        backend.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
