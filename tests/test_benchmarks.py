"""BASELINE-config benchmark runner smoke (benchmarks.py)."""

import pytest

from attacking_federate_learning_tpu import benchmarks


def test_reference_default_cell_runs(tmp_path):
    results = benchmarks.run_cells({1}, rounds=2, scale=0.4,
                                   log_dir=str(tmp_path))
    assert len(results) == 1
    cell = results[0]
    assert cell["cell"] == "ref_default"
    assert cell["rounds_per_sec"] > 0
    assert 0.0 <= cell["final_accuracy"] <= 100.0
    # every line names the device it ran on
    assert (cell["platform"], cell["count"]) == ("cpu", 8)
    assert cell["device_kind"]


def test_unknown_cell_selection_is_empty(tmp_path):
    assert benchmarks.run_cells({9}, rounds=1, scale=1.0,
                                log_dir=str(tmp_path)) == []


def test_command_line_needs_a_tpu(tmp_path):
    """The command line is a device measurement: on a box without a TPU
    it exits non-zero naming the backend it found — no CPU fallback."""
    with pytest.raises(SystemExit, match="needs a TPU, found backend 'cpu'"):
        benchmarks.main(["--rounds", "1", "--cells", "1",
                         "--log-dir", str(tmp_path)])


def test_model_dataset_family_validation():
    from attacking_federate_learning_tpu import config as C
    from attacking_federate_learning_tpu.config import ExperimentConfig

    with pytest.raises(ValueError, match="shaped"):
        ExperimentConfig(dataset=C.MNIST, model="resnet20")
    with pytest.raises(ValueError, match="shaped"):
        ExperimentConfig(dataset=C.CIFAR10, model="mnist_cnn")
    # compatible pairings construct fine
    ExperimentConfig(dataset=C.CIFAR10, model="resnet20")
    ExperimentConfig(dataset=C.SYNTH_MNIST, model="mnist_cnn")


def test_strict_exits_nonzero_on_failed_cell(tmp_path, monkeypatch):
    """strict (default) must distinguish 'cell failed' from 'cell not
    requested' with a nonzero exit."""

    def boom(*a, **k):
        raise RuntimeError("injected cell failure")

    monkeypatch.setattr(benchmarks, "run_cell", boom)
    with pytest.raises(SystemExit, match="ref_default"):
        benchmarks.run_cells({1}, rounds=1, scale=1.0,
                             log_dir=str(tmp_path))
    # strict=False keeps the record-and-continue behavior.
    results = benchmarks.run_cells({1}, rounds=1, scale=1.0,
                                   log_dir=str(tmp_path), strict=False)
    assert results[0]["failed"].startswith("RuntimeError")
