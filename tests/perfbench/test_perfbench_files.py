"""The benchmark's data files hold together: whatever is under
perfbench/{configs,traffic,workloads,metrics} loads by name, keeps to the
contract's character sets, and agrees with BENCHMARK.json.  Everything is
found by listing the directories, so a later PR's files are checked without
an edit here.  CPU only; no test describes a TPU topology."""

import glob
import importlib
import json
import os
import re

import numpy as np
import pytest

from perfbench import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def names(kind):
    return sorted(os.path.basename(p)[:-len(".json")] for p in
                  glob.glob(os.path.join(run.HERE, kind, "*.json")))


def one_line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


METRICS = {m["name"]: m for m in run.metric_files()}
END_TO_END = {n for n, m in METRICS.items() if m["kind"] == "end_to_end"}


def reporting_cells(metric):
    return metric.get("workloads", names("workloads"))


@pytest.mark.parametrize("kind,name", [
    (k, n) for k in ("configs", "traffic", "workloads", "metrics")
    for n in names(k)])
def test_data_file_loads_and_is_named_legally(kind, name):
    assert NAME.match(name), name
    assert isinstance(run.load_json(kind, name), dict)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file(name):
    m = METRICS[name]
    assert UNIT.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    assert m["kind"] in ("end_to_end", "per_layer")
    reader = importlib.import_module("perfbench.readers." + m["reader"])
    assert callable(reader.read)
    for cell in m.get("workloads", []):
        assert cell in names("workloads"), cell
    if m["kind"] == "end_to_end":
        assert m["source"] in ("host_clock", "device_trace")
        return
    assert one_line(m["layer"]) and m["moves"] in END_TO_END
    moved = reporting_cells(METRICS[m["moves"]])
    for cell in reporting_cells(m):
        assert cell in moved, (name, cell, m["moves"])


@pytest.mark.parametrize("name", names("workloads"))
def test_cell_parses_through_the_cli(name):
    from attacking_federate_learning_tpu import cli

    cell = run.load_cell(name)
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    config = cell["config_file"]
    argv = run.cell_argv(cell, 2**31 + 12345, "/tmp/unused")
    cfg = cli.config_from_args(cli.build_parser().parse_args(argv))
    assert cfg.model == config["model"] and cfg.dataset == config["dataset"]
    assert cfg.synth_train == config["synth_train"]
    assert cfg.synth_test == config["synth_test"]
    assert cfg.test_step == config["test_step"]
    assert cfg.seed == 2**31 + 12345 and cfg.epochs == run.EPOCHS
    reference = importlib.import_module("perfbench.configs." + cell["config"])
    assert reference.WIRE_DIM == config["wire_dim"]
    defense = importlib.import_module(
        "perfbench.defenses." + cfg.defense.lower())
    assert callable(defense.ops_bytes) and callable(defense.check)
    # every cell reports setup_s, another end-to-end and a per-layer metric
    e2e = {m["name"] for m in run.metrics_for(name, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.metrics_for(name, "per_layer")


@pytest.mark.parametrize("name", names("configs"))
def test_config_file(name):
    config = run.load_json("configs", name)
    assert config["name"] == name and one_line(config["source"])
    assert isinstance(config["reduced"], list) and len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    assert isinstance(config["dataset_seed"], int)


def test_benchmark_json_agrees_with_the_files():
    b = benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "perfbench/run.py"]
    assert set(b["paths"]) == {"perfbench", "tests/perfbench"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    used = set()
    for w in b["workloads"]:
        cell = run.load_json("workloads", w["name"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert (w["config"], w["traffic"], w["chips"], w["why"]) == (
            cell["config"], cell["traffic"], cell["chips"], cell["why"])
        used.add(w["config"])
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(
        b["workloads"])
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    assert {c["name"] for c in b["configs"]} == used
    for c in b["configs"]:
        config = run.load_json("configs", c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"perfbench/configs/{c['name']}.json"
        assert (c["source"], c["reduced"]) == (config["source"],
                                               config["reduced"])
        assert one_line(c["why"])
    cells = [w["name"] for w in b["workloads"]]
    listed = {}
    for kind in ("end_to_end", "per_layer"):
        for entry in b[kind]:
            m = METRICS[entry["name"]]
            assert m["kind"] == kind
            for key in ("unit", "better", "source"):
                assert entry[key] == m[key], (entry["name"], key)
            want = {"name", "unit", "better", "source"}
            if kind == "end_to_end":
                want.add("bound")
                assert 0.01 <= entry["bound"] <= 0.1
            else:
                want |= {"layer", "moves"}
                assert (entry["layer"], entry["moves"]) == (m["layer"],
                                                            m["moves"])
            if "workloads" in m:
                mine = [c for c in m["workloads"] if c in cells]
                assert entry["workloads"] == mine and mine
                want.add("workloads")
            assert set(entry) == want, entry["name"]
            listed[entry["name"]] = entry
    assert "setup_s" in listed
    assert len(json.dumps(b)) < 64 * 1024


def test_peaks_table_holds_the_published_v5e():
    v5e = run.peaks_for("TPU v5 lite")
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9}
    with pytest.raises(SystemExit):
        run.peaks_for("TPU v99")


def test_krum_counts_at_the_north_star_shape():
    from perfbench.defenses import krum

    ops, nbytes = krum.ops_bytes(10_240, 79_510, 2_457)
    assert ops == pytest.approx(16.7e12, rel=2e-3)
    assert nbytes == pytest.approx(3.68e9, rel=2e-3)


@pytest.mark.parametrize("n,full", [(24, True), (40, False)])
def test_krum_check_agrees_with_the_repo_oracle(n, full, monkeypatch):
    from attacking_federate_learning_tpu.defenses.oracle import (
        np_krum_select
    )
    from perfbench.defenses import krum

    if not full:        # force the sampled path at a size the oracle can do
        monkeypatch.setattr(krum, "FULL_CHECK_ROWS", 8)
        monkeypatch.setattr(krum, "SAMPLED_ROWS", 16)
    f = n // 4
    G = np.random.default_rng(n).standard_normal((n, 300)).astype(np.float32)
    G[:f] = G[0]                    # colluders send one row
    want = np_krum_select(G.astype(np.float64), n, f)
    verdict = krum.check(G, n, f, G[want], seed=5)
    assert verdict["ok"] and verdict["device_winner"] == want
    assert verdict["verdict"] in ("exact_index", "same_row")
    scores = krum.scores(G, np.arange(n), n - f)
    worst = int(np.argmax(scores))
    assert not krum.check(G, n, f, G[worst], seed=5)["ok"]
    assert not krum.check(G, n, f, G[want] + 1.0, seed=5)["ok"]


@pytest.mark.parametrize("name,shape", [("mnist_mlp", (784,)),
                                        ("cifar10_cnn", (3, 32, 32))])
def test_plain_reference_matches_the_program_model(name, shape):
    import jax

    from attacking_federate_learning_tpu.models.base import get_model
    from attacking_federate_learning_tpu.utils.flatten import make_flattener

    model = get_model(name)
    params = model.init(jax.random.key(3))
    w = np.asarray(make_flattener(params).ravel(params))
    x = np.random.default_rng(0).standard_normal(
        (16,) + shape).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply(params, x))       # log-softmax
    got = importlib.import_module("perfbench.configs." + name).logits(w, x)
    got = got - np.log(np.exp(got).sum(axis=1, keepdims=True))
    np.testing.assert_allclose(got, want, atol=1e-5)
