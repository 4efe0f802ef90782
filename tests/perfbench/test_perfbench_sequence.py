"""The runner's functions end to end on the CPU with a sequence
configuration (tests/perfbench/tiny/: the tiny model on token data, a bf16
wire): the defense check reads a bf16 matrix, the model check is the
configuration's own ``compare`` (per-token gaps reduced where the forward
runs), and ``shapes(exp)`` fills the keys the sequence cell's kernels count
from.  The controls of that comparison (chip_sequence_controls.py) are driven
here for their plumbing only: what they have to tell apart exists at the
published widths, on the chip.  Times read here are CPU walls and mean nothing."""

import importlib
import json
import os
import sys

import pytest

from perfbench import run
from perfbench.kernels import attention, experts

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
CELL = "smallthinker_krum_alie_l8192"


@pytest.fixture(scope="module")
def results():
    cell = run.load_cell("tiny_seq_cpu", TINY)
    return {trace: run.measure(cell, 2**31 + 54321, 1.0, bool(trace),
                               root=TINY)
            for trace in (0, 1)}


def test_a_sequence_cell_runs_through_the_runner(results):
    for res in results.values():
        assert res["correct"] is True and res["failed"] == 0, res["compared"]
        assert res["attempted"] > 0 and res["attempted"] % 5 == 0
        compared = res["compared"]
        assert (compared["token_gap_median"]["value"]
                <= compared["largest_gap"]["value"] < 1e-4)
        assert 0 < compared["token_gap_median"]["limit"] < 0.1
        assert compared["largest_gap"]["limit"] == 1.0
        assert compared["defense_score_gap"]["limit"] == 1e-5
        assert compared["aggregate_not_an_input_row"]["value"] == 0
        json.dumps(res)


def test_the_new_metric_files_are_the_cells_own():
    per_layer = {m["name"]: m for m in run.metrics_for(CELL, "per_layer")}
    mine = {"attention_ms", "attention_roofline", "experts_ms",
            "experts_roofline", "seq_client_rest_ms", "seq_round_mfu_pct"}
    assert mine <= set(per_layer)
    for name in mine:
        assert per_layer[name]["workloads"] == [CELL]
    for other in ("mlp_krum_alie_n10240", "cnn_krum_alie_n256"):
        assert not mine & {m["name"] for m in
                           run.metrics_for(other, "per_layer")}
    # round_mfu_pct keeps its two cells; this cell reads the same reader
    assert "round_mfu_pct" not in per_layer
    assert per_layer["seq_round_mfu_pct"]["reader"] == "round_mfu"


def test_attention_counts_the_banded_pairs():
    assert attention.visible_pairs(8192) == 33_558_528
    assert attention.visible_pairs(8192, 4096) == 25_167_872
    sizes = dict(contexts=8, length=8192, heads=28, kv_heads=4, head_dim=128,
                 global_layers=1, window_layers=3, window=4096)
    ops, nbytes = attention.ops_bytes(**sizes)
    assert ops == 3 * 4 * 8 * 28 * 128 * (33_558_528 + 3 * 25_167_872)
    # a window layer that computed every causal pair would be counted less
    # than it worked: the count is the banded one
    full, _ = attention.ops_bytes(**dict(sizes, window=8192))
    assert full > ops
    assert nbytes == 2 * 4 * 8 * 4 * 8192 * (2 * 28 + 2 * 4) * 128


def test_experts_count_the_pairs_even_routing_sends_the_held_experts():
    ops, nbytes = experts.ops_bytes(contexts=8, length=8192, layers=4,
                                    top_k=6, held=8, experts=64,
                                    hidden=2560, width=768)
    routed = 8192 * 6 * 8 / 64
    assert routed == 6144
    assert ops == 3 * 2 * 8 * 4 * routed * 5_898_240
    assert nbytes == 8 * 4 * (2 * 4 * 8 * 5_898_240 + 16 * routed * 2560)


def test_the_configuration_counts_a_context_as_the_issue_does():
    ref = importlib.import_module("perfbench.configs." + run.load_cell(
        CELL)["config"])
    assert ref.WIRE_DIM == 370_547_200
    assert ref.train_flops_per_sample() == pytest.approx(1.21e13, rel=0.01)
    assert ref.runs_of(ref.SIZES) == [(0, 1), (1, 3)]


@pytest.mark.parametrize("rounds", [0, 5])
def test_the_controls_run_through_the_configurations_comparison(
        rounds, capsys):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import chip_sequence_controls as ctl
    finally:
        sys.path.pop(0)
    seed = 2**31 + 99
    exp, dataset = ctl.build(seed, ["--backend", "cpu"], cell="tiny_seq_cpu",
                             root=TINY)
    exp.run_span(0, rounds)
    ctl.controls(exp, dataset, seed, f"round_{rounds}", window=True)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [line["control"] for line in lines] == [
        "reference", "router_reads_rms2", "bfloat16", "window_4095"]
    by = {line["control"]: line["compared"] for line in lines}
    assert lines[0]["ok"] and lines[0]["tokens"] == 2 * 24
    # in f32 on the CPU program and reference agree to rounding; bfloat16
    # is three orders of magnitude away even at this size
    assert by["reference"]["largest_gap"][0] < 1e-5
    assert (by["bfloat16"]["token_gap_median"][0]
            > 100 * by["reference"]["token_gap_median"][0])
