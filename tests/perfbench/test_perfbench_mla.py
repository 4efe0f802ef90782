"""The runner's functions end to end on the CPU with the latent-attention
configuration (tests/perfbench/tiny/: the tiny model on token data, a bf16
wire): the model check is the configuration's own (per-token gaps reduced
where the forward runs), ``shapes(exp)`` fills the key the cell's attention
kernel counts from, and the counts are those the configuration states.  The
controls (chip_mla_controls.py) are driven here for their plumbing only:
what they have to tell apart exists at the published widths, on the chip.
Times read here are CPU walls and mean nothing."""

import importlib
import json
import os
import sys

import pytest

from perfbench import run
from perfbench.kernels import attention, mla

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
CELL = "kanana_krum_alie_l8192"
MINE = {"mla_attention_ms", "mla_attention_roofline", "latent_ms",
        "shared_experts_ms", "mla_client_rest_ms", "mla_round_mfu_pct"}


@pytest.fixture(scope="module")
def results():
    cell = run.load_cell("tiny_mla_cpu", TINY)
    return {trace: run.measure(cell, 2**31 + 54321, 1.0, bool(trace),
                               root=TINY)
            for trace in (0, 1)}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_latent_attention_cell_runs_through_the_runner(results, trace):
    res = results[trace]
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert res["attempted"] > 0 and res["attempted"] % 5 == 0
    compared = res["compared"]
    assert (compared["token_gap_median"]["value"]
            <= compared["largest_gap"]["value"] < 1e-4)
    assert 0 < compared["token_gap_median"]["limit"] < 0.1
    assert compared["largest_gap"]["limit"] is None
    # one silo's bf16 wire row against the f32 reference's gradient: the
    # row's rounding alone at this size
    for name in ("grad_gap", "grad_piece_gap", "grad_routed_gap"):
        assert 0 < compared[name]["value"] < 0.01 < compared[name]["limit"]
    assert compared["aggregate_not_an_input_row"]["value"] == 0
    json.dumps(res)


def test_the_new_metric_files_are_the_cells_own():
    per_layer = {m["name"]: m for m in run.metrics_for(CELL, "per_layer")}
    assert MINE <= set(per_layer)
    for name in MINE:
        assert per_layer[name]["workloads"] == [CELL]
    for other in ("mlp_krum_alie_n10240", "cnn_krum_alie_n256",
                  "smallthinker_krum_alie_l8192"):
        assert not MINE & {m["name"] for m in
                           run.metrics_for(other, "per_layer")}
    # the SmallThinker cell's own attention and expert metrics stay its own
    assert not {"attention_ms", "experts_ms"} & set(per_layer)
    assert per_layer["mla_round_mfu_pct"]["reader"] == "round_mfu"
    assert per_layer["mla_attention_roofline"]["args"] == {
        "module": "kernels.mla", "scope": "attention", "shape": "mla"}
    from attacking_federate_learning_tpu.utils.costs import SUBSTAGES
    for name in MINE - {"mla_round_mfu_pct", "mla_attention_roofline"}:
        assert per_layer[name]["args"]["scope"] in SUBSTAGES


def test_latent_attention_counts_the_causal_pairs_at_both_widths():
    sizes = dict(contexts=6, length=8192, layers=5, heads=32, nope=128,
                 rope=64, v_dim=128)
    ops, nbytes = mla.ops_bytes(**sizes)
    pairs = attention.visible_pairs(8192)
    assert ops == 3 * 2 * 6 * 32 * (192 + 128) * 5 * pairs
    # 10.3 TFLOP a context
    assert ops / 6 == pytest.approx(10.3e12, rel=0.01)
    row = 32 * 192 + 32 * 128 + 64 + 2 * 32 * 128
    assert nbytes == 2 * 4 * 6 * 5 * 8192 * row


def test_the_configuration_counts_a_context_as_its_file_states():
    ref = importlib.import_module("perfbench.configs." + run.load_cell(
        CELL)["config"])
    assert ref.WIRE_DIM == 424_960_512
    assert ref.train_flops_per_sample() == pytest.approx(22.5e12, rel=0.01)
    assert ref.SIZES["experts"] == 128 and ref.SIZES["held"] == list(
        range(8))
    cell = run.load_cell(CELL)
    assert cell["config_file"]["wire_dim"] == ref.WIRE_DIM
    assert sorted(cell["config_file"]["reduced"]) == sorted(
        cell["config_file"]["reduced_why"])


def test_the_controls_run_through_the_configurations_comparison(capsys):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import chip_mla_controls as ctl
        from chip_sequence_controls import build
    finally:
        sys.path.pop(0)
    seed = 2**31 + 99
    exp, dataset = build(seed, ["--backend", "cpu"], cell="tiny_mla_cpu",
                         root=TINY)
    ctl.controls(exp, dataset, seed, "round_0")
    ctl.train_controls(exp, dataset, seed, "round_0")
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [line["control"] for line in lines] == [
        "reference", "bfloat16", "no_selection_bias", "train_reference",
        "train_bfloat16", "train_zero_row", "train_half_context",
        "train_another_context", "train_scale_of_256", "train_no_rope_part"]
    by = {line["control"]: line["compared"] for line in lines}
    assert lines[0]["ok"] and lines[0]["tokens"] == 2 * 24
    # in f32 on the CPU program and reference agree to rounding; bfloat16
    # is orders of magnitude away even at this size
    assert by["reference"]["largest_gap"][0] < 1e-5
    assert (by["bfloat16"]["token_gap_median"][0]
            > 100 * by["reference"]["token_gap_median"][0])
    assert (by["no_selection_bias"]["largest_gap"][0]
            > 100 * by["reference"]["largest_gap"][0])
    # a row left at zero reads 1 exactly, a silo's loss on the wrong
    # tokens far above the row's bf16 rounding; the two faults of the
    # scores' scale and part with positions move a model at init only
    # where its scores are of the published widths' size (on the chip)
    for name in ("grad_gap", "grad_piece_gap", "grad_routed_gap"):
        assert by["train_zero_row"][name][0] == pytest.approx(1.0)
    sound = by["train_reference"]["grad_gap"][0]
    assert sound < 0.01
    for fault in ("half_context", "another_context"):
        assert by["train_" + fault]["grad_gap"][0] > 0.3, fault
    for fault in ("scale_of_256", "no_rope_part"):
        assert by["train_" + fault]["grad_piece_gap"][0] > sound, fault
