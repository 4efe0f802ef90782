"""Device self time by named scope (perfbench/tracereduce.py
``scope_times``, readers/substage.py, readers/substage_roofline.py,
readers/round_mfu.py): on a hand-made trace, and on a recorded chip trace
with the span's HLO text beside it (perfbench/testdata/).  The numbers pin
the arithmetic, not the chip."""

import gzip
import json
import os

import pytest

from perfbench import run, tracereduce
from perfbench.readers import round_mfu, substage, substage_roofline

SCOPES = frozenset({"deliver", "tier1_aggregate", "apply", "gather",
                    "client_step", "craft", "gram", "select"})
HLO = '''
HloModule jit_span
%body (p: f32[4]) -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(span)/while/body/deliver/gather/gather"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, metadata={op_name="jit(span)/while/body/deliver/client_step/dot_general"}
  %copy.3 = f32[4]{0} copy(%fusion.2)
  %fusion.4 = f32[4]{0} fusion(%copy.3), kind=kOutput, metadata={op_name="jit(span)/while/body/tier1_aggregate/gram/dot_general"}
  %sort.5 = f32[4]{0} sort(%fusion.4), metadata={op_name="jit(span)/while/body/tier1_aggregate/select/sort"}
  ROOT %add.6 = f32[4]{0} add(%sort.5, %p), metadata={op_name="jit(span)/while/body/apply/add"}
}
ENTRY %main (a: f32[4]) -> f32[4] {
  %while.7 = f32[4]{0} while(%a), body=%body, metadata={op_name="jit(span)/while"}
  ROOT %bitcast.8 = f32[4]{0} bitcast(%while.7)
}
'''


def plane(name, modules, ops):
    return {"name": name, "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}


def hand_made():
    """Three spans of 100 ns, 200 ns apart, an eval of 20 ns after each:
    the window is two whole periods (first to last span start)."""
    modules, ops = [], []
    for at in (0, 200, 400):
        modules += [["jit_span(1)", at, 100], ["jit_eval(2)", at + 110, 20]]
        ops += [["%while.7 = f32[4] while(...)", at, 100],
                ["%fusion.1 = f32[4] fusion(...)", at + 5, 10],
                ["%fusion.2 = f32[4] fusion(...)", at + 15, 20],
                ["%copy.3 = f32[4] copy(...)", at + 35, 5],
                ["%fusion.4 = f32[4] fusion(...)", at + 40, 30],
                ["%sort.5 = f32[4] sort(...)", at + 70, 10],
                ["%add.6 = f32[4] add(...)", at + 80, 5],
                # the eval's instruction shares a name with the span's
                ["%fusion.1 = f32[8] fusion(...)", at + 110, 20]]
    host = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["interval.log", 100, 5]]}]}
    return {"planes": [plane("/device:TPU:0", modules, ops), host]}


def test_paths_and_the_innermost_scope():
    paths = tracereduce.hlo_scope_paths(HLO)
    assert paths["fusion.1"].endswith("deliver/gather/gather")
    # a compiler-made copy takes the path of what produced its operand
    assert paths["copy.3"] == paths["fusion.2"]
    assert paths["bitcast.8"] == paths["while.7"]
    inner = tracereduce.innermost
    assert inner(paths["fusion.1"], SCOPES) == "gather"    # not lax.gather
    assert inner("jit(f)/deliver/gather", SCOPES) == "deliver"
    assert inner(paths["add.6"], SCOPES) == "apply"
    assert inner(paths["while.7"], SCOPES) is None
    assert inner("", SCOPES) is None


def test_scope_times_book_every_operation_once():
    got = tracereduce.scope_times(hand_made(), HLO, SCOPES)
    assert got["periods"] == 2 and got["chips"] == 1
    assert got["scopes"] == {
        "gather": 20, "client_step": 50,        # 2 x (20 + the copy's 5)
        "gram": 60, "select": 20, "apply": 10,
        "unattributed": 40,                     # the while's own 2 x 20
        "other_programs": 40}                   # the eval, not 'gather'
    assert sum(got["scopes"].values()) == got["busy_ns"] == 240
    # the reducer's busy time is the same window's
    assert tracereduce.reduce(hand_made())["busy_s"] == pytest.approx(240e-9)
    assert tracereduce.scope_times(None, HLO, SCOPES) is None
    assert tracereduce.scope_times(hand_made(), "", SCOPES) is None
    assert tracereduce.scope_times({"planes": []}, HLO, SCOPES) is None


def test_substage_reader_gives_milliseconds_a_round(capsys):
    obs = {"xplane": hand_made(), "span_hlo_text": HLO, "test_step": 5}
    assert substage.read(obs, scope="gram") == pytest.approx(
        60 / (2 * 5) / 1e6)
    assert substage.read(obs, scope="craft") is None       # nothing ran
    assert "scope_times" in obs                 # booked once, kept
    assert capsys.readouterr().out.count("scope_times_ms") == 1
    for empty in ({"test_step": 5}, {"xplane": hand_made(), "test_step": 5},
                  {"xplane": None, "span_hlo_text": HLO, "test_step": 5}):
        assert substage.read(empty, scope="gram") is None


def test_roofline_and_mfu_readers_on_counted_work():
    from perfbench.defenses import krum

    peaks = {"bf16_flops_per_s": 2e12, "hbm_bytes_per_s": 1e9}
    config = type("config", (), {
        "train_flops_per_sample": staticmethod(lambda: 1e6)})
    obs = {"xplane": hand_made(), "span_hlo_text": HLO, "test_step": 5,
           "peaks": peaks, "marks": [(5, 10.0), (10, 10.5), (15, 11.0)],
           "defense": {"module": krum, "n": 4, "d": 100, "f": 1},
           "config": {"module": config, "samples_per_round": 1000}}
    ops, nbytes = krum.ops_bytes(4, 100, 1)
    least = max(ops / 2e12, nbytes / 1e9)           # memory-bound
    assert least == nbytes / 1e9
    gram = {"module": "defenses.krum", "scope": "gram", "shape": "defense"}
    assert substage_roofline.read(obs, **gram) == pytest.approx(
        100 * least / (6e-9))                       # 6 ns of gram a round
    # the sizes come from the key of obs the metric file names
    other = dict(obs, kernel={"n": 8, "d": 100, "f": 1})
    assert substage_roofline.read(other, **dict(gram, shape="kernel")) == (
        pytest.approx(100 * krum.ops_bytes(8, 100, 1)[1] / 1e9 / 6e-9))
    assert substage_roofline.read(obs, **dict(gram, shape="kernel")) is None
    assert round_mfu.read(obs) == pytest.approx(
        100 * (1e9 + ops) / (0.1 * 2e12))
    assert substage_roofline.read(dict(obs, peaks=None), **gram) is None
    assert substage_roofline.read(obs, **dict(gram, scope="craft")) is None
    assert round_mfu.read(dict(obs, peaks=None)) is None
    assert round_mfu.read(dict(obs, marks=[])) is None
    bare = {"module": object(), "samples_per_round": 1}
    assert round_mfu.read(dict(obs, config=bare)) is None


@pytest.mark.parametrize("name", ["gather_ms", "client_step_ms", "craft_ms",
                                  "gram_ms", "select_ms", "gram_roofline",
                                  "round_mfu_pct"])
def test_new_metric_files_name_both_cells_and_an_accepted_layer(name):
    m = run.load_json("metrics", name)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert m["kind"] == "per_layer" and m["moves"] == "rounds_per_s"
    assert m["workloads"] == ["mlp_krum_alie_n10240", "cnn_krum_alie_n256"]
    before = b["per_layer"][:[e["name"] for e in b["per_layer"]].index(
        "gather_ms")]
    assert m["layer"] in {e["layer"] for e in before}
    if m["reader"] == "substage":
        assert m["args"] == {"scope": name[:-len("_ms")]}
        assert m["args"]["scope"] in SCOPES and m["unit"] == "ms"
    else:
        assert m["unit"] == "%" and m["better"] == "higher"
        assert ("roofline" in name) != ("mfu" in name.split("_"))


RECORDED = os.path.join(run.HERE, "testdata",
                        "scopes_mlp_krum_alie_n1024.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_chip_trace_books_to_named_scopes(recorded):
    scopes = substage.scopes()
    got = tracereduce.scope_times(recorded["trace"], recorded["hlo"], scopes)
    reduced = tracereduce.reduce(recorded["trace"])
    assert got["periods"] == reduced["periods"] >= 3
    assert got["busy_ns"] == pytest.approx(reduced["busy_s"] * 1e9, abs=1)
    assert sum(got["scopes"].values()) == pytest.approx(got["busy_ns"],
                                                        abs=1)
    named = sum(v for k, v in got["scopes"].items()
                if k not in (tracereduce.UNNAMED, tracereduce.OTHER))
    span = got["busy_ns"] - got["scopes"].get(tracereduce.OTHER, 0)
    assert named >= 0.98 * span
    assert {"gather", "client_step", "craft", "gram", "select"} <= set(
        got["scopes"])
    for name, want in recorded["expected_ns"].items():
        assert got["scopes"][name] == pytest.approx(want, abs=1), name


def test_recorded_chip_trace_agrees_with_the_programs_own_booking(recorded):
    """A second witness: utils/walls.py books the same events to the same
    sub-stages (it has no window and no notion of another program, so the
    recorded events are cut to the window's spans first)."""
    from attacking_federate_learning_tpu.utils import walls

    plane = recorded["trace"]["planes"][0]
    ops, modules, _, _, _, span = tracereduce._span_window(plane)
    inside = [(s, s + d) for name, s, d in modules if name == span]
    events = [{"name": name, "start_ns": float(a), "dur_ns": float(b - a)}
              for name, a, b in ops
              if any(lo <= a and b <= hi for lo, hi in inside)]
    rec = walls.book_events(
        {"planes": [{"name": plane["name"], "lines": [
            {"name": "XLA Ops", "events": events}]}]},
        walls.hlo_scope_paths(recorded["hlo"]))
    got = tracereduce.scope_times(recorded["trace"], recorded["hlo"],
                                  substage.scopes())
    for name, us in rec.substages.items():
        assert got["scopes"][name] == pytest.approx(us * 1e3, rel=1e-9), name
