"""The bench validity gate: the mechanisms that make an invalid device
measurement impossible to record — implied throughput above the
published peak, an unknown device, a swallowed phase failure, an f32
engine disagreement — pinned as unit behavior so a bench.py refactor
can't silently drop them.
"""

from __future__ import annotations

import importlib

import numpy as np
import pytest

import bench


@pytest.fixture(autouse=True)
def fresh_bench_state():
    """bench module state (RESULT/RECAP/_EMITTED) is global; isolate."""
    importlib.reload(bench)
    yield


V5E = "TPU v5 lite"      # jax's device_kind for one v5e chip


def test_mfu_line_marks_invalid_above_bf16_peak():
    # 667 GFLOP in 0.09 ms = 7.4 PFLOP/s — a broken measurement.
    frac = bench.mfu_line("krum_gram", 667e9, 0.09, V5E)
    assert frac > 1.0
    assert bench.RESULT.get("valid") is False
    assert any("measurement broken" in r
               for r in bench.RESULT["invalid_reasons"])


def test_mfu_line_valid_below_peak():
    frac = bench.mfu_line("krum_gram", 667e9, 40.0, V5E)  # ~17 TFLOP/s
    assert 0.0 < frac < 1.0
    assert "valid" not in bench.RESULT          # nothing poisoned


def test_unknown_device_kind_is_an_error():
    """Peaks are keyed on device_kind with their published source; a
    device that is not in the table is an error, not a default."""
    assert bench.peaks_for(V5E)["bf16_flops"] == 197e12
    with pytest.raises(SystemExit, match="no published peaks"):
        bench.mfu_line("x", 1e9, 1.0, "cpu")


def test_timed_ms_blocks_and_returns_last_output():
    import jax.numpy as jnp

    x = jnp.zeros((4,))
    ms, out = bench.timed_ms(lambda: x + 1.0, iters=2, loops=1)
    assert ms > 0.0
    np.testing.assert_array_equal(np.asarray(out), np.ones((4,)))


def test_emit_result_json_carries_cache_counts(capsys):
    bench.RESULT.update(metric="m", value=1.0, unit="ms",
                        vs_baseline=1.0, valid=True)
    bench.emit_result_json()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and '"metric": "m"' in out[0]
    assert '"compile_cache"' in out[0]


def test_mark_invalid_deduplicates_reasons():
    bench.RESULT.update(metric="m", value=1.0, valid=True)
    bench.mark_invalid("same reason")
    bench.mark_invalid("same reason")
    assert bench.RESULT["invalid_reasons"] == ["same reason"]
    assert bench.RESULT["valid"] is False


def test_failed_phase_fails_the_run():
    with bench.phase("good"):
        pass
    with pytest.raises(RuntimeError, match="boom"):
        with bench.phase("bad"):
            raise RuntimeError("boom")
    assert bench.RESULT["phases_completed"] == ["good"]


def test_bench_without_a_tpu_exits_nonzero():
    """A bench run that finds no TPU exits non-zero with one line saying
    so — never a CPU number under the device metric's name."""
    with pytest.raises(SystemExit, match="needs a TPU, found backend 'cpu'"):
        bench.main()
    assert "metric" not in bench.RESULT


class TestF32FlipAdjudication:
    """A legal near-tie between f32 engines must warn, not poison the
    run; a decisive disagreement must still poison."""

    def test_exact_tie_is_exempt(self):
        rng = np.random.default_rng(3)
        G = rng.standard_normal((16, 32)).astype(np.float32)
        G[5] = G[11]            # identical rows: identical Krum scores
        is_tie, gap, band = bench.adjudicate_f32_flip(G, 3, [5, 11])
        assert is_tie and gap <= band

    def test_decisive_gap_poisons(self):
        rng = np.random.default_rng(4)
        G = rng.standard_normal((16, 32)).astype(np.float32)
        G[2] *= 40.0            # a far outlier: hugely worse score
        is_tie, gap, band = bench.adjudicate_f32_flip(G, 3, [0, 2])
        assert not is_tie and gap > band

    def test_gate_warns_on_tie_and_poisons_on_decisive_gap(self):
        # The gate bench_impl_table routes f32 disagreements through:
        # a legal tie must NOT poison validity; a decisive gap must.
        rng = np.random.default_rng(5)
        G = rng.standard_normal((12, 16)).astype(np.float32)
        G[1] = G[7]
        bench.gate_f32_disagreement(G, 2, {"xla": 1, "other": 7}, 12)
        assert "valid" not in bench.RESULT       # tie: warning only
        assert any("legal tie" in r for r in bench.RECAP)
        G[2] *= 40.0                             # decisive outlier
        bench.gate_f32_disagreement(G, 2, {"xla": 0, "other": 2}, 12)
        assert bench.RESULT["valid"] is False
        assert any("disagree" in r
                   for r in bench.RESULT["invalid_reasons"])
