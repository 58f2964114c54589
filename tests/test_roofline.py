"""core/roofline.py — the shared roofline predictor used by the bench.

The fixture is PERF_NOTES.md round 2, the last good chip measurement
(BENCH_r02): ResNet-50 on one TPU v5 lite at 6.26 TFLOP/step, measured
arithmetic intensity 78.7 FLOP/byte against the v5e ridge of
197e12 / 819e9 ≈ 240.5 — firmly hbm_bandwidth-bound at MFU 0.31. The
predictor factored out of bench.py must reproduce exactly that verdict,
and the bench's row annotator (moved here too) must keep producing the
same fields it did before the refactor.
"""

import math

import pytest

from distributed_tensorflow_framework_tpu.core import roofline

# PERF_NOTES.md round 2 / BENCH_r02: the measured ResNet-50 step.
R02_CHIP = "TPU v5 lite"
R02_FLOPS_PER_STEP = 6.26e12
R02_INTENSITY = 78.7
R02_BYTES_PER_STEP = R02_FLOPS_PER_STEP / R02_INTENSITY
V5E_PEAK_FLOPS, V5E_HBM_BW, _ = roofline.CHIP_PEAKS["TPU v5e"]


def test_v5e_aliases_agree():
    # v5e is listed under both its device_kind and marketing names.
    assert (roofline.CHIP_PEAKS["TPU v5 lite"]
            == roofline.CHIP_PEAKS["TPU v5e"])
    assert (roofline.CHIP_PEAKS["TPU v6 lite"]
            == roofline.CHIP_PEAKS["TPU v6e"])


class TestChipHbmCapacity:
    def test_known_chip_uses_spec_sheet(self):
        assert roofline.chip_hbm_capacity("TPU v4") == 32 * roofline.GIB

    def test_unknown_device_has_no_hbm(self):
        assert roofline.chip_hbm_capacity("cpu") is None


class TestPredict:
    def test_r02_fixture_is_hbm_bound(self):
        p = roofline.predict(R02_CHIP, R02_FLOPS_PER_STEP,
                             R02_BYTES_PER_STEP)
        assert p.bound == "hbm_bandwidth"
        assert p.intensity == pytest.approx(78.7)
        assert p.ridge == pytest.approx(240.5, abs=0.1)
        assert p.ridge == pytest.approx(V5E_PEAK_FLOPS / V5E_HBM_BW)
        # HBM term binds: bytes/bw > flops/peak.
        assert p.sec_per_step == p.sec_hbm > p.sec_compute
        assert p.sec_hbm == pytest.approx(R02_BYTES_PER_STEP / V5E_HBM_BW)

    def test_r02_floor_implies_mfu_ceiling_near_measured(self):
        # The analytic floor's implied MFU ceiling: intensity/ridge =
        # 78.7/240.5 ≈ 0.327. BENCH_r02 measured MFU 0.31 at 94% HBM BW
        # util — the measurement sits just under the model's ceiling,
        # which is exactly what a sound lower-bound model must allow.
        p = roofline.predict(R02_CHIP, R02_FLOPS_PER_STEP,
                             R02_BYTES_PER_STEP)
        mfu_ceiling = (R02_FLOPS_PER_STEP / p.sec_per_step) / V5E_PEAK_FLOPS
        assert mfu_ceiling == pytest.approx(78.7 / 240.5, rel=1e-3)
        assert 0.31 <= mfu_ceiling < 0.35

    def test_compute_bound_above_ridge(self):
        p = roofline.predict("TPU v5e", 1e15, 1e12)  # intensity 1000
        assert p.bound == "compute"
        assert p.sec_per_step == p.sec_compute

    def test_unknown_device_raises(self):
        with pytest.raises(ValueError, match="no roofline for device 'cpu'"):
            roofline.predict("cpu", 1e12, 1e11)

    def test_n_chips_divides_work(self):
        one = roofline.predict(R02_CHIP, R02_FLOPS_PER_STEP,
                               R02_BYTES_PER_STEP, n_chips=1)
        four = roofline.predict(R02_CHIP, R02_FLOPS_PER_STEP,
                                R02_BYTES_PER_STEP, n_chips=4)
        assert four.sec_per_step == pytest.approx(one.sec_per_step / 4)
        assert four.bound == one.bound  # intensity is per-program

    def test_zero_bytes_is_compute_bound(self):
        p = roofline.predict(R02_CHIP, 1e12, 0.0)
        assert p.intensity is None
        assert p.bound == "compute"
        assert math.isfinite(p.sec_per_step)


class TestAnnotateRoofline:
    """The bench row annotator, post-refactor parity."""

    def _r02_result(self):
        # sec_per_step chosen so achieved TFLOP/s ≈ the measured 61.2
        # (MFU 0.311) — BENCH_r02's actual shape.
        sec = R02_FLOPS_PER_STEP / 61.2e12
        return {
            "flops_per_step": R02_FLOPS_PER_STEP,
            "bytes_per_step": R02_BYTES_PER_STEP,
            "sec_per_step": sec,
        }

    def test_r02_row_fields(self):
        out = {}
        roofline.annotate_roofline(out, self._r02_result(), R02_CHIP, 1)
        assert out["tflops_per_sec"] == pytest.approx(61.2, abs=0.01)
        assert out["arith_intensity"] == pytest.approx(78.7)
        assert out["bound"] == "hbm_bandwidth"
        assert out["mfu"] == pytest.approx(61.2 / 197.0, abs=1e-3)
        assert 0.9 < out["hbm_bw_util"] <= 1.0

    def test_bench_reexports_the_shared_model(self):
        # bench.py must serve the same names it always exported, now
        # re-exported from core/roofline so tuner and bench share one
        # ridge.
        import bench

        assert bench.CHIP_PEAKS is roofline.CHIP_PEAKS
        assert bench.GIB == roofline.GIB
        assert bench.chip_hbm_capacity is roofline.chip_hbm_capacity
        assert bench._annotate_roofline is roofline.annotate_roofline

    def test_unknown_device_gets_no_verdict(self):
        out = {}
        roofline.annotate_roofline(out, self._r02_result(), "cpu", 1)
        # The program's own numbers stay; nothing is said about a chip.
        assert out["arith_intensity"] == pytest.approx(78.7)
        for key in ("bound", "mfu", "hbm_bw_util", "bound_ridge_source"):
            assert key not in out

    def test_no_flops_no_annotation(self):
        out = {}
        roofline.annotate_roofline(
            out, {"flops_per_step": 0, "bytes_per_step": 0,
                  "sec_per_step": 1.0}, R02_CHIP, 1)
        assert out == {}

    def test_accum_scaled_tag(self):
        out = {}
        roofline.annotate_roofline(out, self._r02_result(), R02_CHIP, 1,
                                   accum_scaled=True)
        assert out["roofline_bound"] == "accum-scaled-upper"
