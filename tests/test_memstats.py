"""HBM memory telemetry (core/memstats.py) + bench headroom annotation.

On the CPU backend ``device.memory_stats()`` returns nothing, so the
snapshot must fall back to host RSS (tagged ``source_kind=host_rss``)
while ``compiled.memory_analysis()`` still yields the static program
budget — the pair of rulers the bench's ``hbm_peak_bytes_per_chip`` /
``hbm_headroom_frac`` annotation (bench.py) is built on. Real chips flip
``source_kind`` to ``device_memory_stats`` with no code change.
"""

import jax
import jax.numpy as jnp
import pytest

import bench
from distributed_tensorflow_framework_tpu.core import memstats, telemetry


def test_host_rss_bytes_sane():
    current, peak = memstats.host_rss_bytes()
    assert current > 0 and peak > 0
    assert peak >= 1024 * 1024  # a python process is at least a MiB


def test_device_snapshot_cpu_falls_back_to_rss(devices):
    snap = memstats.device_memory_snapshot(devices)
    assert snap["device_count"] == 8
    assert snap["bytes_in_use"] > 0
    assert snap["peak_bytes_in_use"] >= snap["bytes_in_use"] or \
        snap["peak_bytes_in_use"] > 0
    # CPU backend: no allocator stats → the host-RSS ruler, explicitly
    # labeled so readers never mistake RSS for HBM.
    assert snap["source_kind"] in ("host_rss", "device_memory_stats")
    if snap["source_kind"] == "host_rss":
        assert snap["devices"] == []


def test_compiled_memory_analysis_on_cpu():
    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    x = jnp.ones((64, 64), jnp.float32)
    compiled = f.lower(x).compile()
    ana = memstats.compiled_memory_analysis(compiled)
    assert ana is not None
    assert ana["argument_bytes"] >= 64 * 64 * 4
    assert ana["peak_bytes_est"] > 0
    assert ana["peak_bytes_est"] == (
        ana.get("argument_bytes", 0) + ana.get("output_bytes", 0)
        + ana.get("temp_bytes", 0) + ana.get("generated_code_bytes", 0))


def test_monitor_sample_emits_valid_memory_event(tmp_path):
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="mem")
    mon = memstats.MemoryMonitor(w, interval_s=1e9, source="train")
    assert mon.maybe_sample(step=1) is None  # interval not elapsed
    mon.sample(step=2, final=True)
    w.close()
    evs = list(telemetry.read_events(
        path, kind=telemetry.KIND_MEMORY, strict=True))
    assert len(evs) == 1
    ev = evs[0]
    assert ev["metrics"]["bytes_in_use"] > 0
    assert ev["extra"]["source"] == "train"
    assert ev["extra"]["final"] is True


def test_monitor_capture_compiled_emits_analysis(tmp_path):
    @jax.jit
    def f(x):
        return x * 2.0

    compiled = f.lower(jnp.ones((8, 8))).compile()
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="mem")
    mon = memstats.MemoryMonitor(w, source="train")
    ana = mon.capture_compiled(compiled, label="train_step")
    w.close()
    assert ana is not None
    (ev,) = telemetry.read_events(
        path, kind=telemetry.KIND_MEMORY, strict=True)
    assert ev["extra"]["source_kind"] == "memory_analysis"
    assert ev["extra"]["program"] == "train_step"
    assert ev["extra"]["analysis"]["peak_bytes_est"] > 0
    assert ev["metrics"]["peak_bytes_est"] == ana["peak_bytes_est"]


def test_snapshot_no_emit():
    mon = memstats.MemoryMonitor(None)
    snap = mon.snapshot()  # the /healthz path: sample without a writer
    assert snap["bytes_in_use"] > 0


# ------------------------------------------------- bench annotation ----


def test_chip_hbm_capacity_known_and_unknown():
    assert bench.chip_hbm_capacity("TPU v5e") == 16 * bench.GIB
    assert bench.chip_hbm_capacity("TPU v5p") == 95 * bench.GIB
    assert bench.chip_hbm_capacity("cpu") is None  # host RAM is not HBM


def test_chip_peaks_carry_capacity():
    for chip, peak in bench.CHIP_PEAKS.items():
        assert len(peak) == 3, chip
        assert peak[2] >= 8 * bench.GIB, chip


def test_annotate_memory_prefers_device_stats():
    out = {}
    result = {"memory": {"peak_bytes_in_use": 4 * bench.GIB,
                         "source_kind": "device_memory_stats",
                         "analysis": {"peak_bytes_est": 999}}}
    bench._annotate_memory(out, result, "TPU v5e", 8)
    assert out["hbm_peak_bytes_per_chip"] == 4 * bench.GIB
    assert out["hbm_peak_source"] == "device_memory_stats"
    assert out["hbm_capacity_bytes_per_chip"] == 16 * bench.GIB
    assert out["hbm_headroom_frac"] == pytest.approx(0.75)


def test_annotate_memory_cpu_uses_analysis_per_chip():
    out = {}
    result = {"memory": {"peak_bytes_in_use": 123456,
                         "source_kind": "host_rss",
                         "analysis": {"peak_bytes_est": 8 * 1024}}}
    bench._annotate_memory(out, result, "cpu", 8)
    # Static whole-program estimate attributed evenly per chip.
    assert out["hbm_peak_bytes_per_chip"] == 1024
    assert out["hbm_peak_source"] == "memory_analysis"
    assert "hbm_headroom_frac" not in out  # no chip, no capacity


def test_annotate_memory_rss_fallback_without_analysis():
    out = {}
    result = {"memory": {"peak_bytes_in_use": 2 * bench.GIB,
                         "source_kind": "host_rss"}}
    bench._annotate_memory(out, result, "cpu", 1)
    assert out["hbm_peak_bytes_per_chip"] == 2 * bench.GIB
    assert out["hbm_peak_source"] == "host_rss"


def test_annotate_memory_noop_without_data():
    out = {}
    bench._annotate_memory(out, {}, "TPU v5e", 8)
    assert out == {}


def test_annotate_roofline_still_unpacks_3_tuple():
    """The roofline annotation must keep working now that CHIP_PEAKS
    rows carry a third (capacity) element."""
    out = {}
    result = {"sec_per_step": 0.1, "flops_per_step": 1e12,
              "bytes_per_step": 1e10}
    bench._annotate_roofline(out, result, "TPU v5e", 1)
    assert out["tflops_per_sec"] == pytest.approx(10.0)
    assert "mfu" in out and "hbm_bw_util" in out
