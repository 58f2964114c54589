"""Trace analyzer (core/trace_analysis.py + scripts/analyze_trace.py).

Unit-level: the protobuf wire reader on a hand-encoded XSpace, the HLO
op-map parser, and the classifier. Integration: a REAL CPU-captured
ProfileHook trace of a small train run must break down into categories
summing to >= 90% of the traced window, as text report and as a
schema-versioned trace_summary JSONL event (the ISSUE acceptance bar).
"""

import glob
import os
import subprocess
import sys

from distributed_tensorflow_framework_tpu.core import telemetry
from distributed_tensorflow_framework_tpu.core import trace_analysis as ta
from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.train import Trainer

# ------------------------------------------------- synthetic XSpace wire ----
# Hand-encoded protobuf wire format (the same field numbers the reader
# decodes), so the parser is pinned independently of any real profiler run.


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b7 = n & 0x7F
        n >>= 7
        if n:
            out += bytes([b7 | 0x80])
        else:
            return out + bytes([b7])


def _fld(num: int, payload) -> bytes:
    if isinstance(payload, int):  # wire type 0
        return _varint(num << 3 | 0) + _varint(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _metadata_entry(mid: int, name: str) -> bytes:
    meta = _fld(2, name.encode())                       # XEventMetadata.name
    return _fld(4, _fld(1, mid) + _fld(2, meta))        # XPlane.event_metadata

def _event(mid: int, offset_ps: int, dur_ps: int) -> bytes:
    return _fld(4, _fld(1, mid) + _fld(2, offset_ps) + _fld(3, dur_ps))


def _synthetic_xspace() -> bytes:
    # One executor line: dot 0-400ps, all-reduce 500-800ps, fusion
    # 100-200ps (overlaps the dot), plus a ThunkExecutor wrapper spanning
    # everything (must be filtered, its wait time becoming launch_gap).
    line = (
        _fld(2, b"tf_XLATfrtCpuClient/0") + _fld(3, 0)  # name, timestamp_ns
        + _event(1, 0, 400) + _event(2, 500, 300)
        + _event(3, 100, 100) + _event(4, 0, 800)
    )
    plane = (
        _fld(2, b"/host:CPU")
        + _metadata_entry(1, "dot.11")
        + _metadata_entry(2, "all-reduce.3")
        + _metadata_entry(3, "fusion.7")
        + _metadata_entry(4, "ThunkExecutor::Execute")
        + _fld(3, line)
    )
    return _fld(1, plane)  # XSpace.planes


def test_parse_xspace_wire_format():
    events = ta.parse_xspace(_synthetic_xspace())
    assert {e.name for e in events} == {
        "dot.11", "all-reduce.3", "fusion.7", "ThunkExecutor::Execute"}
    by_name = {e.name: e for e in events}
    assert by_name["all-reduce.3"].start_ps == 500
    assert by_name["all-reduce.3"].duration_ps == 300
    assert all(e.line == "tf_XLATfrtCpuClient/0" for e in events)


def test_analyze_synthetic_breakdown():
    report = ta.analyze(ta.parse_xspace(_synthetic_xspace()))
    # Wrapper span filtered: window is the leaf ops' 0..800ps, busy their
    # union [0,400] + [500,800] = 700ps, gap 100ps.
    assert report["num_events"] == 3
    assert report["window_ps"] == 800
    assert report["busy_ps"] == 700
    assert report["launch_gap_ps"] == 100
    b = report["breakdown"]
    assert b["collectives"]["summed_event_ps"] == 300
    assert b["gemm_conv"]["summed_event_ps"] == 400
    # Proportional attribution keeps categories + gap == window (up to
    # 1 ps of int truncation per category — large against an 800 ps toy
    # window, invisible against a real trace).
    assert report["coverage"] >= 0.99
    fracs = sum(v["fraction_of_window"] for v in b.values())
    assert abs(fracs - 1.0) < 1e-6


def test_hlo_op_map_and_scope_classification():
    hlo = """
HloModule jit_train_step

ENTRY main {
  %dot.11 = f32[64,10]{1,0} dot(a, b), metadata={op_name="jit(train)/dense/dot_general"}
  %mul.5 = f32[10]{0} multiply(x, y), metadata={op_name="jit(train)/optimizer_update/mul"}
  ROOT %add.1 = f32[10]{0} add(%mul.5, c)
}
"""
    hlo_map = ta.parse_hlo_op_map(hlo)
    assert hlo_map["dot.11"][0] == "dot"
    assert "optimizer_update" in hlo_map["mul.5"][1]
    assert ta.classify("mul.5", hlo_map) == "optimizer_update"
    assert ta.classify("dot.11", hlo_map) == "gemm_conv"
    assert ta.classify("all-gather.2", hlo_map) == "collectives"
    assert ta.classify("infeed.1", None) == "infeed"
    assert ta.classify("unknown_fusion", None) == "other_compute"


# ----------------------------------------------------- real CPU capture ----


def _profiled_run(tmp_path):
    cfg = load_config(base={
        "name": "trace-test",
        "mesh": {"data": 8},
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.05},
        "train": {"total_steps": 6, "log_interval": 3,
                  "profile_start": 2, "profile_stop": 4},
    })
    cfg.checkpoint.directory = str(tmp_path / "run")
    cfg.checkpoint.save_interval_steps = 1000
    trainer = Trainer(cfg)
    trainer.train()
    traces = glob.glob(os.path.join(str(tmp_path / "run"), "traces", "**",
                                    "*.xplane.pb"), recursive=True)
    assert traces, "ProfileHook produced no XPlane trace"
    return trainer, traces[0]


def test_analyzer_on_cpu_captured_trace(devices, tmp_path):
    trainer, trace = _profiled_run(tmp_path)

    hlo_path = ta.find_hlo_text(trace)
    assert hlo_path and hlo_path.endswith("train_step.hlo.txt"), (
        "Trainer/ProfileHook did not dump the compiled HLO next to the trace")
    report = ta.analyze_trace_file(trace, open(hlo_path).read())

    # Acceptance bar: the category breakdown accounts for >= 90% of the
    # traced window (categories + launch_gap, honest wall-clock shares).
    assert report["coverage"] >= 0.90, report
    assert report["hlo_map_used"]
    assert report["num_events"] > 0
    fracs = {cat: report["breakdown"][cat]["fraction_of_window"]
             for cat in (*ta.CATEGORIES, ta.GAP)}
    assert sum(fracs.values()) >= 0.90
    assert all(0.0 <= f <= 1.0 for f in fracs.values())
    # A conv net's trace must actually show conv/GEMM time.
    assert report["breakdown"]["gemm_conv"]["summed_event_ps"] > 0

    text = ta.format_report(report)
    for cat in (*ta.CATEGORIES, ta.GAP):
        assert cat in text

    # JSON artifact: a valid schema event joinable by the run's id.
    out = str(tmp_path / "summary.jsonl")
    ta.write_summary_event(report, out, run_id=trainer.run_id)
    evs = list(telemetry.read_events(out, kind=telemetry.KIND_TRACE_SUMMARY))
    assert len(evs) == 1
    ev = evs[0]
    assert telemetry.validate_event(ev) == []
    assert ev["run_id"] == trainer.run_id
    assert ev["metrics"]["coverage"] >= 0.90
    assert set(ev["phases"]) == set((*ta.CATEGORIES, ta.GAP))

    # The CLI wrapper end-to-end: text table on stdout + JSONL artifact.
    cli_out = str(tmp_path / "cli_summary.jsonl")
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(ta.__file__),
                                      "..", "..", "scripts",
                                      "analyze_trace.py"),
         os.path.dirname(trace), "--json", cli_out,
         "--run-id", trainer.run_id],
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr
    assert "launch_gap" in proc.stdout
    cli_evs = list(telemetry.read_events(cli_out))
    assert cli_evs and cli_evs[0]["run_id"] == trainer.run_id


def test_trainer_run_emits_joined_telemetry(devices, tmp_path):
    """The tentpole contract: one run id ties events.jsonl, the heartbeat
    file and the trace together."""
    trainer, trace = _profiled_run(tmp_path)
    run_dir = str(tmp_path / "run")

    evs = list(telemetry.read_events(os.path.join(run_dir, "events.jsonl")))
    kinds = [e["kind"] for e in evs]
    assert kinds[0] == telemetry.KIND_RUN_META
    # Every events.jsonl opens with where the run landed and how its
    # Pallas kernels compile there: a CPU run never reads as a chip run.
    meta = evs[0]["extra"]
    assert meta["platform"] == "cpu" and meta["device_kind"] == "cpu"
    assert meta["device_count"] == 8
    assert meta["pallas_kernels"] == "interpret"
    assert telemetry.KIND_TRAIN_STEP in kinds
    assert all(e["run_id"] == trainer.run_id for e in evs)
    step_ev = next(e for e in evs if e["kind"] == telemetry.KIND_TRAIN_STEP)
    assert "loss" in step_ev["metrics"]
    assert "infeed" in step_ev["phases"] and "dispatch" in step_ev["phases"]
    # Per-collective byte counters ride on the step events (profiling was
    # armed, so the build-time lower was tallied).
    assert "collectives" in step_ev
    assert "total_bytes" in step_ev["collectives"]

    import json
    hb = json.load(open(os.path.join(run_dir, "heartbeat.json")))
    assert hb["run_id"] == trainer.run_id
    assert hb["status"] == "finished"
