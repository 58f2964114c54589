"""Each cell's harness end to end on the CPU at a tiny size, by calling
the harness function (rehearsals 1 and 2 of the on-chip-measurement
guide). Proves control flow, counts and ``correct``; the rates it prints
are the CPU's and are asserted on nowhere."""

import json
import os
import time

import jax
import pytest

from benchmarks.harness import runner, trace_reduce
from benchmarks.tests.tiny import ROOT, tiny_cell

PEAKS = json.load(open(os.path.join(
    ROOT, "benchmarks", "harness", "peaks.json")))["TPU v5 lite"]


@pytest.mark.parametrize("name", ["bert_s512", "bert_s8192", "resnet50_i224",
                                  "bert_s512_dp4"])
def test_cell_runs_end_to_end(name, tmp_path):
    cell, extra = tiny_cell(name)
    os.symlink(os.path.join(ROOT, "configs"), tmp_path / "configs")
    os.symlink(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks")
    result, detail = runner.run_cell(
        cell, seed=11, seconds=2.0, trace=False, root=str(tmp_path),
        process_t0=time.perf_counter(), devices=jax.devices()[:cell.chips],
        peaks=PEAKS, extra_overrides=extra)
    assert result["correct"], detail["verdicts"]
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["failed"] == 0 and result["attempted"] >= 5
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["count"] == cell.chips
    w = detail["window"]
    assert w["seconds"] >= 2.0 and w["steps"] == result["attempted"]
    # units are counted from the pool, by the batches the loop consumed
    if w["unit"] == "images":
        assert w["units"] == w["steps"] * 8
    else:
        assert 0.5 < w["units"] / (w["steps"] * 4 * cell.chips * 128) <= 1.0
    assert detail["verdicts"]["no_compile_in_window"]["ok"]
    if cell.chips > 1:
        facts = detail["verdicts"]["multichip"]
        assert facts["batch_array_devices"] == [4] and facts["all_reduces"] > 0
    assert os.path.isfile(tmp_path / ".bench_out" / name / "detail_trace0.json")


def test_traced_run_refuses_a_trace_without_a_device_plane(tmp_path):
    """On the CPU the profiler records no ``/device:TPU`` plane; the
    reduction says so, it does not fall back to host events."""
    cell, extra = tiny_cell("bert_s512")
    os.symlink(os.path.join(ROOT, "configs"), tmp_path / "configs")
    os.symlink(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks")
    with pytest.raises(trace_reduce.TraceError, match="no device plane"):
        runner.run_cell(
            cell, seed=11, seconds=2.0, trace=True, root=str(tmp_path),
            process_t0=time.perf_counter(), devices=jax.devices()[:1],
            peaks=PEAKS, extra_overrides=extra)
