"""Every reader gives a value from the records of a run, and nothing
where there is nothing to read. The trace is the recorded one of
``test_trace_reduce``; the window and the program's events are made up
in the shape the run records them."""

import math

import pytest

from benchmarks.flops import bert as bert_flops
from benchmarks.harness import build, manifest, records, trace_reduce
from benchmarks.tests.test_trace_reduce import profile, scopes  # noqa: F401
from benchmarks.tests.tiny import ROOT

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def run_records(profile, scopes):  # noqa: F811
    cell = manifest.Manifest(ROOT).cell("bert_s512")
    pool = build.make_pool(cell, ROOT, seed=22)
    h = {**cell.config["published"], **cell.config["reference_hparams"]}
    works = [bert_flops.attention_kernel_work(b, h, 32) for b in pool.batches]
    return records.RunRecords(
        cell=cell,
        window={"rate_per_chip": 97_000.0, "hooks_ms_step": 0.06,
                "phase_ms_step": {"infeed": 0.3, "dispatch": 4.0},
                "step_ms_blocks": [160.0, 161.0, 163.0, 170.0]},
        startup={"time_to_first_step_s": 21.5},
        step_memory={"step_gib": 10.05}, peaks=PEAKS,
        model_flops_per_unit=sum(bert_flops.train_flops(b, h)
                                 for b in pool.batches) / sum(pool.real_units),
        attention_work={k: sum(w[k] for w in works) / len(works)
                        for k in works[0]},
        trace=trace_reduce.reduce(profile, scopes))


def read(name, r):
    return manifest.load_reader(ROOT, name).read(r)


def test_every_reader_of_the_cell_gives_a_finite_value(run_records):
    values = {m["name"]: read(m["name"], run_records)
              for m in run_records.cell.per_layer}
    assert all(v is not None and math.isfinite(v) for v in values.values()), \
        values
    assert values["first_step_s"] == 21.5
    assert values["loop_host_ms_step"] == pytest.approx(4.06)
    assert values["step_ms_p50"] == 162.0
    assert values["step_hbm_gib"] == 10.05
    # ~676 MFLOP a real token x 97k tokens/s over 197 TFLOP/s
    assert values["mfu_pct"] == pytest.approx(33.4, abs=0.5)
    assert 25 < values["attn_kernel_pct"] < 35
    assert 38 < values["gemm_conv_pct"] < 50
    assert 1 < values["optimizer_update_pct"] < 5
    assert 0 <= values["device_idle_pct"] < 2
    # whole-K kernels do dense 512 x 512 work where packed documents need
    # only their own blocks: a small share of the roofline
    assert 5 < values["attn_roofline_pct"] < 15


def test_readers_return_nothing_where_there_is_nothing(run_records):
    import dataclasses

    untraced = dataclasses.replace(run_records, trace=None)
    for name in ("device_idle_pct", "gemm_conv_pct", "attn_kernel_pct",
                 "attn_roofline_pct", "optimizer_update_pct",
                 "collective_ms_step", "collective_exposed_pct"):
        assert read(name, untraced) is None, name
    # one chip: no collective metric; no attention: no kernel metric
    assert read("collective_ms_step", run_records) is None
    assert read("collective_exposed_pct", run_records) is None
    conv = dataclasses.replace(run_records, attention_work=None)
    assert read("attn_kernel_pct", conv) is None
    assert read("attn_roofline_pct", conv) is None
    assert read("first_step_s", dataclasses.replace(
        run_records, startup={})) is None
