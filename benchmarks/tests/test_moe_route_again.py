"""``moe_route_again_pct`` (PR 33): the routing's share of the forward
pass that the backward pass runs again, read from the phase ``again`` of
``scope_times.part_label_s``: on made-up labels with and without that
phase, on the labels a traced run of ``nemotron3_super_s8192`` left on
the chip at the parent commit, on a program without expert layers and on
a run without a trace; and the entry in the manifest."""

import dataclasses
import json
import os

import pytest

from benchmarks.harness import manifest, records, scope_times, trace_reduce
from benchmarks.tests.tiny import ROOT

NAME = "moe_route_again_pct"
CELLS = ["lfm2_moe_s8192", "smallthinker_s16384", "nemotron3_super_s8192"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RECORDED = os.path.join(ROOT, "benchmarks", "tests", "data",
                        "nemotron3_super_s8192_labels.json")

# Device seconds by label of a made-up decoder step under ``model.remat``
# whose expert layers keep nothing: the first pass's router and dispatch,
# the same once more in the re-run pass, the backward pass's own.
FIRST_AND_BACKWARD = {
    "convolution:fwd/layerN/moe/router": 2.0,
    "fusion:fwd/layerN/moe/router": 3.0,
    "sort:fwd/layerN/moe/router": 1.0,
    "fusion:fwd/layerN/moe/dispatch": 3.0,
    "sort:fwd/layerN/moe/dispatch": 1.0,
    "fusion:fwd/layerN/moe/combine": 2.0,
    "custom-call:ragged-dot-none": 12.0,
    "convolution:bwd/layerN/moe/router": 4.0,
    "fusion:bwd/layerN/moe/dispatch": 2.0,
    "fusion:bwd/layerN/moe/combine": 2.0,
    "convolution:fwd/layerN/mlp_in": 7.0,
    "optimizer_update": 3.0,
}
RERUN = {
    **FIRST_AND_BACKWARD,
    "convolution:again/layerN/moe/router": 2.0,
    "fusion:again/layerN/moe/router": 3.0,
    "sort:again/layerN/moe/router": 1.0,
    "fusion:again/layerN/moe/dispatch": 3.0,
    "sort:again/layerN/moe/dispatch": 1.0,
    "fusion:again/layerN/moe/combine": 2.0,      # no part of the routing
    "convolution:again/layerN/moe/shared": 1.5,  # nor this
    "convolution:again/layerN/short_conv/in_proj": 2.5,
}
# The routing kept: what the backward pass differentiates through is left.
KEPT = {
    **FIRST_AND_BACKWARD,
    "fusion:again/layerN/moe/router": 0.5,
    "fusion:again/layerN/moe/dispatch": 0.25,
    "fusion:again/layerN/moe/combine": 2.0,
    "convolution:again/layerN/moe/shared": 1.5,
    "convolution:again/layerN/short_conv/in_proj": 2.5,
}
BUSY_S = 50.0


def run_of(tmp_path, monkeypatch, cell_name, label_s, busy_s=BUSY_S):
    """Records of a traced run of the cell whose checkout is ``tmp_path``:
    the benchmark's files linked in, a second reduction that gives
    ``label_s`` (None: the run's files are gone)."""
    os.symlink(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks")
    monkeypatch.setattr(scope_times, "_reduce_again",
                        lambda out, pid: None if label_s is None
                        else dict(label_s))
    # the run's own labels fold the re-run pass's parts away
    folded: dict = {}
    for label, sec in (label_s or {}).items():
        kind, _, scope = label.partition(":")
        if scope.startswith(f"{scope_times.AGAIN}/"):
            path = scope.split("/")[1:3]
            label = f"{kind}:bwd/{scope_times.REMAT}/" + "/".join(path)
        folded[label] = folded.get(label, 0.0) + sec
    red = trace_reduce.TraceReduction(
        devices=1, busy_s=busy_s, window_s=busy_s * 1.001, category_s={},
        label_s=folded, kernel_s={}, collective_s=0.0,
        collective_exposed_s=0.0, idle_gaps=[], steps=5)
    rec = records.RunRecords(
        cell=manifest.Manifest(ROOT).cell(cell_name),
        window={"steps": 20, "rate_per_chip": 3.3e4}, startup={},
        step_memory={"step_gib": 8.1}, peaks=PEAKS,
        model_flops_per_unit=1.4e9, attention_work=None, trace=red)
    return str(tmp_path), rec


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("label_s,want", [
    (RERUN, 100 * (2 + 3 + 1 + 3 + 1) / BUSY_S),
    (KEPT, 100 * 0.75 / BUSY_S),
    (FIRST_AND_BACKWARD, 0.0),        # expert layers, no pass run again
], ids=["rerun", "kept", "no_remat"])
def test_the_reader_gives_the_reruns_routing_share_of_busy_time(
        tmp_path, monkeypatch, cell_name, label_s, want):
    root, rec = run_of(tmp_path, monkeypatch, cell_name, label_s)
    read = lambda name: manifest.load_reader(root, name).read(rec)  # noqa: E731
    assert read(NAME) == pytest.approx(want)
    # a part of what ``moe_dispatch_pct`` reads in every pass, which is a
    # part of ``moe_pct``
    assert read(NAME) < read("moe_dispatch_pct") < read("moe_pct") < 100


def test_the_reader_reads_the_labels_recorded_on_the_chip(
        tmp_path, monkeypatch):
    """The parent commit's traced run of ``nemotron3_super_s8192`` (my
    chip run, PR 32): the re-run pass's router and dispatch are 28.3 ms
    of a 242 ms step."""
    with open(RECORDED) as fh:
        recorded = json.load(fh)
    root, rec = run_of(tmp_path, monkeypatch, CELLS[2], recorded["label_s"],
                       busy_s=recorded["busy_s"])
    got = manifest.load_reader(root, NAME).read(rec)
    assert got == pytest.approx(11.67, abs=0.01)
    ms_step = got / 100 * recorded["busy_s"] / recorded["steps"] * 1e3
    assert ms_step == pytest.approx(28.26, abs=0.01)
    first = sum(sec for label, sec in recorded["label_s"].items()
                if ":fwd/" in label and ("moe/router" in label
                                         or "moe/dispatch" in label))
    # the re-run pass repeats the first pass's routing, to the millisecond
    assert 100 * first / recorded["busy_s"] == pytest.approx(got, abs=0.05)


def test_the_reader_gives_nothing_where_there_is_nothing_to_read(
        tmp_path, monkeypatch):
    """A program without expert layers (the BERT and ResNet cells, re-run
    pass or not), a traced run whose files are gone, an untraced run:
    nothing raises, the line leaves the metric out."""
    no_experts = {k: v for k, v in RERUN.items()
                  if "moe" not in k and "ragged" not in k}
    root, rec = run_of(tmp_path, monkeypatch, CELLS[0], no_experts)
    reader = manifest.load_reader(root, NAME)
    assert any(scope_times.AGAIN in k for k in no_experts)
    assert reader.read(rec) is None
    monkeypatch.setattr(scope_times, "_reduce_again", lambda out, pid: None)
    assert reader.read(rec) is None
    assert reader.read(dataclasses.replace(rec, trace=None)) is None
    monkeypatch.setattr(scope_times, "_reduce_again",
                        lambda out, pid: dict(RERUN))
    idle = dataclasses.replace(
        rec, trace=dataclasses.replace(rec.trace, busy_s=0.0))
    assert reader.read(idle) is None


def test_the_entry_names_the_expert_layer_and_the_three_expert_cells():
    assert manifest.check(ROOT) == []
    data = manifest.Manifest(ROOT).data
    entry = next(e for e in data["per_layer"] if e["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "expert layer",
        "moves": "tokens_per_s_chip", "workloads": CELLS}
    reader = manifest.load_reader(ROOT, NAME)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == (
        entry["layer"], entry["unit"], entry["better"], entry["source"])
    for name in CELLS:
        assert NAME in {m["name"] for m in manifest.Manifest(ROOT).cell(
            name).per_layer}
    for name in ("bert_s512", "bert_s8192", "bert_s512_dp4",
                 "resnet50_i224"):
        assert NAME not in {m["name"] for m in manifest.Manifest(ROOT).cell(
            name).per_layer}
