"""``mlm_head_windows`` (PR 29): the reader on made-up records of a traced
BERT run, on a program without the counter (the parent commit, and
``resnet50_i224`` and ``lfm2_moe_s8192`` at any commit) and on a run
without a flight-recorder dump, and its entry in the manifest."""

import dataclasses
import os

import pytest

from benchmarks.harness import manifest
from benchmarks.tests import test_lfm2_cell
from benchmarks.tests.test_lfm2_cell import events_of
from benchmarks.tests.tiny import ROOT

NAME = "mlm_head_windows"
CELLS = ["bert_s512", "bert_s8192", "bert_s512_dp4"]


def fake_run(tmp_path, monkeypatch, events, cell="bert_s512"):
    """``test_lfm2_cell.fake_run``'s records, as a run of ``cell``."""
    root, rec = test_lfm2_cell.fake_run(tmp_path, monkeypatch, events)
    out = os.path.join(root, ".bench_out")
    os.rename(os.path.join(out, test_lfm2_cell.CELL), os.path.join(out, cell))
    return root, dataclasses.replace(
        rec, cell=manifest.Manifest(ROOT).cell(cell))


def test_the_entry_names_the_models_layer_and_the_three_bert_cells():
    assert manifest.check(ROOT) == []
    data = manifest.Manifest(ROOT).data
    entry = next(e for e in data["per_layer"] if e["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "models",
        "moves": "tokens_per_s_chip", "workloads": CELLS}
    assert data["per_layer"][-1] == entry     # appended, nothing moved
    reader = manifest.load_reader(ROOT, NAME)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == (
        entry["layer"], entry["unit"], entry["better"], entry["source"])
    for cell in data["workloads"]:
        names = {m["name"] for m in manifest.Manifest(ROOT).cell(
            cell["name"]).per_layer}
        assert (NAME in names) == (cell["name"] in CELLS), cell["name"]


@pytest.mark.parametrize("fetched,want", [
    ([1.0, 1.0], 1.0),            # the first window held every label
    ([1.0, 2.0], 1.5),            # one step had a row over the window
    ([4.0, 4.0], 4.0)])           # every position labelled: all of S=512
def test_the_reader_means_the_counter_over_the_windows_steps(
        tmp_path, monkeypatch, fetched, want):
    root, rec = fake_run(tmp_path, monkeypatch, events_of(
        [{"loss": 10.4, "mlm_head_windows": 3.0}]       # before the window
        + [{"loss": 10.4, "mlm_acc": 0.0, "mlm_head_windows": v}
           for v in fetched]))
    assert manifest.load_reader(root, NAME).read(rec) == pytest.approx(want)


@pytest.mark.parametrize("cell", ["bert_s512", "resnet50_i224"])
def test_the_reader_gives_nothing_where_there_is_nothing_to_read(
        tmp_path, monkeypatch, cell):
    """The parent commit's program has no such counter, nor has an image
    model's at any commit; a run may leave no dump. Neither raises, and
    the line leaves the metric out."""
    root, rec = fake_run(tmp_path, monkeypatch, events_of(
        [{"loss": 10.4, "mlm_acc": 0.0}, {"loss": 10.4}]), cell=cell)
    reader = manifest.load_reader(root, NAME)
    assert reader.read(rec) is None
    os.remove(os.path.join(root, ".bench_out", cell,
                           f"flightrec-{os.getpid()}.json"))
    assert reader.read(rec) is None
