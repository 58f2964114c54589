"""The manifest checker, and that adding a cell or a metric adds files
and entries only."""

import json
import os
import shutil

import pytest

from benchmarks.harness import manifest
from benchmarks.tests.tiny import ROOT


def copy_benchmark(tmp_path) -> str:
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def edit_manifest(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        data = json.load(fh)
    fn(data)
    with open(path, "w") as fh:
        json.dump(data, fh)


def test_the_tree_meets_the_contract():
    assert manifest.check(ROOT) == []


def test_exactly_one_four_chip_cell_and_every_cell_resolves():
    man = manifest.Manifest(ROOT)
    cells = [man.cell(w["name"]) for w in man.data["workloads"]]
    assert sorted(c.name for c in cells) == [
        "bert_s512", "bert_s512_dp4", "bert_s8192", "resnet50_i224"]
    assert [c.name for c in cells if c.chips == 4] == ["bert_s512_dp4"]
    for c in cells:
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) == 2
        assert c.per_layer


@pytest.mark.parametrize("breakage, needle", [
    (lambda d: d["workloads"][0].update(name="has space"), "bad name"),
    (lambda d: d["workloads"][0].update(name="x" * 65), "bad name"),
    (lambda d: d["end_to_end"][0].update(unit="tokens per second"), "bad unit"),
    (lambda d: d["end_to_end"][0].update(unit="µs"), "bad unit"),
    (lambda d: d["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda d: d["end_to_end"][0].update(why="no such key"), "extra keys"),
    (lambda d: d["workloads"][1].update(chips=4), "four-chip cells"),
    (lambda d: d["workloads"][1].update(traffic="packed_s512"), "twice"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "moves unknown"),
    (lambda d: d["per_layer"].append(dict(d["per_layer"][1], name="no_reader")),
     "no reader"),
    (lambda d: d.update(run_seconds=52), "run_seconds"),
    (lambda d: d["end_to_end"].pop(), "no setup_s"),
    (lambda d: d["workloads"].append(
        dict(d["workloads"][0], name="ghost", traffic="ghost_mix")),
     "files not found"),
])
def test_checker_refuses(tmp_path, breakage, needle):
    root = copy_benchmark(tmp_path)
    edit_manifest(root, breakage)
    errs = manifest.check(root)
    assert any(needle in e for e in errs), errs


def test_new_cell_and_new_metric_need_new_files_and_entries_only(tmp_path):
    root = copy_benchmark(tmp_path)
    before = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            p = os.path.join(dirpath, f)
            before[p] = open(p, "rb").read()
    bench = os.path.join(root, "benchmarks")
    # a cell: one workload file, one traffic file (data only)
    with open(os.path.join(bench, "traffic", "dummy_mix.json"), "w") as fh:
        json.dump({"generator": "mlm_documents", "seq_len": 64,
                   "doc_length": {"dist": "fixed", "value": 64},
                   "pack": False, "close_after_misses": 1, "mask_prob": 0.15,
                   "mask_token_id": 103, "token_id_min": 1000,
                   "vocab_size": 30522, "pool_batches": 2}, fh)
    with open(os.path.join(bench, "workloads", "dummy_cell.json"), "w") as fh:
        json.dump({"name": "dummy_cell", "config": "bert_base",
                   "traffic": "dummy_mix", "chips": 1, "per_chip_batch": 2,
                   "overrides": [], "check_rows": 2, "trace_steps": 2,
                   "why": "dummy"}, fh)
    # a per-layer metric: one reader file
    with open(os.path.join(bench, "layer_metrics", "dummy_metric.py"),
              "w") as fh:
        fh.write("LAYER = 'device'\nUNIT = 'ms'\nBETTER = 'lower'\n"
                 "SOURCE = 'host_clock'\n\n\ndef read(r):\n    return 42.0\n")

    def add(d):
        d["workloads"].append({"name": "dummy_cell", "config": "bert_base",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "dummy"})
        d["end_to_end"][0]["workloads"].append("dummy_cell")
        d["per_layer"].append({"name": "dummy_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["dummy_cell"]})

    edit_manifest(root, add)
    assert manifest.check(root) == []
    man = manifest.Manifest(root)
    cell = man.cell("dummy_cell")
    assert cell.traffic["seq_len"] == 64
    assert "dummy_metric" in {m["name"] for m in cell.per_layer}
    assert manifest.load_reader(root, "dummy_metric").read(None) == 42.0
    from benchmarks.harness import build

    pool = build.make_pool(cell, root, seed=0)
    assert pool.batches[0]["input_ids"].shape == (2, 64)
    # ... and no file that was there changed
    for p, content in before.items():
        assert open(p, "rb").read() == content


def test_readers_declare_what_the_manifest_says():
    man = manifest.Manifest(ROOT)
    for m in man.data["per_layer"]:
        mod = manifest.load_reader(ROOT, m["name"])
        assert (mod.UNIT, mod.BETTER, mod.SOURCE) == (
            m["unit"], m["better"], m["source"]), m["name"]
        assert m["layer"].startswith(mod.LAYER), m["name"]
