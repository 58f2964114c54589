"""The eight readers of the program's ``startup`` event
(``harness/startup_timeline.py``, ``../STARTUP_TIMELINE.md``) on made-up
records of a run: a value where the event has the fields, ``None`` where
it has not (the parent of PR 34), and never a raise."""

import json
import os

import pytest

from benchmarks.harness import loop_timeline, manifest, records
from benchmarks.tests.tiny import ROOT

STARTUP = {   # an event's ``extra``, in the shape train/loop.py emits it
    "time_to_first_step_s": 24.0, "restored_step": None,
    "compilation_cache_dir": "/x/.jax_cache",
    "phases_s": {"startup:dataset": 0.01, "startup:writer": 0.0,
                 "startup:sample": 0.25, "startup:init_state": 3.5,
                 "startup:make_step": 0.001, "startup:restore": 0.0,
                 "startup:loop_entry": 0.125, "snapshot": 1.5,
                 "infeed": 0.0625, "train_step": 6.0},
    "outside_s": 12.5515, "process_s": 14.25,
    "compile": {"traces": 900, "trace_s": 7.0, "lower_s": 2.5,
                "xla_compiles": 30, "xla_compile_s": 0.75,
                "cache_hits": 3, "cache_misses": 1, "cache_load_s": 4.25,
                "trace_lower_s": {"inside": 5.5, "outside": 4.0},
                "xla_s": {"inside": 3.0, "outside": 2.0}}}

EXPECTED = {
    "startup_process_s": 14.25,
    "startup_state_init_s": 3.5,
    "startup_first_dispatch_s": 6.0,
    "startup_snapshot_s": 1.5,
    "startup_trace_s": 9.5,
    "startup_xla_s": 5.0,
    "startup_cache_misses": 0.25,
    "startup_outside_s": 12.5515,
}


def run_records(startup):
    return records.RunRecords(
        cell=manifest.Manifest(ROOT).cell("bert_s512"), window={},
        startup=startup, step_memory={}, peaks={},
        model_flops_per_unit=0.0, attention_work=None)


def read(name, r):
    return manifest.load_reader(ROOT, name).read(r)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_value_where_the_event_has_the_fields(name):
    assert read(name, run_records(STARTUP)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_nothing_and_no_raise_where_it_has_not(name, capsys):
    parent = {"time_to_first_step_s": 24.0, "restored_step": None}
    assert read(name, run_records(parent)) is None
    assert read(name, run_records({})) is None
    # a field of another shape than the program writes: a line on stderr,
    # one metric less, never a run less
    broken = {**STARTUP, "phases_s": 3, "compile": {"traces": "x"},
              "outside_s": "soon", "process_s": []}
    assert read(name, run_records(broken)) is None
    assert "found nothing to read" in capsys.readouterr().err
    # ... and first_step_s reads what it read
    assert read("first_step_s", run_records(parent)) == 24.0


def test_a_phase_that_did_not_run_is_zero_seconds():
    no_ladder = dict(STARTUP, phases_s={
        k: v for k, v in STARTUP["phases_s"].items() if k != "snapshot"})
    assert read("startup_snapshot_s", run_records(no_ladder)) == 0.0


def test_no_miss_ratio_where_the_cache_was_asked_nothing():
    off = dict(STARTUP, compile=dict(STARTUP["compile"],
                                     cache_hits=0, cache_misses=0))
    assert read("startup_cache_misses", run_records(off)) is None
    cold = dict(STARTUP, compile=dict(STARTUP["compile"],
                                      cache_hits=0, cache_misses=4))
    assert read("startup_cache_misses", run_records(cold)) == 1.0
    warm = dict(STARTUP, compile=dict(STARTUP["compile"],
                                      cache_hits=4, cache_misses=0))
    assert read("startup_cache_misses", run_records(warm)) == 0.0


def test_the_tree_meets_the_contract_with_the_new_entries():
    assert manifest.check(ROOT) == []
    man = manifest.Manifest(ROOT)
    new = [m for m in man.data["per_layer"] if m["name"] in EXPECTED]
    # appended, in every cell (no ``workloads``), all moving setup_s
    assert [m["name"] for m in man.data["per_layer"][-8:]] == [
        m["name"] for m in new] and len(new) == 8
    assert all("workloads" not in m and m["moves"] == "setup_s"
               and m["better"] == "lower" for m in new)
    for m in new:
        reader = manifest.load_reader(ROOT, m["name"])
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == (
            m["layer"], m["unit"], m["better"], m["source"]), m["name"]
    for w in man.data["workloads"]:
        names = {m["name"] for m in man.cell(w["name"]).per_layer}
        assert set(EXPECTED) <= names, w["name"]


def test_a_timeline_file_with_the_two_new_keys_still_loads(tmp_path):
    from benchmarks.tests.test_trace_reduce import DATA

    with open(os.path.join(DATA, "loop_timeline_12steps.json")) as fh:
        doc = json.load(fh)
    assert "startup" not in doc and "compiles" not in doc
    doc["startup"] = [["startup:dataset", 0, doc["spans"][0][2] - 10**9, 5]]
    doc["compiles"] = [["xla", "jit(step)", doc["spans"][0][2], 7, True, 7]]
    out = loop_timeline.out_dir(str(tmp_path), "bert_s512")
    os.makedirs(out)
    with open(os.path.join(out, f"loop_timeline-{os.getpid()}.json"),
              "w") as fh:
        json.dump(doc, fh)
    loaded = loop_timeline.load(str(tmp_path), "bert_s512")
    assert loaded is not None and loaded["spans"] == doc["spans"]
    spans, steps = loop_timeline.window_spans(loaded, {"steps": 10})
    assert steps == 10 and not any(s[0].startswith("startup:") for s in spans)
    # the schema string is the one the loader knows, and no other
    assert loaded["schema"] == loop_timeline.SCHEMA == "dtf-loop-timeline/1"
