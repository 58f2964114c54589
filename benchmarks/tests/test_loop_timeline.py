"""The four readers of the program's loop timeline, from a fixture file
whose values were set by hand (``data/loop_timeline_12steps.json``: 12
iterations, a fetch every 5, a 300 ms snapshot at step 5, spans laid end
to end) and the recorded trace of ``test_trace_reduce``. The fixture sits
on the recorded trace's clock: step 10's ``metrics_fetch`` ends 327.0 ms
after the trace's ``profile_start_time``."""

import dataclasses
import json
import os
import shutil

import pytest

from benchmarks.harness import loop_timeline, manifest
from benchmarks.tests.test_layer_metrics import run_records  # noqa: F401
from benchmarks.tests.test_trace_reduce import DATA, profile, scopes  # noqa: F401
from benchmarks.tools import clock_check

NEW = ("sync_bubble_ms_step", "snapshot_ms_step", "loop_self_ms_step",
       "idle_in_sync_pct", "idle_in_fetch_pct")
TRACED = ("idle_in_sync_pct", "idle_in_fetch_pct")
T0 = 1790447747262924153   # the recorded trace's profile_start_time


def fixture_doc() -> dict:
    with open(os.path.join(DATA, "loop_timeline_12steps.json")) as fh:
        return json.load(fh)


@pytest.fixture()
def records(run_records):  # noqa: F811
    """The window is the last 10 of the fixture's 12 steps."""
    return dataclasses.replace(
        run_records, window={**run_records.window, "steps": 10})


def read(root, name, r):
    return manifest.load_reader(root, name).read(r)


def test_values_by_hand(run_root, records):
    # bubble at step 5: bookkeeping 0.1 + 0.02, snapshot 300, bookkeeping
    # 0.3, three hooks 0.15, the window hook 0.01, the next infeed 0.2 and
    # train_step 4.0 = 304.78 ms, less the window hook; at step 10 the same
    # without the snapshot = 4.78 ms, less the window hook
    assert read(run_root, "sync_bubble_ms_step", records) == pytest.approx(
        (304.77 + 4.77) / 10)
    assert read(run_root, "snapshot_ms_step", records) == pytest.approx(30.0)
    # ten dispatches of 4.0, two fetches' bookkeeping of 0.42, ten
    # iterations' three hooks of 0.05
    assert read(run_root, "loop_self_ms_step", records) == pytest.approx(
        (10 * 4.0 + 2 * 0.42 + 10 * 0.15) / 10)
    # step 10's bubble, fetch end to next dispatch end, is 327.0..331.78
    # ms on the trace's clock and holds five of the first device's ten
    # longest gaps whole: those at 327.2 ms (56613 ns) and at 331.6 ms
    # (18328 + 4671 + 1362 + 1152), of a 321607366 ns window; the fetch
    # before it, -673.0..327.0 ms, holds the three at 170.8 ms (4853 +
    # 1351 + 1152)
    assert read(run_root, "idle_in_sync_pct", records) == pytest.approx(
        100 * 82126 / 321607366)
    assert read(run_root, "idle_in_fetch_pct", records) == pytest.approx(
        100 * 7356 / 321607366)
    # the .images entries are the same readers
    assert read(run_root, "sync_bubble_ms_step.images", records) == \
        pytest.approx(30.954)


def test_idle_in_sync_follows_the_clock_offset(run_root, records):
    """A timeline 1 ms behind the trace's clock says so in ``offset_ns``;
    moved the other way its bubble ends before the gaps at 331.6 ms."""
    path = os.path.join(loop_timeline.out_dir(run_root, "bert_s512"),
                        f"loop_timeline-{os.getpid()}.json")
    doc = fixture_doc()
    for span in doc["spans"]:
        span[2] -= 1_000_000
    doc["offset_ns"] = 1_000_000
    with open(path, "w") as fh:
        json.dump(doc, fh)
    assert read(run_root, "idle_in_sync_pct", records) == pytest.approx(
        100 * 82126 / 321607366)
    doc["offset_ns"] = -3_500_000    # the bubble is 323.5..328.28 ms
    with open(path, "w") as fh:
        json.dump(doc, fh)
    assert read(run_root, "idle_in_sync_pct", records) == pytest.approx(
        100 * 56613 / 321607366)
    assert read(run_root, "idle_in_fetch_pct", records) == pytest.approx(
        100 * 7356 / 321607366)


def break_nothing(root, out):
    pass


def break_file(root, out):
    os.remove(os.path.join(out, f"loop_timeline-{os.getpid()}.json"))


def break_schema(root, out):
    with open(os.path.join(out, f"loop_timeline-{os.getpid()}.json"), "w") as fh:
        json.dump({"schema": "something/2", "spans": []}, fh)


def break_json(root, out):
    with open(os.path.join(out, f"loop_timeline-{os.getpid()}.json"), "w") as fh:
        fh.write('{"schema": "dtf-loop-timeline/1", "spans": [[')


def break_ring(root, out):
    """The ring forgot the window's first iterations."""
    doc = fixture_doc()
    doc["spans"] = [s for s in doc["spans"] if s[1] >= 6]
    with open(os.path.join(out, f"loop_timeline-{os.getpid()}.json"), "w") as fh:
        json.dump(doc, fh)


def break_trace(root, out):
    shutil.rmtree(os.path.join(out, "trace"))


@pytest.mark.parametrize("breakage, silent", [
    (break_nothing, ()), (break_file, NEW), (break_schema, NEW),
    (break_json, NEW), (break_ring, NEW),
    (break_trace, TRACED)],
    ids=lambda x: x.__name__ if callable(x) else None)
def test_readers_give_nothing_and_never_raise(run_root, records, breakage,
                                              silent):
    """The parent of PR 24 writes no timeline: a reader then gives None.
    The runner calls ``read()`` unguarded."""
    breakage(run_root, loop_timeline.out_dir(run_root, "bert_s512"))
    for name in NEW:
        value = read(run_root, name, records)
        assert (value is None) == (name in silent), (name, value)


def test_readers_need_the_windows_length(run_root, run_records):  # noqa: F811
    for name in NEW:
        assert read(run_root, name, run_records) is None      # no "steps"
    untraced = dataclasses.replace(
        run_records, trace=None, window={**run_records.window, "steps": 10})
    for name in TRACED:
        assert read(run_root, name, untraced) is None
    assert read(run_root, "sync_bubble_ms_step", untraced) is not None
    assert read(run_root, "snapshot_ms_step", None) is None   # not even records


def test_helper_pieces():
    spans = fixture_doc()["spans"]
    found = loop_timeline.window_spans(fixture_doc(), {"steps": 10})
    assert found is not None and found[1] == 10
    assert {s[1] for s in found[0]} == set(range(3, 13))
    assert loop_timeline.window_spans(None, {"steps": 10}) is None
    assert loop_timeline.window_spans(fixture_doc(), {"steps": 13}) is None
    bubbles = loop_timeline.sync_bubbles(spans)
    assert [(e - s, i) for s, e, i in bubbles] == [
        (304_780_000, 10_000), (4_780_000, 10_000)]
    assert bubbles[1][0] == T0 + 327_000_000
    # a bubble opens where a fetch ends
    fetches = loop_timeline.fetches(spans)
    assert [e - s for s, e in fetches] == [1_000_000_000] * 2
    assert [e for _, e in fetches] == [s for s, _, _ in bubbles]
    # a fetch that no dispatch follows opens no bubble
    assert len(loop_timeline.sync_bubbles(
        [s for s in spans if s[1] <= 10])) == 1
    assert loop_timeline.total_ns(spans, prefix="hook:") == 12 * 150_000
    assert loop_timeline.total_ns(spans, names=("snapshot",)) == 300_000_000


def test_profile_start_and_clock_pairing(profile):  # noqa: F811
    assert loop_timeline.profile_start_ns(profile) == T0
    host = clock_check.host_events(profile, "train_step")
    assert [round(d) for _, d in host] == [4701270, 3652930]
    # a ring whose train_step spans started 30 us and 50 us before the
    # profiler's events of the same ordinal
    spans = [["infeed", 1, T0 + 168_882_000, 54_000],
             ["train_step", 1, T0 + 170_135_856 - 30_000, 4_740_000],
             ["train_step", 2, T0 + 175_003_495 - 50_000, 3_700_000],
             ["train_step", 3, T0 + 900_000_000, 3_700_000]]   # after the trace
    found = clock_check.pair(spans, profile, "train_step")
    assert found["pairs"] == 2
    assert found["start_diff_us"]["median"] == pytest.approx(40.0)
    assert found["start_diff_us"]["min"] == pytest.approx(30.0)
    assert found["start_diff_us"]["max"] == pytest.approx(50.0)
    assert clock_check.pair(spans[:1], profile, "train_step")["pairs"] == 0
