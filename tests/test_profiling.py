"""Tracing/profiling subsystem (core/profiling.py + ProfileHook).

SURVEY.md §5 "Tracing / profiling": XPlane traces + step annotations +
host-side phase timing. These were dead surface in round 1 — now the
Trainer reports ``time_*_ms`` phases every log interval and ProfileHook
captures a real trace (both asserted here).
"""

import glob
import json
import os
import time

import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.core import goodput, profiling
from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.profiling import StepTimer
from distributed_tensorflow_framework_tpu.train import Trainer
from distributed_tensorflow_framework_tpu.train import hooks as hooks_lib


def _cfg(**train_overrides):
    base = {
        "name": "prof-test",
        "mesh": {"data": 8},
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.05},
        "train": dict({"total_steps": 6, "log_interval": 3}, **train_overrides),
    }
    return load_config(base=base)


def _abab(t: StepTimer) -> None:
    with t.phase("a"):
        pass
    with t.phase("a"):
        pass
    with t.phase("b"):
        pass


def _means_and_totals(t):
    _abab(t)
    means = t.means()
    assert set(means) == {"time_a_ms", "time_b_ms"}
    assert all(v >= 0 for v in means.values())
    assert t.counts == {"a": 2, "b": 1} and set(t.totals) == {"a", "b"}
    assert means["time_a_ms"] == pytest.approx(1e3 * t.totals["a"] / 2)
    t.reset()
    assert t.means() == {} and t.totals == {} and t.counts == {}


def _ring_records_each_occurrence(t):
    before = time.time_ns()
    t.step = 7
    with t.phase("a"):
        time.sleep(0.002)
    t.step = 8
    with t.phase("b"):
        pass
    after = time.time_ns()
    (n0, s0, start0, d0), (n1, s1, start1, d1) = t.spans
    assert (n0, s0, n1, s1) == ("a", 7, "b", 8)
    assert before <= start0 <= start0 + d0 <= start1 + 1_000_000 <= after + 1_000_000
    assert 2_000_000 <= d0 < 500_000_000 and 0 <= d1 < d0
    # the ring's duration is the very time the totals got
    assert t.totals["a"] == pytest.approx(d0 * 1e-9)


def _ring_is_bounded(_):
    assert StepTimer.RING_SPANS >= 1024 * 16

    class Small(StepTimer):
        RING_SPANS = 8

    t = Small()
    for i in range(20):
        t.step = i
        with t.phase("a"):
            pass
    assert [s[1] for s in t.spans] == list(range(12, 20))
    assert t.counts["a"] == 20  # the totals forget nothing


def _ring_survives_reset(t):
    _abab(t)
    t.reset()
    assert [s[0] for s in t.spans] == ["a", "a", "b"]
    assert t.totals == {}


def _span_name_apart_from_phase_name(t):
    with t.phase("compile", span="train_step"):
        pass
    with t.phase("dispatch", span="train_step"):
        pass
    assert set(t.totals) == {"compile", "dispatch"}
    assert [s[0] for s in t.spans] == ["train_step", "train_step"]


def _a_raising_body_is_still_recorded(t):
    with pytest.raises(KeyError):
        with t.phase("a"):
            raise KeyError("x")
    assert t.counts == {"a": 1} and len(t.spans) == 1


@pytest.mark.parametrize("case", [
    _means_and_totals, _ring_records_each_occurrence, _ring_is_bounded,
    _ring_survives_reset, _span_name_apart_from_phase_name,
    _a_raising_body_is_still_recorded], ids=lambda f: f.__name__.strip("_"))
def test_step_timer_phases(case):
    case(StepTimer())


def test_timeline_dump_schema(tmp_path):
    t = StepTimer()
    t.step = 3
    _abab(t)
    path = t.dump(str(tmp_path / "sub" / "loop_timeline-1.json"), final_step=3)
    with open(path) as fh:
        doc = json.load(fh)
    assert set(doc) == {"schema", "pid", "clock", "offset_ns", "final_step",
                        "spans", "startup", "compiles"}
    assert doc["schema"] == profiling.TIMELINE_SCHEMA == "dtf-loop-timeline/1"
    assert doc["pid"] == os.getpid() and doc["final_step"] == 3
    assert doc["clock"] == "time.time_ns" and doc["offset_ns"] == 0
    assert doc["spans"] == [list(s) for s in t.spans]
    # an unwritable place costs the file, never the run
    blocker = tmp_path / "file"
    blocker.write_text("")
    assert t.dump(str(blocker / "x" / "t.json"), final_step=3) is None


def _block(slow_name=None, slow_ms=0.0, wait_ms=0.0, steps=range(1, 11)):
    spans = []
    for step in steps:
        spans += [("infeed", step, 0, 200_000), ("train_step", step, 0, 4_000_000),
                  ("hook:LoggingHook", step, 0, 50_000)]
    if wait_ms:
        spans.append(("metrics_fetch", 10, 0, int(wait_ms * 1e6)))
    if slow_name:
        spans.append((slow_name, 7, 0, int(slow_ms * 1e6)))
    return spans


@pytest.mark.parametrize("spans, floor_ms, expect", [
    (_block(), None, []),
    # a hook that slept is named, with the iteration's other spans
    (_block("hook:Sleepy", 200.0), None, [7]),
    # under the floor (100 ms) nothing is slow, however far from the median
    (_block("hook:Sleepy", 90.0), None, []),
    # a wait on the device follows the device's step time by design
    (_block(wait_ms=1283.0), None, []),
    # a long dispatch is the host's own time
    (_block("train_step", 130.0), None, [7]),
    # ten times the median: 60 ms is slow against a 4.25 ms median at floor 0
    (_block("snapshot", 60.0), 0.0, [7]),
    (_block("snapshot", 30.0), 0.0, []),
    ([], None, []),
], ids=["steady", "slept_hook", "under_floor", "device_wait", "dispatch",
        "factor", "under_factor", "empty"])
def test_slow_iterations(spans, floor_ms, expect, monkeypatch):
    assert (profiling.SLOW_FLOOR_MS, profiling.SLOW_FACTOR) == (100.0, 10.0)
    if floor_ms is not None:
        monkeypatch.setattr(profiling, "SLOW_FLOOR_MS", floor_ms)
    slow = profiling.slow_iterations(spans)
    assert [s["step"] for s in slow] == expect
    for s in slow:
        assert s["block_median_ms"] == pytest.approx(4.25)
        assert s["host_ms"] > 10 * s["block_median_ms"]
        assert {"infeed", "train_step", "hook:LoggingHook"} <= set(s["spans_ms"])


@pytest.mark.parametrize("phase, bucket", [
    ("snapshot", "snapshot"), ("bookkeeping", "bookkeeping"),
    ("hook:LoggingHook", "hooks"), ("hook:TimedHook", "hooks"),
    ("hook:CheckpointHook", "hooks"), ("rollback", "rollback"),
    ("dispatch", "step_compute"), ("mystery", "mystery")])
def test_phase_buckets_out_of_other(phase, bucket):
    assert goodput.phase_bucket(phase) == bucket
    if bucket != "mystery":
        assert bucket in goodput.BUCKET_ORDER
    led = goodput.GoodputLedger()
    led.absorb_phases({phase: 0.5})
    assert led.snapshot()["buckets"][bucket] == 0.5


def test_trainer_reports_phase_times(devices):
    trainer = Trainer(_cfg())
    metrics = trainer.train()
    for key in ("time_infeed_ms", "time_dispatch_ms", "time_metrics_fetch_ms"):
        assert key in metrics, sorted(metrics)
        assert np.isfinite(metrics[key]) and metrics[key] >= 0


def test_profile_hook_captures_trace(devices, tmp_path):
    cfg = _cfg(profile_start=2, profile_stop=4)
    cfg.checkpoint.directory = str(tmp_path / "run")
    cfg.checkpoint.save_interval_steps = 1000
    trainer = Trainer(cfg)
    trainer.train()
    # An XPlane trace landed under <ckpt_dir>/traces.
    produced = glob.glob(
        os.path.join(str(tmp_path / "run"), "traces", "**", "*.xplane.pb"),
        recursive=True,
    )
    assert produced, "ProfileHook produced no XPlane trace"


# ---------------------------------------------------------- loop timeline --
class _SleepyHook(hooks_lib.BaseHook):
    """0.4 s of host time in one iteration's hook (the event's floor is
    0.1 s, or ten times the block's median iteration: ~8 ms here, more
    while the suite's other workers load the cores)."""

    AT_STEP, SLEEP_S = 7, 0.4

    def after_step(self, trainer, step, metrics) -> None:
        if step == self.AT_STEP:
            time.sleep(self.SLEEP_S)


class _SelfChargingHook(hooks_lib.BaseHook):
    """Blocks 50 ms once and books it itself, as the checkpoint hook's
    ``save()`` does through the saver's ``ckpt_save`` event."""

    charges_goodput_itself = True
    AT_STEP, SLEEP_S = 12, 0.05

    def after_step(self, trainer, step, metrics) -> None:
        if step == self.AT_STEP:
            time.sleep(self.SLEEP_S)
            trainer.goodput.add("ckpt_blocked", self.SLEEP_S)


@pytest.fixture(scope="module")
def timeline_run(devices, tmp_path_factory):
    """One run of the real loop on a tiny config: 20 steps, a fetch
    every 5, a recovery snapshot every 10, a hook that sleeps once."""
    out = tmp_path_factory.mktemp("timeline")
    cfg = _cfg(total_steps=20, log_interval=5)
    cfg.resilience.snapshot_interval_steps = 10
    cfg.trace.dump_dir = str(out)
    cfg.data.async_infeed = False
    trainer = Trainer(cfg)
    events, fetched = [], []
    trainer.writer.telemetry.add_listener(events.append)

    class Fetched(hooks_lib.BaseHook):
        def after_step(self, trainer, step, metrics) -> None:
            if metrics is not None:
                fetched.append((step, dict(metrics)))

    trainer.build()
    trainer.train(hooks=trainer.default_hooks() + [
        _SleepyHook(), _SelfChargingHook(), Fetched()])
    with open(out / f"loop_timeline-{os.getpid()}.json") as fh:
        doc = json.load(fh)
    return {"trainer": trainer, "events": events, "fetched": fetched,
            "doc": doc}


def _by_step(spans):
    out: dict = {}
    for name, step, start, dur in spans:
        out.setdefault(step, []).append((name, start, dur))
    return out


def test_loop_spans_cover_every_iteration(timeline_run):
    by = _by_step(timeline_run["doc"]["spans"])
    assert sorted(by) == list(range(0, 21))  # 0: the baseline snapshot
    shares, uncovered_ms = {}, {}
    for step in range(1, 20):
        wall = by[step + 1][0][1] - by[step][0][1]  # infeed to next infeed
        covered = sum(d for _, _, d in by[step])
        shares[step] = covered / wall
        uncovered_ms[step] = (wall - covered) * 1e-6
        names = [n for n, _, _ in by[step]]
        assert names[:2] == ["infeed", "train_step"], names
        assert "hook:_SleepyHook" in names and "hook:LoggingHook" in names
        if step % 5 == 0:
            assert "metrics_fetch" in names and "bookkeeping" in names
        else:
            assert "metrics_fetch" not in names
    # Every iteration is at least 95% under spans (99.5% is usual).
    # Between two spans the loop runs a few statements (~40 us), and that
    # is where a collection of Python's, or the OS parking the thread
    # while the suite's other workers hold every core, adds milliseconds
    # to the odd iteration: two of the nineteen may fall short.
    ordered = sorted(shares.values())
    assert ordered[len(ordered) // 2] >= 0.98, shares
    assert ordered[2] >= 0.95, (shares, uncovered_ms)
    # no nesting: each span starts after the one before it ended
    spans = timeline_run["doc"]["spans"]
    for (_, _, s0, d0), (_, _, s1, _) in zip(spans, spans[1:]):
        assert s1 >= s0 + d0 - 200_000  # two clocks: allow 0.2 ms of skew


def test_snapshot_spans_sit_at_the_snapshot_steps(timeline_run):
    snaps = [s for s in timeline_run["doc"]["spans"] if s[0] == "snapshot"]
    assert [s[1] for s in snaps] == [0, 10, 20]
    counters = timeline_run["trainer"].goodput.snapshot()["counters"]
    assert counters["snapshots"] == 3 and counters["recompiles"] == 1
    state = timeline_run["trainer"].recovery.ring.latest()
    assert counters["snapshot_bytes"] == 3 * state.nbytes > 0


def test_fetched_metrics_keep_their_phase_times(timeline_run):
    assert [s for s, _ in timeline_run["fetched"]] == [5, 10, 15, 20]
    for _, metrics in timeline_run["fetched"]:
        for key in ("time_infeed_ms", "time_metrics_fetch_ms"):
            assert np.isfinite(metrics[key]) and metrics[key] >= 0, key
    # the first block's dispatches: one compile, four plain
    first, second = (m for _, m in timeline_run["fetched"][:2])
    assert "time_compile_ms" in first and "time_dispatch_ms" in first
    assert "time_compile_ms" not in second
    assert second["time_hook:LoggingHook_ms"] >= 0
    # totals are per block: the slept hook shows in its block's mean only
    assert 80 <= second["time_hook:_SleepyHook_ms"] < 130  # 400 ms / 5
    assert timeline_run["fetched"][2][1]["time_hook:_SleepyHook_ms"] < 1


def test_timeline_file_schema(timeline_run):
    doc = timeline_run["doc"]
    assert doc["schema"] == "dtf-loop-timeline/1"
    assert doc["pid"] == os.getpid() and doc["final_step"] == 20
    assert doc["clock"] == "time.time_ns" and doc["offset_ns"] == 0
    assert all(len(s) == 4 and isinstance(s[0], str) for s in doc["spans"])
    assert {s[0] for s in doc["spans"]} >= {
        "infeed", "train_step", "metrics_fetch", "bookkeeping", "snapshot",
        "hook:ThroughputHook", "hook:LoggingHook", "hook:NaNGuardHook"}
    now = time.time_ns()
    assert all(now - 600e9 < s[2] <= now for s in doc["spans"])


def test_loop_buckets_left_other(timeline_run):
    snap = timeline_run["trainer"].goodput.snapshot()
    b = snap["buckets"]
    assert b["hooks"] >= _SleepyHook.SLEEP_S and b["snapshot"] > 0 and b["bookkeeping"] > 0
    # a hook that charges the ledger itself is in the ring, and in no
    # bucket twice: ``hooks`` holds every other hook's spans and no more
    hook_s = {True: 0.0, False: 0.0}
    for name, _, _, dur in timeline_run["doc"]["spans"]:
        if name.startswith("hook:"):
            hook_s[name == "hook:_SelfChargingHook"] += dur * 1e-9
    assert hook_s[True] >= _SelfChargingHook.SLEEP_S
    assert b["hooks"] == pytest.approx(hook_s[False], abs=1e-3)
    assert b["ckpt_blocked"] == pytest.approx(_SelfChargingHook.SLEEP_S)
    assert sum(b.values()) == pytest.approx(snap["wall_s"], abs=0.02)
    # what is left over is the loop's own statements and on_end
    loop_s = sum(v for k, v in b.items() if k not in ("startup", "other"))
    assert b["other"] < 0.05 * loop_s + 0.5


class _TickingClock:
    """A duration clock the test controls: every reading is 1 ms after
    the one before, so a span lasts exactly 1 ms whatever the machine
    does meanwhile, and ``skip`` is the only way an iteration gets long."""

    def __init__(self):
        self.now_ns = 0

    def __call__(self) -> int:
        self.now_ns += 1_000_000
        return self.now_ns

    def skip(self, seconds: float) -> None:
        self.now_ns += int(seconds * 1e9)


def test_slept_hook_yields_one_slow_step_event(devices):
    """The real loop, its recorder on a clock the test controls: the one
    iteration whose hook took 0.4 s of that clock is reported, once, and
    no other. (On the wall clock, under the suite's other workers, the
    block's median iteration now and then passed 40 ms, so that 0.4 s
    was no longer ten times it and no event came.)"""
    cfg = _cfg(total_steps=20, log_interval=5)
    cfg.data.async_infeed = False
    trainer = Trainer(cfg)
    clock = trainer.timer.clock_ns = _TickingClock()
    events = []
    trainer.writer.telemetry.add_listener(events.append)

    class Skips(_SleepyHook):
        def after_step(self, trainer, step, metrics) -> None:
            if step == self.AT_STEP:
                clock.skip(self.SLEEP_S)

    trainer.build()
    trainer.train(hooks=trainer.default_hooks() + [Skips()])
    slow = [e for e in events
            if (e.get("health") or {}).get("event") == "slow_step"]
    assert len(slow) == 1, slow
    ev = slow[0]
    assert ev["kind"] == "health" and ev["step"] == _SleepyHook.AT_STEP
    h = ev["health"]
    assert h["step"] == _SleepyHook.AT_STEP
    assert max(h["spans_ms"], key=h["spans_ms"].get) == "hook:Skips"
    assert h["spans_ms"]["hook:Skips"] == 1e3 * _SleepyHook.SLEEP_S + 1
    assert h["host_ms"] > 10 * h["block_median_ms"]
