"""The restart's own timeline (core/profiling.py + train/loop.py).

From construction to the first dispatch returning, every stretch of the
Trainer runs under a ``startup:*`` span of the loop's recorder; the
``startup`` event says where ``time_to_first_step_s`` went; the compile
log counts what JAX traced, compiled and loaded from its own monitoring
events; a compile that the loop did not ask for becomes a ``recompile``
health event. docs/OBSERVABILITY.md "The loop timeline".
"""

import itertools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from distributed_tensorflow_framework_tpu import data
from distributed_tensorflow_framework_tpu.core import profiling, telemetry
from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.data import shard
from distributed_tensorflow_framework_tpu.data.pipeline import HostDataset
from distributed_tensorflow_framework_tpu.train import Trainer
from distributed_tensorflow_framework_tpu.train import hooks as hooks_lib

SLEEP_S = 0.3          # what the caller does between build() and train()
STARTUP_SPANS = [      # in the order they run, checkpoint directory or not
    "startup:runtime", "startup:dataset", "startup:writer",
    "startup:sample", "startup:init_state", "startup:make_step",
    "startup:eval_build", "startup:restore", "startup:snapshot_program",
    "startup:loop_entry", "snapshot", "infeed", "train_step"]


def _cfg(**train_overrides):
    base = {
        "name": "startup-test",
        "mesh": {"data": 8},
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.05},
        "train": dict({"total_steps": 20, "log_interval": 5}, **train_overrides),
    }
    cfg = load_config(base=base)
    cfg.data.async_infeed = False
    return cfg


class _Fetched(hooks_lib.BaseHook):
    def __init__(self):
        self.fetched = []

    def after_step(self, trainer, step, metrics) -> None:
        if metrics is not None:
            self.fetched.append((step, dict(metrics)))


@pytest.fixture(scope="module")
def startup_run(devices, tmp_path_factory):
    """One run of the real loop on a tiny config, with a ring too small
    for the run (it overflows several times over), an eval step built at
    start, and a caller that sleeps between ``build()`` and ``train()``."""
    out = tmp_path_factory.mktemp("startup")
    cfg = _cfg(eval_steps=2)
    cfg.trace.dump_dir = str(out / "dump")
    cfg.checkpoint.directory = str(out / "ckpt")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(profiling.StepTimer, "RING_SPANS", 48)
        before = time.time_ns()
        trainer = Trainer(cfg)
        assert trainer.timer.spans.maxlen == 48
        events = []
        trainer.writer.telemetry.add_listener(events.append)
        trainer.build()
        time.sleep(SLEEP_S)
        fetched = _Fetched()
        trainer.train(hooks=trainer.default_hooks() + [fetched])
    with open(out / "dump" / f"loop_timeline-{os.getpid()}.json") as fh:
        doc = json.load(fh)
    startup = [e for e in events if e["kind"] == telemetry.KIND_STARTUP]
    assert len(startup) == 1
    return {"trainer": trainer, "events": events, "doc": doc,
            "startup": startup[0]["extra"], "fetched": fetched.fetched,
            "before_ns": before,
            "events_path": str(out / "ckpt" / "events.jsonl")}


def test_startup_event_says_where_the_time_went(startup_run):
    extra = startup_run["startup"]
    phases = extra["phases_s"]
    assert list(phases) == STARTUP_SPANS
    assert all(v >= 0 for v in phases.values())
    assert extra["time_to_first_step_s"] == pytest.approx(
        sum(phases.values()) + extra["outside_s"], abs=1e-5)
    # compiling the forward and the step is where a tiny model's start goes
    assert phases["startup:init_state"] > 0.05 and phases["train_step"] > 0.05


def test_what_the_caller_did_is_outside_every_phase(startup_run):
    extra = startup_run["startup"]
    assert SLEEP_S <= extra["outside_s"] < SLEEP_S + 0.25
    # the sleep sits between ``restore``'s end and ``train()``'s first span
    spans = {s[0]: s for s in startup_run["doc"]["startup"]}
    gap_ns = spans["startup:snapshot_program"][2] - (
        spans["startup:restore"][2] + spans["startup:restore"][3])
    assert SLEEP_S * 1e9 <= gap_ns < (SLEEP_S + 0.25) * 1e9


def test_process_seconds_come_from_the_os(startup_run):
    extra = startup_run["startup"]
    age = profiling.process_age_s()
    # this process started before the trainer was built, and has aged since
    assert 0 < extra["process_s"] < age < extra["process_s"] + 3600


def test_startup_spans_are_in_the_file_after_the_ring_overflowed(startup_run):
    doc = startup_run["doc"]
    assert doc["schema"] == "dtf-loop-timeline/1"
    assert [s[0] for s in doc["startup"]] == STARTUP_SPANS
    # the ring holds the run's end only: no startup span is left in it
    assert len(doc["spans"]) == 48
    assert not any(s[0].startswith("startup:") for s in doc["spans"])
    # on the ring's clock (epoch nanoseconds), ordered, one level
    now = time.time_ns()
    assert all(startup_run["before_ns"] <= s[2] <= now for s in doc["startup"])
    for (_, _, s0, d0), (_, _, s1, _) in zip(doc["startup"], doc["startup"][1:]):
        assert s1 >= s0 + d0 - 200_000  # two clocks: allow 0.2 ms of skew
    # ``step`` is the step the loop started from; the first iteration is 1
    assert [s[1] for s in doc["startup"]] == [0] * 11 + [1, 1]


def test_startup_spans_enter_no_total(startup_run):
    step, first = startup_run["fetched"][0]
    assert step == 5
    assert not [k for k in first if k.startswith("time_startup")]
    assert "time_compile_ms" in first and "time_infeed_ms" in first
    # the ledger's ``startup`` bucket is one wall, construction to loop
    # entry, as before: the spans up to there and what lay between them
    extra = startup_run["startup"]
    in_loop = sum(extra["phases_s"][k]
                  for k in ("snapshot", "infeed", "train_step"))
    bucket = startup_run["trainer"].goodput.snapshot()["buckets"]["startup"]
    assert bucket == pytest.approx(
        extra["time_to_first_step_s"] - in_loop, abs=0.02)
    assert bucket > SLEEP_S


def test_startup_event_counts_what_jax_compiled(startup_run):
    comp = startup_run["startup"]["compile"]
    # the forward for the state, the step: compiled, here without a cache
    assert comp["xla_compiles"] >= 2 and comp["traces"] >= comp["xla_compiles"]
    assert comp["cache_hits"] == comp["cache_misses"] == 0
    assert comp["cache_load_s"] == 0.0
    for total, halves in (
            (comp["trace_s"] + comp["lower_s"], comp["trace_lower_s"]),
            (comp["xla_compile_s"] + comp["cache_load_s"], comp["xla_s"])):
        assert total > 0 and min(halves.values()) >= 0
        assert halves["inside"] + halves["outside"] == pytest.approx(
            total, abs=1e-4)
    # nothing compiled while the caller slept
    assert comp["xla_s"]["outside"] == 0.0
    # the file holds the events themselves, the step's compile among them
    step = [e for e in startup_run["doc"]["compiles"]
            if e[0] == "xla" and "train_step" in e[1]]
    assert len(step) == 1 and step[0][4] is None and 0 < step[0][5] <= step[0][3]
    under = [s for s in startup_run["doc"]["startup"] if s[0] == "train_step"][0]
    assert under[2] <= step[0][2] < under[2] + under[3]


def test_no_recompile_event_in_a_run_that_compiled_once(startup_run):
    health = [e["health"]["event"] for e in startup_run["events"]
              if e["kind"] == telemetry.KIND_HEALTH]
    assert "recompile" not in health
    counters = startup_run["trainer"].goodput.snapshot()["counters"]
    assert counters["recompiles"] == 1


def test_summary_keeps_and_prints_the_parts(startup_run):
    summary = telemetry.summarize_events(startup_run["events_path"])
    (s,) = summary["startups"]
    extra = startup_run["startup"]
    for key in telemetry.STARTUP_PARTS:
        assert s[key] == extra[key], key
    line = [ln for ln in telemetry.format_run_summary(summary).splitlines()
            if ln.strip().startswith("startup:")][0]
    top = sorted(extra["phases_s"], key=extra["phases_s"].get)[-3:]
    assert all(name in line for name in top), line
    assert f"outside {extra['outside_s']:.1f}s" in line
    assert (f"0 loaded, {extra['compile']['xla_compiles']} compiled"
            in line), line


def test_a_summary_of_an_older_event_is_as_it_was(tmp_path):
    path = tmp_path / "events.jsonl"
    ev = telemetry.make_event(
        telemetry.KIND_STARTUP, run_id="r", step=1,
        time_to_first_step_s=12.5, restored_step=None)
    path.write_text(json.dumps(ev) + "\n")
    summary = telemetry.summarize_events(str(path))
    assert summary["startups"] == [{
        "step": 1, "time_to_first_step_s": 12.5, "restored_step": None}]
    assert "  startup: 12.5s to first step (fresh)" in \
        telemetry.format_run_summary(summary).splitlines()


# ------------------------------------------------------------ compile log --
def _fresh(scale: float):
    """A jitted function nobody has traced: one trace, one lowering, one
    backend compile (``lax`` primitives: ``jnp`` functions are jitted
    themselves and would count their own traces)."""
    def startup_timeline_probe(x):
        return jax.lax.mul(jax.lax.add(x, x), jnp.float32(scale))
    return jax.jit(startup_timeline_probe)


def test_a_fresh_function_is_one_trace_and_one_compile():
    log = profiling.compile_log()
    x = jnp.ones((3, 5), jnp.float32)  # made before the counts are read
    before, logged, t0 = log.counts(), log.logged, time.time_ns()
    probe = _fresh(3.0)
    np.testing.assert_allclose(probe(x), 6.0)
    after = log.counts()
    assert after["traces"] == before["traces"] + 1
    assert after["xla_compiles"] == before["xla_compiles"] + 1
    assert after["cache_hits"] == before["cache_hits"]
    assert after["cache_misses"] == before["cache_misses"]
    assert log.logged == logged + 3
    entries = log.since(t0)
    assert [e[0] for e in entries] == ["trace", "lower", "xla"]
    assert all("startup_timeline_probe" in e[1] for e in entries)
    now = time.time_ns()
    for (kind, _, s0, d0, hit, xla_ns), (_, _, s1, _, _, _) in zip(
            entries, entries[1:] + [(None, None, now, 0, None, 0)]):
        assert t0 <= s0 <= s0 + d0 <= s1 + 1000 and hit is None
        assert xla_ns == (d0 if kind == "xla" else 0)
    # a second call of the same function traces and compiles nothing
    np.testing.assert_allclose(probe(x), 6.0)
    assert log.logged == logged + 3 and log.since(now) == []


TRACE, LOWER, XLA = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")


def _feed(log, *events):
    """``(event, fun_name, begin_s, end_s)`` as JAX would send them: the
    scalar where one begins, the time span where it ends, in time order."""
    t0 = 1_700_000_000.0
    marks = sorted(
        [(b, 1, e, name, ev) for ev, name, b, e in events]
        + [(e, 0, b, name, ev) for ev, name, b, e in events],
        key=lambda m: (m[0], m[1], -m[2]))
    for at, begins, other, name, ev in marks:
        if begins:
            log._on_begin(ev, t0 + at, fun_name=name)
        else:
            log._on_span(ev, t0 + other, t0 + at, fun_name=name)


def test_nested_events_count_each_second_once():
    log = profiling.CompileLog()  # not registered: fed by hand
    # a trace of 1.0 s that holds a trace of 0.25 s, which holds a small
    # compile of 0.125 s; then a lowering beside them
    _feed(log, (TRACE, "outer", 0.0, 1.0), (TRACE, "inner", 0.125, 0.375),
          (XLA, "jit(const)", 0.25, 0.375), (LOWER, "jit(outer)", 1.0, 1.5))
    log._on_begin("/jax/some/other/event", 1.0)
    log._on_span("/jax/some/other/event", 0.0, 9.0)
    c = log.counts()
    assert c["traces"] == 2 and c["xla_compiles"] == 1 and log.logged == 4
    assert c["xla_compile_s"] == pytest.approx(0.125, abs=1e-6)
    assert c["trace_s"] == pytest.approx(0.875, abs=1e-6)
    assert c["lower_s"] == pytest.approx(0.5, abs=1e-6)
    # the list takes the outermost: the trace stands for what it held,
    # and says how much of itself was the backend's
    outer, lowered = log.since(0)
    assert (outer[0], outer[1], lowered[0], lowered[1]) == (
        "trace", "outer", "lower", "jit(outer)")
    assert outer[3] == pytest.approx(1e9, abs=1e3)
    assert outer[5] == pytest.approx(0.125e9, abs=1e3) and lowered[5] == 0
    assert outer[3] + lowered[3] == pytest.approx(
        1e9 * (c["trace_s"] + c["lower_s"] + c["xla_compile_s"]), abs=1e3)


def test_the_log_is_bounded_and_the_nested_take_no_room():
    class Small(profiling.CompileLog):
        LOG_ENTRIES = 4

    log = Small()
    _feed(log, *[(TRACE, f"f{i}", 1.0 + i, 1.5 + i) for i in range(10)])
    assert log.logged == 10 and log.counts()["traces"] == 10
    assert [e[1] for e in log.since(0)] == ["f6", "f7", "f8", "f9"]
    # ``since`` goes by when an entry ended: f8 ended at 9.5 s
    t0_ns = 1_700_000_000 * 10**9
    assert [e[1] for e in log.since(t0_ns + int(9.5e9))] == ["f8", "f9"]
    assert log.since(t0_ns + int(11e9)) == []
    # a trace that holds a hundred others evicts nothing that was there
    _feed(log, (TRACE, "step", 20.0, 30.0),
          *[(TRACE, f"n{i}", 20.01 + 0.05 * i, 20.05 + 0.05 * i)
            for i in range(100)])
    assert [e[1] for e in log.since(0)] == ["f7", "f8", "f9", "step"]
    c = log.counts()
    assert c["traces"] == 111
    assert c["trace_s"] == pytest.approx(10 * 0.5 + 10.0, abs=1e-5)


def test_threads_keep_their_own_nesting():
    import threading

    log = profiling.CompileLog()
    t0 = 1_700_000_000.0
    log._on_begin(TRACE, t0, fun_name="main")
    # another thread compiles while this one traces: not a part of it
    other = threading.Thread(target=_feed, args=(
        log, (XLA, "jit(other)", 0.25, 0.75)))
    other.start()
    other.join(timeout=30)
    assert not other.is_alive()
    log._on_span(TRACE, t0, t0 + 1.0, fun_name="main")
    c = log.counts()
    assert c["trace_s"] == pytest.approx(1.0, abs=1e-6)
    assert c["xla_compile_s"] == pytest.approx(0.5, abs=1e-6)
    (theirs, ours) = log.since(0)
    assert (theirs[1], ours[1], ours[5]) == ("jit(other)", "main", 0)
    assert theirs[5] == theirs[3] == pytest.approx(0.5e9, abs=1e3)


@pytest.fixture
def compile_cache(tmp_path):
    """JAX's persistent cache on, in a directory of the test's own, with
    no threshold: every compile is worth an entry. (The suite runs with
    the cache off: tests/conftest.py.)"""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    for name, value in zip(names, (True, str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(name, value)
    cc.reset_cache()
    try:
        yield
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        cc.reset_cache()


def test_a_cache_load_is_told_from_a_compile(compile_cache):
    log = profiling.compile_log()
    x = jnp.ones((7,), jnp.float32)
    before, t0 = log.counts(), time.time_ns()
    first = _fresh(5.0)(x)
    mid = log.counts()
    # compiled, and written: a miss
    assert mid["xla_compiles"] == before["xla_compiles"] + 1
    assert mid["cache_misses"] == before["cache_misses"] + 1
    assert mid["cache_hits"] == before["cache_hits"]
    jax.clear_caches()  # the process forgets; the directory does not
    second = _fresh(5.0)(x)
    after = log.counts()
    np.testing.assert_array_equal(first, second)
    assert after["cache_hits"] == mid["cache_hits"] + 1
    assert after["xla_compiles"] == mid["xla_compiles"]
    assert after["cache_misses"] == mid["cache_misses"]
    assert after["xla_compile_s"] == mid["xla_compile_s"]
    xla = [e for e in log.since(t0) if e[0] == "xla"]
    assert [e[4] for e in xla] == [False, True]
    # the load's seconds are its span's, once: the retrieval time that
    # JAX reports beside it lies inside that span
    assert after["cache_load_s"] - mid["cache_load_s"] == pytest.approx(
        xla[1][3] * 1e-9, abs=1e-9)
    assert xla[1][5] == xla[1][3] > 0


def test_a_small_program_is_neither_hit_nor_miss(compile_cache):
    # under the cache's size threshold: compiled on every start, never
    # written, so JAX sends neither event
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 1 << 30)
    log = profiling.compile_log()
    x = jnp.ones((9,), jnp.float32)
    before, t0 = log.counts(), time.time_ns()
    _fresh(7.0)(x)
    after = log.counts()
    assert after["xla_compiles"] == before["xla_compiles"] + 1
    assert after["cache_misses"] == before["cache_misses"]
    assert after["cache_hits"] == before["cache_hits"]
    assert [e[4] for e in log.since(t0) if e[0] == "xla"] == [None]


def test_two_trainers_register_one_listener_set(devices):
    def ours(listeners):
        return [fn for fn in listeners
                if isinstance(getattr(fn, "__self__", None),
                              profiling.CompileLog)]

    first, second = Trainer(_cfg()), Trainer(_cfg())
    assert first.timer.compiles is second.timer.compiles \
        is profiling.compile_log()
    # (jax.monitoring registers; only jax._src.monitoring lists)
    assert len(ours(monitoring.get_event_time_span_listeners())) == 1
    assert len(ours(monitoring.get_event_listeners())) == 1
    assert len(ours(monitoring.get_scalar_listeners())) == 1
    assert len(ours(monitoring.get_event_duration_listeners())) == 0
    # each reports what was logged since it was made, not the process's all
    first.build()
    assert first.timer.compile_entries()
    third = Trainer(_cfg())
    assert third.timer.compile_entries() == []
    assert third.timer.compile_summary()["traces"] == 0


# ------------------------------------------------------- recompile event --
_dataset_ids = itertools.count()


def _shrinking_dataset(at_batch: int) -> str:
    """Image batches of 64 rows that turn into batches of 32 from the
    ``at_batch``-th on: the jitted step meets a shape it has not seen."""
    name = f"startup_timeline_shrinking_{next(_dataset_ids)}"

    @data.register_dataset(name)
    def factory(config, process_index, process_count, *, train=True):
        def make_iter(state):
            state.setdefault("i", 0)
            while True:
                state["i"] += 1
                rows = 64 if state["i"] < at_batch else 32
                yield {"image": np.zeros((rows, 28, 28, 1), np.float32),
                       "label": np.zeros((rows,), np.int32)}

        return HostDataset(
            make_iter, initial_state={"i": 0},
            element_spec={"image": ((64, 28, 28, 1), np.float32),
                          "label": ((64,), np.int32)},
            repartition=shard.REPARTITION_INVARIANT)

    return name


def test_a_compile_in_the_loop_has_a_name(devices, monkeypatch):
    # any compile counts here: a tiny step compiles in tens of milliseconds
    monkeypatch.setattr(profiling, "SLOW_FLOOR_MS", 0.0)
    cfg = _cfg(total_steps=10)
    cfg.data.name = _shrinking_dataset(at_batch=5)
    trainer = Trainer(cfg)
    events = []
    trainer.writer.telemetry.add_listener(events.append)
    trainer.build()
    trainer.train()
    found = [e for e in events
             if (e.get("health") or {}).get("event") == "recompile"]
    assert len(found) == 1, found
    ev = found[0]
    assert ev["kind"] == telemetry.KIND_HEALTH and ev["step"] == 5
    h = ev["health"]
    assert h["step"] == 5 and h["under"] == "train_step"
    assert "train_step" in h["fun_name"]
    assert h["cache_hit"] is None and h["xla_ms"] > 0 and h["trace_ms"] > 0
    # the loop's own counter counts what the loop asked for, as before
    counters = trainer.goodput.snapshot()["counters"]
    assert counters["recompiles"] == 1
    # and the event is in the file's compile log, under step 5's dispatch
    step5 = [s for s in trainer.timer.spans
             if s[0] == "train_step" and s[1] == 5][0]
    again = [e for e in trainer.timer.compile_entries()
             if e[0] == "xla" and step5[2] <= e[2] < step5[2] + step5[3]]
    assert len(again) == 1 and again[0][1] == h["fun_name"]


def test_a_fast_compile_is_no_event(devices, monkeypatch):
    monkeypatch.setattr(profiling, "SLOW_FLOOR_MS", 1e9)
    cfg = _cfg(total_steps=10)
    cfg.data.name = _shrinking_dataset(at_batch=5)
    trainer = Trainer(cfg)
    events = []
    trainer.writer.telemetry.add_listener(events.append)
    trainer.train()
    assert not [e for e in events
                if (e.get("health") or {}).get("event") == "recompile"]
    # the check has read the log all the same: nothing waits to be told
    assert trainer._compiles_seen == trainer.timer.compiles.logged
