"""One run of one cell: set-up, the window, the result.

The system under test is the program's normal path: ``Trainer``
(``train/loop.py``) built from the shipped YAML plus the cell's
overrides, its ``StepBuilder``, optimizer, ``prefetch_to_device`` infeed
and dispatch-ahead, its default hooks. The benchmark adds a dataset
(through ``data.register_dataset``), hooks that watch, and the stop.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import time

from benchmarks.harness import build, check, manifest, records
from benchmarks.harness.window import (
    TimedHook, WindowHook, annotate_infeed)

WARMUP_STEPS = 3
OUT_DIR = ".bench_out"           # in the checkout, git-ignored


class CompileWatch:
    """Counts compilations by JAX's own monitoring events, so that one
    inside the window cannot hide: every backend compile and every
    persistent-cache load reports a duration event."""

    def __init__(self):
        import jax.monitoring

        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, seconds: float, **_):
        if "compil" in name or "cache" in name:
            self.events.append((time.perf_counter(), name, seconds))

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for t, name, _ in self.events
                   if t0 <= t <= t1 and name.endswith(
                       ("backend_compile_duration",
                        "cache_retrieval_time_sec")))

    def totals(self) -> dict:
        out: dict = {}
        for _, name, sec in self.events:
            n, s = out.get(name, (0, 0.0))
            out[name] = (n + 1, s + sec)
        return out


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def run_cell(cell, *, seed: int, seconds: float, trace: bool, root: str,
             process_t0: float, devices, peaks: dict,
             extra_overrides: tuple = (), warmup_steps: int = WARMUP_STEPS):
    """Returns ``(result_line_dict, detail_dict)``."""
    import jax

    from distributed_tensorflow_framework_tpu.core import supervision
    from distributed_tensorflow_framework_tpu.core.mesh import (
        initialize_runtime)
    from distributed_tensorflow_framework_tpu.core import telemetry
    from distributed_tensorflow_framework_tpu.train import Trainer

    on_chip = devices[0].platform == "tpu"
    out_dir = os.path.join(root, OUT_DIR, cell.name)
    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    watch = CompileWatch()
    marks: dict = {}

    def mark(name):
        marks[name] = time.perf_counter() - process_t0

    # -- traffic: the pool, from the seed, handed to the program ----------
    pool = build.make_pool(cell, root, seed=seed)
    dataset_name = build.register_pool(pool)
    mark("traffic_made")
    flops = manifest.load_family(root, "flops", cell.config["flops"])
    hparams = {**cell.config["published"], **cell.config["reference_hparams"]}
    per_batch = [flops.train_flops(b, hparams) for b in pool.batches]
    flops_per_unit = sum(per_batch) / sum(pool.real_units)
    attention_work = None
    if hasattr(flops, "attention_kernel_work"):
        works = [flops.attention_kernel_work(
            b, hparams, cell.workload["per_chip_batch"]) for b in pool.batches]
        attention_work = {k: sum(w[k] for w in works) / len(works)
                          for k in works[0]}

    # -- the program, as a user starts it ----------------------------------
    load = build.config_loader(
        cell, root, seed=seed, dataset_name=dataset_name,
        extra=(f"trace.dump_dir={out_dir}", *extra_overrides))
    config = load()
    runtime = initialize_runtime(config.mesh, devices=devices)
    trainer = Trainer(config, runtime=runtime)
    startup: dict = {}
    trainer.writer.telemetry.add_listener(
        lambda ev: startup.update(ev.get("extra") or {})
        if ev.get("kind") == telemetry.KIND_STARTUP else None)
    trainer.build()
    mark("trainer_built")

    # The timed step's compiled form: its footprint, and its HLO text for
    # the scope map and the multi-chip reading. Same jitted function and
    # arguments as the loop's first dispatch, which then reuses it.
    compiled = trainer.train_step.lower(trainer.state, trainer._sample).compile()
    step_memory = records.step_memory(compiled)
    hlo_text = compiled.as_text() if trace or cell.chips > 1 else None
    mark("step_compiled")
    verdicts = {}
    if cell.chips > 1:
        verdicts["multichip"] = check.multichip_facts(
            hlo_text, trainer._sample, cell.chips,
            cell.workload["per_chip_batch"], on_chip)

    # -- (1) the program's step against the plain reference ----------------
    sample = check.sample_rows(pool, int(cell.workload["check_rows"]))
    program = check.program_step_values(load, trainer.mesh, trainer.state,
                                        sample)
    reference = check.reference_values(
        manifest.load_family(root, "reference", cell.config["reference"]),
        trainer.state.params, sample,
        {**hparams, "label_smoothing": config.train.label_smoothing})
    verdicts["reference"] = check.compare(
        program, reference, cell.config["check_tolerance"])
    mark("reference_checked")

    # -- the window ----------------------------------------------------------
    hook = WindowHook(
        seconds=seconds, warmup_steps=warmup_steps, pool=pool,
        trace_dir=trace_dir if trace else None,
        trace_steps=int(cell.workload["trace_steps"]) if trace else 0,
        host_tracer_level=int(cell.workload.get("trace_host_level", 1)))
    hooks = [TimedHook(h, hook) for h in trainer.default_hooks()] + [hook]
    if trace:
        annotate_infeed(trainer)
    supervision.install_sigterm_handler()
    raised = None
    try:
        trainer.train(hooks=hooks)
    except FloatingPointError as e:      # NaNGuardHook: a non-finite loss
        raised = e
    finally:
        supervision.reset_preemption()
    if not hook.closed:
        raise RuntimeError(
            f"the loop ended before the window closed ({raised!r})")
    mark("loop_ended")
    w = hook.summary(cell.chips)
    counters = trainer.goodput.snapshot()["counters"]
    compiles_in_window = watch.count_between(hook.t_open, hook.t_close)
    verdicts["losses"] = check.window_losses_ok(
        w["losses"], cell.config["first_loss"])
    verdicts["no_compile_in_window"] = {
        "ok": compiles_in_window == 0 and counters.get("recompiles", 0) == 1,
        "jax_compile_events_in_window": compiles_in_window,
        "program_recompiles_counter": counters.get("recompiles", 0)}
    correct = raised is None and all(v["ok"] for v in verdicts.values())

    # -- metrics -----------------------------------------------------------
    rec = records.RunRecords(
        cell=cell, window=w, startup=startup,
        step_memory=step_memory, peaks=peaks,
        model_flops_per_unit=flops_per_unit, attention_work=attention_work)
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak_bytes(devices),
    }
    result = {"correct": bool(correct), "attempted": int(w["steps"]),
              "failed": int(w["nonfinite_losses"] + (raised is not None)),
              "metrics": {}, "device": device}
    detail = {"cell": cell.name, "seed": seed, "marks_s": marks,
              "setup_s": hook.t_open - process_t0,
              "verdicts": verdicts, "window": {
                  k: v for k, v in w.items()
                  if k not in ("losses", "step_ms_blocks")},
              "losses": w["losses"], "pool": pool.facts,
              "step_memory": step_memory, "compile_events": watch.totals(),
              "goodput_counters": counters,
              "memory_stats": [d.memory_stats() for d in devices][:1]}
    if trace:
        from benchmarks.harness import hlo_scopes, trace_reduce

        with open(os.path.join(out_dir, "step.hlo.txt"), "w") as fh:
            fh.write(hlo_text)
        red = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir)),
            hlo_scopes.HloScopes(hlo_text))
        if not red.steps:
            red.steps = w["traced_steps"]
        rec.trace = red
        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        result["breakdown"] = trace_reduce.breakdown(red)
        detail["trace"] = {"category_s": red.category_s,
                           "kernel_s": red.kernel_s, "steps": red.steps,
                           "devices": red.devices,
                           "collective_s": red.collective_s,
                           "collective_exposed_s": red.collective_exposed_s}
        for m in cell.per_layer:
            value = manifest.load_reader(root, m["name"]).read(rec)
            if value is not None and math.isfinite(value):
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        # A pool counts in "tokens" or "images" (or whatever a later
        # generator counts in); the cell's throughput is named after it.
        values = {f"{pool.unit}_per_s_chip": w["rate_per_chip"],
                  "setup_s": detail["setup_s"]}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    with open(os.path.join(out_dir, f"detail_trace{int(trace)}.json"),
              "w") as fh:
        json.dump(detail, fh, indent=1, default=str)
    return result, detail


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest chip, as the runtime's allocator reports
    it: the peak of live arrays plus the peak of the region it reserves
    for compiled programs' temporaries. (On this runtime
    ``peak_bytes_in_use`` alone counts live arrays only: 2.76 GB beside a
    BERT step whose temporaries take 9.44 GB of ``bytes_reserved``.)"""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak
