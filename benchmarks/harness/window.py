"""The measured window, as hooks on the program's own train loop.

The loop runs exactly as a user's does; these hooks only watch it. The
window opens and closes on a device sync at a step boundary
(``block_until_ready`` on the train state), so the steps counted between
the two syncs are steps the device completed, and steps are counted, not
timed one by one. When the window's time is up the hook closes it and
stops the job the way a scheduler would: SIGTERM to itself, which the
program's graceful-preemption flag turns into a stop at the next step
boundary.
"""

from __future__ import annotations

import math
import os
import signal
import time

import jax


class TimedHook:
    """One of the program's hooks, with its ``after_step`` time summed:
    the benchmark's span around the call into the hook layer."""

    def __init__(self, inner, clock):
        self.inner = inner
        self._clock = clock
        self._span = f"bench:hook:{type(inner).__name__}"

    def on_start(self, trainer) -> None:
        self.inner.on_start(trainer)

    def after_step(self, trainer, step, metrics) -> None:
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(self._span):
                self.inner.after_step(trainer, step, metrics)
        finally:
            self._clock.hook_s += time.perf_counter() - t0

    def on_end(self, trainer) -> None:
        self.inner.on_end(trainer)


def annotate_infeed(trainer) -> None:
    """In a traced run, put the loop's pull from the infeed on the
    trace's clock (the program annotates only its ``train_step``
    dispatch), so that an idle gap can be named ``bench:infeed``."""
    pull = trainer._next_batch

    def annotated(infeed):
        with jax.profiler.TraceAnnotation("bench:infeed"):
            return pull(infeed)

    trainer._next_batch = annotated


class WindowHook:
    """Opens the window after ``warmup_steps``, keeps the books inside
    it, optionally traces ``trace_steps`` steps, closes after
    ``seconds`` and asks the loop to stop."""

    def __init__(self, *, seconds: float, warmup_steps: int, pool,
                 trace_dir: str | None = None, trace_steps: int = 0,
                 trace_after_steps: int = 20, host_tracer_level: int = 1):
        self.seconds = float(seconds)
        self.warmup_steps = int(warmup_steps)
        self.pool = pool
        self.trace_dir = trace_dir
        self.trace_steps = int(trace_steps)
        self.trace_after_steps = int(trace_after_steps)
        self.host_tracer_level = int(host_tracer_level)
        self.hook_s = 0.0            # the program's hooks, summed (TimedHook)
        self.t_open = self.t_close = None
        self.step_open = self.step_close = None
        self.units = 0               # real tokens / images of counted steps
        self.losses: list[float] = []
        self.phase_sums: dict[str, float] = {}   # StepTimer means x steps
        self.fetch_wait_ms: list[float] = []
        self.phase_steps = 0
        self.fetches: list[tuple[float, int]] = []  # (t, step) at loop syncs
        self.trace_t = [None, None]  # start_trace returned .. stop_trace returned
        self.trace_sync_t = None     # the sync that ended the traced steps
        self.trace_step = [None, None]
        self.trace_units = 0
        self.hook_s_open = self.hook_s_close = 0.0

    # -- the program's Hook protocol --------------------------------------
    def on_start(self, trainer) -> None:
        pass

    def on_end(self, trainer) -> None:
        if self._tracing():  # the loop ended inside the traced stretch
            jax.profiler.stop_trace()
            self.trace_t[1] = time.perf_counter()
            self.trace_step[1] = trainer.host_step

    @property
    def opened(self) -> bool:
        return self.t_open is not None

    @property
    def closed(self) -> bool:
        return self.t_close is not None

    def _tracing(self) -> bool:
        return self.trace_t[0] is not None and self.trace_t[1] is None

    def _sync(self, trainer) -> float:
        jax.block_until_ready(trainer.state)
        return time.perf_counter()

    def after_step(self, trainer, step, metrics) -> None:
        if self.closed:
            return
        if not self.opened:
            if step >= self.warmup_steps:
                self.hook_s_open = self.hook_s
                self.step_open = step
                self.t_open = self._sync(trainer)
            return
        # Which pool batch this step consumed: the program's own consumed-
        # batch ordinal, which rides the iterator snapshot beside the batch.
        ordinal = int(trainer.data_ckpt_state["consumed"])
        n = self.pool.real_units[(ordinal - 1) % len(self.pool.real_units)]
        self.units += n
        if self._tracing():
            self.trace_units += n
        if metrics is not None:
            self.fetches.append((time.perf_counter(), step))
            self.losses.append(float(metrics["loss"]))
            steps = step - (self.fetches[-2][1] if len(self.fetches) > 1
                            else self.step_open)
            for phase in ("infeed", "dispatch"):
                # StepTimer means over the block since the loop's last
                # fetch; these two phases run once per step, so mean x
                # steps is the block's total. (backpressure and
                # metrics_fetch are waits on the device, called an
                # unrecorded number of times: not host cost, not summed.)
                self.phase_sums[phase] = self.phase_sums.get(phase, 0.0) \
                    + float(metrics.get(f"time_{phase}_ms", 0.0)) * steps
            self.fetch_wait_ms.append(
                float(metrics.get("time_metrics_fetch_ms", 0.0)))
            self.phase_steps += steps
        if self.trace_dir and self.trace_steps > 0:
            if self.trace_t[0] is None and \
                    step >= self.step_open + self.trace_after_steps:
                self._sync(trainer)
                os.makedirs(self.trace_dir, exist_ok=True)
                # Device ops and annotations only: the Python call tracer
                # slows the host several-fold. Host level 1 keeps the
                # annotations that name idle gaps; a cell sets
                # ``trace_host_level: 0`` in its workload file where even
                # that starves it: the runtime records one span per chunk
                # of every host-side transpose, 3M for ten image batches,
                # which made the ResNet infeed 30x slower (PR 22).
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = self.host_tracer_level
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=options)
                self.trace_t[0] = time.perf_counter()
                self.trace_step[0] = step
                return
            if self._tracing() and \
                    step >= self.trace_step[0] + self.trace_steps:
                self._sync(trainer)
                self.trace_sync_t = time.perf_counter()
                jax.profiler.stop_trace()
                self.trace_t[1] = time.perf_counter()
                self.trace_step[1] = step
                return
        # The traced stretch (its two syncs, writing the trace out) is the
        # instrument's time: the window runs that much longer instead.
        traced = (self.trace_t[1] - self.trace_t[0]
                  if self.trace_t[1] is not None else 0.0)
        if not self._tracing() and \
                time.perf_counter() - self.t_open - traced >= self.seconds:
            self.step_close = step
            self.hook_s_close = self.hook_s
            self.t_close = self._sync(trainer)
            os.kill(os.getpid(), signal.SIGTERM)

    # -- what the run reads afterwards ------------------------------------
    def summary(self, chips: int) -> dict:
        """Counts and times of the window. With a traced stretch, the
        rate is taken over the rest of the window: tracing and its two
        syncs are the instrument's cost, not the program's."""
        steps = self.step_close - self.step_open
        seconds = self.t_close - self.t_open
        units = self.units
        rate_steps, rate_seconds, rate_units = steps, seconds, units
        if self.trace_t[1] is not None:
            rate_steps -= self.trace_step[1] - self.trace_step[0]
            rate_seconds -= self.trace_t[1] - self.trace_t[0]
            rate_units -= self.trace_units
        blocks = [
            1e3 * (t1 - t0) / (s1 - s0)
            for (t0, s0), (t1, s1) in zip(self.fetches, self.fetches[1:])
            if not self._overlaps_trace(t0, t1)]
        blocks.sort()
        return {
            "steps": steps, "seconds": seconds, "units": units,
            "unit": self.pool.unit,
            "rate_per_chip": rate_units / rate_seconds / chips,
            "rate_steps": rate_steps, "rate_seconds": rate_seconds,
            "step_ms_blocks": blocks,
            "losses": list(self.losses),
            "nonfinite_losses": sum(1 for x in self.losses
                                    if not math.isfinite(x)),
            "phase_ms_step": {k: v / max(self.phase_steps, 1)
                              for k, v in self.phase_sums.items()},
            "fetch_wait_ms": list(self.fetch_wait_ms),
            "hooks_ms_step": 1e3 * (self.hook_s_close - self.hook_s_open)
            / max(steps, 1),
            "traced_steps": (self.trace_step[1] - self.trace_step[0]
                             if self.trace_t[1] is not None else 0),
            "traced_seconds": (self.trace_sync_t - self.trace_t[0]
                               if self.trace_sync_t is not None else 0.0),
        }

    def _overlaps_trace(self, t0: float, t1: float) -> bool:
        a, b = self.trace_t
        return a is not None and b is not None and t0 < b and t1 > a
