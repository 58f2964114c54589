"""Gang lifecycle for the multi-process runtime — the pure library half
of the cluster supervisor (``scripts/train_cluster.py``).

``jax.distributed`` gangs fail as a unit: one worker crash or hang wedges
every collective in the job, so recovery decisions are *cluster*-level —
who is stale, who failed to rejoin, what mesh still fits the survivors,
and when it is safe for anyone to exit.  This module holds those
decisions as small, stdlib-only, thread-free functions so the supervisor
script stays a poll loop and tier-1 tests can drive every branch without
spawning a gang:

* ``heartbeat_name`` / ``heartbeat_path`` — the per-worker heartbeat
  file contract shared with ``train/loop.py`` (``heartbeat-p<i>.json``
  when the gang has more than one process, the legacy single-process
  ``heartbeat.json`` otherwise).
* ``worker_env`` — the ``jax.distributed`` discovery env for one worker
  (coordinator address / process id / virtual-device mask), also used by
  ``scripts/launch_local_cluster.py``.
* ``GangBreaker`` — crash-loop breaking keyed on (worker, failure
  signature): one flaky host trips its own breaker instead of burning
  the shared attempt budget, wrapping
  :class:`core.supervision.CrashLoopBreaker` per process id.
* ``decide_rejoin`` — which workers failed to rejoin the gang within
  ``cluster.rejoin_timeout_s`` while their peers did.
* ``decide_refit`` — the gang-level rc-84 path: fit the mesh to the
  surviving process count via :func:`core.supervision.fit_axis_sizes`
  and preserve the effective batch via ``rescale_for_devices``.
* ``exit_barrier`` — coordinator-led exit barrier: no worker returns
  from training until the chief's async-checkpoint commit record for
  the final step is durable in the manifest.

Everything importable without JAX — the supervisor process must stay
light enough to relaunch children in a tight loop.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import time

from distributed_tensorflow_framework_tpu.core import supervision, tracing

# Single-process runs keep the legacy name so scripts/train_resilient.py
# and every existing drill stay untouched.
SINGLE_HEARTBEAT_NAME = "heartbeat.json"


class ClusterSpecError(ValueError):
    """A gang cannot be formed from the requested parameters — e.g. a
    worker index outside the process count or a mesh no surviving
    subset of devices can satisfy."""


class ExitBarrierTimeoutError(RuntimeError):
    """The exit barrier timed out: the manifest never showed a durable
    commit record for the final step within
    ``cluster.exit_barrier_timeout_s``.  Exiting anyway would let this
    host drop its shard of an in-flight async save, so the barrier
    raises instead of returning."""


# ---------------------------------------------------------------------------
# Heartbeat file contract
# ---------------------------------------------------------------------------

def heartbeat_name(process_index: int, process_count: int) -> str:
    """Per-worker heartbeat filename inside the checkpoint directory.

    Every member of a multi-process gang (chief included) writes its own
    ``heartbeat-p<i>.json`` so the supervisor can tell a hung worker from
    a hung gang; single-process runs keep ``heartbeat.json``.
    """
    if process_count <= 1:
        return SINGLE_HEARTBEAT_NAME
    if not 0 <= process_index < process_count:
        raise ClusterSpecError(
            f"process_index {process_index} outside gang of {process_count}")
    return f"heartbeat-p{process_index}.json"


def heartbeat_path(ckpt_dir: str, process_index: int,
                   process_count: int) -> str:
    """Absolute path of one worker's heartbeat file."""
    return os.path.join(ckpt_dir, heartbeat_name(process_index, process_count))


# ---------------------------------------------------------------------------
# Worker environment (the jax.distributed discovery path)
# ---------------------------------------------------------------------------

# The jax.distributed discovery triple, as PUBLIC names: data/shard.py
# derives each host's deterministic shard assignment from the same env
# the gang supervisor writes (worker_env below), so data-shard identity
# and gang identity cannot drift apart.
ENV_COORDINATOR = "JAX_COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "JAX_NUM_PROCESSES"
ENV_PROCESS_ID = "JAX_PROCESS_ID"

_DISCOVERY_VARS = (ENV_COORDINATOR, ENV_NUM_PROCESSES, ENV_PROCESS_ID)


def worker_env(
    base_env: dict[str, str],
    *,
    coordinator_port: int,
    num_processes: int,
    process_id: int,
    devices_per_proc: int,
    coordinator_host: str = "127.0.0.1",
    trace_ctx: str | None = None,
) -> dict[str, str]:
    """Environment for one gang worker on the local discovery path.

    Sets the ``jax.distributed`` discovery triple, forces the CPU
    platform (this is the localhost simulation path) and masks
    ``devices_per_proc`` virtual devices per process.  A gang refit down
    to one process strips the discovery vars entirely so the survivor
    initializes as a plain single-process run.

    ``trace_ctx`` is an encoded :class:`core.tracing.SpanContext` (the
    supervisor's attempt span): it rides ``DTF_TRACE_CTX`` so every
    worker's ``worker.run`` span parents on the same attempt and the
    whole gang stitches into one trace tree.  ``None`` leaves whatever
    ``base_env`` carried untouched (the supervisor usually injects the
    var into the shared base env once per attempt).
    """
    if not 0 <= process_id < num_processes:
        raise ClusterSpecError(
            f"process_id {process_id} outside gang of {num_processes}")
    env = dict(base_env)
    if num_processes > 1:
        env[ENV_COORDINATOR] = f"{coordinator_host}:{coordinator_port}"
        env[ENV_NUM_PROCESSES] = str(num_processes)
        env[ENV_PROCESS_ID] = str(process_id)
    else:
        for key in _DISCOVERY_VARS:
            env.pop(key, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = supervision.mask_host_device_count(
        env.get("XLA_FLAGS", ""), devices_per_proc)
    if trace_ctx is not None:
        env[tracing.TRACE_CTX_ENV] = trace_ctx
    return env


# ---------------------------------------------------------------------------
# Gang capability probe
# ---------------------------------------------------------------------------

# Failure signatures of a backend that can FORM a gang (coordinator
# handshake succeeds, device discovery works) but cannot COMPILE a
# computation spanning processes.  jaxlib's stock CPU backend is the
# canonical case: jax.distributed.initialize() succeeds and every worker
# sees the global device count, then the first jit over a global array
# raises INVALID_ARGUMENT.
GANG_UNSUPPORTED_SIGNS = (
    "multiprocess computations aren't implemented",
    "multi-process computations are not supported",
    "collectives are not implemented",
)

# One worker of the probe gang: init distributed from the discovery env
# (same triple worker_env sets) and run the smallest computation that
# actually spans processes — a jit'd sum over a globally-sharded array.
# probe_gang launches it under worker_env, which sets JAX_PLATFORMS=cpu.
_PROBE_WORKER = """\
import os
import jax
jax.distributed.initialize(
    coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
    num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
    process_id=int(os.environ["JAX_PROCESS_ID"]),
)
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
devices = np.array(jax.devices())
mesh = Mesh(devices, ("d",))
arr = jax.make_array_from_callback(
    (devices.size,), NamedSharding(mesh, PartitionSpec("d")),
    lambda idx: np.ones((1,), np.float32))
total = jax.jit(lambda a: a.sum(),
                out_shardings=NamedSharding(mesh, PartitionSpec()))(arr)
assert float(total) == devices.size, float(total)
print("GANG_PROBE_OK", flush=True)
"""


def is_gang_unsupported(detail: str) -> bool:
    """Does a probe failure match the known this-backend-cannot-do-gangs
    signatures (vs. an environmental flake worth investigating)?"""
    low = detail.lower()
    return any(sign in low for sign in GANG_UNSUPPORTED_SIGNS)


def probe_gang(
    *,
    procs: int = 2,
    devices_per_proc: int = 1,
    timeout_s: float = 120.0,
) -> tuple[bool, str]:
    """Can this host run a REAL ``procs``-process ``jax.distributed``
    gang with a cross-process computation?  Returns ``(ok, detail)``.

    The gang drills (tests/test_cluster_drill.py) gate on this: stub-level
    supervisor behavior is tier-1-tested without JAX, but end-to-end
    drills need a backend whose compiler accepts multi-process programs,
    which stock CPU jaxlib does not (see GANG_UNSUPPORTED_SIGNS).
    """
    import socket
    import subprocess
    import sys

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    workers = []
    for i in range(procs):
        env = worker_env(
            dict(os.environ), coordinator_port=port, num_processes=procs,
            process_id=i, devices_per_proc=devices_per_proc)
        # num_processes == 1 strips the discovery triple (the refit
        # path); the probe worker needs it either way.
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = str(procs)
        env["JAX_PROCESS_ID"] = str(i)
        workers.append(subprocess.Popen(
            [sys.executable, "-c", _PROBE_WORKER],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env))
    outs = []
    ok = True
    try:
        for proc in workers:
            try:
                out, _ = proc.communicate(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                proc.kill()
                out, _ = proc.communicate()
                out = (out or "") + "\n[probe timeout]"
            outs.append(out or "")
            ok = ok and proc.returncode == 0 and "GANG_PROBE_OK" in out
    finally:
        for proc in workers:
            if proc.poll() is None:
                proc.kill()
    return ok, "\n".join(outs)[-4000:]


# ---------------------------------------------------------------------------
# Crash-loop breaking, keyed per worker
# ---------------------------------------------------------------------------

class GangBreaker:
    """Crash-loop breaker keyed on (worker, failure signature).

    One :class:`supervision.CrashLoopBreaker` per process id: worker 3
    segfaulting at the same step every attempt trips after ``threshold``
    repeats, while unrelated failures on other workers keep their own
    streaks — a single flaky host cannot burn the gang's attempt budget
    by alternating with healthy-worker noise.
    """

    def __init__(self, threshold: int = 2):
        self.threshold = threshold
        self._per_worker: dict[int, supervision.CrashLoopBreaker] = {}

    def record(
        self,
        worker: int,
        *,
        rc: int,
        last_step: int | None,
        ckpt_step: int | None,
        hung: bool = False,
        transient: bool = False,
    ) -> bool:
        """Register one failed attempt attributed to ``worker``; True =
        that worker's failure is a deterministic crash loop — stop."""
        breaker = self._per_worker.setdefault(
            worker, supervision.CrashLoopBreaker(self.threshold))
        return breaker.record(rc=rc, last_step=last_step,
                              ckpt_step=ckpt_step, hung=hung,
                              transient=transient)

    def report(self, worker: int) -> dict:
        """Post-mortem for one worker's breaker, tagged with its id."""
        breaker = self._per_worker.get(worker)
        out = breaker.report() if breaker else {
            "verdict": "no_failures_recorded"}
        out["process_id"] = worker
        return out


# ---------------------------------------------------------------------------
# Rejoin watchdog
# ---------------------------------------------------------------------------

def decide_rejoin(
    ages: dict[int, float | None],
    *,
    elapsed_s: float,
    rejoin_timeout_s: float,
) -> list[int]:
    """Which workers failed to rejoin the gang and should be dropped.

    ``ages`` maps process id → heartbeat age (None = never beat this
    attempt, pid-scoped).  A worker is overdue only when the rejoin
    window has elapsed, it has no heartbeat, and at least one peer
    *does* — if nobody has joined yet the gang is still booting (or the
    coordinator itself is stuck) and dropping members would shrink a
    healthy mesh for no reason.  ``rejoin_timeout_s <= 0`` disables the
    watchdog.
    """
    if rejoin_timeout_s <= 0 or elapsed_s <= rejoin_timeout_s:
        return []
    if not any(age is not None for age in ages.values()):
        return []
    return sorted(w for w, age in ages.items() if age is None)


# ---------------------------------------------------------------------------
# Gang-level elastic refit (the rc-84 ladder, across processes)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GangRefit:
    """Outcome of refitting the mesh to a smaller surviving gang."""

    process_count: int          # surviving processes to relaunch
    n_devices: int              # total devices across the survivors
    sizes: dict[str, int]       # fitted mesh axis sizes
    global_batch: int           # rescaled global batch
    grad_accum: int             # rescaled grad-accum factor
    batch_preserved: bool       # effective batch held constant?
    overrides: list[str]        # key.path=value overrides for the child


def decide_refit(
    sizes: dict[str, int],
    global_batch: int,
    grad_accum: int,
    *,
    process_count: int,
    devices_per_proc: int,
) -> GangRefit:
    """Fit the mesh to ``process_count`` surviving workers.

    The same ``fit_axis_sizes``/``rescale_for_devices`` path the
    single-process rc-84 ladder uses, applied to the gang's total device
    count: non-data axes shrink to divisors, the data axis absorbs the
    rest, and the per-device batch is held constant by moving the
    difference into grad accumulation so the *effective* batch — and the
    optimizer trajectory — survive the shrink.
    """
    if process_count < 1:
        raise ClusterSpecError("cannot refit a gang to zero processes")
    n_devices = process_count * devices_per_proc
    try:
        fitted = supervision.fit_axis_sizes(sizes, n_devices)
    except ValueError as e:
        raise ClusterSpecError(
            f"no mesh over {n_devices} devices satisfies {sizes}: {e}"
        ) from e
    old_dp = sizes.get("data", 1)
    new_dp = fitted.get("data", 1)
    if old_dp > 0:
        new_batch, new_accum, preserved = supervision.rescale_for_devices(
            global_batch, grad_accum, old_dp, new_dp)
    else:  # data was -1 (infer): per-device batch is unknowable here
        new_batch, new_accum, preserved = global_batch, grad_accum, False
    overrides = [f"mesh.{axis}={size}" for axis, size in fitted.items()]
    overrides.append("checkpoint.allow_reshard=true")
    if preserved:
        overrides.append(f"data.global_batch_size={new_batch}")
        overrides.append(f"train.grad_accum_steps={new_accum}")
    return GangRefit(
        process_count=process_count,
        n_devices=n_devices,
        sizes=fitted,
        global_batch=new_batch,
        grad_accum=new_accum,
        batch_preserved=preserved,
        overrides=overrides,
    )


# ---------------------------------------------------------------------------
# Coordinator-led exit barrier
# ---------------------------------------------------------------------------

_manifest_module = None


def _load_manifest_module():
    """Import ckpt/manifest.py by file path so the barrier (and the
    supervisor that shares this helper) never pulls JAX/Orbax through
    the package ``__init__``."""
    global _manifest_module
    if _manifest_module is None:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "ckpt", "manifest.py")
        spec = importlib.util.spec_from_file_location("_dtf_manifest", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _manifest_module = module
    return _manifest_module


def latest_committed_step(ckpt_dir: str) -> int | None:
    """Newest committed checkpoint step, read without importing JAX."""
    return _load_manifest_module().latest_committed_step(ckpt_dir)


def exit_barrier(
    ckpt_dir: str,
    *,
    step: int,
    timeout_s: float,
    poll_s: float = 0.5,
    is_chief: bool = False,
    latest_step_fn=None,
    sleep=time.sleep,
    clock=time.monotonic,
) -> int:
    """Block until the final checkpoint's commit record is durable.

    Async checkpointing lets training finish while shards are still in
    flight; in a gang, a worker that exits early tears down the
    coordinator and can strand every peer's commit.  The barrier closes
    that window: the chief confirms its own manifest commit record for
    ``step`` (written after every host's shard landed), and survivors
    poll the same record — nobody returns until the save is durable for
    everyone.  Returns the committed step observed (which may exceed
    ``step`` after an elastic resume).  Raises
    :class:`ExitBarrierTimeoutError` on timeout rather than silently
    exiting with a half-committed save.

    ``latest_step_fn``/``sleep``/``clock`` are test seams.
    """
    read_step = latest_step_fn or latest_committed_step
    deadline = clock() + max(0.0, timeout_s)
    while True:
        committed = read_step(ckpt_dir)
        if committed is not None and committed >= step:
            return committed
        if clock() >= deadline:
            role = "chief" if is_chief else "worker"
            raise ExitBarrierTimeoutError(
                f"exit barrier timed out after {timeout_s:.1f}s: {role} "
                f"waited for commit record of step {step} in {ckpt_dir} "
                f"but the manifest shows "
                f"{'nothing committed' if committed is None else committed}")
        sleep(poll_s)
