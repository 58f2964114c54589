"""Device-mesh construction and distributed runtime initialization.

Replaces the reference's L1 cluster runtime (SURVEY.md §2 rows 1–2:
``tf.train.ClusterSpec`` + ``tf.train.Server`` per-role launcher and
``replica_device_setter`` variable placement). There is no parameter-server
role: every host runs the same SPMD program, parameters live wherever the
sharding rules put them (replicated, or sharded over the ``fsdp`` axis), and
the "cluster spec" collapses to one logical `jax.sharding.Mesh`.

Collectives emitted against this mesh ride ICI within a slice and DCN across
slices — the TPU-native equivalent of the reference's grpc PS transport +
NCCL all-reduce (SURVEY.md §2 native rows).
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_tensorflow_framework_tpu.core.config import MeshConfig

log = logging.getLogger(__name__)

# Axis order matters: data outermost so data-parallel replicas land on
# distinct slices/hosts first, model/seq innermost so tensor- and
# sequence-parallel collectives ride the fastest ICI links; expert/pipe sit
# between (all_to_all and stage ppermute traffic is lighter than TP
# all_reduce but heavier than DP grad reduction per step).
MESH_AXES = ("data", "fsdp", "expert", "pipe", "seq", "model")


class MeshSizeError(ValueError):
    """The configured mesh does not fit the visible device set.

    Typed (vs a bare ValueError) so cli/train.py can map it to the
    supervisor's elastic-reshard exit code (``ELASTIC_RESHARD_RC`` = 84,
    core/supervision.py): when a slice drops out between relaunches this
    is a topology change to adapt to, not a crash to back off from.
    """

    def __init__(self, sizes: dict[str, int], needed: int, available: int):
        self.sizes = dict(sizes)
        self.needed = int(needed)
        self.available = int(available)
        super().__init__(
            f"Mesh {self.sizes} needs {self.needed} devices but "
            f"{self.available} are available"
        )


def initialize_distributed() -> None:
    """Initialize multi-host JAX if a cluster environment is detected.

    The reference required the user to pass ``--ps_hosts/--worker_hosts/
    --job_name/--task_index`` to every process; here multi-host discovery is
    automatic (TPU metadata / cluster env vars), and single-host runs skip
    initialization entirely.
    """
    # NOTE: must not touch jax.process_count()/devices() here — any backend
    # query initializes XLA, after which jax.distributed.initialize raises.
    if jax.distributed.is_initialized():
        return
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    num_procs = os.environ.get("JAX_NUM_PROCESSES")
    if coord and num_procs:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(num_procs),
            process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
        )


def _resolve_axis_sizes(config: MeshConfig, n: int) -> dict[str, int]:
    """Fill the single -1 axis and validate the product against n."""
    sizes = config.axis_sizes()
    fixed = {k: v for k, v in sizes.items() if v != -1}
    fixed_prod = int(np.prod(list(fixed.values()))) if fixed else 1
    free = [k for k, v in sizes.items() if v == -1]
    if len(free) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {free}")
    if free:
        if n % fixed_prod:
            raise MeshSizeError(sizes, fixed_prod, n)
        sizes[free[0]] = n // fixed_prod
    total = int(np.prod(list(sizes.values())))
    if total != n:
        raise MeshSizeError(sizes, total, n)
    return sizes


def fit_mesh(
    config: MeshConfig | dict[str, int], n_devices: int
) -> dict[str, int]:
    """Largest valid axis sizes fitting ``n_devices`` — the elastic
    supervisor's mesh-rewrite primitive. Non-``data`` axes only shrink to
    divisors of their configured size (preserving divisibility of stage/
    shard splits), ``data`` absorbs the rest; axis ORDER is MESH_AXES.
    Pure arithmetic delegated to core/supervision.fit_axis_sizes so the
    jax-free supervisor computes the identical answer."""
    from distributed_tensorflow_framework_tpu.core import supervision

    sizes = config.axis_sizes() if isinstance(config, MeshConfig) else config
    return supervision.fit_axis_sizes(dict(sizes), n_devices)


def hybrid_mesh_shapes(
    sizes: dict[str, int], num_slices: int
) -> tuple[dict[str, int], dict[str, int]]:
    """Split logical axis sizes into (per-slice ICI, cross-slice DCN) parts.

    Multislice placement policy: outer axes span slices first — ``data``
    (one grad all-reduce per step tolerates DCN latency), then ``fsdp``
    (for FSDP-dominant layouts), and so on down MESH_AXES order — while
    everything still fitting intra-slice stays on ICI. The slice count
    must factor into the axis sizes walked in that order.
    """
    import math

    ici = dict(sizes)
    dcn = {a: 1 for a in sizes}
    remaining = num_slices
    for axis in MESH_AXES:
        if remaining == 1:
            break
        f = math.gcd(ici[axis], remaining)
        if f > 1:
            dcn[axis] = f
            ici[axis] //= f
            remaining //= f
    if remaining != 1:
        raise ValueError(
            f"slice count {num_slices} does not factor into the mesh axes "
            f"{sizes} (walked in {MESH_AXES} order) — no DCN-spanning "
            f"layout exists"
        )
    return ici, dcn


def create_mesh(
    config: MeshConfig | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the logical mesh from a MeshConfig over available devices.

    Axes with size 1 are kept in the mesh (size-1 axes are free) so that
    sharding rules can always name all canonical axes regardless of the
    physical topology. On a multislice TPU deployment (devices report
    distinct ``slice_index``), the mesh is built hybrid: ``data`` replicas
    span slices over DCN, every other axis stays within a slice on ICI.
    """
    config = config or MeshConfig()
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    sizes = _resolve_axis_sizes(config, n)
    shape = tuple(sizes[a] for a in MESH_AXES)

    slice_ids = {getattr(d, "slice_index", 0) for d in devs}
    if len(slice_ids) > 1:
        from jax.experimental import mesh_utils

        ici, dcn = hybrid_mesh_shapes(sizes, len(slice_ids))
        dev_array = mesh_utils.create_hybrid_device_mesh(
            tuple(ici[a] for a in MESH_AXES),
            tuple(dcn[a] for a in MESH_AXES),
            devices=devs,
        )
        return Mesh(dev_array, MESH_AXES)

    dev_array = np.asarray(devs).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def batch_spec(mesh: Mesh) -> P:
    """PartitionSpec sharding the leading batch dim over the data-like axes.

    ``expert`` participates: for MoE runs the batch is sharded over it too
    (it acts as extra data parallelism for the dense params; the MoE
    dispatch einsum moves tokens expert-ward via all_to_all). ``pipe``/
    ``seq``/``model`` never shard the batch dim.
    """
    del mesh
    return P(("data", "fsdp", "expert"))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


@dataclasses.dataclass
class MeshRuntime:
    """The process's view of the SPMD runtime (replaces ClusterSpec+Server)."""

    mesh: Mesh
    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int

    @property
    def is_chief(self) -> bool:
        """Process 0 — the reference's "chief" worker. It owns checkpoint
        writes and summary logging (SURVEY.md §2 row 10)."""
        return self.process_index == 0

    @property
    def data_parallel_size(self) -> int:
        return (
            self.mesh.shape["data"]
            * self.mesh.shape["fsdp"]
            * self.mesh.shape["expert"]
        )

    def describe(self) -> str:
        dev = device_record()
        return (
            f"process {self.process_index}/{self.process_count}, "
            f"platform {dev['platform']} ({dev['device_kind']}), "
            f"{self.local_device_count} local / {self.global_device_count} "
            f"global devices, mesh {dict(self.mesh.shape)}"
        )


def device_record() -> dict:
    """Where this process runs, as JAX reports it. Every run-meta record
    (trainer, serve, bench) and the mesh log line carry these fields, so
    an ``events.jsonl`` opens with the device its numbers belong to."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def initialize_runtime(
    config: MeshConfig | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> MeshRuntime:
    initialize_distributed()
    mesh = create_mesh(config, devices=devices)
    rt = MeshRuntime(
        mesh=mesh,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
    )
    log.info("Mesh runtime: %s", rt.describe())
    # Mesh position -> physical placement, once: create_mesh lays devices
    # out in enumeration order, and this is the record that shows which
    # chip (TPU coords; process elsewhere) each mesh index landed on.
    log.info("Mesh devices, row-major over %s: %s", ",".join(MESH_AXES),
             " ".join(
                 f"id{d.id}@{getattr(d, 'coords', None) or f'p{d.process_index}'}"
                 for d in mesh.devices.flat))
    return rt
