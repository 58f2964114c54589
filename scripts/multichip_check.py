"""What a host with several chips must show — read from the run, not from
the config.

Several chips are the point of this system, and a mesh that names four
devices proves nothing: a Mosaic kernel has no partitioning rule, a batch
can be gathered back onto every chip, a step can compile without a single
collective. This builds the shipped BERT-base step (configs/
bert_base_mlm.yaml, synthetic batches from a seed) over every local chip
and checks, from the arrays and the compiled program:

  * every batch array's sharding spans all the chips, each holding its
    1/n of the rows;
  * the compiled step has an all-reduce (the gradient exchange);
  * every Mosaic custom call in it works on batch/n rows (the attention
    kernel is split, not replicated);
  * after a step every chip reports non-zero ``bytes_in_use``;
  * at equal global batch and seed the first-step loss matches the same
    step on ONE chip within bf16 noise;
  * one ``train.spmd_mode=shard_map`` + ``mesh.fsdp=2`` step (explicit
    collectives, params sharded) runs to a finite loss close to it.

    python scripts/multichip_check.py

The last line of stdout is one JSON object with ``ok``, the device, and
every fact read. Exit 0 when all hold, 1 otherwise; fewer than two
devices is an error, not a pass. ``chip_smoke.py`` runs it as its last
leg when the host has more than one chip.
"""

import json
import pathlib
import re
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import numpy as np

from distributed_tensorflow_framework_tpu.core import platform
from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.mesh import (
    create_mesh,
    device_record,
)
from distributed_tensorflow_framework_tpu.data import get_dataset
from distributed_tensorflow_framework_tpu.data.infeed import to_global
from distributed_tensorflow_framework_tpu.ops import flash_attention as fa
from distributed_tensorflow_framework_tpu.train.step import StepBuilder

CONFIG = pathlib.Path(__file__).resolve().parent.parent / "configs" / \
    "bert_base_mlm.yaml"
GLOBAL_BATCH = 32
# The loss is a mean over ~15% of 32*512 tokens of bf16 logits: agreement
# to the third significant digit is the dtype's resolution. The shard_map
# arm also draws its dropout masks per replica, not per global batch.
LOSS_TOL_JIT = 2e-2
LOSS_TOL_SHARD_MAP = 1e-1

_SHAPE = re.compile(r"\w+\[([\d,]+)\]")


def mosaic_batch_dims(hlo: str) -> list[list[int]]:
    """Per Mosaic custom call in a compiled (per-device) HLO text, the
    leading dims of its rank-4 ``(B, H, S, D)`` result and operand shapes
    — the rows of the batch that one device's kernel works on."""
    calls = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        shapes = [tuple(int(d) for d in m.split(","))
                  for m in _SHAPE.findall(line.split("backend_config")[0])]
        calls.append(sorted({s[0] for s in shapes if len(s) == 4}))
    return calls


def first_step(devices, overrides=()):
    """(loss, facts) of step 1 of the shipped config on ``devices``."""
    cfg = load_config(CONFIG, overrides=[
        "data.name=synthetic_mlm",
        f"data.global_batch_size={GLOBAL_BATCH}", *overrides])
    mesh = create_mesh(cfg.mesh, devices=devices)
    builder = StepBuilder(cfg, mesh)
    batch = to_global(next(get_dataset(cfg.data)), mesh)
    state = builder.init_state(cfg.train.seed, batch)
    compiled = builder.make_train_step(batch).lower(state, batch).compile()
    hlo = compiled.as_text()
    state, metrics = compiled(state, batch)
    loss = float(jax.device_get(metrics["loss"]))

    mosaic_batch = mosaic_batch_dims(hlo)
    in_use = []
    for d in devices:
        stats = d.memory_stats() or {}
        in_use.append(int(stats.get("bytes_in_use", 0)))
    facts = {
        "mesh": {a: int(s) for a, s in mesh.shape.items() if s > 1},
        "loss": loss,
        "batch_devices": sorted({len(x.sharding.device_set)
                                 for x in jax.tree.leaves(batch)}),
        "batch_rows_per_device": sorted({
            x.addressable_shards[0].data.shape[0]
            for x in jax.tree.leaves(batch)}),
        "all_reduces": len(re.findall(r" all-reduce(?:-start)?\(", hlo)),
        "all_gathers": len(re.findall(r" all-gather(?:-start)?\(", hlo)),
        "mosaic_calls": len(mosaic_batch),
        "mosaic_batch_dims": sorted({b for bs in mosaic_batch for b in bs}),
        "bytes_in_use": in_use,
    }
    return loss, facts


def main() -> int:
    platform.resolve_compilation_cache()
    devices = jax.devices()
    n = len(devices)
    if n < 2 or n % 2:
        print(f"multichip_check needs an even number (>= 2) of devices, "
              f"found {n}", file=sys.stderr)
        return 1
    on_chip = fa.kernel_mode() == "mosaic"
    failed = []

    def check(cond, what):
        print(("ok    " if cond else "FAILED ") + what, flush=True)
        if not cond:
            failed.append(what)

    loss_one, one = first_step(devices[:1])
    print(f"one device: {json.dumps(one)}", flush=True)
    loss_all, every = first_step(devices)
    print(f"{n} devices, jit: {json.dumps(every)}", flush=True)
    rows = GLOBAL_BATCH // n
    check(np.isfinite(loss_one) and np.isfinite(loss_all), "losses finite")
    check(every["batch_devices"] == [n],
          f"every batch array's sharding spans {n} devices")
    check(every["batch_rows_per_device"] == [rows],
          f"each device holds {rows} of {GLOBAL_BATCH} rows")
    check(every["all_reduces"] > 0, "the compiled step has an all-reduce")
    check(one["all_reduces"] == 0,
          "the one-device step has none (the all-reduce is the exchange)")
    if on_chip:
        check(every["mosaic_calls"] > 0
              and every["mosaic_batch_dims"] == [rows],
              f"every Mosaic call works on {rows} rows per device "
              f"(found batch dims {every['mosaic_batch_dims']})")
        check(all(b > 0 for b in every["bytes_in_use"]),
              f"all {n} devices report non-zero bytes_in_use")
    check(abs(loss_all - loss_one) <= LOSS_TOL_JIT,
          f"first-step loss on {n} devices matches one device "
          f"({loss_all:.5f} vs {loss_one:.5f})")

    loss_sm, explicit = first_step(devices, overrides=(
        "train.spmd_mode=shard_map", f"mesh.data={n // 2}", "mesh.fsdp=2",
        # Explicit fsdp updates parameter shards; the global-norm clip
        # needs whole gradients and is refused there (train/step.py).
        "optimizer.grad_clip_norm=0"))
    print(f"{n} devices, shard_map + fsdp=2: {json.dumps(explicit)}",
          flush=True)
    check(explicit["all_reduces"] > 0 and explicit["all_gathers"] > 0,
          "the explicit step has its all-reduce and its fsdp all-gathers")
    if on_chip:
        check(explicit["mosaic_batch_dims"] == [rows],
              f"its Mosaic calls work on {rows} rows per device too")
    check(np.isfinite(loss_sm)
          and abs(loss_sm - loss_one) <= LOSS_TOL_SHARD_MAP,
          f"shard_map + fsdp=2 first-step loss {loss_sm:.5f} is finite "
          f"and close to one device's {loss_one:.5f}")

    print(json.dumps({
        "ok": not failed, "failed": failed, "device": device_record(),
        "kernel_mode": fa.kernel_mode(), "global_batch": GLOBAL_BATCH,
        "one_device": one, "all_devices_jit": every,
        "all_devices_shard_map_fsdp2": explicit}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
