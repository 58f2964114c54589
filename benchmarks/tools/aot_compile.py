"""Rehearsal 3 of the on-chip-measurement guide: compile a cell's train
step at its real size for a described v5e, in the sandbox, without the
chip. Prints what the chip's compiler says of memory, and how many
kernels and collectives the per-device program holds; optionally writes
the HLO text. It compiles; it cannot run, and nothing it prints is a
time or a rate.

    JAX_PLATFORMS=cpu python3 -m benchmarks.tools.aot_compile <cell> [--hlo-out FILE] [--set k=v ...]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")


def compile_cell(cell, root: str, extra=()):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    from benchmarks.harness import build
    from distributed_tensorflow_framework_tpu.core.mesh import (
        batch_spec, create_mesh)
    from distributed_tensorflow_framework_tpu.ops import flash_attention
    from distributed_tensorflow_framework_tpu.parallel import sharding as shd
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    # The default backend here is the CPU, where the kernels would take
    # interpret mode; the compile is for the chip.
    flash_attention._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    pool = build.make_pool(cell, root, seed=0)
    cfg = build.config_loader(cell, root, seed=0, dataset_name="unused",
                              extra=tuple(extra))()
    mesh = create_mesh(cfg.mesh, devices=topo.devices[:cell.chips])
    b_sh = NamedSharding(mesh, batch_spec(mesh))
    sample = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=b_sh)
              for k, v in pool.batches[0].items()}
    builder = StepBuilder(cfg, mesh)
    state_sh = shd.specs_to_shardings(builder.state_specs(sample), mesh)
    state = jax.eval_shape(builder._create_state,
                           jax.ShapeDtypeStruct((1,), jnp.uint32), sample)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, state_sh)
    return builder.make_train_step(sample).lower(state, sample).compile()


def main(argv=None) -> int:
    from benchmarks.harness import manifest
    from benchmarks.harness.records import step_memory

    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--hlo-out")
    ap.add_argument("--set", dest="extra", action="append", default=[])
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cell = manifest.Manifest(root).cell(args.cell)
    compiled = compile_cell(cell, root, args.extra)
    hlo = compiled.as_text()
    if args.hlo_out:
        with open(args.hlo_out, "w") as fh:
            fh.write(hlo)
    print(json.dumps({
        "cell": cell.name, "compiled_for": "v5e:2x2 (described, not run)",
        "memory": step_memory(compiled),
        "mosaic_calls": hlo.count('custom_call_target="tpu_custom_call"'),
        "all_reduces": len(re.findall(r" all-reduce(?:-start)?\(", hlo)),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
