"""The two readings a cell's ``check_tolerance`` lies between, on the
chip, through the harness's own comparison (``harness/check.py``), at the
cell's own size (``check_rows`` rows of the pool's first batch, the
parameters the program's own init gives the seed).

*Sound*: the program's step against the float32 reference, as every run
compares them. *Control*: the reference computed one precision below the
configuration's (``hparams["dtype"]``, where the family's reference takes
it) put in the program's place. A cell's limits have to pass the first on
every seed and refuse the second by at least one of them, with room on
both sides.

    python3 benchmarks/tools/check_control.py --workload <cell> --seeds 3 5 8 [--control-dtype bfloat16]

One JSON line per seed, then one with the extremes. Needs the chip at the
real size; on the CPU it runs whatever the cell's overrides leave small
enough (``--set k=v``), and what it prints there is no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def readings(cell, root: str, seed: int, control_dtype: str, extra=()):
    import jax

    from benchmarks.harness import build, check, manifest
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    pool = build.make_pool(cell, root, seed=seed)
    load = build.config_loader(cell, root, seed=seed, dataset_name="unused",
                               extra=tuple(extra))
    sample = check.sample_rows(pool, int(cell.workload["check_rows"]))
    rows = len(next(iter(sample.values())))
    cfg = load([f"data.global_batch_size={rows}"])
    mesh = create_mesh(cfg.mesh, devices=jax.devices()[:cell.chips])
    state = StepBuilder(cfg, mesh).init_state(
        cfg.train.seed, to_global(sample, mesh))
    program = check.program_step_values(load, mesh, state, sample)
    reference = manifest.load_family(root, "reference",
                                     cell.config["reference"])
    hparams = {**cell.config["published"], **cell.config["reference_hparams"],
               "label_smoothing": cfg.train.label_smoothing}
    exact = check.reference_values(reference, state.params, sample, hparams)
    below = check.reference_values(reference, state.params, sample,
                                   {**hparams, "dtype": control_dtype})
    tolerance = cell.config["check_tolerance"]
    return {"seed": seed,
            "sound": check.compare(program, exact, tolerance),
            "control": check.compare(below, exact, tolerance)}


def main(argv=None) -> int:
    from benchmarks.harness import manifest
    from distributed_tensorflow_framework_tpu.core import platform

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-dtype", default="bfloat16")
    ap.add_argument("--set", dest="extra", action="append", default=[])
    args = ap.parse_args(argv)
    cell = manifest.Manifest(_ROOT).cell(args.workload)
    platform.resolve_compilation_cache()
    rows = []
    for seed in args.seeds:
        rows.append(readings(cell, _ROOT, seed, args.control_dtype,
                             args.extra))
        print(json.dumps(rows[-1]), flush=True)
    worst = {f"{side}_{key}": (max if side == "sound" else min)(
        r[side][key] for r in rows)
        for side in ("sound", "control")
        for key in ("loss_rel_err", "grad_norm_rel_err")}
    print(json.dumps({
        "cell": cell.name, "control_dtype": args.control_dtype,
        "seeds": args.seeds, **worst,
        "limits": {k: v for k, v in cell.config["check_tolerance"].items()
                   if k != "why"},
        "sound_passes_every_seed": all(r["sound"]["ok"] for r in rows),
        "control_refused_every_seed": not any(r["control"]["ok"]
                                              for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
