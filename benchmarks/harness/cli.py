"""Arguments, the chip check, and the one line."""

from __future__ import annotations

import argparse
import json
import os
import sys


def fail(msg: str) -> int:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv, *, root: str, process_t0: float) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks.harness import manifest

    try:
        cell = manifest.Manifest(root).cell(args.workload)
    except (OSError, KeyError, manifest.ManifestError) as e:
        return fail(str(e))
    try:
        from distributed_tensorflow_framework_tpu.core import platform
    except ImportError as e:
        return fail(f"the program is not in this checkout ({e}); the "
                    f"benchmark measures nothing without it")
    with open(os.path.join(root, manifest.BENCH_DIR, "harness",
                           "peaks.json")) as fh:
        peaks_table = json.load(fh)

    # The compile cache: JAX_COMPILATION_CACHE_DIR if set, else the fixed
    # <checkout>/.jax_cache, placed by the program's own resolver before
    # the first backend use.
    platform.resolve_compilation_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        return fail(f"JAX found no backend: {e}")
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX reports platform "
                    f"{devices[0].platform!r} ({kind}). No CPU fallback: a "
                    f"number from here would not be a device number.")
    if kind not in peaks_table:
        return fail(f"device_kind {kind!r} is not in harness/peaks.json; "
                    f"add its published peaks with their source")
    if len(devices) < cell.chips:
        return fail(f"cell {cell.name} needs {cell.chips} chips, JAX "
                    f"reports {len(devices)}")

    from benchmarks.harness import runner

    result, detail = runner.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        root=root, process_t0=process_t0, devices=devices[:cell.chips],
        peaks=peaks_table[kind])
    print("[bench] detail " + json.dumps(detail, default=str), flush=True)
    print(json.dumps(result), flush=True)
    return 0
