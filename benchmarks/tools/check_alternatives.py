"""What a cell's ``check_tolerance`` refuses beside a lower precision: an
``assumed`` equation's alternative put in the PROGRAM's place, on the
chip, at the cell's own size, through the harness's own comparison
(``harness/check.py``), as ``check_control.py`` does for the precision
below. The float32 reference is computed once a seed; each alternative
costs one more compile of the program's step.

    python3 benchmarks/tools/check_alternatives.py --workload nemotron3_super_s8192 \
        --seeds 7 [--alternatives norm_then_gate ...] [--control-dtype bfloat16]

One JSON line per seed: ``sound`` (the program as it is), ``control`` (the
reference one precision below, where ``--control-dtype`` is given) and
one entry per alternative, each with its errors and ``ok`` (True means
the limits would NOT notice it). Needs the chip at the real size; on the
CPU it runs what ``--set k=v`` leaves small enough, and what it prints
there is no device number.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


@contextlib.contextmanager
def _attr(owner, name, value):
    get = owner.__getitem__ if isinstance(owner, dict) else \
        lambda k: getattr(owner, k)
    put = owner.__setitem__ if isinstance(owner, dict) else \
        lambda k, v: setattr(owner, k, v)
    old = get(name)
    put(name, value)
    try:
        yield ()
    finally:
        put(name, old)


def nemotron_h_alternatives() -> dict:
    """name -> a context manager that yields the overrides the program is
    built with while the alternative is in place. Inside a Mamba-2 layer:
    the gated norm's order, the step size's clamp, the state's reset at a
    document; inside an expert layer: the experts' activation, the
    factor on the routed weights."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_framework_tpu.models import lfm2, moe
    from distributed_tensorflow_framework_tpu.ops import ssm_scan

    def norm_then_gate(y, z, scale, groups, eps):
        lead, d = y.shape[:-1], y.shape[-1]
        y = y.astype(jnp.float32).reshape(*lead, groups, d // groups)
        y = y * jax.lax.rsqrt(
            jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
        return y.reshape(*lead, d) * scale * jax.nn.silu(
            z.astype(jnp.float32))

    def clamped_step_size(dt, dt_bias):
        return jnp.clip(jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                        1e-3, 1e-1)

    scan = ssm_scan.chunked_ssm_scan

    def scan_across_documents(x, dt, a, b, c, segment_ids=None, **kw):
        return scan(x, dt, a, b, c, None, **kw)

    @contextlib.contextmanager
    def overrides(*more):
        yield more

    return {
        "norm_then_gate": lambda: _attr(
            lfm2, "gated_group_norm", norm_then_gate),
        "a_clamped_step_size": lambda: _attr(
            lfm2, "step_size", clamped_step_size),
        "no_state_reset_at_a_document": lambda: _attr(
            ssm_scan, "chunked_ssm_scan", scan_across_documents),
        "a_plain_relu_in_the_experts": lambda: _attr(
            moe.UNGATED_ACTIVATIONS, "relu2", jax.nn.relu),
        "no_scaling_factor": lambda: overrides("model.routed_scaling=1.0"),
    }


FAMILIES = {"nemotron_h": nemotron_h_alternatives}


def readings(cell, root: str, seed: int, names, control_dtype, extra=()):
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
    reference = manifest.load_family(root, "reference",
                                     cell.config["reference"])
    hparams = {**cell.config["published"], **cell.config["reference_hparams"],
               "label_smoothing": cfg.train.label_smoothing}
    tolerance = cell.config["check_tolerance"]
    exact = check.reference_values(reference, state.params, sample, hparams)
    out = {"seed": seed}
    if control_dtype:
        out["control"] = check.compare(
            check.reference_values(reference, state.params, sample,
                                   {**hparams, "dtype": control_dtype}),
            exact, tolerance)
    jax.clear_caches()

    def program(more=()):
        values = check.program_step_values(
            lambda extra=(): load([*extra, *more]), mesh, state, sample)
        jax.clear_caches()       # the step's executable off the chip
        gc.collect()
        return check.compare(values, exact, tolerance)

    out["sound"] = program()
    alternatives = FAMILIES[cell.config["reference"]]()
    for name in alternatives if names is None else names:
        with alternatives[name]() as more:
            out[name] = program(more)
        print(json.dumps({"seed": seed, name: out[name]}), flush=True)
    return out


def main(argv=None) -> int:
    from benchmarks.harness import manifest
    from distributed_tensorflow_framework_tpu.core import platform

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--alternatives", nargs="*", default=None,
                    help="names; none given: all of the family's; "
                         "given empty: none (sound and control only)")
    ap.add_argument("--control-dtype", default="")
    ap.add_argument("--set", dest="extra", action="append", default=[])
    args = ap.parse_args(argv)
    cell = manifest.Manifest(_ROOT).cell(args.workload)
    platform.resolve_compilation_cache()
    for seed in args.seeds:
        print(json.dumps(readings(cell, _ROOT, seed, args.alternatives,
                                  args.control_dtype, args.extra)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
