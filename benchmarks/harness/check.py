"""What decides ``correct``.

(1) The program's own train step against the plain reference, once,
during set-up. The step is built by the program's ``StepBuilder`` from
the cell's configuration with ``model.dropout_rate=0`` as the only
change (a reference cannot share a dropout mask), at the cell's sequence
length or image size, on the first ``check_rows`` rows of the pool's
first batch, from a copy of the parameters the trainer itself
initialised. Its ``loss`` and ``grad_norm`` must agree with the
reference's float32 values.

The tolerance lives in the configuration's file with its reason. The
rule for setting it: the system computes in bfloat16 where the reference
computes in float32, which moves a loss near ln(V) by a few parts in
10^4 and a global gradient norm by under a percent; the tolerance is a
small multiple of what the chip showed for that, and well under the
several percent that an int8 or fp8 step, a dropped term or a wrong
mask costs. A mis-scaled loss fails it (``benchmarks/tests``).

(2) Losses fetched in the window are finite and the first is near the
loss of a uniform guess. (3) Nothing compiled inside the window. (4) On
several chips, the compiled step is really spread over them.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np


def sample_rows(pool, rows: int) -> dict:
    return {k: np.ascontiguousarray(v[:rows])
            for k, v in pool.batches[0].items()}


def program_step_values(config_loader, mesh, state, host_sample) -> dict:
    """Run the program's train step once with dropout off. The step
    donates its state, so it is given a copy."""
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    rows = len(next(iter(host_sample.values())))
    cfg = config_loader(["model.dropout_rate=0",
                         f"data.global_batch_size={rows}"])
    sample = to_global(host_sample, mesh)
    step = StepBuilder(cfg, mesh).make_train_step(sample)
    _, metrics = step(jax.tree.map(jnp.copy, state), sample)
    metrics = jax.device_get(metrics)
    return {"loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"])}


def reference_values(reference, params, host_sample, hparams) -> dict:
    with jax.default_matmul_precision("highest"):
        loss, grad_norm = reference.loss_and_grad_norm(
            params, {k: jnp.asarray(v) for k, v in host_sample.items()},
            hparams)
        return {"loss": float(loss), "grad_norm": float(grad_norm)}


def compare(program: dict, reference: dict, tolerance: dict) -> dict:
    out = {"program": program, "reference": reference, "ok": True}
    for key in ("loss", "grad_norm"):
        ref = reference[key]
        rel = abs(program[key] - ref) / max(abs(ref), 1e-30)
        out[f"{key}_rel_err"] = rel
        if not (math.isfinite(rel) and rel <= tolerance[f"{key}_rel"]):
            out["ok"] = False
    return out


def window_losses_ok(losses: list, first_loss: dict) -> dict:
    finite = all(math.isfinite(x) for x in losses)
    near = bool(losses) and abs(
        losses[0] - first_loss["expected"]) <= first_loss["band"]
    return {"ok": bool(losses) and finite and near, "finite": finite,
            "first": losses[0] if losses else None, "fetched": len(losses)}


_SHAPE = re.compile(r"\w+\[([\d,]+)\]")


def mosaic_batch_dims(hlo: str) -> list:
    """Leading dims of the rank-4 (B, H, S, D) shapes on each Mosaic
    custom call of a compiled, per-device HLO text: the rows one device's
    kernel works on (the reading of scripts/multichip_check.py)."""
    dims = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        for m in _SHAPE.findall(line.split("backend_config")[0]):
            shape = m.split(",")
            if len(shape) == 4:
                dims.add(int(shape[0]))
    return sorted(dims)


def multichip_facts(hlo: str, sample: dict, chips: int,
                    per_chip_batch: int, on_chip: bool) -> dict:
    """The compiled step is spread over ``chips``: every batch array
    spans them, the step holds all-reduces, and the Mosaic calls work on
    one device's rows."""
    spans = sorted({len(x.sharding.device_set)
                    for x in jax.tree.leaves(sample)})
    all_reduces = len(re.findall(r" all-reduce(?:-start)?\(", hlo))
    mosaic = mosaic_batch_dims(hlo)
    ok = spans == [chips] and all_reduces > 0
    if on_chip:  # interpret mode lowers the kernels to plain HLO
        ok = ok and mosaic == [per_chip_batch]
    return {"ok": ok, "batch_array_devices": spans,
            "all_reduces": all_reduces, "mosaic_batch_dims": mosaic}
