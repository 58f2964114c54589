"""Each plain reference against the package's own step at a small size
on the CPU, and that the check can fail."""

import copy
import dataclasses

import jax
import pytest

from benchmarks.harness import build, check, manifest
from benchmarks.tests.tiny import ROOT, tiny_cell


def values(name, dtype):
    from distributed_tensorflow_framework_tpu.core.mesh import (
        initialize_runtime)
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    cell, extra = tiny_cell(name)
    pool = build.make_pool(cell, ROOT, seed=5)
    load = build.config_loader(cell, ROOT, seed=5, dataset_name="synthetic",
                               extra=(*extra, f"model.dtype={dtype}"))
    cfg = load()
    mesh = initialize_runtime(cfg.mesh, devices=jax.devices()[:1]).mesh
    sample = check.sample_rows(pool, cell.workload["per_chip_batch"])
    state = StepBuilder(cfg, mesh).init_state(5, to_global(sample, mesh))
    program = check.program_step_values(load, mesh, state, sample)
    hparams = {**cell.config["published"], **cell.config["reference_hparams"],
               "label_smoothing": cfg.train.label_smoothing}
    reference = check.reference_values(
        manifest.load_family(ROOT, "reference", cell.config["reference"]),
        state.params, sample, hparams)
    return cell, program, reference


@pytest.mark.parametrize("name", ["bert_s512", "bert_s8192", "resnet50_i224"])
def test_reference_is_the_packages_step_in_float32(name):
    """Same mathematics: in float32 the two agree to rounding."""
    _, program, reference = values(name, "float32")
    verdict = check.compare(program, reference,
                            {"loss_rel": 2e-6, "grad_norm_rel": 5e-5})
    assert verdict["ok"], verdict


@pytest.mark.parametrize("name", ["bert_s512", "resnet50_i224"])
def test_bfloat16_step_passes_the_shipped_tolerance_and_a_wrong_one_fails(name):
    cell, program, reference = values(name, "bfloat16")
    tolerance = manifest.Manifest(ROOT).cell(name).config["check_tolerance"]
    assert check.compare(program, reference, tolerance)["ok"]
    # a loss mis-scaled by 1%, as a wrong normaliser would: refused
    wrong = dict(program, loss=program["loss"] * 1.01)
    verdict = check.compare(wrong, reference, tolerance)
    assert not verdict["ok"] and verdict["loss_rel_err"] > tolerance["loss_rel"]
    # gradients 5% off, as a dropped term or a coarser dtype would: refused
    wrong = dict(program, grad_norm=program["grad_norm"] * 1.05)
    assert not check.compare(wrong, reference, tolerance)["ok"]
    assert not check.compare(dict(program, loss=float("nan")), reference,
                             tolerance)["ok"]


def test_window_loss_band():
    band = {"expected": 10.326, "band": 0.6}
    assert check.window_losses_ok([10.4, 10.5], band)["ok"]
    assert not check.window_losses_ok([], band)["ok"]
    assert not check.window_losses_ok([10.4, float("inf")], band)["ok"]
    assert not check.window_losses_ok([7.0, 7.0], band)["ok"]
