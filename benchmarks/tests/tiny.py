"""Tiny versions of the cells, for the CPU rehearsals: same files, same
harness function, sizes cut through function arguments (no flag of the
command does this)."""

from __future__ import annotations

import dataclasses
import os

from benchmarks.harness import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_BERT = ("model.num_layers=2", "model.hidden_size=64",
             "model.num_heads=2", "model.mlp_dim=128",
             "model.vocab_size=2048", "model.max_seq_len=128")
TINY_BERT_PUBLISHED = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 2,
    "intermediate_size": 128, "vocab_size": 2048}


def tiny_cell(name: str, root: str = ROOT) -> tuple:
    """``(cell, extra_overrides)`` for ``runner.run_cell`` on the CPU."""
    cell = manifest.Manifest(root).cell(name)
    traffic, config = dict(cell.traffic), dict(cell.config)
    workload = dict(cell.workload, per_chip_batch=4, trace_steps=3)
    if config["family"] == "bert":
        traffic.update(seq_len=128, vocab_size=2048, token_id_min=200,
                       doc_length=(
                           {"dist": "fixed", "value": 128}
                           if traffic["doc_length"]["dist"] == "fixed" else
                           {"dist": "lognormal", "median": 32, "sigma": 0.8,
                            "min": 4, "max": 128}))
        workload["overrides"] = [
            o for o in workload["overrides"]
            if not o.startswith(("data.seq_len", "model.max_seq_len"))]
        config["published"] = {**config["published"], **TINY_BERT_PUBLISHED}
        config["reference_hparams"] = {"num_heads": 2}
        config["first_loss"] = {"expected": 7.62, "band": 0.8}
        extra = TINY_BERT
    else:
        # The full ResNet-50 on 64x64 images: BatchNorm over fewer than 8
        # images of 2x2 positions is too ill-conditioned to compare.
        traffic.update(image_size=64)
        workload.update(per_chip_batch=8)
        # 8 images a batch are memorised within the first ten steps
        config["first_loss"] = {"expected": 6.9, "band": 4.0}
        extra = ()
    config["check_tolerance"] = {"loss_rel": 5e-3, "grad_norm_rel": 5e-2}
    return dataclasses.replace(cell, traffic=traffic, config=config,
                               workload=workload), extra
