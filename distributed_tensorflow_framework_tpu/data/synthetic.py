"""Synthetic data sources — benchmarking and hardware-free tests.

Mirrors the role of the reference's "fake cluster on localhost" smoke path
(SURVEY.md §4): exercise the full runtime with no dataset on disk. Labels
are a deterministic function of the image/token content so models can
actually overfit them in integration tests (loss must go down).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from distributed_tensorflow_framework_tpu.core.config import DataConfig
from distributed_tensorflow_framework_tpu.core import prng
from distributed_tensorflow_framework_tpu.data.pipeline import (
    HostDataset,
    image_np_dtype,
)
from distributed_tensorflow_framework_tpu.data import shard


def _host_batch(config: DataConfig, process_count: int) -> int:
    g = config.global_batch_size
    if g % process_count:
        raise ValueError(
            f"global_batch_size {g} not divisible by process_count {process_count}"
        )
    return g // process_count


def synthetic_images(
    config: DataConfig, process_index: int, process_count: int
) -> HostDataset:
    b = _host_batch(config, process_count)
    h = w = config.image_size
    c = config.channels
    num_classes = config.num_classes
    out_dtype = image_np_dtype(config.image_dtype)

    def make_iter(state: dict[str, Any]):
        state.setdefault("step", 0)
        while True:
            # Host-local stream: process_index in the derivation
            # (core/prng.py host-side rules).
            rng = prng.host_rng(config.seed, prng.ROLE_DATA,
                                process_index, state["step"])
            images = rng.standard_normal((b, h, w, c), dtype=np.float32)
            # Label = argmax over the first num_classes pixels: uniform over
            # classes, perfectly learnable, and stable at any image size
            # (a per-image-mean hash degenerates by CLT as size grows).
            labels = np.argmax(
                images.reshape(b, -1)[:, :num_classes], axis=1
            ).astype(np.int32)
            state["step"] += 1
            yield {"image": images.astype(out_dtype, copy=False), "label": labels}

    return HostDataset(
        make_iter,
        element_spec={
            "image": ((b, h, w, c), out_dtype),
            "label": ((b,), np.int32),
        },
        initial_state={"step": 0},
        # Generated data has no sample identity to replay or drop: the
        # {"step": N} state restores at any host count (each host simply
        # draws its own stream), so an N→M refit is trivially exact.
        repartition=shard.REPARTITION_INVARIANT,
    )


def synthetic_mlm(
    config: DataConfig, process_index: int, process_count: int
) -> HostDataset:
    b = _host_batch(config, process_count)
    s = config.seq_len
    vocab = config.vocab_size
    lo = min(1000, vocab // 2)  # keep low ids free for specials

    def make_iter(state: dict[str, Any]):
        state.setdefault("step", 0)
        # BERT's [MASK]=103 when it sits below the token range [lo, vocab)
        # (vocab > 103 is NOT enough: e.g. vocab=128 → tokens span [64,128)
        # and 103 would collide with a real token). Fallback is id 0, which
        # is always below lo>=1 and in embedding range.
        mask_id = 103 if lo > 103 else 0
        while True:
            rng = prng.host_rng(config.seed, prng.ROLE_DATA,
                                process_index, state["step"])
            tokens = rng.integers(lo, vocab, size=(b, s), dtype=np.int64).astype(np.int32)
            mask = rng.random((b, s)) < config.mask_prob
            mask[:, 0] = False
            input_ids = np.where(mask, mask_id, tokens)
            targets = np.where(mask, tokens, -1).astype(np.int32)
            state["step"] += 1
            yield {
                "input_ids": input_ids,
                "targets": targets,
                "attention_mask": np.ones((b, s), dtype=np.int32),
            }

    return HostDataset(
        make_iter,
        element_spec={
            "input_ids": ((b, s), np.int32),
            "targets": ((b, s), np.int32),
            "attention_mask": ((b, s), np.int32),
        },
        initial_state={"step": 0},
        # Same refit-safety as synthetic_images: no sample identity.
        repartition=shard.REPARTITION_INVARIANT,
    )


def synthetic_lm(
    config: DataConfig, process_index: int, process_count: int
) -> HostDataset:
    """Causal-LM rows (``data.name: synthetic_lm``): two documents packed
    into each row at a drawn boundary, ids uniform over the vocabulary,
    ``targets[t]`` the next token of the same document (-1 on each
    document's last token), ``segment_ids`` 1 and 2 and ``positions``
    restarting at the boundary."""
    b = _host_batch(config, process_count)
    s = config.seq_len
    vocab = config.vocab_size

    def make_iter(state: dict[str, Any]):
        state.setdefault("step", 0)
        idx = np.arange(s, dtype=np.int32)[None, :]
        while True:
            rng = prng.host_rng(config.seed, prng.ROLE_DATA,
                                process_index, state["step"])
            tokens = rng.integers(0, vocab, size=(b, s),
                                  dtype=np.int64).astype(np.int32)
            cut = rng.integers(1, s, size=(b, 1))
            second = idx >= cut
            last = (idx == cut - 1) | (idx == s - 1)
            targets = np.where(last, -1, np.roll(tokens, -1, axis=1))
            state["step"] += 1
            yield {
                "input_ids": tokens,
                "targets": targets.astype(np.int32),
                "segment_ids": (1 + second).astype(np.int32),
                "positions": np.where(second, idx - cut, idx).astype(np.int32),
            }

    return HostDataset(
        make_iter,
        element_spec={key: ((b, s), np.int32) for key in
                      ("input_ids", "targets", "segment_ids", "positions")},
        initial_state={"step": 0},
        repartition=shard.REPARTITION_INVARIANT,
    )
