"""The generators: the packer splits no document, counts real tokens
exactly, and the same seed gives the same pool."""

import numpy as np
import pytest

from benchmarks.harness import build, manifest
from benchmarks.tests.tiny import ROOT
from benchmarks.traffic.generators import mlm_documents


def pool_for(name, seed):
    return build.make_pool(manifest.Manifest(ROOT).cell(name), ROOT, seed=seed)


def test_first_fit_places_whole_documents():
    placed, carried = mlm_documents.first_fit(
        iter([300, 300, 200, 100, 12, 500, 7]), rows=2, seq_len=512,
        close_after_misses=2)
    assert placed == [[300, 200, 12], [300, 100, 7]] and carried == [500]
    assert all(sum(r) <= 512 for r in placed)


def test_packed_rows_hold_whole_documents_and_count_exactly():
    pool = pool_for("bert_s512", seed=7)
    assert len(pool.batches) >= 8
    for batch, real in zip(pool.batches, pool.real_units):
        seg, mask = batch["segment_ids"], batch["attention_mask"]
        assert batch["input_ids"].shape == (32, 512)
        assert real == int(mask.sum()) == int((seg > 0).sum())
        for row in seg:
            ids = row[row > 0]
            # documents are contiguous, numbered 1..k, padding only at the end
            assert np.all(np.diff(ids) >= 0) and np.all(np.diff(ids) <= 1)
            assert np.all(row[len(ids):] == 0)
            lengths = np.unique(ids, return_counts=True)[1]
            assert lengths.min() >= 16 and lengths.max() <= 512
        assert np.all((batch["targets"] >= 0) <= (mask == 1))
        assert np.all(batch["input_ids"][mask == 0] == 0)
    assert pool.facts["fill"] > 0.95
    masked = sum(int((b["targets"] >= 0).sum()) for b in pool.batches)
    assert 0.13 < masked / sum(pool.real_units) < 0.17


def test_long_rows_are_whole_documents_without_padding():
    pool = pool_for("bert_s8192", seed=7)
    for batch, real in zip(pool.batches, pool.real_units):
        assert batch["input_ids"].shape == (2, 8192)
        assert "segment_ids" not in batch
        assert real == 2 * 8192 == int(batch["attention_mask"].sum())


@pytest.mark.parametrize("name", ["bert_s512", "bert_s512_dp4",
                                  "resnet50_i224"])
def test_same_seed_same_pool(name):
    if name == "resnet50_i224":   # smaller images, same generator and file
        cell = manifest.Manifest(ROOT).cell(name)
        cell.traffic["image_size"] = 32
        make = lambda seed: build.make_pool(cell, ROOT, seed=seed)  # noqa: E731
    else:
        make = lambda seed: pool_for(name, seed)  # noqa: E731
    a, b, c = make(3), make(3), make(4)
    assert a.real_units == b.real_units
    for x, y in zip(a.batches, b.batches):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert any(not np.array_equal(x[k], y[k])
               for x, y in zip(a.batches, c.batches) for k in x)
    keys = {x[next(iter(x))].tobytes()[:4096] for x in a.batches}
    assert len(keys) == len(a.batches) >= 8     # distinct batches
