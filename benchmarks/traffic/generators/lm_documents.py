"""Causal-LM batches from documents of drawn lengths.

Parameters (the mix's JSON):

- ``seq_len``: row length.
- ``doc_length``, ``close_after_misses``: as in ``mlm_documents`` (whose
  length draw and first-fit packer this uses): documents are never
  split, a batch closes after that many documents in a row found no
  room, and those open the next batch.
- ``vocab_size``: ids are uniform over ``[0, vocab_size)``: the slice of
  the vocabulary this chip holds.
- ``pool_batches``: distinct batches made; the run cycles them (any
  other key of the mix, such as a ``*_why``, is the file's own note).

A batch holds ``input_ids``; ``segment_ids`` (1, 2, ... per document, 0
on padding); ``positions`` (restarting at each document); and
``targets``: the next token of the same document, -1 on a document's
last token and on padding, so no label crosses a document boundary. The
unit is real tokens: the sum of the lengths of the documents placed.
"""

from __future__ import annotations

import numpy as np

from benchmarks.traffic.generators import Pool, rng_for
from benchmarks.traffic.generators.mlm_documents import (
    _lengths_stream, first_fit)


def generate(params: dict, *, seed: int, global_batch: int) -> Pool:
    seq_len = int(params["seq_len"])
    vocab = int(params["vocab_size"])
    len_rng = rng_for(seed, "doc_lengths")
    tok_rng = rng_for(seed, "tokens")
    batches, real, docs_per_row = [], [], []
    carried: list[int] = []
    for _ in range(int(params["pool_batches"])):
        placed, carried = first_fit(
            _lengths_stream(params["doc_length"], len_rng, carried),
            global_batch, seq_len, int(params["close_after_misses"]))
        segment_ids = np.zeros((global_batch, seq_len), np.int32)
        positions = np.zeros((global_batch, seq_len), np.int32)
        last = np.zeros((global_batch, seq_len), bool)
        for r, docs in enumerate(placed):
            pos = 0
            for j, n in enumerate(docs):
                segment_ids[r, pos:pos + n] = j + 1
                positions[r, pos:pos + n] = np.arange(n)
                last[r, pos + n - 1] = True
                pos += n
        is_real = segment_ids > 0
        tokens = np.where(is_real, tok_rng.integers(
            0, vocab, size=(global_batch, seq_len), dtype=np.int32), 0)
        targets = np.where(is_real & ~last, np.roll(tokens, -1, axis=1), -1)
        batches.append({
            "input_ids": tokens.astype(np.int32),
            "targets": targets.astype(np.int32),
            "segment_ids": segment_ids,
            "positions": positions,
        })
        real.append(int(is_real.sum()))
        docs_per_row.append(sum(len(d) for d in placed) / global_batch)
    fill = sum(real) / (len(batches) * global_batch * seq_len)
    return Pool(batches=tuple(batches), real_units=tuple(real), unit="tokens",
                facts={"fill": fill, "row_tokens": global_batch * seq_len,
                       "documents_per_row": sum(docs_per_row)
                       / len(docs_per_row)})
