"""Masked-LM batches from documents of drawn lengths.

Parameters (the mix's JSON):

- ``seq_len``: row length.
- ``doc_length``: ``{"dist": "lognormal", "median", "sigma", "min",
  "max"}`` or ``{"dist": "fixed", "value"}``, in tokens.
- ``pack``: first-fit several documents into one row and emit
  ``segment_ids`` (1, 2, ... per document, 0 on padding), or one
  document per row.
- ``close_after_misses``: packing gives up on a batch after this many
  documents in a row found no room; they open the next batch, in order.
- ``mask_prob``, ``mask_token_id``, ``token_id_min``, ``vocab_size``:
  BERT's masking (arXiv:1810.04805 §3.1): each real token is chosen with
  ``mask_prob``; of the chosen, 80% become ``[MASK]``, 10% a random
  token, 10% stay. ``targets`` holds the original id there, -1 elsewhere.
- ``pool_batches``: distinct batches made; the run cycles them.

No document is ever split. A batch's real-token count is exact: the sum
of the lengths of the documents placed in it.
"""

from __future__ import annotations

import numpy as np

from benchmarks.traffic.generators import Pool, rng_for


def draw_lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown doc_length.dist {spec['dist']!r}")


def first_fit(lengths, rows: int, seq_len: int, close_after_misses: int):
    """Place ``lengths`` (an iterator) into ``rows`` rows of ``seq_len``.

    Returns ``(placed, carried)``: ``placed[r]`` lists the lengths put in
    row ``r`` in order, ``carried`` the documents drawn that found no
    room, to be offered first to the next batch.
    """
    free = [seq_len] * rows
    placed: list[list[int]] = [[] for _ in range(rows)]
    carried: list[int] = []
    misses = 0
    for n in lengths:
        if n > seq_len:
            raise ValueError(f"document of {n} tokens exceeds row {seq_len}")
        for r in range(rows):
            if free[r] >= n:
                free[r] -= n
                placed[r].append(n)
                misses = 0
                break
        else:
            carried.append(n)
            misses += 1
            if misses >= close_after_misses:
                break
        if not any(free):
            break
    return placed, carried


def _lengths_stream(spec, rng, carried):
    yield from carried
    while True:
        yield from draw_lengths(spec, rng, 256).tolist()


def generate(params: dict, *, seed: int, global_batch: int) -> Pool:
    seq_len = int(params["seq_len"])
    vocab = int(params["vocab_size"])
    lo = int(params["token_id_min"])
    mask_id = int(params["mask_token_id"])
    pack = bool(params["pack"])
    len_rng = rng_for(seed, "doc_lengths")
    tok_rng = rng_for(seed, "tokens")
    batches, real = [], []
    carried: list[int] = []
    for _ in range(int(params["pool_batches"])):
        if pack:
            placed, carried = first_fit(
                _lengths_stream(params["doc_length"], len_rng, carried),
                global_batch, seq_len, int(params["close_after_misses"]))
        else:
            placed = [[int(n)] for n in draw_lengths(
                params["doc_length"], len_rng, global_batch)]
        segment_ids = np.zeros((global_batch, seq_len), np.int32)
        for r, docs in enumerate(placed):
            pos = 0
            for j, n in enumerate(docs):
                segment_ids[r, pos:pos + n] = j + 1
                pos += n
        is_real = segment_ids > 0
        tokens = tok_rng.integers(lo, vocab, size=(global_batch, seq_len),
                                  dtype=np.int32)
        chosen = (tok_rng.random((global_batch, seq_len))
                  < params["mask_prob"]) & is_real
        how = tok_rng.random((global_batch, seq_len))
        random_tok = tok_rng.integers(lo, vocab, size=(global_batch, seq_len),
                                      dtype=np.int32)
        input_ids = np.where(chosen & (how < 0.8), mask_id, tokens)
        input_ids = np.where(chosen & (how >= 0.9), random_tok, input_ids)
        input_ids = np.where(is_real, input_ids, 0).astype(np.int32)
        batch = {
            "input_ids": input_ids,
            "targets": np.where(chosen, tokens, -1).astype(np.int32),
            "attention_mask": is_real.astype(np.int32),
        }
        if pack:
            batch["segment_ids"] = segment_ids
        batches.append(batch)
        real.append(int(is_real.sum()))
    fill = sum(real) / (len(batches) * global_batch * seq_len)
    return Pool(batches=tuple(batches), real_units=tuple(real), unit="tokens",
                facts={"fill": fill, "row_tokens": global_batch * seq_len})
