"""Visited over causal (q-block, k-block) visits of the window layers'
attention kernels: the program's ``attn_window_block_share`` counter
(``models/lfm2.py``: at the tiles ``select_dispatch`` chose, the share of
a causal call's block visits that hold a pair inside the window; a
static count for rows of one document, 140 of 272 on 512 x 1024 tiles at
16,384 with a window of 4096), mean over the steps the window fetched.
The rest are visits the kernels skip: lower is less work for the same
result. Finer tiles lower it toward the pairs' own share (0.4375)."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "attention kernels"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"


@loop_timeline.reader
def read(r):
    return scope_times.mean_counter(__file__, r, "attn_window_block_share")
