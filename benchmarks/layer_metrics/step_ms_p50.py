"""Median step time: the time between two of the loop's own syncs (its
metrics fetch every ``log_interval`` steps) divided by the steps
between them, median over the window's blocks outside the traced
stretch."""

LAYER = "train loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(r):
    blocks = r.window["step_ms_blocks"]
    if not blocks:
        return None
    mid = len(blocks) // 2
    return blocks[mid] if len(blocks) % 2 else 0.5 * (blocks[mid - 1] + blocks[mid])
