"""Operations and bytes from shapes: what the model's forward and
backward passes require, not what a compiled program happens to do.
Recomputation, padding and the optimizer do not count. Matrix
multiplications only (a multiply-add is 2 operations); normalisation,
softmax and activation arithmetic is left out, as is usual for a model
FLOP/s utilization.

A family module has ``train_flops(batch, hparams) -> float`` for one
host batch of the pool (forward + backward = 3 x forward).
"""
