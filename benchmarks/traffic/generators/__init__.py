"""Traffic generator kinds, found by name from a mix's ``"generator"``.

A kind is a module with one function::

    generate(params, *, seed, global_batch) -> Pool

``params`` is the mix's JSON object. The pool is what the run cycles
through the program's real infeed: host batches made once, during
set-up, from the seed alone.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Pool:
    batches: tuple            # of dict[str, np.ndarray], one global batch each
    real_units: tuple         # real (non-pad) tokens, or images, per batch
    unit: str                 # "tokens" | "images"
    facts: dict               # what the generator wants on record (fill, ...)

    def element_spec(self) -> dict:
        return {k: (tuple(v.shape), v.dtype)
                for k, v in self.batches[0].items()}


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """One independent stream per purpose, from the run's seed alone."""
    return np.random.default_rng(
        [int(seed), *(ord(c) for c in stream)])
