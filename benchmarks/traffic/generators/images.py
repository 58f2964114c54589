"""Image-classification batches: normal pixels, uniform labels.

Parameters: ``image_size``, ``channels``, ``num_classes``, ``dtype``
(``bfloat16`` | ``float32``), ``pool_batches``, ``drawn_batches``. Every
image is real (no padding), so a batch's unit count is its row count.

Drawing 19M normals costs a host core about a second, and every run of
every check pays set-up, so only ``drawn_batches`` batches are drawn;
batch ``i`` of the pool is drawn batch ``i % drawn_batches`` with its
images permuted and every image rolled ``i`` rows down. The batches are
distinct arrays with distinct labels, each moved to the device by the
real infeed; what a convolution costs does not depend on pixel values.
"""

from __future__ import annotations

import numpy as np

from benchmarks.traffic.generators import Pool, rng_for


def generate(params: dict, *, seed: int, global_batch: int) -> Pool:
    size, ch = int(params["image_size"]), int(params["channels"])
    n = int(params["pool_batches"])
    rng = rng_for(seed, "images")
    drawn = rng.standard_normal(
        (int(params["drawn_batches"]), global_batch, size, size, ch),
        dtype=np.float32)
    if params["dtype"] == "bfloat16":
        import ml_dtypes

        drawn = drawn.astype(ml_dtypes.bfloat16)
    elif params["dtype"] != "float32":
        raise ValueError(f"unknown image dtype {params['dtype']!r}")
    label_rng = rng_for(seed, "labels")
    batches = []
    for i in range(n):
        base = drawn[i % len(drawn)]
        if i >= len(drawn):
            base = np.roll(base[label_rng.permutation(global_batch)], i,
                           axis=1)
        batches.append({
            "image": np.ascontiguousarray(base),
            "label": label_rng.integers(0, int(params["num_classes"]),
                                        size=global_batch, dtype=np.int32),
        })
    return Pool(batches=tuple(batches), real_units=(global_batch,) * n,
                unit="images",
                facts={"bytes_per_batch": int(batches[0]["image"].nbytes)})
