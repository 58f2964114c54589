"""From a cell's data files to the program's configuration and traffic.

Shared by the run (``runner.py``) and by the ahead-of-time compile
rehearsal (``tools/aot_compile.py``), so that both build the same step.
"""

from __future__ import annotations

import itertools
import os

from benchmarks.harness import manifest

# What every cell fixes on top of the shipped YAML and its own overrides:
# the normal path, with nothing that is not the step loop itself.
FIXED_OVERRIDES = (
    "train.log_interval=10",      # the StepTimer phases reach a hook only then
    "train.eval_interval=0",
    "train.eval_steps=0",         # no eval step is built or compiled
    "train.total_steps=1000000000",  # out of reach: the window ends the run
    "checkpoint.directory=",      # no checkpoint, so no final save
)

_dataset_ids = itertools.count()


def overrides_for(cell, *, seed: int, dataset_name: str,
                  extra: tuple = ()) -> list:
    traffic = cell.traffic
    out = [*cell.config.get("overrides", ()), *cell.workload.get("overrides", ()),
           *FIXED_OVERRIDES,
           f"data.name={dataset_name}",
           f"data.global_batch_size={cell.workload['per_chip_batch'] * cell.chips}",
           f"data.seed={seed}", f"train.seed={seed}"]
    out.append(f"mesh.data={cell.chips}")
    for key in ("image_size", "channels", "num_classes", "seq_len",
                "vocab_size", "mask_prob"):
        if key in traffic:
            out.append(f"data.{key}={traffic[key]}")
    return [*out, *extra]


def config_loader(cell, root: str, *, seed: int, dataset_name: str,
                  extra: tuple = ()):
    """``load(more_overrides) -> ExperimentConfig``: the shipped YAML, the
    cell's overrides, then ``more_overrides``."""
    from distributed_tensorflow_framework_tpu.core.config import load_config

    yaml_path = os.path.join(root, cell.config["shipped_yaml"])
    base = overrides_for(cell, seed=seed, dataset_name=dataset_name,
                         extra=extra)

    def load(more=()):
        return load_config(yaml_path, overrides=[*base, *more])

    return load


def make_pool(cell, root: str, *, seed: int):
    gen = manifest.load_generator(root, cell.traffic["generator"])
    return gen.generate(
        cell.traffic, seed=seed,
        global_batch=cell.workload["per_chip_batch"] * cell.chips)


def register_pool(pool) -> str:
    """Hand the pool to the program through its own extension point,
    ``data.register_dataset``, under a name of the benchmark's own. The
    loop's real infeed then moves every batch to the device each step."""
    from distributed_tensorflow_framework_tpu import data
    from distributed_tensorflow_framework_tpu.data import shard
    from distributed_tensorflow_framework_tpu.data.pipeline import HostDataset

    name = f"benchmark_traffic_{next(_dataset_ids)}"

    @data.register_dataset(name)
    def factory(config, process_index, process_count, *, train=True):
        if process_count != 1:
            raise ValueError("the benchmark drives one process")

        def make_iter(state):
            state.setdefault("i", 0)
            while True:
                batch = pool.batches[state["i"] % len(pool.batches)]
                state["i"] += 1
                yield batch

        return HostDataset(make_iter, element_spec=pool.element_spec(),
                           initial_state={"i": 0},
                           repartition=shard.REPARTITION_INVARIANT)

    return name
