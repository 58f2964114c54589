"""``python -m distributed_tensorflow_framework_tpu.cli.export`` — freeze
a trained checkpoint into a serving artifact.

    python -m distributed_tensorflow_framework_tpu.cli.export \
        --config configs/lenet_mnist.yaml --output /runs/lenet_artifact \
        [--step 900] [--set serve.allow_reshard=true]

The config names the training run (``checkpoint.directory``) and the
serving mesh (``serve.data``); a checkpoint saved under a different mesh
needs ``serve.allow_reshard`` (the error says so). docs/SERVING.md
covers the artifact layout.
"""

from __future__ import annotations

import argparse
import logging
import sys

from distributed_tensorflow_framework_tpu.core import platform
from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.metrics import setup_logging


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", type=str, default=None, help="YAML config path")
    p.add_argument(
        "--set", dest="overrides", action="append", default=[],
        metavar="key.path=value", help="config override (repeatable)")
    p.add_argument("--output", type=str, required=True,
                   help="artifact directory to create (must not exist "
                        "non-empty — artifacts are immutable)")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step to export (default: latest "
                        "committed)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    setup_logging()
    platform.apply_cpu_collective_timeouts()
    args = parse_args(argv)
    config = load_config(args.config, overrides=list(args.overrides))
    platform.resolve_compilation_cache()
    from distributed_tensorflow_framework_tpu.serve.export import (
        export_checkpoint,
    )

    path = export_checkpoint(config, args.output, step=args.step)
    logging.getLogger(__name__).info("artifact ready: %s", path)
    print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
