"""``train.py`` — the single entrypoint (SURVEY.md §2 row 1 / §3.1).

The reference's train.py parses role flags (--job_name, --task_index,
--ps_hosts, --worker_hosts) and dispatches PS vs worker; here every process
runs the same program:

    python train.py --config configs/lenet_mnist.yaml \
        [--set train.total_steps=100 --set mesh.data=8] [--eval-only]

Multi-host jobs launch the identical command on every host (topology is
discovered, not configured).
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

from distributed_tensorflow_framework_tpu.core import platform, supervision
from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.metrics import setup_logging


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", type=str, default=None, help="YAML config path")
    p.add_argument(
        "--set",
        dest="overrides",
        action="append",
        default=[],
        metavar="key.path=value",
        help="config override (repeatable)",
    )
    p.add_argument("--eval-only", action="store_true",
                   help="restore latest checkpoint and evaluate")
    p.add_argument("--print-config", action="store_true",
                   help="print the resolved config (YAML + --set overrides "
                        "+ defaults) as YAML and exit without touching "
                        "devices — the debugging aid for multi-host runs "
                        "where every host must resolve identically")
    return p.parse_args(argv)


def main(argv=None) -> int:
    setup_logging()
    platform.apply_cpu_collective_timeouts()
    args = parse_args(argv)
    overrides = list(args.overrides)
    # Elastic refit (core/supervision.py): the supervisor passes the
    # fitted mesh / rescaled batch through the environment because the
    # child command line may be opaque to it (e.g. a `python -c` driver
    # with a hardcoded argv). Env overrides append AFTER the CLI's so
    # the refit wins.
    elastic = os.environ.get(supervision.ELASTIC_OVERRIDES_ENV, "")
    if elastic:
        extra = [e.strip() for e in elastic.split(",") if e.strip()]
        logging.getLogger(__name__).warning(
            "applying elastic overrides from %s: %s",
            supervision.ELASTIC_OVERRIDES_ENV, " ".join(extra),
        )
        overrides += extra
    config = load_config(args.config, overrides=overrides)
    if args.print_config:
        import yaml

        print(yaml.safe_dump(config.to_dict(), sort_keys=False))
        return 0
    # Before the Trainer touches a backend: cached executables from the
    # previous attempt turn the relaunch recompile into a disk read.
    platform.resolve_compilation_cache()
    from distributed_tensorflow_framework_tpu.core.mesh import MeshSizeError
    from distributed_tensorflow_framework_tpu.train import Trainer

    try:
        trainer = Trainer(config)
        trainer.build()
    except MeshSizeError as e:
        # The configured mesh no longer fits the visible device set —
        # a slice was lost (or regained). Leave a device report for the
        # supervisor and exit the distinct elastic rc: the supervisor
        # refits the mesh axes (supervision.fit_axis_sizes), rescales
        # the batch, and relaunches with checkpoint.allow_reshard on —
        # WITHOUT consuming a restart-budget attempt (rc contract in
        # scripts/train_resilient.py; docs/RESILIENCE.md).
        logging.getLogger(__name__).error(
            "mesh does not fit the visible device set — exiting rc=%d "
            "for an elastic refit: %s", supervision.ELASTIC_RESHARD_RC, e,
        )
        if config.checkpoint.directory:
            supervision.write_device_report(
                config.checkpoint.directory,
                visible_devices=e.available,
                needed=e.needed,
                mesh=e.sizes,
            )
        return supervision.ELASTIC_RESHARD_RC
    if args.eval_only:
        results = trainer.evaluate()
        logging.getLogger(__name__).info("eval results: %s", results)
        return 0
    # Graceful preemption (docs/RESILIENCE.md): SIGTERM lets the loop
    # finish its in-flight step and save a checkpoint, then the process
    # exits GRACEFUL_PREEMPT_RC — the supervisor relaunches immediately
    # without consuming an attempt. A second SIGTERM kills outright.
    # trainer.train() only returns after the checkpoint manager's exit
    # barrier, so with async_save on the rc-83 exit below can never race
    # an in-flight background commit.
    supervision.install_sigterm_handler()
    try:
        final = trainer.train()
    except Exception as e:
        from distributed_tensorflow_framework_tpu.train.anomaly import (
            PersistentAnomalyError)

        if isinstance(e, PersistentAnomalyError):
            # The in-process recovery ladder is exhausted: this is a
            # poisoned data region or deterministic numeric bug, not a
            # transient. The distinct rc lets the supervisor classify it
            # WITHOUT feeding the crash-loop breaker (relaunching into the
            # same region would burn the whole budget for nothing).
            logging.getLogger(__name__).error(
                "persistent anomaly — escalating with rc=%d: %s "
                "(provenance: %s)",
                supervision.ANOMALY_ESCALATION_RC, e, e.provenance,
            )
            return supervision.ANOMALY_ESCALATION_RC
        raise
    if trainer.preempted:
        logging.getLogger(__name__).warning(
            "preempted gracefully at step %d (checkpoint saved: %s) — "
            "exiting rc=%d for immediate relaunch",
            trainer.host_step, bool(trainer.config.checkpoint.directory),
            supervision.GRACEFUL_PREEMPT_RC,
        )
        return supervision.GRACEFUL_PREEMPT_RC
    if trainer.config.train.eval_steps > 0:
        results = trainer.evaluate(step=trainer.host_step)
        logging.getLogger(__name__).info("final eval: %s", results)
    logging.getLogger(__name__).info("final train metrics: %s", final)
    return 0


if __name__ == "__main__":
    sys.exit(main())
