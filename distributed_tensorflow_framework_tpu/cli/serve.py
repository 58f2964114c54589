"""``python -m distributed_tensorflow_framework_tpu.cli.serve`` — stand up
the batched-inference server on an exported artifact.

    python -m distributed_tensorflow_framework_tpu.cli.serve \
        --artifact /runs/lenet_artifact \
        [--set serve.port=8000 --set serve.max_batch_size=16 \
         --set serve.seq_buckets=[32,64,128]]

Everything about the standing engine is a ``serve.*`` knob (the model
itself comes from the artifact, so ``--config`` is optional and only
consulted for the serve block). The process serves until SIGTERM, then
drains in-flight requests and exits 0 — the same graceful-preemption
contract the trainer honors. The resolved endpoint (ephemeral ports
included) is written to ``<log_dir>/endpoint.json`` for tooling like
scripts/load_gen.py.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys

from distributed_tensorflow_framework_tpu.core import platform
from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.metrics import setup_logging

log = logging.getLogger(__name__)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--artifact", type=str, default=None,
                   help="artifact directory from cli/export.py (overrides "
                        "serve.artifact_dir)")
    p.add_argument("--config", type=str, default=None,
                   help="optional YAML config (serve.* block)")
    p.add_argument(
        "--set", dest="overrides", action="append", default=[],
        metavar="key.path=value", help="config override (repeatable)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    setup_logging()
    platform.apply_cpu_collective_timeouts()
    args = parse_args(argv)
    config = load_config(args.config, overrides=list(args.overrides))
    srv = config.serve
    artifact_dir = args.artifact or srv.artifact_dir
    if not artifact_dir:
        log.error("no artifact: pass --artifact or set serve.artifact_dir")
        return 2
    platform.resolve_compilation_cache()

    from distributed_tensorflow_framework_tpu.core import telemetry, tracing
    from distributed_tensorflow_framework_tpu.core.mesh import device_record
    from distributed_tensorflow_framework_tpu.serve.engine import (
        InferenceEngine,
    )
    from distributed_tensorflow_framework_tpu.serve.export import (
        load_artifact,
    )
    from distributed_tensorflow_framework_tpu.serve.server import (
        ServingServer,
    )

    artifact = load_artifact(artifact_dir)
    log_dir = srv.log_dir or os.path.join(artifact_dir, "serve_logs")
    os.makedirs(log_dir, exist_ok=True)
    writer = telemetry.TelemetryWriter(
        os.path.join(log_dir, "events.jsonl"))
    writer.emit_run_meta(
        argv=list(argv if argv is not None else sys.argv),
        config=config.name, role="serve", artifact=artifact_dir,
        model=artifact.model_config.name, step=artifact.step,
        **device_record())
    engine = InferenceEngine(artifact, srv, telemetry_writer=writer,
                             trace_enabled=config.trace.enabled)
    decode_engine = None
    if config.decode.enabled:
        from distributed_tensorflow_framework_tpu.models import (
            decode_support_reason,
        )
        from distributed_tensorflow_framework_tpu.serve.decode import (
            DecodeEngine,
        )

        reason = (None if artifact.task == "mlm"
                  else f"artifact task {artifact.task!r} has no vocabulary")
        reason = reason or decode_support_reason(artifact.model_config)
        if reason is not None:
            # decode.enabled on an unsupported artifact is a config error,
            # not a silent downgrade: fail before binding the port.
            log.error("decode.enabled but artifact cannot decode: %s",
                      reason)
            return 2
        decode_engine = DecodeEngine(
            artifact, config.decode, srv,
            mesh=engine.mesh, telemetry_writer=writer)
    # Flight recorder on the replica: ring of recent telemetry (spans
    # included), dumped on SIGUSR1 or by the fleet router observing this
    # process die (docs/OBSERVABILITY.md "Tracing and flight recorder").
    recorder = tracing.FlightRecorder(
        config.trace.ring_size,
        dump_dir=config.trace.dump_dir or log_dir,
        tracer=engine.tracer).attach(writer)
    recorder.install_sigusr1()
    server = ServingServer(engine, srv, decode_engine=decode_engine,
                           telemetry_writer=writer)
    # The resolved endpoint record: with serve.port=0 the OS picked the
    # port, so tooling polls this file instead of guessing.
    endpoint = {
        "url": f"http://{server.host}:{server.port}",
        "host": server.host, "port": server.port, "pid": os.getpid(),
        "artifact": os.path.abspath(artifact_dir),
        "events": os.path.join(log_dir, "events.jsonl"),
    }
    with open(os.path.join(log_dir, "endpoint.json"), "w") as fh:
        json.dump(endpoint, fh, indent=2)
        fh.write("\n")
    server.install_sigterm_drain()
    try:
        server.serve_forever()
    finally:
        writer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
