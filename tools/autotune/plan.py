"""The chip_window plan: scripts/chip_window_queue.sh compiled to data.

`autotune.py --plan chip_window` turns the round-5 measurement queue
(§0–§17, PERF_NOTES.md round-4 closeout) into a prioritized trial list
the search loop can journal, resume and supervise like any other trial
set. Priorities, per the queue's own rules:

  1. §0 preflight — a graftcheck finding refuses to spend the window
     at all (exit 1);
  2. §1 — re-validate BENCH_r02 (the last good chip number, 2513
     img/s/chip) before anything else, so a silent regression is caught
     while the whole window is still ahead;
  3. §13 precision ladder — the highest-information dial (the "flipping
     the bound" question);
  4. §7–§12, §14–§17 in section order;
  5. the remaining round-5 backlog (§2–§6) at the tail.

Multi-process arms (serve/fleet/decode/infeed) keep their original
orchestration — background server, load_gen, SIGTERM drain, analyze — as
single composite trials (bash -c), byte-for-byte the recipes the queue
script ran, so the A/B identities the window has been tracking survive
the compilation.
"""

from __future__ import annotations

import dataclasses
import sys

PY = sys.executable or "python"


@dataclasses.dataclass(frozen=True)
class PlannedTrial:
    """One queue arm: ``section`` is the chip_window_queue § it came
    from, ``gate`` names a trial that must succeed first (numerics
    verifies, exports), ``kind`` separates preflights (whose failure
    refuses the window) from ordinary trials."""

    section: str
    label: str
    argv: tuple
    env: tuple = ()          # ((name, value), ...) — hashable
    gate: str = ""
    kind: str = "trial"      # "preflight" | "trial"

    def env_dict(self) -> dict[str, str]:
        return dict(self.env)


def _bench(section, label, gate="", **env) -> PlannedTrial:
    return PlannedTrial(section, label, (PY, "bench.py"),
                        tuple((k, str(v)) for k, v in env.items()),
                        gate=gate)


def _script(section, label, argv, gate="", **env) -> PlannedTrial:
    return PlannedTrial(section, label, tuple(argv),
                        tuple((k, str(v)) for k, v in env.items()),
                        gate=gate)


def _composite(section, label, script, gate="") -> PlannedTrial:
    """A multi-process arm as one bash trial (original queue recipe)."""
    return PlannedTrial(section, label, ("bash", "-c", script), gate=gate)


_SERVE_AB = """
set -u
rm -rf /tmp/chipq_serve/artifact/serve_logs
python -m distributed_tensorflow_framework_tpu.cli.serve \\
    --artifact /tmp/chipq_serve/artifact \\
    --set serve.port=0 --set serve.max_batch_size={batch} \\
    --set serve.max_wait_ms=5 > /tmp/chipq_serve_{label}.log 2>&1 &
pid=$!
for _ in $(seq 120); do
  [ -f /tmp/chipq_serve/artifact/serve_logs/endpoint.json ] && break
  sleep 1
done
python scripts/load_gen.py \\
    --endpoint /tmp/chipq_serve/artifact/serve_logs/endpoint.json \\
    --requests 512 --concurrency 32 --rate 200 --mode both \\
    --out SERVE_BENCH_{label}.json
rc=$?
kill -TERM "$pid" 2>/dev/null
wait "$pid"
python scripts/analyze_trace.py /tmp/chipq_serve/artifact/serve_logs/events.jsonl
exit $rc
"""

_FLEET_AB = """
set -u
python -m distributed_tensorflow_framework_tpu.cli.fleet \\
    --artifact /tmp/chipq_serve/artifact --replicas 3 \\
    --set serve.log_dir=/tmp/chipq_fleet \\
    --set serve.max_batch_size=8 --set serve.max_wait_ms=5 \\
    > /tmp/chipq_fleet.log 2>&1 &
pid=$!
for _ in $(seq 240); do
  [ -f /tmp/chipq_fleet/endpoint.json ] && break
  sleep 1
done
python scripts/load_gen.py \\
    --endpoint /tmp/chipq_fleet/endpoint.json \\
    --requests 512 --concurrency 32 --rate 200 --mode both \\
    --out SERVE_BENCH_fleet.json
rc=$?
kill -TERM "$pid" 2>/dev/null
wait "$pid"
python scripts/analyze_trace.py /tmp/chipq_fleet/events.jsonl
exit $rc
"""

_DECODE_AB = """
set -u
python -m distributed_tensorflow_framework_tpu.cli.serve \\
    --artifact /tmp/chipq_decode/artifact \\
    --set serve.port=0 \\
    --set serve.log_dir=/tmp/chipq_decode/logs_{label} \\
    --set decode.enabled=true --set decode.max_len=128 \\
    --set decode.page_size=16 --set decode.num_pages=256 \\
    --set decode.max_streams=8 --set decode.max_new_tokens=96 \\
    --set decode.stream_interval=8 {extra} \\
    > /tmp/chipq_decode_{label}.log 2>&1 &
pid=$!
for _ in $(seq 120); do
  [ -f /tmp/chipq_decode/logs_{label}/endpoint.json ] && break
  sleep 1
done
python scripts/load_gen.py \\
    --endpoint /tmp/chipq_decode/logs_{label}/endpoint.json \\
    --mode decode --requests 64 --concurrency 8 \\
    --max-new-tokens 96 --out DECODE_BENCH_{label}.json
rc=$?
kill -TERM "$pid" 2>/dev/null
wait "$pid"
exit $rc
"""

_INFEED_AB = """
set -u
rm -rf /tmp/chipq_infeed/{label}
python train.py --config configs/bert_base_mlm.yaml \\
    --set data.name=synthetic_mlm --set train.total_steps=100 \\
    --set train.log_interval=25 --set train.eval_steps=0 \\
    --set train.eval_interval=0 \\
    --set model.hidden_size=256 --set model.num_layers=4 \\
    --set model.num_heads=4 --set model.mlp_dim=1024 \\
    --set model.max_seq_len=512 --set data.seq_len=512 \\
    --set data.global_batch_size=32 \\
    --set checkpoint.directory=/tmp/chipq_infeed/{label} {extra} || exit $?
python scripts/analyze_trace.py /tmp/chipq_infeed/{label}
"""

_GANG_PROBE = (
    "import sys\n"
    "from distributed_tensorflow_framework_tpu.core import cluster\n"
    "ok, detail = cluster.probe_gang(procs=2, devices_per_proc=2)\n"
    "if not ok:\n"
    "    print(detail[-800:], file=sys.stderr)\n"
    "sys.exit(0 if ok else 1)\n")


def _gang_run(workdir, procs, dev, ckpt) -> tuple:
    return (PY, "scripts/train_cluster.py",
            "--procs", str(procs), "--devices-per-proc", str(dev),
            "--workdir", workdir, "--max-attempts", "1", "--",
            "--config", "configs/lenet_mnist.yaml",
            "--set", "train.total_steps=200", "--set",
            "train.log_interval=50", "--set", "train.eval_steps=0",
            "--set", "train.eval_interval=0",
            "--set", "data.global_batch_size=32", "--set", "mesh.data=-1",
            "--set", f"checkpoint.directory={ckpt}")


_SERVE_TRAIN = (
    PY, "train.py", "--config", "configs/lenet_mnist.yaml",
    "--set", "data.name=synthetic_images", "--set", "train.total_steps=30",
    "--set", "checkpoint.directory=/tmp/chipq_serve/ckpt",
    "--set", "checkpoint.save_interval_steps=30",
    "--set", "checkpoint.async_save=false")

_SERVE_EXPORT = (
    PY, "-m", "distributed_tensorflow_framework_tpu.cli.export",
    "--config", "configs/lenet_mnist.yaml",
    "--set", "data.name=synthetic_images",
    "--set", "checkpoint.directory=/tmp/chipq_serve/ckpt",
    "--set", "serve.allow_reshard=true",
    "--output", "/tmp/chipq_serve/artifact")

_DECODE_SHAPES = (
    "--set", "data.name=synthetic_mlm",
    "--set", "model.hidden_size=256", "--set", "model.num_layers=4",
    "--set", "model.num_heads=4", "--set", "model.mlp_dim=1024",
    "--set", "model.max_seq_len=128", "--set", "data.seq_len=128")

_DECODE_TRAIN = (
    (PY, "train.py", "--config", "configs/bert_base_mlm.yaml")
    + _DECODE_SHAPES
    + ("--set", "train.total_steps=30",
       "--set", "data.global_batch_size=32",
       "--set", "train.eval_steps=0", "--set", "train.eval_interval=0",
       "--set", "checkpoint.directory=/tmp/chipq_decode/ckpt",
       "--set", "checkpoint.save_interval_steps=30",
       "--set", "checkpoint.async_save=false"))

_DECODE_EXPORT = (
    (PY, "-m", "distributed_tensorflow_framework_tpu.cli.export",
     "--config", "configs/bert_base_mlm.yaml")
    + _DECODE_SHAPES
    + ("--set", "checkpoint.directory=/tmp/chipq_decode/ckpt",
       "--set", "serve.allow_reshard=true",
       "--output", "/tmp/chipq_decode/artifact"))


def compile_chip_window_plan() -> list[PlannedTrial]:
    """The full prioritized window (see module docstring for the order)."""
    trials: list[PlannedTrial] = []

    # §0 preflight: refuse to spend the window on a tree graftcheck
    # rejects.
    trials.append(PlannedTrial(
        "0", "graftcheck", (PY, "scripts/graftcheck.py"),
        (("JAX_PLATFORMS", "cpu"),), kind="preflight"))

    # §1: re-validate BENCH_r02 (the last good number) FIRST.
    trials.append(_bench("1", "resnet"))

    # §13 precision ladder — the priority dial.
    trials.append(_bench("13", "prec-f32", BENCH_PRECISION="f32"))
    trials.append(_bench("13", "prec-bf16", BENCH_PRECISION="bf16"))
    trials.append(_bench("13", "prec-bf16-fused",
                         BENCH_PRECISION="bf16_fused"))
    trials.append(_bench("13", "prec-bf16-int8",
                         BENCH_PRECISION="bf16_int8"))

    # §7 the lengths that pair the whole-K forward with the fused
    # backward: the numerics check alone (which backward wins there was
    # settled on the chip, PERF.md §6 PR 25, and the loser is gone).
    for seq, case in ((2048, "fused_takeover"),
                      (4096, "fused_takeover_max")):
        trials.append(_script(
            "7", f"wk-verify-{seq}",
            (PY, "scripts/verify_flash_kernels.py", case)))

    # §8 pipeline-schedule A/B (pp-sanity: one cheap default run first).
    trials.append(_bench("8", "pp-sanity"))
    for sched in ("gpipe", "1f1b", "interleaved"):
        trials.append(_bench(
            "8", f"pp-{sched}", BENCH_WORKLOAD="bert", BENCH_PP=4,
            BENCH_MICRO=8, BENCH_SCHEDULE=sched))

    # §9 quantized-collective wire A/B.
    for mode in ("f32", "bf16", "int8"):
        trials.append(_bench("9", f"coll-{mode}", BENCH_COLLECTIVE=mode))

    # §10 serving A/B: train → export gate the two standing-server arms.
    trials.append(_composite(
        "10", "serve-clean", "rm -rf /tmp/chipq_serve"))
    trials.append(_script("10", "serve-train", _SERVE_TRAIN,
                          gate="serve-clean"))
    trials.append(_script("10", "serve-export", _SERVE_EXPORT,
                          gate="serve-train"))
    for label, batch in (("batched", 8), ("unbatched", 1)):
        trials.append(_composite(
            "10", f"serve-{label}",
            _SERVE_AB.format(label=label, batch=batch),
            gate="serve-export"))

    # §11 ZeRO weight-update sharding A/B.
    for mode in ("off", "shard_map"):
        trials.append(_bench("11", f"zero-{mode}", BENCH_ZERO=mode))

    # §12 HBM memory close-out.
    trials.append(_bench("12", "mem-headline",
                         BENCH_JSONL="/tmp/chipq_mem_events.jsonl"))
    trials.append(_script(
        "12", "mem-summary",
        (PY, "scripts/analyze_trace.py", "/tmp/chipq_mem_events.jsonl",
         "--json", "-"), gate="mem-headline"))

    # §14 fleet-vs-single serving A/B (reuses §10's artifact).
    trials.append(_composite("14", "serve-fleet", _FLEET_AB,
                             gate="serve-export"))

    # §15 gang A/B, gated on its own probe_gang preflight.
    trials.append(_script("15", "gang-probe", (PY, "-c", _GANG_PROBE)))
    trials.append(_composite("15", "gang-clean", "rm -rf /tmp/chipq_gang",
                             gate="gang-probe"))
    trials.append(_script(
        "15", "gang-1p",
        _gang_run("/tmp/chipq_gang/w1", 1, 4, "/tmp/chipq_gang/ck1"),
        gate="gang-clean"))
    trials.append(_script(
        "15", "gang-2p",
        _gang_run("/tmp/chipq_gang/w2", 2, 2, "/tmp/chipq_gang/ck2"),
        gate="gang-clean"))
    trials.append(_script(
        "15", "gang-ab",
        (PY, "scripts/analyze_trace.py", "/tmp/chipq_gang/ck1"),
        gate="gang-1p"))
    trials.append(_script(
        "15", "gang-ab-2p",
        (PY, "scripts/analyze_trace.py", "/tmp/chipq_gang/ck2"),
        gate="gang-2p"))

    # §16 decode A/Bs: artifact build gates the three standing-server arms.
    trials.append(_composite("16", "decode-clean",
                             "rm -rf /tmp/chipq_decode"))
    trials.append(_script("16", "decode-train", _DECODE_TRAIN,
                          gate="decode-clean"))
    trials.append(_script("16", "decode-export", _DECODE_EXPORT,
                          gate="decode-train"))
    for label, extra in (
            ("continuous", "--set decode.scheduler=continuous"),
            ("static", "--set decode.scheduler=static"),
            ("int8", "--set decode.scheduler=continuous "
                     "--set decode.kv_dtype=int8")):
        trials.append(_composite(
            "16", f"decode-{label}",
            _DECODE_AB.format(label=label, extra=extra),
            gate="decode-export"))

    # §17 infeed A/B: packing + shard-mode dials.
    for label, extra in (
            ("unpacked", "--set data.pack_factor=1"),
            ("packed", "--set data.pack_factor=4"),
            ("block", "--set data.pack_factor=4 "
                      "--set data.shard_mode=block"),
            ("stride", "--set data.pack_factor=4 "
                       "--set data.shard_mode=stride")):
        trials.append(_composite(
            "17", f"infeed-{label}",
            _INFEED_AB.format(label=label, extra=extra)))

    # Round-5 backlog tail (§2–§6), original order.
    trials.append(_bench("2", "bert-base", BENCH_WORKLOAD="bert"))
    trials.append(_bench("2", "bert-fqkv", BENCH_WORKLOAD="bert",
                         BENCH_FUSED_QKV=1))
    for q in (512, 1024):
        trials.append(_bench(
            "3", f"tile-{q}-1024", BENCH_WORKLOAD="bert",
            BENCH_ATTN="pallas", BENCH_SEQ=8192, BENCH_BS=4,
            FLASH_BLOCK_Q_KB=q, FLASH_BLOCK_K_KB=1024))
    trials.append(_script(
        "4", "crossover",
        (PY, "scripts/bench_chunk_crossover.py", "256", "512", "1024",
         "2048", "4096")))
    trials.append(_script(
        "4b", "fused-bwd-verify", (PY, "scripts/verify_flash_kernels.py",
                                   "kblocked", "fused")))
    trials.append(_bench(
        "4b", "fused-bwd", gate="fused-bwd-verify", BENCH_WORKLOAD="bert",
        BENCH_ATTN="pallas", BENCH_SEQ=8192, BENCH_BS=4))
    trials.append(_bench("4c", "bert-accum4", BENCH_WORKLOAD="bert",
                         BENCH_ACCUM=4))
    trials.append(_bench("5", "trace", BENCH_TRACE="/tmp/bench_trace"))
    trials.append(_bench("6", "inception", BENCH_WORKLOAD="inception"))
    return trials


def format_plan(trials: list[PlannedTrial]) -> str:
    """The --dry-run rendering: one line per trial, parseable
    (``NNN §SEC LABEL [kind] [gate=...] -- ENV.. ARGV..``)."""
    lines = []
    for i, t in enumerate(trials, 1):
        envs = " ".join(f"{k}={v}" for k, v in t.env)
        # Composite arms carry multi-line ``bash -c`` scripts; one trial
        # must stay one parseable line, so collapse to the first line.
        args = [a.splitlines()[0] + " \\..." if "\n" in a else a
                for a in t.argv]
        cmd = " ".join(args[:4]) + (" ..." if len(args) > 4 else "")
        bits = [f"{i:03d}", f"§{t.section}", t.label, f"[{t.kind}]"]
        if t.gate:
            bits.append(f"gate={t.gate}")
        bits.append("--")
        if envs:
            bits.append(envs)
        bits.append(cmd)
        lines.append(" ".join(bits))
    return "\n".join(lines)
