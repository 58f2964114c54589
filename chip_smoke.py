#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main paths once, through the entry points a user would call, at
the full width of the models the repo ships (depth as shipped, weights random
from a seed, batches synthetic from a seed; no network, no git):

  device    JAX's default backend is a TPU whose ``device_kind`` is in
            ``core/roofline.CHIP_PEAKS``; versions printed
  bert      ``train.py --config configs/bert_base_mlm.yaml`` as shipped (12
            layers, hidden 768, seq 512, bf16, pallas attention, fused qkv,
            AdamW), per-chip batch 32, 8 steps, a checkpoint save inside the
            run, a short eval
  resnet    ``train.py --config configs/resnet50_imagenet.yaml``, per-chip
            batch 128, 8 steps, save, eval
  lfm2      ``train.py --config configs/lfm2_8b_a1b.yaml`` cut to one
            chip's share (published widths; 8 of 32 experts, 16384 vocabulary
            rows, one dense and one attention-expert layer), the
            ``causal_lm`` task, rows of 8192 packed tokens, 4 steps: the
            causal grouped-query kernels, the dropless expert layer
            (``moe_dropped`` must read 0) and the short convolutions
  kernels   ``scripts/verify_flash_kernels.py``: all five flash-attention
            kernels compiled by Mosaic and held to a float32 reference on
            bf16 and on float32 inputs, from a sequence under one tile up,
            the fused backward also to the two-pass backward, and the causal
            grouped-query calls under both forwards
  export    ``cli/export.py`` freezes the checkpoint the bert leg saved
  serve     ``cli/serve.py`` with ``decode.enabled=true`` answers
            ``/predict`` and streamed ``/generate`` requests from
            ``scripts/load_gen.py``, then drains cleanly on SIGTERM, exit 0
  multichip (more than one chip only) ``scripts/multichip_check.py``: batch,
            attention kernel and gradients are split across the chips, and a
            ``shard_map`` + ``fsdp=2`` step runs

A chip belongs to one process at a time, so THIS process never imports JAX:
each leg is a child process, run to its end before the next starts, and its
verdict is read from its exit code and its records (``events.jsonl``, the
checkpoint manifest, ``SERVE_BENCH.json``). Any leg failing fails the run
with the leg named; nothing is caught and reported as a warning. The
platform is never overridden here: an inherited ``JAX_PLATFORMS=cpu``, or
JAX's own CPU fallback when libtpu fails to start, is a failure that says
so.

The last line of stdout is ``{"ok": true, "device": {...}}`` with the device
as JAX reported it to the device leg. Logs and the small records land in
``chiprun_out/chip_smoke/``; checkpoints and the artifact live in a temporary
directory that is removed at the end.
"""

from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
LOGS = ROOT / "chiprun_out" / "chip_smoke"
PY = sys.executable
PACKAGE = "distributed_tensorflow_framework_tpu"

# What a run on the chip looks like in the records; a run anywhere else
# differs in every one of these.
REQUIRED_PLATFORM = "tpu"
REQUIRED_KERNEL_MODE = "mosaic"
REQUIRED_MEMORY_SOURCE = "device_memory_stats"
STEPS = 8
BERT_PER_CHIP_BATCH = 32
RESNET_PER_CHIP_BATCH = 128
# Everything else about the two trained models is the shipped YAML.
BERT_OVERRIDES = ("data.name=synthetic_mlm",)
RESNET_OVERRIDES = ("data.name=synthetic_images",)
# LFM2-8B-A1B at its published widths, cut to what one chip of a four-way
# expert-parallel group holds of two layers (the dense short-convolution
# layer and one attention layer with experts): 8 of 32 experts, a quarter
# of the vocabulary. One row of 8192 packed tokens a chip.
LFM2_PER_CHIP_BATCH = 1
LFM2_OVERRIDES = ("model.num_layers=2",
                  "model.layer_types=[conv,full_attention]",
                  "model.num_dense_layers=1", "model.expert_groups=4",
                  "model.vocab_size=16384", "data.vocab_size=16384")
# /predict answers an MLM request with its full-vocabulary logits as JSON:
# 30522 floats a token, ~300 MB of text for one 512-token row, and ~20 s
# of interpreter time to encode it (first chip run, PR 21: 2 of 8 such
# requests outlived the 30 s deadline behind the others' encoding). Short
# buckets keep a smoke request to ~1M floats; the width is still full.
# The decode engine's bucket ladders bound its compiles likewise: a
# smoke needs a few streams, not the 512-token ladder.
SERVE_OVERRIDES = ("serve.seq_buckets=[16,32]", "decode.enabled=true",
                   "decode.max_len=128", "decode.max_new_tokens=16")

_DEVICE_LEG = """
import importlib.metadata as md, json, sys
import jax, jaxlib
from distributed_tensorflow_framework_tpu.core.roofline import CHIP_PEAKS
backend = jax.default_backend()
dev = jax.devices()[0]
versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
for pkg in ("libtpu", "flax", "orbax-checkpoint"):
    try:
        versions[pkg] = md.version(pkg)
    except md.PackageNotFoundError:
        versions[pkg] = None
print(f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
      f"{len(jax.devices())} device(s); " + ", ".join(
          f"{k} {v}" for k, v in versions.items()), flush=True)
if backend != sys.argv[1]:
    sys.exit(f"chip_smoke: JAX's default backend is {backend!r} "
             f"({dev.device_kind!r}), not {sys.argv[1]!r} — refusing to "
             f"smoke-test anything but the chip")
if dev.device_kind not in CHIP_PEAKS:
    sys.exit(f"chip_smoke: device_kind {dev.device_kind!r} is not in "
             f"core/roofline.CHIP_PEAKS {sorted(CHIP_PEAKS)}")
print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "versions": versions}))
"""


class LegFailed(Exception):
    pass


_live: list[subprocess.Popen] = []


def _stop(proc: subprocess.Popen) -> None:
    """Kill a child's whole process group and reap it."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if proc in _live:
        _live.remove(proc)


def _spawn(name: str, argv: list[str]) -> subprocess.Popen:
    """Start a child in its own process group, stdout and stderr to
    ``LOGS/<name>.out|.err``. The environment is inherited untouched but
    for JAX's compiler debug log, whose lines count the persistent
    compilation cache's hits and misses."""
    env = dict(os.environ, JAX_DEBUG_LOG_MODULES="jax._src.compiler")
    with open(LOGS / f"{name}.out", "w") as out, \
            open(LOGS / f"{name}.err", "w") as err:
        proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=out,
                                stderr=err, start_new_session=True)
    _live.append(proc)
    return proc


def _tail(name: str, n: int = 25) -> str:
    lines = []
    for ext in ("out", "err"):
        text = (LOGS / f"{name}.{ext}").read_text(errors="replace")
        lines += [f"  [{name}.{ext}] {ln}" for ln in text.splitlines()[-n:]]
    return "\n".join(lines)


def _wait(name: str, proc: subprocess.Popen, timeout: float) -> None:
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _stop(proc)
        raise LegFailed(f"timed out after {timeout:.0f}s\n{_tail(name)}")
    _live.remove(proc)
    if rc != 0:
        raise LegFailed(f"exit code {rc}\n{_tail(name)}")


def run_child(name: str, argv: list[str], timeout: float) -> str:
    """Run one child to its end; its stdout on success."""
    _wait(name, _spawn(name, argv), timeout)
    return (LOGS / f"{name}.out").read_text()


def _last_json(text: str) -> dict:
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise LegFailed("child printed no JSON result line")


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise LegFailed(what)


def _events(path: pathlib.Path) -> list[dict]:
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def _cache_counts(name: str) -> dict:
    """Persistent-compilation-cache hits and misses of one child, from
    JAX's compiler debug log: distinct cache keys, since a process with
    two log handlers prints each line twice."""
    err = (LOGS / f"{name}.err").read_text(errors="replace")
    seen = set(re.findall(
        r"(cache hit|CACHE MISS) for '[^']*' with key '([^']*)'", err))
    return {"hits": sum(kind == "cache hit" for kind, _ in seen),
            "misses": sum(kind == "CACHE MISS" for kind, _ in seen)}


# ------------------------------------------------------------------ legs --


def leg_device() -> dict:
    out = run_child("device", [PY, "-c", _DEVICE_LEG, REQUIRED_PLATFORM],
                    timeout=180)
    print(out.splitlines()[0], flush=True)
    return _last_json(out)


def leg_train(name: str, config: str, overrides: tuple, global_batch: int,
              ckpt: pathlib.Path, device: dict) -> dict:
    """A few trainer steps through ``train.py`` (cli/train.py:main), then
    the run judged from what it recorded."""
    sets = [*overrides,
            f"data.global_batch_size={global_batch}",
            f"train.total_steps={STEPS}", "train.log_interval=2",
            "train.eval_steps=2", "train.eval_interval=0",
            "train.memory_interval_s=0",
            f"checkpoint.directory={ckpt}",
            f"checkpoint.save_interval_steps={STEPS // 2}"]
    argv = [PY, "train.py", "--config", config]
    for s in sets:
        argv += ["--set", s]
    run_child(name, argv, timeout=600)

    events = _events(ckpt / "events.jsonl")
    shutil.copy(ckpt / "events.jsonl", LOGS / f"{name}.events.jsonl")
    by_kind: dict[str, list[dict]] = {}
    for ev in events:
        by_kind.setdefault(ev["kind"], []).append(ev)
    _check(events[0]["kind"] == "run_meta",
           "events.jsonl does not open with run_meta")
    meta = events[0]["extra"]
    for key, want in (("platform", device["platform"]),
                      ("device_kind", device["kind"]),
                      ("device_count", device["count"]),
                      ("pallas_kernels", REQUIRED_KERNEL_MODE)):
        _check(meta.get(key) == want,
               f"run_meta {key}={meta.get(key)!r}, expected {want!r}")
    _check(meta["mesh"]["data"] == device["count"],
           f"mesh.data=-1 resolved to {meta['mesh']['data']}, "
           f"not {device['count']}")
    steps = by_kind.get("train_step", [])
    _check(bool(steps) and steps[-1]["step"] == STEPS,
           f"no train_step record at step {STEPS}")
    losses = [ev["metrics"]["loss"] for ev in steps]
    _check(all(isinstance(x, float) and x == x and abs(x) < 1e30
               for x in losses), f"non-finite loss in {losses}")
    evals = by_kind.get("eval", [])
    _check(bool(evals), "no eval record")
    eval_loss = evals[-1]["metrics"]["eval_loss"]
    _check(eval_loss == eval_loss and abs(eval_loss) < 1e30,
           f"non-finite eval loss {eval_loss}")
    mem = by_kind.get("memory", [])
    _check(bool(mem), "no memory record")
    kinds = {ev["extra"]["source_kind"] for ev in mem}
    _check(kinds == {REQUIRED_MEMORY_SOURCE},
           f"memstats source_kind {sorted(kinds)}, expected "
           f"{REQUIRED_MEMORY_SOURCE} (host RSS is not HBM)")
    for step in (STEPS // 2, STEPS):
        manifest = ckpt / str(step) / "manifest.json"
        _check(manifest.exists(), f"checkpoint step {step} has no manifest "
                                  f"(uncommitted)")
        rec = json.loads(manifest.read_text())
        _check(rec.get("step") == step and rec.get("file_count", 0) > 0,
               f"checkpoint step {step}: bad manifest {rec.get('step')}")
        for rel, info in rec["files"].items():
            f = ckpt / str(step) / rel
            _check(f.exists() and f.stat().st_size == info["bytes"],
                   f"checkpoint step {step}: {rel} missing or torn")
    startup = by_kind["startup"][0]["extra"]
    return {"loss_first": losses[0], "loss_last": losses[-1],
            "eval_loss": eval_loss,
            "time_to_first_step_s": round(startup["time_to_first_step_s"], 1),
            "compilation_cache_dir": startup["compilation_cache_dir"],
            "hbm_peak_bytes": max(ev["metrics"]["peak_bytes_in_use"]
                                  for ev in mem),
            "cache": _cache_counts(name)}


def leg_lfm2(ckpt: pathlib.Path, device: dict) -> dict:
    """The decoder family through the same trainer leg, then what only it
    records: no dropped assignment, a quarter of the assignments local,
    and an attention call that was causal over 8 key/value heads."""
    out = leg_train("lfm2", "configs/lfm2_8b_a1b.yaml", LFM2_OVERRIDES,
                    LFM2_PER_CHIP_BATCH * device["count"], ckpt, device)
    events = _events(LOGS / "lfm2.events.jsonl")
    steps = [ev["metrics"] for ev in events if ev["kind"] == "train_step"]
    _check(all(m.get("moe_dropped") == 0.0 for m in steps),
           f"dropped assignments: {[m.get('moe_dropped') for m in steps]}")
    share = steps[-1]["moe_local_share"]
    _check(0.15 < share < 0.35,
           f"moe_local_share {share}: 8 of 32 experts should see ~0.25")
    calls = events[0]["extra"]["flash_dispatch"]
    _check(any(c["causal"] and c["kv_heads"] == 8 and c["heads"] == 32
               and c["segmented"] for c in calls),
           f"no causal 32-over-8-head attention call in run_meta: {calls}")
    experts = events[0]["extra"].get("expert_share") or {}
    _check(experts.get("held") == list(range(8)),
           f"run_meta expert_share {experts}")
    return dict(out, moe_local_share=share,
                moe_load_max_mean=steps[-1]["moe_load_max_mean"],
                moe_compact=steps[-1]["moe_compact"])


def leg_kernels(device: dict) -> dict:
    out = run_child("kernels", [PY, "scripts/verify_flash_kernels.py"],
                    timeout=600)
    res = _last_json(out)
    (LOGS / "kernels.json").write_text(json.dumps(res, indent=1))
    _check(res["ok"], "a kernel disagrees with its reference")
    _check(res["platform"] == device["platform"]
           and res["kernel_mode"] == REQUIRED_KERNEL_MODE,
           f"kernels ran in {res['kernel_mode']} mode on {res['platform']}")
    # select_dispatch names no dtype, so the matrix has to have run both.
    ran = {case["dtype"] for case in res["cases"]}
    _check({"bfloat16", "float32"} <= ran, f"kernel cases ran only {ran}")
    for case in res["cases"]:
        for variant, stats in case["variants"].items():
            # Forward plus at least one backward kernel, compiled by
            # Mosaic, in every program.
            _check(stats["mosaic_calls"] >= 2,
                   f"{case['case']}/{variant}: {stats['mosaic_calls']} "
                   f"Mosaic calls in the lowered program")
    return {"cases": [c["case"] for c in res["cases"]],
            "streaming_backward_default": res["streaming_backward_default"],
            "worst_rel_l2_vs_reference": max(
                v for c in res["cases"] for s in c["variants"].values()
                for k, v in s.items() if k.endswith("_vs_reference")),
            "worst_rel_l2_fused_vs_two_pass": max(
                s.get("rel_l2_vs_two_pass", 0.0)
                for c in res["cases"] for s in c["variants"].values()),
            "cache": _cache_counts("kernels")}


def leg_export(ckpt: pathlib.Path, artifact: pathlib.Path,
               device: dict) -> dict:
    argv = [PY, "-m", f"{PACKAGE}.cli.export",
            "--config", "configs/bert_base_mlm.yaml",
            "--output", str(artifact)]
    sets = [*BERT_OVERRIDES, f"checkpoint.directory={ckpt}"]
    if device["count"] > 1:
        # Trained on every chip, served on serve.data (1) of them.
        sets.append("serve.allow_reshard=true")
    for s in sets:
        argv += ["--set", s]
    out = run_child("export", argv, timeout=300)
    _check(out.strip().splitlines()[-1] == str(artifact),
           "export did not print the artifact path")
    _check(any(artifact.iterdir()), "artifact directory is empty")
    return {"cache": _cache_counts("export")}


def leg_serve(artifact: pathlib.Path, work: pathlib.Path,
              device: dict) -> dict:
    """Serve the artifact in a child that holds the chip; load_gen.py
    beside it imports no backend. SIGTERM must drain cleanly, exit 0."""
    log_dir = work / "serve_logs"
    argv = [PY, "-m", f"{PACKAGE}.cli.serve", "--artifact", str(artifact)]
    for s in ("serve.port=0", f"serve.log_dir={log_dir}", *SERVE_OVERRIDES):
        argv += ["--set", s]
    server = _spawn("serve", argv)
    endpoint = log_dir / "endpoint.json"
    deadline = time.monotonic() + 300
    while not endpoint.exists():
        if server.poll() is not None:
            _live.remove(server)
            raise LegFailed(f"server exited {server.returncode} before "
                            f"listening\n{_tail('serve')}")
        if time.monotonic() > deadline:
            _stop(server)
            raise LegFailed(f"server not listening after 300s\n"
                            f"{_tail('serve')}")
        time.sleep(0.5)

    result = {}
    for mode, extra in (("closed", ["--requests", "8", "--concurrency", "4"]),
                        ("decode", ["--requests", "4", "--concurrency", "2",
                                    "--max-new-tokens", "16"])):
        bench = LOGS / f"serve_{mode}.json"
        run_child(f"load_gen_{mode}",
                  [PY, "scripts/load_gen.py", "--endpoint", str(endpoint),
                   "--mode", mode, "--out", str(bench), *extra],
                  timeout=420)
        run = json.loads(bench.read_text())["runs"][0]
        _check(run["ok"] == run["requests"] and run["errors"] == 0,
               f"{mode}: {run['ok']}/{run['requests']} ok, "
               f"{run['errors']} errors ({run['by_status']})")
        result[mode] = {"requests": run["requests"]}
        if mode == "decode":
            _check(run["tokens"] > 0, "decode streamed no tokens")
            result[mode]["tokens"] = run["tokens"]

    server.send_signal(signal.SIGTERM)
    _wait("serve", server, timeout=120)
    events = _events(log_dir / "events.jsonl")
    shutil.copy(log_dir / "events.jsonl", LOGS / "serve.events.jsonl")
    meta = events[0]["extra"]
    _check(events[0]["kind"] == "run_meta"
           and meta.get("platform") == device["platform"]
           and meta.get("device_kind") == device["kind"],
           f"serve run_meta does not name the device: {meta}")
    drains = [ev["health"] for ev in events if ev["kind"] == "health"
              and ev["health"].get("event") == "serve_drain"]
    _check(bool(drains) and drains[-1]["clean"] is True,
           f"no clean drain recorded: {drains}")
    result["cache"] = _cache_counts("serve")
    return result


def leg_multichip() -> dict:
    out = run_child("multichip", [PY, "scripts/multichip_check.py"],
                    timeout=1500)
    res = _last_json(out)
    (LOGS / "multichip.json").write_text(json.dumps(res, indent=1))
    _check(res["ok"], f"multichip check failed: {res.get('failed')}")
    return res


# ------------------------------------------------------------------ main --


def main() -> int:
    t0 = time.monotonic()
    if LOGS.exists():
        shutil.rmtree(LOGS)
    LOGS.mkdir(parents=True)
    work = pathlib.Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    summary: dict = {"legs": {}}
    device: dict = {}

    def leg(name, fn, *args):
        t = time.monotonic()
        print(f"chip_smoke: leg {name} ...", flush=True)
        try:
            out = fn(*args)
        except LegFailed as e:
            print(f"chip_smoke: leg {name} FAILED: {e}", file=sys.stderr,
                  flush=True)
            raise
        out = dict(out or {}, wall_s=round(time.monotonic() - t, 1))
        summary["legs"][name] = out
        print(f"chip_smoke: leg {name} ok {json.dumps(out)}", flush=True)
        return out

    try:
        device.update(leg("device", leg_device))
        n = device["count"]
        bert_ckpt, resnet_ckpt = work / "bert_ckpt", work / "resnet_ckpt"
        leg("bert", leg_train, "bert", "configs/bert_base_mlm.yaml",
            BERT_OVERRIDES, BERT_PER_CHIP_BATCH * n, bert_ckpt, device)
        leg("resnet", leg_train, "resnet", "configs/resnet50_imagenet.yaml",
            RESNET_OVERRIDES, RESNET_PER_CHIP_BATCH * n, resnet_ckpt, device)
        leg("lfm2", leg_lfm2, work / "lfm2_ckpt", device)
        leg("kernels", leg_kernels, device)
        artifact = work / "artifact"
        leg("export", leg_export, bert_ckpt, artifact, device)
        leg("serve", leg_serve, artifact, work, device)
        if n > 1:
            leg("multichip", leg_multichip)
    except LegFailed:
        return 1
    finally:
        for proc in list(_live):
            _stop(proc)
        shutil.rmtree(work, ignore_errors=True)
        summary["wall_s"] = round(time.monotonic() - t0, 1)
        (LOGS / "summary.json").write_text(json.dumps(summary, indent=1))
    print(f"chip_smoke: all legs ok in {summary['wall_s']}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
