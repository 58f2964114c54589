#!/usr/bin/env python
"""Benchmark: ResNet-50/ImageNet-shape training throughput on the local chip.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "images/sec/chip", "vs_baseline": N,
   "chip": ..., "tflops_per_sec": ..., "mfu": ..., "bound": ...}

vs_baseline is measured against BASELINE.json's north-star target of
10,000 images/sec aggregate on v5e-64 → 156.25 images/sec/chip (the
reference's own published numbers are unrecoverable — BASELINE.md).

MFU and the bottleneck verdict come from XLA's own cost model: the
compiled train step's ``flops`` / ``bytes accessed`` give achieved
TFLOP/s, model-flop utilization against the chip's bf16 peak, and
arithmetic intensity vs the chip's ridge point (peak FLOPs / HBM BW) —
intensity below the ridge means the step is HBM-bandwidth-bound.
Measured numbers and analysis are recorded in PERF_NOTES.md.

Set BENCH_TRACE=<dir> to also capture an XPlane trace of the timed window
(core/profiling.trace) for TensorBoard/Perfetto inspection; the compiled
HLO text is dumped next to it so scripts/analyze_trace.py can attribute
trace events to source scopes.

Besides the stdout line (the driver contract), every result/failure is
also appended as a schema-versioned telemetry event (core/telemetry,
docs/OBSERVABILITY.md) with per-collective byte counts, joinable with a
training run's events.jsonl by run id. BENCH_JSONL=<path> overrides the
sink (default: <BENCH_TRACE>/bench_events.jsonl, else ./bench_events.jsonl;
BENCH_JSONL=0 disables). An unavailable backend is exit 1 with the error
in the JSON line. The run-meta event opens the sink with the platform,
device_kind and device count the process landed on.
BENCH_COLLECTIVE=f32|bf16|int8 runs the collective wire-format A/B
instead of a single workload (_run_collective_ab): f32-wire baseline vs
the requested wire format on the same ladder, reporting the tallied
wire-byte ratio and throughput delta.

The persistent compilation cache is placed by
core/platform.resolve_compilation_cache before the first backend use:
JAX_COMPILATION_CACHE_DIR when set, <checkout>/.jax_cache otherwise.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

TARGET_PER_CHIP = 10_000 / 64  # BASELINE.json north star on v5e-64

# Chip peaks and the roofline math moved to core/roofline.py so the
# autotuner's analytic pruner (tools/autotune) and this bench judge
# candidates against the SAME ridge. Re-exported here because the bench
# is the historical home of these names (tests + PERF_NOTES refer to
# bench.CHIP_PEAKS et al.).
from distributed_tensorflow_framework_tpu.core.roofline import (  # noqa: E402,F401
    CHIP_PEAKS,
    GIB,
    annotate_roofline as _annotate_roofline,
    chip_hbm_capacity,
)


def _emit_json_line(payload: dict) -> None:
    """The ONE driver-contract JSON line: always stdout, and additionally
    written (whole-file, not append) to the BENCH_OUT=<path> file when
    set. Supervisors (tools/autotune, run_tier1.sh) read the file instead
    of regexing the tail out of warning-polluted stdout. Failure lines land in
    the file too: an empty/missing BENCH_OUT after exit means the process
    died before producing a verdict, which is itself a classification."""
    line = json.dumps(payload)
    print(line)
    out_path = os.environ.get("BENCH_OUT", "").strip()
    if out_path:
        try:
            with open(out_path, "w") as fh:
                fh.write(line + "\n")
        except OSError as e:
            print(f"bench: BENCH_OUT write failed ({e})", file=sys.stderr)


def _check_leaderboard(out: dict, workload: str) -> None:
    """Regression pin against configs/leaderboard.json (dtf-leaderboard/1,
    written by scripts/autotune.py). When the board has an entry for this
    workload, annotate the result row with the pinned incumbent: its
    config digest (re-verified — a board whose digest doesn't match its
    own config dict has been hand-edited and can't be trusted as a pin),
    the score ratio, and a regression flag when this run undershoots the
    incumbent by more than the pinned margin. Annotation only — the exit
    code stays the driver's; the flag is for the queue/tuner to read."""
    board_path = os.environ.get("BENCH_LEADERBOARD", "").strip() or \
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "configs", "leaderboard.json")
    try:
        with open(board_path) as fh:
            board = json.load(fh)
    except (OSError, ValueError):
        return
    entry = (board.get("entries") or {}).get(workload)
    if not isinstance(entry, dict) or not out.get("value"):
        return
    from tools.autotune.leaderboard import config_digest

    digest = entry.get("config_digest")
    digest_ok = (digest == config_digest(entry.get("config") or {}))
    score = float(entry.get("score") or 0.0)
    margin = float(entry.get("regression_margin") or 0.05)
    note = {"incumbent_score": score, "config_digest": digest,
            "digest_ok": digest_ok}
    if score > 0:
        ratio = float(out["value"]) / score
        note["vs_incumbent"] = round(ratio, 4)
        note["regression"] = bool(ratio < 1.0 - margin)
        if note["regression"]:
            print(f"bench: REGRESSION vs leaderboard incumbent for "
                  f"{workload}: {out['value']} vs pinned {score} "
                  f"(margin {margin})", file=sys.stderr)
    if not digest_ok:
        print(f"bench: leaderboard digest mismatch for {workload} — "
              f"the pin was edited outside scripts/autotune.py",
              file=sys.stderr)
    out["leaderboard"] = note


def _compile_and_time(builder, state, batch, steps: int, warmup: int) -> dict:
    """AOT-compile the train step ONCE (the same executable serves the
    XLA cost model AND the timed loop), then measure wall-clock.

    The timed window ends on a device_get of a param leaf, so the
    barrier includes the final step's optimizer update, not just its
    forward pass.
    """
    import contextlib
    import time

    import jax

    from distributed_tensorflow_framework_tpu.core.profiling import trace
    from distributed_tensorflow_framework_tpu.parallel import collectives as coll

    from distributed_tensorflow_framework_tpu.core import memstats

    # Drill affordability knobs: the observability drill runs the full
    # bench binary on CPU and only needs the JSON shape, not a stable
    # rate — let it shrink the timed loop without forking the workloads.
    steps = int(os.environ.get("BENCH_STEPS") or steps)
    warmup = int(os.environ.get("BENCH_WARMUP") or warmup)

    step = builder.make_train_step(batch)
    trace_dir = os.environ.get("BENCH_TRACE")
    # Collective byte counters record at JAX *trace* time, and lower() IS
    # the trace (it also populates the jit call cache, so the timed loop
    # below never re-traces) — tally around it and the counts describe
    # every timed step. A lowering or compile failure (a Mosaic refusal
    # included) propagates: it is the program about to be timed.
    with coll.tally() as tly:
        lowered = step.lower(state, batch)
    collectives = tly.summary()
    compiled = lowered.compile()
    hlo_text = compiled.as_text()
    if trace_dir:
        # The optimized-HLO side channel scripts/analyze_trace.py uses
        # for scope attribution (same layout as ProfileHook's dump).
        os.makedirs(trace_dir, exist_ok=True)
        with open(os.path.join(trace_dir, "train_step.hlo.txt"), "w") as fh:
            fh.write(hlo_text)
    # The cost model is the one thing a backend may lack: cost_analysis()
    # then returns None (or an empty mapping), and the row goes without
    # roofline fields.
    ca = compiled.cost_analysis()
    ca = (ca[0] if isinstance(ca, (list, tuple)) else ca) or {}
    if not ca:
        print("bench: backend has no cost model (cost_analysis empty)",
              file=sys.stderr)
    flops_per_step = float(ca.get("flops", 0.0)) or None
    bytes_per_step = float(ca.get("bytes accessed", 0.0)) or None
    memory_analysis = memstats.compiled_memory_analysis(compiled)
    if memory_analysis is not None:
        # Donation survival on the program actually being timed: the
        # count of input_output_alias entries in the optimized module
        # (tools/graftcheck audits the same number against the state
        # leaf count).
        from tools.graftcheck.hlo_passes import count_alias_entries

        memory_analysis["donated_alias_entries"] = \
            count_alias_entries(hlo_text)
    step = compiled

    def sync(s):
        leaf = jax.tree.leaves(s.params)[0]
        jax.device_get(leaf)

    for _ in range(warmup):
        state, metrics = step(state, batch)
    sync(state)
    ctx = trace(trace_dir) if trace_dir else contextlib.nullcontext()
    with ctx:
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch)
        sync(state)
        dt = time.perf_counter() - t0
    # HBM occupancy AFTER the timed loop: arrays are live, so the device
    # peak reflects the workload's real footprint at its largest
    # (core/memstats.py; the snapshot's source_kind says which ruler).
    memory = memstats.device_memory_snapshot()
    if memory_analysis:
        memory["analysis"] = memory_analysis
    return {
        "sec_per_step": dt / steps,
        "flops_per_step": flops_per_step,
        "bytes_per_step": bytes_per_step,
        "collectives": collectives,
        "memory": memory,
    }


def _mesh_axes(mesh) -> dict:
    """Mesh tag for bench records: non-trivial axis sizes ({data:1} when
    fully trivial) — so artifacts from different topologies are never read
    as comparable rates (ISSUE 6: throughput at {data:8} vs {fsdp:2,pipe:4}
    is a different experiment, not a regression)."""
    axes = {a: int(s) for a, s in mesh.shape.items() if int(s) > 1}
    return axes or {"data": 1}


def bench_resnet50(batch_size: int, steps: int = 20, warmup: int = 3,
                   model_overrides: dict | None = None,
                   base_overrides: dict | None = None) -> dict:
    """``base_overrides`` merges per top-level section into the base dict
    (the collective A/B uses it to force shard_map + a wire dtype without
    forking the workload definition)."""
    import numpy as np

    from distributed_tensorflow_framework_tpu.core.config import load_config
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    base = {
            "name": "bench-resnet50",
            "model": {"name": "resnet50", "num_classes": 1000,
                      "dtype": "bfloat16",
                      # Space-to-depth stem: exact reparametrization of the
                      # 7×7/s2 conv (tests/test_s2d_stem.py), +8% img/s on
                      # v5e — the 3-channel full-res conv wastes MXU lanes
                      # and HBM BW (PERF_NOTES.md). BENCH_NO_S2D=1 reverts.
                      "space_to_depth_stem":
                          os.environ.get("BENCH_NO_S2D", "0")
                          in ("", "0"),
                      # Per-block remat: trades idle MXU headroom for HBM
                      # bytes on the BW-bound step. BENCH_REMAT=1 → full
                      # replay (measured -13% img/s); BENCH_REMAT=light →
                      # the conv_saved policy (keep conv outputs, replay
                      # only BN/ReLU — the cheap-tail variant). See
                      # PERF_NOTES.md.
                      "remat":
                          os.environ.get("BENCH_REMAT", "0")
                          not in ("", "0"),
                      "remat_policy":
                          "conv_saved"
                          if os.environ.get("BENCH_REMAT") in
                          ("light", "conv", "conv_saved") else "full",
                      **(model_overrides or {})},
            "data": {
                "name": "synthetic_images",
                "num_classes": 1000,
                "global_batch_size": batch_size,
                "image_size": 224,
                "channels": 3,
                # bf16 infeed: the step is HBM-BW-bound (PERF_NOTES.md);
                # halving image bytes is worth ~3% wall-clock.
                "image_dtype": "bfloat16",
            },
            "optimizer": {
                "name": "sgd_momentum",
                "learning_rate": 0.1,
                "weight_decay": 0.0001,
            },
            "train": {"total_steps": 1000},
    }
    for section, override in (base_overrides or {}).items():
        if isinstance(override, dict):
            base[section] = {**base.get(section, {}), **override}
        else:
            base[section] = override
    cfg = load_config(base=base)
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    from distributed_tensorflow_framework_tpu.data.pipeline import image_np_dtype

    rng = np.random.default_rng(0)
    host = {
        "image": rng.standard_normal((batch_size, 224, 224, 3))
        .astype(image_np_dtype(cfg.data.image_dtype)),
        "label": rng.integers(0, 1000, batch_size).astype(np.int32),
    }
    batch = to_global(host, mesh)
    state = builder.init_state(0, batch)
    out = _compile_and_time(builder, state, batch, steps, warmup)
    out["images_per_sec"] = batch_size / out["sec_per_step"]
    out["mesh_axes"] = _mesh_axes(mesh)
    out["opt_state_bytes_per_chip"] = _opt_state_bytes_per_chip(state)
    return out


def _opt_state_bytes_per_chip(state) -> int:
    """Per-device optimizer-slot footprint, read off the placed shardings.

    Sums prod(shard_shape) x itemsize over every opt_state leaf — the
    number ZeRO weight-update sharding divides by the data x fsdp replica
    count, so the BENCH_ZERO A/B reports the memory win exactly (from the
    arrays' own layouts) rather than estimating it."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(state.opt_state):
        sharding = getattr(leaf, "sharding", None)
        shape = (sharding.shard_shape(leaf.shape)
                 if sharding is not None else getattr(leaf, "shape", ()))
        itemsize = int(getattr(getattr(leaf, "dtype", None), "itemsize", 4))
        total += int(np.prod(shape)) * itemsize
    return total


def bench_inception(batch_size: int, steps: int = 20, warmup: int = 3) -> dict:
    """Inception-v3 train-step throughput — BASELINE config 4's recipe,
    loaded from configs/inception_v3.yaml (one source of truth for the
    hyperparameters) with only the bench-necessary overrides: synthetic
    infeed at the recipe's 299px bf16 shape and the requested batch.
    BENCH_WORKLOAD=inception; BENCH_REMAT=1 for full-replay remat (the
    ResNet-only 'light'/'conv_saved' values are rejected — Inception has
    no conv_saved policy)."""
    import numpy as np

    from distributed_tensorflow_framework_tpu.core.config import load_config
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.data.pipeline import (
        image_np_dtype,
    )
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    remat_env = os.environ.get("BENCH_REMAT", "0")
    if remat_env not in ("", "0", "1"):
        raise ValueError(
            f"BENCH_REMAT={remat_env!r} is ResNet-only (conv_saved policy); "
            f"the inception workload takes BENCH_REMAT=1 (full replay) or "
            f"unset.")
    cfg = load_config(
        pathlib.Path(__file__).parent / "configs" / "inception_v3.yaml",
        overrides=[
            "data.name=synthetic_images",
            f"data.global_batch_size={batch_size}",
            f"model.remat={'true' if remat_env == '1' else 'false'}",
        ],
    )
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    rng = np.random.default_rng(0)
    host = {
        "image": rng.standard_normal(
            (batch_size, cfg.data.image_size, cfg.data.image_size, 3))
        .astype(image_np_dtype(cfg.data.image_dtype)),
        "label": rng.integers(0, cfg.data.num_classes, batch_size)
        .astype(np.int32),
    }
    batch = to_global(host, mesh)
    state = builder.init_state(0, batch)
    out = _compile_and_time(builder, state, batch, steps, warmup)
    out["images_per_sec"] = batch_size / out["sec_per_step"]
    out["mesh_axes"] = _mesh_axes(mesh)
    return out


def _ragged_mlm_batch(batch_size: int, seq_len: int, pack: int) -> dict:
    """Document-realistic synthetic MLM batch for the packing A/B.

    Doc lengths ~ U[s/8, s/2] (mean ≈ 0.31·s — the padding waste packing
    exists to reclaim). ``pack==1``: one doc per row, zero-padded (the
    unpacked baseline). ``pack>1``: ``pack·batch`` docs laid end-to-end by
    the production packer (data/text_mlm.pack_documents) with segment ids
    for block-diagonal attention. Real-token and doc counts ride along so
    the bench can report useful-token throughput, the metric packing
    actually moves (PERF_NOTES.md round 3: "fewer, fatter GEMMs").
    """
    import numpy as np

    from distributed_tensorflow_framework_tpu.data.text_mlm import (
        pack_documents,
    )

    rng = np.random.default_rng(0)
    n_docs = batch_size * max(pack, 1)
    lengths = rng.integers(seq_len // 8, seq_len // 2 + 1, n_docs)
    docs = np.zeros((n_docs, seq_len), np.int32)
    for i, n in enumerate(lengths):
        docs[i, :n] = rng.integers(1000, 30522, n)
    if pack > 1:
        tokens, seg_ids, leftover = pack_documents(docs, batch_size, seq_len)
        docs_in_batch = n_docs - len(leftover)
    else:
        tokens, seg_ids, docs_in_batch = docs, None, n_docs
    mask = (rng.random(tokens.shape) < 0.15) & (tokens != 0)
    batch = {
        "input_ids": np.where(mask, 103, tokens).astype(np.int32),
        "targets": np.where(mask, tokens, -1).astype(np.int32),
        "attention_mask": (tokens != 0).astype(np.int32),
    }
    if seg_ids is not None:
        batch["segment_ids"] = seg_ids
    batch["_real_tokens"] = int((tokens != 0).sum())
    batch["_docs"] = int(docs_in_batch)
    return batch


def bench_bert(batch_size: int, steps: int = 20, warmup: int = 3,
               *, seq_len: int = 512, attention_impl: str = "pallas",
               remat: bool = False, pack: int = 0,
               fused_qkv: bool = False, accum: int = 1,
               pipeline_stages: int = 0, pipeline_schedule: str = "gpipe",
               pipeline_microbatches: int = 0,
               pipeline_virtual_stages: int = 0) -> dict:
    """BERT-base MLM train-step throughput — the transformer side of the
    perf story. Measured on v5e it saturates NEITHER roofline (MFU 17.9%
    base at seq 512): the step is dominated by per-optimizer-step fixed
    overheads plus medium-GEMM fragmentation, so the measured levers are
    grad accumulation (MFU → 32-34%) and fused QKV (+21% at accum 1),
    not bandwidth (PERF_NOTES.md round 5, 2026-08-01 window).
    Knobs via env in main(): BENCH_ATTN (pallas|xla|ring), BENCH_REMAT=1,
    BENCH_SEQ=<len>, BENCH_BS=<per-chip batch>, BENCH_FUSED_QKV=1, BENCH_PACK
    (0 = dense synthetic rows; 1 = ragged docs unpacked — the padding
    baseline; n>1 = same doc distribution packed n-to-1).
    BENCH_PP=<stages> carves a pipe axis off the mesh (data = chips/stages)
    for the pipeline-schedule A/B; BENCH_SCHEDULE (gpipe|1f1b|interleaved),
    BENCH_MICRO=<microbatches>, BENCH_VIRTUAL=<v> pick the schedule
    (docs/DISTRIBUTED.md)."""
    import jax

    from distributed_tensorflow_framework_tpu.core.config import load_config
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data import get_dataset
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    mesh_cfg = {}
    if pipeline_stages:
        n = jax.device_count()
        if n % pipeline_stages:
            raise ValueError(
                f"BENCH_PP={pipeline_stages} does not divide the "
                f"{n}-chip slice")
        mesh_cfg = {"mesh": {"data": n // pipeline_stages,
                             "pipe": pipeline_stages}}
    cfg = load_config(
        base={
            "name": "bench-bert",
            **mesh_cfg,
            # configs/bert_base_mlm.yaml shapes (BASELINE config 5).
            "model": {"name": "bert", "vocab_size": 30522,
                      "hidden_size": 768, "num_layers": 12, "num_heads": 12,
                      "mlp_dim": 3072, "max_seq_len": seq_len,
                      "dtype": "bfloat16", "attention_impl": attention_impl,
                      "remat": remat, "fused_qkv": fused_qkv,
                      "pipeline_stages": pipeline_stages,
                      "pipeline_schedule": pipeline_schedule,
                      "pipeline_microbatches": pipeline_microbatches,
                      "pipeline_virtual_stages": pipeline_virtual_stages},
            "data": {"name": "synthetic_mlm", "global_batch_size": batch_size,
                     "seq_len": seq_len},
            "optimizer": {"name": "adamw", "learning_rate": 1e-4,
                          "weight_decay": 0.01},
            # BENCH_ACCUM>1: fatter EFFECTIVE batch at fixed per-micro
            # memory — the VERDICT-r4 fragmentation lever candidate
            # (optimizer + fixed per-step overheads amortize over
            # accum× the tokens; per-micro GEMM shapes unchanged when
            # the ladder is scaled by accum, which main() does).
            "train": {"total_steps": 1000, "grad_accum_steps": accum},
        }
    )
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    if pack:
        host = _ragged_mlm_batch(batch_size, seq_len, pack)
        real_tokens = host.pop("_real_tokens")
        docs = host.pop("_docs")
    else:
        host = next(get_dataset(cfg.data))
        real_tokens = batch_size * seq_len
        docs = batch_size
    batch = to_global(host, mesh)
    state = builder.init_state(0, batch)
    out = _compile_and_time(builder, state, batch, steps, warmup)
    if accum > 1:
        # XLA's cost_analysis counts a lax.scan body ONCE, but the accum
        # scan (train/step.py) runs it `accum` times per optimizer step —
        # verified on-chip 2026-08-01: the raw accum=4 run reported
        # exactly 1/4 the TFLOP/s its wall-clock throughput implied.
        # Scale flops/bytes by the trip count. Residual error: the
        # once-per-step optimizer update is also scaled, over-counting it
        # (accum-1)×. For FLOPs that is <1% (the update is ~10 flops/param
        # vs ~6 TFLOP per BERT-base micro-step). For BYTES it is not
        # negligible (AdamW traffic is ~7 f32 passes over the param tree,
        # ~3 GB for BERT-base — comparable to one micro-step), so for
        # accum runs hbm_bw_util is an UPPER bound and arith_intensity a
        # LOWER bound; the aggregate cost model gives no body/epilogue
        # split to do better with.
        for key in ("flops_per_step", "bytes_per_step"):
            if out.get(key):
                out[key] *= accum
    out["examples_per_sec"] = batch_size / out["sec_per_step"]
    out["tokens_per_sec"] = batch_size * seq_len / out["sec_per_step"]
    out["real_tokens_per_sec"] = real_tokens / out["sec_per_step"]
    out["docs_per_sec"] = docs / out["sec_per_step"]
    out["mesh_axes"] = _mesh_axes(mesh)
    return out


def _pp_bubble(schedule: str, stages: int, micro: int, virtual: int) -> float:
    """Analytic bubble fraction for the bert pp bench (12 BERT-base
    layers fixes the interleaved default v = 12/stages)."""
    from distributed_tensorflow_framework_tpu.parallel import schedule as sched

    v = sched.resolve_virtual(schedule, stages, micro, virtual, 12)
    return sched.bubble_frac(schedule, stages, micro, v)


# _annotate_roofline lives in core/roofline.py now (imported above):
# the tuner's pruning predictor and the bench's measured verdict must
# share one ridge-point implementation or they drift apart.


def _annotate_memory(out: dict, result: dict, chip: str,
                     n_chips: int) -> None:
    """Peak HBM per chip + headroom against the chip's capacity.

    Peak preference order: live device counters (memory_stats peak) →
    the compiled step's static analysis (args+temps+output — works on
    CPU where memory_stats returns nothing) → host RSS. Headroom is
    against CHIP_PEAKS capacity, or host RAM for unknown chips, so the
    number answers "how much bigger a batch/model fits" on any backend.
    """
    import jax

    mem = result.get("memory") or {}
    analysis = mem.get("analysis") or {}
    # Multi-process rows stay comparable across topologies: the process
    # count rides on the row, and the HBM peak below is scoped to THIS
    # host's devices (memory sampling is per-process). Single-process
    # rows keep their exact historical shape — this function stays a
    # no-op when there is nothing to report.
    if int(jax.process_count()) > 1:
        out["process_count"] = int(jax.process_count())
        out["hbm_peak_scope"] = f"host{jax.process_index()}"
    peak = mem.get("peak_bytes_in_use") or 0
    source = mem.get("source_kind", "unknown")
    if source != "device_memory_stats":
        est = analysis.get("peak_bytes_est") or 0
        if est:
            # Static analysis is whole-program; attribute evenly per chip.
            peak, source = est / max(1, n_chips), "memory_analysis"
        elif peak:
            source = "host_rss"
    if not peak:
        return
    out["hbm_peak_bytes_per_chip"] = int(peak)
    out["hbm_peak_source"] = source
    cap = chip_hbm_capacity(chip)
    if cap:
        out["hbm_capacity_bytes_per_chip"] = int(cap)
        out["hbm_headroom_frac"] = round(1.0 - peak / cap, 4)


def _run_ladder(bench_fn, sizes, failure_metric: str, failure_unit: str,
                chip: str, writer=None):
    """Try batch sizes largest-first, stepping down ONLY when the device
    ran out of memory (RESOURCE_EXHAUSTED). Any other failure — a compile
    refusal, a shape error — ends the ladder at that rung: a smaller batch
    would be a number from another experiment. On failure print the
    zero-value JSON line (with the error), mirror it as a telemetry
    failure event, and return None."""
    import traceback

    import jax

    last = "no batch size attempted"
    for bs in sizes:
        try:
            return bench_fn(bs)
        except Exception as e:  # reported as the run's failure line below
            last = f"batch {bs}: {type(e).__name__}: {e}"
            if not (isinstance(e, jax.errors.JaxRuntimeError)
                    and "RESOURCE_EXHAUSTED" in str(e)):
                traceback.print_exc(file=sys.stderr)
                break
            print(f"bench: {last}, retrying smaller", file=sys.stderr)
    fail = {"metric": failure_metric, "value": 0.0, "unit": failure_unit,
            "vs_baseline": 0.0, "chip": chip, "error": last}
    if writer is not None:
        from distributed_tensorflow_framework_tpu.core import telemetry

        fail["run_id"] = writer.run_id
        writer.emit(telemetry.KIND_FAILURE,
                    health={"failure": "bench_ladder", "error": last},
                    metric=failure_metric, chip=chip)
    _emit_json_line(fail)
    return None


def _ladder_override(default: tuple, n_chips: int) -> tuple:
    """BENCH_BS=<per-chip batch> pins the batch ladder to one size."""
    if os.environ.get("BENCH_BS"):
        return (int(os.environ["BENCH_BS"]) * n_chips,)
    return default


_ROOFLINE_KEYS = ("tflops_per_sec", "mfu", "arith_intensity",
                  "ai_flops_per_byte", "bound", "hbm_bw_util",
                  "roofline_bound")


def _bench_writer():
    """Telemetry sink for this bench invocation (module docstring)."""
    from distributed_tensorflow_framework_tpu.core import telemetry

    path = os.environ.get("BENCH_JSONL", "").strip()
    if path.lower() in ("0", "off", "none"):
        path = None
    elif not path:
        trace_dir = os.environ.get("BENCH_TRACE")
        path = (os.path.join(trace_dir, "bench_events.jsonl")
                if trace_dir else "bench_events.jsonl")
    return telemetry.TelemetryWriter(
        path, run_id=os.environ.get("BENCH_RUN_ID") or None)


def _emit_bench_result(writer, workload: str, out: dict, result: dict) -> None:
    """Mirror the stdout JSON line as a schema-versioned bench event, with
    the cost-model raw numbers and per-collective byte counts attached."""
    from distributed_tensorflow_framework_tpu.core import telemetry

    metrics = {"value": out["value"], "sec_per_step": result["sec_per_step"]}
    for k in ("flops_per_step", "bytes_per_step"):
        if result.get(k):
            metrics[k] = result[k]
    roofline = {k: out[k] for k in _ROOFLINE_KEYS if k in out} or None
    extra = {k: v for k, v in out.items()
             if k not in metrics and k not in _ROOFLINE_KEYS
             and k != "run_id"}
    writer.emit(telemetry.KIND_BENCH, metrics=metrics, roofline=roofline,
                collectives=result.get("collectives"), workload=workload,
                **extra)
    mem = result.get("memory")
    if mem:
        # The raw snapshot rides as its own KIND_MEMORY event so the
        # bench trace joins the trainer's memory telemetry stream
        # (core/memstats.py, docs/OBSERVABILITY.md) by kind, not by
        # spelunking bench extras.
        mem_metrics = {k: mem[k] for k in
                       ("bytes_in_use", "peak_bytes_in_use", "device_count")
                       if mem.get(k) is not None}
        mem_extra = {k: out[k] for k in
                     ("hbm_peak_bytes_per_chip", "hbm_peak_source",
                      "hbm_capacity_bytes_per_chip", "hbm_headroom_frac")
                     if k in out}
        if mem.get("analysis"):
            mem_extra["analysis"] = mem["analysis"]
        writer.emit(telemetry.KIND_MEMORY, metrics=mem_metrics or None,
                    source="bench", source_kind=mem.get("source_kind"),
                    workload=workload, **mem_extra)


# BENCH_COLLECTIVE value → parallel.collective_dtype knob value.
_COLLECTIVE_MODES = {"f32": "", "bf16": "bfloat16", "int8": "int8"}


def _run_collective_ab(writer, mode: str, n_chips: int, chip: str) -> int:
    """BENCH_COLLECTIVE=f32|bf16|int8 — collective wire-format A/B.

    Runs the ResNet-50 workload TWICE on the same batch ladder under
    ``train.spmd_mode=shard_map`` (the explicit-collective path
    ``parallel.collective_dtype`` applies to — docs/PERFORMANCE.md):
    an f32-wire baseline, then the requested wire format. The JSON line
    reports the tallied wire-byte ratio (baseline/target; trace-time
    counts from parallel/collectives.tally, exact rather than sampled)
    and the throughput delta. ``f32`` runs the baseline once and reports
    ratio 1.0 — the self-calibration dial for the queue.
    """
    metric = "resnet50_collective_wire_ratio"
    unit = "x"
    ladder = _ladder_override(
        (128 * n_chips, 64 * n_chips, 32 * n_chips), n_chips)

    def run(wire: str):
        return _run_ladder(
            lambda bs: bench_resnet50(bs, base_overrides={
                "train": {"spmd_mode": "shard_map"},
                "parallel": {"collective_dtype": wire},
            }),
            ladder, metric, unit, chip, writer=writer)

    baseline = run("")
    if baseline is None:
        return 1
    wire_dtype = _COLLECTIVE_MODES[mode]
    target = run(wire_dtype) if wire_dtype else baseline
    if target is None:
        return 1

    def wire_bytes(result):
        return (result.get("collectives") or {}).get("total_bytes")

    base_b, tgt_b = wire_bytes(baseline), wire_bytes(target)
    ratio = round(base_b / tgt_b, 3) if base_b and tgt_b else None
    base_rate = baseline["images_per_sec"] / n_chips
    tgt_rate = target["images_per_sec"] / n_chips
    out = {
        "metric": metric,
        "value": ratio if ratio is not None else 0.0,
        "unit": unit,
        "vs_baseline": 0.0,
        "baseline_kind": "f32-wire-self",
        "chip": chip,
        "num_chips": n_chips,
        "mesh_axes": target.get("mesh_axes"),
        "collective_dtype": wire_dtype or "float32",
        "baseline_wire_bytes": base_b,
        "target_wire_bytes": tgt_b,
        "baseline_images_per_sec_per_chip": round(base_rate, 2),
        "target_images_per_sec_per_chip": round(tgt_rate, 2),
        # Relative throughput change from the wire format alone (same
        # ladder, same mesh): +0.04 = 4% faster than the f32 wire.
        "throughput_delta": round(tgt_rate / base_rate - 1.0, 4),
        "run_id": writer.run_id,
    }
    _annotate_roofline(out, target, chip, n_chips)
    _annotate_memory(out, target, chip, n_chips)
    _emit_bench_result(writer, f"resnet50-collective-{mode}", out, target)
    _emit_json_line(out)
    return 0


_ZERO_MODES = ("off", "shard_map")


def _run_zero_ab(writer, mode: str, n_chips: int, chip: str) -> int:
    """BENCH_ZERO=off|shard_map — ZeRO weight-update sharding A/B.

    Runs the ResNet-50 workload TWICE on the same batch ladder under
    ``train.spmd_mode=shard_map``: a replicated-optimizer baseline
    (``optimizer.zero_sharding=off``), then the bucketed reduce-scatter /
    all-gather update path. The JSON line reports the per-chip optimizer
    slot footprint of both arms (read off the placed shardings — the
    memory win is the point of ZeRO-1/2) plus the throughput delta the
    extra collectives cost. ``off`` runs the baseline once and reports
    ratio 1.0 — the self-calibration dial for the queue.
    """
    metric = "resnet50_zero_opt_state_ratio"
    unit = "x"
    ladder = _ladder_override(
        (128 * n_chips, 64 * n_chips, 32 * n_chips), n_chips)

    def run(arm: str):
        return _run_ladder(
            lambda bs: bench_resnet50(bs, base_overrides={
                "train": {"spmd_mode": "shard_map"},
                "optimizer": {"zero_sharding": arm},
            }),
            ladder, metric, unit, chip, writer=writer)

    baseline = run("off")
    if baseline is None:
        return 1
    target = run("shard_map") if mode == "shard_map" else baseline
    if target is None:
        return 1

    base_b = baseline.get("opt_state_bytes_per_chip")
    tgt_b = target.get("opt_state_bytes_per_chip")
    ratio = round(base_b / tgt_b, 3) if base_b and tgt_b else None
    base_rate = baseline["images_per_sec"] / n_chips
    tgt_rate = target["images_per_sec"] / n_chips
    out = {
        "metric": metric,
        "value": ratio if ratio is not None else 0.0,
        "unit": unit,
        "vs_baseline": 0.0,
        "baseline_kind": "zero-off-self",
        "chip": chip,
        "num_chips": n_chips,
        "mesh_axes": target.get("mesh_axes"),
        "zero_sharding": mode,
        "baseline_opt_state_bytes_per_chip": base_b,
        "target_opt_state_bytes_per_chip": tgt_b,
        "baseline_images_per_sec_per_chip": round(base_rate, 2),
        "target_images_per_sec_per_chip": round(tgt_rate, 2),
        # Relative throughput change from the sharded update alone (same
        # ladder, same mesh): -0.02 = 2% slower than the replicated
        # optimizer. The memory ratio above is what that 2% buys.
        "throughput_delta": round(tgt_rate / base_rate - 1.0, 4),
        "run_id": writer.run_id,
    }
    _annotate_roofline(out, target, chip, n_chips)
    _annotate_memory(out, target, chip, n_chips)
    _emit_bench_result(writer, f"resnet50-zero-{mode}", out, target)
    _emit_json_line(out)
    return 0


# BENCH_PRECISION arm → the `precision:` config block it runs under
# (core/config.py PrecisionConfig). The ladder is CUMULATIVE — each rung
# keeps the previous rungs' levers — because the §13 queue item reads the
# deltas as successive bites out of the same HBM roofline, not as
# independent toggles.
_PRECISION_MODES = {
    "f32": {},
    "bf16": {"activation_dtype": "bf16"},
    "bf16_fused": {"activation_dtype": "bf16", "fused_update": True},
    "bf16_int8": {"activation_dtype": "bf16", "fused_update": True,
                  "matmul_dtype": "int8"},
}


def _run_precision_ab(writer, mode: str, n_chips: int, chip: str) -> int:
    """BENCH_PRECISION=f32|bf16|bf16_fused|bf16_int8 — the precision
    ladder A/B (ISSUE 13 / chip_window_queue.sh §13).

    Runs the ResNet-50 workload TWICE on the same batch ladder under
    ``train.spmd_mode=shard_map`` + ZeRO weight-update sharding (the
    substrate precision.fused_update composes with): an all-f32 compute
    baseline (f32 model dtype, empty ``precision:`` block), then the
    requested rung. The JSON line reports the per-chip peak-HBM ratio
    (baseline/target — the memory the rung buys), both arms'
    ``ai_flops_per_byte`` (the roofline position the rung moves), and the
    throughput delta. ``f32`` runs the baseline once and reports ratio
    1.0 — the self-calibration dial for the queue.
    """
    metric = "resnet50_precision_hbm_peak_ratio"
    unit = "x"
    ladder = _ladder_override(
        (128 * n_chips, 64 * n_chips, 32 * n_chips), n_chips)

    def run(precision: dict):
        return _run_ladder(
            lambda bs: bench_resnet50(bs, base_overrides={
                # f32 model dtype in BOTH arms: the ladder isolates the
                # `precision:` block itself (activation_dtype overrides
                # the model dtype for the target rungs), and the f32
                # infeed keeps the batch bytes constant across arms.
                "model": {"dtype": "float32"},
                "data": {"image_dtype": "float32"},
                "train": {"spmd_mode": "shard_map"},
                "optimizer": {"zero_sharding": "shard_map"},
                "precision": precision,
            }),
            ladder, metric, unit, chip, writer=writer)

    baseline = run(_PRECISION_MODES["f32"])
    if baseline is None:
        return 1
    target = run(_PRECISION_MODES[mode]) if mode != "f32" else baseline
    if target is None:
        return 1

    def peak_of(result):
        probe: dict = {}
        _annotate_memory(probe, result, chip, n_chips)
        return probe.get("hbm_peak_bytes_per_chip")

    base_peak, tgt_peak = peak_of(baseline), peak_of(target)
    ratio = (round(base_peak / tgt_peak, 3)
             if base_peak and tgt_peak else None)
    base_rate = baseline["images_per_sec"] / n_chips
    tgt_rate = target["images_per_sec"] / n_chips
    base_probe: dict = {}
    _annotate_roofline(base_probe, baseline, chip, n_chips)
    out = {
        "metric": metric,
        "value": ratio if ratio is not None else 0.0,
        "unit": unit,
        "vs_baseline": 0.0,
        "baseline_kind": "f32-compute-self",
        "chip": chip,
        "num_chips": n_chips,
        "mesh_axes": target.get("mesh_axes"),
        "precision": dict(_PRECISION_MODES[mode]),
        "baseline_hbm_peak_bytes_per_chip": base_peak,
        "target_hbm_peak_bytes_per_chip": tgt_peak,
        "baseline_ai_flops_per_byte": base_probe.get("ai_flops_per_byte"),
        "baseline_images_per_sec_per_chip": round(base_rate, 2),
        "target_images_per_sec_per_chip": round(tgt_rate, 2),
        # Relative throughput change from the precision rung alone (same
        # ladder, same mesh): +0.10 = 10% faster than all-f32 compute.
        "throughput_delta": round(tgt_rate / base_rate - 1.0, 4),
        "run_id": writer.run_id,
    }
    _annotate_roofline(out, target, chip, n_chips)
    _annotate_memory(out, target, chip, n_chips)
    _emit_bench_result(writer, f"resnet50-precision-{mode}", out, target)
    _emit_json_line(out)
    return 0


def _run(writer) -> int:
    from distributed_tensorflow_framework_tpu.core import telemetry

    workload = os.environ.get("BENCH_WORKLOAD", "resnet50")
    metric = {"bert": "bert_base_mlm_examples_per_sec_per_chip",
              "inception": "inception_v3_images_per_sec_per_chip"}.get(
        workload, "resnet50_images_per_sec_per_chip")
    unit = ("examples/sec/chip" if workload == "bert" else "images/sec/chip")
    meta = dict(argv=sys.argv, workload=workload,
                bench_env={k: v for k, v in sorted(os.environ.items())
                           if k.startswith("BENCH_")})
    from distributed_tensorflow_framework_tpu.core.mesh import device_record

    try:
        device = device_record()
    except RuntimeError as e:
        # Backend unavailable: the driver still gets one valid JSON line
        # naming the cause, and exit 1.
        writer.emit_run_meta(**meta)
        writer.emit(telemetry.KIND_FAILURE,
                    health={"failure": "backend_init", "error": str(e)})
        _emit_json_line({"metric": metric, "value": 0.0, "unit": unit,
                         "vs_baseline": 0.0, "error": f"backend init: {e}",
                         "run_id": writer.run_id})
        return 1
    writer.emit_run_meta(**meta, **device)
    n_chips, chip = device["device_count"], device["device_kind"]

    coll_mode = os.environ.get("BENCH_COLLECTIVE", "").strip()
    if coll_mode:
        if coll_mode not in _COLLECTIVE_MODES:
            err = (f"BENCH_COLLECTIVE={coll_mode!r} not in "
                   f"{sorted(_COLLECTIVE_MODES)}")
            writer.emit(telemetry.KIND_FAILURE,
                        health={"failure": "bench_config", "error": err})
            _emit_json_line({"metric": metric, "value": 0.0, "unit": unit,
                             "vs_baseline": 0.0, "error": err,
                             "run_id": writer.run_id})
            return 1
        # The A/B owns the whole invocation (always the resnet50
        # workload): one JSON line comparing f32 wire vs the requested
        # format on the same ladder.
        return _run_collective_ab(writer, coll_mode, n_chips, chip)

    zero_mode = os.environ.get("BENCH_ZERO", "").strip()
    if zero_mode:
        if zero_mode not in _ZERO_MODES:
            err = (f"BENCH_ZERO={zero_mode!r} not in "
                   f"{sorted(_ZERO_MODES)}")
            writer.emit(telemetry.KIND_FAILURE,
                        health={"failure": "bench_config", "error": err})
            _emit_json_line({"metric": metric, "value": 0.0, "unit": unit,
                             "vs_baseline": 0.0, "error": err,
                             "run_id": writer.run_id})
            return 1
        # Like BENCH_COLLECTIVE, the A/B owns the invocation: one JSON
        # line comparing replicated vs ZeRO-sharded optimizer state on
        # the same ladder.
        return _run_zero_ab(writer, zero_mode, n_chips, chip)

    precision_mode = os.environ.get("BENCH_PRECISION", "").strip()
    if precision_mode:
        if precision_mode not in _PRECISION_MODES:
            err = (f"BENCH_PRECISION={precision_mode!r} not in "
                   f"{sorted(_PRECISION_MODES)}")
            writer.emit(telemetry.KIND_FAILURE,
                        health={"failure": "bench_config", "error": err})
            _emit_json_line({"metric": metric, "value": 0.0, "unit": unit,
                             "vs_baseline": 0.0, "error": err,
                             "run_id": writer.run_id})
            return 1
        # One JSON line comparing all-f32 compute vs the requested rung
        # of the precision ladder on the same ladder of batch sizes.
        return _run_precision_ab(writer, precision_mode, n_chips, chip)

    if workload == "bert":
        # The transformer workload (kept OFF the driver's default path —
        # the ONE default JSON line stays ResNet, the tracked BASELINE
        # metric). Knobs: BENCH_ATTN, BENCH_REMAT, BENCH_SEQ, BENCH_BS,
        # BENCH_FUSED_QKV, BENCH_PACK.
        seq = int(os.environ.get("BENCH_SEQ", "512"))
        attn = os.environ.get("BENCH_ATTN", "pallas")
        remat = os.environ.get("BENCH_REMAT", "0") not in ("", "0")
        pack = int(os.environ.get("BENCH_PACK", "0"))
        # One (H,3H) projection GEMM per layer instead of three (H,H) —
        # the fragmentation-lever candidate (models/bert.py).
        fused_qkv = os.environ.get("BENCH_FUSED_QKV", "0") not in ("", "0")
        accum = max(1, int(os.environ.get("BENCH_ACCUM", "1")))
        # Pipeline-schedule A/B (docs/DISTRIBUTED.md): BENCH_PP carves a
        # pipe axis; the schedule knobs only mean something with it set.
        pp = int(os.environ.get("BENCH_PP", "0"))
        pp_sched = os.environ.get("BENCH_SCHEDULE", "gpipe")
        pp_micro = int(os.environ.get("BENCH_MICRO", "0"))
        pp_virtual = int(os.environ.get("BENCH_VIRTUAL", "0"))
        ladder = _ladder_override(
            (64 * n_chips, 32 * n_chips, 16 * n_chips), n_chips)
        # Scale the ladder by accum so each micro-step keeps the ladder's
        # GEMM shapes; the effective batch (and examples counted per
        # timed step) grows accum×. NOTE this makes BENCH_BS the per-chip
        # per-MICRO batch when BENCH_ACCUM>1 (global batch =
        # BENCH_BS × n_chips × BENCH_ACCUM) — there is deliberately no
        # way to pin the effective batch while varying accum, because
        # the accum A/B's contract is constant micro-GEMM shapes.
        ladder = tuple(b * accum for b in ladder)
        result = _run_ladder(
            lambda bs: bench_bert(bs, seq_len=seq, attention_impl=attn,
                                  remat=remat, pack=pack,
                                  fused_qkv=fused_qkv, accum=accum,
                                  pipeline_stages=pp,
                                  pipeline_schedule=pp_sched,
                                  pipeline_microbatches=pp_micro,
                                  pipeline_virtual_stages=pp_virtual),
            ladder, metric, unit, chip, writer=writer)
        if result is None:
            return 1
        out = {
            "metric": metric,
            "value": round(result["examples_per_sec"] / n_chips, 2),
            "unit": unit,
            # No reference-published BERT number exists (BASELINE.md);
            # report the absolute rates and roofline position instead.
            "vs_baseline": 0.0,
            "baseline_kind": "none",
            "chip": chip,
            "num_chips": n_chips,
            "mesh_axes": result.get("mesh_axes"),
            "seq_len": seq,
            "attention_impl": attn,
            "remat": remat,
            "pack": pack,
            "grad_accum": accum,
            **({"pipeline_stages": pp,
                "pipeline_schedule": pp_sched,
                "pipeline_microbatches": pp_micro or pp,
                "pipe_bubble_frac": round(_pp_bubble(
                    pp_sched, pp, pp_micro or pp, pp_virtual), 4)}
               if pp else {}),
            "tokens_per_sec_per_chip": round(
                result["tokens_per_sec"] / n_chips, 1),
            # Useful-token/doc throughput: what packing actually moves —
            # position throughput is ~constant at fixed (bs, seq), but
            # packed rows carry ~3x the real tokens (BENCH_PACK doc).
            "real_tokens_per_sec_per_chip": round(
                result["real_tokens_per_sec"] / n_chips, 1),
            "docs_per_sec_per_chip": round(
                result["docs_per_sec"] / n_chips, 2),
            "run_id": writer.run_id,
        }
        _annotate_roofline(out, result, chip, n_chips,
                           accum_scaled=accum > 1)
        _annotate_memory(out, result, chip, n_chips)
        _check_leaderboard(out, workload)
        _emit_bench_result(writer, workload, out, result)
        _emit_json_line(out)
        return 0

    if workload == "inception":
        # BASELINE config 4's model; no published reference number
        # (BASELINE.json publishes none for any workload), so like bert
        # this reports absolute rate + roofline position only.
        ladder = _ladder_override(
            (128 * n_chips, 64 * n_chips, 32 * n_chips), n_chips)
        result = _run_ladder(bench_inception, ladder, metric, unit, chip,
                             writer=writer)
        if result is None:
            return 1
        out = {
            "metric": metric,
            "value": round(result["images_per_sec"] / n_chips, 2),
            "unit": unit,
            "vs_baseline": 0.0,
            "baseline_kind": "none",
            "chip": chip,
            "num_chips": n_chips,
            "mesh_axes": result.get("mesh_axes"),
            "run_id": writer.run_id,
        }
        _annotate_roofline(out, result, chip, n_chips)
        _annotate_memory(out, result, chip, n_chips)
        _check_leaderboard(out, workload)
        _emit_bench_result(writer, workload, out, result)
        _emit_json_line(out)
        return 0

    ladder = _ladder_override(
        (256 * n_chips, 128 * n_chips, 64 * n_chips), n_chips)
    result = _run_ladder(bench_resnet50, ladder, metric, unit, chip,
                         writer=writer)
    if result is None:
        return 1

    per_chip = result["images_per_sec"] / n_chips
    out = {
        "metric": metric,
        "value": round(per_chip, 2),
        "unit": unit,
        "vs_baseline": round(per_chip / TARGET_PER_CHIP, 4),
        # vs_baseline comparator: BASELINE.json publishes no measured
        # reference number (published: {}), so the denominator is the
        # north-star TARGET slice (10k img/s aggregate on v5e-64 →
        # 156.25/chip), NOT a measured reference (VERDICT r4 weak #5).
        "baseline_kind": "north-star-target",
        "baseline_value": TARGET_PER_CHIP,
        "chip": chip,
        "num_chips": n_chips,
        "mesh_axes": result.get("mesh_axes"),
        "run_id": writer.run_id,
    }
    _annotate_roofline(out, result, chip, n_chips)
    _annotate_memory(out, result, chip, n_chips)
    _check_leaderboard(out, workload)
    _emit_bench_result(writer, workload, out, result)
    _emit_json_line(out)
    return 0


def main() -> int:
    from distributed_tensorflow_framework_tpu.core import platform

    platform.resolve_compilation_cache()
    writer = _bench_writer()
    try:
        return _run(writer)
    finally:
        writer.close()


if __name__ == "__main__":
    sys.exit(main())
