#!/bin/bash
# Round-5 chip-window measurement queue — now a thin wrapper over the
# autotuner's compiled plan (scripts/autotune.py --plan chip_window,
# tools/autotune/plan.py). The queue recipes themselves live in the plan
# compiler; this script only preserves the operator entry point:
#
#   bash scripts/chip_window_queue.sh > /tmp/chipq.log 2>&1
#
# Serial runs, one child at a time under a parent that never touches JAX
# (one process per chip), nothing else on the host. §0 (graftcheck) still
# runs FIRST and still refuses the window — exec passes the autotuner's
# exit codes straight through: 0 done, 1 the preflight failed (window
# refused). The dtf-autotune-journal/1 journal keeps every settled
# trial, so re-landing this same command after a kill resumes where it
# stopped instead of re-spending the budget.
#
# The plan-manifest lines below are the machine-readable section→label
# map; tests/test_autotune.py asserts every label appears in
# `autotune.py --plan chip_window --dry-run`, so the wrapper and the
# compiler cannot drift apart silently.
#
# plan-manifest §0: graftcheck
# plan-manifest §1: resnet
# plan-manifest §13: prec-f32 prec-bf16 prec-bf16-fused prec-bf16-int8
# plan-manifest §7: wk-verify-2048 wk-verify-4096
# plan-manifest §8: pp-sanity pp-gpipe pp-1f1b pp-interleaved
# plan-manifest §9: coll-f32 coll-bf16 coll-int8
# plan-manifest §10: serve-clean serve-train serve-export serve-batched serve-unbatched
# plan-manifest §11: zero-off zero-shard_map
# plan-manifest §12: mem-headline mem-summary
# plan-manifest §14: serve-fleet
# plan-manifest §15: gang-probe gang-clean gang-1p gang-2p gang-ab gang-ab-2p
# plan-manifest §16: decode-clean decode-train decode-export decode-continuous decode-static decode-int8
# plan-manifest §17: infeed-unpacked infeed-packed infeed-block infeed-stride
# plan-manifest §2: bert-base bert-fqkv
# plan-manifest §3: tile-512-1024 tile-1024-1024
# plan-manifest §4: crossover
# plan-manifest §4b: fused-bwd-verify fused-bwd
# plan-manifest §4c: bert-accum4
# plan-manifest §5: trace
# plan-manifest §6: inception
set -u
cd "$(dirname "$0")/.."
echo "=== chip queue start $(date -u +%FT%TZ) (autotune plan mode) ==="
exec python scripts/autotune.py --plan chip_window "$@"
