#!/bin/bash
# Tier-1 wrapper: the ROADMAP.md verify command plus the graftcheck
# static-analysis gate, with both artifacts archived side by side so CI
# keeps the dtf-lint-report/1 JSON next to the pytest log.
#
#   bash scripts/run_tier1.sh [ARTIFACT_DIR]     (default /tmp/tier1)
#
# Exit code: non-zero if EITHER pytest or graftcheck fails. graftcheck
# runs first — it is seconds, and a finding there (untallied collective,
# dead donation, busted thread contract) explains test failures better
# than the tests do.
set -u -o pipefail
cd "$(dirname "$0")/.."

ART="${1:-/tmp/tier1}"
# TIER1_MARKERS widens the run (e.g. TIER1_MARKERS='slow and serve'
# runs the fleet chaos drill, whose serve-bench JSON is archived below).
MARKERS="${TIER1_MARKERS:-not slow}"
mkdir -p "$ART"

echo "=== graftcheck (full run, JSON → $ART/graftcheck.json) ==="
env JAX_PLATFORMS=cpu python scripts/graftcheck.py \
    --json "$ART/graftcheck.json" | tee "$ART/graftcheck.log"
gc_rc=${PIPESTATUS[0]}

echo "=== tier-1 pytest (log → $ART/pytest.log) ==="
# DTF_SERVE_BENCH_DIR: when a slow run includes the fleet chaos drill
# (tests/test_fleet_drill.py), its dtf-serve-bench/2 JSON lands here
# next to the other artifacts instead of dying with pytest's tmpdir.
# DTF_GANG_DRILL_DIR: same contract for the gang chaos drills
# (tests/test_cluster_drill.py) — their supervisor_events.jsonl is the
# attempt-by-attempt record of the coordinated restart / gang refit.
# DTF_TRACE_DIR: the drills' Perfetto trace exports and any
# flight-recorder dumps land here too (docs/OBSERVABILITY.md "Tracing
# and flight recorder").
# DTF_DECODE_BENCH_DIR: the decode acceptance drill
# (tests/test_decode_drill.py) archives its continuous-vs-static A/B
# bench JSON (dtf-serve-bench/2 schema, mode "decode") the same way.
# DTF_DATA_DRILL_DIR: the exactly-once data drill
# (tests/test_data_drill.py) archives its per-attempt telemetry —
# supervisor events plus the worker event streams whose data_state /
# data_shard records prove the multiset claim.
timeout -k 10 870 env JAX_PLATFORMS=cpu DTF_SERVE_BENCH_DIR="$ART" \
    DTF_GANG_DRILL_DIR="$ART" DTF_TRACE_DIR="$ART" \
    DTF_DECODE_BENCH_DIR="$ART" DTF_DATA_DRILL_DIR="$ART" \
    python -m pytest tests/ -q \
    -m "$MARKERS" --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
    | tee "$ART/pytest.log"
py_rc=${PIPESTATUS[0]}

if [ -f "$ART/SERVE_BENCH_FLEET.json" ]; then
  echo "=== serve bench archived: $ART/SERVE_BENCH_FLEET.json ==="
fi
# The autoscale drill (tests/test_autoscale_drill.py) archives its
# shaped-load bench (per-tenant attribution) and the router's raw
# scaling-event telemetry for the same slow runs.
if [ -f "$ART/SERVE_BENCH_AUTOSCALE.json" ]; then
  echo "=== autoscale bench archived: $ART/SERVE_BENCH_AUTOSCALE.json ==="
fi
if [ -f "$ART/AUTOSCALE_EVENTS.jsonl" ]; then
  echo "=== autoscale events archived: $ART/AUTOSCALE_EVENTS.jsonl ==="
fi
if [ -f "$ART/GANG_DRILL_EVENTS.jsonl" ]; then
  echo "=== gang drill events archived: $ART/GANG_DRILL_EVENTS.jsonl ==="
fi
# The decode acceptance drill (tests/test_decode_drill.py) archives its
# continuous-vs-static A/B bench JSON for the same slow runs.
for bench in "$ART"/DECODE_BENCH_*.json; do
  [ -f "$bench" ] && echo "=== decode bench archived: $bench ==="
done
for trace in "$ART"/*TRACE*.json; do
  [ -f "$trace" ] && echo "=== perfetto trace archived: $trace ==="
done
for dump in "$ART"/flightrec-*.json; do
  [ -f "$dump" ] && echo "=== flight-recorder dump archived: $dump ==="
done
# The exactly-once data drill (tests/test_data_drill.py) archives the
# telemetry that backs its consumed-sample multiset comparison.
for ev in "$ART"/DATA_DRILL_*.jsonl; do
  [ -f "$ev" ] && echo "=== data drill events archived: $ev ==="
done

echo "=== tier-1 summary: graftcheck rc=$gc_rc pytest rc=$py_rc ==="
[ "$gc_rc" -eq 0 ] && [ "$py_rc" -eq 0 ]
