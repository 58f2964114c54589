#!/usr/bin/env python
"""Goodput-driven autotuner CLI (tools/autotune; docs/PERFORMANCE.md
"Autotuning").

Two modes, one journal/runner/scoring machinery:

  --space SPEC.json     roofline-pruned config search over a typed knob
                        space (tools/autotune/space): candidates the
                        analytic traffic model predicts more than
                        autotune.prune_margin worse than the incumbent
                        on the binding resource are skipped with the
                        prediction logged; survivors run as supervised
                        bench.py subprocesses, are scored
                        goodput-weighted from their run summary, and the
                        winner is pinned in configs/leaderboard.json +
                        configs/best_<workload>.yaml (bench.py reads the
                        pin back and flags regressions).
  --plan chip_window    the compiled scripts/chip_window_queue.sh
                        backlog (§0 preflight, BENCH_r02
                        revalidation first, then the §13 precision
                        ladder, then §7–§17 and the round-5 tail) run
                        through the same journal. --dry-run prints the
                        prioritized trial list without spending anything.

Exit codes: 0 done, 1 real failure (the §0 preflight failing refuses the
window). The dtf-autotune-journal/1 journal keeps every settled trial, so
re-landing the same command after a kill continues where it stopped.

SPEC.json: {"workload": ..., "incumbent": {chip, n_chips, flops_per_step,
hbm_bytes_per_step, wire_bytes_per_step, opt_state_bytes,
examples_per_step}, "knobs": [{"path": "precision.activation_dtype",
"values": ["", "bf16"], "env": "BENCH_PRECISION"}, ...]} — knob paths are
validated against the real config dataclasses; each knob's FIRST value is
the incumbent's setting.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="autotune.py",
        description="roofline-pruned, goodput-scored config search")
    p.add_argument("--plan", choices=("chip_window",),
                   help="run a compiled plan instead of a space search")
    p.add_argument("--space", help="SearchSpace spec JSON (see docstring)")
    p.add_argument("--dry-run", action="store_true",
                   help="print the trial list and exit (plan mode)")
    p.add_argument("--config",
                   help="experiment YAML supplying the autotune.* knobs")
    p.add_argument("--set", action="append", default=[], dest="overrides",
                   metavar="K=V", help="config override (load_config)")
    p.add_argument("--journal", help="journal path (default: "
                   "autotune.journal_path or <out-dir>/autotune_journal"
                   ".jsonl)")
    p.add_argument("--out-dir", help="leaderboard/best-yaml dir "
                   "(default: autotune.out_dir)")
    p.add_argument("--fake-runner", metavar="SPEC.json",
                   help="deterministic canned runner (the CPU test tier)")
    p.add_argument("--timeout-s", type=float, default=None,
                   help="per-trial subprocess timeout")
    return p


def main(argv: list[str] | None = None) -> int:
    args = _build_parser().parse_args(argv)
    if bool(args.plan) == bool(args.space):
        print("autotune: exactly one of --plan / --space is required",
              file=sys.stderr)
        return 1

    from distributed_tensorflow_framework_tpu.core.config import load_config
    from distributed_tensorflow_framework_tpu.core.telemetry import (
        TelemetryWriter,
    )
    from tools import autotune as tune_lib

    try:
        # No --config still goes through load_config so bare --set
        # overrides apply (and get validated) against the defaults.
        cfg = load_config(args.config, overrides=args.overrides)
    except (OSError, ValueError) as e:
        print(f"autotune: bad config: {e}", file=sys.stderr)
        return 1
    tune = cfg.autotune
    out_dir = args.out_dir or tune.out_dir
    journal_path = (args.journal or tune.journal_path
                    or os.path.join(out_dir, "autotune_journal.jsonl"))

    # Plan mode --dry-run needs no runner/journal — print and leave.
    if args.plan:
        trials = tune_lib.compile_chip_window_plan()
        if args.dry_run:
            print(tune_lib.format_plan(trials))
            return 0
    else:
        try:
            with open(args.space) as fh:
                spec = json.load(fh)
            space = tune_lib.SearchSpace.from_spec(spec)
        except (OSError, ValueError) as e:
            print(f"autotune: bad --space: {e}", file=sys.stderr)
            return 1
        profile = tune_lib.TrafficProfile(
            **{k: v for k, v in (spec.get("incumbent") or {}).items()})

    if args.fake_runner:
        runner = tune_lib.FakeRunner.from_file(args.fake_runner)
    else:
        runner = tune_lib.SubprocessRunner(
            str(_ROOT), timeout_s=args.timeout_s)

    journal = tune_lib.TrialJournal(journal_path)
    events_path = os.path.join(
        os.path.dirname(os.path.abspath(journal_path)),
        "autotune_events.jsonl")
    writer = TelemetryWriter(events_path)
    try:
        if args.plan:
            result = tune_lib.run_plan(trials, runner, journal,
                                       writer=writer)
        else:
            result = tune_lib.run_space_search(
                space, profile, runner, journal,
                prune_margin=tune.prune_margin,
                max_trials=tune.max_trials, writer=writer)
            if result.get("best"):
                tune_lib.pin_winner(
                    result,
                    leaderboard_path=os.path.join(out_dir,
                                                  "leaderboard.json"),
                    best_yaml_path=os.path.join(
                        out_dir, f"best_{space.workload}.yaml"),
                    regression_margin=tune.regression_margin,
                    provenance={"run_id": writer.run_id,
                                "journal": journal_path,
                                "spec": args.space})
    finally:
        writer.close()
    print(json.dumps(dict(result)))
    if result.get("preflight_failed"):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
