"""The search loop: enumerate → prune → run → score → journal → pin.

One loop serves both modes. Space mode turns a SearchSpace into bench
trials, prunes candidates the roofline model predicts more than
``prune_margin`` worse than the incumbent on the binding resource
(tools/autotune/model), runs the survivors through the runner, scores
them goodput-weighted (tools/autotune/scoring), and pins the winner in
configs/leaderboard.json + configs/best_<workload>.yaml. Plan mode runs
a compiled PlannedTrial list (tools/autotune/plan) through the same
journal/runner machinery — no pruning, the queue arms are all wanted.

A TrialRunError fails only its trial. Every decision (ran / pruned /
failed) is journaled (dtf-autotune-journal/1) and emitted as
KIND_AUTOTUNE_TRIAL telemetry when a writer is attached; a killed
window resumes from the journal.
"""

from __future__ import annotations

from tools.autotune import model as traffic_model
from tools.autotune import scoring
from tools.autotune.journal import TrialJournal
from tools.autotune.runner import TrialRunError


def trial_id_for(overrides: dict) -> str:
    """Stable trial id for a candidate = its config digest, so the
    journal, the leaderboard and the telemetry all key the same way."""
    from tools.autotune.leaderboard import config_digest

    return config_digest(overrides)


class SearchResult(dict):
    """Plain dict subclass so callers can json.dump it directly."""


def _emit(writer, **payload) -> None:
    if writer is not None:
        from distributed_tensorflow_framework_tpu.core import telemetry

        writer.emit(telemetry.KIND_AUTOTUNE_TRIAL, **payload)


def run_space_search(space, profile, runner, journal: TrialJournal, *,
                     prune_margin: float = 0.05, max_trials: int = 0,
                     writer=None, log=print) -> SearchResult:
    """Search ``space`` for ``space.workload``; returns the tally dict
    {"workload", "ran", "pruned", "resumed", "failed",
    "best": {trial, overrides, score...}|None}."""
    baseline = space.baseline()
    settled = journal.settled()
    best: dict | None = None
    # Resume: re-adopt the best settled score so a resumed window can't
    # crown a worse winner than the killed one already measured.
    for tid, rec in settled.items():
        if rec.get("status") == "done" and rec.get("score") is not None:
            if best is None or rec["score"] > best["score"]:
                best = {"trial": tid, "overrides": rec.get("overrides"),
                        "score": rec["score"], "value": rec.get("value"),
                        "goodput_frac": rec.get("goodput_frac"),
                        "unit": rec.get("unit"),
                        "payload": rec.get("payload")}
    tally = {"workload": space.workload, "ran": 0, "pruned": 0,
             "resumed": 0, "failed": 0}
    for overrides in space.enumerate():
        if max_trials and tally["ran"] >= max_trials:
            log(f"autotune: max_trials={max_trials} reached — stopping")
            break
        tid = trial_id_for(overrides)
        if tid in settled:
            tally["resumed"] += 1
            log(f"autotune: {tid} already "
                f"{settled[tid].get('status')} (journal) — not re-running")
            continue
        skip, reason, detail = traffic_model.prune_decision(
            profile, overrides, baseline, prune_margin)
        if skip:
            tally["pruned"] += 1
            log(f"autotune: PRUNE {tid} {overrides}: {reason}")
            journal.record(tid, "skipped", overrides=overrides,
                           reason=reason, prediction=detail)
            _emit(writer, trial=tid, status="skipped", reason=reason,
                  overrides=overrides, prediction=detail)
            continue
        log(f"autotune: RUN {tid} {overrides}: {reason}")
        journal.record(tid, "started", overrides=overrides,
                       prediction=detail)
        _emit(writer, trial=tid, status="started", overrides=overrides)
        try:
            result = runner.run(tid, ["python", "bench.py"],
                                space.trial_env(overrides))
        except TrialRunError as e:
            tally["failed"] += 1
            journal.record(tid, "failed", overrides=overrides,
                           error=str(e))
            _emit(writer, trial=tid, status="failed", error=str(e))
            log(f"autotune: FAILED {tid}: {e}")
            continue
        tally["ran"] += 1
        scored = scoring.score_trial(result.payload, result.summary)
        journal.record(tid, "done", overrides=overrides,
                       payload=result.payload,
                       duration_s=round(result.duration_s, 3), **scored)
        _emit(writer, trial=tid, status="done", overrides=overrides,
              **scored)
        log(f"autotune: DONE {tid}: score {scored['score']} "
            f"({scored['value']} x goodput {scored['goodput_frac']})")
        if best is None or scored["score"] > best["score"]:
            best = {"trial": tid, "overrides": overrides,
                    "payload": result.payload, **scored}
    out = SearchResult(tally)
    out["best"] = best
    return out


def pin_winner(result: SearchResult, *, leaderboard_path: str,
               best_yaml_path: str, regression_margin: float = 0.05,
               provenance: dict | None = None, log=print) -> dict | None:
    """Write the leaderboard entry + best_<workload>.yaml for the
    search's winner (no-op when nothing scored)."""
    from tools.autotune import leaderboard as board

    best = result.get("best")
    if not best or not best.get("overrides"):
        log("autotune: no winner to pin (nothing scored)")
        return None
    payload = best.get("payload") or {}
    entry = board.pin_entry(
        leaderboard_path, result["workload"],
        config=best["overrides"], score=best["score"],
        unit=best.get("unit") or payload.get("unit") or "",
        bound=payload.get("bound"), chip=payload.get("chip"),
        provenance=provenance or {},
        regression_margin=regression_margin)
    board.write_best_yaml(
        best_yaml_path, result["workload"], best["overrides"],
        score=best["score"], digest=entry["config_digest"])
    log(f"autotune: pinned {result['workload']} incumbent "
        f"{entry['config_digest']} score {entry['score']} "
        f"→ {leaderboard_path}")
    return entry


def run_plan(trials, runner, journal: TrialJournal, *, writer=None,
             log=print) -> SearchResult:
    """Execute a compiled PlannedTrial list (plan mode). A preflight
    failure refuses the window (the §0 contract); gated trials are
    skipped when their gate didn't succeed; everything is journaled
    under the trial's §section/label id for resume."""
    settled = journal.settled()
    tally = {"workload": "chip_window", "ran": 0, "pruned": 0,
             "resumed": 0, "failed": 0, "preflight_failed": False}
    succeeded: set[str] = {
        rec.get("label") or tid for tid, rec in settled.items()
        if rec.get("status") == "done"}
    for trial in trials:
        tid = f"s{trial.section}:{trial.label}"
        if tid in settled:
            tally["resumed"] += 1
            if settled[tid].get("status") == "done":
                succeeded.add(trial.label)
            log(f"autotune: {tid} already "
                f"{settled[tid].get('status')} (journal) — not re-running")
            continue
        if trial.gate and trial.gate not in succeeded:
            tally["pruned"] += 1
            reason = f"gate {trial.gate!r} did not succeed"
            journal.record(tid, "skipped", label=trial.label,
                           section=trial.section, reason=reason)
            _emit(writer, trial=tid, status="skipped", reason=reason)
            log(f"autotune: SKIP {tid}: {reason}")
            continue
        journal.record(tid, "started", label=trial.label,
                       section=trial.section)
        _emit(writer, trial=tid, status="started", section=trial.section)
        try:
            result = runner.run(tid, list(trial.argv), trial.env_dict())
        except TrialRunError as e:
            tally["failed"] += 1
            journal.record(tid, "failed", label=trial.label, error=str(e))
            _emit(writer, trial=tid, status="failed", error=str(e))
            log(f"autotune: FAILED {tid}: {e}")
            if trial.kind == "preflight":
                # §0: a failed preflight refuses the window.
                tally["preflight_failed"] = True
                log(f"autotune: preflight {tid} failed — refusing to "
                    f"spend the window")
                break
            continue
        tally["ran"] += 1
        succeeded.add(trial.label)
        scored = scoring.score_trial(result.payload, result.summary)
        journal.record(tid, "done", label=trial.label,
                       section=trial.section, payload=result.payload,
                       duration_s=round(result.duration_s, 3), **scored)
        _emit(writer, trial=tid, status="done", section=trial.section,
              **scored)
        log(f"autotune: DONE {tid} (score {scored['score']})")
    return SearchResult(tally)
