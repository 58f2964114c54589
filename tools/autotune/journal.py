"""dtf-autotune-journal/1 — the resumable trial journal.

Append-only JSONL, one record per trial state change. The journal is why
a killed chip window (preemption, operator ctrl-C) continues where it
stopped instead of re-spending completed trials: on restart the tuner
replays the file, treats every trial whose LAST record is terminal
(``done`` / ``skipped`` / ``failed``) as settled, and re-runs only trials
left ``started`` (killed mid-flight) or never seen.
"""

from __future__ import annotations

import json
import os
import time

JOURNAL_SCHEMA = "dtf-autotune-journal/1"

# Terminal statuses: the trial consumed its decision and must not re-run
# on resume. "started" is non-terminal by design.
TERMINAL_STATUSES = ("done", "skipped", "failed")


class JournalError(RuntimeError):
    """A journal line that is not valid JSON or carries the wrong schema
    tag. Raised by TrialJournal.replay (strict mode) and caught by the
    scripts/autotune.py CLI, which refuses to resume from a corrupt
    journal rather than silently re-running paid-for trials."""


class TrialJournal:
    def __init__(self, path: str):
        self.path = path

    def replay(self, strict: bool = True) -> dict[str, dict]:
        """{trial_id: last record} from the journal (empty if absent)."""
        state: dict[str, dict] = {}
        if not os.path.exists(self.path):
            return state
        with open(self.path) as fh:
            for lineno, line in enumerate(fh, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError as e:
                    if strict:
                        raise JournalError(
                            f"{self.path}:{lineno}: not JSON ({e})") from e
                    continue
                if rec.get("schema") != JOURNAL_SCHEMA:
                    if strict:
                        raise JournalError(
                            f"{self.path}:{lineno}: schema "
                            f"{rec.get('schema')!r} != {JOURNAL_SCHEMA!r}")
                    continue
                trial = rec.get("trial")
                if trial:
                    state[trial] = rec
        return state

    def settled(self) -> dict[str, dict]:
        """Trials whose last status is terminal — skipped on resume."""
        return {t: rec for t, rec in self.replay().items()
                if rec.get("status") in TERMINAL_STATUSES}

    def record(self, trial: str, status: str, **fields) -> dict:
        """Append one state change (fsync'd — the journal must survive
        the very kill it exists to recover from)."""
        rec = {"schema": JOURNAL_SCHEMA, "trial": trial, "status": status,
               "t": time.time(), **fields}
        os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                    exist_ok=True)
        with open(self.path, "a") as fh:
            fh.write(json.dumps(rec) + "\n")
            fh.flush()
            os.fsync(fh.fileno())
        return rec
