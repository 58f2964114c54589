"""Trial runners: supervised subprocesses + the deterministic fake.

SubprocessRunner drives bench.py / scripts/load_gen.py exactly the way
scripts/chip_window_queue.sh used to: one child per trial — this parent
is stdlib-only and never touches a backend, so each child has the chip
to itself — and the result read from the BENCH_OUT file (never regexed
out of warning-polluted stdout). A child that exits nonzero fails its
trial (TrialRunError); the search goes on.

FakeRunner serves the CPU-only test tier: a spec table mapping trial ids
to canned payloads/exit codes (plus optional per-trial sleeps, so kill/
resume drills can interrupt a window deterministically) exercises
pruning, journaling, scoring and leaderboard pinning without a chip.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time


class TrialRunError(RuntimeError):
    """The child failed (nonzero exit, timeout, launch error). Caught
    per-trial by the search loop: the trial is journaled ``failed`` and
    the search continues."""


@dataclasses.dataclass
class TrialResult:
    exit_code: int
    payload: dict | None        # the bench's ONE JSON line (BENCH_OUT)
    summary: dict | None = None  # dtf-run-summary/1, when the trial has one
    duration_s: float = 0.0


class SubprocessRunner:
    def __init__(self, cwd: str, *, timeout_s: float | None = None):
        self.cwd = cwd
        self.timeout_s = timeout_s

    def run(self, trial_id: str, argv: list[str],
            env: dict[str, str]) -> TrialResult:
        merged = dict(os.environ)
        merged.update(env)
        with tempfile.TemporaryDirectory(prefix="autotune-") as tmp:
            out_path = os.path.join(tmp, "bench_out.json")
            merged.setdefault("BENCH_OUT", out_path)
            start = time.monotonic()
            try:
                proc = subprocess.run(
                    argv, cwd=self.cwd, env=merged,
                    timeout=self.timeout_s, stdout=subprocess.PIPE,
                    stderr=sys.stderr.fileno() if hasattr(sys.stderr, "fileno")
                    else None, text=True)
            except subprocess.TimeoutExpired as e:
                raise TrialRunError(
                    f"{trial_id}: timed out after {self.timeout_s}s") from e
            except OSError as e:
                raise TrialRunError(f"{trial_id}: launch failed: {e}") from e
            duration = time.monotonic() - start
            payload = self._read_payload(merged["BENCH_OUT"], proc.stdout)
            if proc.returncode != 0:
                raise TrialRunError(
                    f"{trial_id}: exit {proc.returncode} "
                    f"(payload: {payload})")
            return TrialResult(exit_code=proc.returncode, payload=payload,
                               duration_s=duration)

    @staticmethod
    def _read_payload(out_path: str, stdout: str | None) -> dict | None:
        """BENCH_OUT file first; last JSON-parsable stdout line as the
        fallback for children that predate the BENCH_OUT contract
        (scripts/verify_flash_kernels.py et al.)."""
        try:
            with open(out_path) as fh:
                return json.load(fh)
        except (OSError, ValueError):
            pass
        for line in reversed((stdout or "").splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except ValueError:
                    continue
        return None


class FakeRunner:
    """Deterministic runner for the CPU smoke drill. ``spec`` maps trial
    id (or "*" default) to {"exit_code", "payload", "summary",
    "sleep_s"}; a nonzero exit raises TrialRunError, mirroring the
    subprocess runner so the search loop under test is the production
    one."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.calls: list[str] = []

    @classmethod
    def from_file(cls, path: str) -> "FakeRunner":
        with open(path) as fh:
            return cls(json.load(fh))

    def run(self, trial_id: str, argv: list[str],
            env: dict[str, str]) -> TrialResult:
        self.calls.append(trial_id)
        rec = self.spec.get(trial_id) or self.spec.get("*") or {}
        sleep_s = float(rec.get("sleep_s") or 0.0)
        if sleep_s:
            time.sleep(sleep_s)
        rc = int(rec.get("exit_code") or 0)
        if rc != 0:
            raise TrialRunError(f"{trial_id}: fake exit {rc}")
        return TrialResult(exit_code=0, payload=rec.get("payload"),
                           summary=rec.get("summary"), duration_s=sleep_s)
