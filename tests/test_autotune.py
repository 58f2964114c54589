"""The goodput-driven autotuner (scripts/autotune.py, tools/autotune).

CPU-only acceptance drill for the chip-window tuner, per the contracts
in docs/PERFORMANCE.md "Autotuning":

- a toy two-knob space over REAL config paths where the roofline/traffic
  model prunes at least one candidate with the prediction logged;
- a mid-search kill that resumes from the dtf-autotune-journal/1
  journal without re-running settled trials (subprocess, SIGKILL);
- the winner pinned in leaderboard.json with a digest bench.py's
  regression check verifies;
- `autotune.py --plan chip_window --dry-run` covering every section/
  label the chip_window_queue.sh wrapper's plan-manifest declares;
- KIND_AUTOTUNE_TRIAL telemetry rolled up by summarize_events and
  rendered by format_run_summary.

When DTF_AUTOTUNE_DIR is set (scripts/run_tier1.sh), the smoke drill's
journal + leaderboard are archived there as AUTOTUNE_* artifacts.
"""

import json
import os
import pathlib
import shutil
import signal
import subprocess
import sys
import time

import pytest

from distributed_tensorflow_framework_tpu.core import telemetry
from tools import autotune as tune

REPO = pathlib.Path(__file__).resolve().parent.parent

# The toy space: two real knobs, incumbent (first value) = BENCH_r02's
# bf16/no-remat shape on one v5e. float32 activations re-widen the HBM
# traffic the precision pack shrank, so the model must prune them.
SPEC = {
    "workload": "resnet50",
    "incumbent": {
        "chip": "TPU v5 lite", "n_chips": 1,
        "flops_per_step": 6.26e12,
        "hbm_bytes_per_step": 6.26e12 / 78.7,
        "wire_bytes_per_step": 2e9,
        "opt_state_bytes": 1e9,
        "examples_per_step": 256,
    },
    "knobs": [
        {"path": "precision.activation_dtype",
         "values": ["bf16", "float32"], "env": "BENCH_PRECISION"},
        {"path": "model.remat_policy", "values": ["none", "full"]},
    ],
}

GOOD_PAYLOAD = {
    "workload": "resnet50", "value": 2600.0, "unit": "images/sec/chip",
    "bound": "hbm_bandwidth", "chip": "TPU v5 lite",
}
GOOD_SUMMARY = {"schema": "dtf-run-summary/1",
                "goodput_ledger": {"goodput_frac": 0.93}}


def _space_and_profile():
    space = tune.SearchSpace.from_spec(SPEC)
    profile = tune.TrafficProfile(**SPEC["incumbent"])
    return space, profile


def _archive(src: pathlib.Path, name: str) -> None:
    """run_tier1.sh contract: park drill artifacts in DTF_AUTOTUNE_DIR."""
    art_dir = os.environ.get("DTF_AUTOTUNE_DIR", "").strip()
    if art_dir and src.exists():
        shutil.copyfile(src, os.path.join(art_dir, name))


class TestSearchSpace:
    def test_paths_validated_against_real_config(self):
        with pytest.raises(tune.SearchSpaceError):
            tune.SearchSpace.from_spec({
                "workload": "w",
                "knobs": [{"path": "precision.no_such_knob",
                           "values": ["a", "b"]}],
            })

    def test_enumerate_baseline_first(self):
        space, _ = _space_and_profile()
        cands = list(space.enumerate())
        assert len(cands) == 4
        assert cands[0] == space.baseline() == {
            "precision.activation_dtype": "bf16",
            "model.remat_policy": "none",
        }

    def test_trial_env_maps_env_knobs_only(self):
        space, _ = _space_and_profile()
        env = space.trial_env({"precision.activation_dtype": "float32",
                               "model.remat_policy": "full"})
        assert env == {"BENCH_PRECISION": "float32"}


class TestPruning:
    def test_f32_pruned_bf16_kept(self):
        space, profile = _space_and_profile()
        base = space.baseline()
        skip, reason, detail = tune.prune_decision(
            profile, {"precision.activation_dtype": "float32",
                      "model.remat_policy": "none"}, base, 0.05)
        assert skip
        assert "worse on hbm_bandwidth" in reason
        assert detail["predicted_rate"] < detail["incumbent_rate"]
        skip2, _, _ = tune.prune_decision(profile, base, base, 0.05)
        assert not skip2

    def test_digest_is_stable_and_order_insensitive(self):
        a = tune.config_digest({"x": 1, "y": 2})
        b = tune.config_digest({"y": 2, "x": 1})
        assert a == b and a.startswith("sha256:")


class TestJournal:
    def test_terminal_vs_nonterminal(self, tmp_path):
        j = tune.TrialJournal(str(tmp_path / "j.jsonl"))
        j.record("t1", "started")
        j.record("t1", "done", score=1.0)
        j.record("t2", "started")          # interrupted — must re-run
        j.record("t4", "skipped", reason="pruned")
        settled = tune.TrialJournal(str(tmp_path / "j.jsonl")).settled()
        assert set(settled) == {"t1", "t4"}
        assert settled["t1"]["score"] == 1.0

    def test_strict_replay_raises_on_garbage(self, tmp_path):
        p = tmp_path / "j.jsonl"
        p.write_text('{"schema": "wrong/1", "trial": "t", '
                     '"status": "done"}\n')
        with pytest.raises(tune.JournalError):
            tune.TrialJournal(str(p)).replay(strict=True)


class TestScoring:
    def test_goodput_weighted(self):
        s = tune.score_trial(GOOD_PAYLOAD, GOOD_SUMMARY)
        assert s["score"] == pytest.approx(2600.0 * 0.93)
        assert s["unit"] == "images/sec/chip"

    def test_no_ledger_means_full_weight(self):
        s = tune.score_trial({"value": 10.0, "unit": "x"}, None)
        assert s["score"] == 10.0 and s["goodput_frac"] == 1.0


class TestSmokeDrill:
    """The acceptance drill: search → prune → score → pin → bench reads
    the pin back. Everything in-process except the payloads, which come
    from the deterministic FakeRunner."""

    def _run(self, tmp_path):
        space, profile = _space_and_profile()
        runner = tune.FakeRunner({"*": {"exit_code": 0,
                                        "payload": GOOD_PAYLOAD,
                                        "summary": GOOD_SUMMARY}})
        journal_path = tmp_path / "journal.jsonl"
        logs: list[str] = []
        result = tune.run_space_search(
            space, profile, runner, tune.TrialJournal(str(journal_path)),
            prune_margin=0.05, log=logs.append)
        return space, journal_path, logs, result

    def test_prunes_at_least_one_with_logged_prediction(self, tmp_path):
        _, journal_path, logs, result = self._run(tmp_path)
        assert result["pruned"] >= 1 and result["ran"] >= 1
        pruned_logs = [ln for ln in logs if "PRUNE" in ln]
        assert pruned_logs and any("worse on" in ln for ln in pruned_logs)
        # The journal carries the full prediction for every skip.
        settled = tune.TrialJournal(str(journal_path)).settled()
        skipped = [r for r in settled.values()
                   if r.get("status") == "skipped"]
        assert skipped and all("predicted_rate" in r["prediction"]
                               for r in skipped)

    def test_winner_pinned_and_bench_verifies_digest(self, tmp_path,
                                                     monkeypatch):
        space, journal_path, _, result = self._run(tmp_path)
        board_path = tmp_path / "leaderboard.json"
        entry = tune.pin_winner(
            result, leaderboard_path=str(board_path),
            best_yaml_path=str(tmp_path / "best_resnet50.yaml"),
            log=lambda *_: None)
        assert entry["config_digest"] == tune.config_digest(
            entry["config"])
        assert entry["score"] == pytest.approx(2600.0 * 0.93)
        board = tune.load_board(str(board_path))
        assert board["schema"] == tune.LEADERBOARD_SCHEMA
        assert "resnet50" in board["entries"]
        # bench.py reads the pin back: digest verified, ratio annotated.
        import bench

        monkeypatch.setenv("BENCH_LEADERBOARD", str(board_path))
        out = {"value": 2600.0}
        bench._check_leaderboard(out, "resnet50")
        note = out["leaderboard"]
        assert note["digest_ok"] is True
        assert note["regression"] is False
        assert note["vs_incumbent"] == pytest.approx(2600.0 / entry["score"],
                                                     abs=1e-3)
        # A clearly slower rerun trips the regression flag.
        slow = {"value": 1000.0}
        bench._check_leaderboard(slow, "resnet50")
        assert slow["leaderboard"]["regression"] is True
        # A hand-edited pin fails the digest check.
        board["entries"]["resnet50"]["config"]["extra"] = True
        board_path.write_text(json.dumps(board))
        edited = {"value": 2600.0}
        bench._check_leaderboard(edited, "resnet50")
        assert edited["leaderboard"]["digest_ok"] is False
        _archive(journal_path, "AUTOTUNE_JOURNAL.jsonl")
        _archive(board_path, "AUTOTUNE_LEADERBOARD.json")

    def test_best_yaml_written_with_digest(self, tmp_path):
        _, _, _, result = self._run(tmp_path)
        yaml_path = tmp_path / "best_resnet50.yaml"
        tune.pin_winner(result,
                        leaderboard_path=str(tmp_path / "lb.json"),
                        best_yaml_path=str(yaml_path),
                        log=lambda *_: None)
        text = yaml_path.read_text()
        assert result["best"]["trial"] in text  # the digest, traceable
        assert "activation_dtype: bf16" in text


class TestKillResume:
    """SIGKILL the CLI mid-search; the journal must hand the next
    invocation every settled trial. Runs scripts/autotune.py exactly as
    an operator would (subprocess), with the FakeRunner supplying
    deterministic payloads and a long sleep to die inside."""

    def test_killed_search_resumes_without_rerunning(self, tmp_path):
        space, _ = _space_and_profile()
        trial_ids = [tune.trial_id_for(o) for o in space.enumerate()]
        spec_path = tmp_path / "spec.json"
        spec_path.write_text(json.dumps(SPEC))
        journal_path = tmp_path / "journal.jsonl"
        good = {"exit_code": 0, "payload": GOOD_PAYLOAD,
                "summary": GOOD_SUMMARY}
        fake_path = tmp_path / "fake.json"
        # First invocation: trial 0 fast, trial 1 sleeps long enough to
        # be killed inside.
        fake_path.write_text(json.dumps({
            trial_ids[0]: good,
            "*": dict(good, sleep_s=60.0),
        }))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        argv = [sys.executable, "scripts/autotune.py",
                "--space", str(spec_path), "--fake-runner", str(fake_path),
                "--journal", str(journal_path),
                "--out-dir", str(tmp_path)]
        proc = subprocess.Popen(argv, cwd=str(REPO), env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        try:
            # Kill once trial 0 settled and trial 1 started.
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                text = (journal_path.read_text()
                        if journal_path.exists() else "")
                if '"done"' in text and text.count('"started"') >= 2:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("journal never reached the kill point")
        finally:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        settled = tune.TrialJournal(str(journal_path)).settled()
        assert settled[trial_ids[0]]["status"] == "done"
        assert trial_ids[1] not in settled  # died mid-trial: unsettled
        # Second invocation: no sleeps; must resume, not re-run.
        fake_path.write_text(json.dumps({"*": good}))
        done = subprocess.run(argv, cwd=str(REPO), env=env,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              timeout=120)
        assert done.returncode == 0, done.stdout
        assert f"{trial_ids[0]} already done" in done.stdout
        result = json.loads(done.stdout.strip().splitlines()[-1])
        assert result["resumed"] >= 1 and result["ran"] >= 1
        # Exactly ONE done record for trial 0 across both invocations.
        records = [json.loads(ln)
                   for ln in journal_path.read_text().splitlines()]
        dones = [r for r in records
                 if r["trial"] == trial_ids[0] and r["status"] == "done"]
        assert len(dones) == 1
        # The completed window pinned its winner.
        board = tune.load_board(str(tmp_path / "leaderboard.json"))
        assert board["entries"]["resnet50"]["score"] == pytest.approx(
            2600.0 * 0.93)


class TestBenchOut:
    """BENCH_OUT=<path>: bench's ONE JSON line also lands in a file, so
    the runner never regexes results out of warning-polluted stdout."""

    def test_emit_json_line_writes_stdout_and_file(self, tmp_path,
                                                   monkeypatch, capsys):
        import bench

        out_path = tmp_path / "bench_out.json"
        monkeypatch.setenv("BENCH_OUT", str(out_path))
        bench._emit_json_line({"value": 1.5, "unit": "x"})
        assert json.loads(capsys.readouterr().out) == {"value": 1.5,
                                                       "unit": "x"}
        assert json.loads(out_path.read_text()) == {"value": 1.5,
                                                    "unit": "x"}

    def test_emit_json_line_overwrites_not_appends(self, tmp_path,
                                                   monkeypatch, capsys):
        import bench

        out_path = tmp_path / "bench_out.json"
        monkeypatch.setenv("BENCH_OUT", str(out_path))
        bench._emit_json_line({"try": 1})
        bench._emit_json_line({"try": 2})
        capsys.readouterr()
        # Whole-file semantics: the LAST emission is the file.
        assert json.loads(out_path.read_text()) == {"try": 2}

    def test_runner_payload_prefers_file_over_stdout(self, tmp_path):
        out_path = tmp_path / "out.json"
        out_path.write_text('{"value": 7}')
        got = tune.SubprocessRunner._read_payload(
            str(out_path), 'WARNING: noise\n{"value": 99}\n')
        assert got == {"value": 7}

    def test_runner_payload_stdout_fallback(self, tmp_path):
        got = tune.SubprocessRunner._read_payload(
            str(tmp_path / "missing.json"),
            'WARNING: noise\nnot json {\n{"value": 42}\n')
        assert got == {"value": 42}


class TestChipWindowPlan:
    """The compiled plan must cover every A/B the shell queue carried;
    chip_window_queue.sh's plan-manifest lines are the contract."""

    @pytest.fixture(scope="class")
    def dry_run(self):
        proc = subprocess.run(
            [sys.executable, "scripts/autotune.py", "--plan",
             "chip_window", "--dry-run"],
            cwd=str(REPO), env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        return proc.stdout

    def _manifest(self) -> dict[str, list[str]]:
        sections: dict[str, list[str]] = {}
        script = (REPO / "scripts" / "chip_window_queue.sh").read_text()
        for line in script.splitlines():
            if line.startswith("# plan-manifest §"):
                head, labels = line[len("# plan-manifest §"):].split(":", 1)
                sections[head.strip()] = labels.split()
        return sections

    def test_every_manifest_label_in_dry_run(self, dry_run):
        manifest = self._manifest()
        assert manifest, "wrapper lost its plan-manifest lines"
        planned = {(ln.split()[1].lstrip("§"), ln.split()[2])
                   for ln in dry_run.splitlines() if ln.strip()}
        for section, labels in manifest.items():
            for label in labels:
                assert (section, label) in planned, (
                    f"§{section} {label} declared by chip_window_queue.sh "
                    f"but missing from --plan chip_window --dry-run")
        # And nothing planned that the manifest doesn't declare.
        declared = {(s, lb) for s, lbs in manifest.items() for lb in lbs}
        assert planned == declared

    def test_sections_7_to_17_all_covered(self, dry_run):
        manifest = self._manifest()
        for section in [str(n) for n in range(7, 18)]:
            assert manifest.get(section), f"§{section} missing"
            assert f"§{section} " in dry_run

    def test_priority_order(self, dry_run):
        lines = dry_run.splitlines()
        # The §0 preflight first, then the BENCH_r02 revalidation, then
        # the §13 precision ladder before everything else.
        assert "§0 graftcheck [preflight]" in lines[0]
        assert "§1 resnet" in lines[1]
        assert "§13" in lines[2]

    def test_wrapper_is_thin(self):
        script = (REPO / "scripts" / "chip_window_queue.sh").read_text()
        assert "exec python scripts/autotune.py --plan chip_window" \
            in script

    def test_gates_respected_in_plan_mode(self, tmp_path):
        trials = tune.compile_chip_window_plan()
        by_label = {t.label: t for t in trials}
        # Spot-check the load-bearing gates: measurement arms wait on
        # their verify/export predecessors.
        assert by_label["fused-bwd"].gate == "fused-bwd-verify"
        assert by_label["serve-batched"].gate == "serve-export"
        # A failed preflight refuses the window (§0 contract).
        preflight_fail = tune.FakeRunner({"s0:graftcheck": {"exit_code": 1},
                                          "*": {"exit_code": 0}})
        result = tune.run_plan(
            trials, preflight_fail,
            tune.TrialJournal(str(tmp_path / "j.jsonl")),
            log=lambda *_: None)
        assert result["preflight_failed"] and result["ran"] == 0


class TestTelemetryRollup:
    def test_kind_summarized_and_rendered(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        w = telemetry.TelemetryWriter(path)
        w.emit(telemetry.KIND_AUTOTUNE_TRIAL, trial="sha256:aa",
               status="done", score=2418.0, unit="images/sec/chip")
        w.emit(telemetry.KIND_AUTOTUNE_TRIAL, trial="sha256:bb",
               status="skipped", reason="pruned")
        w.emit(telemetry.KIND_AUTOTUNE_TRIAL, trial="sha256:cc",
               status="failed", error="exit 1")
        w.close()
        summary = telemetry.summarize_events(path)
        at = summary["autotune"]
        assert at["ran"] == 1 and at["pruned"] == 1
        assert at["failed"] == 1
        assert at["best"] == {"trial": "sha256:aa", "score": 2418.0,
                              "unit": "images/sec/chip"}
        rendered = telemetry.format_run_summary(summary)
        assert "autotune: 1 ran / 1 pruned / 1 failed" in rendered
        assert "best: sha256:aa score 2418.0 images/sec/chip" in rendered

    def test_absent_without_events(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        w = telemetry.TelemetryWriter(path)
        w.emit(telemetry.KIND_TRAIN_STEP, step=1)
        w.close()
        assert telemetry.summarize_events(path)["autotune"] is None

    def test_search_loop_emits_the_kind(self, tmp_path):
        space, profile = _space_and_profile()
        runner = tune.FakeRunner({"*": {"exit_code": 0,
                                        "payload": GOOD_PAYLOAD,
                                        "summary": GOOD_SUMMARY}})
        path = str(tmp_path / "events.jsonl")
        w = telemetry.TelemetryWriter(path)
        tune.run_space_search(
            space, profile, runner,
            tune.TrialJournal(str(tmp_path / "j.jsonl")),
            prune_margin=0.05, writer=w, log=lambda *_: None)
        w.close()
        kinds = telemetry.summarize_events(path)["kinds"]
        assert kinds.get(telemetry.KIND_AUTOTUNE_TRIAL, 0) >= 4
