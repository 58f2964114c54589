"""scripts/train_resilient.py: bounded relaunch around a failing command.

The recovery contract it wraps (auto-restore + exact resume) is tested
end-to-end elsewhere (test_fault_tolerance.py, the RESULTS.md MoE run);
these tests pin the wrapper's own loop semantics with cheap commands.
"""

import subprocess
import sys

import pytest

SCRIPT = "scripts/train_resilient.py"


def run(args, env_extra=None):
    import os

    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, SCRIPT, *args], env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_succeeds_first_try(tmp_path):
    r = run(["--max-attempts", "3", "--",
             sys.executable, "-c", "print('ok')"])
    assert r.returncode == 0
    assert "done (attempt 1)" in r.stderr


def test_retries_until_success(tmp_path):
    # Fails twice (no state file yet, then one marker), succeeds third.
    marker = tmp_path / "tries"
    prog = (
        "import pathlib, sys; p = pathlib.Path(r'%s'); "
        "n = int(p.read_text()) if p.exists() else 0; "
        "p.write_text(str(n + 1)); sys.exit(0 if n >= 2 else 1)" % marker
    )
    r = run(["--max-attempts", "5", "--retry-sleep", "0.1", "--",
             sys.executable, "-c", prog])
    assert r.returncode == 0
    assert "done (attempt 3)" in r.stderr
    assert marker.read_text() == "3"


def test_exhaustion_propagates_rc():
    r = run(["--max-attempts", "2", "--retry-sleep", "0.1", "--",
             sys.executable, "-c", "import sys; sys.exit(7)"])
    assert r.returncode == 7
    assert "attempt 2 exited rc=7" in r.stderr


def test_checkpoint_warning():
    r = run(["--max-attempts", "1", "--",
             sys.executable, "-c", "print('x')"])
    assert "no checkpoint.directory" in r.stderr
    r2 = run(["--max-attempts", "1", "--",
              sys.executable, "-c", "print('x')",
              "--set", "checkpoint.directory=/tmp/ck"])
    assert "no checkpoint.directory" not in r2.stderr


def test_cpu_fast_fail_flags_env():
    from scripts.train_resilient import build_env

    env = build_env({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    assert "terminate_timeout_seconds=240" in env["XLA_FLAGS"]
    # user-set value wins
    env = build_env({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_cpu_collective_call_terminate_timeout_seconds=9",
    })
    assert env["XLA_FLAGS"].count("terminate_timeout_seconds") == 1
    # non-CPU platform untouched
    env = build_env({"JAX_PLATFORMS": "tpu", "XLA_FLAGS": "abc"})
    assert env["XLA_FLAGS"] == "abc"


def test_empty_checkpoint_dir_still_warns():
    # `checkpoint.directory=` (explicitly empty → checkpointing OFF) must
    # still warn: relaunches would restart from step 0.
    r = run(["--max-attempts", "1", "--",
             sys.executable, "-c", "print('x')",
             "--set", "checkpoint.directory="])
    assert "no checkpoint.directory" in r.stderr


def test_signal_death_maps_to_shell_convention():
    # The designed failure mode: XLA's terminate timeout SIGABRTs the
    # child (returncode -6) — the wrapper must report 134 (128+SIGABRT).
    r = run(["--max-attempts", "1", "--",
             sys.executable, "-c",
             "import os, signal; os.kill(os.getpid(), signal.SIGABRT)"])
    assert r.returncode == 134, r.returncode
    assert "exited rc=134" in r.stderr


def test_config_yaml_suppresses_checkpoint_warning():
    # A --config may set checkpoint.directory in YAML — don't cry wolf.
    r = run(["--max-attempts", "1", "--",
             sys.executable, "-c", "print('x')",
             "--config", "configs/bert_base_mlm.yaml"])
    assert "no checkpoint.directory" not in r.stderr


def test_config_yaml_without_checkpoint_dir_warns(tmp_path):
    # A user YAML with checkpointing disabled must NOT suppress the
    # warning — the launcher parses the YAML instead of assuming any
    # --config enables checkpointing (ADVICE r4).
    cfg = tmp_path / "no_ckpt.yaml"
    cfg.write_text("model:\n  name: lenet5\ncheckpoint:\n  directory: ''\n")
    r = run(["--max-attempts", "1", "--",
             sys.executable, "-c", "print('x')",
             "--config", str(cfg)])
    assert "no checkpoint.directory" in r.stderr
    # An unreadable --config keeps the benefit of the doubt (the trainer
    # itself fails loudly on it).
    r2 = run(["--max-attempts", "1", "--",
              sys.executable, "-c", "print('x')",
              "--config", str(tmp_path / "missing.yaml")])
    assert "no checkpoint.directory" not in r2.stderr


def test_cancellation_not_retried():
    r = run(["--max-attempts", "5", "--retry-sleep", "0.1", "--",
             sys.executable, "-c",
             "import os, signal; os.kill(os.getpid(), signal.SIGTERM)"])
    assert r.returncode == 143
    assert "cancelled" in r.stderr
    assert "attempt 2" not in r.stderr
