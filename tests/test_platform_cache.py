"""core/platform.resolve_compilation_cache: where the persistent
compilation cache lives.

The contract: with ``JAX_COMPILATION_CACHE_DIR`` set, JAX honours the
variable itself and the program sets no directory in code; unset, the
directory is ``<checkout>/.jax_cache`` — fixed, derived from the
package's own path, the same in every process (the directory is part of
the cache key, so a path that moves never hits). No compile runs with
the cache armed here, and the jax.config value is always restored.
"""

import os
import pathlib
import subprocess
import sys

import jax
import pytest

from distributed_tensorflow_framework_tpu.core import platform

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_env_set_means_no_directory_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(platform.COMPILATION_CACHE_ENV, str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert platform.resolve_compilation_cache() == str(tmp_path)
    assert calls == []


def test_unset_is_the_fixed_checkout_path_in_every_process(monkeypatch):
    monkeypatch.delenv(platform.COMPILATION_CACHE_ENV, raising=False)
    want = str(REPO / ".jax_cache")
    assert platform.DEFAULT_COMPILATION_CACHE_DIR == want
    before = jax.config.jax_compilation_cache_dir
    try:
        assert platform.resolve_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # A second process, started from another directory, lands on the
    # same path: nothing in it comes from cwd, pid, time or tempfile.
    env = {k: v for k, v in os.environ.items()
           if k != platform.COMPILATION_CACHE_ENV}
    env["PYTHONPATH"] = str(REPO)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from distributed_tensorflow_framework_tpu.core import platform\n"
         "print(platform.resolve_compilation_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd="/", env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]


def test_cpu_collective_timeouts_only_when_pointed_at_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_cpu_collective_call_warn_stuck_timeout_seconds=7")
    platform.apply_cpu_collective_timeouts()
    flags = os.environ["XLA_FLAGS"]
    assert flags.count("warn_stuck_timeout_seconds") == 1  # the user's wins
    assert "warn_stuck_timeout_seconds=7" in flags
    assert "terminate_timeout_seconds=1200" in flags


@pytest.mark.parametrize("platforms", ["", "tpu", "tpu,cpu"])
def test_no_cpu_flags_for_any_other_platform(monkeypatch, platforms):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", "--foo=1")
    platform.apply_cpu_collective_timeouts()
    assert os.environ["XLA_FLAGS"] == "--foo=1"
