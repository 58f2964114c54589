"""Host-platform environment policy shared by the CLI and the test harness.

Pure string helpers only — this module must stay importable before JAX
initializes a backend (XLA_FLAGS is consumed at first backend init, so
callers mutate os.environ with these helpers first).
"""

from __future__ import annotations

import os

# XLA:CPU aborts a collective whose participants don't all reach the
# rendezvous within ~40 s (`rendezvous.cc` termination timeout). On small
# hosts running N virtual devices (N threads time-sharing few cores) the
# default trips mid-training — observed repeatedly on 8-device MoE
# runs on a 1-core VM. These defaults keep transient scheduling stalls
# from aborting short runs; anything the user already put in XLA_FLAGS
# wins. KNOWN LIMIT: some long-run freezes are NOT transient — a
# participant blocks permanently at an all-reduce with zero CPU load
# (intermittent; reproduced with async AND sync infeed). For those, the
# working recipe is the opposite tuning: a LOW terminate timeout (e.g.
# 240 s) plus frequent checkpoints and a relaunch loop, so the
# framework's auto-restore turns each freeze into a bounded restart —
# fault recovery doing its job rather than a hang.
CPU_COLLECTIVE_TIMEOUT_FLAGS: tuple[tuple[str, int], ...] = (
    ("xla_cpu_collective_call_warn_stuck_timeout_seconds", 120),
    ("xla_cpu_collective_call_terminate_timeout_seconds", 1200),
)


FAST_FAIL_COLLECTIVE_FLAGS: tuple[tuple[str, int], ...] = (
    # The retry-loop tuning (scripts/train_resilient.py): fast death +
    # relaunch beats a 20-minute hang when auto-restore is standing by.
    ("xla_cpu_collective_call_warn_stuck_timeout_seconds", 60),
    ("xla_cpu_collective_call_terminate_timeout_seconds", 240),
)


# JAX reads this variable itself (it is the ``jax_compilation_cache_dir``
# config flag's environment form), so when it is set the program sets no
# directory in code.
COMPILATION_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# The fixed fallback: ``<checkout>/.jax_cache``, derived from this
# package's own location. The directory is part of every cache key, so
# it must be the same in every process of every run — never a tempfile,
# a pid or a timestamp. Git-ignored.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def resolve_compilation_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Called by every entry point (cli/train.py, cli/serve.py,
    cli/export.py, bench.py, chip_smoke.py's children) before the first
    backend use, so a relaunched or sibling process reloads the compiled
    step instead of recompiling it — the dominant share of start-up
    (the ``startup`` telemetry event reports time-to-first-step and the
    directory). JAX's own thresholds decide what is worth caching.
    """
    from_env = os.environ.get(COMPILATION_CACHE_ENV)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILATION_CACHE_DIR)
    return DEFAULT_COMPILATION_CACHE_DIR


def with_cpu_collective_timeouts(flags: str, table=None) -> str:
    """Append rendezvous-timeout flags to an XLA_FLAGS string, skipping
    any flag the caller already set. ``table`` defaults to the
    long-run-tolerant values; pass FAST_FAIL_COLLECTIVE_FLAGS for the
    relaunch-loop tuning."""
    for name, value in (table or CPU_COLLECTIVE_TIMEOUT_FLAGS):
        if name not in flags:
            flags += f" --{name}={value}"
    return flags.strip()


def apply_cpu_collective_timeouts() -> None:
    """When the process was pointed at the CPU backend
    (``JAX_PLATFORMS=cpu...``), add the rendezvous-timeout defaults to
    ``XLA_FLAGS``. XLA reads the variable at first backend init, so entry
    points call this before touching JAX."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        os.environ["XLA_FLAGS"] = with_cpu_collective_timeouts(
            os.environ.get("XLA_FLAGS", ""))
