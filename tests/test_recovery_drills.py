"""End-to-end recovery-ladder drills (docs/RESILIENCE.md).

Real training children under DTF_FAULTS, proving the ladder's three
acceptance contracts: (1) a transient NaN is detected, rolled back, and
skipped IN PROCESS — the run finishes rc=0 with no relaunch; (2) a
stalled input pipeline surfaces through the infeed watchdog and the loop
retries through it; (3) a persistent anomaly (re-poisoned data region)
exhausts max_rollbacks and escalates with the distinct
ANOMALY_ESCALATION_RC, which the supervisor classifies as
persistent_anomaly without feeding the crash-loop breaker.

The fast per-rung mechanics live in tests/test_anomaly.py /
tests/test_infeed.py / tests/test_faults.py; these are tier-2 by their
slow marks (subprocess training children, minutes each).
"""

import os
import subprocess
import sys

import pytest

from distributed_tensorflow_framework_tpu.core import supervision, telemetry
from tests.test_fault_tolerance import _child_env

RECOVERY_DRIVER = """
import sys
import jax; jax.config.update('jax_platforms','cpu')
from distributed_tensorflow_framework_tpu.cli.train import main
{prelude}
sys.exit(
 main(['--set','model.name=lenet5','--set','model.dtype=float32',
      '--set','data.name=synthetic_images','--set','data.image_size=28',
      '--set','data.channels=1','--set','data.global_batch_size=64',
      '--set','mesh.data=8',
      '--set','optimizer.name=sgd_momentum','--set','optimizer.learning_rate=0.01',
      '--set','train.total_steps={steps}','--set','train.log_interval=10',
      '--set','train.eval_steps=0',
      '--set','checkpoint.directory={ckpt}',
      '--set','checkpoint.save_interval_steps=20',
      '--set','checkpoint.async_save=false'{extra}]))
"""


# XLA:CPU reports no memory statistics, so a child takes the blocking
# snapshot; this line gives it a device with room, and with it the
# two-phase snapshot (train/anomaly.py).
ROOMY_DEVICE = (
    "from distributed_tensorflow_framework_tpu.train import anomaly; "
    "anomaly.device_memory = lambda d: (1 << 20, 1 << 34)")


def _driver(ckpt: str, steps: int, overrides: dict[str, str],
            prelude: str = "") -> str:
    extra = "".join(f",\n      '--set','{k}={v}'" for k, v in overrides.items())
    return RECOVERY_DRIVER.format(ckpt=ckpt, steps=steps, extra=extra,
                                  prelude=prelude)


def _run_child(prog: str, env_extra: dict, timeout: float = 420.0):
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [sys.executable, "-c", prog], env=_child_env(env_extra),
        cwd=repo_root, capture_output=True, text=True, timeout=timeout,
    )


def _events(ckpt_dir: str, kind: str) -> list[dict]:
    return list(telemetry.read_events(
        os.path.join(ckpt_dir, "events.jsonl"), kind=kind, strict=False))


@pytest.mark.slow
@pytest.mark.slowest
@pytest.mark.parametrize("two_phase", [False, True])
def test_nan_recovers_in_process_no_relaunch(tmp_path, two_phase):
    """Acceptance drill 1: DTF_FAULTS=nan_grads:30 poisons one batch; the
    run must detect at the next metric fetch, roll back to the last clean
    snapshot, skip the poisoned region, and FINISH — rc=0, one process,
    zero relaunches, with the full event trail on disk. The same with
    the snapshots launched on the device and landed beside the next
    steps: the rollback finds step 20's in the ring all the same."""
    ckpt = str(tmp_path / "ckpt")
    prog = _driver(ckpt, steps=60, overrides={
        "resilience.snapshot_interval_steps": "10",
        "resilience.lr_rewarmup_steps": "5",
    }, prelude=ROOMY_DEVICE if two_phase else "")
    r = _run_child(prog, {"DTF_FAULTS": "nan_grads:30"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    # in process: no checkpoint restore ever happened
    assert "Restored checkpoint at step" not in r.stdout + r.stderr

    anomalies = _events(ckpt, telemetry.KIND_ANOMALY)
    rollbacks = _events(ckpt, telemetry.KIND_ROLLBACK)
    skips = _events(ckpt, telemetry.KIND_BATCH_SKIPPED)
    assert len(anomalies) == 1 and anomalies[0]["step"] == 30
    assert anomalies[0]["health"]["anomaly"] == "non_finite_metric"
    assert len(rollbacks) == 1
    assert rollbacks[0]["health"] == {"from_step": 30, "to_step": 20,
                                      "consecutive_rollbacks": 1}
    assert skips[0]["health"]["batches"] == 10
    # a single run_id across every event: the same process start to finish
    run_ids = {e.get("run_id") for e in telemetry.read_events(
        os.path.join(ckpt, "events.jsonl"), strict=False)}
    assert len(run_ids) == 1
    # the ladder's rollup renders in the analyzer summary
    summary = telemetry.summarize_events(os.path.join(ckpt, "events.jsonl"))
    text = telemetry.format_run_summary(summary)
    assert "rollback: step 30 -> 20" in text
    assert "batches skipped: 10" in text
    counters = summary["goodput"]["counters"]
    assert counters["snapshots"] >= 6
    # every periodic snapshot took the path the device's room allows
    assert counters.get("snapshots_overlapped", 0) == (
        counters["snapshots"] - 1 if two_phase else 0)


@pytest.mark.slow
@pytest.mark.slowest
def test_infeed_stall_watchdog_recovers(tmp_path):
    """Acceptance drill 2: a 6s pipeline stall mid-run (pull 25, well past
    compile and the prefetch buffer's coverage) surfaces as watchdog
    retries, and the loop rides through it to rc=0."""
    ckpt = str(tmp_path / "ckpt")
    prog = _driver(ckpt, steps=40, overrides={
        "resilience.infeed_deadline_s": "0.5",
        "resilience.infeed_retries": "20",
        "resilience.infeed_backoff_s": "0.1",
    })
    r = _run_child(prog, {"DTF_FAULTS": "stall_infeed:6s:25"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    stalls = _events(ckpt, telemetry.KIND_INFEED_STALL)
    assert stalls, "watchdog never fired — the stall was absorbed silently"
    assert all(e["health"]["deadline_s"] == 0.5 for e in stalls)
    attempts = [e["health"]["attempt"] for e in stalls]
    assert attempts == sorted(attempts)  # one incident, monotone retries
    summary = telemetry.summarize_events(os.path.join(ckpt, "events.jsonl"))
    assert summary["recovery"]["infeed_stalls"] == len(stalls)


@pytest.mark.slow
@pytest.mark.slowest
def test_persistent_anomaly_escalates_distinct_rc(tmp_path):
    """Acceptance drill 3: repeat_nan re-poisons steps [30, 35) so every
    rollback lands back on a bad step; after max_rollbacks=2 the child
    must exit ANOMALY_ESCALATION_RC — not a generic crash rc — with the
    rollback trail in telemetry."""
    ckpt = str(tmp_path / "ckpt")
    prog = _driver(ckpt, steps=60, overrides={
        "resilience.snapshot_interval_steps": "10",
        "resilience.max_rollbacks": "2",
    })
    r = _run_child(prog, {"DTF_FAULTS": "repeat_nan:30:5"})
    assert r.returncode == supervision.ANOMALY_ESCALATION_RC, (
        f"rc={r.returncode}\n" + r.stdout[-3000:] + r.stderr[-3000:])
    assert "Persistent anomaly" in r.stdout + r.stderr
    rollbacks = _events(ckpt, telemetry.KIND_ROLLBACK)
    assert len(rollbacks) == 2  # the full budget, then escalation
    assert all(e["health"]["to_step"] == 20 for e in rollbacks)
    assert len(_events(ckpt, telemetry.KIND_ANOMALY)) == 3
