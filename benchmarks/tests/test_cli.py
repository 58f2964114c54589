"""Off the chip the command fails, says why, and prints no result."""

import os
import subprocess
import sys

from benchmarks.tests.tiny import ROOT


def run(args, cwd=ROOT, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
        cwd=cwd, env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
        capture_output=True, text=True, timeout=300)


def test_run_py_fails_off_the_chip_with_a_clear_line():
    p = run(["--workload", "bert_s512", "--seed", "1", "--seconds", "1",
             "--trace", "0"])
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    assert "{" not in p.stdout          # no metric line, nothing to mistake


def test_unknown_cell_fails_before_touching_jax():
    p = run(["--workload", "nope", "--seed", "1", "--seconds", "1",
             "--trace", "0"])
    assert p.returncode != 0 and "no workload 'nope'" in p.stderr


def test_fails_where_the_program_is_absent(tmp_path):
    from benchmarks.tests.test_manifest import copy_benchmark

    root = copy_benchmark(tmp_path)
    p = run(["--workload", "bert_s512", "--seed", "1", "--seconds", "1",
             "--trace", "0"], cwd=root, env={"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "program is not in this checkout" in p.stderr
