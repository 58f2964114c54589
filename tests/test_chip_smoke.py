"""chip_smoke.py off the chip: it fails, says why, and prints no result.

The smoke itself only means something on a TPU (the builder's tool runs
it there). What tier-1 holds it to is the other half of its contract: on
a CPU backend — this suite's ``JAX_PLATFORMS=cpu`` is inherited, never
overridden — and in a directory that holds nothing else of the repo, it
exits non-zero without the ``{"ok": true, ...}`` line.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = "distributed_tensorflow_framework_tpu"


def _run_smoke(cwd):
    assert os.environ["JAX_PLATFORMS"] == "cpu"  # tests/conftest.py
    shutil.copy(REPO / "chip_smoke.py", cwd)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          capture_output=True, text=True, timeout=300)


def test_off_the_chip_fails_naming_the_platform(tmp_path):
    (tmp_path / PACKAGE).symlink_to(REPO / PACKAGE)
    out = _run_smoke(tmp_path)
    assert out.returncode != 0
    assert "leg device FAILED" in out.stderr
    assert "default backend is 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
    # It got as far as asking JAX, and no further.
    assert "platform cpu" in (
        tmp_path / "chiprun_out" / "chip_smoke" / "device.out").read_text()
    assert not (tmp_path / "chiprun_out" / "chip_smoke" / "bert.out").exists()


def test_alone_in_a_directory_fails_without_a_result(tmp_path):
    out = _run_smoke(tmp_path)
    assert out.returncode != 0
    assert "leg device FAILED" in out.stderr
    assert '"ok"' not in out.stdout


# -- the parent's own helpers (stdlib only; importing it touches no JAX) --


def _load_smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke_under_test", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cache_counts_are_distinct_keys_not_log_lines(tmp_path, monkeypatch):
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "LOGS", tmp_path)
    hit = ("Persistent compilation cache hit for 'jit_step' with key "
           "'jit_step-aa11'")
    miss = ("PERSISTENT COMPILATION CACHE MISS for 'jit_init' with key "
            "'jit_init-bb22'")
    # A process with two log handlers prints every line twice.
    (tmp_path / "leg.err").write_text("\n".join(
        [f"DEBUG:x:{hit}", f"2026 D jax] {hit}",
         f"DEBUG:x:{miss}", f"2026 D jax] {miss}", "unrelated"]))
    assert smoke._cache_counts("leg") == {"hits": 1, "misses": 1}


def test_a_child_without_a_result_line_fails_its_leg():
    smoke = _load_smoke()
    assert smoke._last_json('log line\n{"ok": true}\n') == {"ok": True}
    with pytest.raises(smoke.LegFailed):
        smoke._last_json("log line\nanother\n")


def _kernel_result(cases):
    return json.dumps({
        "ok": True, "platform": "tpu", "kernel_mode": "mosaic",
        "streaming_backward_default": "fused",
        "cases": [{"case": name, "dtype": dtype, "variants": {
            "unsegmented": {"mosaic_calls": calls,
                            "out_rel_l2_vs_reference": 1e-3}}}
            for name, dtype, calls in cases]})


@pytest.mark.parametrize("cases,verdict", [
    ([("cell_s512", "bfloat16", 2), ("f32_s512", "float32", 2),
      ("sub_tile", "bfloat16", 3)], None),
    ([("cell_s512", "bfloat16", 2)], "ran only"),
    ([("cell_s512", "bfloat16", 2), ("f32_s512", "float32", 1)],
     "f32_s512/unsegmented: 1 Mosaic calls"),
], ids=["whole-matrix", "one-dtype", "interpreted-case"])
def test_kernel_leg_wants_both_dtypes_compiled_by_mosaic(
        tmp_path, monkeypatch, cases, verdict):
    """The kernel leg passes the script no case names, so it runs the
    script's own matrix, and holds the answer to: bf16 and float32 cases
    both there (the dispatch rule names no dtype), a forward and a
    backward kernel from Mosaic in every program."""
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "LOGS", tmp_path)
    (tmp_path / "kernels.err").write_text("")
    argvs = []
    monkeypatch.setattr(
        smoke, "run_child",
        lambda name, argv, timeout: (argvs.append(argv),
                                     _kernel_result(cases))[1])
    device = {"platform": "tpu"}
    if verdict is None:
        out = smoke.leg_kernels(device)
        assert out["cases"] == [c[0] for c in cases]
    else:
        with pytest.raises(smoke.LegFailed, match=verdict):
            smoke.leg_kernels(device)
    assert argvs[0][1:] == ["scripts/verify_flash_kernels.py"]
