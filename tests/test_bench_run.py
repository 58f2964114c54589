"""bench.py run policy, with every backend effect injected: roofline
tagging, the batch ladder (steps down on resource exhaustion only), an
unavailable backend (exit 1 with the error line), and the collective
wire-format A/B.
"""

import importlib.util
import json
import pathlib

import jax

_BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / "bench.py"
_spec = importlib.util.spec_from_file_location("bench_under_test", _BENCH_PATH)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


# ------------------------------------------------- roofline tagging ----
# The accum>1 roofline artifacts must carry the "accum-scaled-upper" tag
# (accum-scaled flops/bytes make hbm_bw_util an upper bound — untagged,
# they read as directly comparable roofline positions).


def _roofline(chip="v5litepod-8", *, accum_scaled, flops=1.0e12):
    out = {}
    result = {"flops_per_step": flops, "bytes_per_step": 2.0e9,
              "sec_per_step": 0.1}
    bench._annotate_roofline(out, result, chip, 8,
                             accum_scaled=accum_scaled)
    return out


def test_accum_scaled_roofline_is_tagged():
    out = _roofline(accum_scaled=True)
    assert out["roofline_bound"] == "accum-scaled-upper"
    # the tag annotates, never replaces, the roofline numbers
    assert "tflops_per_sec" in out and "arith_intensity" in out


def test_unscaled_roofline_carries_no_tag():
    out = _roofline(accum_scaled=False)
    assert "roofline_bound" not in out
    assert "tflops_per_sec" in out


def test_roofline_tag_needs_a_cost_model():
    # No XLA cost model (flops 0/None): nothing to scale, nothing to tag.
    assert _roofline(accum_scaled=True, flops=0) == {}


class _FakeWriter:
    run_id = "test-run"

    def __init__(self):
        self.events = []
        self.run_meta = None

    def emit(self, kind, **kw):
        self.events.append((kind, kw))

    def emit_run_meta(self, **kw):
        self.run_meta = kw


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ------------------------------------------------- backend bring-up ----


def test_unavailable_backend_is_exit_1_with_the_error_line(
        monkeypatch, capsys):
    from distributed_tensorflow_framework_tpu.core import mesh

    monkeypatch.delenv("BENCH_WORKLOAD", raising=False)

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(mesh, "device_record", boom)
    writer = _FakeWriter()
    assert bench._run(writer) == 1
    fail = _last_json(capsys)
    assert fail["value"] == 0.0
    assert "Unable to initialize backend 'tpu'" in fail["error"]
    failures = [kw for _, kw in writer.events
                if kw.get("health", {}).get("failure") == "backend_init"]
    assert failures


def test_run_meta_names_the_device(monkeypatch, capsys):
    """Every bench sink opens with where it ran."""
    monkeypatch.setenv("BENCH_COLLECTIVE", "fp4")  # stop right after meta
    writer = _FakeWriter()
    assert bench._run(writer) == 1
    capsys.readouterr()
    dev = jax.devices()[0]
    assert writer.run_meta["platform"] == dev.platform == "cpu"
    assert writer.run_meta["device_kind"] == dev.device_kind
    assert writer.run_meta["device_count"] == len(jax.devices())


# ------------------------------------------------------ batch ladder ----
# A smaller batch is another experiment: the ladder steps down only when
# the device ran out of memory, never past a compile or shape failure.


def test_ladder_steps_down_on_resource_exhaustion(capsys):
    tried = []

    def fn(bs):
        tried.append(bs)
        if bs > 64:
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")
        return {"batch": bs}

    assert bench._run_ladder(fn, (256, 128, 64), "m", "u", "cpu") == \
        {"batch": 64}
    assert tried == [256, 128, 64]


def test_ladder_stops_at_a_compile_failure(capsys):
    tried = []

    def fn(bs):
        tried.append(bs)
        raise jax.errors.JaxRuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel")

    assert bench._run_ladder(fn, (256, 128, 64), "m", "u", "cpu") is None
    assert tried == [256]  # no number from a smaller rung
    fail = _last_json(capsys)
    assert fail["value"] == 0.0 and "batch 256" in fail["error"]
    assert "Mosaic failed" in fail["error"]


# ------------------------------------------- collective wire-format A/B


def _fake_resnet(rate, wire_bytes):
    return {"images_per_sec": rate, "sec_per_step": 0.1,
            "flops_per_step": None, "bytes_per_step": None,
            "collectives": {"total_bytes": wire_bytes,
                            "total_logical_bytes": 800_000},
            "mesh_axes": {"data": 8}}


def test_collective_ab_reports_ratio_and_delta(monkeypatch, capsys):
    calls = []

    def fake_bench(bs, base_overrides=None, **kw):
        wire = (base_overrides or {}).get(
            "parallel", {}).get("collective_dtype", "")
        calls.append(wire)
        assert (base_overrides or {}).get(
            "train", {}).get("spmd_mode") == "shard_map"
        return (_fake_resnet(1040.0, 200_000) if wire == "int8"
                else _fake_resnet(1000.0, 800_000))

    monkeypatch.setattr(bench, "bench_resnet50", fake_bench)
    rc = bench._run_collective_ab(_FakeWriter(), "int8", 8, "TPU v5e")
    out = _last_json(capsys)
    assert rc == 0
    assert calls == ["", "int8"]  # baseline first, then the target wire
    assert out["value"] == 4.0    # wire-byte ratio from the tally
    assert out["throughput_delta"] == 0.04
    assert out["collective_dtype"] == "int8"
    assert out["baseline_wire_bytes"] == 800_000
    assert out["target_wire_bytes"] == 200_000


def test_collective_ab_f32_is_self_calibration(monkeypatch, capsys):
    calls = []

    def fake_bench(bs, base_overrides=None, **kw):
        calls.append(bs)
        return _fake_resnet(1000.0, 800_000)

    monkeypatch.setattr(bench, "bench_resnet50", fake_bench)
    rc = bench._run_collective_ab(_FakeWriter(), "f32", 8, "TPU v5e")
    out = _last_json(capsys)
    assert rc == 0 and len(calls) == 1  # one run: baseline IS the target
    assert out["value"] == 1.0 and out["throughput_delta"] == 0.0


def test_bench_collective_env_validated(monkeypatch, capsys):
    monkeypatch.setenv("BENCH_COLLECTIVE", "fp4")
    rc = bench._run(_FakeWriter())
    out = _last_json(capsys)
    assert rc == 1 and "BENCH_COLLECTIVE" in out["error"]
