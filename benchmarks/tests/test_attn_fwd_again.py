"""``attn_fwd_again_pct`` (PR 31): the second reduction that tells the
re-run forward kernel apart, on a made-up step and trace of a decoder
under ``model.remat`` with and without the re-run; the reader on those,
on a program without attention kernels and on a run without a trace; and
the entry in the manifest."""

import dataclasses
import os

import pytest

from benchmarks.harness import (attn_passes, hlo_scopes, manifest, records,
                                trace_reduce)
from benchmarks.tests.tiny import ROOT

NAME = "attn_fwd_again_pct"
CELLS = ["lfm2_moe_s8192", "smallthinker_s16384"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

STEP = "jit(_train_step_jit)"
FWD = f"{STEP}/jvp(Lfm2ForCausalLM)"
BWD = f"{STEP}/transpose(jvp(Lfm2ForCausalLM))/jvp(Lfm2ForCausalLM)/checkpoint"
KERNEL = ('custom-call(%p), custom_call_target="tpu_custom_call", '
          'metadata={{op_name="{}"}}')
OUT, DQ, DKV = ("(bf16[2,28,16384,128], f32[2,28,16384,1])",
                "bf16[2,28,16384,128]",
                "(f32[2,28,16384,128], f32[2,28,16384,128], "
                "f32[2,28,1,16384])")


def step_hlo(rerun: bool) -> str:
    """A global and a window layer's kernels, the forward pass re-run
    under ``rematted_computation`` or not, and a fusion of the re-run
    pass that is no kernel."""
    calls = [
        ("_flash_fwd.1", OUT, f"{FWD}/layer0/attn/jit(_flash_fwd)/pallas_call"),
        ("_flash_fwd.2", OUT,
         f"{FWD}/layer1/attn_window/jit(_flash_fwd)/pallas_call"),
        ("_flash_bwd.5", DQ,
         f"{BWD}/layer1/attn_window/jit(_flash_bwd)/pallas_call"),
        ("_flash_bwd.6", DKV, f"{BWD}/layer0/attn/jit(_flash_bwd)/pallas_call"),
    ]
    if rerun:
        calls += [
            ("_flash_fwd.3", OUT, f"{BWD}/rematted_computation/layer1/"
             "attn_window/jit(_flash_fwd)/pallas_call"),
            ("_flash_fwd.4", OUT, f"{BWD}/rematted_computation/layer0/attn/"
             "jit(_flash_fwd)/pallas_call")]
    lines = [f"  %{name} = {shape} {KERNEL.format(op)}"
             for name, shape, op in calls]
    lines.append(
        f"  ROOT %fusion.7 = {DQ} fusion(%p), kind=kLoop, calls=%f, "
        f'metadata={{op_name="{BWD}/rematted_computation/layer0/attn/'
        'qk_norm_rope/mul"}')
    return ("HloModule step\nENTRY %main (p: bf16[2,28,16384,128]) -> "
            f"{DQ} {{\n  %p = {DQ} parameter(0)\n" + "\n".join(lines) + "\n}\n")


class _Fake:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def profile(names, each_ns=100):
    """One device that runs ``names`` back to back, ``each_ns`` apiece."""
    events = [_Fake(name=f"%{n} = f32[] custom-call()", start_ns=i * each_ns,
                    duration_ns=each_ns, stats=[])
              for i, n in enumerate(names)]
    return _Fake(planes=[_Fake(name="/device:TPU:0", lines=[
        _Fake(name="XLA Ops", events=events),
        _Fake(name="XLA Modules", events=[
            _Fake(name="jit_step", start_ns=0,
                  duration_ns=each_ns * len(names), stats=[])])])])


PARENT_OPS = ["_flash_fwd.1", "_flash_fwd.2", "fusion.7", "_flash_fwd.3",
              "_flash_bwd.5", "_flash_fwd.4", "_flash_bwd.6", "fusion.7"]
CHANGE_OPS = ["_flash_fwd.1", "_flash_fwd.2", "fusion.7", "_flash_bwd.5",
              "_flash_bwd.6", "fusion.7"]


def test_the_second_reduction_tells_the_rerun_forward_apart():
    plain = hlo_scopes.HloScopes(step_hlo(True))
    passes = attn_passes._PassScopes(step_hlo(True))
    label = lambda sc, n: sc.label(sc.find(n), n)  # noqa: E731
    for name in ("_flash_fwd.3", "_flash_fwd.4"):
        assert label(plain, name) == "attn_kernel:_flash_fwd"
        assert label(passes, name) == "attn_kernel:again/_flash_fwd"
    # the first forward pass, the backward kernels, a fusion of the re-run
    # pass that is no kernel and an event the HLO lacks keep their labels
    for name in ("_flash_fwd.1", "_flash_fwd.2", "_flash_bwd.5",
                 "_flash_bwd.6", "fusion.7", "not-there.8"):
        assert label(passes, name) == label(plain, name)
    red = trace_reduce.reduce(profile(PARENT_OPS), passes)
    assert red.label_s == pytest.approx({
        "attn_kernel:_flash_fwd": 200e-9,
        "attn_kernel:again/_flash_fwd": 200e-9,
        "attn_kernel:_flash_bwd:dq": 100e-9,
        "attn_kernel:_flash_bwd:dkv": 100e-9,
        "fusion:bwd/rematted_computation/layerN/attn": 200e-9})
    # the category, and so ``attn_kernel_pct``, is what it was
    assert red.category_s["attn_kernel"] == pytest.approx(600e-9)
    assert attn_passes.forward_again_s(red.label_s) == pytest.approx(200e-9)
    assert attn_passes.forward_again_s(
        {"attn_kernel:_flash_fwd": 1.0, "attn_kernel:_flash_bwd:dq": 1.0}) == 0


def run_of(tmp_path, monkeypatch, cell_name, rerun, ops):
    """Records of a traced run whose checkout is ``tmp_path``: the
    benchmark's files linked in, the step's HLO where the runner leaves
    it, and the made-up device trace in the place of the profiler's file."""
    os.symlink(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks")
    out = tmp_path / ".bench_out" / cell_name
    (out / "trace").mkdir(parents=True)
    (out / "step.hlo.txt").write_text(step_hlo(rerun))
    made_up = profile(ops)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda trace_dir: "t.pb")
    monkeypatch.setattr(trace_reduce, "load", lambda path: made_up)
    attn_passes._reduce.cache_clear()
    red = trace_reduce.reduce(made_up, hlo_scopes.HloScopes(step_hlo(rerun)))
    rec = records.RunRecords(
        cell=manifest.Manifest(ROOT).cell(cell_name),
        window={"steps": 20, "rate_per_chip": 30_000.0}, startup={},
        step_memory={"step_gib": 9.9}, peaks=PEAKS,
        model_flops_per_unit=1.72e9, attention_work=None, trace=red)
    return str(tmp_path), rec


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("rerun,ops,want", [
    (True, PARENT_OPS, 100 * 2 / 8),     # two of eight equal operations
    (False, CHANGE_OPS, 0.0),            # the kernels run, none again
], ids=["rerun", "kept"])
def test_the_reader_gives_the_share_of_busy_time(
        tmp_path, monkeypatch, cell_name, rerun, ops, want):
    root, rec = run_of(tmp_path, monkeypatch, cell_name, rerun, ops)
    reader = manifest.load_reader(root, NAME)
    assert reader.read(rec) == pytest.approx(want)
    assert attn_passes.attention_kernel_s(reader.__file__, rec).keys() == {
        "attn_kernel:_flash_fwd", "attn_kernel:_flash_bwd:dq",
        "attn_kernel:_flash_bwd:dkv",
        *(["attn_kernel:again/_flash_fwd"] if rerun else [])}
    # a part of what ``attn_kernel_pct`` reads from the run's own labels
    whole = 100 * rec.trace.category_s["attn_kernel"] / rec.trace.busy_s
    assert reader.read(rec) <= whole < 100


def test_the_reader_gives_nothing_where_there_is_nothing_to_read(
        tmp_path, monkeypatch):
    """No trace (an untraced run), no files beside it, a program that
    runs no attention kernel: nothing raises, the line leaves it out."""
    root, rec = run_of(tmp_path, monkeypatch, CELLS[1], True, PARENT_OPS)
    reader = manifest.load_reader(root, NAME)
    assert reader.read(dataclasses.replace(rec, trace=None)) is None
    no_kernels = dataclasses.replace(rec, trace=trace_reduce.reduce(
        profile(["fusion.7"]), hlo_scopes.HloScopes(step_hlo(True))))
    monkeypatch.setattr(trace_reduce, "load",
                        lambda path: profile(["fusion.7", "fusion.7"]))
    attn_passes._reduce.cache_clear()
    assert reader.read(no_kernels) is None
    os.remove(os.path.join(root, ".bench_out", CELLS[1], "step.hlo.txt"))
    attn_passes._reduce.cache_clear()
    assert reader.read(rec) is None
    assert attn_passes._reduce("/nowhere", 0) is None


def test_the_entry_names_the_kernels_layer_and_the_two_decoder_cells():
    assert manifest.check(ROOT) == []
    data = manifest.Manifest(ROOT).data
    entry = next(e for e in data["per_layer"] if e["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "attention kernels",
        "moves": "tokens_per_s_chip", "workloads": CELLS}
    reader = manifest.load_reader(ROOT, NAME)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == (
        entry["layer"], entry["unit"], entry["better"], entry["source"])
    for name in CELLS:
        assert NAME in {m["name"] for m in manifest.Manifest(ROOT).cell(
            name).per_layer}
