"""The trace reduction and the HLO scope map, on a trace recorded on a
TPU v5e by PR 22 and committed: two steps of ``bert_s512`` (per-chip
batch 32), with the compiled step's HLO text (``backend_config`` bodies
cut). ``data/README.md`` says how it was made."""

import gzip
import os
import shutil

import pytest

from benchmarks.harness import hlo_scopes, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def scopes():
    with gzip.open(os.path.join(DATA, "bert_s512_2steps.hlo.txt.gz"), "rt") as fh:
        return hlo_scopes.HloScopes(fh.read())


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, "bert_s512_2steps.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace_reduce.load(str(path))


def test_interval_algebra():
    cover = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert cover == [(0, 3), (5, 8)] and trace_reduce.measure(cover) == 6
    assert trace_reduce.subtract([(0, 10)], cover) == [(3, 5), (8, 10)]
    assert trace_reduce.subtract(cover, [(2, 6)]) == [(0, 2), (6, 8)]
    # nested events: the inner one gets its time, the sum is the union
    events = [(0, 10), (2, 4), (3, 4), (12, 13)]
    own = trace_reduce.self_times(events)
    assert own == [8, 1, 1, 1]
    assert sum(own) == trace_reduce.measure(trace_reduce.union(events)) == 11


def test_scope_map_names_what_the_trace_runs(scopes):
    kinds = {}
    for instr in scopes.instrs.values():
        if instr.target == "tpu_custom_call":
            kind = scopes.kernel_kind(instr)
            kinds[kind] = kinds.get(kind, 0) + 1
            assert scopes.category(instr) == "attn_kernel"
    # 12 layers: one forward kernel, and a dq and a dk/dv kernel backward
    assert kinds == {"_flash_fwd": 12, "_flash_bwd:dq": 12, "_flash_bwd:dkv": 12}
    by_label = {}
    for instr in scopes.instrs.values():
        if instr.opcode in ("fusion", "convolution"):
            by_label.setdefault(scopes.label(instr), []).append(instr)
    assert "optimizer_update" in by_label
    assert "convolution:bwd/layerN/mlp_in" in by_label
    assert all(scopes.category(i) == "gemm_conv"
               for i in by_label["convolution:fwd/layerN/attn/qkv"])
    assert hlo_scopes.scope_of(
        "jit(_train_step_jit)/transpose(jvp(BertForMLM))/layer3/mlp_in/dot_general"
    ) == "bwd/layerN/mlp_in"
    # a trace event's name is the instruction's whole text
    some = next(i for i in scopes.instrs.values() if i.opcode == "fusion")
    assert scopes.find(f"%{some.name} = f32[8]{{0}} fusion(...)") is some
    assert scopes.find("no_such_instruction.1") is None
    assert scopes.category(None, "%all-reduce.7 = f32[] all-reduce(...)") \
        == "collective"


def test_reduction_of_the_recorded_trace(profile, scopes):
    red = trace_reduce.reduce(profile, scopes)
    assert red.devices == 1 and red.steps == 2
    # two steps of about 161 ms each, the device busy nearly all of it
    assert 0.30 < red.window_s < 0.34
    assert 0.0 <= red.idle_share < 0.02
    assert red.busy_s <= red.window_s
    # self times by category add up to the busy time
    assert sum(red.category_s.values()) == pytest.approx(red.busy_s, rel=1e-6)
    assert sum(red.label_s.values()) == pytest.approx(red.busy_s, rel=1e-6)
    share = {k: v / red.busy_s for k, v in red.category_s.items()}
    assert 0.25 < share["attn_kernel"] < 0.35
    assert 0.38 < share["gemm_conv"] < 0.50
    assert 0.01 < share["optimizer_update"] < 0.05
    assert red.collective_s == 0.0 and red.collective_exposed_s == 0.0
    # 12 layers x 2 steps of each kernel
    assert {k: n for k, (n, _) in red.kernel_s.items()} == {
        "_flash_fwd": 24, "_flash_bwd:dq": 24, "_flash_bwd:dkv": 24}
    assert sum(s for _, s in red.kernel_s.values()) == pytest.approx(
        red.category_s["attn_kernel"], rel=1e-6)
    b = trace_reduce.breakdown(red)
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][0].startswith(
        "attn_kernel:")
    assert all(isinstance(s, float) and s > 0 for _, s in b["device_ops"])
    assert b["idle_gaps"] and all(s > 0 for _, s in b["idle_gaps"])


def test_without_the_scope_map_everything_is_other(profile):
    red = trace_reduce.reduce(profile, None)
    assert set(red.category_s) == {"other"}


class _Fake:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_no_device_plane_is_an_error_not_a_fallback():
    host_only = _Fake(planes=[_Fake(name="/host:CPU", lines=[])])
    with pytest.raises(trace_reduce.TraceError, match="no device plane"):
        trace_reduce.reduce(host_only)
    empty = _Fake(planes=[_Fake(name="/device:TPU:0", lines=[
        _Fake(name="XLA Ops", events=[])])])
    with pytest.raises(trace_reduce.TraceError, match="no operation ran"):
        trace_reduce.reduce(empty)


def test_exposed_collective_time_on_a_made_up_device():
    """An asynchronous all-reduce from 10 to 30 whose first half overlaps
    a fusion: 10 ns of it are exposed."""
    ev = lambda name, s, d: _Fake(name=name, start_ns=s, duration_ns=d,  # noqa: E731
                                  stats=[])
    plane = _Fake(name="/device:TPU:0", lines=[
        _Fake(name="XLA Ops", events=[
            ev("%fusion.1 = f32[] fusion()", 0, 20),
            ev("%all-reduce-done.1 = f32[] all-reduce-done()", 28, 2),
            ev("%fusion.2 = f32[] fusion()", 30, 10)]),
        _Fake(name="Async XLA Ops", events=[
            ev("%all-reduce-start.1 = f32[] all-reduce-start()", 10, 20)]),
        _Fake(name="XLA Modules", events=[ev("jit_step", 0, 40)])])
    red = trace_reduce.reduce(_Fake(planes=[plane]),
                              hlo_scopes.HloScopes(""))
    assert red.collective_s == pytest.approx(20e-9)
    assert red.collective_exposed_s == pytest.approx(10e-9)
    assert red.busy_s == pytest.approx(32e-9) and red.window_s == pytest.approx(40e-9)
    assert red.steps == 1
