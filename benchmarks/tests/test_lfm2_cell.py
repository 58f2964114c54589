"""What PR 26 added for the ``lfm2_moe_s8192`` cell: the generator, the
operation counts, the reference, the scope and counter readers, and the
cell end to end on the CPU at a tiny size."""

import dataclasses
import json
import math
import os
import time

import numpy as np
import pytest

from benchmarks.flops import lfm2 as flops
from benchmarks.harness import manifest, records, scope_times
from benchmarks.tests.tiny import ROOT
from benchmarks.traffic.generators import lm_documents

CELL = "lfm2_moe_s8192"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cell():
    return manifest.Manifest(ROOT).cell(CELL)


def hparams(c=None):
    c = c or cell()
    return {**c.config["published"], **c.config["reference_hparams"]}


# ------------------------------------------------------------ the files --
def test_the_tree_meets_the_contract_with_the_new_cell():
    assert manifest.check(ROOT) == []
    data = manifest.Manifest(ROOT).data
    assert len(data["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    c = cell()
    assert (c.chips, c.workload["per_chip_batch"]) == (1, 4)
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s_chip",
                                                  "setup_s"}
    names = {m["name"] for m in c.per_layer}
    assert {"moe_pct", "moe_dispatch_pct", "moe_gemm_roofline_pct",
            "short_conv_pct", "expert_load_max_mean", "mfu_pct",
            "attn_kernel_pct", "attn_roofline_pct"} <= names
    assert not names & {"collective_ms_step", "collective_exposed_pct"}


def test_the_configuration_file_states_the_cut():
    c = cell().config
    published = c["published"]
    changed = {k for k, v in published.items() if c[k] != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_dense_layers", "layer_types"}
    entry = [e for e in manifest.Manifest(ROOT).data["configs"]
             if e["name"] == "lfm2_8b_a1b"][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "conv_L_cache"):
        assert c[key] == published[key], key
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        5, 8, 16384)
    assert c["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                "conv"]
    h = hparams()
    assert h["experts_held"] == list(range(8)) and h["experts_routed"] == 32


def test_parameters_and_bytes_of_the_cut():
    """507.8M parameters x 16 B = 8.13 GB, the sum ISSUE.md reckons."""
    h = hparams()
    H, M, F = 2048, 7168, 1792
    conv = 3 * H * H + H * H + 3 * H + 2 * H
    attn = 2 * H * H + 2 * H * 512 + 2 * 64 + 2 * H
    dense, moe = 3 * H * M, H * 32 + 32 + 8 * 3 * H * F
    total = 16384 * H + (conv + dense) + (attn + moe) + 3 * (conv + moe) + H
    assert total == pytest.approx(507.8e6, rel=1e-3)
    assert total * 16 == pytest.approx(8.13e9, rel=2e-3)
    assert len(h["layer_types"]) == 5


# -------------------------------------------------------- the generator --
@pytest.fixture(scope="module")
def pool():
    c = cell()
    return lm_documents.generate(c.traffic, seed=2 ** 31 + 12345,
                                 global_batch=4)


def test_pool_shape_and_units(pool):
    assert len(pool.batches) == 8 and pool.unit == "tokens"
    for b, real in zip(pool.batches, pool.real_units):
        assert set(b) == {"input_ids", "targets", "segment_ids", "positions"}
        assert all(v.shape == (4, 8192) and v.dtype == np.int32
                   for v in b.values())
        assert real == int((b["segment_ids"] > 0).sum())
    assert 0.95 < pool.facts["fill"] <= 1.0
    assert pool.facts["documents_per_row"] >= 2


def test_labels_never_cross_a_document(pool):
    for b in pool.batches:
        seg, tgt, ids = b["segment_ids"], b["targets"], b["input_ids"]
        labelled = tgt >= 0
        same_doc_next = np.zeros_like(labelled)
        same_doc_next[:, :-1] = (seg[:, :-1] == seg[:, 1:]) & (seg[:, :-1] > 0)
        # a label exactly where the next token is of the same document
        np.testing.assert_array_equal(labelled, same_doc_next)
        np.testing.assert_array_equal(
            tgt[labelled], np.roll(ids, -1, axis=1)[labelled])


def test_ids_stay_inside_the_slice_and_positions_restart(pool):
    for b in pool.batches:
        assert b["input_ids"].min() >= 0 and b["input_ids"].max() < 16384
        assert b["targets"].max() < 16384
        seg, pos = b["segment_ids"], b["positions"]
        start = np.ones_like(seg, bool)
        start[:, 1:] = seg[:, 1:] != seg[:, :-1]
        assert np.all(pos[start & (seg > 0)] == 0)
        inside = ~start & (seg > 0)
        assert np.all(pos[inside] == np.roll(pos, 1, axis=1)[inside] + 1)
        lengths = flops.document_lengths(b)
        assert lengths.min() >= 64 and lengths.max() <= 8192


def test_the_seed_alone_decides_the_pool():
    c = cell()
    small = dict(c.traffic, pool_batches=2)
    a = lm_documents.generate(small, seed=7, global_batch=4)
    b = lm_documents.generate(small, seed=7, global_batch=4)
    other = lm_documents.generate(small, seed=8, global_batch=4)
    for x, y in zip(a.batches, b.batches):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    assert any(not np.array_equal(x["input_ids"], y["input_ids"])
               for x, y in zip(a.batches, other.batches))


# ------------------------------------------------------------ the counts --
def test_forward_operations_per_token_by_hand():
    h = hparams()
    H, M, F = 2048, 7168, 1792
    conv = 2 * H * 3 * H + 2 * H * H                       # 33_554_432
    attn = 2 * H * H + 2 * 2 * H * 512 + 2 * H * H         # 20_971_520
    dense = 3 * 2 * H * M                                  # 88_080_384
    moe = 2 * H * 32 + (4 * 8 / 32) * 3 * 2 * H * F        # 22_151_168
    head = 2 * H * 16384                                   # 67_108_864
    want = 4 * conv + attn + dense + 4 * moe + head
    assert flops.dense_flops_per_token(h) == want == 398_983_168


def test_train_flops_count_causal_pairs_inside_documents():
    h = hparams()
    seg = np.zeros((1, 8192), np.int32)
    seg[0, :1000], seg[0, 1000:3000] = 1, 2
    batch = {"segment_ids": seg, "input_ids": np.zeros_like(seg)}
    pairs = 1000 * 1001 / 2 + 2000 * 2001 / 2
    assert flops.causal_pairs(flops.document_lengths(batch)) == pairs
    want = 3 * (398_983_168 * 3000 + 4 * 2048 * pairs)
    assert flops.train_flops(batch, h) == want
    work = flops.attention_kernel_work(batch, h, rows_per_chip=1)
    assert work["forward_flops"] == 4 * 2048 * pairs
    assert work["backward_flops"] == 2.5 * work["forward_flops"]
    q_like, kv_like, lse = 8192 * 2048 * 2, 8192 * 512 * 2, 32 * 8192 * 4
    assert work["forward_bytes"] == 2 * q_like + 2 * kv_like + lse
    assert work["backward_bytes"] == 4 * q_like + 4 * kv_like + lse


def test_grouped_product_work_follows_the_counted_assignments():
    h = hparams()
    work = flops.moe_gemm_work(32768, h)
    assert work["forward_flops"] == 32768 * 3 * 2 * 2048 * 1792
    assert work["backward_flops"] == 2 * work["forward_flops"]
    assert flops.moe_gemm_work(16384, h)["forward_flops"] \
        == work["forward_flops"] / 2
    weights = 3 * 8 * 2048 * 1792 * 2
    assert work["forward_bytes"] == 32768 * 2 * (3 * 2048 + 3 * 1792) + weights
    # operations bound it: 3.7 ms against 1.1 ms of bytes a layer
    assert work["forward_flops"] / 197e12 > 3 * work["forward_bytes"] / 819e9


# ---------------------------------------------------- scopes and counters --
LABELS = {
    "custom-call:ragged-dot-none": 12.0,
    "custom-call:fwd/layerN/moe/experts": 4.0,
    "fusion:fwd/layerN/moe/experts": 1.0,
    "fusion:fwd/layerN/moe/dispatch": 2.0,
    "sort:fwd/layerN/moe/dispatch": 1.0,
    "fusion:fwd/layerN/moe/router": 1.0,
    "fusion:fwd/layerN/moe/combine": 1.0,
    "custom-call:bwd/layerN/moe/experts": 8.0,
    "fusion:bwd/layerN/moe/combine": 2.0,
    "fusion:bwd/rematted_computation/layerN/moe": 5.0,
    "fusion:fwd/layerN/short_conv/gate_conv": 1.0,
    "convolution:fwd/layerN/short_conv/in_proj": 3.0,
    "fusion:bwd/layerN/short_conv/gate_conv": 2.0,
    "fusion:bwd/rematted_computation/layerN/short_conv": 4.0,
    "convolution:fwd/layerN/mlp_in": 7.0,
    "optimizer_update": 3.0,
}


# The same run reduced once more with the re-run pass's parts kept.
PART_LABELS = {
    **{k: v for k, v in LABELS.items() if scope_times.REMAT not in k},
    "fusion:again/layerN/moe/dispatch": 3.0,
    "fusion:again/layerN/moe/router": 1.0,
    "fusion:again/layerN/moe/experts": 1.0,
    "fusion:again/layerN/short_conv/gate_conv": 1.5,
    "convolution:again/layerN/short_conv/in_proj": 2.5,
}


def test_scope_seconds_by_module_part_and_kind():
    s = scope_times.seconds
    assert s(LABELS, "moe") == s(PART_LABELS, "moe") == 25.0   # all of it
    # read, not worked out: forward 5, backward 2, the re-run pass 3 + 1
    assert s(PART_LABELS, "moe", ("router", "dispatch", "combine")) == 11.0
    assert s(PART_LABELS, "moe", ("experts",),
             kinds=scope_times.PRODUCT_KINDS) == 12.0    # no fusion
    assert s(PART_LABELS, "short_conv", ("gate_conv",)) == 1.0 + 2.0 + 1.5
    assert s(LABELS, "nothing") == 0.0
    assert scope_times.ragged_dot_seconds(LABELS) == 12.0
    for labels in (LABELS, PART_LABELS):
        assert scope_times.recomputes(labels, "moe")
        assert not scope_times.recomputes(labels, "mlp_in")


HLO = """HloModule step
ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8] parameter(0)
  %fusion.1 = bf16[8,8] fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_train_step_jit)/transpose(jvp(Lfm2ForCausalLM))/rematted_computation/layer3/moe/dispatch/gather"}
  %fusion.2 = bf16[8,8] fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_train_step_jit)/transpose(jvp(Lfm2ForCausalLM))/layer3/moe/dispatch/gather"}
  %fusion.3 = bf16[8,8] fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_train_step_jit)/transpose(jvp(Lfm2ForCausalLM))/checkpoint/rematted_computation/layer12/short_conv/gate_conv/mul"}
  ROOT %fusion.4 = bf16[8,8] fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_train_step_jit)/optimizer_update/add"}
}
"""


def test_part_scopes_keep_the_rerun_passes_parts():
    from benchmarks.harness import hlo_scopes

    plain, parts = hlo_scopes.HloScopes(HLO), scope_times._PartScopes(HLO)
    label = lambda sc, n: sc.label(sc.find(n), n)  # noqa: E731
    assert label(plain, "fusion.1") == \
        "fusion:bwd/rematted_computation/layerN/moe"
    assert label(parts, "fusion.1") == "fusion:again/layerN/moe/dispatch"
    assert label(parts, "fusion.3") == \
        "fusion:again/layerN/short_conv/gate_conv"
    for name in ("fusion.2", "fusion.4", "not-in-the-text.7"):
        assert label(parts, name) == label(plain, name)


def test_part_labels_come_from_the_runs_own_trace(tmp_path):
    """On the committed two-step ``bert_s512`` trace (no remat): the
    second reduction reads what the runner's read; without the files,
    nothing."""
    import gzip
    import shutil

    from benchmarks.harness import hlo_scopes, trace_reduce

    data = os.path.join(ROOT, "benchmarks", "tests", "data")
    assert scope_times._reduce_again(str(tmp_path), 0) is None
    run = tmp_path / "trace" / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    with gzip.open(os.path.join(data, "bert_s512_2steps.hlo.txt.gz"),
                   "rt") as fh:
        text = fh.read()
    (tmp_path / "step.hlo.txt").write_text(text)
    assert scope_times._reduce_again(str(tmp_path), 1) is None   # no trace
    with gzip.open(os.path.join(data, "bert_s512_2steps.xplane.pb.gz")) as src, \
            open(run / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    again = scope_times._reduce_again(str(tmp_path), 2)
    first = trace_reduce.reduce(
        trace_reduce.load(str(run / "t.xplane.pb")),
        hlo_scopes.HloScopes(text)).label_s
    assert again == first and "convolution:bwd/layerN/mlp_in" in again


def fake_run(tmp_path, monkeypatch, events, steps=20):
    """Records of a traced run whose checkout is ``tmp_path``: the
    benchmark's files linked in, a flight-recorder dump of this process."""
    from benchmarks.harness import trace_reduce

    os.symlink(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks")
    out = tmp_path / ".bench_out" / CELL
    out.mkdir(parents=True)
    if events is not None:
        (out / f"flightrec-{os.getpid()}.json").write_text(
            json.dumps({"events": events}))
    monkeypatch.setattr(scope_times, "_reduce_again",
                        lambda out, pid: dict(PART_LABELS))
    red = trace_reduce.TraceReduction(
        devices=1, busy_s=50.0, window_s=50.1, category_s={},
        label_s=dict(LABELS), kernel_s={}, collective_s=0.0,
        collective_exposed_s=0.0, idle_gaps=[], steps=5)
    rec = records.RunRecords(
        cell=cell(), window={"steps": steps, "rate_per_chip": 50_000.0},
        startup={}, step_memory={"step_gib": 12.0}, peaks=PEAKS,
        model_flops_per_unit=1.24e9, attention_work=None, trace=red)
    return str(tmp_path), rec


def events_of(values, start=10):
    return [{"kind": "train_step", "step": start + 10 * i, "metrics": m}
            for i, m in enumerate(values)] + [{"kind": "health", "step": 1}]


def test_readers_read_scopes_and_counters(tmp_path, monkeypatch):
    root, rec = fake_run(tmp_path, monkeypatch, events_of([
        {"loss": 9.7, "moe_load_max_mean": 9.0,
         "moe_local_assignments": 1.0},               # before the window
        {"loss": 9.7, "moe_load_max_mean": 1.2,
         "moe_local_assignments": 32000.0},
        {"loss": 9.7, "moe_load_max_mean": 1.4,
         "moe_local_assignments": 33000.0}]))
    read = lambda name: manifest.load_reader(root, name).read(rec)  # noqa: E731
    assert read("moe_pct") == pytest.approx(100 * (25 + 12) / 50)
    assert read("moe_dispatch_pct") == pytest.approx(100 * 11 / 50)
    assert read("short_conv_pct") == pytest.approx(100 * 4.5 / 50)
    assert read("expert_load_max_mean") == pytest.approx(1.3)
    work = flops.moe_gemm_work(32500.0, hparams(), recomputed_forward=True)
    assert work["forward_flops"] == work["backward_flops"]
    least = 4 * (work["forward_flops"] + work["backward_flops"]) / 197e12
    assert read("moe_gemm_roofline_pct") == pytest.approx(
        100 * least / ((12.0 + 12.0) / 5))
    assert read("mfu_pct") == pytest.approx(100 * 1.24e9 * 5e4 / 197e12)


def test_readers_give_nothing_on_a_program_without_the_scopes(
        tmp_path, monkeypatch):
    """The parent commit: no ``moe``/``short_conv`` scope in the trace, no
    counter in the events, or no dump at all. Nothing raises."""
    root, rec = fake_run(tmp_path, monkeypatch,
                         events_of([{"loss": 10.3}, {"loss": 10.3}]))
    rec.trace.label_s = {"convolution:fwd/layerN/mlp_in": 7.0}
    monkeypatch.setattr(scope_times, "_reduce_again",
                        lambda out, pid: dict(rec.trace.label_s))
    for name in ("moe_pct", "moe_dispatch_pct", "moe_gemm_roofline_pct",
                 "short_conv_pct", "expert_load_max_mean"):
        assert manifest.load_reader(root, name).read(rec) is None, name
    monkeypatch.setattr(scope_times, "_reduce_again", lambda out, pid: None)
    for name in ("moe_dispatch_pct", "moe_gemm_roofline_pct",
                 "short_conv_pct"):       # a traced run whose files are gone
        assert manifest.load_reader(root, name).read(rec) is None, name
    os.remove(os.path.join(root, ".bench_out", CELL,
                           f"flightrec-{os.getpid()}.json"))
    assert manifest.load_reader(root, "expert_load_max_mean").read(rec) is None
    untraced = dataclasses.replace(rec, trace=None)
    for name in ("moe_pct", "moe_dispatch_pct", "moe_gemm_roofline_pct",
                 "short_conv_pct"):
        assert manifest.load_reader(root, name).read(untraced) is None


# ------------------------------------------- the reference and the cell --
TINY = ("model.hidden_size=64", "model.num_heads=4", "model.num_kv_heads=2",
        "model.mlp_dim=128", "model.moe_mlp_dim=32",
        "model.vocab_size=512")


def tiny_cell():
    c = cell()
    traffic = dict(c.traffic, seq_len=256, vocab_size=512, pool_batches=4,
                   doc_length={"dist": "lognormal", "median": 48,
                               "sigma": 0.8, "min": 8, "max": 256})
    config = dict(c.config)
    config["overrides"] = [o for o in config["overrides"]
                           if not o.startswith("model.vocab_size")]
    config["published"] = {
        **config["published"], "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2}
    config["reference_hparams"] = {**config["reference_hparams"],
                                   "head_dim": 16, "vocab_size": 512}
    config["first_loss"] = {"expected": math.log(512), "band": 0.5}
    config["check_tolerance"] = {"loss_rel": 5e-3, "grad_norm_rel": 5e-2}
    workload = dict(c.workload, trace_steps=3)
    return dataclasses.replace(c, traffic=traffic, config=config,
                               workload=workload), TINY


def test_reference_loss_of_random_weights_is_near_a_uniform_guess():
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import lfm2 as ref
    from distributed_tensorflow_framework_tpu.core.config import ModelConfig
    from distributed_tensorflow_framework_tpu.models import get_model

    c, _ = tiny_cell()
    h = {**c.config["published"], **c.config["reference_hparams"]}
    pool = lm_documents.generate(c.traffic, seed=3, global_batch=2)
    batch = {k: jnp.asarray(v) for k, v in pool.batches[0].items()}
    model = get_model(ModelConfig(
        name="lfm2_moe", vocab_size=512, hidden_size=64, num_layers=5,
        layer_types=h["layer_types"], num_dense_layers=1, num_heads=4,
        num_kv_heads=2, mlp_dim=128, moe_mlp_dim=32,
        num_experts=32, expert_topk=4, expert_groups=4, dtype="float32"))
    params = model.init(jax.random.key(0), batch["input_ids"],
                        batch["segment_ids"], batch["positions"])["params"]
    loss, norm = ref.loss_and_grad_norm(params, batch, h)
    assert abs(float(loss) - math.log(512)) < 0.3
    assert 0.0 < float(norm) < 100.0
    worse, _ = ref.loss_and_grad_norm(
        jax.tree.map(lambda p: p * 1.5, params), batch, h)
    assert float(worse) != float(loss)


def test_control_one_precision_below_is_refused_and_the_program_is_not():
    """``tools/check_control.py`` on the small cell (float32 on the CPU,
    so the sound reading is rounding only): the reference computed in
    bfloat16 throughout is refused by the harness's comparison."""
    from benchmarks.tools import check_control

    c, extra = tiny_cell()
    c.config["check_tolerance"] = {"loss_rel": 1e-4, "grad_norm_rel": 1e-3}
    r = check_control.readings(
        c, ROOT, 2 ** 31 + 11, "bfloat16",
        (*extra, "model.dtype=float32", "model.attention_impl=xla"))
    assert r["sound"]["ok"], r["sound"]
    assert not r["control"]["ok"], r["control"]
    assert r["control"]["loss_rel_err"] > 10 * r["sound"]["loss_rel_err"]


def test_cell_runs_end_to_end_on_the_cpu(tmp_path):
    import jax

    from benchmarks.harness import runner

    c, extra = tiny_cell()
    os.symlink(os.path.join(ROOT, "configs"), tmp_path / "configs")
    os.symlink(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks")
    result, detail = runner.run_cell(
        c, seed=2 ** 31 + 11, seconds=2.0, trace=False, root=str(tmp_path),
        process_t0=time.perf_counter(), devices=jax.devices()[:1],
        peaks=PEAKS, extra_overrides=extra)
    assert result["correct"], detail["verdicts"]
    assert result["failed"] == 0 and result["attempted"] >= 5
    assert set(result["metrics"]) == {"tokens_per_s_chip", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    w = detail["window"]
    assert 0.5 < w["units"] / (w["steps"] * 4 * 256) <= 1.0
    assert detail["verdicts"]["no_compile_in_window"]["ok"]
    assert detail["verdicts"]["reference"]["loss_rel_err"] < 5e-3
    # the counters reached the flight recorder's dump, where readers look
    rec = records.RunRecords(
        cell=c, window=w, startup={}, step_memory={}, peaks=PEAKS,
        model_flops_per_unit=1.0, attention_work=None)
    reader = manifest.load_reader(str(tmp_path), "expert_load_max_mean")
    assert 1.0 <= reader.read(rec) < 8.0
    fetched = scope_times.window_counters(reader.__file__, rec)
    assert all(m["moe_dropped"] == 0.0 for m in fetched)
    assert all(0.15 < m["moe_local_share"] < 0.35 for m in fetched)
