"""What the ``kimi_linear_s8192`` cell adds: its entries under the contract
(its own cell and entries, not a count of the benchmark's cells), the
configuration's cut against the catalog's row and its bytes, the
traffic's ids inside the vocabulary's slice, the operation and byte
counts against numbers worked by hand (the chunked delta scan's at one
small shape), the two new readers on labels of the shape a traced run
records, and the cell small on the CPU through the harness's own run and
comparison. By hand, like the other cell tests:
``python -m pytest benchmarks/tests/test_kimi_linear_cell.py -q``."""

import dataclasses
import math
import os
import time

import numpy as np
import pytest

from benchmarks.flops import deepseek_v3
from benchmarks.flops import kimi_linear as flops
from benchmarks.harness import build, manifest, records, scope_times
from benchmarks.tests.tiny import ROOT
from distributed_tensorflow_framework_tpu.ops import delta_rule

CELL = "kimi_linear_s8192"
CONFIG = "kimi_linear_48b_a3b"
NEW_READERS = ("kda_pct", "kda_scan_roofline_pct")
S, H = 8192, 2304


def cell():
    return manifest.Manifest(ROOT).cell(CELL)


def hparams(c=None):
    c = c or cell()
    return {**c.config["published"], **c.config["reference_hparams"]}


# ------------------------------------------------------------ the files --
def test_the_cell_and_its_entries_meet_the_contract():
    assert manifest.check(ROOT) == []
    data = manifest.Manifest(ROOT).data
    entry = next(w for w in data["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "lm_packed_s8192_v20480", 1)
    assert len(entry["why"]) <= 200
    c = cell()
    assert (c.workload["per_chip_batch"], c.workload["check_rows"],
            c.workload["trace_steps"]) == (4, 1, 5)
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s_chip",
                                                  "setup_s"}
    names = {m["name"] for m in c.per_layer}
    # what every decoder cell with experts reports, the shared expert's
    # and the latent layer's shares, and the new entries
    for name in ("mfu_pct", "attn_kernel_pct", "attn_roofline_pct",
                 "attn_fwd_again_pct", "mla_latent_pct", "moe_pct",
                 "moe_dispatch_pct", "moe_gemm_roofline_pct",
                 "moe_shared_pct", "moe_route_again_pct",
                 "expert_load_max_mean", "moe_compact_share", "step_hbm_gib",
                 "device_idle_pct", *NEW_READERS):
        assert name in names, name
    # nothing of a window, a gate, a rotation, a gated short convolution
    # or a Mamba-2 scan runs here
    assert not names & {"attn_window_pct", "attn_gate_pct", "attn_rope_pct",
                        "short_conv_pct", "ssm_pct", "ssm_scan_roofline_pct",
                        "moe_latent_gemm_roofline_pct"}
    for name in NEW_READERS:
        new = next(e for e in data["per_layer"] if e["name"] == name)
        assert new["workloads"] == [CELL]
        assert new["moves"] == "tokens_per_s_chip"
        assert new["layer"] == "linear attention"
        reader = manifest.load_reader(ROOT, name)
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == (
            new["layer"], new["unit"], new["better"], new["source"])


def test_the_configuration_file_states_the_cut():
    c = cell().config
    published = c["published"]
    changed = {k for k, v in published.items() if c[k] != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_attention_heads", "num_key_value_heads", "linear_attn_config"}
    assert set(c["reduced_why"]) == set(c["reduced"])
    entry = [e for e in manifest.Manifest(ROOT).data["configs"]
             if e["name"] == CONFIG][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    assert c["source"] == ("https://huggingface.co/moonshotai/"
                           "Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert (published["model_type"], published["num_hidden_layers"],
            published["num_experts"], published["num_attention_heads"],
            published["vocab_size"]) == ("kimi_linear", 27, 256, 32, 163840)
    # inside the nested group, only the head count and the layer lists
    group, whole = c["linear_attn_config"], published["linear_attn_config"]
    assert {k for k in whole if group[k] != whole[k]} == {
        "num_heads", "kda_layers", "full_attn_layers"}
    assert (group["num_heads"], group["kda_layers"],
            group["full_attn_layers"]) == (8, [1, 2, 3, 5], [4])
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "head_dim", "num_shared_experts",
                "num_experts_per_token", "routed_scaling_factor",
                "rms_norm_eps", "mla_use_nope"):
        assert c[key] == published[key], key
    assert (group["head_dim"], group["short_conv_kernel_size"]) == (128, 4)
    h = hparams()
    assert h["layer_types"] == ["kda", "kda", "kda", "latent_attention",
                                "kda"]
    assert h["num_dense_layers"] == published["first_k_dense_replace"] == 1
    assert h["experts_held"] == list(range(8)) and h["experts_routed"] == 256
    assert h["heads_held"] == h["kda_heads_held"] == list(range(8))
    assert (h["dense_units_held"], h["shared_units_held"]) == (2304, 256)
    assert 20480 * 8 == published["vocab_size"]
    for o in ("model.expert_groups=32", "model.tensor_groups=4",
              "model.vocab_size=20480", "model.num_layers=5"):
        assert o in c["overrides"]
    for key in ("kda_scale", "beta_range", "gate_after_norm", "no_bias",
                "l2_norm", "decay", "inits", "nope_mla", "router",
                "shared_expert", "inits_for_an_even_routing", "optimizer"):
        assert "alternative" in c["assumed"][key], key


def test_parameters_and_bytes_of_the_cut():
    """395.8M parameters x 16 B = 6.33 GB = 5.90 GiB, twice 11.8 GiB."""
    kda = 3 * H * 1024 + 3 * 4 * 1024 + H * 128 + 128 * 1024 + 1024 + 8 \
        + H * 8 + H * 128 + 128 * 1024 + 128 + 1024 * H      # 10.32M
    mla = H * 8 * 192 + H * 576 + 512 + 512 * 8 * 256 + 8 * 128 * H  # 8.27M
    dense = 3 * H * 2304                                     # 15.93M
    experts = H * 256 + 256 + 3 * H * 256 + 8 * 3 * H * 1024  # 58.98M
    head = 2 * 20480 * H                                     # 94.37M
    norms = 2 * H
    total = (kda + dense + norms) + 3 * (kda + experts + norms) \
        + (mla + experts + norms) + head + H
    assert total == 395_811_616
    assert total * 16 == pytest.approx(6.33e9, rel=1e-3)
    assert total * 16 / 2 ** 30 == pytest.approx(5.90, rel=1e-3)
    assert 2 * total * 16 / 2 ** 30 == pytest.approx(11.8, rel=2e-3)


def test_the_traffic_draws_its_ids_from_the_slice():
    c = cell()
    assert c.traffic["vocab_size"] == c.config["vocab_size"] == 20480
    theirs = manifest.Manifest(ROOT).cell("lfm2_moe_s8192").traffic
    assert {k: v for k, v in c.traffic.items()
            if k not in ("why", "vocab_size")} == {
                k: v for k, v in theirs.items()
                if k not in ("why", "vocab_size")}
    pool = build.make_pool(c, ROOT, seed=2_900_000_011)
    assert len(pool.batches) == 8
    starts = []
    for batch in pool.batches:
        ids = np.asarray(batch["input_ids"])
        assert ids.shape == (4, S) and 0 <= ids.min() and ids.max() < 20480
        seg = np.asarray(batch["segment_ids"])
        starts.append(int(np.sum((np.diff(seg, axis=1) != 0)
                                 & (seg[:, 1:] > 0)) + 4))
    # about 4.8 documents a row: the scan resets inside rows and chunks
    assert 3.5 < np.mean(starts) / 4 < 6.5


# ------------------------------------------------------------ the counts --
def test_the_scans_work_by_hand_at_one_small_shape():
    """2 heads of 4 channels, the program's chunks of 64, two kda layers,
    100 positions: per token and head 2*64*4 (pair products) + 63*2*4 (the
    solve) + 6*4*4 (three state products) + 65*4 (P W) = 1372 operations
    forward; bytes
    3*2*4 (q, k, v in bf16) + 4*4 (g) + 4 (β) + 4*4 (o) = 60."""
    h = {"kda_heads_held": [0, 1], "linear_attn_config": {"head_dim": 4},
         "layer_types": ["kda", "latent_attention", "kda"]}
    assert delta_rule.CHUNK == 64
    assert flops.scan_flops_per_token(h) == 2 * 1372
    work = flops.kda_scan_work(100, h)
    assert work["forward_flops"] == 2 * 100 * 2 * 1372
    assert work["backward_flops"] == 2 * work["forward_flops"]
    assert work["forward_bytes"] == 2 * 100 * 2 * 60
    assert work["backward_bytes"] == 2 * work["forward_bytes"]
    again = flops.kda_scan_work(100, h, recomputed_forward=True)
    assert again["forward_flops"] == 2 * work["forward_flops"]
    assert again["backward_bytes"] == work["backward_bytes"]


def test_the_cells_scan_is_bound_by_bytes_on_a_v5e():
    """At the cell's share: 8 heads of 128, chunks of 64: 139,136
    operations a token and head forward against 1796 bytes, under the
    chip's 240 operations a byte."""
    h = hparams()
    assert flops.scan_flops_per_token(h) == 8 * 139_136
    work = flops.kda_scan_work(4 * S, h)
    assert work["forward_bytes"] == 4 * 4 * S * 8 * 1796
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work["forward_flops"] / peaks["bf16_flops_per_s"] < \
        work["forward_bytes"] / peaks["hbm_bytes_per_s"]


def test_forward_operations_per_token_by_hand():
    h = hparams()
    kda = 3 * 2 * H * 1024 + 2 * H * 128 + 2 * 128 * 1024 + 2 * H * 8 \
        + 2 * H * 128 + 2 * 128 * 1024 + 2 * 1024 * H + 8 * 139_136
    mla = 2 * H * 8 * 192 + 2 * H * 576 + 2 * 512 * 8 * 256 \
        + 2 * 8 * 128 * H
    dense = 6 * H * 2304
    moe = 2 * H * 256 + 6 * H * 256 + 8 * 8 / 256 * 6 * H * 1024
    want = 4 * kda + mla + dense + 4 * moe + 2 * H * 20480
    assert flops.dense_flops_per_token(h) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(262.7e6, rel=1e-3)


def test_a_rows_operations_and_the_latent_kernels_work():
    """One document of 8192 tokens: the latent layer's pairs at 8 heads
    of 192 over values of 128, counted as flops/deepseek_v3.py counts
    them, for the one latent layer of the five."""
    h = hparams()
    row = {"input_ids": np.zeros((1, S), np.int32),
           "segment_ids": np.ones((1, S), np.int32)}
    pairs = S * (S + 1) // 2
    assert flops.train_flops(row, h) == pytest.approx(
        3 * (flops.dense_flops_per_token(h) * S + 8 * 640 * pairs),
        rel=1e-12)
    work = flops.attention_kernel_work(row, h, 4)
    assert work == deepseek_v3.attention_kernel_work(
        row, {**h, "layer_types": ["latent_attention"]}, 4)
    assert work["forward_flops"] == 8 * (2 * 192 + 2 * 128) * 4 * pairs


def test_moe_gemm_work_by_hand():
    h = hparams()
    a = 8192.0                          # 32768 x 8 x 8 / 256
    work = flops.moe_gemm_work(a, h)
    assert work["forward_flops"] == a * 6 * H * 1024
    assert len(h["layer_types"]) - int(h["num_dense_layers"]) == 4


# ----------------------------------------------------------- the readers --
LABELS = {
    "dot:fwd/layerN/kda": 0.040,
    "fusion:bwd/layerN/kda": 0.060,
    "fusion:bwd/rematted_computation/layerN/kda": 0.030,
    "dot:fwd/layerN/mla": 0.020,
    "custom-call:ragged-dot-none": 0.100,
}
PART_LABELS = {
    "dot:fwd/layerN/kda/delta_scan": 0.010,
    "fusion:again/layerN/kda/delta_scan": 0.010,
    "fusion:bwd/layerN/kda/delta_scan": 0.030,
    "dot:fwd/layerN/kda/qkv_proj": 0.030,
    "custom-call:ragged-dot-none": 0.100,
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@dataclasses.dataclass
class _Trace:
    busy_s: float = 2.0
    steps: int = 5
    label_s: dict = dataclasses.field(default_factory=lambda: dict(LABELS))


def _records(trace):
    return records.RunRecords(
        cell=cell(), window={"steps": 40}, startup={}, step_memory={},
        peaks=PEAKS, model_flops_per_unit=0.0, attention_work=None,
        trace=trace)


def test_the_readers_sum_their_scopes_in_every_pass(monkeypatch):
    monkeypatch.setattr(scope_times, "part_label_s",
                        lambda reader_file, r: PART_LABELS)
    r = _records(_Trace())
    assert manifest.load_reader(ROOT, "kda_pct").read(r) == pytest.approx(
        100 * 0.130 / 2.0)
    work = flops.kda_scan_work(4 * S, hparams(), recomputed_forward=True)
    least = sum(max(work[f"{p}_flops"] / PEAKS["bf16_flops_per_s"],
                    work[f"{p}_bytes"] / PEAKS["hbm_bytes_per_s"])
                for p in ("forward", "backward"))
    got = manifest.load_reader(ROOT, "kda_scan_roofline_pct").read(r)
    assert got == pytest.approx(100 * least / (0.050 / 5))
    assert 0 < got <= 100


def test_the_readers_give_nothing_without_their_scopes(monkeypatch):
    """The parent's program has no kda layer, and an untraced run no
    trace: nothing, and no error."""
    for name in NEW_READERS:
        assert manifest.load_reader(ROOT, name).read(_records(None)) is None
    monkeypatch.setattr(scope_times, "part_label_s",
                        lambda reader_file, r: {
                            "dot:fwd/layerN/mla/q_proj": 0.05})
    trace = _Trace(label_s={"dot:fwd/layerN/mla/q_proj": 0.05})
    for name in NEW_READERS:
        assert manifest.load_reader(ROOT, name).read(_records(trace)) is None


# ---------------------------------------------- the cell, small, on the CPU --
TINY = ("model.hidden_size=64", "model.kda_heads=8", "model.kda_head_dim=16",
        "model.mla_kv_rank=32", "model.mla_nope_dim=16",
        "model.mla_rope_dim=8", "model.mla_v_dim=16", "model.num_heads=8",
        "model.num_kv_heads=8", "model.mlp_dim=96", "model.moe_mlp_dim=24",
        "model.moe_shared_dim=32", "model.vocab_size=512")


def tiny_cell():
    """The cell's own files with the widths cut through arguments: rows
    of 256 tokens in documents of median 60, 2 of 8 heads of 16 in the
    kda layers and of 24 over values of 16 in the latent layer, 24 dense
    and 8 shared units, the same share and layers."""
    c = cell()
    traffic = dict(c.traffic, seq_len=256, vocab_size=512, pool_batches=4,
                   doc_length={"dist": "lognormal", "median": 60,
                               "sigma": 1.0, "min": 8, "max": 256})
    config = dict(c.config)
    config["overrides"] = [o for o in config["overrides"]
                           if not o.startswith("model.vocab_size")]
    config["published"] = {
        **config["published"], "hidden_size": 64, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "moe_intermediate_size": 24,
        "linear_attn_config": {**config["published"]["linear_attn_config"],
                               "head_dim": 16}}
    config["reference_hparams"] = {
        **config["reference_hparams"], "vocab_size": 512,
        "dense_units_held": 24, "shared_units_held": 8,
        "heads_held": [0, 1], "kda_heads_held": [0, 1]}
    config["first_loss"] = {"expected": math.log(512), "band": 0.5}
    config["check_tolerance"] = {"loss_rel": 5e-3, "grad_norm_rel": 5e-2}
    workload = dict(c.workload, trace_steps=3)
    return dataclasses.replace(c, traffic=traffic, config=config,
                               workload=workload), TINY


def test_control_one_precision_below_is_refused_and_the_program_is_not():
    from benchmarks.tools import check_control

    c, extra = tiny_cell()
    c.config["check_tolerance"] = {"loss_rel": 1e-4, "grad_norm_rel": 1e-3}
    r = check_control.readings(
        c, ROOT, 2 ** 31 + 11, "bfloat16",
        (*extra, "model.dtype=float32", "model.attention_impl=xla"))
    assert r["sound"]["ok"], r["sound"]
    assert not r["control"]["ok"], r["control"]


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
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"tokens_per_s_chip", "setup_s"}
    assert detail["verdicts"]["reference"]["loss_rel_err"] < 5e-3
    rec = records.RunRecords(
        cell=c, window=detail["window"], startup={}, step_memory={},
        peaks=PEAKS, model_flops_per_unit=1.0, attention_work=None)
    fetched = scope_times.window_counters(
        manifest.load_reader(str(tmp_path), NEW_READERS[0]).__file__, rec)
    assert fetched and all(m["moe_dropped"] == 0.0 for m in fetched)
    assert all(m["kda_resets"] > 4 for m in fetched)
