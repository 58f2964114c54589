"""What the ``kanana2_s16384`` cell adds: its entries under the contract
(its own cell and entries, not a count of the benchmark's cells), the
configuration's cut against the catalog's row and its bytes, the
traffic's ids inside the vocabulary's slice, the operation and byte
counts against numbers worked by hand for one row, the new reader on
labels of the shape a traced run records, and the cell small on the CPU
through the harness's own run and comparison. By hand, like the other
cell tests: ``python -m pytest benchmarks/tests/test_kanana_cell.py -q``."""

import dataclasses
import math
import os
import time

import numpy as np
import pytest

from benchmarks.flops import deepseek_v3 as flops
from benchmarks.harness import build, manifest, records, scope_times
from benchmarks.tests.tiny import ROOT

CELL = "kanana2_s16384"
CONFIG = "kanana_2_30b_a3b"
NEW_READER = "mla_latent_pct"
S, H = 16384, 2048
N, D_QK, D_V, RANK, ROPE = 16, 192, 128, 512, 64


def cell():
    return manifest.Manifest(ROOT).cell(CELL)


def hparams(c=None):
    c = c or cell()
    return {**c.config["published"], **c.config["reference_hparams"]}


def one_row() -> dict:
    """One whole chunk: a single document of 16384 tokens."""
    return {"input_ids": np.zeros((1, S), np.int32),
            "segment_ids": np.ones((1, S), np.int32)}


# ------------------------------------------------------------ the files --
def test_the_cell_and_its_entries_meet_the_contract():
    assert manifest.check(ROOT) == []
    data = manifest.Manifest(ROOT).data
    entry = next(w for w in data["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "lm_chunks_s16384_v16032", 1)
    assert len(entry["why"]) <= 200
    c = cell()
    assert (c.workload["per_chip_batch"], c.workload["check_rows"],
            c.workload["trace_steps"]) == (2, 1, 5)
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s_chip",
                                                  "setup_s"}
    names = {m["name"] for m in c.per_layer}
    # what every decoder cell with experts reports, the shared experts'
    # and the rotation's shares, and the new entry
    for name in ("mfu_pct", "attn_kernel_pct", "attn_roofline_pct",
                 "attn_fwd_again_pct", "attn_rope_pct", "moe_pct",
                 "moe_dispatch_pct", "moe_gemm_roofline_pct",
                 "moe_shared_pct", "moe_route_again_pct",
                 "expert_load_max_mean", "moe_compact_share", "step_hbm_gib",
                 "device_idle_pct", NEW_READER):
        assert name in names, name
    # nothing of a window, a gate, a convolution or a scan runs here
    assert not names & {"attn_window_pct", "attn_gate_pct", "short_conv_pct",
                        "ssm_pct", "moe_latent_gemm_roofline_pct"}
    new = next(e for e in data["per_layer"] if e["name"] == NEW_READER)
    assert new["workloads"] == [CELL] and new["moves"] == "tokens_per_s_chip"
    reader = manifest.load_reader(ROOT, NEW_READER)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == (
        new["layer"], new["unit"], new["better"], new["source"])


def test_the_configuration_file_states_the_cut():
    c = cell().config
    published = c["published"]
    changed = {k for k, v in published.items() if c[k] != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_attention_heads", "num_key_value_heads"}
    assert set(c["reduced_why"]) == set(c["reduced"])
    entry = [e for e in manifest.Manifest(ROOT).data["configs"]
             if e["name"] == CONFIG][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    assert c["source"] == ("https://huggingface.co/kakaocorp/"
                           "kanana-2-30b-a3b-instruct-2601/blob/main/config.json")
    assert (published["model_type"], published["num_hidden_layers"],
            published["n_routed_experts"], published["num_attention_heads"],
            published["vocab_size"]) == ("deepseek_v3", 48, 128, 32, 128256)
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "qk_head_dim", "v_head_dim", "head_dim", "n_shared_experts",
                "num_experts_per_tok", "routed_scaling_factor", "rope_theta",
                "rope_interleave", "rms_norm_eps"):
        assert c[key] == published[key], key
    h = hparams()
    assert h["layer_types"] == ["latent_attention"] * 5
    assert h["num_dense_layers"] == published["first_k_dense_replace"] == 1
    assert h["experts_held"] == list(range(8)) and h["experts_routed"] == 128
    assert h["heads_held"] == list(range(16))
    assert (h["dense_units_held"], h["shared_units_held"]) == (3072, 768)
    assert 16032 * 8 == published["vocab_size"]
    for o in ("model.expert_groups=16", "model.tensor_groups=2",
              "model.vocab_size=16032", "model.num_layers=5"):
        assert o in c["overrides"]
    for key in ("interleaved_pairs", "softmax_scale", "latent_norm",
                "one_rotary_key", "router", "shared_experts"):
        assert "alternative" in c["assumed"][key], key


def test_parameters_and_bytes_of_the_cut():
    """324.3M parameters x 16 B = 5.19 GB = 4.83 GiB; with all 32 heads
    and every dense and shared unit 425.0M = 6.80 GB."""
    attention = H * N * D_QK + H * (RANK + ROPE) + RANK + RANK * N * 256 \
        + N * D_V * H                                       # 13.76M
    dense = 3 * H * 3072                                    # 18.87M
    experts = H * 128 + 128 + 3 * H * 768 + 8 * 3 * H * 768  # 42.73M
    head = 2 * 16032 * H                                    # 65.67M
    norms = 2 * H
    total = (attention + dense + norms) + 4 * (attention + experts + norms) \
        + head + H
    assert attention == 13_763_072
    assert total == pytest.approx(324.3e6, rel=2e-4)
    assert total * 16 == pytest.approx(5.19e9, rel=1e-3)
    assert total * 16 / 2 ** 30 == pytest.approx(4.83, rel=1e-3)
    # the other 16 heads' columns and rows, the other 3072 dense and 768
    # shared units
    others = 5 * (H * 16 * D_QK + RANK * 16 * 256 + 16 * D_V * H) \
        + 3 * H * 3072 + 4 * 3 * H * 768
    assert total + others == pytest.approx(425.0e6, rel=1e-3)
    assert (total + others) * 16 == pytest.approx(6.80e9, rel=1e-3)


def test_the_traffic_draws_its_ids_from_the_slice():
    c = cell()
    assert c.traffic["vocab_size"] == c.config["vocab_size"] == 16032
    theirs = manifest.Manifest(ROOT).cell("laguna_s_s16384").traffic
    assert {k: v for k, v in c.traffic.items()
            if k not in ("why", "vocab_size")} == {
                k: v for k, v in theirs.items()
                if k not in ("why", "vocab_size")}
    pool = build.make_pool(c, ROOT, seed=2_900_000_011)
    assert len(pool.batches) == 8
    for batch in pool.batches:
        ids = np.asarray(batch["input_ids"])
        assert ids.shape == (2, S) and 0 <= ids.min() and ids.max() < 16032
        assert (np.asarray(batch["segment_ids"]) == 1).all()


# ------------------------------------------------------------ the counts --
def test_forward_operations_per_token_by_hand():
    h = hparams()
    attention = 2 * H * N * D_QK + 2 * H * (RANK + ROPE) \
        + 2 * RANK * N * 256 + 2 * N * D_V * H
    assert attention == 27_525_120                       # 138 MFLOP in 5
    dense = 6 * H * 3072
    moe = 2 * H * 128 + 6 * H * 768 + 6 * 8 / 128 * 6 * H * 768
    want = 5 * attention + dense + 4 * moe + 2 * H * 16032
    assert flops.dense_flops_per_token(h) == want
    assert want == pytest.approx(295.04e6, rel=1e-4)
    # pairs: 16 heads x (2 x 192 + 2 x 128) a pair and layer
    assert flops.pair_flops(h) == 16 * 640


def test_a_rows_operations_by_hand():
    """One document of 16384 tokens: 134,225,920 causal pairs; with the
    per-token work, 714 MFLOP a token forward and 2.14 GFLOP a token a
    step, 70 TFLOP a step of two rows."""
    h = hparams()
    causal = S * (S + 1) // 2
    assert causal == 134_225_920
    pairs = 5 * 16 * 640 * causal
    want = 3 * (flops.dense_flops_per_token(h) * S + pairs)
    assert flops.train_flops(one_row(), h) == want
    assert want / S / 3 == pytest.approx(714e6, rel=2e-3)
    assert 2 * want == pytest.approx(70.2e12, rel=3e-3)
    assert pairs / (want / 3) == pytest.approx(0.586, abs=0.002)


def test_attention_kernel_work_by_hand():
    """Operations at 192 for QK^T, dS K and dS^T Q, at 128 for P V, dO V^T
    and P^T dO; bytes of q, k, dq, dk at 192 and of v, o, do, dv at 128,
    the logsumexp at 4 B a row and head; two rows a chip."""
    h = hparams()
    work = flops.attention_kernel_work(one_row(), h, 2)
    pairs = 2 * S * (S + 1) // 2
    assert work["forward_flops"] == 5 * 16 * (2 * 192 + 2 * 128) * pairs
    assert work["backward_flops"] == 5 * 16 * (6 * 192 + 4 * 128) * pairs
    qk, v, lse = 2 * S * 16 * 192 * 2, 2 * S * 16 * 128 * 2, 2 * S * 16 * 4
    assert work["forward_bytes"] == 5 * (2 * qk + 2 * v + lse)
    assert work["backward_bytes"] == 5 * (4 * qk + 4 * v + lse)
    # bound by operations on a v5e, not bytes
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work["forward_flops"] / peaks["bf16_flops_per_s"] > \
        10 * work["forward_bytes"] / peaks["hbm_bytes_per_s"]


def test_moe_gemm_work_by_hand():
    h = hparams()
    a = 12288.0                         # 32768 x 6 x 8 / 128
    work = flops.moe_gemm_work(a, h)
    assert work["forward_flops"] == a * 6 * H * 768
    weights = 3 * 8 * H * 768 * 2
    assert work["forward_bytes"] == a * 2 * (3 * H + 3 * 768) + weights
    assert len(h["layer_types"]) - int(h["num_dense_layers"]) == 4


# ----------------------------------------------------------- the reader --
LABELS = {
    "fusion:fwd/layerN/mla/mla_latent": 0.010,
    "dot:fwd/layerN/mla/mla_latent": 0.030,
    "dot:again/layerN/mla/mla_latent": 0.030,
    "dot:bwd/layerN/mla/mla_latent": 0.050,
    "fusion:fwd/layerN/mla/qk_norm_rope": 0.008,
    "dot:fwd/layerN/mla/q_proj": 0.060,
    "custom-call:ragged-dot-none": 0.100,
}


@dataclasses.dataclass
class _Trace:
    busy_s: float = 2.0
    steps: int = 5
    label_s: dict = dataclasses.field(default_factory=lambda: dict(LABELS))


def _records(trace):
    return records.RunRecords(
        cell=cell(), window={"steps": 40}, startup={}, step_memory={},
        peaks={}, model_flops_per_unit=0.0, attention_work=None, trace=trace)


def test_the_reader_sums_its_scope_in_every_pass(monkeypatch):
    monkeypatch.setattr(scope_times, "part_label_s",
                        lambda reader_file, r: r.trace.label_s)
    r = _records(_Trace())
    got = manifest.load_reader(ROOT, NEW_READER).read(r)
    assert got == pytest.approx(100 * 0.120 / 2.0)
    rope = manifest.load_reader(ROOT, "attn_rope_pct").read(r)
    assert rope == pytest.approx(100 * 0.008 / 2.0)


def test_the_reader_gives_nothing_without_its_scope(monkeypatch):
    """The parent's program has no latent layer, and an untraced run no
    trace: nothing, and no error."""
    assert manifest.load_reader(ROOT, NEW_READER).read(_records(None)) is None
    monkeypatch.setattr(scope_times, "part_label_s",
                        lambda reader_file, r: {
                            "dot:fwd/layerN/attn/query": 0.05})
    assert manifest.load_reader(ROOT, NEW_READER).read(
        _records(_Trace())) is None


# ---------------------------------------------- the cell, small, on the CPU --
TINY = ("model.hidden_size=64", "model.mla_kv_rank=32",
        "model.mla_nope_dim=16", "model.mla_rope_dim=8", "model.mla_v_dim=16",
        "model.num_heads=4", "model.num_kv_heads=4", "model.mlp_dim=96",
        "model.moe_mlp_dim=24", "model.moe_shared_dim=32",
        "model.vocab_size=512")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell():
    """The cell's own files with the widths cut through arguments: rows
    of 256 tokens, 2 of 4 heads of 24 over values of 16, 48 dense and 16
    shared units, the same share and layers."""
    c = cell()
    traffic = dict(c.traffic, seq_len=256, vocab_size=512, pool_batches=4,
                   doc_length={"dist": "fixed", "value": 256})
    config = dict(c.config)
    config["overrides"] = [o for o in config["overrides"]
                           if not o.startswith("model.vocab_size")]
    config["published"] = {
        **config["published"], "hidden_size": 64, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "moe_intermediate_size": 24}
    config["reference_hparams"] = {
        **config["reference_hparams"], "vocab_size": 512,
        "dense_units_held": 48, "shared_units_held": 16, "heads_held": [0, 1]}
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
        manifest.load_reader(str(tmp_path), NEW_READER).__file__, rec)
    assert fetched and all(m["moe_dropped"] == 0.0 for m in fetched)
