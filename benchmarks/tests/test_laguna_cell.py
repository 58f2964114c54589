"""What PR 36 added for the ``laguna_s_s16384`` cell: the tree under the
contract with eight cells, the configuration's cut against the catalog's
row and its bytes, the traffic's ids inside the vocabulary's slice, the
operation counts against numbers worked by hand for one row, and the two
new readers on labels of the shape a traced run records. By hand, like
the other cell tests: ``python -m pytest
benchmarks/tests/test_laguna_cell.py -q``."""

import dataclasses
import json
import math
import os
import time

import numpy as np
import pytest

from benchmarks.flops import laguna as flops
from benchmarks.harness import build, manifest, records, scope_times
from benchmarks.tests.tiny import ROOT

CELL = "laguna_s_s16384"
SIBLING = "smallthinker_s16384"
NEW_READERS = ("attn_gate_pct", "attn_rope_pct")
S, H, D, W = 16384, 3072, 128, 512


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
def test_the_tree_meets_the_contract_with_eight_cells():
    assert manifest.check(ROOT) == []
    data = manifest.Manifest(ROOT).data
    assert len(data["workloads"]) == 8 and len(data["configs"]) == 6
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    assert data["workloads"][-1]["name"] == CELL
    assert data["configs"][-1]["name"] == "laguna_s_2_1"
    c = cell()
    assert (c.chips, c.workload["per_chip_batch"], c.workload["check_rows"],
            c.workload["trace_steps"], c.entry["traffic"]) == (
                1, 2, 1, 5, "lm_chunks_s16384_v12544")
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s_chip",
                                                  "setup_s"}
    names = {m["name"] for m in c.per_layer}
    theirs = {m["name"] for m in manifest.Manifest(ROOT).cell(
        SIBLING).per_layer}
    # everything the other window-and-global cell reports, the shared
    # expert's share, and the two new entries, here alone
    assert names == theirs | {"moe_shared_pct"} | set(NEW_READERS)
    for name in NEW_READERS:
        entry = next(e for e in data["per_layer"] if e["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s_chip"
        reader = manifest.load_reader(ROOT, name)
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["better"], entry["source"])
    assert [e["name"] for e in data["per_layer"][-2:]] == list(NEW_READERS)
    for key in ("why",):
        assert len(c.entry[key]) <= 200


def test_the_configuration_file_states_the_cut():
    c = cell().config
    published = c["published"]
    changed = {k for k, v in published.items() if c[k] != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "gating_types", "num_attention_heads_per_layer", "num_experts",
        "vocab_size", "num_attention_heads", "num_key_value_heads"}
    assert set(c["reduced_why"]) == set(c["reduced"])
    entry = [e for e in manifest.Manifest(ROOT).data["configs"]
             if e["name"] == "laguna_s_2_1"][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = [json.loads(line) for line in fh
               if '"Laguna-S-2.1"' in line][0]
    assert published == row["config"] and c["source"] == row["source_url"]
    # no width is cut, no rotary number changed
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "num_experts_per_tok", "sliding_window", "rope_parameters",
                "moe_routed_scaling_factor", "rms_norm_eps",
                "max_position_embeddings"):
        assert c[key] == published[key], key
    assert not set(c["reduced"]) & {
        "intermediate_size", "shared_expert_intermediate_size"}
    assert c["layer_types"] == published["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert published["num_attention_heads_per_layer"][:5] == [
        48, 72, 72, 72, 48]
    assert c["num_attention_heads_per_layer"] == [6, 9, 9, 9, 6]
    h = hparams()
    assert h["layer_types"] == c["layer_types"] and h["num_dense_layers"] == 1
    assert h["experts_held"] == list(range(8)) and h["experts_routed"] == 256
    assert {k: len(v) for k, v in h["heads_held"].items()} == {
        "full_attention": 6, "sliding_attention": 9, "key_value": 1}
    assert (h["dense_units_held"], h["shared_units_held"]) == (1536, 128)
    assert 12544 * 8 == published["vocab_size"]
    for o in ("model.expert_groups=32", "model.tensor_groups=8",
              "model.vocab_size=12544"):
        assert o in c["overrides"]
    for key in ("gate", "qk_norm", "rotated_dims", "attention_factor",
                "router", "activation", "shared_expert", "window"):
        assert "alternative" in c["assumed"][key], key


def test_parameters_and_bytes_of_the_cut():
    """435.8M parameters x 16 B = 6.97 GB = 6.49 GiB; with every head and
    unit kept 810.9M = 13.0 GB, whose state the harness's check cannot
    hold twice."""
    norms = 2 * H
    glob = 2 * H * 6 * D + 2 * H * D + H * 6               # 5.52M
    window = 2 * H * 9 * D + 2 * H * D + H * 9             # 7.89M
    dense = 3 * H * 1536                                   # 14.16M
    experts = H * 256 + 3 * H * 128 + 8 * 3 * H * 1024     # 77.46M
    head = 2 * 12544 * H + H                               # 77.07M
    total = (glob + dense + norms) + 3 * (window + experts + norms) \
        + (glob + experts + norms) + head
    assert (glob, window, dense, experts) == (
        5_523_456, 7_891_968, 14_155_776, 77_463_552)
    assert total == pytest.approx(435.8e6, rel=1e-4)
    assert total * 16 == pytest.approx(6.97e9, rel=1e-3)
    assert total * 16 / 2 ** 30 == pytest.approx(6.49, rel=1e-3)
    whole_glob = 2 * H * 48 * D + 2 * H * 8 * D + H * 48   # 44.19M
    whole_window = 2 * H * 72 * D + 2 * H * 8 * D + H * 72  # 63.14M
    whole_experts = H * 256 + 3 * H * 1024 + 8 * 3 * H * 1024
    every_head = (whole_glob + 3 * H * 12288 + norms) \
        + 3 * (whole_window + whole_experts + norms) \
        + (whole_glob + whole_experts + norms) + head
    assert whole_glob == pytest.approx(44.19e6, rel=1e-3)
    assert whole_window == pytest.approx(63.14e6, rel=1e-3)
    assert every_head == pytest.approx(810.9e6, rel=1e-3)
    assert every_head * 12 * 2 > 16.9e9 > total * 12 * 2


def test_the_traffic_draws_its_ids_from_the_slice():
    c = cell()
    assert c.traffic["vocab_size"] == c.config["vocab_size"] == 12544
    theirs = manifest.Manifest(ROOT).cell(SIBLING).traffic
    assert {k: v for k, v in c.traffic.items()
            if k not in ("why", "vocab_size")} == {
                k: v for k, v in theirs.items()
                if k not in ("why", "vocab_size")}
    pool = build.make_pool(c, ROOT, seed=2_500_000_011)
    assert len(pool.batches) == 8
    for batch in pool.batches:
        ids = np.asarray(batch["input_ids"])
        assert ids.shape == (2, S) and 0 <= ids.min() and ids.max() < 12544
        assert (np.asarray(batch["segment_ids"]) == 1).all()
    again = build.make_pool(c, ROOT, seed=2_500_000_011)
    np.testing.assert_array_equal(pool.batches[3]["input_ids"],
                                  again.batches[3]["input_ids"])


# ------------------------------------------------------------ the counts --
def test_forward_operations_per_token_by_hand():
    h = hparams()
    glob = 2 * H * 768 + 2 * 2 * H * D + 2 * H * 6 + 2 * 768 * H
    window = 2 * H * 1152 + 2 * 2 * H * D + 2 * H * 9 + 2 * 1152 * H
    assert (glob, window) == (11_046_912, 15_783_936)
    dense = 6 * H * 1536                                   # 28.3M
    local = 10 * 8 / 256
    moe = 2 * H * 256 + 6 * H * 128 + local * 6 * H * 1024  # 9.83M
    head = 2 * H * 12544                                   # 77.07M
    want = 2 * glob + 3 * window + dense + 4 * moe + head
    assert flops.dense_flops_per_token(h) == want
    assert want == pytest.approx(214.1e6, rel=1e-3)


def test_a_rows_operations_by_hand():
    """One document of 16384 tokens: 134,225,920 causal pairs, 8,257,792
    of them inside a window of 512; 6 heads in 2 global layers, 9 in 3
    window layers; 13.34 TFLOP forward and backward, 26.7 a step of two
    rows."""
    h = hparams()
    causal = S * (S + 1) // 2
    inside = causal - (S - W) * (S - W + 1) // 2
    assert (causal, inside) == (134_225_920, 8_257_792)
    pairs = 2 * 4 * 6 * D * causal + 3 * 4 * 9 * D * inside
    want = 3 * (flops.dense_flops_per_token(h) * S + pairs)
    assert flops.train_flops(one_row(), h) == want
    assert want == pytest.approx(13.34e12, rel=1e-3)
    assert pairs / (want / 3) == pytest.approx(0.211, abs=0.002)


def test_attention_kernel_work_by_hand():
    h = hparams()
    work = flops.attention_kernel_work(one_row(), h, 2)
    causal = 2 * S * (S + 1) // 2               # two rows a chip
    inside = causal - 2 * ((S - W) * (S - W + 1) // 2)
    g_fwd, w_fwd = 2 * 4 * 6 * D * causal, 3 * 4 * 9 * D * inside
    assert work["forward_flops"] == g_fwd + w_fwd
    assert work["backward_flops"] == 2.5 * (g_fwd + w_fwd)
    assert work["window_forward_flops"] == w_fwd
    # bytes: q, o (and do, dq) of the layer's heads, k and v of 1 head
    q6, q9, kv = 2 * S * 6 * D * 2, 2 * S * 9 * D * 2, 2 * S * D * 2
    lse6, lse9 = 2 * 6 * S * 4, 2 * 9 * S * 4
    assert work["window_forward_bytes"] == 3 * (2 * q9 + 2 * kv + lse9)
    assert work["forward_bytes"] == 2 * (2 * q6 + 2 * kv + lse6) \
        + work["window_forward_bytes"]
    assert work["backward_bytes"] == 2 * (4 * q6 + 4 * kv + lse6) \
        + 3 * (4 * q9 + 4 * kv + lse9)
    part = flops.window_part(work, recomputed_forward=True)
    assert part["forward_flops"] == 2 * w_fwd
    assert part["backward_flops"] == work["window_backward_flops"]
    # the window layers are bound by bytes, not operations, on a v5e
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work["window_forward_bytes"] / peaks["hbm_bytes_per_s"] > \
        work["window_forward_flops"] / peaks["bf16_flops_per_s"] * 0.5


def test_moe_gemm_work_by_hand():
    h = hparams()
    a = 10240.0                         # 32768 x 10 x 8 / 256
    work = flops.moe_gemm_work(a, h)
    assert work["forward_flops"] == a * 6 * H * 1024
    assert work["backward_flops"] == 2 * work["forward_flops"]
    weights = 3 * 8 * H * 1024 * 2
    assert work["forward_bytes"] == a * 2 * (3 * H + 3 * 1024) + weights
    again = flops.moe_gemm_work(a, h, recomputed_forward=True)
    assert again["forward_flops"] == 2 * work["forward_flops"]
    # what moe_gemm_roofline_pct multiplies it by
    assert len(h["layer_types"]) - int(h["num_dense_layers"]) == 4


# ----------------------------------------------------------- the readers --
LABELS = {
    "fusion:fwd/layerN/attn/attn_gate": 0.004,
    "fusion:fwd/layerN/attn_window/attn_gate": 0.006,
    "fusion:again/layerN/attn_window/attn_gate": 0.006,
    "fusion:bwd/layerN/attn_window/attn_gate": 0.010,
    "dot:bwd/layerN/attn/attn_gate": 0.002,
    "fusion:fwd/layerN/attn/qk_norm_rope": 0.008,
    "fusion:again/layerN/attn_window/qk_norm_rope": 0.012,
    "fusion:bwd/layerN/attn_window/qk_norm_rope": 0.020,
    "dot:fwd/layerN/attn/query": 0.050,
    "custom-call:ragged-dot-none": 0.100,
    "fusion:fwd/layerN/moe/shared": 0.003,
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


def test_the_new_readers_sum_their_scope_in_every_pass(monkeypatch):
    monkeypatch.setattr(scope_times, "part_label_s",
                        lambda reader_file, r: r.trace.label_s)
    r = _records(_Trace())
    gate = manifest.load_reader(ROOT, "attn_gate_pct").read(r)
    rope = manifest.load_reader(ROOT, "attn_rope_pct").read(r)
    assert gate == pytest.approx(100 * 0.028 / 2.0)
    assert rope == pytest.approx(100 * 0.040 / 2.0)


def test_the_new_readers_give_nothing_without_their_scope(monkeypatch):
    """The parent's program has neither scope under attention (no gate)
    nor a trace in an untraced run: nothing, and no error."""
    r = _records(None)
    for name in NEW_READERS:
        assert manifest.load_reader(ROOT, name).read(r) is None
    monkeypatch.setattr(scope_times, "part_label_s",
                        lambda reader_file, r: {
                            "dot:fwd/layerN/attn/query": 0.05})
    r = _records(_Trace())
    assert manifest.load_reader(ROOT, "attn_gate_pct").read(r) is None
    assert manifest.load_reader(ROOT, "attn_rope_pct").read(r) is None


# ---------------------------------------------- the cell, small, on the CPU --
TINY = ("model.hidden_size=64", "model.num_heads=16",
        "model.sliding_num_heads=24", "model.num_kv_heads=8",
        "model.head_dim=16", "model.sliding_window=48", "model.mlp_dim=96",
        "model.moe_mlp_dim=24", "model.moe_shared_dim=32",
        "model.vocab_size=512", "model.rope_yarn_original_len=32")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell():
    """The cell's own files with the widths cut through arguments: rows
    of 256 tokens, 2 of 16 global and 3 of 24 window heads, 12 dense and
    4 shared units, the same share and layers."""
    c = cell()
    traffic = dict(c.traffic, seq_len=256, vocab_size=512, pool_batches=4,
                   doc_length={"dist": "fixed", "value": 256})
    config = dict(c.config)
    config["overrides"] = [o for o in config["overrides"]
                           if not o.startswith("model.vocab_size")]
    rope = {k: dict(v) for k, v in config["published"][
        "rope_parameters"].items()}
    rope["full_attention"]["original_max_position_embeddings"] = 32
    config["published"] = {
        **config["published"], "hidden_size": 64, "head_dim": 16,
        "sliding_window": 48, "moe_intermediate_size": 24,
        "rope_parameters": rope}
    config["reference_hparams"] = {
        **config["reference_hparams"], "vocab_size": 512,
        "dense_units_held": 12, "shared_units_held": 4,
        "heads_held": {"full_attention": [0, 1],
                       "sliding_attention": [0, 1, 2], "key_value": [0]}}
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


def test_alternatives_in_the_programs_place_are_read_by_the_comparison():
    """``tools/check_alternatives_laguna.py`` on the small cell in
    float32: every alternative of its list runs through the harness's own
    comparison with the kernels on. Under the fan-in rule and 0.02
    embeddings all but the sigmoid router are refused; under the shipped
    initialisers (output projections of std 0.002 under unit-variance
    embeddings) the branches are small beside the stream and the two
    scalars move by 1e-6..8e-4, as on the chip (PERF.md section 6, PR 36:
    there the shared expert's gate and the factor's place are refused,
    the rest is tests/test_laguna.py's, leaf by leaf)."""
    from benchmarks.tools import check_alternatives, check_alternatives_laguna

    check_alternatives.FAMILIES["laguna"] = \
        check_alternatives_laguna.laguna_alternatives
    c, extra = tiny_cell()
    c.config["check_tolerance"] = {"loss_rel": 1e-4, "grad_norm_rel": 1e-3}
    names = sorted(check_alternatives_laguna.laguna_alternatives())
    assert names == [
        "a_gate_for_each_channel", "a_gate_on_the_shared_expert",
        "a_sigmoid_router", "attention_factor_on_the_softmax_scale_alone",
        "interleaved_pairs_rotate"]
    r = check_alternatives.readings(
        c, ROOT, 2 ** 31 + 11, names, "",
        (*extra, "model.dtype=float32", "model.embed_init_std=0.02",
         "model.out_proj_init_std=0.0"))
    assert r["sound"]["ok"], r["sound"]
    for name in ("a_gate_for_each_channel", "a_gate_on_the_shared_expert",
                 "attention_factor_on_the_softmax_scale_alone",
                 "interleaved_pairs_rotate"):
        assert not r[name]["ok"], (name, r[name])
    # a sigmoid's weights over the chosen, near-uniform at a random init
    # like the softmax's, move the two scalars by 4e-6 and 2e-4 here
    assert r["a_sigmoid_router"]["grad_norm_rel_err"] < 1e-3
    shipped = check_alternatives.readings(
        c, ROOT, 2 ** 31 + 11, ["a_gate_on_the_shared_expert"], "",
        (*extra, "model.dtype=float32"))
    assert shipped["sound"]["ok"], shipped["sound"]
    moved = shipped["a_gate_on_the_shared_expert"]["grad_norm_rel_err"]
    assert shipped["sound"]["grad_norm_rel_err"] < 1e-5 < moved < 1e-2


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
    w = detail["window"]
    assert detail["verdicts"]["no_compile_in_window"]["ok"]
    assert detail["verdicts"]["reference"]["loss_rel_err"] < 5e-3
    # the counters reached the flight recorder's dump, where readers look
    rec = records.RunRecords(
        cell=c, window=w, startup={}, step_memory={}, peaks=PEAKS,
        model_flops_per_unit=1.0, attention_work=None)
    fetched = scope_times.window_counters(
        manifest.load_reader(str(tmp_path), "attn_gate_pct").__file__, rec)
    assert all(m["moe_dropped"] == 0.0 for m in fetched)
    assert all(0.3 < m["attn_gate_mean"] < 0.7 for m in fetched)
    assert all(0.0 < m["attn_window_block_share"] <= 1.0 for m in fetched)
