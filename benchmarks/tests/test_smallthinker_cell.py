"""What PR 30 added for the ``smallthinker_s16384`` cell: the
configuration's cut and its bytes, the traffic, the operation counts, the
reference, the three readers of the window layers' kernels, and the cell
end to end on the CPU at a tiny size."""

import dataclasses
import json
import math
import os
import time

import numpy as np
import pytest

from benchmarks.flops import smallthinker as flops
from benchmarks.harness import attn_scopes, manifest, records, scope_times
from benchmarks.tests.tiny import ROOT
from benchmarks.traffic.generators import lm_documents

CELL = "smallthinker_s16384"
LFM2_CELL = "lfm2_moe_s8192"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("attn_window_pct", "attn_window_roofline_pct",
               "attn_window_block_share")


def cell():
    return manifest.Manifest(ROOT).cell(CELL)


def hparams(c=None):
    c = c or cell()
    return {**c.config["published"], **c.config["reference_hparams"]}


# ------------------------------------------------------------ the files --
def test_the_tree_meets_the_contract_with_six_cells():
    assert manifest.check(ROOT) == []
    data = manifest.Manifest(ROOT).data
    assert len(data["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    c = cell()
    assert (c.chips, c.workload["per_chip_batch"], c.entry["traffic"]) == (
        1, 2, "lm_chunks_s16384")
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s_chip",
                                                  "setup_s"}
    names = {m["name"] for m in c.per_layer}
    theirs = {m["name"] for m in manifest.Manifest(ROOT).cell(
        LFM2_CELL).per_layer}
    # everything the other decoder cell reports but the short convolution,
    # and the three entries of the window layers' kernels, here alone
    assert names == (theirs - {"short_conv_pct"}) | set(NEW_READERS)
    for name in NEW_READERS:
        entry = next(e for e in data["per_layer"] if e["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"]) == ("attention kernels",
                                                    "tokens_per_s_chip")
        reader = manifest.load_reader(ROOT, name)
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["better"], entry["source"])


def test_the_configuration_file_states_the_cut():
    c = cell().config
    published = c["published"]
    changed = {k for k, v in published.items() if c[k] != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "rope_layout", "sliding_window_layout"}
    entry = [e for e in manifest.Manifest(ROOT).data["configs"]
             if e["name"] == "smallthinker_21b_a3b"][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    # no width is cut
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_ffn_hidden_size",
                "moe_num_active_primary_experts", "sliding_window_size",
                "rope_theta", "rms_norm_eps"):
        assert c[key] == published[key], key
    assert (c["num_hidden_layers"], c["moe_num_primary_experts"],
            c["vocab_size"]) == (4, 8, 18992)
    assert c["rope_layout"] == c["sliding_window_layout"] == [0, 1, 1, 1]
    assert published["rope_layout"] == [0, 1, 1, 1] * 13
    h = hparams()
    assert h["experts_held"] == list(range(8)) and h["experts_routed"] == 64
    assert h["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3
    assert h["num_dense_layers"] == 0
    assert 18992 * 8 == published["vocab_size"]
    assert "model.expert_groups=8" in c["overrides"]
    for key in ("router_input", "window", "rotary", "experts"):
        assert key in c["assumed"], key


def test_parameters_and_bytes_of_the_cut():
    """370.5M parameters x 16 B = 5.93 GB, the sum ISSUE.md 30 reckons."""
    H, F, n, m, d = 2560, 768, 28, 4, 128
    attn = 2 * H * n * d + 2 * H * m * d                  # 20.97M
    layer = attn + H * 64 + 8 * 3 * H * F + 2 * H         # two norms
    total = 4 * layer + 2 * 18992 * H + H
    assert attn == pytest.approx(20.97e6, rel=1e-3)
    assert total == pytest.approx(370.5e6, rel=1e-3)
    assert total * 16 == pytest.approx(5.93e9, rel=2e-3)
    whole = 52 * (attn + H * 64 + 64 * 3 * H * F + 2 * H) + 2 * 151936 * H
    assert whole == pytest.approx(21.5e9, rel=1e-2)


# ---------------------------------------------------------- the traffic --
@pytest.fixture(scope="module")
def pool():
    c = cell()
    return lm_documents.generate(c.traffic, seed=2 ** 31 + 12345,
                                 global_batch=c.workload["per_chip_batch"])


def test_the_traffic_is_issue_30s(pool):
    t = cell().traffic
    assert {k: t[k] for k in ("generator", "seq_len", "doc_length",
                              "close_after_misses", "vocab_size",
                              "pool_batches")} == {
        "generator": "lm_documents", "seq_len": 16384,
        "doc_length": {"dist": "fixed", "value": 16384},
        "close_after_misses": 1, "vocab_size": 18992, "pool_batches": 8}


def test_pool_shape_and_units(pool):
    """Every row one whole chunk: the same work in every step."""
    assert len(pool.batches) == 8 and pool.unit == "tokens"
    assert pool.facts["fill"] == 1.0
    assert pool.facts["documents_per_row"] == 1.0
    for b, real in zip(pool.batches, pool.real_units):
        assert set(b) == {"input_ids", "targets", "segment_ids", "positions"}
        assert all(v.shape == (2, 16384) and v.dtype == np.int32
                   for v in b.values())
        assert real == 2 * 16384 and np.all(b["segment_ids"] == 1)
        np.testing.assert_array_equal(b["positions"][0], np.arange(16384))
        assert 0 <= b["input_ids"].min() and b["input_ids"].max() < 18992
        assert np.all(b["targets"][:, -1] == -1)
        np.testing.assert_array_equal(b["targets"][:, :-1],
                                      b["input_ids"][:, 1:])
    assert not np.array_equal(pool.batches[0]["input_ids"],
                              pool.batches[1]["input_ids"])


# ------------------------------------------------------------ the counts --
def test_forward_operations_per_token_by_hand():
    h = hparams()
    H, F = 2560, 768
    attn = 2 * H * 3584 + 2 * 2 * H * 512 + 2 * 3584 * H    # 41_943_040
    moe = 2 * H * 64 + (6 * 8 / 64) * 3 * 2 * H * F         #  9_175_040
    head = 2 * H * 18992                                    # 97_239_040
    want = 4 * (attn + moe) + head
    assert flops.dense_flops_per_token(h) == want == 301_711_360


def test_pairs_inside_the_window_by_hand():
    """ISSUE 30's numbers: 134.2M causal pairs a head and row at 16,384,
    58.7M of them inside a window of 4096 (44%); a document shorter than
    the window keeps all of its pairs."""
    lengths = np.array([16384])
    assert flops.causal_pairs(lengths) == 134_225_920
    assert flops.causal_pairs(lengths, 4096) == 58_722_304
    brute = sum(min(i + 1, 4096) for i in range(16384))
    assert brute == 58_722_304
    short = np.array([1000, 4096, 5000])
    assert flops.causal_pairs(short, 4096) == (
        1000 * 1001 / 2 + 4096 * 4097 / 2
        + 5000 * 5001 / 2 - 904 * 905 / 2)


def test_train_flops_and_kernel_work_count_each_layer_kind():
    h = hparams()
    seg = np.ones((2, 16384), np.int32)
    batch = {"segment_ids": seg, "input_ids": np.zeros_like(seg)}
    in_global, in_window = 2 * 134_225_920, 2 * 58_722_304
    per_pair = 4 * 28 * 128
    want = 3 * (301_711_360 * 32768
                + per_pair * (in_global + 3 * in_window))
    assert flops.train_flops(batch, h) == want
    assert want / 32768 == pytest.approx(1.72e9, rel=1e-3)   # a token
    work = flops.attention_kernel_work(batch, h, rows_per_chip=2)
    assert work["forward_flops"] == per_pair * (in_global + 3 * in_window)
    assert work["backward_flops"] == 2.5 * work["forward_flops"]
    assert work["window_forward_flops"] == per_pair * 3 * in_window
    q_like, kv_like, lse = (2 * 16384 * 3584 * 2, 2 * 16384 * 512 * 2,
                            2 * 28 * 16384 * 4)
    assert work["forward_bytes"] == 4 * (2 * q_like + 2 * kv_like + lse)
    assert work["window_backward_bytes"] == 3 * (4 * q_like + 4 * kv_like
                                                 + lse)
    # half the chip's share of the batch: half the work
    half = flops.attention_kernel_work(batch, h, rows_per_chip=1)
    assert half["forward_flops"] == work["forward_flops"] / 2
    part = flops.window_part(work)
    again = flops.window_part(work, recomputed_forward=True)
    assert part["forward_flops"] == work["window_forward_flops"]
    assert again["forward_flops"] == 2 * part["forward_flops"]
    assert again["forward_bytes"] == 2 * part["forward_bytes"]
    assert again["backward_flops"] == part["backward_flops"]
    # operations bound the window layers' kernels, not bytes
    assert part["forward_flops"] / 197e12 > 10 * part["forward_bytes"] / 819e9


def test_grouped_product_work_follows_the_counted_assignments():
    h = hparams()
    work = flops.moe_gemm_work(24576, h)
    assert work["forward_flops"] == 24576 * 3 * 2 * 2560 * 768
    assert work["backward_flops"] == 2 * work["forward_flops"]
    weights = 3 * 8 * 2560 * 768 * 2
    assert work["forward_bytes"] == 24576 * 2 * (3 * 2560 + 3 * 768) + weights
    twice = flops.moe_gemm_work(24576, h, recomputed_forward=True)
    assert twice["forward_flops"] == twice["backward_flops"]


# ----------------------------------------------------------- the readers --
HLO = """HloModule step
ENTRY %main (p: bf16[2,28,16384,128]) -> bf16[2,28,16384,128] {
  %p = bf16[2,28,16384,128] parameter(0)
  %_flash_fwd.1 = (bf16[2,28,16384,128], f32[2,28,16384,1]) custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_train_step_jit)/jvp(Lfm2ForCausalLM)/layer0/attn/jit(_flash_fwd)/pallas_call"}
  %_flash_fwd.2 = (bf16[2,28,16384,128], f32[2,28,16384,1]) custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_train_step_jit)/jvp(Lfm2ForCausalLM)/layer1/attn_window/jit(_flash_fwd)/pallas_call"}
  %_flash_fwd.3 = (bf16[2,28,16384,128], f32[2,28,16384,1]) custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_train_step_jit)/transpose(jvp(Lfm2ForCausalLM))/jvp(Lfm2ForCausalLM)/checkpoint/rematted_computation/layer1/attn_window/jit(_flash_fwd)/pallas_call"}
  %_flash_bwd.4 = bf16[2,28,16384,128] custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_train_step_jit)/transpose(jvp(Lfm2ForCausalLM))/jvp(Lfm2ForCausalLM)/checkpoint/layer1/attn_window/jit(_flash_bwd)/pallas_call"}
  %_flash_bwd.5 = bf16[2,28,16384,128] custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_train_step_jit)/transpose(jvp(Lfm2ForCausalLM))/jvp(Lfm2ForCausalLM)/checkpoint/layer0/attn/jit(_flash_bwd)/pallas_call"}
  ROOT %fusion.6 = bf16[2,28,16384,128] fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_train_step_jit)/jvp(Lfm2ForCausalLM)/layer1/attn_window/qk_norm_rope/mul"}
}
"""


def test_window_scopes_tell_the_two_kinds_of_layer_apart():
    from benchmarks.harness import hlo_scopes

    plain, window = hlo_scopes.HloScopes(HLO), attn_scopes._WindowScopes(HLO)
    label = lambda sc, n: sc.label(sc.find(n), n)  # noqa: E731
    assert label(plain, "_flash_fwd.2") == "attn_kernel:_flash_fwd"
    assert label(window, "_flash_fwd.2") == \
        "attn_kernel:attn_window/_flash_fwd"
    assert label(window, "_flash_fwd.3") == \
        "attn_kernel:attn_window/again/_flash_fwd"
    assert label(window, "_flash_bwd.4") == \
        "attn_kernel:attn_window/_flash_bwd:dq"
    # a global layer's kernels, and a fusion under the window scope that
    # is no kernel, keep the run's own labels
    for name in ("_flash_fwd.1", "_flash_bwd.5", "fusion.6", "not-there.7"):
        assert label(window, name) == label(plain, name)
    both = {"attn_kernel:attn_window/_flash_fwd": 1.0,
            "attn_kernel:attn_window/again/_flash_fwd": 1.0}
    assert attn_scopes.recomputes(both)
    assert not attn_scopes.recomputes(
        {"attn_kernel:attn_window/_flash_fwd": 1.0})
    assert attn_scopes._reduce("/nowhere", 0) is None


WINDOW_S = {"attn_kernel:attn_window/_flash_fwd": 0.30,
            "attn_kernel:attn_window/again/_flash_fwd": 0.30,
            "attn_kernel:attn_window/_flash_bwd:dq": 0.45,
            "attn_kernel:attn_window/_flash_bwd:dkv": 0.75}
LABELS = {**WINDOW_S, "attn_kernel:_flash_fwd": 0.4,
          "attn_kernel:_flash_bwd:dq": 0.3, "attn_kernel:_flash_bwd:dkv": 0.5,
          "convolution:fwd/layerN/attn_window/query": 1.0,
          "custom-call:ragged-dot-none": 0.6, "optimizer_update": 0.1}


def fake_run(tmp_path, monkeypatch, events, *, label_s=LABELS, steps=20):
    """Records of a traced run of the cell whose checkout is ``tmp_path``:
    the benchmark's files linked in, a flight-recorder dump of this
    process, a second reduction that gives ``label_s``."""
    from benchmarks.harness import trace_reduce

    os.symlink(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks")
    out = tmp_path / ".bench_out" / CELL
    out.mkdir(parents=True)
    if events is not None:
        (out / f"flightrec-{os.getpid()}.json").write_text(
            json.dumps({"events": events}))
    monkeypatch.setattr(attn_scopes, "_reduce",
                        lambda out, pid: None if label_s is None
                        else dict(label_s))
    red = trace_reduce.TraceReduction(
        devices=1, busy_s=5.0, window_s=5.01, category_s={"attn_kernel": 3.0},
        label_s={}, kernel_s={}, collective_s=0.0, collective_exposed_s=0.0,
        idle_gaps=[], steps=5)
    c = cell()
    seg = np.ones((2, 16384), np.int32)
    work = flops.attention_kernel_work(
        {"segment_ids": seg, "input_ids": seg}, hparams(c), 2)
    rec = records.RunRecords(
        cell=c, window={"steps": steps, "rate_per_chip": 30_000.0},
        startup={}, step_memory={"step_gib": 9.27}, peaks=PEAKS,
        model_flops_per_unit=1.72e9, attention_work=work, trace=red)
    return str(tmp_path), rec


def events_of(values, start=10):
    return [{"kind": "train_step", "step": start + 10 * i, "metrics": m}
            for i, m in enumerate(values)] + [{"kind": "health", "step": 1}]


def test_the_new_readers_read_scopes_and_the_counter(tmp_path, monkeypatch):
    root, rec = fake_run(tmp_path, monkeypatch, events_of(
        [{"loss": 10.3, "attn_window_block_share": 1.0}]   # before the window
        + [{"loss": 10.3, "attn_window_block_share": 140 / 272}] * 2))
    read = lambda name: manifest.load_reader(root, name).read(rec)  # noqa: E731
    assert read("attn_window_block_share") == pytest.approx(140 / 272)
    assert read("attn_window_pct") == pytest.approx(100 * 1.8 / 5.0)
    # least time: operations bound both passes; the forward counted twice,
    # because the trace shows it run again
    per_pair, in_window = 28 * 128, 3 * 2 * 58_722_304
    least = (2 * 4 + 10) * per_pair * in_window / 197e12
    assert read("attn_window_roofline_pct") == pytest.approx(
        100 * least / (1.8 / 5))
    assert read("attn_window_roofline_pct") < 100
    # without the re-run in the trace the forward is counted once
    monkeypatch.setattr(
        attn_scopes, "_reduce", lambda out, pid: {
            k: v for k, v in WINDOW_S.items() if "again" not in k})
    assert read("attn_window_roofline_pct") == pytest.approx(
        100 * (4 + 10) * per_pair * in_window / 197e12 / (1.5 / 5))
    # the accepted readers of the layer read this cell's records too
    assert manifest.load_reader(root, "attn_roofline_pct").read(rec) \
        == pytest.approx(100 * (4 + 10) * per_pair
                         * (2 * 134_225_920 + in_window) / 197e12 / (3.0 / 5))
    assert read("mfu_pct") == pytest.approx(100 * 1.72e9 * 3e4 / 197e12)


def test_the_new_readers_give_nothing_on_a_program_without_the_scopes(
        tmp_path, monkeypatch):
    """The parent commit's program has neither the ``attn_window`` scope
    nor the counter (it cannot build the model at all, and a later
    program may name things otherwise); a run may leave no trace or no
    dump. Nothing raises, and the line leaves the metric out."""
    root, rec = fake_run(
        tmp_path, monkeypatch, events_of([{"loss": 10.3}, {"loss": 10.3}]),
        label_s={k: v for k, v in LABELS.items() if k not in WINDOW_S})
    for name in NEW_READERS:
        assert manifest.load_reader(root, name).read(rec) is None, name
    monkeypatch.setattr(attn_scopes, "_reduce", lambda out, pid: None)
    os.remove(os.path.join(root, ".bench_out", CELL,
                           f"flightrec-{os.getpid()}.json"))
    untraced = dataclasses.replace(rec, trace=None)
    for name in NEW_READERS:
        assert manifest.load_reader(root, name).read(rec) is None, name
        assert manifest.load_reader(root, name).read(untraced) is None, name
    # a family whose flops module has no window part: the other decoder
    lfm2 = dataclasses.replace(rec, cell=manifest.Manifest(ROOT).cell(
        LFM2_CELL))
    monkeypatch.setattr(attn_scopes, "_reduce",
                        lambda out, pid: dict(WINDOW_S))
    assert manifest.load_reader(
        root, "attn_window_roofline_pct").read(lfm2) is None


# ------------------------------------------- the reference and the cell --
TINY = ("model.hidden_size=64", "model.num_heads=4", "model.num_kv_heads=2",
        "model.head_dim=32", "model.moe_mlp_dim=32", "model.vocab_size=512",
        "model.sliding_window=64")


def tiny_cell():
    c = cell()
    traffic = dict(c.traffic, seq_len=256, vocab_size=512, pool_batches=4,
                   doc_length={"dist": "fixed", "value": 256})
    config = dict(c.config)
    config["overrides"] = [o for o in config["overrides"]
                           if not o.startswith("model.vocab_size")]
    config["published"] = {
        **config["published"], "hidden_size": 64, "head_dim": 32,
        "moe_ffn_hidden_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window_size": 64}
    config["reference_hparams"] = {**config["reference_hparams"],
                                   "vocab_size": 512}
    config["first_loss"] = {"expected": math.log(512), "band": 0.5}
    config["check_tolerance"] = {"loss_rel": 5e-3, "grad_norm_rel": 5e-2}
    workload = dict(c.workload, trace_steps=3)
    return dataclasses.replace(c, traffic=traffic, config=config,
                               workload=workload), TINY


def test_reference_loss_of_random_weights_is_near_a_uniform_guess():
    """At the cell's own vocabulary slice: ln 18,992 = 9.852, plus what a
    random head's logits (std ~1) add."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import smallthinker as ref
    from distributed_tensorflow_framework_tpu.core.config import ModelConfig
    from distributed_tensorflow_framework_tpu.models import get_model

    c, _ = tiny_cell()
    h = {**c.config["published"], **c.config["reference_hparams"],
         "vocab_size": 18992}
    pool = lm_documents.generate(dict(c.traffic, vocab_size=18992), seed=3,
                                 global_batch=1)
    batch = {k: jnp.asarray(v) for k, v in pool.batches[0].items()}
    model = get_model(ModelConfig(
        name="smallthinker_moe", vocab_size=18992, hidden_size=64,
        num_layers=4, layer_types=h["layer_types"],
        rope_layout=h["rope_layout"], sliding_window=64, num_heads=4,
        num_kv_heads=2, head_dim=32, qk_norm=False, moe_mlp_dim=32,
        num_experts=64, expert_topk=6, expert_groups=8,
        router_input="stream", router_score="softmax_topk",
        expert_activation="relu", tie_embeddings=False, embed_init_std=1.0,
        norm_eps=1e-6, rope_theta=1.5e6, dtype="float32"))
    params = model.init(jax.random.key(0), batch["input_ids"],
                        batch["segment_ids"], batch["positions"])["params"]
    loss, norm = ref.loss_and_grad_norm(params, batch, h)
    assert abs(float(loss) - math.log(18992)) < 0.3
    assert cell().config["first_loss"]["expected"] == pytest.approx(
        math.log(18992), abs=0.6)
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
    assert w["units"] == w["steps"] * 2 * 256          # every row full
    assert detail["verdicts"]["no_compile_in_window"]["ok"]
    assert detail["verdicts"]["reference"]["loss_rel_err"] < 5e-3
    # the counters reached the flight recorder's dump, where readers look
    rec = records.RunRecords(
        cell=c, window=w, startup={}, step_memory={}, peaks=PEAKS,
        model_flops_per_unit=1.0, attention_work=None)
    reader = manifest.load_reader(str(tmp_path), "attn_window_block_share")
    # 128 x 256 tiles on rows of 256 with a window of 64: the two row
    # blocks see the one key block, nothing to skip
    assert reader.read(rec) == 1.0
    fetched = scope_times.window_counters(reader.__file__, rec)
    assert all(m["moe_dropped"] == 0.0 for m in fetched)
    assert all(0.05 < m["moe_local_share"] < 0.25 for m in fetched)
