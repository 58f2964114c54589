"""What PR 32 added for the ``nemotron3_super_s8192`` cell: the
configuration's cut and its bytes, the operation counts, the four readers
of the Mamba-2 layers' and the latent experts' scopes on labels recorded
from the chip, and the cell end to end on the CPU at a tiny size. By hand,
like the other cell tests: ``python -m pytest
benchmarks/tests/test_nemotron3_cell.py -q``."""

import dataclasses
import json
import math
import os
import time

import numpy as np
import pytest

from benchmarks.flops import nemotron_h as flops
from benchmarks.harness import manifest, records, scope_times
from benchmarks.tests.tiny import ROOT

CELL = "nemotron3_super_s8192"
LFM2_CELL = "lfm2_moe_s8192"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("ssm_pct", "ssm_scan_roofline_pct", "moe_shared_pct",
               "moe_latent_gemm_roofline_pct")
# Device seconds by label (``scope_times.part_label_s``: every pass kept
# apart) of one traced run of the cell on a TPU v5e, 5 steps, with the
# run's busy seconds: my chip run, PR 32.
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "nemotron3_super_s8192_labels.json")


def cell():
    return manifest.Manifest(ROOT).cell(CELL)


def hparams(c=None):
    c = c or cell()
    return {**c.config["published"], **c.config["reference_hparams"]}


# ------------------------------------------------------------ the files --
def test_the_tree_meets_the_contract_with_seven_cells():
    assert manifest.check(ROOT) == []
    data = manifest.Manifest(ROOT).data
    assert len(data["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 1
    c = cell()
    assert (c.chips, c.workload["per_chip_batch"], c.entry["traffic"]) == (
        1, 1, "lm_packed_s8192")
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s_chip",
                                                  "setup_s"}
    names = {m["name"] for m in c.per_layer}
    theirs = {m["name"] for m in manifest.Manifest(ROOT).cell(
        LFM2_CELL).per_layer}
    # everything the first decoder cell reports but the short convolution
    # and the gated experts' roofline (its reader counts every non-dense
    # layer as an expert layer), and the four new entries, here alone
    assert names == (theirs - {"short_conv_pct", "moe_gemm_roofline_pct"}
                     ) | set(NEW_READERS)
    for name in NEW_READERS:
        entry = next(e for e in data["per_layer"] if e["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s_chip"
        reader = manifest.load_reader(ROOT, name)
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["better"], entry["source"])


def test_the_configuration_file_states_the_cut():
    c = cell().config
    published = c["published"]
    changed = {k for k, v in published.items() if c[k] != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads", "num_nextn_predict_layers"}
    assert set(c["reduced_why"]) == set(c["reduced"])
    entry = [e for e in manifest.Manifest(ROOT).data["configs"]
             if e["name"] == "nemotron3_super_120b_a12b"][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = [json.loads(line) for line in fh
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line][0]
    assert published == row["config"] and c["source"] == row["source_url"]
    # no width is cut
    for key in ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
                "chunk_size", "conv_kernel", "expand", "moe_latent_size",
                "moe_intermediate_size", "intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "layer_norm_epsilon"):
        assert c[key] == published[key], key
    pattern = published["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (88, 40, 40, 8)
    assert pattern[26:37] == c["hybrid_override_pattern"] == "EMEMEMEMEM*"
    h = hparams()
    assert h["pattern"] == c["hybrid_override_pattern"]
    assert h["experts_held"] == list(range(8)) and h["experts_routed"] == 512
    assert {k: len(v) for k, v in h["heads_held"].items()} == {
        "mamba": 16, "bc_groups": 1, "attention": 4, "key_value": 1}
    assert 16384 * 8 == published["vocab_size"]
    for o in ("model.expert_groups=64", "model.tensor_groups=8"):
        assert o in c["overrides"]
    for key in ("positions", "what_the_expert_layer_reads", "latent",
                "routed_scaling_factor", "gated_norm", "step_size"):
        assert "alternative" in c["assumed"][key], key


def test_parameters_and_bytes_of_the_cut():
    """508.2M parameters x 16 B = 8.13 GB = 7.57 GiB; with the shared
    expert whole on every chip, ISSUE.md 32's 700.9M = 11.21 GB, whose
    8.41 GB of weights and moments the harness's check cannot hold twice;
    every head kept it is 1210.9M = 19.4 GB."""
    H = 4096
    mamba = H * 2320 + 1280 * 5 + 1024 * H                 # 13.71M
    attention = H * (512 + 128 + 128) + 512 * H            # 5.25M
    outside = H * 512 + 2 * H * 1024 + 2 * H * 5376        # 54.5M
    held = H * 512 + 2 * H * 1024 + 2 * H * 672            # 16.0M
    experts = 8 * 2 * 1024 * 2688                          # 44.0M
    rest = 5 * mamba + attention + 2 * 16384 * H
    total = rest + 5 * (held + experts)
    assert mamba == pytest.approx(13.71e6, rel=1e-3)
    assert attention == pytest.approx(5.25e6, rel=2e-3)
    assert outside + experts == pytest.approx(98.57e6, rel=1e-3)
    assert rest + 5 * (outside + experts) == pytest.approx(700.9e6, rel=1e-3)
    # the state twice and the 2.15 GB the check's step reserves (the chip)
    assert (rest + 5 * (outside + experts)) * 12 * 2 + 2.15e9 > 16.9e9
    assert total == pytest.approx(508.2e6, rel=1e-3)
    assert total * 16 == pytest.approx(8.13e9, rel=1e-3)
    assert total * 16 / 2 ** 30 == pytest.approx(7.57, rel=1e-3)
    assert total * 12 * 2 + 2.15e9 < 16.9e9
    whole_mamba = H * (2 * 8192 + 2 * 8 * 128 + 128) + 10240 * 5 + 8192 * H
    whole_attention = H * (4096 + 256 + 256) + 4096 * H
    every_head = 5 * whole_mamba + whole_attention + 5 * (
        outside + experts) + 2 * 16384 * H
    assert whole_mamba == pytest.approx(109.64e6, rel=1e-3)
    assert every_head * 16 == pytest.approx(19.4e9, rel=3e-3)


# ------------------------------------------------------------ the counts --
def test_forward_operations_per_token_by_hand():
    h = hparams()
    H, L, F, S = 4096, 1024, 2688, 672
    scan = 1 * 2 * 128 * 64.5 + 16 * (2 * 64 * 64.5 + 4 * 64 * 128)
    assert flops.scan_flops_per_token(h) == scan
    mamba = 2 * H * 2320 + 2 * 1024 * H + scan
    attention = 2 * H * 512 + 2 * 2 * H * 128 + 2 * 512 * H
    local = 22 * 8 / 512
    moe = 2 * H * 512 + 4 * H * L + 4 * H * S + local * 4 * L * F
    assert flops.dense_flops_per_token(h) == pytest.approx(
        5 * mamba + attention + 5 * moe + 2 * H * 16384)
    assert hparams()["shared_units_held"] * 8 == 5376


def test_train_flops_and_kernel_work_count_the_held_heads():
    h = hparams()
    seg = np.concatenate([np.full((2, 5000), 1), np.full((2, 3000), 2),
                          np.zeros((2, 192))], axis=1).astype(np.int32)
    batch = {"segment_ids": seg, "input_ids": seg}
    pairs = 2 * (5000 * 5001 / 2 + 3000 * 3001 / 2)
    assert flops.train_flops(batch, h) == pytest.approx(3 * (
        flops.dense_flops_per_token(h) * 16000 + 4 * 4 * 128 * pairs))
    work = flops.attention_kernel_work(batch, h, 2)
    assert work["forward_flops"] == pytest.approx(4 * 4 * 128 * pairs)
    assert work["backward_flops"] == pytest.approx(10 * 4 * 128 * pairs)
    q_like, kv_like = 2 * 8192 * 4 * 128 * 2, 2 * 8192 * 1 * 128 * 2
    assert work["forward_bytes"] == 2 * q_like + 2 * kv_like + 2 * 4 * 8192 * 4
    scan = flops.ssm_scan_work(16384, h)
    assert scan["forward_flops"] == 5 * 16384 * flops.scan_flops_per_token(h)
    assert scan["backward_flops"] == 2 * scan["forward_flops"]
    assert scan["forward_bytes"] == 5 * 16384 * (
        2 * (1024 + 256) + 4 * 16 + 4 * 1024)
    twice = flops.ssm_scan_work(16384, h, recomputed_forward=True)
    assert twice["forward_flops"] == twice["backward_flops"]


def test_grouped_product_work_follows_the_counted_assignments():
    h = hparams()
    work = flops.moe_gemm_work(5632, h)
    assert work["forward_flops"] == 5632 * 2 * 2 * 1024 * 2688
    assert work["backward_flops"] == 2 * work["forward_flops"]
    weights = 2 * 8 * 1024 * 2688 * 2
    assert work["forward_bytes"] == 5632 * 2 * (2 * 1024 + 2 * 2688) + weights
    twice = flops.moe_gemm_work(5632, h, recomputed_forward=True)
    assert twice["forward_flops"] == twice["backward_flops"]


# ----------------------------------------------------------- the readers --
def fake_run(tmp_path, monkeypatch, events, label_s, *, busy_s, steps=5,
             window_steps=20):
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
    monkeypatch.setattr(scope_times, "_reduce_again",
                        lambda out, pid: None if label_s is None
                        else dict(label_s))
    # the run's own labels fold the re-run pass's parts away; a whole
    # module's seconds are the same sum
    folded = {k.replace(":again/", ":bwd/rematted_computation/"): v
              for k, v in (label_s or {}).items()}
    red = trace_reduce.TraceReduction(
        devices=1, busy_s=busy_s, window_s=busy_s * 1.001,
        category_s={}, label_s=folded, kernel_s={}, collective_s=0.0,
        collective_exposed_s=0.0, idle_gaps=[], steps=steps)
    rec = records.RunRecords(
        cell=cell(), window={"steps": window_steps, "rate_per_chip": 1.4e4},
        startup={}, step_memory={"step_gib": 11.7}, peaks=PEAKS,
        model_flops_per_unit=1.3e9, attention_work=None, trace=red)
    return str(tmp_path), rec


def events_of(values, start=10):
    return [{"kind": "train_step", "step": start + 10 * i, "metrics": m}
            for i, m in enumerate(values)]


def test_every_new_reader_reads_the_recorded_labels(tmp_path, monkeypatch):
    """The labels a traced run of the cell left on the chip: each of the
    four readers gives a finite share, the roofline shares under 100%."""
    with open(RECORDED) as fh:
        recorded = json.load(fh)
    counters = {"loss": 10.5, "moe_local_assignments":
                recorded["moe_local_assignments"]}
    root, rec = fake_run(tmp_path, monkeypatch,
                         events_of([counters] * 3), recorded["label_s"],
                         busy_s=recorded["busy_s"], steps=recorded["steps"])
    got = {name: manifest.load_reader(root, name).read(rec)
           for name in NEW_READERS}
    assert all(v is not None and math.isfinite(v) and 0 < v < 100
               for v in got.values()), got
    label_s = recorded["label_s"]
    mamba = scope_times.seconds(label_s, "mamba")
    assert got["ssm_pct"] == pytest.approx(100 * mamba / recorded["busy_s"])
    assert got["moe_shared_pct"] == pytest.approx(
        100 * scope_times.seconds(
            label_s, "moe", ("shared", "latent_in", "latent_out"))
        / recorded["busy_s"])
    # the scan's least time: the trace shows the forward run again
    assert scope_times.recomputes(label_s, "mamba")
    work = flops.ssm_scan_work(8192, hparams(), recomputed_forward=True)
    least = sum(max(work[f"{p}_flops"] / 197e12, work[f"{p}_bytes"] / 819e9)
                for p in ("forward", "backward"))
    spent = scope_times.seconds(label_s, "mamba", ("scan",)) / recorded["steps"]
    assert got["ssm_scan_roofline_pct"] == pytest.approx(100 * least / spent)
    # the accepted readers of the expert layer read these labels too
    for name in ("moe_pct", "moe_dispatch_pct"):
        value = manifest.load_reader(root, name).read(rec)
        assert value is not None and 0 < value < 100, name


def test_the_new_readers_give_nothing_on_a_program_without_the_scopes(
        tmp_path, monkeypatch):
    """The parent commit's program has none of the scopes (it cannot build
    the model at all); a run may leave no trace or no dump. Nothing
    raises, and the line leaves the metric out."""
    others = {"convolution:fwd/layerN/short_conv/in_proj": 1.0,
              "custom-call:ragged-dot-none": 0.6,
              "fusion:fwd/layerN/moe/router": 0.2, "optimizer_update": 0.1}
    root, rec = fake_run(tmp_path, monkeypatch,
                         events_of([{"loss": 10.3}] * 2), others, busy_s=5.0)
    lfm2 = dataclasses.replace(rec, cell=manifest.Manifest(ROOT).cell(
        LFM2_CELL))
    for name in NEW_READERS:
        assert manifest.load_reader(root, name).read(rec) is None, name
        assert manifest.load_reader(root, name).read(lfm2) is None, name
    monkeypatch.setattr(scope_times, "_reduce_again", lambda out, pid: None)
    untraced = dataclasses.replace(rec, trace=None)
    for name in NEW_READERS:
        assert manifest.load_reader(root, name).read(rec) is None, name
        assert manifest.load_reader(root, name).read(untraced) is None, name


# ---------------------------------------------------- the cell on the CPU --
TINY = ("model.hidden_size=64", "model.num_heads=8", "model.num_kv_heads=8",
        "model.head_dim=16", "model.mamba_num_heads=8",
        "model.mamba_head_dim=8", "model.ssm_state_size=16",
        "model.mamba_chunk=32", "model.moe_mlp_dim=24",
        "model.moe_latent_dim=32", "model.moe_shared_dim=48",
        "model.vocab_size=512")


def tiny_cell():
    c = cell()
    traffic = dict(c.traffic, seq_len=256, vocab_size=512, pool_batches=4,
                   doc_length={"dist": "lognormal", "median": 60,
                               "sigma": 0.8, "min": 8, "max": 256})
    config = dict(c.config)
    config["overrides"] = [o for o in config["overrides"]
                           if not o.startswith("model.vocab_size")]
    config["published"] = {
        **config["published"], "hidden_size": 64, "head_dim": 16,
        "mamba_head_dim": 8, "ssm_state_size": 16, "chunk_size": 32,
        "moe_intermediate_size": 24, "moe_latent_size": 32,
        "moe_shared_expert_intermediate_size": 48}
    config["reference_hparams"] = {
        **config["reference_hparams"], "vocab_size": 512,
        "shared_units_held": 6,
        "heads_held": {"mamba": [0], "bc_groups": [0], "attention": [0],
                       "key_value": [0]}}
    config["first_loss"] = {"expected": math.log(512), "band": 0.5}
    config["check_tolerance"] = {"loss_rel": 5e-3, "grad_norm_rel": 5e-2}
    workload = dict(c.workload, trace_steps=3)
    return dataclasses.replace(c, traffic=traffic, config=config,
                               workload=workload), TINY


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


def test_alternatives_in_the_programs_place_are_read_by_the_comparison():
    """``tools/check_alternatives.py`` on the small cell in float32: the
    gated norm's order (inside a Mamba-2 layer) and a plain ReLU (inside
    an expert layer), each put in the program's place, are refused by
    the harness's comparison that the program itself passes. A scan
    that runs across documents moves the whole model's ``loss`` and
    ``grad_norm`` by under 1e-4 here: the configuration's output
    projections (std 0.0015) keep every branch small beside the head, so
    the two numbers the harness compares cannot see it (PERF.md section 7;
    tier-1 fails it leaf by leaf under the fan-in rule)."""
    from benchmarks.tools import check_alternatives

    c, extra = tiny_cell()
    c.config["check_tolerance"] = {"loss_rel": 1e-4, "grad_norm_rel": 1e-3}
    r = check_alternatives.readings(
        c, ROOT, 2 ** 31 + 11,
        ["norm_then_gate", "a_plain_relu_in_the_experts",
         "no_state_reset_at_a_document"], "",
        (*extra, "model.dtype=float32", "model.attention_impl=xla"))
    assert r["sound"]["ok"], r["sound"]
    assert not r["norm_then_gate"]["ok"], r
    assert not r["a_plain_relu_in_the_experts"]["ok"], r
    assert r["no_state_reset_at_a_document"]["grad_norm_rel_err"] < 1e-4


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
        manifest.load_reader(str(tmp_path), "ssm_pct").__file__, rec)
    assert all(m["moe_dropped"] == 0.0 for m in fetched)
    assert all(m["ssm_resets"] >= 2.0 for m in fetched)
