"""Config system tests (SURVEY.md §2 row 11 replacement)."""

import pytest

from distributed_tensorflow_framework_tpu.core.config import (
    ExperimentConfig,
    load_config,
)


def test_default_config():
    cfg = load_config()
    assert isinstance(cfg, ExperimentConfig)
    assert cfg.model.name == "lenet5"
    assert cfg.mesh.data == -1


def test_yaml_and_overrides(tmp_path):
    p = tmp_path / "c.yaml"
    p.write_text(
        """
name: lenet-mnist
model:
  name: lenet5
  num_classes: 10
data:
  name: mnist
  global_batch_size: 128
optimizer:
  name: sgd_momentum
  learning_rate: 0.01
train:
  total_steps: 500
"""
    )
    cfg = load_config(p, overrides=["train.total_steps=7", "optimizer.learning_rate=0.5", "mesh.data=4", "mesh.fsdp=2"])
    assert cfg.name == "lenet-mnist"
    assert cfg.train.total_steps == 7
    assert cfg.optimizer.learning_rate == 0.5
    assert cfg.mesh.data == 4 and cfg.mesh.fsdp == 2
    assert cfg.data.global_batch_size == 128


def test_override_scalar_coercion():
    # YAML-1.1 gap: "1e-3" (no dot) parses as a string — we coerce it.
    cfg = load_config(overrides=["optimizer.learning_rate=1e-3"])
    assert cfg.optimizer.learning_rate == 1e-3
    cfg = load_config(overrides=["optimizer.learning_rate=2.5E+2"])
    assert cfg.optimizer.learning_rate == 250.0
    # But float()-parseable *strings* must stay strings: a bare float()
    # would turn these into nan / inf. ("1_000" is already an int per
    # YAML 1.1 underscore syntax — that's the YAML parser, not coercion.)
    for raw in ("nan", "inf", "infinity", "1e", "e5"):
        cfg = load_config(overrides=[f"name={raw}"])
        assert cfg.name == raw, raw


def test_unknown_key_rejected(tmp_path):
    p = tmp_path / "c.yaml"
    p.write_text("modell: {name: lenet5}\n")
    with pytest.raises(ValueError, match="Unknown key"):
        load_config(p)


def test_print_config_roundtrips(capsys):
    """--print-config dumps the resolved config as YAML and exits before
    any device/Trainer work; the dump must parse and carry overrides."""
    import yaml

    from distributed_tensorflow_framework_tpu.cli.train import main

    rc = main(["--config", "configs/bert_base_mlm.yaml",
               "--set", "mesh.data=4", "--set", "train.total_steps=7",
               "--print-config"])
    assert rc == 0
    dumped = yaml.safe_load(capsys.readouterr().out)
    assert dumped["mesh"]["data"] == 4
    assert dumped["train"]["total_steps"] == 7
    # And the dump is itself a loadable config (round-trip property).
    cfg = load_config(base=dumped)
    assert cfg.train.total_steps == 7


def test_grad_allreduce_dtype_deprecation_shim(caplog):
    """train.grad_allreduce_dtype predates parallel.collective_dtype; the
    old spelling must keep working (mapped with a warning), agree-both
    must pass silently, and a conflict must be a hard error — a silent
    precedence pick would change which wire format a run uses."""
    import logging

    with caplog.at_level(logging.WARNING):
        cfg = load_config(overrides=["train.grad_allreduce_dtype=bfloat16"])
    assert cfg.parallel.collective_dtype == "bfloat16"
    assert "deprecated" in caplog.text

    # Both knobs set to the SAME value: fine (explicit, unambiguous).
    cfg = load_config(overrides=["train.grad_allreduce_dtype=int8",
                                 "parallel.collective_dtype=int8"])
    assert cfg.parallel.collective_dtype == "int8"

    with pytest.raises(ValueError, match="conflicts"):
        load_config(overrides=["train.grad_allreduce_dtype=bfloat16",
                               "parallel.collective_dtype=int8"])


def test_collective_dtype_validated():
    with pytest.raises(ValueError, match="collective_dtype"):
        load_config(overrides=["parallel.collective_dtype=fp8"])
    with pytest.raises(ValueError, match="collective_block_size"):
        load_config(overrides=["parallel.collective_block_size=0"])


def test_fleet_autoscale_and_tenant_knobs_validated():
    with pytest.raises(ValueError, match="fleet_min_replicas"):
        load_config(overrides=["serve.fleet_min_replicas=0"])
    with pytest.raises(ValueError, match="fleet_max_replicas"):
        load_config(overrides=["serve.fleet_min_replicas=4",
                               "serve.fleet_max_replicas=2"])
    # The hysteresis band must be a band: 0 < down < up.
    with pytest.raises(ValueError, match="hysteresis"):
        load_config(overrides=["serve.fleet_scale_down_threshold=0.9"])
    with pytest.raises(ValueError, match="cooldown"):
        load_config(overrides=["serve.fleet_scale_cooldown_s=-1"])
    # A reserve so large the lowest class can never claim is a footgun.
    with pytest.raises(ValueError, match="tenant_priority_reserve"):
        load_config(overrides=["serve.queue_capacity=4",
                               "serve.tenant_priority_reserve=2"])
    with pytest.raises(ValueError, match="tenant_quota_rps"):
        load_config(overrides=["serve.tenant_quota_rps=-1"])
    with pytest.raises(ValueError, match="tenant_quota_burst"):
        load_config(overrides=["serve.tenant_quota_burst=-1"])
    cfg = load_config(overrides=["serve.fleet_autoscale=true",
                                 "serve.fleet_max_replicas=4",
                                 "serve.tenant_quota_rps=2.5"])
    assert cfg.serve.fleet_autoscale is True
    assert cfg.serve.fleet_max_replicas == 4
    assert cfg.serve.tenant_quota_rps == 2.5


# --- decoder family: heads and rotary rules by layer kind, the gate, shares --
def _laguna_overrides(*more):
    return ["model.num_layers=3",
            "model.layer_types=[full_attention,sliding_attention,"
            "full_attention]", "model.hidden_size=64", "model.num_heads=4",
            "model.sliding_num_heads=6", "model.num_kv_heads=2",
            "model.head_dim=16", "model.sliding_window=24",
            "model.mlp_dim=96", "model.moe_mlp_dim=24",
            "model.moe_shared_dim=32", "model.num_experts=16",
            "model.expert_topk=3", "model.vocab_size=256",
            "data.vocab_size=256", "data.seq_len=128", *more]


def _laguna_yaml():
    import os
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "laguna_s_2_1.yaml")


def test_rotary_rules_by_layer_kind_parse_from_overrides():
    from distributed_tensorflow_framework_tpu.models import get_model

    cfg = load_config(_laguna_yaml(), _laguna_overrides(
        "model.rope_yarn_factor=8", "model.rope_yarn_original_len=32",
        "model.rope_fraction=0.25", "model.sliding_rope_theta=50",
        "model.sliding_rope_fraction=0.5", "model.attention_gate=per_head"))
    m = cfg.model
    assert (m.rope_yarn_factor, m.rope_yarn_original_len, m.rope_fraction,
            m.sliding_rope_theta, m.sliding_rope_fraction) == (
                8.0, 32, 0.25, 50.0, 0.5)
    model = get_model(m)
    assert model._rotary("full_attention") == (500000.0, model.rope_rule)
    assert model.rope_rule.fraction == 0.25
    assert model.rope_rule.yarn_factor == 8.0
    assert model._rotary("sliding_attention")[0] == 50.0
    assert model._rotary("sliding_attention")[1].fraction == 0.5
    # no theta of their own: the window layers share the global rule
    shared = get_model(load_config(_laguna_yaml(), _laguna_overrides(
        "model.sliding_rope_theta=0", "model.rope_yarn_original_len=32")
    ).model)
    assert shared._rotary("sliding_attention") == shared._rotary(
        "full_attention")
    # the defaults are the plain rule: no rule object at all
    plain = get_model(load_config(_laguna_yaml(), _laguna_overrides(
        "model.sliding_rope_theta=0", "model.rope_yarn_factor=0",
        "model.rope_fraction=1", "model.rope_attention_factor=1")).model)
    assert plain.rope_rule is None and plain.sliding_rope_rule is None


@pytest.mark.parametrize("bad,says", [
    (["model.tensor_groups=4"], "whole, even shares"),   # 6 window heads
    (["model.tensor_groups=2", "model.mlp_dim=97"], "dense"),
    (["model.tensor_groups=2", "model.moe_shared_dim=33"], "shared"),
    (["model.tensor_groups=2", "model.tensor_group=2"], "is not one of"),
    (["model.sliding_num_heads=5"], "sliding_num_heads"),
    (["model.attention_gate=per_channel"], "attention_gate"),
    (["model.rope_fraction=0.3"], "rope_fraction"),
    (["model.rope_yarn_original_len=0"], "rope_yarn"),
    (["model.sliding_rope_theta=-1"], "sliding_rope_theta"),
])
def test_shares_and_rotary_rules_by_kind_are_validated(bad, says):
    from distributed_tensorflow_framework_tpu.models import get_model

    cfg = load_config(_laguna_yaml(), _laguna_overrides(
        "model.rope_yarn_original_len=32", *bad))
    with pytest.raises(ValueError, match=says):
        get_model(cfg.model)


def test_a_share_of_heads_by_kind_and_of_both_unit_runs_is_legal():
    from distributed_tensorflow_framework_tpu.models import get_model

    cfg = load_config(_laguna_yaml(), _laguna_overrides(
        "model.rope_yarn_original_len=32", "model.tensor_groups=2",
        "model.tensor_group=1", "model.expert_groups=4"))
    share = get_model(cfg.model).tensor_share()
    assert share["attention"]["held"] == [2, 3]
    assert share["attention_window"]["held"] == [3, 4, 5]
    assert share["dense_ffn"] == {"units": 96, "held": [48, 96]}
    assert share["shared_expert"] == {"units": 32, "held": [16, 32]}


# --- decoder family: latent attention and interleaved rotary pairs ---------
def _kanana_yaml():
    import os
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "kanana_2_30b_a3b.yaml")


def _kanana_overrides(*more):
    return ["model.num_layers=2",
            "model.layer_types=[latent_attention,latent_attention]",
            "model.hidden_size=64", "model.num_heads=4",
            "model.num_kv_heads=4", "model.mla_kv_rank=32",
            "model.mla_nope_dim=16", "model.mla_rope_dim=8",
            "model.mla_v_dim=16", "model.mlp_dim=96", "model.moe_mlp_dim=24",
            "model.moe_shared_dim=32", "model.num_experts=16",
            "model.expert_topk=3", "model.vocab_size=256",
            "data.vocab_size=256", "data.seq_len=128", *more]


def test_latent_attention_settings_parse_from_overrides():
    from distributed_tensorflow_framework_tpu.models import get_model

    m = load_config(_kanana_yaml(), _kanana_overrides(
        "model.tensor_groups=2", "model.tensor_group=1")).model
    assert (m.mla_kv_rank, m.mla_nope_dim, m.mla_rope_dim, m.mla_v_dim,
            m.rope_pairs) == (32, 16, 8, 16, "interleaved")
    model = get_model(m)
    assert model.latent == (32, 16, 8, 16)
    assert model.rope_rule.pairs == "interleaved"
    assert model.tensor_share()["attention"]["held"] == [2, 3]
    # every other configuration keeps the half rule and no latent
    plain = load_config().model
    assert (plain.rope_pairs, plain.mla_kv_rank, plain.mla_v_dim) == (
        "half", 0, 0)


@pytest.mark.parametrize("bad,says", [
    (["model.mla_kv_rank=0"], "mla_kv_rank"),
    (["model.mla_v_dim=0"], "mla_v_dim"),
    (["model.mla_rope_dim=5"], "mla_rope_dim"),
    (["model.qk_norm=true"], "no q/k norm"),
    (["model.rope_layout=[1,0]"], "always rotates"),
    (["model.rope_pairs=adjacent"], "rope_pairs"),
])
def test_a_latent_layer_without_its_settings_is_refused(bad, says):
    from distributed_tensorflow_framework_tpu.models import get_model

    cfg = load_config(_kanana_yaml(), _kanana_overrides(*bad))
    with pytest.raises(ValueError, match=says):
        get_model(cfg.model)
