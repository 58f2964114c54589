"""graftcheck jaxpr-layer audits against the REAL train step.

Acceptance pins from ISSUE 11:

  * the donation audit catches a seeded regression — lowering the same
    step WITHOUT ``donate_argnums`` must produce the finding;
  * the collective census exactly matches the CollectiveTally rows for a
    dp×fsdp shard_map step (and the q8/ZeRO probes), in both directions;
  * the f32-upcast audit flags exactly the deliberate f32 logits head
    (covered by the shipped suppressions) and nothing else.

Probes are memoized in tools/graftcheck/jaxpr_passes._PROBE_CACHE, so
these tests and the tier-1 self-audit trace each configuration once.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_framework_tpu.parallel import collectives as coll
from tools.graftcheck import cli, jaxpr_passes as jp, registry
from tools.graftcheck.context import RepoContext
from tools.graftcheck.findings import apply_suppressions, load_suppressions

ROOT = pathlib.Path(__file__).resolve().parent.parent
SNIPPETS_PATH = (pathlib.Path(__file__).resolve().parent
                 / "graftcheck_fixtures" / "jaxpr_snippets.py")


def _snippets():
    spec = importlib.util.spec_from_file_location(
        "graftcheck_jaxpr_snippets", SNIPPETS_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ctx(devices):
    return RepoContext(ROOT)


# ---------------------------------------------------------------- donation --
def test_donation_pass_clean_on_real_step(ctx):
    findings = jp.donation_pass(ctx)
    assert findings == [], [f.message for f in findings]
    probe = jp.get_probe(ctx, "jit_f32")
    # The audit is counting something real: one alias per state leaf.
    assert probe["n_state_leaves"] > 0
    assert probe["alias_count"] >= probe["n_state_leaves"]


def test_donation_audit_catches_seeded_regression(ctx):
    """Re-jit the SAME underlying step function without donate_argnums:
    the aliasing markers vanish from the lowered text and the audit must
    produce the finding. This is the proof the pass would catch someone
    dropping donate_argnums=(0,) from train/step.py."""
    probe = jp.get_probe(ctx, "jit_f32")
    undonated = jax.jit(probe["builder"]._train_step_jit)
    text = undonated.lower(probe["state_shapes"], probe["batch"]).as_text()
    alias_count = jp.count_output_aliases(text)
    assert alias_count < probe["n_state_leaves"]
    findings = jp.audit_donation(alias_count, probe["n_state_leaves"],
                                 "trace:seeded_no_donate")
    assert len(findings) == 1
    assert "donor-aliased" in findings[0].message
    assert "donate_argnums" in findings[0].message


# -------------------------------------------------------------- f32 upcast --
def test_upcast_audit_fires_on_bad_snippet(devices):
    snip = _snippets()
    x = jnp.zeros((8, 16), jnp.bfloat16)
    w = jnp.zeros((16, 4), jnp.bfloat16)
    hits = jp.collect_upcasts(jax.make_jaxpr(snip.upcast_bad)(x, w))
    assert hits, "bf16→f32 widening feeding a dot must be detected"
    assert all(prim == "dot_general" for prim, _ in hits)


def test_upcast_audit_silent_on_clean_snippet(devices):
    snip = _snippets()
    x = jnp.zeros((8, 16), jnp.bfloat16)
    w = jnp.zeros((16, 4), jnp.bfloat16)
    assert jp.collect_upcasts(jax.make_jaxpr(snip.upcast_clean)(x, w)) == []


def test_upcast_pass_flags_only_the_f32_logits_head(ctx):
    """On the real bf16 step every finding is the deliberate f32 logits
    head — with op provenance naming the layer — and the shipped
    suppression file covers all of them."""
    findings = jp.f32_upcast_pass(ctx)
    assert findings, "the bf16 probe must see the known f32 logits head"
    assert all("logits" in f.where for f in findings), \
        [(f.where, f.message) for f in findings]
    sups, _ = load_suppressions(cli.DEFAULT_SUPPRESSIONS)
    apply_suppressions(findings, sups)
    assert all(f.suppressed for f in findings)


def test_policy_fixture_bad_mid_network_widening_detected(devices):
    """Violating fixture for the bf16-policy probe: a hidden (non-logits)
    matmul widened to f32 must be flagged."""
    snip = _snippets()
    x = jnp.zeros((8, 16), jnp.bfloat16)
    wh = jnp.zeros((16, 16), jnp.bfloat16)
    wl = jnp.zeros((16, 4), jnp.bfloat16)
    hits = jp.collect_upcasts(jax.make_jaxpr(snip.policy_upcast_bad)(x, wh, wl))
    assert hits, "mid-network bf16→f32 widening must be detected"
    assert all(prim == "dot_general" for prim, _ in hits)


def test_policy_fixture_clean_preferred_accum_not_flagged(devices):
    """Clean twin: bf16 operands with f32 MXU accumulation carry no
    convert op — nothing to flag."""
    snip = _snippets()
    x = jnp.zeros((8, 16), jnp.bfloat16)
    wh = jnp.zeros((16, 16), jnp.bfloat16)
    wl = jnp.zeros((16, 4), jnp.bfloat16)
    assert jp.collect_upcasts(
        jax.make_jaxpr(snip.policy_upcast_clean)(x, wh, wl)) == []


def test_bf16_policy_probe_overrides_f32_model_dtype(ctx):
    """The jit_bf16_policy probe keeps model.dtype=float32 and flips the
    compute dtype purely through precision.activation_dtype — its trace
    must show the same deliberate f32 logits-head widening the explicit
    bf16 model does (an all-f32 trace would mean the policy override was
    silently dropped)."""
    probe = jp.get_probe(ctx, "jit_bf16_policy")
    assert str(probe["config"].model.dtype) == "float32"
    assert probe["config"].precision.activation_dtype == "bf16"
    hits = jp.collect_upcasts(probe["jaxpr"])
    assert hits, "policy override dropped: no bf16 compute in the trace"
    assert all("logits" in stack for _, stack in hits), hits


# --------------------------------------------------------- collective census --
def _mesh_1d(devices):
    import numpy as np
    return jax.sharding.Mesh(np.array(devices).reshape(8), ("data",))


def _census_of(fn, devices):
    from jax.sharding import PartitionSpec as P
    # check_vma=False to match the trainer's shard_map usage — with vma
    # tracking on, jax rewrites psum to a different primitive family.
    mapped = jax.shard_map(fn, mesh=_mesh_1d(devices),
                            in_specs=(P("data"),), out_specs=P(),
                            check_vma=False)
    with coll.tally() as t:
        jx = jax.make_jaxpr(mapped)(jnp.zeros((8, 4), jnp.float32))
    return jp.collective_census(jx), jp.expected_census(dict(t.calls))


def test_census_fixture_bad_raw_psum_is_a_mismatch(devices):
    snip = _snippets()
    actual, (expected, unknown) = _census_of(snip.census_bad, devices)
    assert unknown == []
    assert actual.get("psum", 0) > expected.get("psum", 0), (actual, expected)


def test_census_fixture_clean_wrapper_matches(devices):
    snip = _snippets()
    actual, (expected, unknown) = _census_of(snip.census_clean, devices)
    assert unknown == []
    assert actual == expected and actual.get("psum") == 1


def test_census_matches_tally_for_dp_fsdp_step(ctx):
    """ISSUE 11 acceptance: exact two-way census match for the explicit
    dp=4 × fsdp=2 shard_map step, with the known composition pinned."""
    probe = jp.get_probe(ctx, "shard_dp_fsdp")
    actual = jp.collective_census(probe["jaxpr"])
    expected, unknown = jp.expected_census(probe["tally_calls"])
    assert unknown == []
    assert actual == expected, (actual, expected)
    calls = probe["tally_calls"]
    assert calls["allreduce_grads_pmean"] > 0    # grad sync-DP reduce
    assert calls["all_gather"] > 0               # fsdp param gathers
    assert actual["psum"] == (calls["allreduce_grads_pmean"]
                              + calls["pmean"])
    assert actual["all_gather"] == calls["all_gather"]


def test_census_q8_wire_honesty(ctx):
    """int8+error-feedback probe: each q8 scatter is TWO all_to_all ops
    on the wire (payload + block scales) and each q8 gather TWO
    all_gather ops — the tally's byte accounting rides exactly that."""
    probe = jp.get_probe(ctx, "shard_q8_ef")
    actual = jp.collective_census(probe["jaxpr"])
    expected, unknown = jp.expected_census(probe["tally_calls"])
    assert unknown == []
    assert actual == expected, (actual, expected)
    calls = probe["tally_calls"]
    assert calls["allreduce_grads_q8_scatter"] > 0
    assert calls["allreduce_grads_q8_gather"] > 0
    assert actual["all_to_all"] == 2 * calls["allreduce_grads_q8_scatter"]
    assert actual["all_gather"] == 2 * calls["allreduce_grads_q8_gather"]


def test_census_zero_probe_accounts_for_the_grad_norm_psum(ctx):
    """Regression pin for the untallied lax.psum the census flushed out of
    zero.shard_global_norm: the ZeRO probe's grad-norm psum must now have
    a tally row, and the whole step must census-match."""
    probe = jp.get_probe(ctx, "shard_zero")
    actual = jp.collective_census(probe["jaxpr"])
    expected, unknown = jp.expected_census(probe["tally_calls"])
    assert unknown == []
    assert actual == expected, (actual, expected)
    calls = probe["tally_calls"]
    assert calls["zero_reduce_scatter"] > 0
    assert calls["zero_all_gather"] > 0
    assert calls.get("psum", 0) >= 1  # shard_global_norm, now tallied


def test_census_fused_update_keeps_the_wire_identical(ctx):
    """precision.fused_update moves the optax apply into the bucketed
    walk — it must change WHERE the update runs, not what goes on the
    wire: identical tally kinds and counts to the unfused ZeRO probe,
    and a clean two-way census."""
    fused = jp.get_probe(ctx, "shard_zero_fused")
    unfused = jp.get_probe(ctx, "shard_zero")
    actual = jp.collective_census(fused["jaxpr"])
    expected, unknown = jp.expected_census(fused["tally_calls"])
    assert unknown == []
    assert actual == expected, (actual, expected)
    assert fused["tally_calls"] == unfused["tally_calls"], (
        fused["tally_calls"], unfused["tally_calls"])


# -------------------------------------------------------------- self-audit --
def test_self_audit_jaxpr_layer_clean(ctx):
    findings = []
    for info in registry.passes_for_layer(registry.LAYER_JAXPR):
        findings.extend(info.fn(ctx))
    sups, _ = load_suppressions(cli.DEFAULT_SUPPRESSIONS)
    apply_suppressions(findings, sups)
    active = [f for f in findings if not f.suppressed]
    assert active == [], [(f.pass_id, f.where, f.message) for f in active]
