"""Analytic traffic model: predict a candidate's roofline position
BEFORE spending a run on it.

The incumbent's measured artifacts (XLA cost-model flops, memory_analysis
footprint, CollectiveTally wire bytes, opt_state_bytes_per_chip — all
already on every bench row) give a TrafficProfile. Each knob value
carries analytic multipliers on the four traffic components (flops, HBM
bytes, wire bytes, optimizer-state bytes) relative to that knob's
baseline value; a candidate's predicted traffic is the incumbent's
scaled by the product of its knobs' relative factors. core/roofline
turns predicted traffic into a step-time floor per resource, and the
pruning rule compares candidates to the incumbent ON THE BINDING
RESOURCE: a candidate whose predicted rate is more than ``prune_margin``
below the incumbent's predicted rate is skipped with the numbers logged.
Both sides of the comparison go through the same model, so systematic
model error divides out; the margin absorbs the rest.

Factor values are analytic-with-measured-anchors, documented inline
(PERF_NOTES.md / docs/PERFORMANCE.md are the sources). A (path, value)
absent from the table is neutral (factor 1.0) — the model must never
prune on a knob it has no opinion about.
"""

from __future__ import annotations

import dataclasses

from distributed_tensorflow_framework_tpu.core import roofline


@dataclasses.dataclass(frozen=True)
class Factors:
    """Multipliers on the four traffic components (1.0 = unchanged)."""

    flops: float = 1.0
    hbm: float = 1.0
    wire: float = 1.0
    opt: float = 1.0


@dataclasses.dataclass
class TrafficProfile:
    """The incumbent's measured per-step traffic (from its bench row)."""

    chip: str
    n_chips: int = 1
    flops_per_step: float = 0.0
    hbm_bytes_per_step: float = 0.0   # memory_analysis arg+out+temp
    wire_bytes_per_step: float = 0.0  # CollectiveTally total
    opt_state_bytes: float = 0.0      # bench opt_state_bytes_per_chip
    examples_per_step: float = 1.0


# (knob path, value) → Factors. Sources: the precision-pack A/B rows
# (docs/PERFORMANCE.md "Flipping the bound"), the EQuARX-style wire
# ratios (int8 ≈ 3.9x fewer wire bytes), and the ZeRO argument that
# sharded optimizer state divides its HBM traffic by the data-parallel
# width (applied via the ``opt`` component, resolved per-profile).
TRAFFIC_FACTORS: dict[str, dict[object, Factors]] = {
    "precision.activation_dtype": {
        # bf16 activations halve the activation stream; params/grads stay
        # f32, so the whole-step HBM byte count lands near 0.55x.
        "bf16": Factors(hbm=0.55),
    },
    "precision.fused_update": {
        # Fused AdamW update removes one full read+write pass over the
        # param tree (~10% of a ResNet step's bytes).
        True: Factors(hbm=0.90),
    },
    "precision.matmul_dtype": {
        # int8 MXU matmuls shrink the streamed operand bytes but add
        # quantize/dequantize flops.
        "int8": Factors(hbm=0.85, flops=1.05),
    },
    "parallel.collective_dtype": {
        "bfloat16": Factors(wire=0.5),
        "int8": Factors(wire=0.26),  # EQuARX-style ≈3.9x wire reduction
    },
    "optimizer.zero_sharding": {
        # Resolved against profile.n_chips in predict_candidate: each
        # chip keeps 1/n of the optimizer state.
        "shard_map": Factors(opt=0.0),  # sentinel; see _resolve_factors
    },
    "model.remat_policy": {
        # Full-replay remat trades ~30% more flops for not streaming
        # saved activations (PERF_NOTES round 2: 78.7→84.5 FLOP/byte,
        # net loss on an HBM-bound step — exactly what pruning catches).
        "full": Factors(flops=1.30, hbm=0.80),
    },
}


def _resolve_factors(path: str, value: object,
                     profile: TrafficProfile) -> Factors:
    table = TRAFFIC_FACTORS.get(path, {})
    f = table.get(value)
    if f is None:
        return Factors()
    if path == "optimizer.zero_sharding" and f.opt == 0.0:
        return Factors(flops=f.flops, hbm=f.hbm, wire=f.wire,
                       opt=1.0 / max(1, profile.n_chips))
    return f


def predict_candidate(profile: TrafficProfile,
                      overrides: dict[str, object],
                      baseline: dict[str, object]) -> roofline.RooflinePrediction:
    """Roofline step-time floor for a candidate's override dict, scaling
    the incumbent profile by each knob's factor RELATIVE to the baseline
    value of that knob (so the incumbent predicts onto itself exactly)."""
    flops = profile.flops_per_step
    hbm = profile.hbm_bytes_per_step
    wire = profile.wire_bytes_per_step
    opt = profile.opt_state_bytes
    for path, value in overrides.items():
        cand = _resolve_factors(path, value, profile)
        base = _resolve_factors(path, baseline.get(path), profile)
        flops *= cand.flops / base.flops
        hbm *= cand.hbm / base.hbm
        wire *= cand.wire / base.wire
        opt *= cand.opt / base.opt
    total_bytes = roofline.traffic_bytes(None, wire, opt) + hbm
    return roofline.predict(profile.chip, flops, total_bytes,
                            n_chips=profile.n_chips)


def prune_decision(profile: TrafficProfile, overrides: dict[str, object],
                   baseline: dict[str, object],
                   prune_margin: float) -> tuple[bool, str, dict]:
    """(skip, reason, detail) for one candidate.

    Predicted rate = examples_per_step / predicted step-time floor, for
    candidate and incumbent through the SAME model; skip when the
    candidate undershoots by more than ``prune_margin`` on the binding
    resource (the max() term inside roofline.predict IS the binding
    resource's time).
    """
    cand = predict_candidate(profile, overrides, baseline)
    incumbent = predict_candidate(profile, baseline, baseline)
    cand_rate = profile.examples_per_step / cand.sec_per_step \
        if cand.sec_per_step else 0.0
    inc_rate = profile.examples_per_step / incumbent.sec_per_step \
        if incumbent.sec_per_step else 0.0
    detail = {
        "predicted_rate": round(cand_rate, 2),
        "incumbent_rate": round(inc_rate, 2),
        "bound": cand.bound,
        "chip": cand.chip,
        "sec_compute": cand.sec_compute,
        "sec_hbm": cand.sec_hbm,
    }
    if inc_rate <= 0:
        return False, "no incumbent prediction — running", detail
    ratio = cand_rate / inc_rate
    detail["vs_incumbent"] = round(ratio, 4)
    if ratio < 1.0 - prune_margin:
        return True, (
            f"predicted {cand_rate:.1f} vs incumbent {inc_rate:.1f} "
            f"({(1 - ratio) * 100:.1f}% worse on {cand.bound}, margin "
            f"{prune_margin * 100:.0f}%) — pruned"), detail
    return False, (
        f"predicted {cand_rate:.1f} vs incumbent {inc_rate:.1f} "
        f"(within margin) — running"), detail
