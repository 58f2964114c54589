"""Roofline model: chip peaks, ridge points, and the step-time predictor.

Factored out of bench.py so the bound verdict and the analytic step-time
lower bound are one implementation shared by the bench (annotating
measured rows) and the autotuner (pruning candidate configs BEFORE
spending a chip run — tools/autotune). Stdlib-only: nothing here touches
jax, so the CPU-side tuner harness can import it without a backend.

The model is the classic two-resource roofline. A step that must move
``B`` bytes through HBM and execute ``F`` flops cannot finish faster
than ``max(F / peak_flops, B / hbm_bw)`` per chip; whichever term is
larger is the *binding resource* ("compute" vs "hbm_bandwidth"), and the
crossover sits at the ridge point ``peak_flops / hbm_bw`` (v5e:
197e12 / 819e9 ≈ 240 FLOP/byte — PERF_NOTES.md round 2 measured the
ResNet-50 step at 78.7 FLOP/byte, firmly HBM-bound).

Traffic inputs come from the artifacts the repo already measures
(docs/PERFORMANCE.md "The bench as the measurement instrument"):
the compiled step's ``memory_analysis`` footprint (argument + output +
temp bytes), the CollectiveTally's wire bytes, and
``opt_state_bytes_per_chip`` — see :func:`traffic_bytes`.
"""

from __future__ import annotations

import dataclasses

GIB = 1024 ** 3

# device_kind → (peak bf16 FLOP/s, HBM bytes/s, HBM capacity bytes/chip).
# Public spec-sheet numbers.
CHIP_PEAKS: dict[str, tuple[float, float, float]] = {
    "TPU v2": (45e12, 700e9, 8 * GIB),
    "TPU v3": (123e12, 900e9, 16 * GIB),
    "TPU v4": (275e12, 1228e9, 32 * GIB),
    "TPU v5 lite": (197e12, 819e9, 16 * GIB),   # v5e
    "TPU v5e": (197e12, 819e9, 16 * GIB),
    "TPU v5p": (459e12, 2765e9, 95 * GIB),
    "TPU v6 lite": (918e12, 1640e9, 32 * GIB),  # v6e / Trillium
    "TPU v6e": (918e12, 1640e9, 32 * GIB),
}


def chip_hbm_capacity(chip: str) -> float | None:
    """Per-chip HBM capacity, or None for a device that is not in
    CHIP_PEAKS (host RAM is not HBM)."""
    peak = CHIP_PEAKS.get(chip)
    return peak[2] if peak else None


def traffic_bytes(memory_analysis: dict | None, wire_bytes: float = 0.0,
                  opt_state_bytes: float = 0.0) -> float:
    """HBM + interconnect bytes/step from the measured artifacts.

    ``memory_analysis`` is the compiled step's cost breakdown
    (core/memstats.compiled_memory_analysis): argument + output + temp
    bytes is the compiled footprint one execution streams. ``wire_bytes``
    is the CollectiveTally grand total for the step. ``opt_state_bytes``
    (bench's opt_state_bytes_per_chip) covers callers whose footprint was
    taken on a forward/backward program only — a compiled WHOLE step
    already carries the optimizer state in its argument bytes, so pass 0
    there or the state is counted twice.
    """
    analysis = memory_analysis or {}
    footprint = sum(int(analysis.get(f) or 0) for f in
                    ("argument_bytes", "output_bytes", "temp_bytes"))
    return float(footprint) + float(wire_bytes) + float(opt_state_bytes)


@dataclasses.dataclass
class RooflinePrediction:
    """Analytic step-time lower bound and the binding-resource verdict.

    ``sec_per_step`` is ``max(sec_compute, sec_hbm)`` — the roofline
    says the step can't beat the slower resource. ``bound`` names that
    resource, judged against ``chip``'s own ridge.
    """

    chip: str
    flops_per_step: float
    bytes_per_step: float
    intensity: float | None
    ridge: float
    sec_compute: float
    sec_hbm: float
    sec_per_step: float
    bound: str


def predict(chip: str, flops_per_step: float, bytes_per_step: float,
            n_chips: int = 1) -> RooflinePrediction:
    """Predict the per-step time floor for a program on ``chip``.

    Inputs are WHOLE-program flops and bytes (use :func:`traffic_bytes`
    to assemble bytes from footprint + wire + opt state); the work is
    assumed evenly divided across ``n_chips``. A chip that is not in
    CHIP_PEAKS raises: there is no roofline to predict against.
    """
    n = max(1, int(n_chips))
    if chip not in CHIP_PEAKS:
        raise ValueError(
            f"no roofline for device {chip!r}: not in CHIP_PEAKS "
            f"({sorted(CHIP_PEAKS)})")
    peak_flops, hbm_bw = CHIP_PEAKS[chip][:2]
    ridge = peak_flops / hbm_bw
    sec_compute = flops_per_step / n / peak_flops
    sec_hbm = bytes_per_step / n / hbm_bw
    intensity = (flops_per_step / bytes_per_step) if bytes_per_step else None
    if intensity is not None:
        bound = "hbm_bandwidth" if intensity < ridge else "compute"
    else:
        bound = "compute"
    return RooflinePrediction(
        chip=chip, flops_per_step=float(flops_per_step),
        bytes_per_step=float(bytes_per_step), intensity=intensity,
        ridge=ridge, sec_compute=sec_compute,
        sec_hbm=sec_hbm, sec_per_step=max(sec_compute, sec_hbm),
        bound=bound)


def annotate_roofline(out: dict, result: dict, chip: str, n_chips: int,
                      *, accum_scaled: bool = False) -> None:
    """Achieved TFLOP/s, MFU, arithmetic intensity and the bottleneck
    verdict from the XLA cost model + public chip peaks (the bench row
    annotator, moved here from bench.py so the tuner's predictor and the
    bench's measured verdict share one ridge). A device that is not in
    CHIP_PEAKS gets the program's own numbers (TFLOP/s, intensities) and
    no ``mfu`` / ``bound`` / ``hbm_bw_util``: those are statements about
    a chip, and there is none to make them about.

    Two intensity numbers ride every row that can compute them:
    ``arith_intensity`` (cost-model flops / cost-model bytes accessed —
    counts every HBM touch, fusion-aware) and ``ai_flops_per_byte``
    (cost-model flops / (memory_analysis arg+out+temp footprint + the
    CollectiveTally's wire bytes)). The second is the one the precision
    levers move: activation-width and fused-update changes shrink the
    compiled footprint and the wire, so the ratio climbing toward the
    ridge is the "flipping the bound" claim in one column
    (docs/PERFORMANCE.md).

    ``accum_scaled``: the flops/bytes were multiplied by the accum trip
    count (bench_bert) and the once-per-step optimizer traffic got scaled
    with them, so hbm_bw_util is an UPPER bound and arith_intensity a
    LOWER bound. Tag the output so accum and non-accum artifacts are not
    read as directly comparable roofline positions.
    """
    peak = CHIP_PEAKS.get(chip)
    if not result["flops_per_step"]:
        return
    if accum_scaled:
        out["roofline_bound"] = "accum-scaled-upper"
    achieved = result["flops_per_step"] / result["sec_per_step"] / n_chips
    out["tflops_per_sec"] = round(achieved / 1e12, 2)
    intensity = None
    if result["bytes_per_step"]:
        intensity = result["flops_per_step"] / result["bytes_per_step"]
        out["arith_intensity"] = round(intensity, 1)
    wire = (result.get("collectives") or {}).get("total_bytes") or 0
    ai = None
    footprint_plus_wire = traffic_bytes(
        (result.get("memory") or {}).get("analysis"), wire)
    if footprint_plus_wire > wire:  # a footprint was actually present
        ai = result["flops_per_step"] / footprint_plus_wire
        out["ai_flops_per_byte"] = round(ai, 1)
    if peak:
        peak_flops, hbm_bw = peak[:2]
        out["mfu"] = round(achieved / peak_flops, 4)
        if intensity is not None:
            ridge = peak_flops / hbm_bw
            out["bound"] = "hbm_bandwidth" if intensity < ridge else "compute"
            # Fraction of peak HBM bandwidth actually sustained.
            out["hbm_bw_util"] = round(
                result["bytes_per_step"] / result["sec_per_step"]
                / n_chips / hbm_bw, 4,
            )
