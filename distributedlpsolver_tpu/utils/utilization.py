"""Utilization folding for phase reports (review round 3 item 4).

`core.drive_phase_plan` records one ``{"phase", "iters", "wall_s"}`` row
per phase; the backends stamp each row's ``"mode"`` from their own plan
specs ("f32"/"mixed"/"f64"/"f64c"/"pcg"/"endgame"). This helper turns
that into the utilization fields the scale artifacts record: effective
FLOP/s per assembly-bound phase, its percentage of the watchdog seed
rates (`core.SEG_RATE_F32`/`SEG_RATE_F64` — the conservative per-dtype
device rates every backend already budgets segments with), and — the
honest number (review round 4 item 9) — its percentage of the CHIP's
peak for that arithmetic class. The two percentages answer different
questions: ``pct_of_seed_rate`` is budget-relative (is the phase running
at the rate its watchdog segments were sized for?), while
``pct_of_chip_peak`` is roofline-relative (how much of the silicon does
this phase actually use?). A healthy f32 phase can read ~50% of seed
while using low-single-digit percent of the MXU — both are reported so
neither can be mistaken for the other. PCG and endgame phases get no
rate: their per-iteration flops are data-dependent (CG sweep counts;
endgame host/device split), so a single flops-per-iteration figure would
be fiction — their rows still carry the measured iters/wall split.
"""

from __future__ import annotations

# Chip peaks for the utilization denominator, keyed by the device's
# ``device_kind``. A kind missing from the table gets NO
# ``pct_of_chip_peak`` — a guessed denominator would be a made-up number.
#
# "TPU v5 lite" (v5e): 197 TFLOP/s bf16 MXU, 819 GB/s HBM (Google Cloud
# documentation, "TPU v5e"). f32 matmuls at HIGHEST precision run as
# multi-pass bf16 (~1/4 of the bf16 rate → ~49 TFLOP/s usable f32).
# Emulated f64 has no hardware peak; its ceiling is the MXU-split GEMM
# rate measured on this chip (~1.8e11 FLOP/s, scripts/probe_chol_mxu.py),
# so "100%" for f64 phases means "at the software-f64 GEMM ceiling".
CHIP_PEAKS = {
    "TPU v5 lite": {"f32": 4.9e13, "f64_sw": 1.8e11},
}


def fold_utilization(report, flops_per_iter: float, device_kind=None):
    """Annotate ``report`` rows (in place) with ``eff_flops_per_s``,
    ``pct_of_seed_rate``, and — when ``device_kind`` (default: the first
    JAX device's) has a row in :data:`CHIP_PEAKS` — ``pct_of_chip_peak``;
    returns the list.

    ``flops_per_iter`` is the backend's own per-iteration estimate for
    the direct factorization path (e.g. ``BlockAngularBackend._f64_flops``)
    — the same operation count runs in f32 and f64, only the rates
    differ.
    """
    from distributedlpsolver_tpu.ipm import core

    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    peaks = CHIP_PEAKS.get(device_kind)
    rates = {
        "f32": (core.SEG_RATE_F32, "f32"),
        "mixed": (core.SEG_RATE_F32, "f32"),
        "f64": (core.SEG_RATE_F64, "f64_sw"),
        "f64c": (core.SEG_RATE_F64, "f64_sw"),
    }
    for ph in report:
        pair = rates.get(ph.get("mode"))
        if pair and ph.get("iters") and ph.get("wall_s", 0) > 0:
            seed, peak_key = pair
            eff = flops_per_iter * ph["iters"] / ph["wall_s"]
            ph["eff_flops_per_s"] = f"{eff:.3g}"
            ph["pct_of_seed_rate"] = round(100.0 * eff / seed, 1)
            if peaks is not None:
                ph["pct_of_chip_peak"] = round(
                    100.0 * eff / peaks[peak_key], 2
                )
                if peak_key == "f64_sw":
                    ph["chip_peak_basis"] = "software-f64 GEMM ceiling"
    return report
