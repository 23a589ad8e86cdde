"""The whole step's share of the chip's bf16 peak: the operations the steps
need (``bench/flops.py``; no recomputation, causal half of attention) over
the step phase's time by the host's clock."""
import peaks


def read(rec: dict):
    f = rec.get("flops_per_step")
    if not f or rec.get("step_phase_s", 0) <= 0:
        return None
    peak = peaks.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return 100.0 * f * rec["steps"] / (rec["step_phase_s"] * peak)
