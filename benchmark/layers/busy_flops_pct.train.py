"""How near the kernels are to the chip's peak WHILE the device is busy:
the operations the model needs for the traced steps (from its shapes, the
configuration's own function) over busy seconds x the published bf16 peak.
Model FLOP/s utilisation = (100 - device_idle_pct) x this / 100."""
from lib import peaks


def read(bench):
    trace, flops = bench.trace_summary, bench.outcome.get("traced_flops")
    if not trace or not flops:
        return None
    peak = peaks.peak(bench.device_kind, "bf16_flops")
    return 100.0 * flops / (trace["busy_s"] * trace["chips"] * peak)
