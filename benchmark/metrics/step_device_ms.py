"""Fold: device milliseconds per run of the fused step program (XLA
modules of ``MultiAggregator._step`` / ``_step_pre``) in the traced
window; on several chips, the busiest device."""


def read(run):
    t = run.trace
    if not t or not t.get("steps"):
        return None
    return 1e3 * t["step_device_s"] / t["steps"]
