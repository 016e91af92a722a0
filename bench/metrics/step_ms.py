"""Dispatch to pull of the fused step, per decode step (the scheduler's own
host-clock span, over the window; host work drained in flight excluded)."""


def read(run):
    c = run.counters
    if c["decode_steps"] <= 0:
        return None
    return c["device_step_ms"] / c["decode_steps"]
