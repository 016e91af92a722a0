"""Host time building draft trees, per decode step (the scheduler's own
host-clock span, over the window)."""


def read(run):
    c = run.counters
    if c["decode_steps"] <= 0:
        return None
    return c["host_draft_ms"] / c["decode_steps"]
