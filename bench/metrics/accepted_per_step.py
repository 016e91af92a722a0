"""Output tokens emitted by decode steps over active lane-steps: how many
tokens one lane gains from one verified tree (1.0 is plain decoding)."""


def read(run):
    c = run.counters
    if c["active_lane_steps"] <= 0:
        return None
    return run.decode_tokens / c["active_lane_steps"]
