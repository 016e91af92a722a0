"""Share of the prompt tokens presented to the prefix cache that it served
(scheduler counters over the window)."""


def read(run):
    c = run.counters
    if c["prefix_prompt_tokens"] <= 0:
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prefix_prompt_tokens"]
