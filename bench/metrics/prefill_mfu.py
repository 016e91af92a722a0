"""FLOPs of the admitted prompts' true (unpadded) tokens over the device
time of the admission prefill programs, times the bf16 peak."""

PROGRAMS = {"prefill": "jit__prefill",
            "prefill_into_slot": "jit__prefill_into_slot",
            "prefill_suffix": "jit__prefill_suffix"}


def read(run):
    if not run.probe:
        return None
    flops = sum(run.probe.work.get(p, {}).get("flops", 0.0) for p in PROGRAMS)
    t = run.device_seconds(PROGRAMS.values())
    if flops <= 0 or t <= 0:
        return None
    return 100.0 * flops / (t * run.peaks["bf16_flops"])
