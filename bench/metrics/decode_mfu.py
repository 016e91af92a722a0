"""The fused tree-verify step's FLOPs (live rows only, at their true
context) over its device time from the trace times the bf16 peak."""

MODULES = ("jit__fused_step",)


def read(run):
    w = run.probe.work.get("fused_step") if run.probe else None
    t = run.device_seconds(MODULES)
    if not w or t <= 0:
        return None
    return 100.0 * w["flops"] / (t * run.peaks["bf16_flops"])
