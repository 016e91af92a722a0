"""Paged tree-attention kernel in the fused decode step: the least time
its required FLOPs and bytes take at the chip's peaks (K/V up to each
lane's length at the true head size, the live rows' queries and outputs)
over the kernel's device time in the trace."""

MODULES = ("jit__fused_step",)
KERNEL = (r"^_paged_tree_attention$",)


def read(run):
    w = run.probe.work.get("fused_step") if run.probe else None
    if not w or run.trace is None:
        return None
    t = run.trace.kernel_time(MODULES, KERNEL)
    if t <= 0:
        return None
    return 100.0 * w["attn_roof_s"] / t
