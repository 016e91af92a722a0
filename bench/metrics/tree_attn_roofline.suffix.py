"""Paged tree-attention kernel in the prefix-hit suffix prefill: the least
time its required FLOPs and bytes take at the chip's peaks (the true
suffix tokens, causal, over the cached prefix) over the kernel's device
time in that program."""

MODULES = ("jit__prefill_suffix",)
KERNEL = (r"^_paged_tree_attention$",)


def read(run):
    w = run.probe.work.get("prefill_suffix") if run.probe else None
    if not w or run.trace is None:
        return None
    t = run.trace.kernel_time(MODULES, KERNEL)
    if t <= 0:
        return None
    return 100.0 * w["attn_roof_s"] / t
