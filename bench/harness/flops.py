"""Helpers every family's work functions share, and the roofline.

A family's ``step_work`` and ``prefill_work`` (``bench/families/``) count
what the algorithm needs, whatever implements it: padded slots, idle lanes,
padded prompt rows and padded head dimensions count nothing, so a share of
a peak computed from them is the share of useful work.  They return
``flops`` for the whole call and, for each kernel ``<k>`` they count, a
``<k>_flops`` and ``<k>_bytes`` pair; ``rooflines`` turns every such pair
into ``<k>_roof_s``.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2   # bytes per element of the served dtype


def tree_rows(cache_len: int, ancestors: Iterable[int]) -> Tuple[int, int]:
    """(rows, keys) of one lane's live tree slots: each slot attends the
    committed cache plus its own ancestors (itself included)."""
    anc = list(ancestors)
    return len(anc), sum(cache_len + a for a in anc)


def roofline_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])


def rooflines(work: Dict[str, float], peaks: Dict) -> Dict[str, float]:
    """``<k>_roof_s`` for every ``<k>_flops`` / ``<k>_bytes`` pair in a
    call's work (the whole call's ``flops`` is no kernel's)."""
    out = {}
    for key, f in work.items():
        k = key[:-len("_flops")]
        if key.endswith("_flops") and k and f"{k}_bytes" in work:
            out[f"{k}_roof_s"] = roofline_seconds(f, work[f"{k}_bytes"],
                                                  peaks)
    return out
