"""Operations and bytes the work requires, from the configuration's shapes.

These count what the algorithm needs, whatever implements it: only the live
rows of a step, keys up to each row's true context, K and V at the model's
true head size.  Padded slots, idle lanes, padded prompt rows and padded
head dimensions count nothing, so a share of a peak computed from them is
the share of useful work.  One multiply-add is 2 operations.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2   # bytes per element of the served dtype


def linear_params(s: Dict) -> int:
    """Matrix parameters one token multiplies per layer stack (attention
    projections and the SwiGLU MLP; no embedding gather, no head)."""
    d, H, K, dh, F = (s["d_model"], s["heads"], s["kv_heads"],
                      s["head_dim"], s["d_ff"])
    per_layer = d * H * dh + 2 * d * K * dh + H * dh * d + 3 * d * F
    return s["layers"] * per_layer


def head_params(s: Dict) -> int:
    return s["d_model"] * s["vocab"]


def attn_flops(s: Dict, keys: int) -> float:
    """QK^T and PV over ``keys`` (query row, key) pairs, every layer."""
    return 4.0 * s["layers"] * s["heads"] * s["head_dim"] * keys


def kv_bytes(s: Dict, rows: int) -> float:
    """K and V of ``rows`` cache rows, every layer, at the true head size."""
    return 2.0 * s["layers"] * s["kv_heads"] * s["head_dim"] * rows * BF16


def tree_rows(cache_len: int, ancestors: Iterable[int]) -> Tuple[int, int]:
    """(rows, keys) of one lane's live tree slots: each slot attends the
    committed cache plus its own ancestors (itself included)."""
    anc = list(ancestors)
    return len(anc), sum(cache_len + a for a in anc)


def step_work(s: Dict, lanes: Iterable[Tuple[int, Iterable[int]]]
              ) -> Dict[str, float]:
    """One fused tree-verify step.  ``lanes`` holds, per live lane, its
    cache length and the ancestor count of each live slot.

    Returns the step's FLOPs (projections, MLP, head for every live row,
    attention) and the tree-attention kernel's required FLOPs and bytes
    (its K/V up to each lane's length plus the step's new rows, its queries
    and outputs)."""
    rows = keys = kv_rows = 0
    for cache_len, anc in lanes:
        r, k = tree_rows(cache_len, anc)
        rows += r
        keys += k
        kv_rows += cache_len + r
    af = attn_flops(s, keys)
    qo = 2.0 * s["layers"] * s["heads"] * s["head_dim"] * rows * BF16
    return {"flops": 2.0 * (linear_params(s) + head_params(s)) * rows + af,
            "attn_flops": af, "attn_bytes": kv_bytes(s, kv_rows) + qo,
            "rows": rows}


def prefill_work(s: Dict, n: int, offset: int = 0) -> Dict[str, float]:
    """Prefill of n true prompt tokens after ``offset`` cached ones: the
    projections and MLP of n rows, the head of the last row, causal
    attention; the attention kernel's required FLOPs and bytes (queries,
    outputs and the K/V it reads)."""
    keys = n * offset + n * (n + 1) // 2
    af = attn_flops(s, keys)
    qo = 2.0 * s["layers"] * s["heads"] * s["head_dim"] * n * BF16
    return {"flops": 2.0 * linear_params(s) * n + 2.0 * head_params(s)
            + af, "attn_flops": af,
            "attn_bytes": kv_bytes(s, offset + n) + qo, "rows": n}


def roofline_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"])
