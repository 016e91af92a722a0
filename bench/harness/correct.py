"""Decide ``correct``: hold what the timed path served to the plain
reference.

After the window has closed (and the program's state is freed) a sample of
the finished requests, drawn from the seed with the longest among them and
one of each admission program the window used, is run through the
reference once: prompt plus served tokens, causal, in float32.  Each served
token was chosen greedily from the program's logits plus the guide bias; the
number compared is the widest gap by which a served token's reference logit
(plus the same bias) lies below the reference's best at that position.
Sound bfloat16 serving differs from the reference only at near-ties, so the
gap stays small; a wrong token, a stale cache or a lower precision opens it.

The control reads, at each position of the same prompts and tokens, the gap
of the token that a lower precision of the reference puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from harness.e2e import Record
from harness.model import Guide, add_bias
from harness.traffic import stream_rng


def pick(recs: List[Record], n: int, seed: int) -> List[Record]:
    """Up to n finished requests: the longest, one of each admission kind,
    the rest drawn from the seed."""
    done = [r for r in recs if r.complete and r.n_tokens >= 1]
    if not done:
        return []
    chosen = [max(done, key=lambda r: r.prompt_len + r.n_tokens)]
    for kind in sorted({r.kind for r in done}):
        if not any(c.kind == kind for c in chosen):
            chosen.append(next(r for r in done if r.kind == kind))
    rest = [r for r in done if all(r is not c for c in chosen)]
    order = stream_rng(seed, 40).permutation(len(rest))
    chosen += [rest[i] for i in order[:max(0, n - len(chosen))]]
    return chosen


def gaps(ref_mod, shape: Dict, weights: Dict, rec: Record,
         guide: Optional[Guide], size: float, controls: Sequence[str] = ()
         ) -> Dict[str, np.ndarray]:
    """Per served token: its gap below the reference's best; for each
    precision in ``controls``, the gap of that control's own first
    choice."""
    seq = np.asarray(list(rec.prompt) + list(rec.tokens[:rec.max_new]),
                     np.int64)
    n_out = min(rec.n_tokens, rec.max_new)
    rows = np.arange(rec.prompt_len - 1, rec.prompt_len - 1 + n_out)
    # a token outside the vocabulary is as wrong as a token can be
    bad = (seq < 0) | (seq >= shape["vocab"])
    seq = np.where(bad, 0, seq).astype(np.int32)
    ref = add_bias(ref_mod.logits_at(shape, weights, seq, rows, "f32"),
                   guide, seq[rows], rows, size)
    best = ref.max(axis=1)
    served = seq[rows + 1]
    gap = best - ref[np.arange(len(rows)), served]
    out = {"served": np.where(bad[rows + 1], np.inf, gap)}
    for mode in controls:
        low = add_bias(ref_mod.logits_at(shape, weights, seq, rows, mode),
                       guide, seq[rows], rows, size)
        first = low.argmax(axis=1)
        out[mode] = best - ref[np.arange(len(rows)), first]
    return out


def check(ref_mod, shape: Dict, weights: Dict, recs: List[Record],
          guide: Optional[Guide], size: float, spec: Dict[str, Any],
          seed: int, controls: Sequence[str] = ()) -> Dict[str, Any]:
    """Compare the sampled requests; the result's ``checks`` hold each
    number with its limit."""
    sample = pick(recs, int(spec["requests"]), seed)
    served: List[float] = []
    ctl: Dict[str, List[float]] = {m: [] for m in controls}
    worst = None
    for rec in sample:
        g = gaps(ref_mod, shape, weights, rec, guide, size, controls)
        if len(g["served"]) and (worst is None
                                 or g["served"].max() > worst["gap"]):
            i = int(g["served"].argmax())
            worst = {"gap": float(g["served"][i]), "kind": rec.kind,
                     "prompt_len": rec.prompt_len, "cached": rec.cached,
                     "token": i,
                     "tokens": rec.n_tokens}
        served.extend(g["served"].tolist())
        for m in controls:
            ctl[m].extend(g[m].tolist())
    widest = float(max(served)) if served else float("inf")
    checks = {
        "max_logit_gap": {"value": widest,
                          "limit": float(spec["max_logit_gap"])},
        "tokens_checked": {"value": len(served),
                           "limit": int(spec["min_tokens"])},
    }
    ok = (widest <= checks["max_logit_gap"]["limit"]
          and len(served) >= checks["tokens_checked"]["limit"])
    out = {"correct": bool(ok), "checks": checks, "worst": worst,
           "requests_checked": len(sample),
           "kinds": sorted({r.kind for r in sample})}
    if controls:
        out["control_gap"] = {m: float(max(v)) if v else float("inf")
                              for m, v in ctl.items()}
    return out
