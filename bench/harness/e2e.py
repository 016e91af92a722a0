"""End-to-end arithmetic over per-request timestamps (host clock, seconds).

Every percentile is over all requests of the window; nothing is averaged
over chunks.  Latency is timed from when a request was *due* (open loop:
its scheduled arrival; closed loop: the moment its client's previous answer
was complete), so a stalled server pays for the requests queued behind the
stall, and the generator's own lateness is reported beside it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Record:
    """One request as its client saw it."""
    due: float                      # when it was due (host clock)
    max_new: int
    prompt_len: int = 0
    submit: Optional[float] = None  # when the generator handed it over
    first: Optional[float] = None   # first token seen
    last: Optional[float] = None    # last token seen
    n_tokens: int = 0
    deltas: List[tuple] = field(default_factory=list)   # (time, n tokens)
    done: bool = False
    tag: str = ""
    prompt: List[int] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    client: int = -1
    kind: str = ""                  # the admission program that served it
    cached: int = 0                 # prompt tokens the prefix cache served
    handle: object = None

    @property
    def complete(self) -> bool:
        return self.n_tokens >= self.max_new


def percentile(xs, q: float) -> float:
    """numpy's linear-interpolation percentile; NaN for no samples."""
    xs = np.asarray(list(xs), np.float64)
    return float(np.percentile(xs, q)) if xs.size else float("nan")


def ttft_ms(recs: List[Record]) -> List[float]:
    """Due time to first token, per request that got one."""
    return [(r.first - r.due) * 1e3 for r in recs if r.first is not None]


def tpot_ms(recs: List[Record]) -> List[float]:
    """(last token - first token) / (output tokens - 1), per request with
    two or more tokens: the decode speed each user felt."""
    return [(r.last - r.first) * 1e3 / (r.n_tokens - 1) for r in recs
            if r.first is not None and r.n_tokens >= 2]


def tokens_between(recs: List[Record], t0: float, t1: float) -> int:
    """Output tokens that reached their clients in [t0, t1)."""
    return sum(n for r in recs for (t, n) in r.deltas if t0 <= t < t1)


def lateness_ms(recs: List[Record]) -> Dict[str, float]:
    """How late the generator handed requests over, against their due
    times: a starved generator is not a fast server."""
    late = [(r.submit - r.due) * 1e3 for r in recs if r.submit is not None]
    return {"p50": percentile(late, 50), "p99": percentile(late, 99),
            "max": max(late) if late else float("nan"), "n": len(late)}


def summarize(recs: List[Record], t0: float, t1: float
              ) -> Dict[str, float]:
    """The end-to-end numbers of one window [t0, t1)."""
    tt, tp = ttft_ms(recs), tpot_ms(recs)
    return {
        "ttft_p50_ms": percentile(tt, 50),
        "ttft_p95_ms": percentile(tt, 95),
        "tpot_p50_ms": percentile(tp, 50),
        "tpot_p95_ms": percentile(tp, 95),
        "output_tok_s": tokens_between(recs, t0, t1) / (t1 - t0),
        "requests": len(recs),
        "ttft_samples": len(tt),
        "tpot_samples": len(tp),
    }
