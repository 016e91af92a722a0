"""The one traffic generator.  A mix is a data file of parameters
(``bench/traffic/<name>.json``); this module turns it and a seed into
requests.

Prompt parts, each optional, in this order: a system prompt shared by every
request; a document picked by Zipf rank from a pool (a walk of the guide,
so answers that continue it can be drafted from the prompt); a question
(random tokens ending on a phase-aligned span of its document); for
sessions, the history and a user turn.  Loops: ``open`` (arrivals due on
a schedule whatever the server does) or ``closed`` (clients that send the
next request when the previous answer is complete).

With ``tenants`` (``{"n": 4, "shares": [...], "namespace": "tenant{i}"}``)
each request belongs to one tenant, drawn by the shares: every tenant has
its own system prompt and its own document pool, at the mix's sizes, and
its requests carry the tenant's namespace.  A mix without ``tenants``
draws what it drew before tenants existed.

Sizes are stratified: a run of N requests takes the N quantiles
(i + 0.5) / N of each length distribution, of the arrival gaps and of the
Zipf ranks, in an order that is the same for every seed (the schedule,
drawn from ``SCHEDULE``), and so are the documents.  Every seed so sends
the same documents, at the same sizes and times; the seed draws the
weights, the system prompt and the questions' words.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from harness.model import Guide, guide_walk

_NORMAL = NormalDist()
SCHEDULE = 0        # the seed of every mix's schedule, for every run


def stream_rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    seed = int(seed)
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32,
                                  *[int(s) for s in stream]])


def schedule_rng(*stream: int) -> np.random.Generator:
    """The generator of a schedule (the order of sizes, ranks and arrival
    gaps): the same whatever the run's seed."""
    return stream_rng(SCHEDULE, 100, *stream)


def quantiles(spec: Dict[str, Any], n: int, scale: float = 1.0
              ) -> np.ndarray:
    """The n stratified quantiles of a length distribution, as ints within
    [min, cap], in increasing order.

    ``{"dist": "lognormal", "mean": m, "sigma": s, "min": a, "cap": b}``
    (mean of the uncapped distribution), ``{"dist": "uniform", "min": a,
    "max": b}`` or ``{"dist": "fixed", "value": v}``; ``scale`` multiplies
    every size (CPU rehearsal)."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full((n,), float(spec["value"]))
        lo, hi = x[0], x[0]
    elif dist == "uniform":
        lo, hi = float(spec["min"]), float(spec["max"])
        x = lo + u * (hi - lo + 1) - 0.5
    elif dist == "lognormal":
        s = float(spec["sigma"])
        mu = math.log(float(spec["mean"])) - s * s / 2
        x = np.exp(mu + s * np.asarray([_NORMAL.inv_cdf(v) for v in u]))
        lo, hi = float(spec["min"]), float(spec["cap"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    x = np.clip(np.rint(x * scale), max(1, round(lo * scale)),
                max(1, round(hi * scale)))
    return x.astype(np.int64)


def zipf_ranks(pool: int, exponent: float, n: int) -> np.ndarray:
    """n stratified draws of a Zipf(exponent) rank over ``pool`` items."""
    w = 1.0 / np.arange(1, pool + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(w) / w.sum()
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u), pool - 1)


@dataclass
class Request:
    prompt: List[int]
    max_new: int
    due: float = 0.0              # seconds after the window opens (open loop)
    client: int = -1              # closed loop: which client sent it
    tag: str = ""
    namespace: str = ""           # the tenant's (the draft trie's scope)


@dataclass
class Limits:
    """What the cell's engine admits: prompts up to ``prefill_len``, and
    prompt + answer within ``max_seq_len`` less one tree width."""
    prefill_len: int
    max_seq_len: int
    width: int

    def fits(self, prompt_len: int, max_new: int) -> bool:
        return (prompt_len <= self.prefill_len and prompt_len + max_new
                <= self.max_seq_len - self.width + 1)


class Mix:
    """A traffic mix bound to a seed, a vocabulary and a guide."""

    def __init__(self, traffic: Dict[str, Any], seed: int, vocab: int,
                 guide: Optional[Guide], limits: Limits,
                 scale: float = 1.0):
        self.t = traffic
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.guide = guide
        self.limits = limits
        self.scale = float(scale)
        n_sys = round(int(traffic.get("system_prompt", 0)) * scale)
        self.tenants = traffic.get("tenants")
        # tenant t's streams: those of a mix without tenants, plus 1 + t
        nt = int(self.tenants["n"]) if self.tenants else 1
        self._tid = [(1 + t,) if self.tenants else () for t in range(nt)]
        self.namespaces = ([str(self.tenants["namespace"]).format(i=t)
                            for t in range(nt)] if self.tenants else [""])
        self.systems = [stream_rng(seed, 1, *tid).integers(
            2, vocab, size=(n_sys,)).tolist() for tid in self._tid]
        self.system = self.systems[0]
        self._docs: Dict[tuple, List[int]] = {}
        docs = traffic.get("documents")
        if docs:
            pool = int(docs["pool"])
            # length by popularity rank: the same for every seed and tenant
            lens = quantiles(docs["length"], pool, scale)
            self._doc_len = np.random.default_rng(0).permutation(lens)
            # which document holds each rank: part of the schedule
            self._doc_of_rank = [schedule_rng(2, *tid).permutation(pool)
                                 for tid in self._tid]

    # ----------------------------------------------------------- parts
    def _rand(self, rng: np.random.Generator, n: int) -> List[int]:
        return rng.integers(2, self.vocab, size=(int(n),)).tolist()

    def document(self, rank: int, tenant: int = 0) -> List[int]:
        """Tenant ``tenant``'s document of popularity ``rank`` (a guide walk
        that starts right after the system prompt)."""
        tid = self._tid[tenant]
        doc_id = int(self._doc_of_rank[tenant][rank])
        doc = self._docs.get((tenant, doc_id))
        if doc is None:
            n = int(self._doc_len[rank])
            start = int(schedule_rng(3, doc_id, *tid).integers(2, self.vocab))
            if self.guide is not None:
                doc = guide_walk(self.guide, start, len(self.system),
                                 n).tolist()
            else:
                doc = self._rand(schedule_rng(3, doc_id, *tid), n)
            self._docs[(tenant, doc_id)] = doc
        return doc

    def tenant_of(self, n: int, *stream: int) -> np.ndarray:
        """Each of n requests' tenant: the shares' stratified counts (largest
        remainders), in an order that is part of the schedule."""
        if not self.tenants:
            return np.zeros((n,), np.int64)
        w = np.asarray(self.tenants["shares"], np.float64)
        exact = w / w.sum() * n
        counts = np.floor(exact).astype(np.int64)
        rest = np.argsort(-(exact - counts), kind="stable")
        counts[rest[:n - int(counts.sum())]] += 1
        return schedule_rng(*stream, 7).permutation(
            np.repeat(np.arange(len(w)), counts))

    def question(self, rng: np.random.Generator, doc: List[int], n: int,
                 pos0: int, where: float) -> List[int]:
        """n tokens at absolute position ``pos0``: random words, then the
        span of ``doc`` that starts ``where`` (a fraction) of the way into
        it, shifted so the guide's rules there are the document's own; the
        answer then continues the document."""
        q = self.t["question"]
        span = min(max(1, round(int(q.get("span", 0)) * self.scale)), n,
                   len(doc) - 1)
        if span <= 0 or self.guide is None:
            return self._rand(rng, n)
        period = self.guide.period
        base = len(self.system)
        s = int(where * (len(doc) - span))
        # the span's last token must sit where the guide's rules (phase and
        # free positions) are those of its place in the document
        shift = (base + s + span - 1 - (pos0 + n - 1)) % period
        s -= shift
        if s < 0:
            s += period
        if s + span >= len(doc):
            return self._rand(rng, n)
        return self._rand(rng, n - span) + doc[s:s + span]

    # ----------------------------------------------------------- loops
    def open_loop(self, rate: float, seconds: float, stream: int = 0
                  ) -> List[Request]:
        """round(rate * seconds) requests due in [0, seconds): Poisson
        gaps (stratified), documents by Zipf rank, questions and answers
        with lengths from the file."""
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = schedule_rng(10, stream, 1).permutation(gaps)
        gaps *= seconds / gaps.sum()
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return [dataclasses.replace(r, due=float(d))
                for r, d in zip(self.requests(n, 10, stream), due)]

    def requests(self, n: int, *stream: int) -> List[Request]:
        """n single-turn requests (documents and questions, or plain
        prompts), sizes stratified over the n; ``stream`` names this batch
        of requests, its schedule and its tokens."""
        t = self.t
        rng = stream_rng(self.seed, *stream)
        order = schedule_rng(*stream)
        out_len = order.permutation(quantiles(t["output"]["length"], n,
                                              self.scale))
        docs = t.get("documents")
        ranks = (order.permutation(zipf_ranks(int(docs["pool"]),
                                              float(docs["zipf"]), n))
                 if docs else None)
        q_len = (order.permutation(quantiles(t["question"]["length"], n,
                                             self.scale))
                 if "question" in t else None)
        # where in its document each question's span sits: stratified too,
        # so every seed leaves answers the same amount of document to copy
        where = order.permutation((np.arange(n) + 0.5) / n)
        p_len = (order.permutation(quantiles(t["prompt"]["length"], n,
                                             self.scale))
                 if "prompt" in t else None)
        tenant = self.tenant_of(n, *stream)
        reqs = []
        for i in range(n):
            ti = int(tenant[i])
            prompt = list(self.systems[ti])
            tag = ""
            if ranks is not None:
                doc = self.document(int(ranks[i]), ti)
                prompt += doc
                tag = f"rank{int(ranks[i])}"
            if q_len is not None:
                prompt += self.question(rng, doc, int(q_len[i]), len(prompt),
                                        float(where[i]))
            if p_len is not None:
                prompt += self._rand(rng, int(p_len[i]))
            max_new = int(out_len[i])
            cut = self.limits.max_seq_len - self.limits.width + 1
            prompt = prompt[:self.limits.prefill_len]
            max_new = max(1, min(max_new, cut - len(prompt)))
            reqs.append(Request(prompt=prompt, max_new=max_new, tag=tag,
                                namespace=self.namespaces[ti]))
        return reqs

    def clients(self, n_clients: int, stream: int = 0) -> "ClosedLoop":
        return ClosedLoop(self, n_clients, stream)


@dataclass
class _Session:
    history: List[int]
    turn: int = 0


class ClosedLoop:
    """``n_clients`` clients, each sending its next request as soon as its
    previous answer is complete.  With ``sessions`` in the mix each client
    is a user holding multi-turn sessions (each turn's prompt is the whole
    conversation so far); otherwise clients draw single requests from one
    stratified list."""

    def __init__(self, mix: Mix, n_clients: int, stream: int):
        self.mix = mix
        self.n = int(n_clients)
        self.stream = int(stream)
        self.sessions = mix.t.get("sessions")
        self._rng = stream_rng(mix.seed, 20, stream)
        self._batch: List[Request] = []
        self._next = 0
        self._blocks = 0
        if self.sessions:
            self._users = [_Session(history=list(mix.system))
                           for _ in range(self.n)]
            self._turn_plan = self._plan_turns()

    # ----------------------------------------------------- single requests
    def _draw(self) -> Request:
        if self._next >= len(self._batch):
            # a fresh stratified block of requests, 16 per client
            self._batch = self.mix.requests(16 * self.n, 20, self.stream,
                                            self._blocks)
            self._blocks += 1
            self._next = 0
        r = self._batch[self._next]
        self._next += 1
        return r

    # ----------------------------------------------------------- sessions
    def _plan_turns(self) -> Iterator:
        """Endless (user turn length, answer length) pairs, stratified in
        blocks of 64 * clients."""
        s = self.sessions
        order = schedule_rng(21, self.stream)
        while True:
            n = 64 * self.n
            u = order.permutation(quantiles(s["user"]["length"], n,
                                            self.mix.scale))
            a = order.permutation(quantiles(
                self.mix.t["output"]["length"], n, self.mix.scale))
            yield from zip(u.tolist(), a.tolist())

    def next(self, client: int, answer: Optional[List[int]]) -> Request:
        """The request client ``client`` sends once ``answer`` (its previous
        request's output, None at the start) is complete."""
        if not self.sessions:
            return dataclasses.replace(self._draw(), client=client)
        st = self._users[client]
        s = self.sessions
        lim = self.mix.limits
        cap = int(s.get("context_cap", lim.max_seq_len))
        if answer is not None:
            st.history = st.history + list(answer)
        while True:
            user_len, ans_len = next(self._turn_plan)
            user = self.mix._rand(self._rng, user_len)
            prompt = st.history + user
            if (st.turn < int(s["turns"]) and lim.fits(len(prompt), ans_len)
                    and len(prompt) + ans_len <= cap):
                st.history = prompt
                st.turn += 1
                return Request(prompt=list(prompt), max_new=int(ans_len),
                               client=client, tag=f"turn{st.turn}")
            if st.turn == 0:
                raise ValueError("a first turn does not fit the cell's "
                                 "context; shorten the system prompt")
            # the session is over: the user starts a new one
            st.history = list(self.mix.system)
            st.turn = 0
