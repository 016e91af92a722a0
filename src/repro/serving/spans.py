"""Host spans of the serving loop: an in-memory log, off by default.

``ContinuousScheduler`` owns one ``SpanLog``.  While it is enabled, each
span is recorded with its name, start and end (``time.perf_counter_ns``,
the clock of every other host timing in the loop), its id, its parent's id
and, for a request's spans, the request's rid.  Each span is also exported,
live, as a ``jax.profiler.TraceAnnotation`` of the same name: a running
profiler stamps that copy on the device trace's own clock, so a device idle
gap can be put against the span the host was in.  While disabled no span
object and no annotation is built; a span site costs one attribute check.

Spans, all under ``serve.``:

  request spans (rid set, no parent)
    queue    submit -> admission picked
    admit    admission picked -> its prefill dispatched (block claim,
             prefix lookup, prompt into the draft sources, table sync,
             copy-on-write block copy)
    prefill  prefill dispatched -> first token emitted
  step spans
    step     one decode iteration; ``attr`` holds the riders' rids
    draft / settle / pack / dispatch / retire / pull / accept
             its consecutive phases (parent: the step)
  compile    one XLA backend compile; ``attr`` names the program.  In
             memory only: JAX reports a compile once it has ended.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STEP_PHASES = ("draft", "settle", "pack", "dispatch", "retire", "pull",
               "accept")


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int = 0        # 0: no parent
    rid: int = -1          # -1: not a request's span
    attr: Any = None

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-6


class OpenSpan:
    """A span that has started; ``close`` records it.  Its annotation
    starts when it is opened, so a span opened with an earlier
    ``start_ns`` (an admission stamped before its block claim) starts
    earlier in the log than in the profiler's copy."""

    __slots__ = ("log", "name", "start_ns", "id", "parent", "rid", "_ann")

    def __init__(self, log: "SpanLog", name: str, start_ns: int,
                 parent: int, rid: int):
        self.log, self.name, self.start_ns = log, name, start_ns
        self.parent, self.rid = parent, rid
        self.id = log._new_id()
        self._ann = TraceAnnotation(name)
        self._ann.__enter__()

    def close(self, end_ns: Optional[int] = None, attr: Any = None) -> None:
        self._ann.__exit__(None, None, None)
        self.log._add(Span(self.name, self.start_ns,
                           time.perf_counter_ns() if end_ns is None
                           else end_ns, self.id, self.parent, self.rid,
                           attr))


class Phases:
    """Span ``name`` opened at ``t_ns`` with its first child ``first``, and
    the consecutive children after it: each ``to`` ends the current phase
    and starts the next at the same stamp, so the phases tile the parent's
    time exactly."""

    __slots__ = ("log", "parent", "cur")

    def __init__(self, log: "SpanLog", name: str, first: str, t_ns: int):
        self.log = log
        self.parent = log.open(name, t_ns)
        self.cur = log.open(first, t_ns, parent=self.parent.id)

    def to(self, name: str, t_ns: Optional[int] = None) -> None:
        t = time.perf_counter_ns() if t_ns is None else t_ns
        self.cur.close(t)
        self.cur = self.log.open(name, t, parent=self.parent.id)

    def close(self, t_ns: int, attr: Any = None) -> None:
        self.cur.close(t_ns)
        self.parent.close(t_ns, attr)


class SpanLog:
    """In-memory span records, capped at ``cap`` (later ones are counted in
    ``dropped``).  Enabling also starts listening for XLA compiles."""

    def __init__(self, cap: int = 200_000):
        self.cap = int(cap)
        self.enabled = False
        self.dropped = 0
        self._records: List[Span] = []
        self._next_id = 1

    def enable(self) -> None:
        if not self.enabled:
            self.enabled = True
            jax.monitoring.register_event_duration_secs_listener(
                self._on_compile)

    def disable(self) -> None:
        if self.enabled:
            self.enabled = False
            jax.monitoring.unregister_event_duration_listener(
                self._on_compile)

    def records(self) -> List[Span]:
        return list(self._records)

    def clear(self) -> None:
        self._records.clear()
        self.dropped = 0

    def open(self, name: str, t_ns: Optional[int] = None, *,
             parent: int = 0, rid: int = -1) -> OpenSpan:
        return OpenSpan(self, name, time.perf_counter_ns() if t_ns is None
                        else t_ns, parent, rid)

    def _new_id(self) -> int:
        i = self._next_id
        self._next_id += 1
        return i

    def _add(self, span: Span) -> None:
        if len(self._records) < self.cap:
            self._records.append(span)
        else:
            self.dropped += 1

    def _on_compile(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            end = time.perf_counter_ns()
            self._add(Span("serve.compile", end - int(duration * 1e9), end,
                           self._new_id(), attr=kw.get("fun_name", "")))


# ------------------------------------------------------------- reductions
def step_table(records: Sequence[Span]) -> List[Dict[str, float]]:
    """One row per decode step: its riders, its ms, and the ms of each
    phase (``draft_ms`` ... ``accept_ms``; 0 where a phase did not run)."""
    rows: Dict[int, Dict[str, float]] = {}
    for s in records:
        if s.name == "serve.step":
            row = rows.setdefault(s.id, dict.fromkeys(
                [f"{p}_ms" for p in STEP_PHASES], 0.0))
            row.update(start_ns=s.start_ns, step_ms=s.ms,
                       riders=len(s.attr or ()))
    for s in records:
        row = rows.get(s.parent)
        if row is not None:
            row[f"{s.name[len('serve.'):]}_ms"] += s.ms
    return sorted(rows.values(), key=lambda r: r["start_ns"])


def request_table(records: Sequence[Span]) -> Dict[int, Dict[str, float]]:
    """rid -> ms of its ``queue``, ``admit`` and ``prefill`` spans, and
    ``engine_ttft_ms`` (queue start to prefill end) once it has both."""
    by_rid: Dict[int, Dict[str, Span]] = {}
    for s in records:
        if s.rid >= 0:
            by_rid.setdefault(s.rid, {})[s.name[len("serve."):]] = s
    out: Dict[int, Dict[str, float]] = {}
    for rid, spans in by_rid.items():
        row = out[rid] = {f"{k}_ms": s.ms for k, s in spans.items()}
        if "queue" in spans and "prefill" in spans:
            row["engine_ttft_ms"] = (spans["prefill"].end_ns
                                     - spans["queue"].start_ns) * 1e-6
    return out


__all__ = ["SpanLog", "Span", "OpenSpan", "Phases", "step_table",
           "request_table", "STEP_PHASES", "COMPILE_EVENT"]
