"""Drive the served path: ``ServingEngine.submit`` / ``step`` under a
cell's load, recording what each client saw.

Only public surfaces are driven: requests go in through ``submit``, tokens
come back through ``RequestHandle.on_token`` (stamped on the host clock as
they arrive), the engine is stepped with ``step()``.  In a traced run the
``Probe`` also wraps the ``StepFns`` handed to the engine (to count the
work of every call, from its arguments) and a few scheduler methods (to
name host spans in the trace); an untimed run carries none of it.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from harness import flops
from harness.e2e import Record
from harness.traffic import ClosedLoop, Request

STAT_KEYS = ("decode_steps", "active_lane_steps", "admitted", "finished",
             "host_draft_ms", "device_step_ms", "accept_commit_ms",
             "hidden_host_ms", "prefix_lookups", "prefix_hits",
             "prefix_hit_tokens", "prefix_prompt_tokens", "block_waits")


def stat_snapshot(stats) -> Dict[str, float]:
    return {k: float(getattr(stats, k)) for k in STAT_KEYS}


class Probe:
    """Counts the work of each ``StepFns`` call (from its host arguments)
    while ``active``, and names the host phases in the profiler trace."""

    PROGRAMS = ("fused_step", "prefill", "prefill_into_slot",
                "prefill_suffix", "copy_block")

    def __init__(self, family, shape: Dict[str, Any], pad_id: int,
                 peaks: Optional[Dict[str, Any]] = None):
        self.family = family
        self.shape = shape
        self.pad_id = pad_id
        self.peaks = peaks
        self.active = False
        self.work: Dict[str, Dict[str, float]] = {}
        self.calls: Dict[str, int] = {}

    def _add(self, prog: str, w: Dict[str, float]) -> None:
        if self.peaks:
            w = dict(w, **flops.rooflines(w, self.peaks))
        acc = self.work.setdefault(prog, {})
        for k, v in w.items():
            acc[k] = acc.get(k, 0.0) + float(v)
        self.calls[prog] = self.calls.get(prog, 0) + 1

    def _count(self, prog: str, args) -> None:
        s, fam = self.shape, self.family
        if prog == "fused_step":
            _, lens, _, _, mask, _, n_live = args[:7]
            lens, n_live = np.asarray(lens), np.asarray(n_live)
            anc = np.asarray(mask).sum(-1)                   # (B, W)
            lanes = [(int(lens[b]), anc[b, :int(n_live[b])].tolist())
                     for b in range(len(n_live)) if n_live[b] > 0]
            self._add(prog, fam.step_work(s, lanes))
        elif prog == "prefill":
            tokens, lens = np.asarray(args[0]), np.asarray(args[1])
            for b in range(tokens.shape[0]):
                if tokens[b, 0] != self.pad_id:
                    self._add(prog, fam.prefill_work(s, int(lens[b])))
        elif prog == "prefill_into_slot":
            self._add(prog, fam.prefill_work(
                s, int(np.asarray(args[3])[0])))
        elif prog == "prefill_suffix":
            n = int(np.asarray(args[2]).shape[1])
            self._add(prog, fam.prefill_work(s, n, int(args[3])))
        else:
            self._add(prog, {})

    def wrap_fns(self, fns):
        """A copy of ``fns`` whose device members count and annotate."""
        from jax.profiler import TraceAnnotation

        def wrap(prog: str, fn: Callable) -> Callable:
            label = f"bench.dispatch.{prog}"

            def call(*args, **kw):
                if self.active:
                    self._count(prog, args)
                with TraceAnnotation(label):
                    return fn(*args, **kw)
            return call

        repl = {p: wrap(p, getattr(fns, p)) for p in self.PROGRAMS
                if getattr(fns, p, None) is not None}
        return dataclasses.replace(fns, **repl)

    @staticmethod
    def annotate_scheduler(sch) -> None:
        """Host spans around the scheduler's phases: ``admit``, ``draft``
        (one tree), ``pull`` (device to host), and ``step`` (one decode
        iteration; its time outside the others is accept and commit)."""
        from jax.profiler import TraceAnnotation
        for meth, label in (("_admit", "admit"), ("_build_tree", "draft"),
                            ("_pull", "pull"), ("_decode", "step")):
            fn = getattr(sch, meth)

            def call(*a, _fn=fn, _label=f"bench.{label}", **kw):
                with TraceAnnotation(_label):
                    return _fn(*a, **kw)
            setattr(sch, meth, call)


class Stalls:
    """Engine steps that held the host longer than ``threshold`` seconds,
    each with the collector's time and the admissions and retirements
    inside it: every request due in a stall waits it out."""

    def __init__(self, threshold: float = 0.25):
        self.threshold = threshold
        self.on = True
        self.gc_s = 0.0
        self.gc_runs = 0
        self.steps: List[Dict[str, float]] = []
        self._gc_t0: Optional[float] = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if not self.on:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_runs += 1
            self._gc_t0 = None

    def close(self) -> None:
        self.on = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def summary(self) -> str:
        top = sorted(self.steps, key=lambda r: -r["s"])[:5]
        return (f"{len(self.steps)} steps over {self.threshold * 1e3:.0f} ms"
                f" ({json.dumps(top)}); collector {self.gc_s:.3f} s in "
                f"{self.gc_runs} runs")


class Driver:
    """One engine under one cell's load."""

    def __init__(self, engine, clock=time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.records: List[Record] = []
        self.stalls: Optional[Stalls] = None

    def step(self) -> None:
        """One engine step; while ``stalls`` is on, a long one is noted."""
        st = self.stalls
        if st is None or not st.on:
            self.engine.step()
            return
        stats = self.engine.stats
        t0, g0 = self.clock(), st.gc_s
        a0, f0 = stats.admitted, stats.finished
        self.engine.step()
        dt = self.clock() - t0
        if dt > st.threshold:
            st.steps.append({"s": round(dt, 4),
                             "gc_s": round(st.gc_s - g0, 4),
                             "admitted": stats.admitted - a0,
                             "finished": stats.finished - f0})

    # ------------------------------------------------------------ submit
    def submit(self, req: Request, due: float, params_fn,
               on_complete: Optional[Callable] = None) -> Record:
        rec = Record(due=due, max_new=req.max_new, prompt_len=len(req.prompt),
                     tag=req.tag, prompt=list(req.prompt), client=req.client)
        handle = self.engine.submit(req.prompt, params=params_fn(req))
        rec.submit = self.clock()
        rec.handle = handle

        def on_token(delta, rec=rec):
            now = self.clock()
            if rec.first is None:
                rec.first = now
            rec.last = now
            rec.n_tokens += len(delta)
            rec.tokens.extend(delta)
            rec.deltas.append((now, len(delta)))
            if rec.complete and not rec.done:
                rec.done = True
                if on_complete is not None:
                    on_complete(rec)
        handle.on_token(on_token)
        self.records.append(rec)
        return rec

    def _step_or_wait(self, until: float) -> None:
        if self.engine.idle:
            wait = until - self.clock()
            if wait > 0:
                time.sleep(min(wait, 0.002))
        else:
            self.step()

    def drain(self, recs: List[Record], deadline: float) -> None:
        """Step until every record is complete, or ``deadline``."""
        while (any(not r.done for r in recs) and self.clock() < deadline
               and not self.engine.idle):
            self.step()
        while not self.engine.idle and self.clock() < deadline:
            self.step()

    # --------------------------------------------------------- open loop
    def open_loop(self, reqs: List[Request], t0: float, t_end: float,
                  params_fn, hooks=()) -> List[Record]:
        """Submit each request at t0 + due, whatever the server does;
        step the engine in between.  Requests still unsent at t_end were
        due inside the window and go in at once."""
        out: List[Record] = []
        i = 0
        while True:
            now = self.clock()
            for h in hooks:
                h(now)
            if now >= t_end:
                break
            while i < len(reqs) and t0 + reqs[i].due <= now:
                out.append(self.submit(reqs[i], t0 + reqs[i].due, params_fn))
                i += 1
            nxt = t0 + reqs[i].due if i < len(reqs) else t_end
            self._step_or_wait(min(nxt, t_end))
        for r in reqs[i:]:
            out.append(self.submit(r, t0 + r.due, params_fn))
        return out

    # ------------------------------------------------------- closed loop
    def closed_loop(self, loop: ClosedLoop, ready: List, t_end: float,
                    params_fn, hooks=(), stop_after: Optional[int] = None
                    ) -> List[Record]:
        """Each client sends its next request as soon as its previous
        answer is complete (no think time).  ``ready`` holds (client,
        previous answer or None, due time) for clients with nothing in
        flight; it is left holding those that are due when the loop ends,
        so a later window continues the same sessions."""
        out: List[Record] = []

        def done(rec: Record) -> None:
            ready.append((rec.client, list(rec.tokens), rec.last))

        while True:
            now = self.clock()
            for h in hooks:
                h(now)
            if now >= t_end or (stop_after is not None
                                and len(out) >= stop_after):
                break
            while ready and (stop_after is None or len(out) < stop_after):
                client, answer, due = ready.pop(0)
                req = loop.next(client, answer)
                out.append(self.submit(req, due if due is not None else now,
                                       params_fn, on_complete=done))
            self._step_or_wait(t_end)
        return out
