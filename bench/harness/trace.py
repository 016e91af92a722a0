"""Reduce a profiler trace to device busy time, per-program and per-op
device time, and idle gaps labelled by the benchmark's host spans.

The reduction works on a plain ``Trace`` (device op and program intervals,
host spans, all in nanoseconds on one clock), so a small synthetic trace
checks it; ``from_profile`` builds one from the ``.xplane.pb`` the JAX
profiler writes.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "bench."


@dataclass
class Op:
    name: str
    start: float           # ns
    end: float             # ns
    module: str = ""       # program (HLO module) the op ran in
    device: int = 0


@dataclass
class Trace:
    ops: List[Op] = field(default_factory=list)
    modules: List[Op] = field(default_factory=list)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    window_s: float = 0.0
    n_devices: int = 1


def module_name(name: str) -> str:
    """``jit__fused_step(123)`` -> ``jit__fused_step``."""
    return re.sub(r"\(.*$", "", name).strip()


def op_name(name: str) -> str:
    """An op event's instruction name: the TPU trace names an op by its HLO
    text, ``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ")[0].strip().lstrip("%")


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: instances of one op kind together."""
    name = op_name(name)
    return re.sub(r"[.\-_]?\d+$", "", name).strip() or name


# ops that only hold other ops (a layer loop's body runs as ops of its own)
CONTAINERS = ("while", "conditional", "call")


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals, in seconds."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


def idle_gaps(intervals: Sequence[Tuple[float, float]]
              ) -> List[Tuple[float, float]]:
    """The gaps between the union's pieces (start, end), in ns."""
    gaps, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            gaps.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return gaps


def label_at(t: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost host span covering time t (the shortest one), or
    ``idle host`` where none does."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    if best is None:
        return "idle host"
    return best[0][len(SPAN_PREFIX):] if best[0].startswith(
        SPAN_PREFIX) else best[0]


@dataclass
class Summary:
    window_s: float
    busy_s: float          # mean over the chips of each one's busy time
    busy_by_device: Dict[int, float]
    module_s: Dict[str, float]
    module_calls: Dict[str, int]
    op_s: Dict[Tuple[str, str], float]      # (module, op family) -> s
    top_ops: List[List]
    top_gaps: List[List]

    def module_time(self, names: Iterable[str]) -> float:
        names = set(names)
        return sum(v for k, v in self.module_s.items() if k in names)

    def calls(self, names: Iterable[str]) -> int:
        names = set(names)
        return sum(v for k, v in self.module_calls.items() if k in names)

    def kernel_time(self, modules: Iterable[str],
                    patterns: Iterable[str]) -> float:
        """Device seconds of ops in ``modules`` whose family matches any of
        ``patterns`` (regular expressions)."""
        mods, pats = set(modules), [re.compile(p) for p in patterns]
        return sum(v for (m, op), v in self.op_s.items()
                   if m in mods and any(p.search(op) for p in pats))


def summarize(tr: Trace, top: int = 10) -> Summary:
    per_dev: Dict[int, List[Tuple[float, float]]] = {}
    for o in tr.ops:
        per_dev.setdefault(o.device, []).append((o.start, o.end))
    n_dev = max(tr.n_devices, len(per_dev), 1)
    by_dev = {d: union_seconds(v) for d, v in per_dev.items()}
    busy = sum(by_dev.values()) / n_dev
    module_s: Dict[str, float] = {}
    module_calls: Dict[str, int] = {}
    for m in tr.modules:
        k = module_name(m.name)
        module_s[k] = module_s.get(k, 0.0) + (m.end - m.start) * 1e-9
        module_calls[k] = module_calls.get(k, 0) + 1
    op_s: Dict[Tuple[str, str], float] = {}
    for o in tr.ops:
        fam = op_family(o.name)
        if fam in CONTAINERS:
            continue
        k = (module_name(o.module), fam)
        op_s[k] = op_s.get(k, 0.0) + (o.end - o.start) * 1e-9
    tops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    top_ops = [[f"{m.replace('jit_', '')}:{op}", s] for (m, op), s in tops]
    dev0 = min(per_dev) if per_dev else 0
    gaps = idle_gaps(per_dev.get(dev0, []))
    gaps.sort(key=lambda g: g[0] - g[1])
    top_gaps = [[label_at((s + e) / 2, tr.spans), (e - s) * 1e-9]
                for s, e in gaps[:top]]
    return Summary(window_s=tr.window_s, busy_s=busy, busy_by_device=by_dev,
                   module_s=module_s,
                   module_calls=module_calls, op_s=op_s, top_ops=top_ops,
                   top_gaps=top_gaps)


# ------------------------------------------------------------ from xplane
def _assign_modules(ops: List[Op], modules: List[Op]) -> None:
    """Give each op the program whose interval holds it (same device)."""
    by_dev: Dict[int, List[Op]] = {}
    for m in modules:
        by_dev.setdefault(m.device, []).append(m)
    for v in by_dev.values():
        v.sort(key=lambda m: m.start)
    idx: Dict[int, int] = {d: 0 for d in by_dev}
    for o in sorted(ops, key=lambda o: (o.device, o.start)):
        if o.module:
            continue
        mods = by_dev.get(o.device, [])
        i = idx.get(o.device, 0)
        while i < len(mods) and mods[i].end < o.start:
            i += 1
        idx[o.device] = i
        if i < len(mods) and mods[i].start <= o.start <= mods[i].end:
            o.module = mods[i].name


def from_profile(log_dir: str, window_s: float) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    tr = Trace(window_s=window_s)
    devices = 0
    for plane in pd.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)", plane.name)
        if m and "TPU" in plane.name:
            dev = int(m.group(1))
            devices += 1
            for line in plane.lines:
                lname = line.name
                if lname not in ("XLA Ops", "XLA Modules"):
                    continue
                for ev in line.events:
                    op = Op(name=ev.name, start=float(ev.start_ns),
                            end=float(ev.start_ns + ev.duration_ns),
                            device=dev)
                    (tr.modules if lname == "XLA Modules"
                     else tr.ops).append(op)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.spans.append((ev.name, float(ev.start_ns),
                                         float(ev.start_ns
                                               + ev.duration_ns)))
    tr.n_devices = max(devices, 1)
    _assign_modules(tr.ops, tr.modules)
    return tr
