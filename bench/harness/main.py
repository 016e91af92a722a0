"""One run of one cell: set up, measure for ``--seconds``, check, print.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``, from process start): weights from the
seed, the engine, every program the cell's traffic uses (from the compile
cache after the first run), and warm-up traffic that brings the prefix
cache, the drafts and, for sessions, the conversations to steady depth.
The window then runs the cell's load for ``--seconds``; nothing compiles in
it.  Requests in flight at its close are drained; their latency counts the
wait.  The reference runs after the window, once the device's peak memory
has been read and the program's state freed.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the window and from the engine's counters.  The last stdout line is the
result object; the numbers compared for ``correct`` end stderr.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Tuple

DRAIN_S = 60.0      # in-flight requests finish within this after the window
SETUP_S = 900.0     # a cold first run compiles every program in set-up

# The CPU rehearsal: any cell's engine, traffic and check at a size the CPU
# serves in seconds (widths: the family's REHEARSAL_WIDTHS).
REHEARSAL: Dict[str, Any] = {
    "lanes": 4, "prefill_len": 160, "context": 256, "block_size": 16,
    "n_blocks": 96, "traffic_scale": 0.125, "warmup": {"requests": 8},
    "correct": {"requests": 4, "min_tokens": 10, "max_logit_gap": 0.05},
    "bias_sigmas": 3.5, "overlap_drafts": False}


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


class CompileClock:
    """Sums XLA backend compile time and counts compiles (JAX monitoring
    events), so set-up and the window can report theirs."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="one benchmark run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the cell at rehearsal widths on the CPU; "
                         "prints counts only, no device metric")
    return ap.parse_args(argv)


def devices_for(chips: int, rehearsal: bool):
    import jax
    devs = jax.devices()
    if rehearsal:
        return devs
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, found {len(devs)}")
    return devs


def engine_config(settings: Dict[str, Any]):
    from repro.core.draft_sources import DraftPolicy
    from repro.serving.api import EngineConfig
    return EngineConfig(
        lanes=int(settings["lanes"]),
        prefill_len=int(settings["prefill_len"]),
        decoding_length=int(settings.get("decoding_length", 32)),
        backend=settings.get("backend", "pallas"),
        kv_layout="paged", block_size=int(settings["block_size"]),
        n_blocks=int(settings["n_blocks"]),
        prefix_cache=bool(settings.get("prefix_cache", True)),
        overlap_drafts=bool(settings.get("overlap_drafts", True)),
        sampling="greedy", eos_id=-1,
        draft_policy=DraftPolicy(sources=tuple(settings["sources"])))


def params_for(ecfg, namespace: str = ""):
    """Each request's ``SamplingParams``: its draft policy scoped to the
    request's tenant namespace, or to ``namespace`` where it has none."""
    import dataclasses
    from repro.core.request import SamplingParams
    policies: Dict[str, Any] = {}

    def fn(req):
        ns = req.namespace or namespace
        policy = policies.get(ns)
        if policy is None:
            policy = policies[ns] = dataclasses.replace(ecfg.draft_policy,
                                                        namespace=ns)
        return SamplingParams(max_new_tokens=int(req.max_new), draft=policy)
    return fn


def warm_programs(drv, ecfg, fns, vocab: int, seed: int, deadline: float
                  ) -> None:
    """Run, under a namespace of their own, requests that reach every
    program the cell's traffic can use: the first cohort's prefill, a slot
    prefill, a prefix-hit suffix prefill in every suffix bucket, a
    copy-on-write fork of a part-matched block, and steps whose block
    tables come from the host."""
    from harness.traffic import Request, stream_rng
    rng = stream_rng(seed, 90)
    plen, bs = ecfg.prefill_len, ecfg.block_size
    params = params_for(ecfg, "bench.warmup")

    def rand(n):
        return rng.integers(2, vocab, size=(int(n),)).tolist()

    base = rand(plen)
    first = [Request(base, 2)] + [Request(rand(16 + i), 2)
                                  for i in range(ecfg.lanes - 1)]
    recs = [drv.submit(r, drv.clock(), params) for r in first]
    drv.drain(recs, deadline)
    log(f"bench: first cohort served ({sum(r.done for r in recs)} of "
        f"{len(recs)})")
    # a suffix prefill after a copy-on-write fork gets block tables from
    # the fork's output, one after a whole-block hit from the host: the two
    # compile apart, so every bucket is reached both ways
    later = [Request(rand(plen - 1), 2)]
    cow = bs // 2
    for b in fns.suffix_buckets:
        later.append(Request(base[:bs] + rand(min(b, plen - bs)), 2))
        later.append(Request(base[:bs + cow] + rand(min(b, plen - bs - cow)),
                             2))
    recs = [drv.submit(r, drv.clock(), params) for r in later]
    drv.drain(recs, deadline)
    # a step after a lane's table changed on the host (here: its neighbour
    # retired, and nothing was admitted since) takes the tables from the
    # host, and compiles apart from one whose tables a program returned
    pair = [Request(rand(bs), 48), Request(rand(bs), 2)]
    recs = [drv.submit(r, drv.clock(), params) for r in pair]
    drv.drain(recs, deadline)


def main(argv: List[str], t_process: float) -> int:
    args = parse(argv)
    rehearse = bool(args.cpu_rehearsal)
    from harness.spec import load_cell
    cell = load_cell(args.workload)
    try:
        devs = devices_for(cell.chips, rehearse)
    except NoChip as e:
        log(f"bench: {e}")
        return 3
    clock = start_jax()
    result = run_once(cell, int(args.seed), float(args.seconds),
                      bool(args.trace), devs, clock, t_process,
                      rehearse=rehearse)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


def side_by_side(fn, items) -> List[Any]:
    """``fn`` of each item, one thread each where there are several: each
    chip compiles or loads its own executables, and the compiles overlap."""
    if len(items) == 1:
        return [fn(items[0])]
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(len(items)) as pool:
        return list(pool.map(fn, items))


def start_jax() -> CompileClock:
    """Compile cache where the program keeps it; every program cached."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # a cell on four chips holds each program once per chip: under a size
    # cap the cache evicts one chip's programs for the next one's, and
    # every run compiles everything again
    cap = jax.config.jax_compilation_cache_max_size
    jax.config.update("jax_compilation_cache_max_size", -1)
    log(f"bench: compile cache {cache_dir} (size cap was {cap}, now none)")
    return CompileClock()


def run_once(cell, seed: int, seconds: float, trace: bool, devs,
             clock: CompileClock, t_process: float, *,
             rehearse: bool = False, controls: Tuple[str, ...] = ()
             ) -> Dict[str, Any]:
    """Set up, run the window, check; returns the result object."""
    import jax
    from repro.core.trie import TrieTree
    from repro.serving.api import ServingEngine, build_session_fns

    from harness import correct, e2e
    from harness.driver import Driver, Probe, Stalls, stat_snapshot
    from harness.fleet import Fleet
    from harness.model import Guide, bias_size, make_bias
    from harness.spec import family_module, peaks_for, reference_module
    from harness.traffic import Limits, Mix

    settings = dict(cell.settings)
    if rehearse:
        settings.update(REHEARSAL)
    fam = family_module(cell.config)
    rep_spec = settings.get("replicas")
    n_rep = int(rep_spec["n"]) if rep_spec else 1
    if len(devs) < n_rep:
        raise NoChip(f"{n_rep} replicas need {n_rep} devices, found "
                     f"{len(devs)}")
    used = list(devs[:n_rep])
    dev = used[0]
    shape = fam.shape_of(cell.config, rehearsal=rehearse)
    context = int(settings.get("context", shape["context"]))
    peaks = None if rehearse else peaks_for(dev.device_kind)
    log(f"bench: {cell.name} seed {seed} on {dev.platform} "
        f"{dev.device_kind} x{len(devs)}, {n_rep} replica(s); family "
        f"{cell.config.get('family', 'decoder')} {json.dumps(shape)}")

    t_phase = time.perf_counter()

    def phase(name: str) -> None:
        nonlocal t_phase
        now = time.perf_counter()
        log(f"bench: {name} {now - t_phase:.1f} s ({clock.count} compiles, "
            f"{clock.seconds:.1f} s compiling)")
        t_phase = now

    # each replica's own copy, drawn on its own chip from the same seed
    weights = side_by_side(lambda d: fam.make_weights(shape, seed, device=d),
                           used)
    phase("weights")
    guide_spec = cell.traffic.get("guide")
    guide, size, bias = None, 0.0, None
    if guide_spec:
        # the guide is part of the served model, fixed by the mix (not by
        # the run's seed), so every run reuses the same compiled programs
        guide = Guide.of(guide_spec, shape["vocab"])
        size = bias_size(shape, float(settings.get(
            "bias_sigmas", guide_spec["bias_sigmas"])))
        bias = make_bias(guide, size)
    ecfg = engine_config(settings)
    tcfg = fam.program_config(shape, context=context)
    probe = Probe(fam, shape, ecfg.pad_id, peaks) if trace else None
    trie_capacity = (int(settings.get("trie_capacity", 0))
                     or ecfg.lookahead().trie_capacity)

    def build(w):
        fns = build_session_fns(ecfg, tcfg, w, logits_transform=bias)
        if probe is not None:
            fns = probe.wrap_fns(fns)
        engine = ServingEngine(fns, ecfg,
                               trie=TrieTree(capacity=trie_capacity))
        if probe is not None:
            Probe.annotate_scheduler(engine.scheduler)
        return engine

    fleet = None
    if rep_spec:
        fleet = Fleet([lambda w=w: build(w) for w in weights], used,
                      policy=str(rep_spec.get("policy", "affinity")),
                      max_queue_depth=int(rep_spec.get("max_queue_depth",
                                                       8)))
        engines, front = fleet.engines, fleet
    else:
        front = build(weights[0])
        engines = [front]
    drv = Driver(front)
    limits = Limits(ecfg.prefill_len, context, ecfg.slots)
    mix = Mix(cell.traffic, seed, shape["vocab"], guide, limits,
              scale=float(settings.get("traffic_scale", 1.0)))
    params = params_for(ecfg)

    # ---------------------------------------------------------- set-up
    c_before = (clock.count, clock.seconds)
    phase("engine")
    deadline = time.perf_counter() + SETUP_S
    if fleet is None:
        warm_programs(drv, ecfg, front.fns, shape["vocab"], seed, deadline)
    else:
        side_by_side(lambda p: warm_programs(
            Driver(p), ecfg, p.engine.fns, shape["vocab"], seed, deadline),
            fleet.pinned)
    phase("programs warmed")
    load = settings["load"]
    warm = settings.get("warmup", {})
    loop, ready = None, []
    if cell.traffic["loop"] == "closed":
        loop = mix.clients(int(load["clients"]))
        ready = [(c, None, None) for c in range(int(load["clients"]))]
        wrecs = drv.closed_loop(loop, ready, deadline, params,
                                stop_after=int(warm.get("requests", 0)))
    else:
        wrecs = [drv.submit(r, drv.clock(), params) for r in
                 mix.requests(int(warm.get("requests", 0)), 30)]
    drv.drain(wrecs, deadline)
    if time.perf_counter() >= deadline:
        raise RuntimeError(f"set-up did not finish in {SETUP_S:.0f} s")
    phase("warm-up traffic")
    c_setup = (clock.count - c_before[0], clock.seconds - c_before[1])
    # what set-up built (weights' host mirrors, warm-up traces, the warm
    # trie) stays for the run: keep the collector from rescanning it in
    # the window, where a full pass stalls the host for seconds
    gc.collect()
    gc.freeze()

    # ---------------------------------------------------------- window
    trace_dir = None
    traced = {"t0": None, "t1": None}
    hooks = []

    def caches():
        return [e.scheduler.cache for e in engines]

    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        t_off = min(float(settings.get("trace_offset_s", 5.0)),
                    seconds / 3)
        t_len = min(float(settings.get("trace_seconds", 5.0)),
                    seconds - t_off)
        state = {"on": False, "off": False}

        def trace_hook(now):
            if not state["on"] and now >= t_start + t_off:
                jax.block_until_ready(caches())
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # host spans are our own
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                state["on"] = True
                traced["t0"] = time.perf_counter()
                probe.active = True
            elif state["on"] and not state["off"] and (
                    now >= traced["t0"] + t_len or now >= t_end):
                probe.active = False
                jax.block_until_ready(caches())
                traced["t1"] = time.perf_counter()
                jax.profiler.stop_trace()
                state["off"] = True
        hooks.append(trace_hook)

    def snapshot():
        snap = stat_snapshot(front.stats)
        if fleet is not None:
            snap.update(fleet.route_counts())
        return snap

    drv.stalls = Stalls()
    c0 = clock.count
    t_start = time.perf_counter()
    t_end = t_start + seconds
    setup_s = t_start - t_process
    s0 = snapshot()
    if loop is not None:
        for i, (c, ans, _) in enumerate(ready):
            ready[i] = (c, ans, t_start)
        recs = drv.closed_loop(loop, ready, t_end, params, hooks=hooks)
    else:
        reqs = mix.open_loop(float(load["rate"]), seconds)
        recs = drv.open_loop(reqs, t_start, t_end, params, hooks=hooks)
    t_close = time.perf_counter()
    drv.stalls.close()
    for h in hooks:
        h(t_end + 1e9)
    s1 = snapshot()
    compiles_in_window = clock.count - c0
    drv.drain(recs, t_close + DRAIN_S)
    for r in recs:
        if r.handle is not None and r.handle.done:
            st = r.handle.result().stats
            r.kind, r.cached = st.prefill, st.cached_prompt_tokens
        r.handle = None
    failed = sum(1 for r in recs if not r.complete)
    summary = e2e.summarize(recs, t_start, t_end)
    late = e2e.lateness_ms(recs)
    log(f"bench: set-up {setup_s:.2f} s ({c_setup[0]} compiles, "
        f"{c_setup[1]:.1f} s compiling); {compiles_in_window} compiles in "
        f"the window; generator lateness p50 {late['p50']:.3f} ms p99 "
        f"{late['p99']:.3f} ms max {late['max']:.3f} ms over {late['n']} "
        f"requests")
    log(f"bench: e2e {json.dumps(summary)}")
    log(f"bench: host stalls in the window: {drv.stalls.summary()}")
    if fleet is not None:
        log(f"bench: router {fleet.router.policy}: homes "
            f"{json.dumps(fleet.homes(mix.namespaces))}; placed per "
            f"replica {fleet.placed()} (warm-up included); in the window "
            f"routed {s1['routed'] - s0['routed']:.0f}, home "
            f"{s1['affinity_hits'] - s0['affinity_hits']:.0f}, spilled "
            f"{s1['spills'] - s0['spills']:.0f}")

    mem_peak = None
    if not rehearse:
        # the fullest chip's peak
        peaks_in_use = [int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in used]
        mem_peak = max(peaks_in_use) or None
        if len(used) > 1:
            log(f"bench: peak HBM per chip {peaks_in_use}")
    counters = {k: s1[k] - s0[k] for k in s0}
    decode_tokens = sum(n for r in recs for (t, n) in r.deltas[1:]
                        if t_start <= t < t_end)
    lanes = ecfg.lanes
    # the reference reads the first replica's weights (all are the same)
    weights = weights[0]
    # warm-up handles hold the engines
    del front, engines, fleet, drv, wrecs
    gc.unfreeze()
    gc.collect()

    # ------------------------------------------------------- per-layer
    trace_sum = None
    if trace and traced["t1"] is not None:
        from harness.trace import from_profile, summarize
        trace_sum = summarize(from_profile(trace_dir, traced["t1"]
                                           - traced["t0"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if not rehearse and trace_sum.window_s > 0:
            idle = {d: round(100.0 * (1.0 - b / trace_sum.window_s), 3)
                    for d, b in sorted(trace_sum.busy_by_device.items())}
            log(f"bench: device idle % per chip {json.dumps(idle)}")

    # ------------------------------------------------------- correctness
    t_ref = time.perf_counter()
    ref = reference_module(cell.config)
    verdict = correct.check(ref, shape, weights, recs, guide, size,
                            settings["correct"], seed, controls=controls)
    log(f"bench: checked {verdict['requests_checked']} requests "
        f"({', '.join(verdict['kinds'])}) in "
        f"{time.perf_counter() - t_ref:.1f} s; widest gap "
        f"{json.dumps(verdict['worst'])}")
    del weights

    result: Dict[str, Any] = {
        "correct": verdict["correct"],
        "attempted": len(recs),
        "failed": failed,
    }
    from harness.spec import metric_reader
    run = RunData(shape=shape, peaks=peaks, settings=settings,
                  counters=counters, decode_tokens=decode_tokens,
                  probe=probe, trace=trace_sum, lanes=lanes)
    if rehearse:
        # only what the program counts: no device metric comes from a CPU
        counted = {m["name"]: metric_reader(m["name"])(run)
                   for m in cell.per_layer
                   if m["source"] == "program_counter"}
        result["rehearsal"] = {
            "platform": dev.platform, "replicas": n_rep,
            "requests": len(recs),
            "tokens": sum(r.n_tokens for r in recs),
            "decode_steps": counters["decode_steps"],
            "accepted_per_step": decode_tokens / max(
                counters["active_lane_steps"], 1),
            "prefix_hit_tokens": counters["prefix_hit_tokens"],
            "program_counters": counted,
            "compiles_in_window": compiles_in_window,
            "kinds_checked": verdict["kinds"]}
    else:
        metrics: Dict[str, Dict[str, Any]] = {}
        if not trace:
            values = dict(summary, setup_s=setup_s)
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        else:
            for m in cell.per_layer:
                v = metric_reader(m["name"])(run)
                if v is not None and math.isfinite(v):
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = {"platform": dev.platform,
                            "kind": dev.device_kind, "count": len(devs),
                            "memory_peak_bytes": mem_peak}
        if trace_sum is not None:
            result["device"]["busy_s"] = trace_sum.busy_s
            result["device"]["window_s"] = trace_sum.window_s
            result["breakdown"] = {"device_ops": trace_sum.top_ops,
                                   "idle_gaps": trace_sum.top_gaps}
            log(f"bench: traced {trace_sum.window_s:.3f} s; fused steps "
                f"dispatched {probe.calls.get('fused_step', 0)}, in trace "
                f"{trace_sum.calls(['jit__fused_step'])}")
    result["compiles_in_window"] = compiles_in_window
    if controls:
        result["control_gap"] = verdict["control_gap"]
    result["checks"] = verdict["checks"]
    return result


class RunData:
    """What a per-layer metric's reader reads: the window's counter
    deltas, the work counted from the traced calls, the reduced trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def device_seconds(self, modules) -> float:
        return self.trace.module_time(modules) if self.trace else 0.0
