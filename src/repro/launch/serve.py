"""Serving launcher: drive the request-centric serving engine (or the legacy
lock-step loop) over an arch config with a synthetic arrival stream.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --smoke --requests 16 --lanes 4 --rate 8 --mixed-sampling

Reports throughput (tokens/s), EDL, lane occupancy and per-request latency
percentiles (p50/p95/p99) plus time-to-first-token.  ``--rate 0`` submits
every request at t=0 (closed-loop batch mode); a positive rate draws Poisson
inter-arrival gaps (open-loop mode — the scheduler admits mid-flight).

All engine knobs are one validated ``EngineConfig``
(repro.serving.api.build_engine); requests are ``Request`` objects with
per-request ``SamplingParams``: ``--mixed-sampling`` alternates greedy and
sampled traffic (distinct temperatures/seeds) inside the same lane pool, and
``--cancel-every N`` cancels every Nth request mid-flight through its
``RequestHandle`` — both exercises of the production API surface.

Without --smoke the full config is served from one device (random weights,
or --ckpt-dir via training.checkpoint); serve builds no mesh.
``--replicas N`` drives N in-process engines behind the fleet router, one
per chip (replica i on ``jax.devices()[i % n]``).  The persistent
compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<repo>/.jax_cache`` (repro.launch.compile_cache).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import List

import jax
import numpy as np

from repro import configs as cfgreg
from repro.core import (DraftPolicy, LookaheadEngine, Request,
                        SamplingParams)
from repro.core.draft_sources import available_sources
from repro.launch.compile_cache import enable_compile_cache
from repro.models import attention as attn_backends
from repro.models import transformer as tx
from repro.serving.api import EngineConfig, build_engine
from repro.serving.spans import STEP_PHASES, request_table, step_table
from repro.training.checkpoint import CheckpointManager
from repro.training.data import PROFILES, SyntheticCorpus


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


def _request_params(args, i: int) -> SamplingParams:
    """Per-request SamplingParams for request i of the synthetic stream."""
    max_new = args.max_new if (not args.mixed or i % 2) else \
        max(args.max_new // 4, 2)
    if args.mixed_sampling:
        # alternate greedy / sampled at cycling temperatures, one seed per
        # request — a co-batched mix the per-lane param vectors must honor
        if i % 2:
            return SamplingParams(max_new_tokens=max_new, sample=True,
                                  temperature=(0.5, 0.8, 1.1)[i % 3],
                                  seed=1000 + i)
        return SamplingParams(max_new_tokens=max_new)
    return SamplingParams(max_new_tokens=max_new, sample=args.sample,
                          temperature=args.temperature, seed=0)


def _print_spans(log) -> None:
    """Mean ms of each decode-step phase (draft + settle + pack is the
    breakdown's draft), request phases, and the programs compiled."""
    records = log.records()
    rows = [r for r in step_table(records) if r["riders"]]
    n = max(len(rows), 1)
    print("step phases: " + "   ".join(
        f"{p} {sum(r[f'{p}_ms'] for r in rows) / n:.2f} ms"
        for p in STEP_PHASES))
    reqs = request_table(records).values()

    def p95(key):
        return _pct([r[key] for r in reqs if key in r], 95)
    admit = [r["admit_ms"] for r in reqs if "admit_ms" in r]
    print(f"request phases: queue p95 {p95('queue_ms'):.1f} ms   admit mean "
          f"{sum(admit) / max(len(admit), 1):.2f} ms   prefill p95 "
          f"{p95('prefill_ms'):.1f} ms   engine ttft p95 "
          f"{p95('engine_ttft_ms'):.1f} ms")
    compiled = sorted({s.attr for s in records if s.name == "serve.compile"})
    print(f"compiled while serving: {len(compiled)} programs "
          f"({', '.join(compiled)}); {log.dropped} spans over the log's cap")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=4,
                    help="KV-cache slots held on device (continuous mode)")
    ap.add_argument("--mode", choices=["continuous", "lockstep"],
                    default="continuous")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="mean arrivals/s (Poisson); 0 = all at t0")
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--mixed", action="store_true",
                    help="mixed-length workload: alternate max_new/4 and "
                         "max_new budgets (the continuous-batching case)")
    ap.add_argument("--mixed-sampling", action="store_true",
                    help="mixed per-request sampling: alternate greedy and "
                         "sampled (distinct temperatures/seeds) requests in "
                         "the same lane pool")
    ap.add_argument("--cancel-every", type=int, default=0,
                    help="cancel every Nth request mid-flight through its "
                         "RequestHandle (0 = never)")
    ap.add_argument("--overlap-drafts", action="store_true",
                    help="overlap host work with the in-flight device step "
                         "(deferred retirement + admission settles after "
                         "draft building); bit-identical outputs to the "
                         "serial path")
    ap.add_argument("--prefill-len", type=int, default=128,
                    help="fixed prompt pad length (compile prefill once)")
    ap.add_argument("--decoding-length", type=int, default=32)
    ap.add_argument("--branch-length", type=int, default=12)
    ap.add_argument("--draft-sources", default="trie",
                    help="comma-separated draft sources feeding every "
                         "request's trees, in merge-priority order "
                         f"(registry: {', '.join(available_sources())})")
    ap.add_argument("--adaptive-draft", action="store_true",
                    help="per-lane adaptive draft budget from the "
                         "accepted-length EMA (paper §5.2 warmup/CDL)")
    ap.add_argument("--trie-namespace-key", default=None,
                    help="request-metadata key whose value scopes the trie "
                         "namespace (per-scenario tries, isolated branch "
                         "frequencies; the synthetic stream tags requests "
                         "with 'tenant')")
    ap.add_argument("--lane-shares", default=None,
                    help="per-namespace lane shares as ns=frac,... (e.g. "
                         "t0=0.5,t1=0.5): weighted-fair admission across "
                         "tenants with a lane-occupancy cap of "
                         "ceil(lanes*frac) each; unlisted namespaces are "
                         "uncapped at the lowest listed weight")
    ap.add_argument("--draft-budget-caps", default=None,
                    help="per-namespace draft budget caps as ns=int,... — "
                         "bounds speculative tokens per tree for that "
                         "tenant's requests")
    ap.add_argument("--autotune", action="store_true",
                    help="per-namespace draft-source auto-tuning: drive a "
                         "source's quota to zero on namespaces where it "
                         "never verifies (EMA acceptance controller; "
                         "outputs stay bit-identical)")
    ap.add_argument("--sanitize", action="store_true",
                    help="runtime sanitizer: shadow block-ownership "
                         "ledger, per-request lifecycle state machine, "
                         "retrace monitor (repro.analysis.sanitizer). "
                         "Raises on any invariant violation; adds host "
                         "overhead, outputs unchanged")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--sample", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="EOS token id ending generation early (-1 = the "
                         "arch defines none; synthetic corpora avoid one)")
    ap.add_argument("--backend", default=None,
                    choices=attn_backends.available_backends(),
                    help="attention backend for BOTH phases (registry: "
                         f"{', '.join(attn_backends.available_backends())})")
    ap.add_argument("--prefill-backend", default=None,
                    choices=attn_backends.available_backends(),
                    help="prefill-phase attention backend override")
    ap.add_argument("--decode-backend", default=None,
                    choices=attn_backends.available_backends(),
                    help="tree-decode-phase attention backend override")
    ap.add_argument("--kv-layout", default="dense",
                    choices=["dense", "paged"],
                    help="KV-cache layout: dense (lanes, max_seq_len) rows "
                         "or a paged block pool with per-lane block tables")
    ap.add_argument("--block-size", type=int, default=64,
                    help="paged layout: KV rows per block")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="paged layout: total pool blocks (0 = size the "
                         "pool to the workload's worst-case footprint; the "
                         "dense-equivalent is lanes*ceil(max_seq_len/"
                         "block_size)+1)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix caching on the paged pool: "
                         "admissions whose prompt prefix is already "
                         "resident skip that portion of prefill "
                         "(copy-on-write block sharing; bit-identical "
                         "outputs)")
    ap.add_argument("--prefix-cache-blocks", type=int, default=0,
                    help="cap on blocks the prefix cache may keep resident "
                         "(0 = bounded only by pool pressure)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request a shared system-prompt prefix "
                         "of this many tokens (prefix-heavy traffic for "
                         "--prefix-cache)")
    # ---- fleet serving (repro.fleet; DESIGN.md §Fleet serving)
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through N in-process engine replicas behind "
                         "the namespace-affinity router (1 = single engine)")
    ap.add_argument("--routing", default="affinity",
                    choices=["affinity", "round_robin"],
                    help="fleet placement policy: consistent-hash namespace "
                         "affinity (warm tries keep their traffic) or "
                         "round-robin (the cold baseline)")
    ap.add_argument("--gossip-every", type=int, default=0,
                    help="fleet rounds between all-to-all draft-state "
                         "merges (0 = gossip off)")
    ap.add_argument("--fleet-queue-depth", type=int, default=8,
                    help="per-replica queue depth at which affinity "
                         "routing spills to the least-loaded replica")
    ap.add_argument("--warm-state", default=None,
                    help="draft-state file: loaded at startup when it "
                         "exists (warm restart), saved at exit")
    ap.add_argument("--verify-fleet", action="store_true",
                    help="re-run the fleet workload on one reference "
                         "engine and assert bit-identical outputs")
    args = ap.parse_args()
    enable_compile_cache()

    def _ns_map(spec, cast):
        if not spec:
            return None
        out = {}
        for cell in spec.split(","):
            ns, _, val = cell.partition("=")
            if not _:
                raise SystemExit(f"bad ns=value cell {cell!r}")
            out[ns] = cast(val)
        return out

    lane_shares = _ns_map(args.lane_shares, float)
    draft_caps = _ns_map(args.draft_budget_caps, int)
    if (lane_shares or draft_caps) and not args.trie_namespace_key:
        raise SystemExit("--lane-shares/--draft-budget-caps key on the "
                         "request namespace; set --trie-namespace-key "
                         "(e.g. tenant) so requests carry one")
    if args.prefix_cache and args.kv_layout != "paged":
        raise SystemExit("--prefix-cache requires --kv-layout paged")
    if args.kv_layout == "paged" and args.mode == "lockstep":
        raise SystemExit("--kv-layout paged requires --mode continuous "
                         "(the scheduler owns the block allocator)")
    draft_policy = DraftPolicy(
        sources=tuple(args.draft_sources.split(",")),
        adaptive=args.adaptive_draft).validate()
    if args.mode == "lockstep" and (
            draft_policy.sources != ("trie",) or draft_policy.adaptive
            or args.trie_namespace_key or args.autotune):
        raise SystemExit("--draft-sources/--adaptive-draft/"
                         "--trie-namespace-key/--autotune require --mode "
                         "continuous (the lock-step loop is the "
                         "hardwired-trie baseline)")

    mod = cfgreg.get_arch(args.arch)
    cfg = mod.smoke_config() if args.smoke else mod.full_config()
    if not hasattr(cfg, "n_layers"):
        raise SystemExit(f"{args.arch} is not an LM arch; serving loop is "
                         "for autoregressive decoders (see DESIGN.md "
                         "§Arch-applicability)")
    cfg = type(cfg)(**{**cfg.__dict__, "max_seq_len": 768}) \
        if args.smoke else cfg
    params = tx.init_params(cfg, jax.random.key(0))
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        state, step = mgr.restore({"params": params})
        params = state["params"]
        print(f"restored checkpoint step {step}")

    n_blocks = None
    slots = 1 + args.decoding_length
    if args.kv_layout == "paged":
        # size the pool to the workload's worst-case footprint instead of
        # lanes * max_seq_len (the paged memory win), with the SAME formula
        # the scheduler admits by
        from repro.serving.block_allocator import worst_case_pool_blocks
        n_blocks = args.kv_blocks or worst_case_pool_blocks(
            args.lanes, args.prefill_len, args.max_new, slots,
            cfg.max_seq_len, args.block_size)
    # ---- one validated spec instead of kwargs threaded through four layers
    ecfg = EngineConfig(
        lanes=args.lanes, prefill_len=args.prefill_len,
        decoding_length=args.decoding_length,
        branch_length=args.branch_length,
        eos_id=args.eos_id,
        backend=args.backend, prefill_backend=args.prefill_backend,
        decode_backend=args.decode_backend,
        kv_layout=args.kv_layout, block_size=args.block_size,
        n_blocks=n_blocks,
        default_params=SamplingParams(
            max_new_tokens=args.max_new, sample=args.sample,
            temperature=args.temperature),
        draft_policy=draft_policy,
        overlap_drafts=args.overlap_drafts,
        prefix_cache=args.prefix_cache,
        prefix_cache_blocks=args.prefix_cache_blocks or None,
        lane_shares=lane_shares,
        draft_budget_caps=draft_caps,
        autotune=args.autotune, sanitize=args.sanitize)

    corpus = SyntheticCorpus(PROFILES["antrag"], cfg.vocab_size, seed=0)
    prompt_cap = min(96, args.prefill_len)
    system_prompt = (corpus.sample()[0][:min(args.shared_prefix, prompt_cap)]
                     if args.shared_prefix > 0 else [])
    def _prompt():
        tail_cap = max(prompt_cap - len(system_prompt), 1)
        return list(system_prompt) + corpus.sample()[0][:tail_cap]
    reqs = [Request(prompt=_prompt(),
                    params=_request_params(args, i),
                    metadata={"i": i, "tenant": f"t{i % 2}"})
            for i in range(args.requests)]
    if args.trie_namespace_key:
        # scenario-scoped tries: each request speculates inside the trie
        # namespace its metadata names (per-request DraftPolicy override)
        for r in reqs:
            ns = str(r.metadata.get(args.trie_namespace_key, ""))
            r.params = dataclasses.replace(
                r.params,
                draft=dataclasses.replace(draft_policy, namespace=ns))

    if args.replicas > 1:
        if args.mode != "continuous":
            raise SystemExit("--replicas requires --mode continuous")
        if args.cancel_every:
            raise SystemExit("--cancel-every is a single-engine exercise; "
                             "drop it with --replicas")
        _run_fleet(args, ecfg, cfg, params, reqs, lane_shares)
        return

    engine = build_engine(ecfg, cfg, params)
    if args.warm_state:
        import os
        if os.path.exists(args.warm_state):
            engine.load_draft_state(args.warm_state)
            print(f"warm state loaded from {args.warm_state} "
                  f"(trie={len(engine.scheduler.sources['trie'].forest)} "
                  "nodes)")

    if args.mode == "lockstep":
        lock = LookaheadEngine(engine.fns, ecfg.lookahead(),
                               eos_id=ecfg.eos_id)
        t0 = time.time()
        tok = steps = 0
        for i in range(0, len(reqs), args.lanes):
            chunk = reqs[i:i + args.lanes]
            outs = lock.generate_batch_lockstep(
                [r.prompt for r in chunk],
                params=[r.params for r in chunk])
            for o in outs:
                tok += len(o.tokens)
                steps += o.stats.steps
        dt = time.time() - t0
        print(f"lockstep: {tok} tokens / {steps} steps "
              f"(EDL {tok/max(steps,1):.2f}) in {dt:.1f}s "
              f"-> {tok/dt:.1f} tok/s; trie={len(lock.trie)} nodes")
        return

    # ---------------------------------------------------- continuous serving
    rng = np.random.RandomState(0)
    if args.rate > 0:
        gaps = rng.exponential(1.0 / args.rate, size=len(reqs))
        arrivals = np.cumsum(gaps)
    else:
        arrivals = np.zeros(len(reqs))

    streamed = [0]          # tokens observed through handle callbacks
    handles = []
    cancelled = []
    # the loop's host spans feed the phase split printed below
    engine.scheduler.spans.enable()

    t0 = time.time()
    nxt = 0
    while nxt < len(reqs) or not engine.idle:
        now = time.time() - t0
        while nxt < len(reqs) and arrivals[nxt] <= now:
            h = engine.submit(reqs[nxt])
            h.on_token(lambda delta: streamed.__setitem__(
                0, streamed[0] + len(delta)))
            handles.append(h)
            if args.cancel_every and (nxt % args.cancel_every
                                      == args.cancel_every - 1):
                cancelled.append(h)
            nxt += 1
        if engine.idle:
            # open-loop gap: nothing in flight, wait for the next arrival
            time.sleep(min(max(arrivals[nxt] - now, 0.0), 0.05))
            continue
        engine.step()
        for h in cancelled:
            if not h.done:
                h.cancel()
    dt = time.time() - t0
    results = [h.result() for h in handles]

    live = [r for r in results if not r.cancelled]
    tok = sum(len(r.tokens) for r in live)
    steps = sum(r.stats.steps for r in live)
    lat = [r.latency_s for r in live]
    ttft = [r.ttft_s for r in live]
    st = engine.stats
    sched = engine.scheduler
    n_cancelled = sum(1 for r in results if r.cancelled)
    print(f"continuous: {tok} tokens / {len(live)} requests "
          f"({n_cancelled} cancelled, {streamed[0]} streamed deltas, "
          f"{st.decode_steps} device steps, EDL {tok/max(steps,1):.2f}, "
          f"occupancy {st.occupancy:.2f}) in {dt:.1f}s -> {tok/dt:.1f} tok/s")
    if sched.cache is not None:
        cache_mb = sum(v.nbytes for v in sched.cache.values()) / 2**20
        extra = (f", peak {st.peak_blocks} blocks, "
                 f"{st.block_waits} block-waits"
                 if args.kv_layout == "paged" else "")
        print(f"kv cache [{args.kv_layout}]: {cache_mb:.1f} MiB{extra}")
    if args.prefix_cache:
        print(f"prefix cache: {st.prefix_hits}/{st.prefix_lookups} hits "
              f"({st.prefix_hit_rate:.0%}), "
              f"{st.prefix_hit_tokens}/{st.prefix_prompt_tokens} prefill "
              f"tokens saved ({st.prefill_tokens_saved:.0%}), "
              f"{st.prefix_cow_forks} COW forks, "
              f"{sched.prefix.n_blocks} resident blocks, "
              f"{st.prefix_evicted_blocks} evicted")
    br = st.breakdown()
    mode = "overlap" if args.overlap_drafts else "serial"
    print(f"step breakdown [{mode}]: draft {br['host_draft_ms']:.2f} ms   "
          f"device {br['device_step_ms']:.2f} ms   "
          f"accept {br['accept_commit_ms']:.2f} ms   "
          f"hidden {br['hidden_host_ms']:.2f} ms   "
          f"{br['syncs_per_step']:.1f} sync/step")
    _print_spans(engine.scheduler.spans)
    # per-tenant deployments report latency through namespace_summary():
    # pooled percentiles over all requests let a hot tenant's volume dilute
    # a cold tenant's p99 (the SLO the shares exist to protect), so the
    # pooled lines only headline single-tenant runs
    ns_sum = st.namespace_summary()
    multi_tenant = bool(lane_shares) or len(ns_sum) > 1
    if not multi_tenant:
        print(f"latency  p50 {_pct(lat, 50)*1e3:7.1f} ms   "
              f"p95 {_pct(lat, 95)*1e3:7.1f} ms   "
              f"p99 {_pct(lat, 99)*1e3:7.1f} ms")
    else:
        print("latency: per-tenant percentiles below (pooled percentiles "
              "would dilute cold-tenant p99 under hot-tenant volume)")
    forest = engine.scheduler.sources["trie"].forest
    if not multi_tenant:
        print(f"ttft     p50 {_pct(ttft, 50)*1e3:7.1f} ms   "
              f"p95 {_pct(ttft, 95)*1e3:7.1f} ms   "
              f"p99 {_pct(ttft, 99)*1e3:7.1f} ms")
    print(f"trie={len(forest)} nodes "
          f"across {len(forest.namespaces())} namespace(s)")
    # per-draft-source speculation telemetry (paper Table 3-style): how many
    # draft tokens each source placed and how many the model verified
    drafted: dict = {}
    accepted: dict = {}
    for r in results:
        for k, v in r.stats.source_drafted.items():
            drafted[k] = drafted.get(k, 0) + v
        for k, v in r.stats.source_accepted.items():
            accepted[k] = accepted.get(k, 0) + v
    if drafted:
        cells = [f"{name} {accepted.get(name, 0)}/{n} "
                 f"({accepted.get(name, 0) / max(n, 1):.0%})"
                 for name, n in sorted(drafted.items())]
        print(f"draft sources (accepted/drafted): {'   '.join(cells)}")
    # per-tenant SLO telemetry: latency percentiles, occupancy share and the
    # controller's per-source verdicts for every namespace seen this run
    if multi_tenant or args.autotune:
        for ns, row in ns_sum.items():
            print(f"tenant {ns or '<default>'!s:10s} "
                  f"fin {row['finished']:3d}/{row['submitted']:3d} "
                  f"({row['cancelled']} cancelled) "
                  f"occ {row['occupancy']:.2f}  "
                  f"p50 {row['p50_latency_s']*1e3:7.1f} ms  "
                  f"p99 {row['p99_latency_s']*1e3:7.1f} ms  "
                  f"ttft-p99 {row['p99_ttft_s']*1e3:7.1f} ms  "
                  f"queue-p99 {row['p99_queue_s']*1e3:7.1f} ms")
    if sched.sanitizer is not None:
        # reaching this line means every shadow check passed (violations
        # raise); report the audit so smoke logs show it actually ran
        n_tracked = len(sched.sanitizer.lifecycle._state)
        print(f"sanitizer: clean — {n_tracked} request lifecycles "
              "drained, block ledger and retrace manifest verified")
    if sched.autotuner is not None:
        for ns, srcs in sorted(sched.autotuner.snapshot().items()):
            cells = [f"{name} {'on' if s['enabled'] else 'OFF'} "
                     f"ema {s['ema']:.2f} "
                     f"({s['accepted']}/{s['drafted']}, "
                     f"{s['probes']} probes)"
                     for name, s in sorted(srcs.items())]
            print(f"autotune [{ns or '<default>'}]: {'   '.join(cells)}")
    if args.warm_state:
        engine.save_draft_state(args.warm_state)
        print(f"warm state saved to {args.warm_state}")


# -------------------------------------------------------------- fleet serving
def _run_fleet(args, ecfg, cfg, params, reqs, lane_shares) -> None:
    """Drive the synthetic arrival stream through an N-replica fleet
    (repro.fleet): namespace-affinity or round-robin routing, optional
    gossip cadence, warm-state load-at-start / save-at-exit, and an
    optional bit-identity verification against one reference engine."""
    import os

    from repro.fleet import EngineReplica, FleetRouter, GossipCoordinator

    def _builder(device):
        return build_engine(ecfg, cfg, jax.device_put(params, device))

    # one replica per chip, round-robin over the devices this process holds
    devices = jax.devices()
    replicas = []
    for i in range(args.replicas):
        dev = devices[i % len(devices)]
        replicas.append(EngineReplica(functools.partial(_builder, dev),
                                      replica_id=f"r{i}", device=dev))
    if args.warm_state and os.path.exists(args.warm_state):
        for rep in replicas:
            rep.load_draft_state(args.warm_state)
        print(f"warm state loaded from {args.warm_state} "
              f"(all {args.replicas} replicas)")
    router = FleetRouter(replicas, policy=args.routing,
                         max_queue_depth=args.fleet_queue_depth)
    gossip = GossipCoordinator(replicas, every=args.gossip_every)

    rng = np.random.RandomState(0)
    arrivals = (np.cumsum(rng.exponential(1.0 / args.rate, size=len(reqs)))
                if args.rate > 0 else np.zeros(len(reqs)))
    t0 = time.time()
    nxt = 0
    while nxt < len(reqs) or not router.idle:
        now = time.time() - t0
        while nxt < len(reqs) and arrivals[nxt] <= now:
            r = reqs[nxt]
            router.submit(r.prompt, r.params)
            nxt += 1
        if router.idle:
            time.sleep(min(max(arrivals[nxt] - now, 0.0), 0.05))
            continue
        router.step_all()
        gossip.tick()
    dt = time.time() - t0

    results = router.results()
    tok = sum(len(r["tokens"]) for r in results)
    fs = router.fleet_stats()
    print(f"fleet [{args.replicas}x {args.routing}]: {tok} tokens / "
          f"{len(results)} requests in {dt:.1f}s -> {tok/dt:.1f} tok/s; "
          f"routed {fs.routed} ({fs.affinity_hits} affinity, "
          f"{fs.spills} spills), {gossip.exchanges} gossip exchanges")
    for i, snap in enumerate(fs.replicas):
        print(f"  replica r{i}: {snap['finished']} finished / "
              f"{snap['admitted']} admitted, {snap['decode_steps']} device "
              f"steps, trie={snap['trie_nodes']} nodes")
    # fleet rollup reuses namespace_summary(): per-tenant percentiles over
    # the UNION of every replica's raw samples (never pooled across
    # tenants, never averaged across replicas)
    for ns, row in fs.namespace_summary().items():
        print(f"tenant {ns or '<default>'!s:10s} "
              f"fin {row['finished']:3d}/{row['submitted']:3d} "
              f"occ {row['occupancy']:.2f}  "
              f"p50 {row['p50_latency_s']*1e3:7.1f} ms  "
              f"p99 {row['p99_latency_s']*1e3:7.1f} ms  "
              f"ttft-p99 {row['p99_ttft_s']*1e3:7.1f} ms")
    for ns, accs in sorted(fs.source_acceptance().items()):
        cells = [f"{name} {rate:.0%}" for name, rate in sorted(accs.items())]
        print(f"acceptance [{ns or '<default>'}]: {'   '.join(cells)}")

    if args.verify_fleet:
        single = build_engine(ecfg, cfg, params)
        handles = [single.submit(Request(prompt=list(r.prompt),
                                         params=r.params)) for r in reqs]
        single.run()
        bad = sum(1 for h, res in zip(handles, results)
                  if h.result().tokens != res["tokens"])
        if bad:
            raise SystemExit(f"fleet outputs differ from the single-replica "
                             f"reference on {bad}/{len(reqs)} requests "
                             "(losslessness violation)")
        print(f"verify: fleet outputs bit-identical to the single-replica "
              f"reference ({len(reqs)} requests)")

    if args.warm_state:
        if len(replicas) > 1:
            gossip.exchange()   # fold every replica's warmth into one file
        replicas[0].save_draft_state(args.warm_state)
        print(f"warm state saved to {args.warm_state}")


if __name__ == "__main__":
    main()
