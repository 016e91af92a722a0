"""Continuous batching vs lock-step batching on a mixed-length workload,
optionally swept over the attention-backend registry and the KV-cache
layouts (dense vs paged).

The workload alternates short and long ``max_new_tokens`` budgets.  Lock-step
serving chunks requests into fixed batches and every chunk drains at its
slowest member — short requests occupy a device lane doing nothing.  The
slot-based scheduler admits the next queued request into the freed lane
mid-flight, so the same device-step shapes deliver more tokens per wall
second.  Per-request outputs are asserted identical (losslessness is
independent of batch composition) — and, in matrix mode, identical across
every attention backend (dense / pallas / flash_decode) AND every KV layout
(registry I1 contract + DESIGN.md §Paged KV cache).

The paged runs size their block pool to the workload's worst-case footprint
(prompt + budget + tree width) instead of lanes * max_seq_len, so the
benchmark also reports peak KV-cache bytes per layout and asserts the paged
pool is strictly smaller at equal lane count.

    PYTHONPATH=src python -m benchmarks.bench_continuous_batch \
        --backends all --kv-layout dense,paged --queries 8 --max-new 32

Output CSV: name,us_per_token,tok/s | steps | occupancy
"""
from __future__ import annotations

import time
from typing import Sequence, Tuple

from benchmarks.common import (VOCAB, bench_model, emit,
                               make_dataset, make_guided_session_fns)
from repro.core import (DraftPolicy, LookaheadConfig, LookaheadEngine,
                        Request, SamplingParams, reference_decode)
from repro.serving.scheduler import ContinuousScheduler
from repro.serving.spans import step_table

PREFILL_LEN = 64
LANES = 4
BLOCK_SIZE = 64


def _mixed_params(budgets):
    """Per-request SamplingParams alternating greedy and sampled traffic
    (distinct temperatures/seeds) — seeded sampling is deterministic, so
    outputs must stay bit-identical across layouts/backends/disciplines."""
    return [SamplingParams(max_new_tokens=m) if i % 2 else
            SamplingParams(max_new_tokens=m, sample=True,
                           temperature=(0.4, 0.7, 1.0)[i % 3], seed=100 + i)
            for i, m in enumerate(budgets)]


def _continuous(fns, la, prompts, specs, lanes, draft_policy=None,
                overlap=False, spans=False, prefix_cache=False
                ) -> Tuple[list, float, object, int]:
    """One scheduler generation; ``specs`` are per-request budgets (ints,
    legacy submit) or SamplingParams (request-centric submit).  Returns the
    scheduler itself so callers can read stats AND (``spans``) its span
    log's per-step records."""
    sched = ContinuousScheduler(fns, la, lanes=lanes,
                                prefill_len=PREFILL_LEN,
                                draft_policy=draft_policy,
                                overlap_drafts=overlap,
                                prefix_cache=prefix_cache)
    if spans:
        sched.spans.enable()
    t0 = time.perf_counter()
    for p, s in zip(prompts, specs):
        if isinstance(s, SamplingParams):
            sched.submit_request(Request(prompt=list(p), params=s))
        else:
            sched.submit(p, s)
    out = sched.run()
    wall = time.perf_counter() - t0
    cache_bytes = sum(v.nbytes for v in sched.cache.values()) \
        if sched.cache is not None else 0
    return out, wall, sched, cache_bytes


def run(n_queries: int = 24, max_new: int = 96, lanes: int = LANES,
        backends: Sequence[str] = ("dense",),
        kv_layouts: Sequence[str] = ("dense",),
        draft_combos: Sequence[str] = ("trie", "prompt_copy",
                                       "trie+ngram")) -> None:
    # continuous batching only differs from lock-step when a queue exists
    # behind the lane pool; keep at least a 2x oversubscription
    lanes = max(2, min(lanes, n_queries // 2))
    cfg, params = bench_model()
    la = LookaheadConfig(decoding_length=16, branch_length=8)
    ds = make_dataset("antrag", n_queries, prompt_cap=PREFILL_LEN - 8)
    prompts = [p for p, _ in ds]
    # mixed-length: every other request is short (the continuous-batching case)
    budgets = [max_new if i % 2 else max(max_new // 8, 2)
               for i in range(len(prompts))]

    # --- lock-step baseline (dense backend): chunks of `lanes`, each chunk
    # drains at its slowest member
    fns = make_guided_session_fns(cfg, params, phase=2, slots=la.slots,
                                  prefill_len=PREFILL_LEN)
    warm_lock = LookaheadEngine(fns, la)
    warm_lock.generate_batch_lockstep(prompts[:lanes], 4)
    eng = LookaheadEngine(fns, la)
    t0 = time.perf_counter()
    lock_out = []
    lock_steps = 0
    for i in range(0, len(prompts), lanes):
        outs = eng.generate_batch_lockstep(prompts[i:i + lanes],
                                           budgets[i:i + lanes])
        lock_out.extend(outs)
        lock_steps += max(o.stats.steps for o in outs)
    lock_wall = time.perf_counter() - t0
    lock_tok = sum(len(o.tokens) for o in lock_out)
    lock_tps = lock_tok / lock_wall
    emit("batch_lockstep", lock_wall / lock_tok * 1e6,
         f"{lock_tps:.1f} tok/s | {lock_steps} batch-steps")

    # --- continuous: same lanes, admission queue keeps them full; one run
    # per (kv layout, attention backend), outputs asserted identical across
    # all of them.  Paged pools are sized to the workload's worst case, not
    # lanes * max_seq_len.
    from repro.serving.block_allocator import demand_blocks
    dense_eq_blocks = -(-cfg.max_seq_len // BLOCK_SIZE)
    per_lane_blocks = demand_blocks(PREFILL_LEN, max_new, la.slots,
                                    cfg.max_seq_len, BLOCK_SIZE)
    paged_blocks = 1 + lanes * per_lane_blocks
    layout_bytes = {}
    for layout in kv_layouts:
        for backend in backends:
            if layout == "dense" and backend == "dense":
                fns_b = fns
            else:
                fns_b = make_guided_session_fns(
                    cfg, params, phase=2, slots=la.slots,
                    prefill_len=PREFILL_LEN, backend=backend,
                    kv_layout=layout,
                    block_size=BLOCK_SIZE if layout == "paged" else None,
                    n_blocks=paged_blocks if layout == "paged" else None)
            warm, _, _, _ = _continuous(fns_b, la, prompts[:lanes],
                                        [4] * lanes, lanes)  # compile warmup
            cont_out, cont_wall, sched, cache_bytes = _continuous(
                fns_b, la, prompts, budgets, lanes)
            stats = sched.stats
            cont_tok = sum(len(o.tokens) for o in cont_out)
            layout_bytes[layout] = cache_bytes

            # --- losslessness across serving disciplines, backends, layouts
            assert len(lock_out) == len(cont_out)
            for a, b in zip(lock_out, cont_out):
                assert a.tokens == b.tokens, \
                    f"kv_layout {layout!r} / backend {backend!r} changed " \
                    "an output"
            assert cont_tok == lock_tok

            cont_tps = cont_tok / cont_wall
            tag = f"{layout}/{backend}"
            emit(f"batch_continuous[{tag}]", cont_wall / cont_tok * 1e6,
                 f"{cont_tps:.1f} tok/s | {stats.decode_steps} steps | "
                 f"occupancy {stats.occupancy:.2f}")
            emit(f"continuous_speedup[{tag}]", 0.0,
                 f"{cont_tps / lock_tps:.2f}x")
        extra = (f" | peak {stats.peak_blocks} blocks | "
                 f"{stats.block_waits} block-waits"
                 if layout == "paged" else "")
        emit(f"kv_cache_bytes[{layout}]", 0.0,
             f"{layout_bytes[layout] / 2**20:.2f} MiB{extra}")
    if "dense" in layout_bytes and "paged" in layout_bytes:
        # the strict-savings claim only holds when the workload footprint is
        # below max_seq_len; at the cap the paged pool costs one extra NULL
        # block (+ tables) for identical coverage
        if per_lane_blocks < dense_eq_blocks:
            assert layout_bytes["paged"] < layout_bytes["dense"], \
                layout_bytes
        emit("kv_cache_savings[paged/dense]", 0.0,
             f"{layout_bytes['dense'] / layout_bytes['paged']:.2f}x")

    # --- mixed per-request sampling traffic (request-centric API): greedy
    # and sampled requests at distinct temperatures/seeds co-batched in ONE
    # lane pool; seeded position-keyed sampling is deterministic, so every
    # (layout, backend) cell and the lock-step baseline must agree
    # bit-for-bit per request
    plist = _mixed_params(budgets)
    mixed_lock = LookaheadEngine(fns, la).generate_batch_lockstep(
        prompts, params=plist)
    for layout in kv_layouts:
        for backend in backends:
            if layout == "dense" and backend == "dense":
                fns_b = fns
            else:
                fns_b = make_guided_session_fns(
                    cfg, params, phase=2, slots=la.slots,
                    prefill_len=PREFILL_LEN, backend=backend,
                    kv_layout=layout,
                    block_size=BLOCK_SIZE if layout == "paged" else None,
                    n_blocks=paged_blocks if layout == "paged" else None)
            mixed_out, mixed_wall, msched, _ = _continuous(
                fns_b, la, prompts, plist, lanes)
            mstats = msched.stats
            for a, b in zip(mixed_lock, mixed_out):
                assert a.tokens == b.tokens, \
                    f"mixed sampling: kv_layout {layout!r} / backend " \
                    f"{backend!r} changed an output"
            mtok = sum(len(o.tokens) for o in mixed_out)
            emit(f"mixed_sampling[{layout}/{backend}]",
                 mixed_wall / max(mtok, 1) * 1e6,
                 f"{mtok / mixed_wall:.1f} tok/s | "
                 f"{mstats.decode_steps} steps | lossless-per-params ✓")

    # --- draft-source matrix (DESIGN.md §Draft sources): the same workload
    # speculating through trie-only / prompt-copy-only / merged policies —
    # the device step never changes, so every combination must reproduce the
    # lock-step baseline per request AND step-by-step reference decoding
    # (spot-checked on the first queries); only tok/s and acceptance move
    for combo in draft_combos:
        policy = DraftPolicy(sources=tuple(combo.split("+")))
        src_out, src_wall, ssched, _ = _continuous(
            fns, la, prompts, budgets, lanes, draft_policy=policy)
        sstats = ssched.stats
        assert len(src_out) == len(lock_out)
        for a, b in zip(lock_out, src_out):
            assert a.tokens == b.tokens, \
                f"draft sources {combo!r} changed an output"
        for q in range(min(3, len(prompts))):
            ref = reference_decode(fns, prompts[q], budgets[q])
            assert src_out[q].tokens == ref, \
                f"draft sources {combo!r} diverged from reference_decode " \
                f"on query {q}"
        stok = sum(len(o.tokens) for o in src_out)
        drafted: dict = {}
        accepted: dict = {}
        for o in src_out:
            for k, v in o.stats.source_drafted.items():
                drafted[k] = drafted.get(k, 0) + v
            for k, v in o.stats.source_accepted.items():
                accepted[k] = accepted.get(k, 0) + v
        acc = " ".join(f"{n}={accepted.get(n, 0)}/{d}"
                       for n, d in sorted(drafted.items())) or "-"
        emit(f"draft_sources[{combo}]", src_wall / max(stok, 1) * 1e6,
             f"{stok / src_wall:.1f} tok/s | {sstats.decode_steps} steps | "
             f"acc {acc} | lossless ✓")


def run_breakdown(n_queries: int = 16, max_new: int = 48, lanes: int = LANES,
                  json_out: str = None) -> dict:
    """``--breakdown``: per-step latency split for the fused single-sync
    decode step — host draft-building / device step / accept+commit /
    host work hidden inside the device flight window — serial vs
    ``overlap_drafts``, on both KV layouts.

    Asserts (a) outputs bit-identical between the two modes, (b) exactly
    ONE host sync per decode step in both (the packed accept array is the
    only device->host transfer on the hot path).  Emits CSV lines and
    optionally a JSON document (the BENCH trajectory seed) whose per-step
    records are the scheduler's serve.step spans and their phases.
    """
    import json

    lanes = max(2, min(lanes, n_queries // 2))
    cfg, params = bench_model()
    la = LookaheadConfig(decoding_length=16, branch_length=8)
    ds = make_dataset("antrag", n_queries, prompt_cap=PREFILL_LEN - 8)
    prompts = [p for p, _ in ds]
    budgets = [max_new if i % 2 else max(max_new // 8, 2)
               for i in range(len(prompts))]
    from repro.serving.block_allocator import demand_blocks
    paged_blocks = 1 + lanes * demand_blocks(PREFILL_LEN, max_new, la.slots,
                                             cfg.max_seq_len, BLOCK_SIZE)
    doc = {"bench": "continuous_batch_breakdown", "queries": n_queries,
           "max_new": max_new, "lanes": lanes,
           "slots": la.slots, "cells": {}}
    for layout in ("dense", "paged"):
        fns_b = make_guided_session_fns(
            cfg, params, phase=2, slots=la.slots, prefill_len=PREFILL_LEN,
            kv_layout=layout,
            block_size=BLOCK_SIZE if layout == "paged" else None,
            n_blocks=paged_blocks if layout == "paged" else None)
        outs = {}
        visible = {}
        for mode, overlap in (("serial", False), ("overlap", True)):
            _continuous(fns_b, la, prompts[:lanes], [4] * lanes, lanes,
                        overlap=overlap)                    # compile warmup
            out, wall, sched, _ = _continuous(fns_b, la, prompts, budgets,
                                              lanes, overlap=overlap,
                                              spans=True)
            st = sched.stats
            assert st.decode_syncs == st.decode_steps, (layout, mode)
            br = st.breakdown()
            outs[mode] = [o.tokens for o in out]
            # host time the device stream actually waits on per step: draft
            # building + accept/commit (overlap additionally reports the
            # bookkeeping it moved INTO the flight window as hidden ms)
            visible[mode] = br["host_draft_ms"] + br["accept_commit_ms"]
            tok = sum(len(t) for t in outs[mode])
            cell = {
                "decode_steps": st.decode_steps,
                "syncs_per_step": br["syncs_per_step"],
                "host_draft_ms": round(br["host_draft_ms"], 4),
                "device_step_ms": round(br["device_step_ms"], 4),
                "accept_commit_ms": round(br["accept_commit_ms"], 4),
                "hidden_host_ms": round(br["hidden_host_ms"], 4),
                "visible_host_ms": round(visible[mode], 4),
                "tokens_per_s": round(tok / wall, 2),
                "steps": [{k: round(v, 4) for k, v in r.items()
                           if k != "start_ns"}
                          for r in step_table(sched.spans.records())[:200]],
            }
            doc["cells"][f"{layout}/{mode}"] = cell
            step_ms = (br["host_draft_ms"] + br["device_step_ms"]
                       + br["accept_commit_ms"])
            emit(f"step_breakdown[{layout}/{mode}]", step_ms * 1e3,
                 f"draft {br['host_draft_ms']:.2f} ms | "
                 f"device {br['device_step_ms']:.2f} ms | "
                 f"accept {br['accept_commit_ms']:.2f} ms | "
                 f"hidden {br['hidden_host_ms']:.2f} ms | "
                 f"{br['syncs_per_step']:.1f} sync/step")
        assert outs["serial"] == outs["overlap"], layout   # bit-identical
        emit(f"overlap_host_ms[{layout}]", 0.0,
             f"visible {visible['serial']:.2f} -> {visible['overlap']:.2f} "
             "ms/step | lossless ✓")
    if json_out:
        with open(json_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {json_out}")
    return doc


def run_prefix(n_queries: int = 24, max_new: int = 48, lanes: int = LANES,
               shared_len: int = 40, json_out: str = None) -> dict:
    """``--prefix-cache``: radix prefix caching on a prefix-heavy stream.

    Every request opens with the same system prompt (``shared_len`` tokens)
    followed by a per-request tail — the RAG/chat shape the radix cache
    targets — plus a slice of divergent miss traffic.  Runs the paged
    scheduler with the cache off and on, asserts bit-identical outputs (and
    reference_decode on spot-checked queries), and reports hit rate,
    prefill-tokens-saved and tok/s.  A small block size (16) keeps block
    granularity well under the shared head so full-block sharing dominates.
    Emits CSV lines and optionally a JSON document (the BENCH_prefix seed).
    """
    import json

    from repro.serving.block_allocator import demand_blocks

    block_size = 16
    lanes = max(2, min(lanes, n_queries // 2))
    cfg, params = bench_model()
    la = LookaheadConfig(decoding_length=16, branch_length=8)
    shared_len = min(shared_len, PREFILL_LEN - 8)
    tail_cap = max(PREFILL_LEN - shared_len, 4)
    ds = make_dataset("antrag", n_queries + 1, prompt_cap=PREFILL_LEN - 8)
    system_prompt = ds[0][0][:shared_len]
    prompts = [system_prompt + p[:tail_cap - 1] for p, _ in ds[1:]]
    # ~1 in 6 requests is divergent miss traffic (no shared head)
    for i in range(0, len(prompts), 6):
        prompts[i] = ds[1 + i][0]
    budgets = [max_new if i % 2 else max(max_new // 8, 2)
               for i in range(len(prompts))]

    per_lane = demand_blocks(PREFILL_LEN, max_new, la.slots,
                             cfg.max_seq_len, block_size)
    # headroom beyond the lanes' worst case so cached prefixes stay resident
    paged_blocks = 1 + (lanes + 2) * per_lane
    fns = make_guided_session_fns(cfg, params, phase=2, slots=la.slots,
                                  prefill_len=PREFILL_LEN, kv_layout="paged",
                                  block_size=block_size,
                                  n_blocks=paged_blocks)
    doc = {"bench": "continuous_batch_prefix", "queries": len(prompts),
           "max_new": max_new, "lanes": lanes, "shared_len": shared_len,
           "block_size": block_size, "cells": {}}
    outs = {}
    tps = {}
    for mode, cached in (("uncached", False), ("cached", True)):
        _continuous(fns, la, prompts[:lanes * 2], [4] * (lanes * 2), lanes,
                    prefix_cache=cached)                     # compile warmup
        out, wall, sched, _ = _continuous(fns, la, prompts, budgets, lanes,
                                          prefix_cache=cached)
        st = sched.stats
        outs[mode] = [o.tokens for o in out]
        tok = sum(len(t) for t in outs[mode])
        tps[mode] = tok / wall
        cell = {"tokens_per_s": round(tps[mode], 2),
                "decode_steps": st.decode_steps,
                "occupancy": round(st.occupancy, 3)}
        if cached:
            cell.update(
                lookups=st.prefix_lookups, hits=st.prefix_hits,
                hit_rate=round(st.prefix_hit_rate, 4),
                hit_tokens=st.prefix_hit_tokens,
                prompt_tokens=st.prefix_prompt_tokens,
                prefill_tokens_saved=round(st.prefill_tokens_saved, 4),
                cow_forks=st.prefix_cow_forks,
                evicted_blocks=st.prefix_evicted_blocks,
                resident_blocks=sched.prefix.n_blocks)
        doc["cells"][mode] = cell
    # --- losslessness: cache on == cache off == reference, per request
    assert outs["cached"] == outs["uncached"], \
        "prefix cache changed an output"
    for q in range(min(3, len(prompts))):
        ref = reference_decode(fns, prompts[q], budgets[q])
        assert outs["cached"][q] == ref, \
            f"prefix cache diverged from reference_decode on query {q}"
    st = sched.stats
    assert st.prefill_tokens_saved >= 0.30, \
        f"prefix-heavy stream saved only {st.prefill_tokens_saved:.1%} " \
        "of prefill tokens (expected >= 30%)"
    emit("prefix_cache[off]", 0.0, f"{tps['uncached']:.1f} tok/s")
    emit("prefix_cache[on]", 0.0,
         f"{tps['cached']:.1f} tok/s | "
         f"hit {st.prefix_hits}/{st.prefix_lookups} "
         f"({st.prefix_hit_rate:.0%}) | "
         f"saved {st.prefix_hit_tokens}/{st.prefix_prompt_tokens} prefill "
         f"tokens ({st.prefill_tokens_saved:.0%}) | "
         f"{st.prefix_cow_forks} COW forks | "
         f"{st.prefix_evicted_blocks} evicted | lossless ✓")
    emit("prefix_cache_speedup", 0.0,
         f"{tps['cached'] / tps['uncached']:.2f}x")
    doc["prefill_tokens_saved"] = round(st.prefill_tokens_saved, 4)
    doc["speedup"] = round(tps["cached"] / tps["uncached"], 4)
    if json_out:
        with open(json_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {json_out}")
    return doc


def run_multi_tenant(n_hot: int = 16, n_svc: int = 8, max_new: int = 48,
                     lanes: int = LANES, json_out: str = None) -> dict:
    """``--multi-tenant``: SLO isolation for a latency-sensitive co-tenant.

    Two namespaces share one lane pool.  Tenant ``hot`` floods the queue at
    t=0 with long-budget requests whose draft policy includes a junk source
    (drafts chains the guided model never emits — pure host overhead, zero
    acceptance).  Tenant ``svc`` trickles short requests in mid-run.  Cell 1
    runs the legacy global-FIFO admission: svc waits behind the entire hot
    backlog.  Cell 2 turns on per-namespace lane shares, a draft-budget cap
    on hot, and the per-namespace autotuner.  Asserts (a) every request's
    output is bit-identical across both cells AND reference_decode on spot
    checks — scheduling policy is pure performance (I1); (b) svc p99 latency
    under shares is <= 0.6x the FIFO cell's; (c) the controller disabled the
    junk source on the hot namespace (quota driven to zero, retrieval cost
    skipped).  Emits CSV lines and optionally the BENCH_slo JSON seed.
    """
    import json

    from repro.core.draft_sources import DraftSource, register_source
    from repro.serving.scheduler import SchedulerStats  # noqa: F401

    class _JunkSource(DraftSource):
        """Drafts chains of token 1.  The guided bench model only ever
        emits tokens >= 2, so nothing this source proposes can verify —
        the autotuner's worst case: real retrieve cost, zero acceptance."""
        name = "junk"

        def retrieve(self, rid, context, *, budget, namespace=""):
            k = min(self.config.branch_length, budget)
            return ([[1] * k], [1.0]) if k >= 1 else ([], [])

    register_source("junk", _JunkSource)

    lanes = max(2, min(lanes, n_hot // 2))
    cfg, params = bench_model()
    la = LookaheadConfig(decoding_length=16, branch_length=8)
    ds = make_dataset("antrag", n_hot + n_svc, prompt_cap=PREFILL_LEN - 8)
    hot_policy = DraftPolicy(sources=("trie", "junk"),
                             namespace="hot").validate()
    svc_policy = DraftPolicy(sources=("trie",), namespace="svc").validate()
    svc_budget = max(max_new // 8, 4)
    hot_reqs = [Request(prompt=list(p), params=SamplingParams(
        max_new_tokens=max_new, draft=hot_policy))
        for p, _ in ds[:n_hot]]
    svc_reqs = [Request(prompt=list(p), params=SamplingParams(
        max_new_tokens=svc_budget, draft=svc_policy))
        for p, _ in ds[n_hot:]]
    svc_gap = 2          # scheduler steps between svc arrivals
    fns = make_guided_session_fns(cfg, params, phase=2, slots=la.slots,
                                  prefill_len=PREFILL_LEN)

    def _drive(shares, caps, autotune):
        """One online run: hot floods at t0, svc arrives every svc_gap
        decode steps.  Returns (rid->tokens, scheduler, wall_s)."""
        sched = ContinuousScheduler(fns, la, lanes=lanes,
                                    prefill_len=PREFILL_LEN,
                                    lane_shares=shares,
                                    draft_budget_caps=caps,
                                    autotune=autotune)
        t0 = time.perf_counter()
        for r in hot_reqs:
            sched.submit_request(Request(prompt=list(r.prompt),
                                         params=r.params))
        step = nxt = 0
        while nxt < len(svc_reqs) or not sched.idle:
            while nxt < len(svc_reqs) and step >= (nxt + 1) * svc_gap:
                sched.submit_request(Request(
                    prompt=list(svc_reqs[nxt].prompt),
                    params=svc_reqs[nxt].params))
                nxt += 1
            if sched.idle:        # hot drained before svc finished arriving
                step = (nxt + 1) * svc_gap
                continue
            sched.step()
            step += 1
        wall = time.perf_counter() - t0
        return ({rid: res.tokens for rid, res in sched.results.items()},
                sched, wall)

    _drive(None, None, False)                              # compile warmup
    doc = {"bench": "continuous_batch_multi_tenant", "hot": n_hot,
           "svc": n_svc, "lanes": lanes, "max_new": max_new,
           "svc_budget": svc_budget, "svc_gap_steps": svc_gap, "cells": {}}
    outs = {}
    p99 = {}
    cells = (("fifo", None, None, False),
             ("slo", {"hot": 0.5, "svc": 0.5}, {"hot": 8}, True))
    for mode, shares, caps, autotune in cells:
        outs[mode], sched, wall = _drive(shares, caps, autotune)
        st = sched.stats
        ns_sum = st.namespace_summary()
        p99[mode] = {ns: row["p99_latency_s"] for ns, row in ns_sum.items()}
        tok = sum(len(t) for t in outs[mode].values())
        cell = {"tokens_per_s": round(tok / wall, 2),
                "decode_steps": st.decode_steps,
                "namespaces": ns_sum}
        if autotune:
            cell["autotune"] = sched.autotuner.snapshot()
        doc["cells"][mode] = cell
        for ns, row in ns_sum.items():
            emit(f"tenant[{mode}/{ns}]", row["p99_latency_s"] * 1e6,
                 f"p50 {row['p50_latency_s'] * 1e3:.1f} ms | "
                 f"p99 {row['p99_latency_s'] * 1e3:.1f} ms | "
                 f"queue-p99 {row['p99_queue_s'] * 1e3:.1f} ms | "
                 f"occ {row['occupancy']:.2f}")

    # --- losslessness: scheduling policy never touches an output token
    assert outs["fifo"].keys() == outs["slo"].keys()
    for rid in outs["fifo"]:
        assert outs["fifo"][rid] == outs["slo"][rid], \
            f"lane shares / autotune changed request {rid}'s output"
    prompts = [list(r.prompt) for r in hot_reqs] + \
        [list(r.prompt) for r in svc_reqs]
    budgets = [max_new] * n_hot + [svc_budget] * n_svc
    for q in (0, n_hot, n_hot + n_svc - 1):
        ref = reference_decode(fns, prompts[q], budgets[q])
        assert outs["slo"][q] == ref, \
            f"multi-tenant cell diverged from reference_decode on rid {q}"

    # --- the controller zeroed the never-accepting source's quota on hot
    snap = sched.autotuner.snapshot()
    junk = snap["hot"]["junk"]
    assert not junk["enabled"] and junk["disables"] >= 1, snap
    assert junk["accepted"] == 0, snap
    assert snap["hot"]["trie"]["enabled"], snap

    # --- the SLO claim: shares cut the co-tenant's tail latency
    ratio = p99["slo"]["svc"] / max(p99["fifo"]["svc"], 1e-9)
    assert ratio <= 0.6, \
        f"svc p99 with shares is {ratio:.2f}x FIFO (expected <= 0.6x)"
    doc["svc_p99_ratio"] = round(ratio, 4)
    emit("svc_p99_ratio[slo/fifo]", 0.0, f"{ratio:.2f}x | junk OFF on hot "
         f"after {junk['drafted']} drafted/0 accepted | lossless ✓")
    if json_out:
        with open(json_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {json_out}")
    return doc


if __name__ == "__main__":
    import argparse

    from repro.models.attention import available_backends

    ap = argparse.ArgumentParser()
    ap.add_argument("--backends", default="dense",
                    help="comma-separated backend names, or 'all' for every "
                         f"registered backend ({', '.join(available_backends())})")
    ap.add_argument("--kv-layout", default="dense",
                    help="comma-separated KV layouts (dense, paged) or "
                         "'all' for both")
    ap.add_argument("--queries", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=96)
    ap.add_argument("--lanes", type=int, default=LANES)
    ap.add_argument("--draft-sources", default="trie,prompt_copy,trie+ngram",
                    help="comma-separated draft-source combinations; '+' "
                         "merges sources within one policy")
    ap.add_argument("--breakdown", action="store_true",
                    help="per-step latency breakdown (host draft / device "
                         "step / accept+commit / hidden), serial vs "
                         "--overlap-drafts, instead of the throughput sweep")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix-cache cell: a prefix-heavy stream "
                         "(shared system prompt + per-request tails) with "
                         "the cache off vs on; reports hit rate and "
                         "prefill-tokens-saved, asserts bit-identical")
    ap.add_argument("--shared-prefix", type=int, default=40,
                    help="with --prefix-cache: shared system-prompt length")
    ap.add_argument("--multi-tenant", action="store_true",
                    help="SLO cell: a hot tenant flooding the queue (with a "
                         "junk draft source) vs a latency-sensitive svc "
                         "tenant, global FIFO vs lane shares + draft caps + "
                         "per-namespace autotune; asserts bit-identical "
                         "outputs, svc p99 <= 0.6x and the junk source "
                         "disabled on the hot namespace")
    ap.add_argument("--json-out", default=None,
                    help="with --breakdown / --prefix-cache / "
                         "--multi-tenant: write the records and per-cell "
                         "means to this JSON file")
    args = ap.parse_args()
    if args.breakdown:
        run_breakdown(n_queries=args.queries, max_new=args.max_new,
                      lanes=args.lanes, json_out=args.json_out)
        raise SystemExit(0)
    if args.prefix_cache:
        run_prefix(n_queries=args.queries, max_new=args.max_new,
                   lanes=args.lanes, shared_len=args.shared_prefix,
                   json_out=args.json_out)
        raise SystemExit(0)
    if args.multi_tenant:
        run_multi_tenant(max_new=args.max_new, lanes=args.lanes,
                         json_out=args.json_out)
        raise SystemExit(0)
    names = (available_backends() if args.backends == "all"
             else tuple(args.backends.split(",")))
    layouts = (("dense", "paged") if args.kv_layout == "all"
               else tuple(args.kv_layout.split(",")))
    run(n_queries=args.queries, max_new=args.max_new, lanes=args.lanes,
        backends=names, kv_layouts=layouts,
        draft_combos=tuple(c for c in args.draft_sources.split(",") if c))
