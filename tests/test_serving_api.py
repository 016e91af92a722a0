"""Request-centric serving API (DESIGN.md §Serving API).

Covers the ISSUE-4 acceptance surface:

  * per-request ``SamplingParams`` honored inside ONE co-batched scheduler
    run (mixed greedy + distinct temperatures/seeds + stop conditions),
    every request bit-identical to ``reference_decode`` under its own
    params, across the dense/paged × dense/pallas matrix;
  * streaming: concatenated handle deltas == ``result().tokens`` (iterator
    and callback styles);
  * ``cancel()`` mid-flight: lane + KV blocks released (allocator returns
    to empty), co-resident requests unperturbed;
  * compile-once (I2): per-lane params are traced inputs — mixed params
    never retrace;
  * lockstep-vs-continuous retirement alignment in the cache-overflow
    regime (the PR-3 known divergence, now pinned at the boundary);
  * user-input validation raises ValueError (not bare asserts).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (LookaheadConfig, LookaheadEngine, Request,
                        SamplingParams, reference_decode)
from repro.models.transformer import TransformerConfig, init_params
from repro.serving.api import EngineConfig, ServingEngine, build_engine
from repro.serving.scheduler import ContinuousScheduler

PREFILL = 32
VOCAB = 53
_CFG = TransformerConfig(n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
                         d_ff=64, vocab_size=VOCAB, max_seq_len=160)
_PARAMS = init_params(_CFG, jax.random.key(11))
_ECFG = EngineConfig(lanes=2, prefill_len=PREFILL, decoding_length=8,
                     branch_length=4)

CELLS = [("dense", "dense"), ("dense", "pallas"),
         ("paged", "dense"), ("paged", "pallas")]
_ENGINES = {}


def _engine(layout, backend) -> ServingEngine:
    key = (layout, backend)
    if key not in _ENGINES:
        _ENGINES[key] = build_engine(
            dataclasses.replace(_ECFG, kv_layout=layout, backend=backend,
                                block_size=8 if layout == "paged" else 64),
            _CFG, _PARAMS)
    return _ENGINES[key]


def _prompts(n, lo=4, hi=24, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, VOCAB - 1, size=rng.randint(lo, hi)))
            for _ in range(n)]


def _mix(n, seed=0, max_new=16, stop_sequences=()):
    """Greedy + sampled params at distinct temperatures/seeds."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        if i % 2:
            out.append(SamplingParams(
                max_new_tokens=max_new, sample=True,
                temperature=float(rng.choice([0.3, 0.7, 1.1])),
                seed=int(rng.randint(0, 10_000)),
                stop_sequences=stop_sequences))
        else:
            out.append(SamplingParams(max_new_tokens=max_new,
                                      stop_sequences=stop_sequences))
    return out


# ---------------------------------------------------------------- mixed params
@pytest.mark.parametrize("layout,backend", CELLS)
def test_mixed_params_lossless_per_request(layout, backend):
    """Acceptance: mixed greedy + distinct temperatures co-batched in one
    lane pool, each request bit-identical to reference_decode under its own
    params, on every (kv layout, attention backend) cell."""
    eng = _engine(layout, backend)
    prompts = _prompts(5, seed=3)
    plist = _mix(5, seed=4)
    handles = [eng.submit(Request(prompt=p, params=q))
               for p, q in zip(prompts, plist)]
    eng.run()
    for h, p, q in zip(handles, prompts, plist):
        assert h.result().tokens == reference_decode(eng.fns, p, params=q), \
            (layout, backend, q)


@pytest.mark.parametrize("layout,backend", [("dense", "dense"),
                                            ("paged", "pallas")])
def test_full_width_reference_replays_served_admission(layout, backend):
    """reference_decode(lanes=n, like=stats) decodes through the very
    programs an n-lane engine compiled — first-cohort prefill, slot
    prefill, or suffix prefill after the same prefix-cache hit — with no
    new executable, and agrees with the served tokens and with the width-1
    reference."""
    paged = layout == "paged"
    eng = build_engine(
        dataclasses.replace(_ECFG, kv_layout=layout, backend=backend,
                            block_size=8 if paged else 64,
                            prefix_cache=paged),
        _CFG, _PARAMS)
    head = _prompts(1, lo=12, hi=13, seed=40)[0]
    tails = _prompts(4, lo=3, hi=8, seed=41)
    # the short first request retires first, so the last one (same head)
    # is admitted mid-flight onto its cached prefix
    prompts = [head + tails[0], tails[1], tails[2], head + tails[3]]
    plist = _mix(4, seed=42)
    plist[0] = dataclasses.replace(plist[0], max_new_tokens=3)
    handles = [eng.submit(Request(prompt=p, params=q))
               for p, q in zip(prompts, plist)]
    eng.run()
    stats = [h.result().stats for h in handles]
    admitted = {s.prefill for s in stats}
    assert admitted == ({"prefill", "prefill_into_slot", "prefill_suffix"}
                        if paged else {"prefill", "prefill_into_slot"})
    members = ("prefill", "prefill_into_slot", "fused_step") + (
        ("prefill_suffix",) if paged else ())
    compiled = [getattr(eng.fns, m)._cache_size() for m in members]
    full = [reference_decode(eng.fns, p, params=q, lanes=_ECFG.lanes, like=s)
            for p, q, s in zip(prompts, plist, stats)]
    assert [getattr(eng.fns, m)._cache_size() for m in members] == compiled
    for h, f, p, q in zip(handles, full, prompts, plist):
        assert h.result().tokens == f == reference_decode(eng.fns, p,
                                                          params=q)
    with pytest.raises(ValueError, match="lanes="):
        reference_decode(eng.fns, prompts[0], params=plist[0], like=stats[0])


def test_seed_controls_sampled_stream():
    """Distinct seeds give distinct streams; equal seeds equal streams
    (sampling is a pure function of (seed, position, logits))."""
    eng = _engine("dense", "dense")
    prompt = _prompts(1, lo=10, hi=11, seed=8)[0]
    outs = {}
    for seed in (1, 2):
        q = SamplingParams(max_new_tokens=16, sample=True, temperature=0.9,
                           seed=seed)
        outs[seed] = eng.submit(prompt, params=q).result().tokens
        assert outs[seed] == reference_decode(eng.fns, prompt, params=q)
    q1 = SamplingParams(max_new_tokens=16, sample=True, temperature=0.9,
                        seed=1)
    assert eng.submit(prompt, params=q1).result().tokens == outs[1]
    assert outs[1] != outs[2]   # astronomically unlikely to collide


# ------------------------------------------------------------------- streaming
def test_stream_deltas_concatenate_to_result():
    """(a) iterator and callback streams both reproduce result().tokens."""
    eng = _engine("dense", "dense")
    prompts = _prompts(4, seed=5)
    plist = _mix(4, seed=6)
    handles = [eng.submit(Request(prompt=p, params=q))
               for p, q in zip(prompts, plist)]
    cb_tokens = {h.rid: [] for h in handles}
    for h in handles:
        h.on_token(lambda d, r=h.rid: cb_tokens[r].extend(d))
    # iterate the FIRST handle (pumps the whole pool), then drain the rest
    it_tokens = list(handles[0])
    eng.run()
    assert it_tokens == handles[0].result().tokens
    for h in handles:
        assert cb_tokens[h.rid] == h.result().tokens
        assert h.tokens == h.result().tokens
        assert h.done


def test_on_token_replays_backlog():
    eng = _engine("dense", "dense")
    h = eng.submit(_prompts(1, seed=9)[0], max_new_tokens=8)
    res = h.result()
    late = []
    h.on_token(late.extend)     # registered after completion: full replay
    assert late == res.tokens


# ---------------------------------------------------------------------- cancel
def test_cancel_mid_flight_releases_blocks_and_lanes():
    """(c) a cancelled request frees lane + KV blocks (allocator returns to
    empty) and never perturbs co-resident outputs."""
    eng = build_engine(
        dataclasses.replace(_ECFG, kv_layout="paged", block_size=8,
                            scrub_freed=True),
        _CFG, _PARAMS)
    prompts = _prompts(4, seed=13)
    plist = _mix(4, seed=14, max_new=24)
    refs = [reference_decode(eng.fns, p, params=q)
            for p, q in zip(prompts, plist)]
    handles = [eng.submit(Request(prompt=p, params=q))
               for p, q in zip(prompts, plist)]
    for _ in range(3):          # let the victim make some progress
        eng.step()
    victim = handles[1]
    assert not victim.done
    res = victim.cancel()
    assert res.cancelled and res.finish_reason == "cancelled"
    assert res.tokens == refs[1][:len(res.tokens)]   # prefix of its stream
    eng.run()
    for i, h in enumerate(handles):
        if h is victim:
            continue
        assert h.result().tokens == refs[i], "cancel perturbed a neighbor"
    alloc = eng.scheduler.allocator
    assert alloc.n_allocated == 0 and alloc.n_reserved == 0
    assert eng.scheduler.n_active == 0
    assert victim.cancel() is res     # idempotent after completion


def test_cancel_queued_request_never_admits():
    eng = _engine("dense", "dense")
    prompts = _prompts(3, seed=15)
    hs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    # lanes=2: the third request is queued; cancel it before any step
    res = hs[2].cancel()
    assert res.cancelled and res.tokens == []
    eng.run()
    for h, p in zip(hs[:2], prompts[:2]):
        assert h.result().tokens == reference_decode(eng.fns, p,
                                                     max_new_tokens=12)


# ----------------------------------------------------------------------- stops
def test_stop_sequence_truncation_matches_stepwise():
    """A tree step may accept past the stop match; host-side truncation must
    reproduce exactly what step-by-step decoding emits (I1)."""
    eng = _engine("dense", "dense")
    prompts = _prompts(4, seed=21)
    # derive stop strings that WILL fire: slices of the unconstrained output
    bare = [reference_decode(eng.fns, p, max_new_tokens=24) for p in prompts]
    plist = []
    for i, b in enumerate(bare):
        stops = ((tuple(b[5:7]),) if i % 2 else
                 (tuple(b[3:6]), (VOCAB + 7,)))   # 2nd never fires
        base = _mix(4, seed=22, max_new=24)[i]
        plist.append(dataclasses.replace(base, stop_sequences=stops))
    handles = [eng.submit(Request(prompt=p, params=q))
               for p, q in zip(prompts, plist)]
    eng.run()
    for h, p, q in zip(handles, prompts, plist):
        res = h.result()
        assert res.tokens == reference_decode(eng.fns, p, params=q), q
        if res.finish_reason == "stop":
            assert any(res.tokens[-len(s):] == list(s)
                       for s in q.stop_sequences if len(s) <= len(res.tokens))


def test_stop_token_ids_act_like_eos():
    eng = _engine("dense", "dense")
    prompt = _prompts(1, seed=23)[0]
    bare = reference_decode(eng.fns, prompt, max_new_tokens=20)
    stop_tok = bare[6]
    q = SamplingParams(max_new_tokens=20, stop_token_ids=(stop_tok,))
    res = eng.submit(prompt, params=q).result()
    assert res.tokens == reference_decode(eng.fns, prompt, params=q)
    assert res.tokens[-1] == stop_tok and res.finish_reason == "stop"
    assert len(res.tokens) <= len(bare)


# ------------------------------------------------------------------- I2 traces
def test_mixed_params_never_retrace():
    """(d) per-lane param vectors are traced inputs: serving mixed greedy /
    sampled / stop-constrained traffic compiles each member exactly once."""
    fresh = build_engine(_ECFG, _CFG, _PARAMS)
    for seed in (31, 32):
        prompts = _prompts(4, seed=seed)
        for p, q in zip(prompts, _mix(4, seed=seed, max_new=10,
                                      stop_sequences=((VOCAB + 5,),))):
            fresh.submit(Request(prompt=p, params=q))
        fresh.run()
    assert fresh.fns.prefill._cache_size() == 1
    assert fresh.fns.prefill_into_slot._cache_size() == 1
    assert fresh.fns.fused_step._cache_size() == 1
    assert fresh.fns.tree_step._cache_size() == 0  # unfused parity oracle only
    assert fresh.fns.commit._cache_size() == 0


# ------------------------------------------- overflow retirement (PR-3 fix)
def test_lockstep_and_continuous_agree_in_overflow_regime():
    """Regression (ISSUE 4 satellite): both serving loops retire at the SAME
    token when generation hits the KV-cache cap — truncation is
    token-granular (cache_token_limit), not step-granular."""
    cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                            d_ff=64, vocab_size=VOCAB, max_seq_len=96)
    params = init_params(cfg, jax.random.key(3))
    from repro.serving.session import make_session_fns
    fns = make_session_fns(cfg, params, slots=9, prefill_len=32)
    la = LookaheadConfig(decoding_length=8, branch_length=4)
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, VOCAB - 1, size=n))
               for n in (20, 31, 5, 28)]
    budgets = [200] * 4                      # all must hit the cache cap
    cont = LookaheadEngine(fns, la).generate_batch(prompts, budgets)
    lock = LookaheadEngine(fns, la).generate_batch_lockstep(prompts, budgets)
    for a, b in zip(cont, lock):
        assert a.tokens == b.tokens
        assert a.finish_reason == b.finish_reason == "cache"
    # pinned boundary: truncation lands exactly at the shared token cap
    for r, p in zip(cont, prompts):
        assert len(r.tokens) == 96 - 9 - len(p) + 1   # == cache_token_limit


def test_overflow_boundary_budget_pinned():
    """At budget == cache_token_limit the request finishes by 'length'; one
    more token flips it to 'cache' with the SAME output."""
    cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                            d_ff=64, vocab_size=VOCAB, max_seq_len=96)
    params = init_params(cfg, jax.random.key(3))
    from repro.serving.session import make_session_fns
    fns = make_session_fns(cfg, params, slots=9, prefill_len=32)
    la = LookaheadConfig(decoding_length=8, branch_length=4)
    prompt = list(np.random.RandomState(9).randint(1, VOCAB - 1, size=16))
    limit = 96 - 9 - 16 + 1
    at = LookaheadEngine(fns, la).generate(prompt, limit)
    over = LookaheadEngine(fns, la).generate(prompt, limit + 1)
    assert at.tokens == over.tokens
    assert at.finish_reason == "length"
    assert over.finish_reason == "cache"


# ------------------------------------------------------------------ validation
def test_budget_list_mismatch_raises_value_error():
    eng = _engine("dense", "dense")
    lae = LookaheadEngine(eng.fns, LookaheadConfig(decoding_length=8,
                                                   branch_length=4))
    with pytest.raises(ValueError, match="budget"):
        lae.generate_batch(_prompts(3, seed=41), [4, 5])


def test_long_prompt_raises_value_error_in_lockstep():
    cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                            d_ff=64, vocab_size=VOCAB, max_seq_len=160)
    params = init_params(cfg, jax.random.key(5))
    from repro.serving.session import make_session_fns
    fns = make_session_fns(cfg, params, slots=9, prefill_len=8)
    lae = LookaheadEngine(fns, LookaheadConfig(decoding_length=8,
                                               branch_length=4))
    with pytest.raises(ValueError, match="prefill_len"):
        lae.generate_batch_lockstep([_prompts(1, lo=12, hi=13, seed=42)[0]],
                                    4)


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="max_new_tokens"):
        SamplingParams(max_new_tokens=0).validate()
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(sample=True, temperature=0.0).validate()
    with pytest.raises(ValueError, match="stop sequence"):
        SamplingParams(stop_sequences=((),)).validate()
    # list inputs normalize to hashable tuples
    q = SamplingParams(stop_token_ids=[1, 2], stop_sequences=[[3, 4]])
    assert q.stop_token_ids == (1, 2) and q.stop_sequences == ((3, 4),)


def test_engine_config_validation():
    with pytest.raises(ValueError, match="lanes"):
        EngineConfig(lanes=0).validate()
    with pytest.raises(ValueError, match="kv_layout"):
        EngineConfig(kv_layout="sparse").validate()
    with pytest.raises(ValueError, match="backend"):
        EngineConfig(backend="cuda").validate()
    with pytest.raises(ValueError, match="sampling"):
        EngineConfig(sampling="nucleus").validate()
    with pytest.raises(ValueError, match="max_seq_len"):
        build_engine(EngineConfig(prefill_len=1024), _CFG, _PARAMS)


def test_greedy_only_session_rejects_sampled_requests():
    eng = build_engine(dataclasses.replace(_ECFG, sampling="greedy"),
                       _CFG, _PARAMS)
    with pytest.raises(ValueError, match="greedy"):
        eng.submit(_prompts(1, seed=44)[0],
                   params=SamplingParams(max_new_tokens=4, sample=True))
    # and the argmax-only path still serves greedy traffic losslessly
    p = _prompts(1, seed=45)[0]
    assert eng.submit(p, max_new_tokens=8).result().tokens == \
        reference_decode(eng.fns, p, max_new_tokens=8)


def test_bare_request_inherits_session_defaults():
    """Request(params=None) resolves to the engine's default_params at
    submit — including the sampled mode, not the library defaults."""
    eng = build_engine(
        dataclasses.replace(_ECFG, default_params=SamplingParams(
            max_new_tokens=9, sample=True, temperature=0.6, seed=17)),
        _CFG, _PARAMS)
    prompt = _prompts(1, seed=51)[0]
    res = eng.submit(Request(prompt=prompt)).result()
    assert res.tokens == reference_decode(
        eng.fns, prompt, params=SamplingParams(max_new_tokens=9, sample=True,
                                               temperature=0.6, seed=17))
    assert len(res.tokens) <= 9


def test_scheduler_drops_handles_at_retire():
    """Finished requests leave no handle entry behind (long-running server
    loops must not accrete per-request state)."""
    eng = _engine("dense", "dense")
    hs = [eng.submit(p, max_new_tokens=6) for p in _prompts(3, seed=52)]
    hs[2].cancel()                       # queued-cancel path too
    eng.run()
    assert eng.scheduler.handles == {}
    assert all(h.done for h in hs)       # callers still hold their results


def test_legacy_surfaces_keep_working():
    """Acceptance: old generate/generate_batch/submit call sites run
    unchanged through the compat wrappers."""
    eng = _engine("dense", "dense")
    lae = LookaheadEngine(eng.fns, LookaheadConfig(decoding_length=8,
                                                   branch_length=4))
    prompts = _prompts(3, seed=46)
    outs = lae.generate_batch(prompts, 10)
    assert [o.tokens for o in outs] == \
        [reference_decode(eng.fns, p, 10) for p in prompts]
    one = lae.generate(prompts[0], 10)
    assert one.tokens == outs[0].tokens
    sched = ContinuousScheduler(eng.fns,
                                LookaheadConfig(decoding_length=8,
                                                branch_length=4),
                                lanes=2, prefill_len=PREFILL)
    rid = sched.submit(prompts[0], 10)       # positional legacy submit
    assert isinstance(rid, int)
    res = sched.run()
    assert res[0].tokens == outs[0].tokens
