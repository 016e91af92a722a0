"""Continuous-batching scheduler invariants (DESIGN.md §Scheduler).

I1: per-request losslessness — a request's tokens equal reference_decode
    output regardless of arrival order, slot assignment or co-batched
    requests (greedy AND position-keyed sample mode).
I2: fixed shapes — every StepFns member compiles exactly once per engine.
I3: the committed cache prefix of a lane equals the stepwise cache.
"""
import itertools

import jax
import numpy as np
import pytest

from repro.core import LookaheadConfig, LookaheadEngine, reference_decode
from repro.models.transformer import TransformerConfig, init_params
from repro.serving.scheduler import ContinuousScheduler
from repro.serving.session import make_session_fns

PREFILL = 48


@pytest.fixture(scope="module")
def fns():
    cfg = TransformerConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                            d_ff=128, vocab_size=101, max_seq_len=320)
    params = init_params(cfg, jax.random.key(0))
    return make_session_fns(cfg, params, slots=17, prefill_len=PREFILL)


@pytest.fixture(scope="module")
def sample_fns():
    cfg = TransformerConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                            d_ff=128, vocab_size=101, max_seq_len=320)
    params = init_params(cfg, jax.random.key(2))
    return make_session_fns(cfg, params, sample=True, temperature=0.8,
                            base_key=jax.random.key(7), slots=17,
                            prefill_len=PREFILL)


def _prompts(n, lo=8, hi=40, vocab=100, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, vocab, size=rng.randint(lo, hi)))
            for _ in range(n)]


def _la(**kw):
    base = dict(decoding_length=16, branch_length=6)
    base.update(kw)
    return LookaheadConfig(**base)


def test_scheduler_lossless_any_arrival_order(fns):
    """I1: same outputs for every submission order of the same request set."""
    prompts = _prompts(4, seed=11)
    refs = [reference_decode(fns, p, 24) for p in prompts]
    for order in itertools.permutations(range(4)):
        sched = ContinuousScheduler(fns, _la(), lanes=2, prefill_len=PREFILL)
        rids = {}
        for i in order:
            rids[sched.submit(prompts[i], 24)] = i
        res = sched.run()
        for r in res:
            assert r.tokens == refs[rids[r.rid]], order


def test_scheduler_lossless_mixed_budgets(fns):
    """Short requests leave mid-flight; late requests join freed slots; every
    output still equals the (budget-truncated) reference."""
    prompts = _prompts(7, seed=12)
    budgets = [3, 28, 1, 9, 28, 2, 14]
    refs = [reference_decode(fns, p, m) for p, m in zip(prompts, budgets)]
    sched = ContinuousScheduler(fns, _la(), lanes=2, prefill_len=PREFILL)
    for p, m in zip(prompts, budgets):
        sched.submit(p, m)
    res = sched.run()
    assert len(res) == len(prompts)
    for r, ref in zip(res, refs):
        assert r.tokens == ref
    # the pool really was reused: more requests than lanes were admitted
    assert sched.stats.admitted == len(prompts)
    assert sched.stats.finished == len(prompts)
    assert sched.stats.occupancy > 0.5


def test_scheduler_lossless_sampling(sample_fns):
    """I1 in sample mode: the position-keyed RNG makes sampling a pure
    function of (key, absolute position, logits) — batch composition and
    slot assignment must not leak into the stream."""
    prompts = _prompts(5, seed=13)
    refs = [reference_decode(sample_fns, p, 20) for p in prompts]
    sched = ContinuousScheduler(sample_fns, _la(decoding_length=12),
                                lanes=2, prefill_len=PREFILL)
    for p in prompts:
        sched.submit(p, 20)
    for r, ref in zip(sched.run(), refs):
        assert r.tokens == ref


@pytest.mark.parametrize("backend", ["dense", "pallas", "flash_decode"])
def test_scheduler_lossless_per_backend(backend):
    """I1 holds under every attention backend: scheduler outputs equal
    reference_decode run through the SAME backend, and equal the dense
    outputs bit-for-bit (registry contract, DESIGN.md §Attention
    backends)."""
    cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                            d_ff=64, vocab_size=53, max_seq_len=160)
    params = init_params(cfg, jax.random.key(3))
    prompts = _prompts(4, lo=4, hi=24, vocab=52, seed=21)
    outs = {}
    for name in ("dense", backend):
        fns_b = make_session_fns(cfg, params, slots=9, prefill_len=32,
                                 backend=name)
        refs = [reference_decode(fns_b, p, 12) for p in prompts]
        sched = ContinuousScheduler(fns_b, _la(decoding_length=8,
                                               branch_length=4),
                                    lanes=2, prefill_len=32)
        for p in prompts:
            sched.submit(p, 12)
        res = sched.run()
        for r, ref in zip(res, refs):
            assert r.tokens == ref, name
        outs[name] = [r.tokens for r in res]
    assert outs[backend] == outs["dense"]


def test_engine_wrapper_routes_through_scheduler(fns):
    """generate/generate_batch keep their contract on the scheduler path and
    agree with the legacy lock-step loop."""
    prompts = _prompts(3, seed=14)
    eng = LookaheadEngine(fns, _la())
    outs = eng.generate_batch(prompts, 24)
    eng2 = LookaheadEngine(fns, _la())
    locks = eng2.generate_batch_lockstep(prompts, 24)
    for a, b in zip(outs, locks):
        assert a.tokens == b.tokens
    one = LookaheadEngine(fns, _la()).generate(prompts[0], 24)
    assert one.tokens == outs[0].tokens


def test_step_fns_compile_once():
    """I2: varying prompt lengths, budgets and request counts never retrace
    the jitted step functions — one executable per (lanes, T) /
    (lanes, prefill_len) / (1, prefill_len) shape.  The decode hot path is
    the single-dispatch ``fused_step``; ``tree_step``/``commit`` stay cold
    (they are the unfused parity oracle and the lock-step loop's surface)."""
    cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                            d_ff=64, vocab_size=53, max_seq_len=160)
    params = init_params(cfg, jax.random.key(5))
    fresh = make_session_fns(cfg, params, slots=9, prefill_len=PREFILL)
    la = _la(decoding_length=8, branch_length=4)
    # several scheduler generations with different workloads, same lanes
    for seed, n, budget in [(40, 5, 12), (41, 3, 7), (42, 4, 20)]:
        sched = ContinuousScheduler(fresh, la, lanes=2, prefill_len=PREFILL)
        for p in _prompts(n, lo=4, hi=40, vocab=52, seed=seed):
            sched.submit(p, budget)
        sched.run()
    assert fresh.prefill._cache_size() == 1           # (lanes, prefill_len)
    assert fresh.prefill_into_slot._cache_size() == 1  # (1, prefill_len)
    assert fresh.fused_step._cache_size() == 1         # (lanes, T)
    assert fresh.tree_step._cache_size() == 0          # parity oracle only
    assert fresh.commit._cache_size() == 0


@pytest.mark.parametrize("overlap", [False, True], ids=["serial", "overlap"])
def test_decode_hot_path_one_sync_per_step(fns, overlap):
    """The fused decode step makes exactly ONE device->host pull (the
    packed accept array) per decode step, serial and overlap mode alike;
    admission pulls stay off the decode counter."""
    prompts = _prompts(6, seed=31)
    sched = ContinuousScheduler(fns, _la(), lanes=2, prefill_len=PREFILL,
                                overlap_drafts=overlap)
    for p in prompts:
        sched.submit(p, 16)
    res = sched.run()
    assert len(res) == len(prompts)
    st = sched.stats
    assert st.decode_steps > 0
    assert st.decode_syncs == st.decode_steps
    assert st.syncs_per_decode_step == 1.0
    # total pulls = decode steps + one first-token pull per admission batch
    # (the initial cohort is one batched pull; mid-flight admissions pull
    # once each) — strictly fewer than 2 per decode step overall
    assert st.host_syncs <= st.decode_steps + st.admitted
    # breakdown accrues on every decode step
    br = st.breakdown()
    assert br["device_step_ms"] > 0.0
    assert br["syncs_per_step"] == 1.0


def test_overlap_mode_bit_identical_to_serial(fns):
    """overlap_drafts defers bookkeeping but never changes tokens: same
    request set through serial and overlap schedulers, same outputs, and
    both equal reference_decode (I1)."""
    prompts = _prompts(6, seed=33)
    budgets = [3, 24, 1, 15, 24, 8]
    refs = [reference_decode(fns, p, m) for p, m in zip(prompts, budgets)]
    outs = {}
    for overlap in (False, True):
        sched = ContinuousScheduler(fns, _la(), lanes=2, prefill_len=PREFILL,
                                    overlap_drafts=overlap)
        for p, m in zip(prompts, budgets):
            sched.submit(p, m)
        res = sched.run()
        assert len(res) == len(prompts)
        outs[overlap] = [r.tokens for r in res]
        for r, ref in zip(res, refs):
            assert r.tokens == ref, overlap
        assert sched.stats.finished == len(prompts)
        assert not sched._retired and not sched._pending
    assert outs[True] == outs[False]


def test_reset_slot_scrubs_one_lane_only():
    """reset_slot zeroes exactly the freed lane's KV rows (debug scrub; I3
    means correctness never depends on it)."""
    cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                            d_ff=64, vocab_size=53, max_seq_len=160)
    params = init_params(cfg, jax.random.key(6))
    fns = make_session_fns(cfg, params, slots=9, prefill_len=16)
    toks = np.asarray(_prompts(2, lo=10, hi=11, vocab=52, seed=50),
                      dtype=np.int32)
    toks = np.pad(toks, ((0, 0), (0, 16 - toks.shape[1])))
    lens = np.asarray([10, 10], dtype=np.int32)
    cache, _ = fns.prefill(toks, lens)
    before = {k: np.asarray(v).copy() for k, v in cache.items()}
    cache = fns.reset_slot(cache, 1)
    after = {k: np.asarray(v) for k, v in cache.items()}
    for k in ("k", "v"):
        np.testing.assert_array_equal(after[k][:, 0], before[k][:, 0])
        assert not after[k][:, 1].any()


def test_prefill_into_slot_matches_batched_prefill(fns):
    """I3 at admission: admitting request r into lane l writes the same KV
    rows a batched prefill would have put there."""
    prompts = _prompts(3, lo=6, hi=20, seed=15)
    toks = np.zeros((3, PREFILL), dtype=np.int32)
    lens = np.zeros((3,), dtype=np.int32)
    for b, p in enumerate(prompts):
        toks[b, :len(p)] = p
        lens[b] = len(p)
    cache_ref, roots_ref = fns.prefill(toks, lens)
    cache_ref = {k: np.asarray(v) for k, v in cache_ref.items()}
    roots_ref = np.asarray(roots_ref)

    cache = fns.init_cache(3)
    roots = []
    for lane in (2, 0, 1):   # deliberately out of order
        cache, r = fns.prefill_into_slot(
            cache, lane, toks[lane][None], lens[lane][None])
        roots.append((lane, int(np.asarray(r)[0])))
    for lane, root in roots:
        assert root == int(roots_ref[lane])
        n = int(lens[lane])
        np.testing.assert_allclose(
            np.asarray(cache["k"])[:, lane, :n],
            cache_ref["k"][:, lane, :n], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(cache["v"])[:, lane, :n],
            cache_ref["v"][:, lane, :n], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_cancel_of_pending_overlap_admission_defers_block_free(layout):
    """Satellite regression (use-after-free): cancelling a request whose
    overlap-mode admission prefill is still IN FLIGHT must finalize the
    host-visible side immediately but route the KV block free through the
    deferred-retirement queue — freeing at cancel time would let a
    same-iteration admission be handed block ids the in-flight prefill is
    still writing into.  Driven the only way it can happen in production:
    a co-resident request's first-token stream callback cancels a pending
    neighbor mid-settle."""
    cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                            d_ff=64, vocab_size=53, max_seq_len=160)
    params = init_params(cfg, jax.random.key(9))
    kw = (dict(kv_layout="paged", block_size=16, n_blocks=24)
          if layout == "paged" else {})
    fns = make_session_fns(cfg, params, slots=9, prefill_len=32, **kw)
    la = _la(decoding_length=8, branch_length=4)
    prompts = _prompts(4, lo=6, hi=20, vocab=52, seed=61)
    budgets = [30, 8, 8, 10]
    refs = [reference_decode(fns, p, m) for p, m in zip(prompts, budgets)]

    from repro.core.request import Request, SamplingParams
    sched = ContinuousScheduler(fns, la, lanes=3, prefill_len=32,
                                overlap_drafts=True, scrub_freed=True)

    def _submit(i):
        return sched.submit_request(Request(
            prompt=list(prompts[i]),
            params=SamplingParams(max_new_tokens=budgets[i])))

    ha = _submit(0)
    sched.step()                 # initial cohort: A active on lane 0
    assert sched.n_active == 1

    hb, hc = _submit(1), _submit(2)
    seen = {}

    def on_b_token(delta):
        if seen:
            return
        # fires inside _decode's pending-settle loop: C is still a pending
        # admission whose prefill dispatch is in flight on device
        assert any(rs.rid == hc.rid for rs in sched._pending.values())
        res = hc.cancel()
        seen["result"] = res
        seen["retired"] = any(rs.rid == hc.rid for rs in sched._retired)
        if sched.allocator is not None:
            # the bug under test: blocks must STILL be owned here — the
            # deferred free runs behind the next device dispatch
            seen["owned_at_cancel"] = sched.allocator.owns(hc.rid)

    hb.on_token(on_b_token)
    sched.step()                 # B+C go pending; B's settle cancels C
    assert seen, "B's first-token callback never fired"
    assert seen["result"].cancelled
    assert seen["result"].finish_reason == "cancelled"
    assert seen["retired"]
    if layout == "paged":
        assert seen["owned_at_cancel"]
        # the deferred free drained inside that same step's flight window
        assert not sched.allocator.owns(hc.rid)
    assert hc.done and hc.cancel() is seen["result"]   # idempotent
    assert not any(rs.rid == hc.rid for rs in sched._pending.values())

    # the freed lane is reusable: D admits into it and stays lossless
    hd = _submit(3)
    sched.run()
    assert ha.result().tokens == refs[0]
    assert hb.result().tokens == refs[1]
    assert hd.result().tokens == refs[3]
    assert not sched._retired and not sched._pending
    if layout == "paged":
        assert not sched.allocator._tables      # every rid fully released
    ns = sched.stats.ns("")
    assert ns.cancelled == 1 and ns.finished == 4


def test_breakdown_accrues_per_rider_steps(fns):
    """Telemetry skew regression: a request's step time is the sum over
    exactly the decode steps it rode — each serve.step span names its
    riders, so a short request co-resident with a long one rides only its
    own steps, and the overlap mode's hidden host work (serve.retire) lands
    in the steps that drained it, on their riders."""
    from repro.core.request import Request, SamplingParams
    from repro.core.draft_sources import DraftPolicy
    from repro.serving.spans import step_table
    prompts = _prompts(2, seed=62)
    sched = ContinuousScheduler(fns, _la(), lanes=2, prefill_len=PREFILL)
    sched.spans.enable()
    hs = sched.submit_request(Request(prompt=prompts[0], params=SamplingParams(
        max_new_tokens=4, draft=DraftPolicy(namespace="a"))))
    hl = sched.submit_request(Request(prompt=prompts[1], params=SamplingParams(
        max_new_tokens=28, draft=DraftPolicy(namespace="b"))))
    sched.run()
    short, long_ = hs.result(), hl.result()
    k = short.stats.steps - 1          # decode steps (start() counts one)
    n = long_.stats.steps - 1
    steps = [s for s in sched.spans.records() if s.name == "serve.step"]
    assert 0 < k < n == len(steps) == sched.stats.decode_steps
    assert [s for s in steps if short.rid in s.attr] == steps[:k]
    assert all(long_.rid in s.attr for s in steps)
    # the long request rode more wall time than the short one
    assert sum(s.ms for s in steps) > sum(s.ms for s in steps[:k])
    # the phases split the scheduler's totals
    rows = step_table(sched.spans.records())
    st = sched.stats
    assert sum(r["dispatch_ms"] + r["pull_ms"] for r in rows) == \
        pytest.approx(st.device_step_ms, rel=1e-6)
    assert sum(r["accept_ms"] for r in rows) == pytest.approx(
        st.accept_commit_ms, rel=1e-6)
    # per-namespace lane-step accounting matches the ride counts
    assert sched.stats.ns("a").lane_steps == k
    assert sched.stats.ns("b").lane_steps == n

    # overlap mode: bookkeeping drained inside the flight window is its own
    # phase of the draining step, which the long request rode
    sched2 = ContinuousScheduler(fns, _la(), lanes=2, prefill_len=PREFILL,
                                 overlap_drafts=True)
    sched2.spans.enable()
    sched2.submit_request(Request(
        prompt=prompts[0], params=SamplingParams(max_new_tokens=4)))
    h2l = sched2.submit_request(Request(
        prompt=prompts[1], params=SamplingParams(max_new_tokens=28)))
    sched2.run()
    recs = sched2.spans.records()
    rode = {s.id for s in recs
            if s.name == "serve.step" and h2l.result().rid in s.attr}
    retire = [s for s in recs if s.name == "serve.retire"]
    assert retire and {s.parent for s in retire} <= rode
    assert sched2.stats.hidden_host_ms > 0.0
    assert sum(s.ms for s in retire) == pytest.approx(
        sched2.stats.hidden_host_ms, rel=1e-6)
