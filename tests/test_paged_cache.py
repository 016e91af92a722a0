"""Paged KV-cache tests: layout/shape contracts, block-table attention
parity (gather path vs the Pallas streaming kernel), scheduler losslessness
vs the dense layout, reset-slot hygiene under block reuse, and compile-once
shapes (I2) for the paged step functions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LookaheadConfig, reference_decode
from repro.models import transformer as tx
from repro.models.attention import build_full_tree_mask
from repro.serving.scheduler import ContinuousScheduler
from repro.serving.session import make_session_fns

pytestmark = pytest.mark.paged

PREFILL = 32


def _model(seed=0, max_seq_len=160):
    cfg = tx.TransformerConfig(n_layers=2, d_model=32, n_heads=4,
                               n_kv_heads=2, d_ff=64, vocab_size=53,
                               max_seq_len=max_seq_len)
    return cfg, tx.init_params(cfg, jax.random.key(seed))


def _prompts(n, lo=4, hi=24, vocab=52, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, vocab, size=rng.randint(lo, hi)))
            for _ in range(n)]


# ------------------------------------------------------------------- layout
def test_init_paged_cache_shapes_and_axes():
    cfg, _ = _model()
    cfg = tx.TransformerConfig(**{**cfg.__dict__, "kv_layout": "paged",
                                  "kv_block_size": 16})
    assert tx.blocks_per_lane(cfg) == 10          # ceil(160 / 16)
    cache = tx.init_paged_cache(cfg, lanes=3, n_blocks=7)
    # the kernel's layout: K=2 heads of dh=8 padded to 128 lanes, flattened
    assert cache["k"].shape == (2, 7, 16, 2 * 128)
    assert cache["v"].shape == (2, 7, 16, 2 * 128)
    assert cache["block_tables"].shape == (3, 10)
    assert cache["block_tables"].dtype == jnp.int32
    axes = tx.cache_logical_axes(cfg)
    assert set(axes) == {"k", "v", "block_tables"}
    assert axes["k"] == axes["v"] == (None, None, None, "kv_heads")
    # a lane-multiple head size is stored unpadded
    wide = tx.TransformerConfig(**{**cfg.__dict__, "d_model": 256,
                                   "n_heads": 2, "n_kv_heads": 1})
    assert tx.init_paged_cache(wide, lanes=1, n_blocks=3)["k"].shape == \
        (2, 3, 16, 128)
    # default pool = dense-equivalent worst case + NULL block
    assert tx.init_paged_cache(cfg, lanes=2)["k"].shape[1] == 1 + 2 * 10


def test_paged_row_index_maps_through_tables():
    bt = jnp.asarray([[3, 1, 0], [2, 0, 0]], jnp.int32)
    pos = jnp.asarray([[0, 5, 16, 21], [1, 15, 16, 40]], jnp.int32)
    rows = tx.paged_row_index(bt, pos, 16)
    # lane 0: block 3 rows 0,5; block 1 rows 0,5
    np.testing.assert_array_equal(np.asarray(rows[0]), [48, 53, 16, 21])
    # lane 1: block 2 rows 1,15; block 0 (NULL) row 0; past-coverage
    # positions clip to the last table entry (NULL) -> garbage rows
    np.testing.assert_array_equal(np.asarray(rows[1]), [33, 47, 0, 8])


# ------------------------------------------------------------ kernel parity
def _to_pool(logical):
    """(L, nb, bs, K, dh) logical KV → the pool's (L, nb, bs, K*dh_p)."""
    from repro.kernels.tree_attention.ops import kv_view
    return kv_view(jnp.asarray(logical))


def _from_pool(pool, K, dh):
    """Inverse of ``_to_pool`` (the pad columns must stay zero)."""
    p = np.asarray(pool)
    p = p.reshape(p.shape[:-1] + (K, -1))
    assert not p[..., dh:].any()
    return p[..., :dh]


@pytest.mark.kernels
@pytest.mark.parametrize("dh,bs", [(8, 16), (16, 8), (8, 32)])
def test_paged_kernel_matches_gather_reference(dh, bs):
    """paged_tree_attention == dense attention over the gathered cache,
    for every layer of a pool whose layers hold different contents (the
    kernel must read the layer it is given)."""
    from repro.kernels.tree_attention.paged import paged_tree_attention
    from repro.models.layers import gqa_attention

    rng = np.random.RandomState(0)
    B, T, H, K, nb, bpl, L = 3, 5, 4, 2, 9, 4, 3
    S_virtual = bpl * bs
    q = jnp.asarray(rng.randn(B, T, H, dh), jnp.float32)
    k_log = rng.randn(L, nb, bs, K, dh).astype(np.float32)
    v_log = rng.randn(L, nb, bs, K, dh).astype(np.float32)
    k_pool, v_pool = _to_pool(k_log), _to_pool(v_log)
    # distinct physical blocks per lane; lane 2 mostly NULL
    bt = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7], [8, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([bs + 3, 2 * bs + 1, 4], jnp.int32)
    tree = np.zeros((B, T, T), dtype=bool)
    for b in range(B):
        tree[b] = np.tril(rng.rand(T, T) < 0.7) | np.eye(T, dtype=bool)
    mask = build_full_tree_mask(lens, jnp.asarray(tree), S_virtual)
    pos = jnp.broadcast_to(jnp.arange(S_virtual)[None], (B, S_virtual))
    rows = tx.paged_row_index(bt, pos, bs).reshape(-1)

    def ref(layer):
        kg, vg = (jnp.take(jnp.asarray(c[layer]).reshape(nb * bs, K, dh),
                           rows, axis=0).reshape(B, S_virtual, K, dh)
                  for c in (k_log, v_log))
        return np.asarray(gqa_attention(q, kg, vg, mask))

    refs = [ref(layer) for layer in range(L)]
    for layer in range(L):
        out = np.asarray(paged_tree_attention(q, k_pool, v_pool, bt, mask,
                                              layer))
        np.testing.assert_allclose(out, refs[layer], rtol=2e-5, atol=2e-5)
        other = refs[(layer + 1) % L]
        assert np.abs(out - other).max() > 1e-2     # layers really differ


# --------------------------------------------------------- prefill/commit I3
def test_paged_prefill_matches_dense_rows():
    """Admitting through block tables writes the same KV a dense prefill
    would, modulo the block permutation."""
    cfg, params = _model()
    pcfg = tx.TransformerConfig(**{**cfg.__dict__, "kv_layout": "paged",
                                   "kv_block_size": 16})
    prompts = _prompts(2, lo=10, hi=30, seed=5)
    toks = np.zeros((2, PREFILL), dtype=np.int32)
    lens = np.zeros((2,), dtype=np.int32)
    for b, p in enumerate(prompts):
        toks[b, :len(p)] = p
        lens[b] = len(p)
    dense_cache, dense_last = tx.prefill(cfg, params, jnp.asarray(toks),
                                         jnp.asarray(lens),
                                         tx.init_cache(cfg, 2))
    cache = tx.init_paged_cache(pcfg, lanes=2, n_blocks=9)
    bt = np.zeros((2, tx.blocks_per_lane(pcfg)), np.int32)
    bt[0, :3] = [2, 7, 1]
    bt[1, :3] = [5, 3, 8]
    cache["block_tables"] = jnp.asarray(bt)
    cache, last = tx.prefill_paged(pcfg, params, jnp.asarray(toks),
                                   jnp.asarray(lens), cache)
    np.testing.assert_allclose(np.asarray(last), np.asarray(dense_last),
                               rtol=1e-5, atol=1e-5)
    kf = _from_pool(cache["k"], 2, 8).reshape(2, 9 * 16, 2, 8)
    rows = np.asarray(tx.paged_row_index(
        jnp.asarray(bt), jnp.arange(PREFILL)[None].repeat(2, 0), 16))
    for b in range(2):
        n = int(lens[b])
        np.testing.assert_allclose(kf[:, rows[b, :n]].transpose(0, 1, 2, 3),
                                   np.asarray(dense_cache["k"])[:, b, :n],
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------- scheduler losslessness
@pytest.mark.parametrize("backend", ["dense", "pallas", "flash_decode"])
def test_paged_scheduler_lossless_per_backend(backend):
    """Paged serving equals reference decode through the same backend AND
    equals the dense layout bit-for-bit (the tentpole's I1 contract)."""
    cfg, params = _model(seed=3)
    prompts = _prompts(4, seed=21)
    la = LookaheadConfig(decoding_length=8, branch_length=4)
    outs = {}
    for layout in ("dense", "paged"):
        fns = make_session_fns(cfg, params, slots=9, prefill_len=PREFILL,
                               backend=backend, kv_layout=layout,
                               block_size=16)
        refs = [reference_decode(fns, p, 12) for p in prompts]
        sched = ContinuousScheduler(fns, la, lanes=2, prefill_len=PREFILL)
        for p in prompts:
            sched.submit(p, 12)
        res = sched.run()
        for r, ref in zip(res, refs):
            assert r.tokens == ref, (layout, backend)
        outs[layout] = [r.tokens for r in res]
    assert outs["paged"] == outs["dense"]


def test_paged_sampling_lossless():
    """Position-keyed sampling is layout-independent too."""
    cfg, params = _model(seed=2)
    prompts = _prompts(4, seed=13)
    fns = make_session_fns(cfg, params, sample=True, temperature=0.8,
                           base_key=jax.random.key(7), slots=9,
                           prefill_len=PREFILL, kv_layout="paged",
                           block_size=8)
    refs = [reference_decode(fns, p, 14) for p in prompts]
    sched = ContinuousScheduler(fns, LookaheadConfig(decoding_length=8,
                                                     branch_length=4),
                                lanes=2, prefill_len=PREFILL)
    for p in prompts:
        sched.submit(p, 14)
    for r, ref in zip(sched.run(), refs):
        assert r.tokens == ref


# ----------------------------------------------------- reset-slot hygiene
def test_paged_reset_scrubs_freed_blocks_only():
    """reset_blocks zeroes exactly the named physical blocks (NULL-padded
    ids are harmless); other requests' blocks are untouched."""
    cfg, params = _model()
    pcfg = tx.TransformerConfig(**{**cfg.__dict__, "kv_layout": "paged",
                                   "kv_block_size": 16})
    cache = tx.init_paged_cache(pcfg, lanes=2, n_blocks=6)
    filled = {k: (jnp.ones_like(v) if k != "block_tables" else v)
              for k, v in cache.items()}
    out = tx.reset_blocks(filled, np.asarray([2, 4, 0, 0], np.int32))
    k = np.asarray(out["k"])
    assert not k[:, 2].any() and not k[:, 4].any()
    for blk in (1, 3, 5):
        assert k[:, blk].all()


def test_paged_pool_ops_touch_the_rows_they_name():
    """commit, COW block copy, reset and the sanitizer's poison probe act on
    the same logical rows as on an unflattened (L, nb, bs, K, dh) pool:
    a numpy oracle over the logical pool predicts every row."""
    from repro.analysis.sanitizer import InvariantViolation, ShadowLedger
    cfg, _ = _model()
    pcfg = tx.TransformerConfig(**{**cfg.__dict__, "kv_layout": "paged",
                                   "kv_block_size": 4})
    L, nb, bs, K, dh = 2, 7, 4, 2, 8
    rng = np.random.RandomState(3)
    k_log = rng.randn(L, nb, bs, K, dh).astype(np.float32)
    v_log = rng.randn(L, nb, bs, K, dh).astype(np.float32)
    bt = np.asarray([[2, 5, 0, 0], [4, 1, 6, 0]], np.int32)
    cache = {"k": _to_pool(k_log), "v": _to_pool(v_log),
             "block_tables": jnp.asarray(bt)}

    # commit: lane b's logical row lens+j takes row lens+gather[j]
    lens = np.asarray([3, 6], np.int32)
    gather = np.asarray([[0, 2, 3], [0, 1, 4]], np.int32)
    n_acc = np.asarray([3, 2], np.int32)
    out, new_lens = tx.commit_paged_cache(pcfg, cache, jnp.asarray(lens),
                                          jnp.asarray(gather),
                                          jnp.asarray(n_acc))
    np.testing.assert_array_equal(np.asarray(new_lens), lens + n_acc)
    for name, log in (("k", k_log), ("v", v_log)):
        want = log.reshape(L, nb * bs, K, dh).copy()
        src = [bt[b, p // bs] * bs + p % bs
               for b in range(2) for p in lens[b] + gather[b]]
        dst = [bt[b, p // bs] * bs + p % bs
               for b in range(2) for p in lens[b] + np.arange(3)]
        want[:, dst] = log.reshape(L, nb * bs, K, dh)[:, src]
        np.testing.assert_array_equal(
            _from_pool(out[name], K, dh).reshape(L, nb * bs, K, dh), want)

    # copy-on-write fork of block 5 into block 3, every layer, K and V
    out = tx.copy_paged_block(cache, np.int32(5), np.int32(3))
    for name, log in (("k", k_log), ("v", v_log)):
        want = log.copy()
        want[:, 3] = log[:, 5]
        np.testing.assert_array_equal(_from_pool(out[name], K, dh), want)

    # scrub blocks 1 and 6 (0 pads the id list); the rest untouched
    out = tx.reset_blocks(cache, np.asarray([1, 6, 0], np.int32))
    for name, log in (("k", k_log), ("v", v_log)):
        want = log.copy()
        want[:, [0, 1, 6]] = 0
        np.testing.assert_array_equal(_from_pool(out[name], K, dh), want)

    # the poison probe reads whole blocks: clean after the scrub, and a
    # single stray row of a scrubbed block is caught
    ledger = ShadowLedger()
    ledger.on_scrubbed([1, 6])
    ledger.check_poison(out)
    k_bad = np.asarray(out["k"]).copy()
    k_bad[1, 6, bs - 1, K * 128 - 128] = 1.0     # layer 1, last row, head 1
    with pytest.raises(InvariantViolation, match="use-after-free write"):
        ledger.check_poison({**out, "k": k_bad})


def test_paged_finish_admit_interleave_with_scrub():
    """Regression for reset hygiene: with scrub-on-free enabled, a pool so
    small that a finishing request's blocks are immediately re-allocated to
    the next admission must not scrub the new request's KV.  (A lane/table-
    keyed scrub after re-allocation would; the scheduler scrubs by physical
    id at free time instead.)"""
    cfg, params = _model(seed=4)
    prompts = _prompts(6, lo=4, hi=20, seed=33)
    budgets = [2, 10, 1, 8, 3, 6]      # instant finishes interleave admits
    la = LookaheadConfig(decoding_length=8, branch_length=4)
    fns = make_session_fns(cfg, params, slots=9, prefill_len=PREFILL,
                           kv_layout="paged", block_size=16, n_blocks=7)
    refs = [reference_decode(fns, p, m) for p, m in zip(prompts, budgets)]
    sched = ContinuousScheduler(fns, la, lanes=2, prefill_len=PREFILL,
                                scrub_freed=True)
    for p, m in zip(prompts, budgets):
        sched.submit(p, m)
    res = sched.run()
    assert len(res) == len(prompts)
    for r, ref in zip(res, refs):
        assert r.tokens == ref
    # blocks really were recycled across requests (the hazard was live)
    assert sched.stats.admitted == len(prompts)
    assert sched.stats.peak_blocks <= 6
    # paged sessions must not expose the lane-keyed scrub at all
    assert fns.reset_slot is None and fns.reset_blocks is not None


def test_paged_near_max_prompt_raises_clearly():
    """Near-max-length prompts have no room for a tree step; dense degrades
    through the lock-step loop, paged (which has no lock-step fallback)
    must refuse with an actionable error instead of crashing incidentally."""
    from repro.core import LookaheadEngine
    cfg, params = _model(max_seq_len=64)
    la = LookaheadConfig(decoding_length=14, branch_length=4)
    prompt = list(range(1, 51))
    fns_d = make_session_fns(cfg, params, slots=la.slots)
    assert len(LookaheadEngine(fns_d, la).generate(prompt, 8).tokens) == 1
    fns_p = make_session_fns(cfg, params, slots=la.slots, kv_layout="paged",
                             block_size=16)
    with pytest.raises(ValueError, match="paged layout has no lock-step"):
        LookaheadEngine(fns_p, la).generate(prompt, 8)


# ------------------------------------------------------------ compile-once
def test_paged_step_fns_compile_once():
    """I2 for the paged layout: block-table edits change values, never
    shapes — one executable per step fn across varied workloads."""
    cfg, params = _model(seed=5)
    fns = make_session_fns(cfg, params, slots=9, prefill_len=PREFILL,
                           kv_layout="paged", block_size=16)
    la = LookaheadConfig(decoding_length=8, branch_length=4)
    for seed, n, budget in [(40, 5, 12), (41, 3, 7), (42, 4, 20)]:
        sched = ContinuousScheduler(fns, la, lanes=2, prefill_len=PREFILL)
        for p in _prompts(n, lo=4, hi=30, seed=seed):
            sched.submit(p, budget)
        sched.run()
    assert fns.prefill._cache_size() == 1
    assert fns.prefill_into_slot._cache_size() == 1
    assert fns.fused_step._cache_size() == 1
    assert fns.tree_step._cache_size() == 0   # unfused parity oracle only
    assert fns.commit._cache_size() == 0
