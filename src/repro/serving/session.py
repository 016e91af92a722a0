"""Build the jitted StepFns driving a LookaheadEngine for a transformer LM.

Compile-once contract (DESIGN.md §Compile-once shapes): for one session every
device function is traced for exactly one shape —

  * ``tree_step`` / ``fused_step`` / ``commit`` at the engine's tree width T
    and lane count B,
  * ``prefill`` at ``(B, prefill_len)`` for the initial admission cohort,
  * ``prefill_into_slot`` at ``(1, prefill_len)`` (lane index is a traced
    scalar, so admission into any slot reuses the same executable).

Without ``prefill_len`` the legacy pad-to-batch-max behaviour retraces per
distinct prompt length.

Per-request sampling (DESIGN.md §Serving API): every token-choosing member
additionally takes a trailing ``lane_params`` dict of per-lane device vectors
``{"greedy": (B,) bool, "temp": (B,) f32, "seed": (B,) u32}`` — traced
*inputs*, so one executable serves a lane pool mixing greedy and sampled
requests at distinct temperatures/seeds.  Call sites that omit it (legacy
tests, one-shot scripts) get the session-level defaults.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.request import SamplingParams, StepFns
from repro.models import attention as attn_backends
from repro.models import transformer as tx
from repro.serving.sampler import choose_tokens, choose_tokens_lanes


def _seed_from_key(base_key) -> int:
    """Legacy ``base_key`` compat: collapse a typed PRNG key to the u32 seed
    the per-lane mechanism derives its keys from.  XORs every key word so
    distinct keys (e.g. fold_in/split siblings differing only in the high
    word) keep distinct seeds; the sampled stream still changes across the
    upgrade — only determinism-per-session is preserved, which is all the
    lossless property needs."""
    words = np.asarray(jax.random.key_data(base_key)).ravel()
    return int(np.bitwise_xor.reduce(words.astype(np.uint32)))


def _expose(wrapper: Callable, jitted: Callable) -> Callable:
    """Give a thin python wrapper the jit introspection surface the
    compile-once tests (and resume tooling) rely on."""
    wrapper._cache_size = jitted._cache_size
    wrapper._jitted = jitted
    return wrapper


def make_session_fns(cfg: tx.TransformerConfig, params: tx.Params, *,
                     sample: bool = False, temperature: float = 1.0,
                     base_key: Optional[jax.Array] = None,
                     seed: Optional[int] = None,
                     sampling: str = "mixed",
                     slots: int = 1, pad_id: int = 0,
                     prefill_len: Optional[int] = None,
                     logits_transform: Optional[Callable] = None,
                     backend: Optional[str] = None,
                     prefill_backend: Optional[str] = None,
                     decode_backend: Optional[str] = None,
                     kv_layout: Optional[str] = None,
                     block_size: Optional[int] = None,
                     n_blocks: Optional[int] = None) -> StepFns:
    """Jitted prefill / prefill_into_slot / tree_step / commit steps over
    ``params``.

    Every jitted step takes ``params`` as its first, non-donated argument
    and the python wrappers pass it in: a closed-over array would be baked
    into each executable as an HLO constant (one weight copy per compiled
    step), and no step could be compiled ahead of time from shapes alone.

    ``slots`` is the tree width T = 1 + decoding_length the serving loop pads
    every draft to.  ``prefill_len`` fixes the prompt pad length so prefill
    paths compile once; prompts longer than it are rejected at submit time.
    ``logits_transform(logits, tokens, positions)`` optionally rewrites the
    step logits before token choice (the benchmarks' guided model) — it must
    stay a pure function of (token, position) to preserve losslessness.

    ``sample`` / ``temperature`` / ``seed`` set the *session defaults* a
    request inherits when submitted without its own ``SamplingParams``
    (``base_key`` is the deprecated spelling of ``seed``).  ``sampling``
    selects the token-choice lane: "mixed" (default) honors per-request
    params via traced per-lane vectors; "greedy" compiles an argmax-only
    session — fastest pure-greedy path, sampled requests are rejected at
    submit.

    ``backend`` overrides both attention phases at once;
    ``prefill_backend`` / ``decode_backend`` override one phase (names are
    resolved against the repro.models.attention registry — bad names fail
    here, not at trace time).

    ``kv_layout`` ("dense" | "paged") / ``block_size`` override the config's
    KV-cache layout; for the paged layout ``n_blocks`` sizes the shared
    block pool (None = the dense-equivalent worst case of
    lanes * ceil(max_seq_len / block_size) + 1 NULL block — serving stacks
    pass a smaller pool sized to the workload, which is the memory win).
    """
    overrides = {}
    if backend is not None:
        overrides["prefill_backend"] = backend
        overrides["decode_backend"] = backend
    if prefill_backend is not None:
        overrides["prefill_backend"] = prefill_backend
    if decode_backend is not None:
        overrides["decode_backend"] = decode_backend
    if kv_layout is not None:
        overrides["kv_layout"] = kv_layout
    if block_size is not None:
        overrides["kv_block_size"] = int(block_size)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    attn_backends.get_backend(cfg.prefill_backend)
    attn_backends.get_backend(cfg.decode_backend)
    if cfg.kv_layout not in ("dense", "paged"):
        raise ValueError(f"unknown kv_layout {cfg.kv_layout!r}")
    if cfg.kv_layout == "paged" and cfg.kv_block_size < 1:
        raise ValueError(f"kv_block_size={cfg.kv_block_size}")
    if sampling not in ("mixed", "greedy"):
        raise ValueError(f"sampling={sampling!r}: expected 'mixed' or "
                         "'greedy'")
    if sampling == "greedy" and sample:
        raise ValueError("sampling='greedy' builds an argmax-only session; "
                         "it cannot default to sample=True")
    if seed is None:
        seed = _seed_from_key(base_key) if base_key is not None else 0
    defaults = SamplingParams(sample=sample, temperature=float(temperature),
                              seed=int(seed)).validate()

    if sampling == "greedy":
        def _choose(logits, pred_positions, lane_params):
            del lane_params   # argmax-only session: params carry no entropy
            return choose_tokens(logits, pred_positions)
    else:
        def _choose(logits, pred_positions, lane_params):
            return choose_tokens_lanes(logits, pred_positions, lane_params)

    def _default_lane_params(n: int):
        return {
            "greedy": np.full((n,), not defaults.sample),
            "temp": np.full((n,), defaults.temperature, dtype=np.float32),
            "seed": np.full((n,), defaults.seed, dtype=np.uint32),
        }

    def _choose_last(tokens, lens, last_logits, lane_params):
        lg = last_logits[:, None, :]
        if logits_transform is not None:
            last_tok = jnp.take_along_axis(tokens, (lens - 1)[:, None],
                                           axis=1)
            lg = logits_transform(lg, last_tok, (lens - 1)[:, None])
        return _choose(lg, lens[:, None], lane_params)[:, 0]

    if cfg.kv_layout == "paged":
        @functools.partial(jax.jit, donate_argnums=())
        def _prefill(params, tokens, lens, block_tables, lane_params):
            cache = tx.init_paged_cache(cfg, tokens.shape[0], n_blocks)
            cache["block_tables"] = jnp.asarray(block_tables, jnp.int32)
            cache, last_logits = tx.prefill_paged(cfg, params, tokens, lens,
                                                  cache)
            return cache, _choose_last(tokens, lens, last_logits,
                                       lane_params)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _prefill_into_slot(params, cache, slot, tokens, lens,
                               lane_params):
            cache, last_logits = tx.prefill_into_slot_paged(
                cfg, params, cache, slot, tokens, lens)
            return cache, _choose_last(tokens, lens, last_logits,
                                       lane_params)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _tree_step(params, cache, cache_lens, tokens, pos, mask,
                       lane_params):
            cache, logits = tx.tree_step_paged(cfg, params, cache,
                                               cache_lens, tokens, pos, mask)
            if logits_transform is not None:
                logits = logits_transform(logits, tokens, pos)
            chosen = _choose(logits, pos + 1, lane_params)
            return cache, chosen

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _commit(cache, cache_lens, gather_idx, n_accept):
            return tx.commit_paged_cache(cfg, cache, cache_lens, gather_idx,
                                         n_accept)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _fused_step(params, cache, cache_lens, tokens, pos, mask, parent,
                        n_live, lane_params):
            cache, logits = tx.tree_step_paged(cfg, params, cache,
                                               cache_lens, tokens, pos, mask)
            if logits_transform is not None:
                logits = logits_transform(logits, tokens, pos)
            chosen = _choose(logits, pos + 1, lane_params)
            n_acc, acc_tok, kv_slots = tx.verify_accept_device(
                tokens, parent, n_live, chosen)
            cache, _ = tx.commit_paged_cache(cfg, cache, cache_lens,
                                             kv_slots, n_acc)
            return cache, tx.pack_step_result(n_acc, acc_tok, kv_slots)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _reset_blocks(cache, block_ids):
            return tx.reset_blocks(cache, block_ids)

        # Prefix-cache device surface.  ONE jitted suffix prefill serves
        # every bucket: jax.jit keys its executable cache on the padded
        # token shape, so the compile count equals the number of distinct
        # buckets actually used — never the number of requests (lane and
        # offset are traced scalars).
        @functools.partial(jax.jit, donate_argnums=(1,))
        def _prefill_suffix(params, cache, slot, tokens, offset, slen,
                            lane_params):
            cache, last_logits = tx.prefill_from_offset_paged(
                cfg, params, cache, slot, tokens, offset, slen)
            lg = last_logits[:, None, :]
            if logits_transform is not None:
                last_tok = jnp.take_along_axis(tokens, (slen - 1)[:, None],
                                               axis=1)
                lg = logits_transform(lg, last_tok,
                                      (offset + slen - 1)[:, None])
            return cache, _choose(lg, (offset + slen)[:, None],
                                  lane_params)[:, 0]

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _copy_block(cache, src, dst):
            return tx.copy_paged_block(cache, src, dst)

        _cap = int(prefill_len) if prefill_len else cfg.max_seq_len
        suffix_buckets, _b = [], 8
        while _b < _cap:
            suffix_buckets.append(_b)
            _b *= 2
        suffix_buckets.append(_cap)
        suffix_buckets = tuple(suffix_buckets)

        # preallocated staging buffers: jax copies numpy inputs at
        # dispatch, so reusing host scratch across calls is safe and
        # avoids three fresh allocations per suffix prefill
        _pad_bufs = {b: np.full((1, b), pad_id, np.int32)
                     for b in suffix_buckets}
        _off_buf = np.zeros((1,), np.int32)
        _len_buf = np.zeros((1,), np.int32)

        def prefill_suffix(cache, slot, tokens, offset, lane_params=None):
            """tokens (1, n): the UN-padded prompt suffix; offset: cached
            prefix length.  Pads n up to the smallest suffix bucket."""
            tokens = np.asarray(tokens, np.int32)
            n = tokens.shape[1]
            bucket = next(b for b in suffix_buckets if b >= n)
            padded = _pad_bufs[bucket]
            padded[0, :n] = tokens[0]
            padded[0, n:] = pad_id
            _off_buf[0] = offset
            _len_buf[0] = n
            if lane_params is None:
                lane_params = _default_lane_params(1)
            return _prefill_suffix(params, cache, slot, padded,
                                   _off_buf, _len_buf, lane_params)

        def copy_block(cache, src, dst):
            return _copy_block(cache, np.int32(src), np.int32(dst))

        def _init_cache(lanes: int):
            return tx.init_paged_cache(cfg, lanes, n_blocks)

        def prefill(tokens, lens, block_tables, lane_params=None):
            if lane_params is None:
                lane_params = _default_lane_params(tokens.shape[0])
            return _prefill(params, tokens, lens, block_tables, lane_params)

        def prefill_into_slot(cache, slot, tokens, lens, lane_params=None):
            if lane_params is None:
                lane_params = _default_lane_params(tokens.shape[0])
            return _prefill_into_slot(params, cache, slot, tokens, lens,
                                      lane_params)

        def tree_step(cache, cache_lens, tokens, pos, mask,
                      lane_params=None):
            if lane_params is None:
                lane_params = _default_lane_params(tokens.shape[0])
            return _tree_step(params, cache, cache_lens, tokens, pos, mask,
                              lane_params)

        def fused_step(cache, cache_lens, tokens, pos, mask, parent, n_live,
                       lane_params=None):
            if lane_params is None:
                lane_params = _default_lane_params(tokens.shape[0])
            return _fused_step(params, cache, cache_lens, tokens, pos, mask,
                               parent, n_live, lane_params)

        return StepFns(prefill=_expose(prefill, _prefill),
                       tree_step=_expose(tree_step, _tree_step),
                       fused_step=_expose(fused_step, _fused_step),
                       commit=_commit, slots=slots,
                       max_seq_len=cfg.max_seq_len, pad_id=pad_id,
                       init_cache=_init_cache,
                       prefill_into_slot=_expose(prefill_into_slot,
                                                 _prefill_into_slot),
                       reset_slot=None, prefill_len=prefill_len,
                       kv_layout="paged", block_size=cfg.kv_block_size,
                       n_blocks=n_blocks, reset_blocks=_reset_blocks,
                       prefill_suffix=_expose(prefill_suffix,
                                              _prefill_suffix),
                       copy_block=_expose(copy_block, _copy_block),
                       suffix_buckets=suffix_buckets,
                       per_lane_params=True, session_defaults=defaults,
                       sampling=sampling)

    @functools.partial(jax.jit, donate_argnums=())
    def _prefill(params, tokens, lens, lane_params):
        cache = tx.init_cache(cfg, tokens.shape[0])
        cache, last_logits = tx.prefill(cfg, params, tokens, lens, cache)
        return cache, _choose_last(tokens, lens, last_logits, lane_params)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def _prefill_into_slot(params, cache, slot, tokens, lens, lane_params):
        cache, last_logits = tx.prefill_into_slot(cfg, params, cache, slot,
                                                  tokens, lens)
        return cache, _choose_last(tokens, lens, last_logits, lane_params)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def _tree_step(params, cache, cache_lens, tokens, pos, mask, lane_params):
        cache, logits = tx.tree_step(cfg, params, cache, cache_lens,
                                     tokens, pos, mask)
        if logits_transform is not None:
            logits = logits_transform(logits, tokens, pos)
        chosen = _choose(logits, pos + 1, lane_params)
        return cache, chosen

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _commit(cache, cache_lens, gather_idx, n_accept):
        return tx.commit_cache(cache, cache_lens, gather_idx, n_accept)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def _fused_step(params, cache, cache_lens, tokens, pos, mask, parent,
                    n_live, lane_params):
        cache, logits = tx.tree_step(cfg, params, cache, cache_lens,
                                     tokens, pos, mask)
        if logits_transform is not None:
            logits = logits_transform(logits, tokens, pos)
        chosen = _choose(logits, pos + 1, lane_params)
        n_acc, acc_tok, kv_slots = tx.verify_accept_device(
            tokens, parent, n_live, chosen)
        cache, _ = tx.commit_cache(cache, cache_lens, kv_slots, n_acc)
        return cache, tx.pack_step_result(n_acc, acc_tok, kv_slots)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _reset_slot(cache, slot):
        return tx.reset_slot(cache, slot)

    def _init_cache(lanes: int):
        return tx.init_cache(cfg, lanes)

    def prefill(tokens, lens, lane_params=None):
        if lane_params is None:
            lane_params = _default_lane_params(tokens.shape[0])
        return _prefill(params, tokens, lens, lane_params)

    def prefill_into_slot(cache, slot, tokens, lens, lane_params=None):
        if lane_params is None:
            lane_params = _default_lane_params(tokens.shape[0])
        return _prefill_into_slot(params, cache, slot, tokens, lens,
                                  lane_params)

    def tree_step(cache, cache_lens, tokens, pos, mask, lane_params=None):
        if lane_params is None:
            lane_params = _default_lane_params(tokens.shape[0])
        return _tree_step(params, cache, cache_lens, tokens, pos, mask,
                          lane_params)

    def fused_step(cache, cache_lens, tokens, pos, mask, parent, n_live,
                   lane_params=None):
        if lane_params is None:
            lane_params = _default_lane_params(tokens.shape[0])
        return _fused_step(params, cache, cache_lens, tokens, pos, mask,
                           parent, n_live, lane_params)

    return StepFns(prefill=_expose(prefill, _prefill),
                   tree_step=_expose(tree_step, _tree_step),
                   fused_step=_expose(fused_step, _fused_step),
                   commit=_commit,
                   slots=slots, max_seq_len=cfg.max_seq_len, pad_id=pad_id,
                   init_cache=_init_cache,
                   prefill_into_slot=_expose(prefill_into_slot,
                                             _prefill_into_slot),
                   reset_slot=_reset_slot, prefill_len=prefill_len,
                   per_lane_params=True, session_defaults=defaults,
                   sampling=sampling)


__all__ = ["make_session_fns"]
