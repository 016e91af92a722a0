"""Pluggable attention backends for the transformer serving stack.

Every attention call site in ``repro.models.transformer`` dispatches through
this registry instead of hard-coding one math path.  A backend implements
the two serving phases:

  * ``prefill_attention(cfg, q, k, v, positions, len_mask)`` —
    full-sequence causal attention (train / ``prefill`` /
    ``prefill_into_slot``): q (B, S, H, dh), k/v (B, S, K, dh)
    → (B, S, H, dh).
  * ``make_tree_attend(cfg, cache_lens, tree_mask, S_max)`` — returns the
    per-layer tree-decode closure
    ``attend(q, k_new, v_new, k_cache, v_cache) -> (out, k_cache, v_cache)``
    that scatters the T draft-slot KV rows at ``cache_len + slot`` and
    attends the slots against the whole cache.
  * ``make_paged_tree_attend(cfg, block_tables, cache_lens, tree_mask)`` —
    the same over the paged pool: ``attend(q, k_new, v_new, k_pool, v_pool,
    layer) -> (out, k_pool, v_pool)`` takes the whole (L, n_blocks,
    block_size, K*dh_p) pool that the layer scan carries, writes only this
    layer's new rows and reads this layer's blocks through the tables.

Per-phase selection lives on ``TransformerConfig``: ``prefill_backend`` and
``decode_backend`` name a registered backend (the registry replaces the old
ad-hoc ``decode_attn`` string).  Registered here:

  dense        — jnp.einsum GQA over the full cache (reference semantics;
                 materializes the (B, T, S) score path per layer)
  pallas       — kernels/flash_prefill + kernels/tree_attention: blocked
                 HBM→VMEM streaming with an online-softmax accumulator
                 (compiled on TPU, interpret mode elsewhere)
  flash_decode — sequence-parallel shard_map decode
                 (repro.distributed.flash_decode); prefill delegates to
                 dense, and without an active mesh the decode phase
                 degrades to the dense math

Invariants every backend must uphold (DESIGN.md §Attention backends):

  * the mask semantics of ``build_full_tree_mask`` — past rows
    (j < cache_len) plus the ancestor-closure tree block;
  * the KV-scatter layout — draft slot i's KV lands at row
    ``cache_len + i`` of its lane (I3: the committed prefix is untouched);
  * fixed shapes (I2): nothing about the closure may depend on values, only
    on shapes, so every StepFns member still compiles once;
  * per-backend losslessness (I1): serving outputs must equal
    ``reference_decode`` run through the same backend bit-for-bit (asserted
    by the scheduler suite parameterized over backends).
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels.tree_attention.ops import kv_unview, kv_view
from repro.models.layers import (causal_prefill_mask, gqa_attention,
                                 gqa_attention_chunked)


# ------------------------------------------------------------ shared helpers
def scatter_kv(k_cache: jax.Array, v_cache: jax.Array, cache_lens: jax.Array,
               k: jax.Array, v: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Write the (B, T) draft-slot KV rows at ``cache_len + slot`` (I3)."""
    B, T = k.shape[:2]
    bidx = jnp.arange(B)[:, None]
    sidx = cache_lens[:, None] + jnp.arange(T)[None, :]
    with jax.named_scope("kv_pool"):
        k_cache = k_cache.at[bidx, sidx].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, sidx].set(v.astype(v_cache.dtype))
    return k_cache, v_cache


def pool_rows(pool: jax.Array, rows: jax.Array, layer=None) -> jax.Array:
    """Indices into the paged pool's flat (L*n_blocks*block_size, K*dh_p)
    view (a bitcast of (L, n_blocks, block_size, K*dh_p)) of the physical
    ``rows`` of ``layer``, or of every layer (layer-major) when None.
    Gathers and scatters of whole rows on that view keep the pool's own
    layout; indexing a layer axis beside the rows would have XLA relay the
    pool out to make the layer axis part of each row."""
    if layer is None:
        layer = jnp.arange(pool.shape[0])[:, None]
    return (layer * (pool.shape[1] * pool.shape[2])
            + rows.reshape(-1)).reshape(-1)


def scatter_kv_paged(k_pool: jax.Array, v_pool: jax.Array, layer,
                     slot_rows: jax.Array, k: jax.Array, v: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Paged twin of ``scatter_kv``: write the (B, T) draft-slot KV rows of
    ``layer`` at precomputed physical rows of the (L, n_blocks, block_size,
    K*dh_p) pool.  Only those rows are written: the pool carried through
    the layer scan is updated in place.  Rows are distinct across lanes
    (block ownership is exclusive); only NULL-block garbage of idle lanes
    ever collides."""
    KD = k_pool.shape[-1]
    idx = pool_rows(k_pool, slot_rows, layer)
    with jax.named_scope("kv_pool"):
        kr = kv_view(k).reshape(-1, KD).astype(k_pool.dtype)
        vr = kv_view(v).reshape(-1, KD).astype(v_pool.dtype)
        kf = k_pool.reshape(-1, KD).at[idx].set(kr)
        vf = v_pool.reshape(-1, KD).at[idx].set(vr)
        return kf.reshape(k_pool.shape), vf.reshape(v_pool.shape)


def build_full_tree_mask(cache_lens: jax.Array, tree_mask: jax.Array,
                         S_max: int) -> jax.Array:
    """(B, T, T) ancestor-closure → (B, T, S_max): past ∨ tree block."""
    B, T = tree_mask.shape[:2]
    j = jnp.arange(S_max)[None, None, :]                  # (1, 1, S)
    past = j < cache_lens[:, None, None]
    rel = j - cache_lens[:, None, None]                   # slot index
    in_block = (rel >= 0) & (rel < T)
    relc = jnp.clip(rel, 0, T - 1).astype(jnp.int32)      # (B, 1, S)
    # tm[b, i, s] = tree_mask[b, i, relc[b, 0, s]]
    tm = jnp.take_along_axis(
        tree_mask, jnp.broadcast_to(relc, (B, T, S_max)), axis=2)
    return past | (in_block & tm)


def dense_prefill_attention(cfg, q: jax.Array, k: jax.Array, v: jax.Array,
                            positions: jax.Array, len_mask: jax.Array
                            ) -> jax.Array:
    """Reference causal prefill: chunked scan when cfg.q_chunk divides S."""
    T = q.shape[1]
    if cfg.q_chunk and T % cfg.q_chunk == 0 and T > cfg.q_chunk:
        return gqa_attention_chunked(q, k, v, positions, len_mask,
                                     cfg.q_chunk)
    return gqa_attention(q, k, v, causal_prefill_mask(positions, len_mask))


# ---------------------------------------------------------------- backends
class AttentionBackend:
    """Base class doubling as the ``dense`` reference backend."""

    name = "dense"

    def prefill_attention(self, cfg, q, k, v, positions, len_mask
                          ) -> jax.Array:
        return dense_prefill_attention(cfg, q, k, v, positions, len_mask)

    def make_tree_attend(self, cfg, cache_lens: jax.Array,
                         tree_mask: jax.Array, S_max: int) -> Callable:
        full_mask = build_full_tree_mask(cache_lens, tree_mask, S_max)

        def attend(q, k, v, k_cache, v_cache):
            q = constrain(q, "batch", None, "heads", None)
            k_cache, v_cache = scatter_kv(k_cache, v_cache, cache_lens, k, v)
            out = gqa_attention(q, k_cache, v_cache, full_mask,
                                softmax_in_f32=cfg.attn_score_f32)
            return out, k_cache, v_cache

        return attend

    def _paged_geometry(self, cfg, block_tables: jax.Array,
                        cache_lens: jax.Array, tree_mask: jax.Array,
                        slot_valid=None):
        """Shared paged-decode precompute: the (B, T, S_virtual) full mask
        plus the physical rows for the draft-slot scatter and (for the
        gather path) every logical position of every lane.

        slot_valid (B, T) bool: slots to actually scatter; invalid slots'
        KV writes redirect to the NULL block (row 0).  Used by bucketed
        suffix prefill, whose pad slots may sit past the lane's table
        coverage where ``paged_row_index`` clipping would otherwise alias
        them onto the last real block."""
        from repro.models.transformer import paged_row_index
        bs = cfg.kv_block_size
        B, T = tree_mask.shape[:2]
        S_virtual = block_tables.shape[1] * bs
        full_mask = build_full_tree_mask(cache_lens, tree_mask, S_virtual)
        slots = cache_lens[:, None] + jnp.arange(T)[None, :]
        slot_rows = paged_row_index(block_tables, slots, bs)
        if slot_valid is not None:
            slot_rows = jnp.where(slot_valid, slot_rows, 0)
        all_pos = jnp.broadcast_to(jnp.arange(S_virtual)[None, :],
                                   (B, S_virtual))
        all_rows = paged_row_index(block_tables, all_pos, bs)
        return full_mask, slot_rows, all_rows, S_virtual

    def make_paged_tree_attend(self, cfg, block_tables: jax.Array,
                               cache_lens: jax.Array, tree_mask: jax.Array,
                               slot_valid=None) -> Callable:
        """Tree-decode closure over the paged pool
        ``attend(q, k, v, k_pool, v_pool, layer) -> (out, k_pool, v_pool)``
        — the whole (L, n_blocks, block_size, K*dh_p) pool is carried
        through the layer scan and ``layer`` picks this layer's rows.
        Reference semantics: gather each lane's blocks back into a
        contiguous (B, S_virtual) window and reuse the dense math (parity
        oracle for the streaming kernel; positions beyond a lane's coverage
        resolve to NULL-block garbage and are masked)."""
        full_mask, slot_rows, all_rows, S_virtual = self._paged_geometry(
            cfg, block_tables, cache_lens, tree_mask, slot_valid)
        B = tree_mask.shape[0]
        K, dh = cfg.n_kv_heads, cfg.dh

        def attend(q, k, v, k_pool, v_pool, layer):
            q = constrain(q, "batch", None, "heads", None)
            k_pool, v_pool = scatter_kv_paged(k_pool, v_pool, layer,
                                              slot_rows, k, v)
            KD = k_pool.shape[-1]
            idx = pool_rows(k_pool, all_rows, layer)
            with jax.named_scope("kv_pool"):
                kg, vg = (kv_unview(p.reshape(-1, KD)[idx]
                                    .reshape(B, S_virtual, KD), K, dh)
                          for p in (k_pool, v_pool))
            out = gqa_attention(q, kg, vg, full_mask,
                                softmax_in_f32=cfg.attn_score_f32)
            return out, k_pool, v_pool

        return attend


class PallasBackend(AttentionBackend):
    """Blocked Pallas kernels for both phases.

    The flash-prefill kernel is causal over the buffer index; the serving
    prefill paths satisfy ``positions == arange(S)``, and pad rows sit
    causally *after* every real query, so ``len_mask`` needs no separate
    treatment — real rows see exactly the dense mask, pad rows only feed
    cache rows beyond ``lens`` (garbage by I3, never attended).
    """

    name = "pallas"

    def prefill_attention(self, cfg, q, k, v, positions, len_mask
                          ) -> jax.Array:
        from repro.kernels.flash_prefill.ops import flash_prefill
        return flash_prefill(q, k, v)

    def make_tree_attend(self, cfg, cache_lens, tree_mask, S_max):
        from repro.kernels.tree_attention.ops import tree_attention
        full_mask = build_full_tree_mask(cache_lens, tree_mask, S_max)

        def attend(q, k, v, k_cache, v_cache):
            k_cache, v_cache = scatter_kv(k_cache, v_cache, cache_lens, k, v)
            out = tree_attention(q, k_cache, v_cache, full_mask)
            return out, k_cache, v_cache

        return attend

    def make_paged_tree_attend(self, cfg, block_tables, cache_lens,
                               tree_mask, slot_valid=None):
        """Streaming paged decode: the kernel walks each lane's logical
        blocks and the scalar-prefetched block table and layer index steer
        the DMA to the physical block of the carried pool — no contiguous
        per-lane or per-layer cache is ever materialized (the jnp.take of
        the dense path disappears into addressing)."""
        from repro.kernels.tree_attention.paged import paged_tree_attention
        full_mask, slot_rows, _, _ = self._paged_geometry(
            cfg, block_tables, cache_lens, tree_mask, slot_valid)

        def attend(q, k, v, k_pool, v_pool, layer):
            k_pool, v_pool = scatter_kv_paged(k_pool, v_pool, layer,
                                              slot_rows, k, v)
            out = paged_tree_attention(q, k_pool, v_pool, block_tables,
                                       full_mask, layer)
            return out, k_pool, v_pool

        return attend


# ---------------------------------------------------------------- registry
_REGISTRY: Dict[str, AttentionBackend] = {}


def register_backend(backend) -> None:
    """Register a backend instance under ``backend.name`` (last wins)."""
    _REGISTRY[backend.name] = backend


def get_backend(name: str):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown attention backend {name!r}; registered: "
            f"{', '.join(sorted(_REGISTRY))}") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register_backend(AttentionBackend())           # "dense"
register_backend(PallasBackend())

from repro.distributed.flash_decode import FlashDecodeBackend  # noqa: E402

register_backend(FlashDecodeBackend())

__all__ = ["AttentionBackend", "PallasBackend", "register_backend",
           "get_backend", "available_backends", "scatter_kv",
           "pool_rows", "scatter_kv_paged", "build_full_tree_mask",
           "dense_prefill_attention"]
