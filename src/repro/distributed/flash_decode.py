"""Sequence-parallel tree-decode attention (flash-decoding style).

Axis assignment is derived from the shapes at trace time:

  * batch → (pod, data) when divisible (decode_32k: B=128);
  * KV heads / Q heads → model when divisible (phi3-mini K=32, moonshot 16);
  * otherwise the KV **sequence** absorbs the leftover axes — batch=1
    long-context decode shards S over (pod, data[, model]), and GQA archs
    whose K doesn't divide TP=16 (qwen2 K=2, phi3-medium K=10, qwen3 K=4)
    shard S over model.  Partial attention per shard is combined with the
    numerically-stable log-sum-exp trick:

      M = pmax(m_l);  S = psum(e^{m_l-M} s_l);  O = psum(e^{m_l-M} o_l)

Collective cost per layer: one pmax + two psums of (B_loc, T, H_loc, dh) —
independent of S.  This replaces either an all-gather of a multi-GB KV cache
or 16× replicated attention compute (the two naive alternatives XLA picks).
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import active_mesh
from repro.models.layers import NEG_INF


def _derive_axes(mesh: Mesh, B: int, S: int, K: int, H: int):
    """Returns (batch_axes, seq_axes, head_axis)."""
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)
    heads_ok = tp > 1 and K % tp == 0 and H % tp == 0
    if dp > 1 and B % dp == 0 and B >= dp:
        batch_axes, seq_dp = dp_axes, ()
    else:
        batch_axes, seq_dp = (), dp_axes
    seq_axes = tuple(seq_dp)
    if not heads_ok and tp > 1:
        seq_axes = seq_axes + ("model",)
    # drop seq sharding if not divisible
    nseq = 1
    for a in seq_axes:
        nseq *= mesh.shape[a]
    if nseq <= 1 or S % nseq != 0:
        seq_axes = ()
    head_ax = "model" if heads_ok else None
    return batch_axes, seq_axes, head_ax


def make_flash_attend(mesh: Mesh, cache_lens: jax.Array,
                      tree_mask: jax.Array, score_f32: bool = True
                      ) -> Callable:
    """Returns attend(q, k_new, v_new, k_cache, v_cache)
    -> (attn_out, k_cache, v_cache) with sharded caches."""

    def attend(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
               k_cache: jax.Array, v_cache: jax.Array):
        B, T, H, dh = q.shape
        S, K = k_cache.shape[1], k_cache.shape[2]
        batch_axes, seq_axes, h_ax = _derive_axes(mesh, B, S, K, H)
        ba = batch_axes if batch_axes else None
        sa = seq_axes if seq_axes else None

        fn = functools.partial(_local_attend, seq_axes=seq_axes,
                               T=T, scale=dh ** -0.5, score_f32=score_f32)
        out, kc, vc = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(ba, None, h_ax, None),      # q
                      P(ba, None, h_ax, None),      # k_new
                      P(ba, None, h_ax, None),      # v_new
                      P(ba, sa, h_ax, None),        # k_cache
                      P(ba, sa, h_ax, None),        # v_cache
                      P(ba),                        # cache_lens
                      P(ba, None, None)),           # tree_mask
            out_specs=(P(ba, None, h_ax, None),
                       P(ba, sa, h_ax, None),
                       P(ba, sa, h_ax, None)),
            check_vma=False,
        )(q, k_new, v_new, k_cache, v_cache, cache_lens, tree_mask)
        return out, kc, vc

    return attend


def cache_partition_spec(mesh: Mesh, B: int, S: int, K: int, H: int) -> P:
    """PartitionSpec for a (L, B, S, K, dh) cache consistent with attend."""
    batch_axes, seq_axes, h_ax = _derive_axes(mesh, B, S, K, H)
    return P(None, batch_axes if batch_axes else None,
             seq_axes if seq_axes else None, h_ax, None)


def _local_attend(q, k_new, v_new, k_c, v_c, cache_lens, tree_mask, *,
                  seq_axes: Tuple[str, ...], T: int, scale: float,
                  score_f32: bool = True):
    B, _, Hl, dh = q.shape
    Sl, Kl = k_c.shape[1], k_c.shape[2]
    G = Hl // Kl
    # global offset of this shard's KV rows
    idx = jnp.zeros((), jnp.int32)
    for a in seq_axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    offset = idx * Sl

    # scatter the new draft KV rows that land in this shard.  NB: negative
    # indices wrap (Python semantics) BEFORE mode="drop" applies — redirect
    # them to Sl, which IS out of bounds and therefore dropped.
    bidx = jnp.arange(B)[:, None]
    loc = cache_lens[:, None] + jnp.arange(T)[None, :] - offset    # (B,T)
    loc = jnp.where((loc >= 0) & (loc < Sl), loc, Sl)
    k_c = k_c.at[bidx, loc].set(k_new.astype(k_c.dtype), mode="drop")
    v_c = v_c.at[bidx, loc].set(v_new.astype(v_c.dtype), mode="drop")

    # mask over local rows
    jglob = offset + jnp.arange(Sl)
    past = jglob[None, None, :] < cache_lens[:, None, None]
    rel = jglob[None, None, :] - cache_lens[:, None, None]          # (B,1,Sl)
    relc = jnp.clip(rel, 0, T - 1).astype(jnp.int32)
    tm = jnp.take_along_axis(tree_mask,
                             jnp.broadcast_to(relc, (B, T, Sl)), axis=2)
    mask = past | ((rel >= 0) & (rel < T) & tm)                     # (B,T,Sl)

    qg = q.reshape(B, T, Kl, G, dh)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k_c,
                   preferred_element_type=jnp.float32 if score_f32
                   else q.dtype) * scale
    s = s.astype(jnp.float32)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m_l = jnp.maximum(jnp.max(s, axis=-1), -1e30)                   # (B,K,G,T)
    p = jnp.where(mask[:, None, None], jnp.exp(s - m_l[..., None]), 0.0)
    s_l = jnp.sum(p, axis=-1)
    o_l = jnp.einsum("bkgts,bskh->bkgth", p.astype(v_c.dtype), v_c
                     ).astype(jnp.float32)
    if seq_axes:
        M = jax.lax.pmax(m_l, seq_axes)
        c = jnp.exp(m_l - M)
        s_g = jax.lax.psum(s_l * c, seq_axes)
        o_g = jax.lax.psum(o_l * c[..., None], seq_axes)
    else:
        s_g, o_g = s_l, o_l
    out = o_g / jnp.maximum(s_g[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, T, Hl, dh)
    return out.astype(q.dtype), k_c, v_c


class FlashDecodeBackend:
    """Attention backend (registry name ``flash_decode``) wrapping the
    sequence-parallel shard_map decode above.

    This folds the old ``decode_attn == "flash_decode"`` special case that
    lived inside ``transformer.tree_step`` into the common backend
    interface (repro.models.attention).  Prefill delegates to the dense
    reference math; the decode phase uses the sharded path whenever a mesh
    is active and otherwise degrades to dense — identical semantics, no
    shard_map.  Imports of the registry module are deferred to call time
    (attention.py imports this module to register the backend).
    """

    name = "flash_decode"

    def prefill_attention(self, cfg, q, k, v, positions, len_mask):
        from repro.models.attention import dense_prefill_attention
        return dense_prefill_attention(cfg, q, k, v, positions, len_mask)

    def make_tree_attend(self, cfg, cache_lens, tree_mask, S_max):
        mesh = active_mesh()
        if mesh is None:
            from repro.models.attention import get_backend
            return get_backend("dense").make_tree_attend(cfg, cache_lens,
                                                         tree_mask, S_max)
        return make_flash_attend(mesh, cache_lens, tree_mask,
                                 score_f32=cfg.attn_score_f32)

    def make_paged_tree_attend(self, cfg, block_tables, cache_lens,
                               tree_mask, slot_valid=None):
        """The paged pool is lane-agnostic, so the sequence-parallel
        shard_map layout does not apply; delegate to the dense gather path
        (identical semantics, no mesh)."""
        from repro.models.attention import get_backend
        return get_backend("dense").make_paged_tree_attend(
            cfg, block_tables, cache_lens, tree_mask, slot_valid)


__all__ = ["make_flash_attend", "cache_partition_spec", "FlashDecodeBackend"]
