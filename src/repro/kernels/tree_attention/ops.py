"""jit'd public wrapper for the tree-attention kernel.

Handles layout: (B, T, H, dh) q + (B, S, K, dh) cache → group-major
(B, K, G·Tp, dh) queries over a (B, S, K·dh) cache view, pads dh→multiple
of 128, T→Tp (multiple of 8) and S→multiple of block_s (padded rows are
masked out), cuts the mask into int32 (Tp, block_s) tiles, and
auto-detects the platform for interpret mode — the compiled Mosaic kernel
on TPU, the interpreter everywhere else."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .ref import tree_attention_ref
from .tree_attention import tree_attention_grouped


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def group_queries(q: jax.Array, K: int) -> jax.Array:
    """(B, T, H, dh) → group-major (B, K, G*Tp, dh_p): row g*Tp + t holds
    query head k*G + g of slot t; T padded to Tp (multiple of 8), dh to a
    multiple of 128, and pre-scaled so the kernel's padded-dh scale
    matches the true one."""
    B, T, H, dh = q.shape
    G = H // K
    qg = q.reshape(B, T, K, G, dh).transpose(0, 2, 3, 1, 4)
    qg = _pad_to(_pad_to(qg, 3, 8), 4, 128)
    dh_p = qg.shape[-1]
    return qg.reshape(B, K, -1, dh_p) * ((dh_p / dh) ** 0.5)


def ungroup_out(out: jax.Array, T: int, dh: int) -> jax.Array:
    """Inverse of ``group_queries`` on the kernel output → (B, T, H, dh)."""
    B, K, rows, dh_p = out.shape
    Tp = -(-T // 8) * 8
    out = out.reshape(B, K, rows // Tp, Tp, dh_p)[:, :, :, :T, :dh]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, -1, dh)


def lane_dh(dh: int) -> int:
    """Head size as the kernels read it: dh padded up to 128 lanes."""
    return -(-dh // 128) * 128


def kv_view(cache: jax.Array) -> jax.Array:
    """(..., K, dh) cache → (..., K*dh_p), dh padded to ``lane_dh(dh)``; a
    free reshape when dh is already a lane multiple (in row-major order: a
    tiled device layout may relayout it, under the ``kv_pool`` scope).
    The paged pool is stored in this layout, so only new rows pass here."""
    with jax.named_scope("kv_pool"):
        c = _pad_to(cache, cache.ndim - 1, 128)
        return c.reshape(c.shape[:-2] + (-1,))


def kv_unview(rows: jax.Array, K: int, dh: int) -> jax.Array:
    """Inverse of ``kv_view``: (..., K*dh_p) → (..., K, dh)."""
    return rows.reshape(rows.shape[:-1] + (K, -1))[..., :dh]


def mask_tiles(mask: jax.Array, bs: int) -> jax.Array:
    """(B, T, S) bool, S a multiple of bs → (B, S/bs, Tp, bs) int32 tiles
    (pad slots masked out)."""
    B, T, S = mask.shape
    m = _pad_to(mask.astype(jnp.int32), 1, 8, value=0)
    return m.reshape(B, -1, S // bs, bs).transpose(0, 2, 1, 3)


def default_interpret() -> bool:
    """Pallas TPU kernels compile only on TPU; interpret elsewhere."""
    return jax.default_backend() != "tpu"


def tree_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                   mask: jax.Array, *, block_s: int = 512,
                   interpret: Optional[bool] = None) -> jax.Array:
    """q (B, T, H, dh); k/v (B, S, K, dh); mask (B, T, S) → (B, T, H, dh)."""
    if interpret is None:
        interpret = default_interpret()
    return _tree_attention(q, k_cache, v_cache, mask, block_s=block_s,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def _tree_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    mask: jax.Array, *, block_s: int,
                    interpret: bool) -> jax.Array:
    T, dh = q.shape[1], q.shape[3]
    S, K = k_cache.shape[1], k_cache.shape[2]
    kp, vp = kv_view(k_cache), kv_view(v_cache)
    # S not divisible by block_s: pad S up to the block multiple (padded
    # rows masked out → exp(-inf) contributes nothing) instead of collapsing
    # to a single full-S block.  bs is capped at S rounded up to the lane
    # multiple so short caches don't pad all the way to block_s.
    bs = min(block_s, -(-S // 128) * 128)
    if S % bs:
        kp = _pad_to(kp, 1, bs)
        vp = _pad_to(vp, 1, bs)
        mask = _pad_to(mask, 2, bs, value=False)
    out = tree_attention_grouped(group_queries(q, K), kp, vp,
                                 mask_tiles(mask, bs), block_s=bs,
                                 interpret=interpret)
    return ungroup_out(out, T, dh)


def tree_attention_reference(q, k_cache, v_cache, mask):
    """Oracle with the public layout."""
    B, T, H, dh = q.shape
    K = k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, T, K, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, K, T * G, dh)
    out = tree_attention_ref(qg, k_cache, v_cache, mask)
    out = out.reshape(B, K, T, G, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, dh)


__all__ = ["tree_attention", "tree_attention_reference", "default_interpret",
           "lane_dh", "kv_view", "kv_unview"]
