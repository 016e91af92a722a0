"""Pallas TPU kernel: block-table (paged) tree-verification attention.

Same online-softmax structure as ``tree_attention.py``, but the KV cache is
the paged block pool ``(n_blocks, block_size, K, dh)`` shared by every lane:
the grid's innermost axis walks a lane's *logical* blocks and a scalar-
prefetched block table translates each step to the physical block the DMA
streams HBM→VMEM.  Decode therefore never materializes a contiguous
per-lane cache — the gather that the dense paged backend does with
``jnp.take`` happens inside the DMA engine's address computation instead
(PagedAttention, Kwon et al. SOSP 2023; flash-attention block-table decode).

Grid = (B, K, blocks_per_lane); the block axis is innermost/sequential and
carries (m, l, acc) scratch in VMEM.  Unallocated table entries point at the
reserved NULL block 0 — their rows are masked out, so the wasted DMA is the
only cost of fixed shapes (I2).  The pool is read through a free
(n_blocks, block_size, K*dh) view and the mask arrives as whole
(Tp, block_size) int32 tiles, the same layout as the dense kernel.  On TPU
``block_size`` must be a sublane multiple (16 for bf16); interpret mode
(any non-TPU platform) takes any size.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ops import (default_interpret, group_queries, kv_view, mask_tiles,
                  ungroup_out)
from .tree_attention import _kernel, _vmem


def _paged_kernel(bt_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, g, n_blocks):
    # the block table only steers the index maps; the body never reads it
    del bt_ref
    _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr,
            scale=scale, g=g, n_blocks=n_blocks)


def paged_tree_attention_grouped(q: jax.Array, k: jax.Array, v: jax.Array,
                                 block_tables: jax.Array, mask: jax.Array, *,
                                 interpret: bool = False) -> jax.Array:
    """q (B, K, G*Tp, dh) group-major; k/v (n_blocks, block_size, K*dh)
    head-flattened pool view; block_tables (B, blocks_per_lane) int32;
    mask (B, blocks_per_lane, Tp, block_size) int32 tiles.
    Returns (B, K, G*Tp, dh).  dh should be a multiple of 128 (pad
    upstream).
    """
    from jax.experimental.pallas import tpu as pltpu

    B, K, TG, dh = q.shape
    bs = k.shape[1]
    bpl = block_tables.shape[1]
    Tp = mask.shape[2]
    assert mask.shape[1] == bpl and mask.shape[3] == bs, (mask.shape, bpl)
    g = TG // Tp
    grid = (B, K, bpl)
    kernel = functools.partial(_paged_kernel, scale=dh ** -0.5, g=g,
                               n_blocks=bpl)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, TG, dh), lambda b, h, j, bt: (b, h, 0, 0)),
            pl.BlockSpec((1, bs, dh), lambda b, h, j, bt: (bt[b, j], 0, h)),
            pl.BlockSpec((1, bs, dh), lambda b, h, j, bt: (bt[b, j], 0, h)),
            pl.BlockSpec((1, 1, Tp, bs), lambda b, h, j, bt: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TG, dh),
                               lambda b, h, j, bt: (b, h, 0, 0)),
        scratch_shapes=[
            _vmem((TG, 128), jnp.float32),
            _vmem((TG, 128), jnp.float32),
            _vmem((TG, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, TG, dh), q.dtype),
        interpret=interpret,
    )(block_tables, q, k, v, mask)


def paged_tree_attention(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, block_tables: jax.Array,
                         mask: jax.Array, *,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Public layout wrapper (mirrors ``ops.tree_attention``).

    q (B, T, H, dh); k/v (n_blocks, block_size, K, dh);
    block_tables (B, blocks_per_lane); mask (B, T, blocks_per_lane *
    block_size) → (B, T, H, dh)."""
    if interpret is None:
        interpret = default_interpret()
    return _paged_tree_attention(q, k_cache, v_cache, block_tables, mask,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_tree_attention(q, k_cache, v_cache, block_tables, mask, *,
                          interpret: bool):
    T, dh = q.shape[1], q.shape[3]
    K, bs = k_cache.shape[2], k_cache.shape[1]
    out = paged_tree_attention_grouped(
        group_queries(q, K), kv_view(k_cache), kv_view(v_cache),
        block_tables.astype(jnp.int32), mask_tiles(mask, bs),
        interpret=interpret)
    return ungroup_out(out, T, dh)


__all__ = ["paged_tree_attention", "paged_tree_attention_grouped"]
