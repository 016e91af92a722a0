"""Pallas TPU kernel: block-table (paged) tree-verification attention.

Same online-softmax structure as ``tree_attention.py``, but the KV cache is
the paged block pool shared by every lane and every layer,
``(L, n_blocks, block_size, K*dh_p)``: the grid's innermost axis walks a
lane's *logical* blocks and two scalar-prefetched operands, the block table
and the layer index, steer each step's DMA to the physical block of that
layer in HBM.  Decode therefore never materializes a contiguous per-lane or
per-layer cache — the gather that the dense paged backend does with
``jnp.take`` happens inside the DMA engine's address computation instead
(PagedAttention, Kwon et al. SOSP 2023; flash-attention block-table decode).

Grid = (B, K, blocks_per_lane); the block axis is innermost/sequential and
carries (m, l, acc) scratch in VMEM.  Unallocated table entries point at the
reserved NULL block 0 — their rows are masked out, so the wasted DMA is the
only cost of fixed shapes (I2).  The pool is stored head-flattened with dh
padded to a lane multiple (``ops.lane_dh``), so a kv head is the 128-lane
column block ``h`` of a (block_size, dh_p) tile and the layer scan can pass
the whole pool by reference; the mask arrives as whole (Tp, block_size)
int32 tiles, the same layout as the dense kernel.  On TPU ``block_size``
must be a sublane multiple (16 for bf16); interpret mode (any non-TPU
platform) takes any size.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ops import (default_interpret, group_queries, lane_dh, mask_tiles,
                  ungroup_out)
from .tree_attention import _kernel, _vmem


def _paged_kernel(bt_ref, layer_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, g, n_blocks):
    # the block table and the layer only steer the index maps; the body
    # never reads them
    del bt_ref, layer_ref
    _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr,
            scale=scale, g=g, n_blocks=n_blocks)


def paged_tree_attention_grouped(q: jax.Array, k: jax.Array, v: jax.Array,
                                 block_tables: jax.Array, layer: jax.Array,
                                 mask: jax.Array, *,
                                 interpret: bool = False) -> jax.Array:
    """q (B, K, G*Tp, dh) group-major; k/v (L, n_blocks, block_size, K*dh)
    head-flattened pool; block_tables (B, blocks_per_lane) int32; layer
    (1,) int32, the pool layer to read; mask (B, blocks_per_lane, Tp,
    block_size) int32 tiles.  Returns (B, K, G*Tp, dh).  dh should be a
    multiple of 128 (the pool stores it padded).
    """
    from jax.experimental.pallas import tpu as pltpu

    B, K, TG, dh = q.shape
    bs = k.shape[2]
    bpl = block_tables.shape[1]
    Tp = mask.shape[2]
    assert k.shape[3] == K * dh, (k.shape, q.shape)
    assert mask.shape[1] == bpl and mask.shape[3] == bs, (mask.shape, bpl)
    g = TG // Tp
    grid = (B, K, bpl)
    kernel = functools.partial(_paged_kernel, scale=dh ** -0.5, g=g,
                               n_blocks=bpl)
    kv_spec = pl.BlockSpec((pl.squeezed, 1, bs, dh),
                           lambda b, h, j, bt, ly: (ly[0], bt[b, j], 0, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, TG, dh), lambda b, h, j, bt, ly: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, 1, Tp, bs), lambda b, h, j, bt, ly: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TG, dh),
                               lambda b, h, j, bt, ly: (b, h, 0, 0)),
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
    )(block_tables, layer, q, k, v, mask)


def paged_tree_attention(q: jax.Array, k_pool: jax.Array,
                         v_pool: jax.Array, block_tables: jax.Array,
                         mask: jax.Array, layer, *,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Public layout wrapper (mirrors ``ops.tree_attention``).

    q (B, T, H, dh); k/v pool (L, n_blocks, block_size, K*lane_dh(dh));
    block_tables (B, blocks_per_lane); mask (B, T, blocks_per_lane *
    block_size); layer: the pool layer to attend (a traced scalar inside
    the layer scan) → (B, T, H, dh)."""
    if interpret is None:
        interpret = default_interpret()
    return _paged_tree_attention(q, k_pool, v_pool, block_tables, mask,
                                 jnp.asarray(layer, jnp.int32).reshape(1),
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_tree_attention(q, k_pool, v_pool, block_tables, mask, layer, *,
                          interpret: bool):
    T, dh = q.shape[1], q.shape[3]
    K, bs = k_pool.shape[3] // lane_dh(dh), k_pool.shape[2]
    out = paged_tree_attention_grouped(
        group_queries(q, K), k_pool, v_pool, block_tables.astype(jnp.int32),
        layer, mask_tiles(mask, bs), interpret=interpret)
    return ungroup_out(out, T, dh)


__all__ = ["paged_tree_attention", "paged_tree_attention_grouped"]
