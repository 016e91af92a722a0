"""Pallas TPU kernel: tree-verification decode attention (the Lookahead hot
spot — paper §4.2/§4.3 VA step).

One forward step scores T = 1+decoding_length draft slots against a KV cache
of S rows plus the freshly-written draft rows.  Flash-decoding style: the
kernel streams KV blocks HBM→VMEM with an online-softmax accumulator, so the
(T, S) score matrix never exists in HBM — on v5e this turns the dense-path
3× score-tensor traffic into pure KV traffic (the roofline floor).

TPU mapping (vs. the paper's A100 version):
  * grid = (B, K, S/block_s); the S axis is the innermost, sequential
    dimension, carrying (m, l, acc) scratch in VMEM across iterations,
  * q rows for one kv-head group are laid out group-major, G blocks of
    Tp = T padded to a sublane multiple (8), so the (Tp, block_s) mask tile
    broadcasts over the groups with a tile-aligned reshape; dh is padded to
    a multiple of 128 lanes by ops.py,
  * the cache is read through a free (B, S, K*dh) view: a kv head is the
    128-lane column block ``h`` of a (block_s, dh) tile, so no block has a
    squeezed second-minor dimension (Mosaic refuses a (1, dh) head slice),
  * the tree mask enters as int32 tiles (B, S/block_s, Tp, block_s) —
    ancestor-closure for the draft region, causal for the cache region
    (built by ops.py / the serving layer); each tile is a whole array
    trailing dim pair, so any block_s the cache allows is legal,
  * masked-out blocks contribute zeros (exp(-inf)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, g: int, n_blocks: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (G*Tp, dh)
    k = k_ref[0].astype(jnp.float32)               # (bs, dh)
    v = v_ref[0].astype(jnp.float32)               # (bs, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    tm = mask_ref[0, 0]                            # (Tp, bs) int32
    tp, bs = tm.shape
    tm = jnp.broadcast_to(tm[None], (g, tp, bs)).reshape(g * tp, bs)
    mask = tm != 0                                 # (G*Tp, bs)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]                          # (TG, 1)
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)   # (TG, bs)
    alpha = jnp.exp(m_prev - m_new)                # (TG, 1)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def tree_attention_grouped(q: jax.Array, k: jax.Array, v: jax.Array,
                           mask: jax.Array, *, block_s: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q (B, K, G*Tp, dh) group-major; k/v (B, S, K*dh) head-flattened
    cache view; mask (B, S/block_s, Tp, block_s) int32 tiles (nonzero =
    attend).  Returns (B, K, G*Tp, dh).

    S must be a multiple of block_s; dh should be a multiple of 128 and Tp
    a multiple of 8 (ops.py pads and builds the tiles).
    """
    B, K, TG, dh = q.shape
    S = k.shape[1]
    Tp = mask.shape[2]
    g = TG // Tp
    assert S % block_s == 0 and k.shape[2] == K * dh, (k.shape, block_s)
    n_blocks = S // block_s
    grid = (B, K, n_blocks)
    kernel = functools.partial(_kernel, scale=dh ** -0.5, g=g,
                               n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, TG, dh), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, block_s, dh), lambda b, h, j: (b, j, h)),
            pl.BlockSpec((1, block_s, dh), lambda b, h, j: (b, j, h)),
            pl.BlockSpec((1, 1, Tp, block_s), lambda b, h, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TG, dh), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, TG, dh), q.dtype),
        scratch_shapes=[
            _vmem((TG, 128), jnp.float32),
            _vmem((TG, 128), jnp.float32),
            _vmem((TG, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, mask)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


__all__ = ["tree_attention_grouped"]
