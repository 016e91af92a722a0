"""Pallas TPU kernel: causal flash attention for the 32k prefill path.

FlashAttention-2 style: grid = (B, K, q_blocks, kv_blocks), kv innermost and
sequential with (m, l, acc) VMEM scratch; blocks strictly above the causal
diagonal contribute nothing (masked; on real TPU the block can be skipped
with a scalar-prefetch grid, noted for the hardware build).

GQA layout: q rows grouped per kv head — (B, K, Sq·G, dh) like
tree_attention; the causal mask is derived from block indices in-kernel
(no (S, S) mask tensor ever materializes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, g: int, block_q: int, block_k: int,
            n_kv_blocks: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)             # (bq*G, dh)
    k = k_ref[0].astype(jnp.float32)                # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # causal mask from absolute positions
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q * g, 1), 0) // g
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    mask = q_pos >= k_pos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kj == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_prefill_grouped(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          block_q: int = 256, block_k: int = 512,
                          interpret: bool = False) -> jax.Array:
    """q (B, K, S·G, dh) grouped causal self-attention; k/v (B, S, K·dh)
    head-flattened view (kv head h is the 128-lane column block h)."""
    B, K, SG, dh = q.shape
    S = k.shape[1]
    g = SG // S
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    grid = (B, K, S // block_q, S // block_k)
    kernel = functools.partial(_kernel, scale=dh ** -0.5, g=g,
                               block_q=block_q, block_k=block_k,
                               n_kv_blocks=S // block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q * g, dh),
                         lambda b, h, qi, kj: (b, h, qi, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, h, qi, kj: (b, kj, h)),
            pl.BlockSpec((1, block_k, dh), lambda b, h, qi, kj: (b, kj, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q * g, dh),
                               lambda b, h, qi, kj: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, SG, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q * g, 128), jnp.float32),
            pltpu.VMEM((block_q * g, 128), jnp.float32),
            pltpu.VMEM((block_q * g, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _tri_qi(t):
    """Triangular enumeration: t -> (qi, kj) with kj <= qi."""
    tf = t.astype(jnp.float32)
    qi = jnp.floor((jnp.sqrt(8.0 * tf + 1.0) - 1.0) * 0.5 + 1e-4
                   ).astype(jnp.int32)
    kj = t - qi * (qi + 1) // 2
    return qi, kj


def _kernel_tri(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                scale: float, g: int, block: int):
    t = pl.program_id(2)
    qi, kj = _tri_qi(t)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * block + jax.lax.broadcasted_iota(
        jnp.int32, (block * g, 1), 0) // g
    k_pos = kj * block + jax.lax.broadcasted_iota(
        jnp.int32, (1, block), 1)
    mask = q_pos >= k_pos            # only the diagonal block is partial
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((0 + 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kj == qi)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_prefill_grouped_tri(q: jax.Array, k: jax.Array, v: jax.Array, *,
                              block: int = 256,
                              interpret: bool = False) -> jax.Array:
    """Causal flash attention on a TRIANGULAR grid: blocks strictly above the
    diagonal are never scheduled, halving kernel FLOPs and KV traffic vs the
    rectangular grid (beyond-paper §Perf optimization for prefill_32k).
    Requires block_q == block_k == ``block``."""
    B, K, SG, dh = q.shape
    S = k.shape[1]
    g = SG // S
    assert S % block == 0, (S, block)
    nq = S // block
    n_tri = nq * (nq + 1) // 2
    grid = (B, K, n_tri)
    kernel = functools.partial(_kernel_tri, scale=dh ** -0.5, g=g,
                               block=block)

    def qmap(b, h, t):
        qi, _ = _tri_qi(t)
        return (b, h, qi, 0)

    def kmap(b, h, t):
        _, kj = _tri_qi(t)
        return (b, kj, h)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block * g, dh), qmap),
            pl.BlockSpec((1, block, dh), kmap),
            pl.BlockSpec((1, block, dh), kmap),
        ],
        out_specs=pl.BlockSpec((1, 1, block * g, dh), qmap),
        out_shape=jax.ShapeDtypeStruct((B, K, SG, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block * g, 128), jnp.float32),
            pltpu.VMEM((block * g, 128), jnp.float32),
            pltpu.VMEM((block * g, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


__all__ = ["flash_prefill_grouped", "flash_prefill_grouped_tri"]
