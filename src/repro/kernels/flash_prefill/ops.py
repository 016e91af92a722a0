"""jit'd wrapper for the causal flash-prefill kernel (layout + padding).

Pads dh→multiple of 128; a ragged S (not divisible by the block sizes) is
padded up to a common block multiple — causality keeps the pad keys
invisible to every real query (their positions sit after all real rows) and
the pad query rows are sliced off the output.  Interpret mode auto-detects
the platform: compiled Mosaic kernel on TPU, interpreter elsewhere."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.tree_attention.ops import default_interpret

from .flash_prefill import flash_prefill_grouped, flash_prefill_grouped_tri
from .ref import flash_prefill_ref


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  block_q: int = 256, block_k: int = 512,
                  interpret: Optional[bool] = None, triangular: bool = False
                  ) -> jax.Array:
    """q (B, S, H, dh); k/v (B, S, K, dh) → causal attention (B, S, H, dh)."""
    if interpret is None:
        interpret = default_interpret()
    return _flash_prefill(q, k, v, block_q=block_q, block_k=block_k,
                          interpret=interpret, triangular=triangular)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "triangular"))
def _flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   block_q: int, block_k: int, interpret: bool,
                   triangular: bool) -> jax.Array:
    B, S, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S % bq or S % bk:
        # ragged S: fall back to one shared block size and pad S up to it
        bq = bk = min(block_q, block_k)
        pad_s = (-S) % bq
        widths = ((0, 0), (0, pad_s), (0, 0), (0, 0))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    S_pad = q.shape[1]
    dh_p = -(-dh // 128) * 128
    pad = dh_p - dh
    qg = q.reshape(B, S_pad, K, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, K, S_pad * G, dh)
    if pad:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, pad)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    qg = qg * ((dh_p / dh) ** 0.5)       # kernel scales by padded dh
    k = k.reshape(B, S_pad, K * dh_p)     # free head-flattened view
    v = v.reshape(B, S_pad, K * dh_p)
    if triangular:
        out = flash_prefill_grouped_tri(qg, k, v, block=min(bq, bk),
                                        interpret=interpret)
    else:
        out = flash_prefill_grouped(qg, k, v, block_q=bq, block_k=bk,
                                    interpret=interpret)
    out = out[..., :dh].reshape(B, K, S_pad, G, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S_pad, H, dh)[:, :S]


flash_prefill_reference = flash_prefill_ref

__all__ = ["flash_prefill", "flash_prefill_reference"]
