"""Mixture-of-Experts FFN: reference path + expert-parallel shard_map path.

* ``moe_ref``  — dense all-experts einsum; exact, O(E·N·D·F); used for smoke
  tests, lossless tests and small benches.
* ``moe_ep``   — production path: tokens sharded over (pod, data, model),
  local top-k routing, sort-based dispatch into per-expert capacity blocks,
  all-to-all over the ``model`` (expert-parallel) axis, per-expert GEMMs,
  all-to-all back, weighted combine.  With a high enough capacity factor it
  is numerically identical to ``moe_ref`` (property-tested).

Routing: softmax → top-k → renormalized top-k weights (Qwen/Mixtral style).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def router_topk(x: jax.Array, w_router: jax.Array, top_k: int
                ) -> Tuple[jax.Array, jax.Array]:
    """x (N, D) -> (weights (N,k) f32 normalized, idx (N,k) i32)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(gates, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx


def moe_ref(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
            w_up: jax.Array, w_down: jax.Array, top_k: int,
            act=jax.nn.silu) -> jax.Array:
    """Exact reference: every expert computes every token. x (N, D)."""
    N, D = x.shape
    E = w_router.shape[-1]
    w, idx = router_topk(x, w_router, top_k)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (N,k,E)
    comb = jnp.einsum("nke,nk->ne", onehot, w)               # (N,E)
    g = jnp.einsum("nd,edf->enf", x, w_gate)
    u = jnp.einsum("nd,edf->enf", x, w_up)
    h = act(g) * u
    y = jnp.einsum("enf,efd->end", h, w_down)
    return jnp.einsum("ne,end->nd", comb.astype(x.dtype), y)


def _dispatch_local(x: jax.Array, w: jax.Array, idx: jax.Array, E: int,
                    capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array,
                                            jax.Array]:
    """Sort-based local dispatch.

    x (n, D); idx/w (n, k).  Returns
      buf (E, C, D)      — tokens grouped per expert (zero-padded / dropped),
      src (n*k,) i32     — source token per sorted element,
      dest (n*k,) i32    — flat destination slot (E*C = dropped),
      wflat (n*k,) f32   — combine weight per sorted element (0 if dropped).
    """
    n, k = idx.shape
    D = x.shape[-1]
    flat_e = idx.reshape(-1)
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_e)                      # stable
    sorted_e = flat_e[order]
    counts = jax.ops.segment_sum(jnp.ones_like(sorted_e), sorted_e,
                                 num_segments=E)     # (E,)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n * k) - starts[sorted_e]
    keep = pos < capacity
    dest = jnp.where(keep, sorted_e * capacity + jnp.clip(pos, 0, capacity - 1),
                     E * capacity)
    src = order // k
    buf = jnp.zeros((E * capacity + 1, D), dtype=x.dtype)
    buf = buf.at[dest].set(x[src])                   # unique dests (except drop row)
    buf = buf[:-1].reshape(E, capacity, D)
    wflat = jnp.where(keep, flat_w[order], 0.0)
    return buf, src, dest, wflat


def _expert_ffn(buf: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array, act) -> jax.Array:
    """buf (E, C, D) × per-expert weights (E, D, F) -> (E, C, D)."""
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", act(g) * u, w_down)


def moe_local(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
              w_up: jax.Array, w_down: jax.Array, top_k: int,
              capacity_factor: float, act=jax.nn.silu,
              ep_axis: Optional[str] = None) -> jax.Array:
    """Single-device (or per-shard, when called inside shard_map) MoE.

    When ``ep_axis`` is given the expert dimension of the weights is assumed
    already sharded over that mesh axis and two all-to-alls move the capacity
    blocks to/from the owning devices.
    """
    n, D = x.shape
    if ep_axis is not None:
        ep = jax.lax.axis_size(ep_axis)
        E = w_gate.shape[0] * ep      # global expert count
    else:
        ep = 1
        E = w_gate.shape[0]
    # static per-expert capacity (shapes must be static under trace)
    C = max(4, math.ceil(top_k * n / E * capacity_factor))
    C = -(-C // 4) * 4

    rw, ridx = router_topk(x, w_router, top_k)
    buf, src, dest, wflat = _dispatch_local(x, rw, ridx, E, C)
    if ep_axis is not None:
        # (E, C, D) -> (E/ep, C*ep, D): each rank keeps its expert slice,
        # receiving that slice's rows from every peer.
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
    y = _expert_ffn(buf, w_gate, w_up, w_down, act)
    if ep_axis is not None:
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                               tiled=True)
    yflat = jnp.concatenate(
        [y.reshape(E * C, D), jnp.zeros((1, D), y.dtype)], axis=0)
    contrib = yflat[dest] * wflat[:, None].astype(y.dtype)
    out = jnp.zeros_like(x).at[src].add(contrib)
    return out


def moe_ep(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
           w_up: jax.Array, w_down: jax.Array, top_k: int,
           capacity_factor: float, mesh: Mesh, act=jax.nn.silu) -> jax.Array:
    """Expert-parallel MoE over a (pod?, data, model) mesh. x (N, D) global.

    Tokens are sharded over every mesh axis; experts live on ``model``.
    N is padded to a multiple of the device count.
    """
    N, D = x.shape
    ndev = mesh.size
    pad = (-N) % ndev
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, D), x.dtype)], axis=0)
    dp_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)

    fn = functools.partial(moe_local, top_k=top_k,
                           capacity_factor=capacity_factor, act=act,
                           ep_axis="model")
    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp_axes, None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=P(dp_axes, None),
        check_vma=False,
    )(x, w_router, w_gate, w_up, w_down)
    return out[:N] if pad else out


__all__ = ["router_topk", "moe_ref", "moe_local", "moe_ep"]
