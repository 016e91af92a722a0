"""Token choice: greedy argmax or *position-keyed* sampling, per lane.

Lossless sampling for tree verification requires the sampled token at output
position ``p`` to be a deterministic function of (seed, p, logits) —
independent of how many tokens were accepted per step.  We use Gumbel-argmax
with a per-request key folded on the position:
``argmax(logits/τ_b + gumbel(fold_in(key(seed_b), p)))``.
Step-by-step decoding with the same rule produces bit-identical streams, which
is what the lossless property tests assert.

``choose_tokens_lanes`` is the request-centric entry point: the greedy flag,
temperature and seed are (B,) device vectors — traced *inputs*, not trace
constants — so one compiled step serves a lane pool mixing greedy and sampled
requests at distinct temperatures without retracing (I2).  ``choose_tokens``
keeps the legacy session-constant surface (dry-run cells, ad-hoc callers).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import active_mesh


def _sharded_argmax(logits: jax.Array) -> jax.Array:
    """§Perf: argmax over vocab-SHARDED logits without XLA's fallback of
    all-gathering (batch, T, V) — local argmax per model shard, then a tiny
    (tp, B, T) cross-shard reduction."""
    mesh = active_mesh()
    B, T, V = logits.shape
    if mesh is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tp = mesh.shape.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    if tp <= 1 or V % tp:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ba = dp_axes if (dp > 1 and B % dp == 0) else None

    def local(lg):                           # (B_loc, T, V/tp)
        v_loc = lg.shape[-1]
        li = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        lv = jnp.take_along_axis(lg, li[..., None], axis=-1)[..., 0]
        gi = li + jax.lax.axis_index("model") * v_loc
        vs = jax.lax.all_gather(lv, "model")         # (tp, B_loc, T)
        gs = jax.lax.all_gather(gi, "model")
        w = jnp.argmax(vs, axis=0)
        return jnp.take_along_axis(gs, w[None], axis=0)[0]

    return jax.shard_map(local, mesh=mesh,
                     in_specs=P(ba, None, "model"),
                     out_specs=P(ba, None), check_vma=False)(logits)


def choose_tokens(logits: jax.Array, pred_positions: jax.Array,
                  sample: bool = False, temperature: float = 1.0,
                  base_key: Optional[jax.Array] = None) -> jax.Array:
    """logits (B, T, V); pred_positions (B, T) — the *output* position each
    slot's logits predict.  Returns (B, T) int32 chosen ids."""
    if not sample:
        return _sharded_argmax(logits)
    assert base_key is not None
    B, T, V = logits.shape
    flat_pos = pred_positions.reshape(-1)
    keys = jax.vmap(lambda p: jax.random.fold_in(base_key, p))(flat_pos)
    gum = jax.vmap(lambda k: jax.random.gumbel(k, (V,), jnp.float32))(keys)
    z = logits.astype(jnp.float32).reshape(-1, V) / max(temperature, 1e-6)
    return jnp.argmax(z + gum, axis=-1).astype(jnp.int32).reshape(B, T)


# ------------------------------------------------------------- per-lane choice
LaneParams = Dict[str, jax.Array]   # {"greedy": (B,) bool, "temp": (B,) f32,
                                    #  "seed": (B,) u32}


def choose_tokens_lanes(logits: jax.Array, pred_positions: jax.Array,
                        lane_params: LaneParams) -> jax.Array:
    """Per-lane token choice: lane b argmaxes when ``greedy[b]`` else
    Gumbel-argmax samples at ``temp[b]`` with key fold_in(key(seed[b]), p).

    logits (B, T, V); pred_positions (B, T) absolute output positions.
    Returns (B, T) int32.  All lane params are traced device vectors —
    values never retrace.  Both branches are evaluated and selected with
    ``where`` (per-lane mixing forbids lax.cond); build the session with
    ``sampling="greedy"`` to skip the Gumbel lane entirely.
    """
    arg = _sharded_argmax(logits)
    B, T, V = logits.shape
    seeds = lane_params["seed"]

    def _lane_keys(seed, ps):                       # ps (T,)
        base = jax.random.key(seed)
        return jax.vmap(lambda p: jax.random.fold_in(base, p))(ps)

    keys = jax.vmap(_lane_keys)(seeds, pred_positions)          # (B, T) keys
    gum = jax.vmap(jax.vmap(
        lambda k: jax.random.gumbel(k, (V,), jnp.float32)))(keys)
    tau = jnp.maximum(lane_params["temp"].astype(jnp.float32), 1e-6)
    z = logits.astype(jnp.float32) / tau[:, None, None]
    samp = jnp.argmax(z + gum, axis=-1).astype(jnp.int32)
    return jnp.where(lane_params["greedy"][:, None], arg, samp)


__all__ = ["choose_tokens", "choose_tokens_lanes", "LaneParams"]
