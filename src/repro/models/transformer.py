"""Config-driven decoder-only transformer (dense + MoE) with three lowerable
entry points:

  * ``train_logits``  — full-sequence causal forward (training).
  * ``prefill``       — causal forward that fills a KV cache and returns the
                        logits of each sequence's last real token.
  * ``tree_step``     — the Lookahead step: T = 1+decoding_length slots with a
                        tree-structured attention mask attend to the cache,
                        new KV entries are scattered at cache_len + slot.

Layers are stacked and iterated with ``lax.scan`` (HLO size O(1) in depth);
``remat=True`` wraps the scanned body in ``jax.checkpoint`` for training.
All tensors carry logical-axis sharding hints (repro.distributed.sharding).
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import active_mesh, constrain
from repro.kernels.tree_attention.ops import kv_view, lane_dh
from repro.models import attention as attn_backends
from repro.models.attention import pool_rows
from repro.models import moe as moe_lib
from repro.models.layers import (ACTS, apply_rope, rms_norm, rope_angles,
                                 swiglu)

Params = Dict[str, Any]


@dataclass(frozen=True)
class TransformerConfig:
    name: str = "tiny"
    n_layers: int = 2
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 128
    vocab_size: int = 256
    head_dim: Optional[int] = None          # default: d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    act: str = "silu"
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    # MoE
    moe: bool = False
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.5
    moe_impl: str = "auto"                  # "ref" | "ep" | "auto"
    # execution
    dtype: str = "float32"                  # activation dtype
    param_dtype: str = "float32"
    remat: bool = False
    scan_layers: bool = True                # False: unroll (dry-run accuracy —
                                            # XLA cost_analysis counts while
                                            # bodies once; see EXPERIMENTS.md)
    q_chunk: int = 0                        # >0: chunked prefill attention
    max_seq_len: int = 512                  # KV cache allocation length
    # per-phase attention backends, resolved from the registry in
    # repro.models.attention: "dense" | "pallas" | "flash_decode"
    prefill_backend: str = "dense"
    decode_backend: str = "dense"
    attn_score_f32: bool = True             # False: bf16 score temps (perf)
    # KV-cache layout: "dense" = (lanes, max_seq_len) rows per lane;
    # "paged" = a shared pool of (n_blocks, kv_block_size) rows indexed by
    # per-lane block tables (vLLM-style; block 0 reserved as NULL/trash)
    kv_layout: str = "dense"
    kv_block_size: int = 64

    @property
    def dh(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def n_params(self) -> int:
        """Total parameter count (for 6ND model-flops accounting)."""
        d, dh, V = self.d_model, self.dh, self.vocab_size
        qkvo = d * (self.n_heads * dh) * 2 + d * (self.n_kv_heads * dh) * 2
        if self.moe:
            ffn = 3 * d * self.moe_d_ff * self.n_experts + d * self.n_experts
            ffn += 3 * d * self.moe_d_ff * self.n_shared_experts
        else:
            ffn = 3 * d * self.d_ff
        per_layer = qkvo + ffn + 2 * d
        emb = V * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d

    def n_active_params(self) -> int:
        """Active parameters per token (MoE: only routed experts)."""
        if not self.moe:
            return self.n_params()
        d, dh, V = self.d_model, self.dh, self.vocab_size
        qkvo = d * (self.n_heads * dh) * 2 + d * (self.n_kv_heads * dh) * 2
        ffn = 3 * d * self.moe_d_ff * (self.top_k + self.n_shared_experts)
        ffn += d * self.n_experts
        per_layer = qkvo + ffn + 2 * d
        emb = V * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d


# ------------------------------------------------------------------ parameters
def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    k = jax.random.split(key, 16)
    d, dh, H, K = cfg.d_model, cfg.dh, cfg.n_heads, cfg.n_kv_heads
    L, V = cfg.n_layers, cfg.vocab_size
    pd = cfg.pdtype
    init = lambda kk, shape, scale=0.02: (
        jax.random.normal(kk, shape, dtype=jnp.float32) * scale).astype(pd)

    layers: Params = {
        "ln1": jnp.ones((L, d), pd),
        "ln2": jnp.ones((L, d), pd),
        "wq": init(k[0], (L, d, H * dh)),
        "wk": init(k[1], (L, d, K * dh)),
        "wv": init(k[2], (L, d, K * dh)),
        "wo": init(k[3], (L, H * dh, d)),
    }
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, H * dh), pd)
        layers["bk"] = jnp.zeros((L, K * dh), pd)
        layers["bv"] = jnp.zeros((L, K * dh), pd)
    if cfg.moe:
        E, F = cfg.n_experts, cfg.moe_d_ff
        layers["router"] = init(k[4], (L, d, E))
        layers["we_gate"] = init(k[5], (L, E, d, F))
        layers["we_up"] = init(k[6], (L, E, d, F))
        layers["we_down"] = init(k[7], (L, E, F, d))
        if cfg.n_shared_experts:
            Fs = F * cfg.n_shared_experts
            layers["ws_gate"] = init(k[8], (L, d, Fs))
            layers["ws_up"] = init(k[9], (L, d, Fs))
            layers["ws_down"] = init(k[10], (L, Fs, d))
    else:
        layers["w_gate"] = init(k[4], (L, d, cfg.d_ff))
        layers["w_up"] = init(k[5], (L, d, cfg.d_ff))
        layers["w_down"] = init(k[6], (L, cfg.d_ff, d))

    params: Params = {
        "embed": init(k[11], (V, d)),
        "ln_f": jnp.ones((d,), pd),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init(k[12], (d, V))
    return params


def param_logical_axes(cfg: TransformerConfig) -> Params:
    """Logical-axis names per param (for dry-run in_shardings)."""
    layers = {
        "ln1": (None, None), "ln2": (None, None),
        "wq": (None, "fsdp", "tensor"), "wk": (None, "fsdp", "tensor"),
        "wv": (None, "fsdp", "tensor"), "wo": (None, "tensor", "fsdp"),
    }
    if cfg.qkv_bias:
        layers.update({"bq": (None, "tensor"), "bk": (None, "tensor"),
                       "bv": (None, "tensor")})
    if cfg.moe:
        layers.update({
            "router": (None, "fsdp", None),
            "we_gate": (None, "expert", "fsdp", None),
            "we_up": (None, "expert", "fsdp", None),
            "we_down": (None, "expert", None, "fsdp"),
        })
        if cfg.n_shared_experts:
            layers.update({"ws_gate": (None, "fsdp", "tensor"),
                           "ws_up": (None, "fsdp", "tensor"),
                           "ws_down": (None, "tensor", "fsdp")})
    else:
        layers.update({"w_gate": (None, "fsdp", "tensor"),
                       "w_up": (None, "fsdp", "tensor"),
                       "w_down": (None, "tensor", "fsdp")})
    out = {"embed": ("tensor", "fsdp"), "ln_f": (None,), "layers": layers}
    if not cfg.tie_embeddings:
        out["lm_head"] = ("fsdp", "tensor")
    return out


# ------------------------------------------------------------------- layer fwd
def _qkv(cfg: TransformerConfig, lp: Params, h: jax.Array
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, T, _ = h.shape
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    return (q.reshape(B, T, H, dh), k.reshape(B, T, K, dh),
            v.reshape(B, T, K, dh))


def _ffn(cfg: TransformerConfig, lp: Params, h: jax.Array) -> jax.Array:
    B, T, d = h.shape
    act = ACTS[cfg.act]
    if not cfg.moe:
        return swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], act)
    x = h.reshape(B * T, d)
    mesh = active_mesh()
    impl = cfg.moe_impl
    if impl == "auto":
        impl = "ep" if (mesh is not None and "model" in mesh.shape) else "ref"
    if impl == "ep":
        y = moe_lib.moe_ep(x, lp["router"], lp["we_gate"], lp["we_up"],
                           lp["we_down"], cfg.top_k, cfg.capacity_factor,
                           mesh, act)
    elif impl == "local":
        y = moe_lib.moe_local(x, lp["router"], lp["we_gate"], lp["we_up"],
                              lp["we_down"], cfg.top_k, cfg.capacity_factor,
                              act)
    else:
        y = moe_lib.moe_ref(x, lp["router"], lp["we_gate"], lp["we_up"],
                            lp["we_down"], cfg.top_k, act)
    y = y.reshape(B, T, d)
    if cfg.n_shared_experts:
        y = y + swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], act)
    return y


def _layer_self(cfg: TransformerConfig, lp: Params, h: jax.Array,
                positions: jax.Array, len_mask: jax.Array,
                want_kv: bool = True):
    """Self-attention layer over the full sequence (train / prefill).
    Returns new hidden states and the (k, v) tensors for cache filling."""
    with jax.named_scope("attn"):
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, lp, hn)
        cos, sin = rope_angles(positions, cfg.dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        q = constrain(q, "batch", "seq", "heads", None)
        k = constrain(k, "batch", "seq", "kv_heads", None)
        v = constrain(v, "batch", "seq", "kv_heads", None)
        backend = attn_backends.get_backend(cfg.prefill_backend)
        attn = backend.prefill_attention(cfg, q, k, v, positions, len_mask)
        B, T, H, dh = attn.shape
        h = h + attn.reshape(B, T, H * dh) @ lp["wo"]
        h = constrain(h, "batch", "residual_seq", None)
    with jax.named_scope("mlp"):
        h = h + _ffn(cfg, lp, rms_norm(h, lp["ln2"], cfg.norm_eps))
        # residual_seq: () by default; train cells map it to ("model",) so
        # the remat-saved residual stream is sequence-sharded (Megatron-SP)
        h = constrain(h, "batch", "residual_seq", None)
    return h, ((k, v) if want_kv else None)


def _layer_tree(cfg: TransformerConfig, lp: Params, h: jax.Array,
                positions: jax.Array, attend: Any, *cache: jax.Array
                ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Tree-decode layer: T slots attend to cache + tree siblings.

    ``attend`` is the backend closure built by ``make_tree_attend`` (cache
    = this layer's (B, S_max, K, dh) K and V) or ``make_paged_tree_attend``
    (cache = the whole carried K and V pool and the layer index) — it
    scatters the new KV at cache_len + slot, then attends the slots against
    the cache, and returns the updated K and V.
    """
    B, T, _ = h.shape
    with jax.named_scope("attn"):
        hn = rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(cfg, lp, hn)
        cos, sin = rope_angles(positions, cfg.dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn, k_cache, v_cache = attend(q, k, v, *cache)
        H, dh = cfg.n_heads, cfg.dh
        h = h + attn.reshape(B, T, H * dh) @ lp["wo"]
    with jax.named_scope("mlp"):
        h = h + _ffn(cfg, lp, rms_norm(h, lp["ln2"], cfg.norm_eps))
    return h, (k_cache, v_cache)


# ----------------------------------------------------------------- full models
def _embed(cfg: TransformerConfig, params: Params, tokens: jax.Array
           ) -> jax.Array:
    h = params["embed"].astype(cfg.adtype)[tokens]
    return h * jnp.asarray(1.0, cfg.adtype)


def _unembed(cfg: TransformerConfig, params: Params, h: jax.Array
             ) -> jax.Array:
    with jax.named_scope("head"):
        h = rms_norm(h, params["ln_f"], cfg.norm_eps)
        w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = h @ w.astype(h.dtype)
        if logits.ndim == 3:
            logits = constrain(logits, "batch", "seq", "vocab_act")
        return logits


def _layer_weights(cfg: TransformerConfig, params: Params) -> Params:
    """The stacked layer weights in the activation dtype.

    §Perf (train cells): cast BEFORE the layer loop — XLA hoists the
    loop-invariant fsdp all-gather out of the scan, and gathering the f32
    master copy moves (and holds) 2x the bytes of the bf16 compute copy."""
    return jax.tree.map(lambda a: a.astype(cfg.adtype)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a,
                        params["layers"])


def _scan_layers(cfg: TransformerConfig, params: Params, h: jax.Array,
                 layer_fn, extra_xs: Tuple = (), extra_args: Tuple = (),
                 alias_ys_to_xs: bool = False) -> Tuple[jax.Array, Tuple]:
    """Run layer_fn over stacked layer params with lax.scan (or unrolled
    when cfg.scan_layers=False — dry-run cost accuracy).

    layer_fn(cfg, lp, h, *extra_args, *per_layer_xs) -> (h, per_layer_ys)

    alias_ys_to_xs: per-layer ys have the same structure/shape as per-layer
    xs (tree-decode cache update): in unrolled mode write y back into the
    stacked xs buffer with .at[i].set — XLA aliases this in place, so the
    unrolled path does NOT hold n_layers live cache copies.

    Per-layer xs (the stacked dense KV cache, in the dense decode step)
    are sliced out and written back under the ``kv_pool`` scope;
    ``layer_fn``'s own ops carry the scopes it opens (``attn``, ``mlp``).
    The paged pool does not pass here (``_scan_layers_pooled``).
    """
    lps = _layer_weights(cfg, params)

    def body(h, xs):
        lp, xtra = xs
        h, ys = layer_fn(cfg, lp, h, *extra_args, *xtra)
        return h, ys

    if cfg.remat:
        body = jax.checkpoint(body)
    pool = jax.named_scope("kv_pool") if extra_xs else nullcontext()
    if cfg.scan_layers:
        with pool:
            h, ys = jax.lax.scan(body, h, (lps, extra_xs))
        return h, ys
    # unrolled
    buf = extra_xs
    ys_buf = None
    for i in range(cfg.n_layers):
        lp_i = jax.tree.map(lambda a: a[i], lps)
        with pool:
            xs_i = jax.tree.map(lambda a: a[i], buf)
        h, y = body(h, (lp_i, xs_i))
        if y is None:
            continue
        if alias_ys_to_xs:
            with pool:
                buf = jax.tree.map(lambda acc, yy: acc.at[i].set(yy), buf, y)
        else:
            if ys_buf is None:
                ys_buf = jax.tree.map(
                    lambda yy: jnp.zeros((cfg.n_layers,) + yy.shape,
                                         yy.dtype), y)
            ys_buf = jax.tree.map(lambda acc, yy: acc.at[i].set(yy),
                                  ys_buf, y)
    return h, (buf if alias_ys_to_xs else ys_buf)


def _scan_layers_pooled(cfg: TransformerConfig, params: Params, h: jax.Array,
                        layer_fn, k_pool: jax.Array, v_pool: jax.Array
                        ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Run layer_fn over the layers with the paged KV pool as scan carry.

    layer_fn(cfg, lp, h, k_pool, v_pool, layer) -> (h, (k_pool, v_pool))

    The scan runs over the layer index beside the stacked weights; each
    layer writes its new rows at ``[layer, rows]`` of the carried pool and
    the kernel reads the pool at ``layer``, so no layer's slice is cut out
    or written back and the donated pool buffer is updated in place.  The
    unrolled path (cfg.scan_layers=False) threads the same carry with a
    constant index.
    """
    lps = _layer_weights(cfg, params)

    def body(carry, xs):
        h, pools = carry
        lp, layer = xs
        h, pools = layer_fn(cfg, lp, h, *pools, layer)
        return (h, pools), None

    carry = (h, (k_pool, v_pool))
    if cfg.scan_layers:
        carry, _ = jax.lax.scan(
            body, carry, (lps, jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    else:
        for i in range(cfg.n_layers):
            carry, _ = body(carry, (jax.tree.map(lambda a: a[i], lps),
                                    jnp.int32(i)))
    return carry


def train_logits(cfg: TransformerConfig, params: Params, tokens: jax.Array,
                 ) -> jax.Array:
    """tokens (B, S) -> logits (B, S, V); plain causal, no padding mask."""
    B, S = tokens.shape
    h = _embed(cfg, params, tokens)
    h = constrain(h, "batch", "residual_seq", None)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    len_mask = jnp.ones((B, S), dtype=bool)
    h, _ = _scan_layers(cfg, params, h,
                        lambda c, lp, hh, pos, lm: _layer_self(
                            c, lp, hh, pos, lm, want_kv=False),
                        extra_xs=(), extra_args=(positions, len_mask))
    return _unembed(cfg, params, h)


def lm_loss(cfg: TransformerConfig, params: Params, tokens: jax.Array,
            labels: jax.Array, loss_mask: Optional[jax.Array] = None
            ) -> jax.Array:
    B, S = tokens.shape
    h = _embed(cfg, params, tokens)
    h = constrain(h, "batch", "residual_seq", None)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    len_mask = jnp.ones((B, S), dtype=bool)
    h, _ = _scan_layers(cfg, params, h,
                        lambda c, lp, hh, pos, lm: _layer_self(
                            c, lp, hh, pos, lm, want_kv=False),
                        extra_xs=(), extra_args=(positions, len_mask))

    # checkpointed loss head: the (B, S, V) f32 logits are NOT saved for the
    # backward pass — only h (bf16, V/vocab-factor smaller) is; logits are
    # recomputed during bwd.  Cuts several GiB/chip at 100k+ vocabularies.
    def head(h_, labels_, mask_):
        logits = _unembed(cfg, params, h_)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels_[..., None], axis=-1)[..., 0]
        if mask_ is None:
            return jnp.mean(nll)
        return jnp.sum(nll * mask_) / jnp.maximum(jnp.sum(mask_), 1.0)

    return jax.checkpoint(head, static_argnums=())(h, labels, loss_mask) \
        if cfg.remat else head(h, labels, loss_mask)


def init_cache(cfg: TransformerConfig, batch: int,
               dtype: Optional[jnp.dtype] = None) -> Dict[str, jax.Array]:
    L, S, K, dh = cfg.n_layers, cfg.max_seq_len, cfg.n_kv_heads, cfg.dh
    dt = dtype or cfg.adtype
    return {"k": jnp.zeros((L, batch, S, K, dh), dt),
            "v": jnp.zeros((L, batch, S, K, dh), dt)}


def cache_logical_axes(cfg: TransformerConfig) -> Dict[str, Tuple]:
    if cfg.kv_layout == "paged":
        # block pool is lane-agnostic: only the head-flattened last axis
        # (K heads of lane_dh(dh) columns) is shardable
        return {"k": (None, None, None, "kv_heads"),
                "v": (None, None, None, "kv_heads"),
                "block_tables": (None, None)}
    if cfg.decode_backend == "flash_decode":
        return {"k": (None, None, "kv_seq", "kv_heads", None),
                "v": (None, None, "kv_seq", "kv_heads", None)}
    return {"k": (None, "batch", None, "kv_heads", None),
            "v": (None, "batch", None, "kv_heads", None)}


# ------------------------------------------------------------ paged KV cache
def blocks_per_lane(cfg: TransformerConfig) -> int:
    """Block-table width: blocks covering max_seq_len logical positions."""
    return -(-cfg.max_seq_len // cfg.kv_block_size)


def init_paged_cache(cfg: TransformerConfig, lanes: int,
                     n_blocks: Optional[int] = None,
                     dtype: Optional[jnp.dtype] = None
                     ) -> Dict[str, jax.Array]:
    """Block-pool KV cache: k/v (L, n_blocks, block_size, K*dh_p) plus the
    per-lane block tables (lanes, blocks_per_lane) int32.

    The pool is stored in the paged kernel's layout: heads flattened into
    the last axis, dh padded to a lane multiple (``lane_dh``; free at dh =
    128, paid once in HBM elsewhere).  Every paged writer and reader works
    on rows of the flat (L, n_blocks*block_size, K*dh_p) view, a bitcast,
    so no program relays the pool out.

    ``n_blocks`` defaults to the dense-equivalent worst case (every lane can
    hold max_seq_len rows) plus the reserved NULL block 0; serving stacks
    pass a smaller pool sized to the actual workload — that is the paged
    layout's memory win.  Table entries start at 0 (the NULL block), where
    never-attended scatters land harmlessly.
    """
    L, KD = cfg.n_layers, cfg.n_kv_heads * lane_dh(cfg.dh)
    bs, bpl = cfg.kv_block_size, blocks_per_lane(cfg)
    nb = int(n_blocks) if n_blocks else 1 + lanes * bpl
    dt = dtype or cfg.adtype
    return {"k": jnp.zeros((L, nb, bs, KD), dt),
            "v": jnp.zeros((L, nb, bs, KD), dt),
            "block_tables": jnp.zeros((lanes, bpl), jnp.int32)}


def paged_row_index(block_tables: jax.Array, positions: jax.Array,
                    block_size: int) -> jax.Array:
    """Logical positions -> physical flat cache rows through block tables.

    block_tables (B, blocks_per_lane) int32; positions (B, N) logical token
    positions.  Returns (B, N) rows into the (n_blocks*block_size, ...) flat
    view.  Positions past a lane's allocated coverage resolve through table
    entry 0 to the NULL block (garbage rows, never attended)."""
    blk = jnp.clip(positions // block_size, 0, block_tables.shape[-1] - 1)
    phys = jnp.take_along_axis(block_tables, blk.astype(jnp.int32), axis=-1)
    return phys * block_size + positions % block_size


def prefill(cfg: TransformerConfig, params: Params, tokens: jax.Array,
            lens: jax.Array, cache: Optional[Dict[str, jax.Array]] = None
            ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Causal forward over padded prompts; fills cache[:, :, :S].

    cache=None: the per-layer KV stack itself becomes the cache (S must be
    max_seq_len) — avoids a second cache-sized buffer for big prefills.
    Returns (cache, last_logits (B, V)) at position lens-1 of each row.
    """
    B, S = tokens.shape
    h = _embed(cfg, params, tokens)
    h = constrain(h, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    len_mask = positions < lens[:, None]
    h, kv = _scan_layers(cfg, params, h, _layer_self, extra_xs=(),
                         extra_args=(positions, len_mask))
    k_new, v_new = kv     # (L, B, S, K, dh)
    if cache is None:
        assert S == cfg.max_seq_len, (S, cfg.max_seq_len)
        k_new = k_new.astype(cfg.adtype)
        v_new = v_new.astype(cfg.adtype)
        mesh = active_mesh()
        if mesh is not None:
            from jax.sharding import NamedSharding
            from repro.distributed.flash_decode import cache_partition_spec
            spec = NamedSharding(mesh, cache_partition_spec(
                mesh, B, S, cfg.n_kv_heads, cfg.n_heads))
            k_new = jax.lax.with_sharding_constraint(k_new, spec)
            v_new = jax.lax.with_sharding_constraint(v_new, spec)
        cache = {"k": k_new, "v": v_new}
    else:
        cache = {
            "k": cache["k"].at[:, :, :S].set(k_new.astype(cache["k"].dtype)),
            "v": cache["v"].at[:, :, :S].set(v_new.astype(cache["v"].dtype))}
    h_last = jnp.take_along_axis(
        h, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return cache, _unembed(cfg, params, h_last)


def prefill_into_slot(cfg: TransformerConfig, params: Params,
                      cache: Dict[str, jax.Array], slot: jax.Array,
                      tokens: jax.Array, lens: jax.Array
                      ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Prefill ONE request into batch lane ``slot`` of an existing cache.

    tokens (1, S) padded prompt; lens (1,).  Writes KV for positions [0, S)
    of that lane only (other lanes untouched — mid-flight admission in the
    continuous-batching scheduler).  ``slot`` may be a traced scalar, so one
    compilation serves every lane.  Returns (cache, last_logits (1, V)).
    """
    B, S = tokens.shape
    assert B == 1, "prefill_into_slot admits one request at a time"
    h = _embed(cfg, params, tokens)
    h = constrain(h, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    len_mask = positions < lens[:, None]
    h, kv = _scan_layers(cfg, params, h, _layer_self, extra_xs=(),
                         extra_args=(positions, len_mask))
    k_new, v_new = kv     # (L, 1, S, K, dh)
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    start = (zero, slot, zero, zero, zero)
    cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), start),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), start)}
    h_last = jnp.take_along_axis(
        h, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return cache, _unembed(cfg, params, h_last)


def _scatter_paged_rows(cache: Dict[str, jax.Array], rows: jax.Array,
                        k_new: jax.Array, v_new: jax.Array
                        ) -> Dict[str, jax.Array]:
    """Write per-layer KV (L, B, N, K, dh) at flat physical ``rows`` (B, N)
    of the paged pool.  Duplicate rows only ever arise on NULL-block
    garbage, where any write order is fine."""
    k, v = cache["k"], cache["v"]
    KD = k.shape[-1]
    idx = pool_rows(k, rows)
    with jax.named_scope("kv_pool"):
        kf = k.reshape(-1, KD).at[idx].set(
            kv_view(k_new).reshape(-1, KD).astype(k.dtype))
        vf = v.reshape(-1, KD).at[idx].set(
            kv_view(v_new).reshape(-1, KD).astype(v.dtype))
        return {"k": kf.reshape(k.shape), "v": vf.reshape(v.shape),
                "block_tables": cache["block_tables"]}


def prefill_paged(cfg: TransformerConfig, params: Params, tokens: jax.Array,
                  lens: jax.Array, cache: Dict[str, jax.Array]
                  ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Batched causal prefill into a paged cache: row p of lane b lands at
    the physical row its block table maps p to.  Rows past a lane's
    allocated coverage (prompt padding, lanes without a request) resolve to
    the NULL block — garbage, never attended (I3)."""
    B, S = tokens.shape
    h = _embed(cfg, params, tokens)
    h = constrain(h, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    len_mask = positions < lens[:, None]
    h, kv = _scan_layers(cfg, params, h, _layer_self, extra_xs=(),
                         extra_args=(positions, len_mask))
    rows = paged_row_index(cache["block_tables"], positions,
                           cfg.kv_block_size)
    cache = _scatter_paged_rows(cache, rows, kv[0], kv[1])
    h_last = jnp.take_along_axis(
        h, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return cache, _unembed(cfg, params, h_last)


def prefill_into_slot_paged(cfg: TransformerConfig, params: Params,
                            cache: Dict[str, jax.Array], slot: jax.Array,
                            tokens: jax.Array, lens: jax.Array
                            ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Paged twin of ``prefill_into_slot``: one request's KV scatters through
    lane ``slot``'s block table; every other lane's blocks are untouched
    (block ownership is exclusive, so no start-index arithmetic needed)."""
    B, S = tokens.shape
    assert B == 1, "prefill_into_slot admits one request at a time"
    h = _embed(cfg, params, tokens)
    h = constrain(h, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    len_mask = positions < lens[:, None]
    h, kv = _scan_layers(cfg, params, h, _layer_self, extra_xs=(),
                         extra_args=(positions, len_mask))
    bt_row = jax.lax.dynamic_index_in_dim(
        cache["block_tables"], jnp.asarray(slot, jnp.int32), axis=0)  # (1,bpl)
    rows = paged_row_index(bt_row, positions, cfg.kv_block_size)
    cache = _scatter_paged_rows(cache, rows, kv[0], kv[1])
    h_last = jnp.take_along_axis(
        h, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return cache, _unembed(cfg, params, h_last)


def prefill_from_offset_paged(cfg: TransformerConfig, params: Params,
                              cache: Dict[str, jax.Array], slot: jax.Array,
                              tokens: jax.Array, offset: jax.Array,
                              lens: jax.Array
                              ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Suffix prefill for prefix-cache hits: prefill only the uncached tail
    of one request's prompt, attending the shared prefix blocks through
    lane ``slot``'s block table.

    tokens (1, Sb): the prompt suffix padded to a fixed bucket length;
    offset (1,): cached prefix length (absolute position of tokens[0]);
    lens (1,): real (un-padded) suffix length.

    Implemented as a causally-masked tree step at cache_lens=offset: the
    decode backend scatters the suffix KV at rows offset+i through the
    block-table indirection and masks attention to past ∨ causal-within-
    suffix — exactly what full prefill computes for those positions, so the
    resulting KV (and logits) match the uncached path.  Pad slots scatter
    to the NULL block (``slot_valid``) and are causally invisible to real
    queries.  One executable per (bucket, lane-count) — lanes and offsets
    are traced, so compile-once survives arbitrary hit patterns."""
    B, Sb = tokens.shape
    assert B == 1, "prefill_from_offset admits one request at a time"
    bt_row = jax.lax.dynamic_index_in_dim(
        cache["block_tables"], jnp.asarray(slot, jnp.int32), axis=0)  # (1,bpl)
    positions = offset[:, None] + jnp.arange(Sb)[None, :]             # (1,Sb)
    causal = jnp.broadcast_to(
        jnp.tril(jnp.ones((Sb, Sb), bool)), (B, Sb, Sb))
    valid = jnp.arange(Sb)[None, :] < lens[:, None]
    backend = attn_backends.get_backend(cfg.decode_backend)
    attend = backend.make_paged_tree_attend(
        cfg, bt_row, jnp.asarray(offset, jnp.int32), causal, valid)

    h = _embed(cfg, params, tokens)

    def layer(cfg_, lp, h_, *pool):
        return _layer_tree(cfg_, lp, h_, positions, attend, *pool)

    h, (k, v) = _scan_layers_pooled(cfg, params, h, layer, cache["k"],
                                    cache["v"])
    new_cache = {"k": k, "v": v, "block_tables": cache["block_tables"]}
    h_last = jnp.take_along_axis(
        h, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return new_cache, _unembed(cfg, params, h_last)


def copy_paged_block(cache: Dict[str, jax.Array], src: jax.Array,
                     dst: jax.Array) -> Dict[str, jax.Array]:
    """Device copy of one physical block (all layers, K and V) — the
    copy-on-write fork of a partially-filled boundary block a prefix-cache
    hit must extend.  Rows past the valid prefix are garbage in ``src`` and
    stay garbage in ``dst`` until the suffix prefill overwrites them."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = dict(cache)
    with jax.named_scope("kv_pool"):
        for name in ("k", "v"):
            buf = cache[name]                             # (L, nb, bs, KD)
            blk = jax.lax.dynamic_slice_in_dim(buf, src, 1, axis=1)
            out[name] = jax.lax.dynamic_update_slice_in_dim(buf, blk, dst,
                                                            axis=1)
    return out


def tree_step_paged(cfg: TransformerConfig, params: Params,
                    cache: Dict[str, jax.Array], cache_lens: jax.Array,
                    tokens: jax.Array, positions: jax.Array,
                    tree_mask: jax.Array
                    ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Lookahead VA forward over the paged cache: the decode backend's
    ``make_paged_tree_attend`` scatters draft-slot KV through the block
    tables and attends against the blocks (dense: gather via jnp.take;
    pallas: the block-table streaming kernel)."""
    bt = cache["block_tables"]
    backend = attn_backends.get_backend(cfg.decode_backend)
    attend = backend.make_paged_tree_attend(cfg, bt, cache_lens, tree_mask)

    h = _embed(cfg, params, tokens)

    def layer(cfg_, lp, h_, *pool):
        return _layer_tree(cfg_, lp, h_, positions, attend, *pool)

    h, (k, v) = _scan_layers_pooled(cfg, params, h, layer, cache["k"],
                                    cache["v"])
    new_cache = {"k": k, "v": v, "block_tables": bt}
    return new_cache, _unembed(cfg, params, h)


def commit_paged_cache(cfg: TransformerConfig, cache: Dict[str, jax.Array],
                       cache_lens: jax.Array, gather_idx: jax.Array,
                       n_accept: jax.Array
                       ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Paged twin of ``commit_cache``: logical src/dst positions resolve
    through the block tables before the gather/scatter, on the flat view of
    the (donated) pool — only the accepted rows move."""
    k, v, bt = cache["k"], cache["v"], cache["block_tables"]
    KD = k.shape[-1]
    B, T = gather_idx.shape
    src = cache_lens[:, None] + gather_idx                         # (B, T)
    dst = cache_lens[:, None] + jnp.arange(T)[None, :]
    src_rows = pool_rows(k, paged_row_index(bt, src, cfg.kv_block_size))
    dst_rows = pool_rows(k, paged_row_index(bt, dst, cfg.kv_block_size))
    with jax.named_scope("kv_pool"):
        kf = k.reshape(-1, KD)
        vf = v.reshape(-1, KD)
        kg = kf[src_rows]                                   # (L*B*T, KD)
        vg = vf[src_rows]
        kf = kf.at[dst_rows].set(kg)
        vf = vf.at[dst_rows].set(vg)
        new = {"k": kf.reshape(k.shape), "v": vf.reshape(v.shape),
               "block_tables": bt}
    return new, cache_lens + n_accept


def reset_blocks(cache: Dict[str, jax.Array], block_ids: jax.Array
                 ) -> Dict[str, jax.Array]:
    """Zero the given physical blocks of a paged cache (hygiene scrub).

    ``block_ids`` (N,) int32 — pad with 0: scrubbing the NULL block is
    harmless.  MUST be called on blocks at free time, BEFORE the allocator
    can hand them to a newly admitted request (a lane- or table-keyed scrub
    after re-allocation would destroy the new request's KV)."""
    block_ids = jnp.asarray(block_ids, jnp.int32)
    out = dict(cache)
    for name in ("k", "v"):
        buf = cache[name]
        zero = jnp.zeros((buf.shape[0], block_ids.shape[0]) + buf.shape[2:],
                         buf.dtype)
        out[name] = buf.at[:, block_ids].set(zero)
    return out


def reset_slot(cache: Dict[str, jax.Array], slot: jax.Array
               ) -> Dict[str, jax.Array]:
    """Zero one batch lane of the KV cache.  Hygiene only: correctness never
    depends on it (rows ≥ cache_len are masked out of every attention)."""
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    start = (zero, slot, zero, zero, zero)
    out = {}
    for name, buf in cache.items():
        lane = jax.lax.dynamic_slice_in_dim(buf, 0, 1, axis=1)
        out[name] = jax.lax.dynamic_update_slice(
            buf, jnp.zeros_like(lane), start)
    return out


def tree_step(cfg: TransformerConfig, params: Params,
              cache: Dict[str, jax.Array], cache_lens: jax.Array,
              tokens: jax.Array, positions: jax.Array, tree_mask: jax.Array
              ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Lookahead VA forward.

    tokens (B, T), positions (B, T), tree_mask (B, T, T) ancestor-closure.
    Returns (cache-with-slots-written, logits (B, T, V)).
    """
    B, T = tokens.shape
    S_max = cache["k"].shape[2]
    h = _embed(cfg, params, tokens)

    backend = attn_backends.get_backend(cfg.decode_backend)
    attend = backend.make_tree_attend(cfg, cache_lens, tree_mask, S_max)

    def layer(cfg_, lp, h_, k_c, v_c):
        return _layer_tree(cfg_, lp, h_, positions, attend, k_c, v_c)

    h, kv = _scan_layers(cfg, params, h, layer,
                         extra_xs=(cache["k"], cache["v"]), extra_args=(),
                         alias_ys_to_xs=True)
    new_cache = {"k": kv[0], "v": kv[1]}
    return new_cache, _unembed(cfg, params, h)


def commit_cache(cache: Dict[str, jax.Array], cache_lens: jax.Array,
                 gather_idx: jax.Array, n_accept: jax.Array
                 ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Compact accepted slots: new position m+j takes KV from m+gather[j].

    gather_idx (B, T) slot indices (monotone increasing over valid j);
    n_accept (B,).  Rows beyond n_accept keep garbage (never attended).
    """
    k, v = cache["k"], cache["v"]
    L, B, S, K, dh = k.shape
    T = gather_idx.shape[1]
    bidx = jnp.arange(B)[:, None]
    src = cache_lens[:, None] + gather_idx                       # (B, T)
    dst = cache_lens[:, None] + jnp.arange(T)[None, :]
    with jax.named_scope("kv_pool"):
        kg = k[:, bidx, src]                                     # (L,B,T,K,dh)
        vg = v[:, bidx, src]
        k = k.at[:, bidx, dst].set(kg)
        v = v.at[:, bidx, dst].set(vg)
    return {"k": k, "v": v}, cache_lens + n_accept


def verify_accept_device(tree_tokens: jax.Array, parent: jax.Array,
                         n_live: jax.Array, chosen: jax.Array
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Device twin of ``repro.core.verify.verify_accept`` (the host accept
    walk), vmapped over lanes — the fused-step epilogue.

    tree_tokens (B, T) draft-slot tokens; parent (B, T) slot parents
    (root = -1, padded slots = 0); n_live (B,) live slot count per lane
    (0 marks an idle placeholder lane); chosen (B, T) the model's
    prediction at each slot.

    Returns (n_acc (B,), acc_tokens (B, T), kv_slots (B, T)) int32.  The
    walk starts at the root (always accepted: acc_tokens[0] = chosen[0],
    kv_slots[0] = 0) and repeatedly steps to the smallest slot c with
    ``parent[c] == cur and tree_tokens[c] == chosen[cur] and 0 < c <
    n_live``.  "Smallest slot" is exactly the host semantics: DraftTree
    children lists are built in increasing slot order and verify_accept
    takes the first matching child.  Entries past n_acc are zero (commit
    gathers row lens+0 there — garbage rows, never attended).  Idle lanes
    (n_live == 0) return n_acc == 0.
    """
    B, T = tree_tokens.shape

    def walk(tok, par, nl, ch):
        slots = jnp.arange(T, dtype=jnp.int32)
        acc0 = jnp.zeros((T,), jnp.int32).at[0].set(ch[0])
        kvs0 = jnp.zeros((T,), jnp.int32)

        def body(carry, _):
            cur, n, done, acc, kvs = carry
            want = ch[cur]
            ok = ((par == cur) & (tok == want) & (slots < nl)
                  & (slots > 0) & jnp.logical_not(done))
            nxt = jnp.argmax(ok).astype(jnp.int32)
            found = ok[nxt]
            acc = jnp.where(found, acc.at[n].set(ch[nxt]), acc)
            kvs = jnp.where(found, kvs.at[n].set(nxt), kvs)
            cur = jnp.where(found, nxt, cur)
            n = jnp.where(found, n + 1, n)
            done = done | jnp.logical_not(found)
            return (cur, n, done, acc, kvs), None

        init = (jnp.int32(0), jnp.int32(1), nl <= 0, acc0, kvs0)
        (_, n, _, acc, kvs), _ = jax.lax.scan(body, init, None,
                                              length=max(T - 1, 0))
        n = jnp.where(nl > 0, n, 0)
        return n, acc, kvs

    tok = jnp.asarray(tree_tokens, jnp.int32)
    par = jnp.asarray(parent, jnp.int32)
    nl = jnp.asarray(n_live, jnp.int32)
    ch = jnp.asarray(chosen, jnp.int32)
    return jax.vmap(walk)(tok, par, nl, ch)


def pack_step_result(n_acc: jax.Array, acc_tokens: jax.Array,
                     kv_slots: jax.Array) -> jax.Array:
    """Pack the fused-step outputs into the ONE (B, 1+2T) int32 array that
    crosses the host boundary per decode step:
    ``[n_acc | acc_tokens (T) | kv_slots (T)]`` per lane."""
    return jnp.concatenate([n_acc[:, None].astype(jnp.int32),
                            acc_tokens.astype(jnp.int32),
                            kv_slots.astype(jnp.int32)], axis=1)


__all__ = ["TransformerConfig", "Params", "init_params", "param_logical_axes",
           "train_logits", "lm_loss", "init_cache", "cache_logical_axes",
           "prefill", "prefill_into_slot", "reset_slot", "tree_step",
           "commit_cache", "blocks_per_lane", "init_paged_cache",
           "paged_row_index", "prefill_paged", "prefill_into_slot_paged",
           "prefill_from_offset_paged", "copy_paged_block",
           "tree_step_paged", "commit_paged_cache", "reset_blocks",
           "verify_accept_device", "pack_step_result"]
