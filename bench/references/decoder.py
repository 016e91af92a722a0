"""Plain reference of a decoder-only transformer of the Llama family
(Qwen2, Phi-3): RMSNorm, rotate-half RoPE, grouped-query attention with
optional q/k/v biases, SwiGLU MLP, tied or separate output head.

Straightforward ``jax.numpy`` over one whole sequence, causal, with no
cache, kernel, batching or padding tricks.  It imports nothing of the
program under test and reads only the benchmark's own weights.  Run layer
by layer, so a full-width model fits beside its weights.

``precision``:
  * ``"f32"``  - the reference: float32 activations and weights, every
    matrix product at ``Precision.HIGHEST``;
  * ``"fp8"``  - the control, the step below the configuration's
    bfloat16: weights rounded to float8 e4m3 with one scale per output
    channel, activations in bfloat16, products accumulated in float32;
  * ``"int8"`` - the same with int8 weights (W8A16), read beside it.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _quant(w: jax.Array, mode: str) -> jax.Array:
    """Round a (..., in, out) matrix per output channel; back in f32."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    if mode == "int8":
        scale = jnp.maximum(amax, 1e-12) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    scale = jnp.maximum(amax, 1e-12) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _act(precision: str):
    return jnp.float32 if precision == "f32" else jnp.bfloat16


def _mm(x, w, precision: str):
    """x @ w in the mode's precision, float32 result."""
    dt = _act(precision)
    if precision != "f32":
        w = _quant(w, precision)
    return jnp.matmul(x.astype(dt), w.astype(dt), precision=HI,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, heads, dh), rotate-half pairing (i, i + dh/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("shape_key", "precision"))
def _layer(h, layers, i, shape_key, precision):
    (H, K, dh, eps, theta, bias) = shape_key
    lw = jax.tree.map(lambda a: a[i], layers)
    S = h.shape[0]
    dt = _act(precision)
    hn = _rms(h, lw["ln1"], eps).astype(dt)
    q = _mm(hn, lw["wq"], precision)
    k = _mm(hn, lw["wk"], precision)
    v = _mm(hn, lw["wv"], precision)
    if bias:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    pos = jnp.arange(S)
    q = _rope(q.reshape(S, H, dh), pos, theta)
    k = _rope(k.reshape(S, K, dh), pos, theta)
    v = v.reshape(S, K, dh)
    k = jnp.repeat(k, H // K, axis=1).astype(dt)
    v = jnp.repeat(v, H // K, axis=1).astype(dt)
    s = jnp.einsum("qhd,khd->hqk", q.astype(dt), k, precision=HI,
                   preferred_element_type=jnp.float32) * (dh ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p.astype(dt), v, precision=HI,
                   preferred_element_type=jnp.float32).reshape(S, H * dh)
    h = h + _mm(o.astype(dt), lw["wo"], precision)
    hn = _rms(h, lw["ln2"], eps).astype(dt)
    g = _mm(hn, lw["w_gate"], precision)
    u = _mm(hn, lw["w_up"], precision)
    h = h + _mm((jax.nn.silu(g) * u).astype(dt), lw["w_down"], precision)
    return h.astype(jnp.float32) if precision == "f32" else h.astype(dt)


@functools.partial(jax.jit, static_argnames=("eps", "precision", "tied"))
def _head(h, rows, ln_f, head, eps, precision, tied):
    x = _rms(h[rows], ln_f, eps).astype(_act(precision))
    w = head.T if tied else head
    return _mm(x, w, precision)


def logits_at(shape: Dict, weights: Dict, tokens: np.ndarray,
              rows: np.ndarray, precision: str = "f32") -> np.ndarray:
    """Float32 logits (len(rows), vocab) of the causal forward pass over
    ``tokens`` at positions ``rows``.  The sequence is padded at its end
    to a multiple of 512 (padding lies after every real position, so the
    causal mask keeps it out) and ``rows`` to a multiple of 256, so a few
    shapes compile once and every later run finds them cached."""
    S = len(tokens)
    Sp = -(-S // 512) * 512
    toks = np.zeros((Sp,), np.int32)
    toks[:S] = tokens
    n = len(rows)
    npad = -(-n // 256) * 256
    r = np.zeros((npad,), np.int32)
    r[:n] = rows
    key = (shape["heads"], shape["kv_heads"], shape["head_dim"],
           shape["norm_eps"], shape["rope_theta"], shape["qkv_bias"])
    h = weights["embed"][jnp.asarray(toks)].astype(_act(precision))
    if precision == "f32":
        h = h.astype(jnp.float32)
    for i in range(shape["layers"]):
        h = _layer(h, weights["layers"], jnp.int32(i), key, precision)
    tied = shape["tied"]
    head = weights["embed"] if tied else weights["lm_head"]
    out = _head(h, jnp.asarray(r), weights["ln_f"], head, shape["norm_eps"],
                precision, tied)
    return np.asarray(out)[:n]
