"""The decoder family: a dense decoder-only transformer of the Llama kind
(Qwen2, Phi-3): grouped-query attention with optional q/k/v biases, a
SwiGLU MLP, a tied or separate output head.

A family module turns a configuration file into what the harness needs:
its plain sizes (``shape_of``), its weights from the seed
(``make_weights``), the program's ``TransformerConfig``
(``program_config``), and the work one served call requires
(``step_work``, ``prefill_work``).  The contract is in
``harness/spec.py``.

Weights are the benchmark's own: drawn on the device from ``--seed`` in
one jitted call, in the type they are served in, laid out as the program's
parameter tree.  Work counts what the algorithm needs, whatever implements
it: only the live rows of a step, keys up to each row's true context, K and
V at the model's true head size.  One multiply-add is 2 operations.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from harness.flops import BF16, tree_rows
from harness.model import seed_key

# widths of the CPU rehearsal: every cell's structure (GQA ratio, biases,
# tied head) at a size the CPU serves in seconds
REHEARSAL_WIDTHS = {"num_hidden_layers": 2, "hidden_size": 256,
                    "num_attention_heads": 4, "intermediate_size": 512,
                    "vocab_size": 4096, "head_dim": 64}


def shape_of(config: Dict[str, Any], rehearsal: bool = False
             ) -> Dict[str, Any]:
    """Plain sizes of a configuration file (published keys), the cut ones
    already as the file states them; ``rehearsal`` shrinks the widths."""
    c = dict(config)
    heads = int(c["num_attention_heads"])
    kv = int(c.get("num_key_value_heads", heads))
    s = {
        "name": c["name"],
        "layers": int(c["num_hidden_layers"]),
        "d_model": int(c["hidden_size"]),
        "heads": heads,
        "kv_heads": kv,
        "head_dim": int(c.get("head_dim")
                        or int(c["hidden_size"]) // heads),
        "d_ff": int(c["intermediate_size"]),
        "vocab": int(c["vocab_size"]),
        "qkv_bias": bool(c.get("qkv_bias", False)),
        "tied": bool(c.get("tie_word_embeddings", False)),
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "act": str(c["hidden_act"]),
        "context": int(c["max_position_embeddings"]),
        "dtype": str(c.get("torch_dtype", "bfloat16")),
        "init_std": float(c.get("initializer_range", 0.02)),
    }
    if rehearsal:
        r = REHEARSAL_WIDTHS
        s.update(layers=r["num_hidden_layers"], d_model=r["hidden_size"],
                 heads=r["num_attention_heads"],
                 kv_heads=max(1, kv * r["num_attention_heads"] // heads),
                 head_dim=r["head_dim"], d_ff=r["intermediate_size"],
                 vocab=r["vocab_size"])
    return s


def program_config(shape: Dict[str, Any], *, context: int):
    """The program's ``TransformerConfig`` for these sizes."""
    from repro.models.transformer import TransformerConfig
    return TransformerConfig(
        name=shape["name"], n_layers=shape["layers"],
        d_model=shape["d_model"], n_heads=shape["heads"],
        n_kv_heads=shape["kv_heads"], d_ff=shape["d_ff"],
        vocab_size=shape["vocab"], head_dim=shape["head_dim"],
        qkv_bias=shape["qkv_bias"], rope_theta=shape["rope_theta"],
        act=shape["act"], tie_embeddings=shape["tied"],
        norm_eps=shape["norm_eps"], dtype=shape["dtype"],
        param_dtype=shape["dtype"], max_seq_len=int(context))


def make_weights(shape: Dict[str, Any], seed: int,
                 device: Optional[Any] = None) -> Dict[str, Any]:
    """Random weights in the served dtype, drawn in one jitted call.

    Every matrix is N(0, initializer_range); norm scales are 1 + N(0, std)
    and biases N(0, std), so a program that dropped either would differ
    from the reference."""
    L, d, H, K = (shape["layers"], shape["d_model"], shape["heads"],
                  shape["kv_heads"])
    dh, F, V = shape["head_dim"], shape["d_ff"], shape["vocab"]
    std, dt = shape["init_std"], jnp.dtype(shape["dtype"])
    leaves = {
        "embed": (V, d), "ln_f": (d,),
        "layers/ln1": (L, d), "layers/ln2": (L, d),
        "layers/wq": (L, d, H * dh), "layers/wk": (L, d, K * dh),
        "layers/wv": (L, d, K * dh), "layers/wo": (L, H * dh, d),
        "layers/w_gate": (L, d, F), "layers/w_up": (L, d, F),
        "layers/w_down": (L, F, d),
    }
    if shape["qkv_bias"]:
        leaves.update({"layers/bq": (L, H * dh), "layers/bk": (L, K * dh),
                       "layers/bv": (L, K * dh)})
    if not shape["tied"]:
        leaves["lm_head"] = (d, V)

    def draw(key):
        out: Dict[str, Any] = {"layers": {}}
        for i, (path, shp) in enumerate(sorted(leaves.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shp,
                                  jnp.float32) * std
            if path.endswith(("ln1", "ln2", "ln_f")):
                x = x + 1.0
            x = x.astype(dt)
            if path.startswith("layers/"):
                out["layers"][path.split("/", 1)[1]] = x
            else:
                out[path] = x
        return out

    fn = jax.jit(draw) if device is None else jax.jit(
        draw, out_shardings=jax.sharding.SingleDeviceSharding(device))
    return jax.block_until_ready(fn(seed_key(seed)))


# -------------------------------------------------------------------- work
def linear_params(s: Dict) -> int:
    """Matrix parameters one token multiplies per layer stack (attention
    projections and the SwiGLU MLP; no embedding gather, no head)."""
    d, H, K, dh, F = (s["d_model"], s["heads"], s["kv_heads"],
                      s["head_dim"], s["d_ff"])
    per_layer = d * H * dh + 2 * d * K * dh + H * dh * d + 3 * d * F
    return s["layers"] * per_layer


def head_params(s: Dict) -> int:
    return s["d_model"] * s["vocab"]


def attn_flops(s: Dict, keys: int) -> float:
    """QK^T and PV over ``keys`` (query row, key) pairs, every layer."""
    return 4.0 * s["layers"] * s["heads"] * s["head_dim"] * keys


def kv_bytes(s: Dict, rows: int) -> float:
    """K and V of ``rows`` cache rows, every layer, at the true head size."""
    return 2.0 * s["layers"] * s["kv_heads"] * s["head_dim"] * rows * BF16


def step_work(s: Dict, lanes: Iterable[Tuple[int, Iterable[int]]]
              ) -> Dict[str, float]:
    """One fused tree-verify step.  ``lanes`` holds, per live lane, its
    cache length and the ancestor count of each live slot.

    Returns the step's FLOPs (projections, MLP, head for every live row,
    attention) and the tree-attention kernel's required FLOPs and bytes
    (its K/V up to each lane's length plus the step's new rows, its queries
    and outputs)."""
    rows = keys = kv_rows = 0
    for cache_len, anc in lanes:
        r, k = tree_rows(cache_len, anc)
        rows += r
        keys += k
        kv_rows += cache_len + r
    af = attn_flops(s, keys)
    qo = 2.0 * s["layers"] * s["heads"] * s["head_dim"] * rows * BF16
    return {"flops": 2.0 * (linear_params(s) + head_params(s)) * rows + af,
            "attn_flops": af, "attn_bytes": kv_bytes(s, kv_rows) + qo,
            "rows": rows}


def prefill_work(s: Dict, n: int, offset: int = 0) -> Dict[str, float]:
    """Prefill of n true prompt tokens after ``offset`` cached ones: the
    projections and MLP of n rows, the head of the last row, causal
    attention; the attention kernel's required FLOPs and bytes (queries,
    outputs and the K/V it reads)."""
    keys = n * offset + n * (n + 1) // 2
    af = attn_flops(s, keys)
    qo = 2.0 * s["layers"] * s["heads"] * s["head_dim"] * n * BF16
    return {"flops": 2.0 * linear_params(s) * n + 2.0 * head_params(s)
            + af, "attn_flops": af,
            "attn_bytes": kv_bytes(s, offset + n) + qo, "rows": n}
