"""Compile the serving path for a TPU v5e that is described, not attached.

The kernels of the main path compile with ``interpret=False`` at
qwen2-1.5b's attention widths (H=12, K=2, dh=128, T=33 draft slots, bf16)
against one chip of a described ``v5e:2x2`` topology: Mosaic refuses here
what interpret mode accepts (misaligned blocks, unsupported operands), at
no chip time.  The topology is described only inside the module fixture —
never at import — so every xdist worker collects the same tests and only
the worker running this file loads the TPU compiler.

Also pinned on the CPU: the jitted session steps take the weights as
arguments (no weight-sized HLO constants), and where the persistent
compilation cache goes.
"""
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill.ops import flash_prefill
from repro.kernels.tree_attention.ops import tree_attention
from repro.kernels.tree_attention.paged import paged_tree_attention

B, T, H, K, DH = 4, 33, 12, 2, 128       # qwen2-1.5b decode widths
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache without a chip; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text      # the Mosaic kernel, not a loop
    return text


def test_dense_tree_attention_compiles_for_v5e(one_chip):
    S = 512
    _compile(lambda q, k, v, m: tree_attention(q, k, v, m, interpret=False),
             one_chip, ((B, T, H, DH), BF16), ((B, S, K, DH), BF16),
             ((B, S, K, DH), BF16), ((B, T, S), jnp.bool_))


def test_paged_tree_attention_compiles_for_v5e(one_chip):
    bs, bpl = 64, 8                       # block_size 64, 512 positions
    nb = 1 + B * bpl
    _compile(lambda q, k, v, bt, m: paged_tree_attention(
                 q, k, v, bt, m, interpret=False),
             one_chip, ((B, T, H, DH), BF16), ((nb, bs, K, DH), BF16),
             ((nb, bs, K, DH), BF16), ((B, bpl), jnp.int32),
             ((B, T, bpl * bs), jnp.bool_))


@pytest.mark.parametrize("S", [128, 2048])
def test_flash_prefill_compiles_for_v5e(one_chip, S):
    _compile(lambda q, k, v: flash_prefill(q, k, v, interpret=False),
             one_chip, ((1, S, H, DH), BF16), ((1, S, K, DH), BF16),
             ((1, S, K, DH), BF16))


def test_fused_step_takes_weights_as_arguments():
    """Lowering the dense fused step leaves no weight-shaped constant: a
    closed-over weight would be baked in as ``stablehlo.constant`` (one
    copy per executable, and no compile from shapes alone)."""
    from repro.models import transformer as tx
    from repro.serving.session import make_session_fns
    cfg = tx.TransformerConfig(n_layers=2, d_model=64, n_heads=4,
                               n_kv_heads=2, d_ff=128, vocab_size=97,
                               max_seq_len=64)
    params = tx.init_params(cfg, jax.random.key(0))
    W, lanes = 5, 2
    fns = make_session_fns(cfg, params, slots=W, prefill_len=16)
    z = np.zeros((lanes, W), np.int32)
    lane_params = {"greedy": np.ones((lanes,), bool),
                   "temp": np.ones((lanes,), np.float32),
                   "seed": np.zeros((lanes,), np.uint32)}
    text = fns.fused_step._jitted.lower(
        params, fns.init_cache(lanes), np.zeros((lanes,), np.int32), z, z,
        np.zeros((lanes, W, W), bool), z, np.zeros((lanes,), np.int32),
        lane_params).as_text()
    weight_types = {"tensor<{}x{}>".format("x".join(map(str, a.shape)),
                                           a.dtype.name.replace("float", "f"))
                    for a in jax.tree.leaves(params) if a.ndim >= 2}
    consts = re.findall(r"stablehlo\.constant .*?: (tensor<[^>]*>)", text)
    assert consts and not weight_types & set(consts), \
        weight_types & set(consts)
    # every weight enters as a parameter of the executable
    main = text[text.index("func.func public @main"):]
    main = main[:main.index("{")]
    for t in weight_types:
        assert t in main, t


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX's own


def test_compile_cache_defaults_to_fixed_path_in_checkout(monkeypatch):
    from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache
    repo = Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (repo / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
