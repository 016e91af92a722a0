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
    bs, bpl, L = 64, 8, 2                 # block_size 64, 512 positions
    nb = 1 + B * bpl
    _compile(lambda q, k, v, bt, m, layer: paged_tree_attention(
                 q, k, v, bt, m, layer, interpret=False),
             one_chip, ((B, T, H, DH), BF16), ((L, nb, bs, K * DH), BF16),
             ((L, nb, bs, K * DH), BF16), ((B, bpl), jnp.int32),
             ((B, T, bpl * bs), jnp.bool_), ((), jnp.int32))


@pytest.mark.parametrize("S", [128, 2048])
def test_flash_prefill_compiles_for_v5e(one_chip, S):
    _compile(lambda q, k, v: flash_prefill(q, k, v, interpret=False),
             one_chip, ((1, S, H, DH), BF16), ((1, S, K, DH), BF16),
             ((1, S, K, DH), BF16))


SCOPES = ("attn", "kv_pool", "mlp", "head", "accept")


def _canonical_hlo(text: str) -> str:
    """Compiled HLO without metadata, stack-frame tables and the numeric
    suffixes that make instruction names unique (renumbered by first use:
    passes may number intermediate instructions differently)."""
    lines, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            lines.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    seen = {}

    def renumber(m):
        return seen.setdefault(m.group(0), f"{m.group(1)}#{len(seen)}")
    return re.sub(r"(?<![\w.])([A-Za-z_][\w\-]*)\.(\d+)\b", renumber,
                  "\n".join(lines))


def _compile_step_and_suffix(one_chip, n_layers=1, nb=9, lanes=2,
                             prefill_len=64, text=True):
    """The paged Pallas fused step and the largest suffix-prefill bucket at
    qwen2-1.5b's attention widths, compiled for the described chip."""
    from repro.models import transformer as tx
    from repro.serving.session import make_session_fns
    cfg = tx.TransformerConfig(n_layers=n_layers, d_model=H * DH, n_heads=H,
                               n_kv_heads=K, d_ff=256, vocab_size=512,
                               qkv_bias=True, max_seq_len=256,
                               dtype="bfloat16", param_dtype="bfloat16")
    bs = 64
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: tx.init_params(cfg, jax.random.key(0))))
    fns = make_session_fns(cfg, params, slots=T, prefill_len=prefill_len,
                           backend="pallas", kv_layout="paged",
                           block_size=bs, n_blocks=nb, sampling="greedy")

    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def lp(n):
        return {"greedy": sd((n,), jnp.bool_), "temp": sd((n,), jnp.float32),
                "seed": sd((n,), jnp.uint32)}
    i32 = jnp.int32
    cache = {"k": sd((n_layers, nb, bs, K * DH), BF16),
             "v": sd((n_layers, nb, bs, K * DH), BF16),
             "block_tables": sd((lanes, 256 // bs), i32)}
    step = fns.fused_step._jitted.lower(
        params, cache, sd((lanes,), i32), sd((lanes, T), i32),
        sd((lanes, T), i32), sd((lanes, T, T), jnp.bool_),
        sd((lanes, T), i32), sd((lanes,), i32), lp(lanes)).compile()
    suffix = fns.prefill_suffix._jitted.lower(
        params, cache, sd((), i32), sd((1, fns.suffix_buckets[-1]), i32),
        sd((1,), i32), sd((1,), i32), lp(1)).compile()
    if not text:
        return step, suffix
    return [c.as_text() for c in (step, suffix)]


def test_named_scopes_are_metadata_only(one_chip, monkeypatch):
    """The device programs carry the scopes a trace reduction reads
    (attn, kv_pool, mlp, head, accept), and compile to the same program
    without them: the scopes live in metadata alone."""
    import contextlib
    import repro.kernels.flash_prefill.ops as fp_ops
    import repro.kernels.tree_attention.paged as ta_paged
    for mod in (fp_ops, ta_paged):      # the Mosaic kernels, not interpret
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    # the kernels' own jits cache their traces and lowerings (with the
    # source locations of whichever caller came first): start both
    # compiles from empty caches
    jax.clear_caches()
    scoped = _compile_step_and_suffix(one_chip)
    names = set(re.findall(r'op_name="[^"]*/(' + "|".join(SCOPES) + r')/',
                           scoped[0]))
    assert names == set(SCOPES), names
    assert "%_paged_tree_attention" in scoped[0]      # the kernel's name
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plain = _compile_step_and_suffix(one_chip)
    assert not re.search(r'op_name="[^"]*/kv_pool/', plain[0])
    for a, b in zip(scoped, plain):
        assert _canonical_hlo(a) == _canonical_hlo(b)


# HLO instruction: name = dtype[dims]{layout} opcode(
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]"
                    r"(?:\{[^}]*\})? ([\w\-]+)\(%?([\w.\-]*)")
_COPY_START = re.compile(r"^\s*%?(copy-start[\w.]*) = \((\w+\[[^\]]*\]"
                         r"\{[^}]*\}), (\w+\[[^\]]*\]\{[^}]*\})")
_POOL_COPIES = ("copy", "dynamic-slice", "dynamic-update-slice", "reshape")


def _pool_copies(text: str, sizes) -> list:
    """Instructions (or fusions named after them) that copy, slice, write
    back or relayout an array of one of ``sizes`` elements.  Asynchronous
    moves between HBM and VMEM (memory space S(1)) are left out: they are
    the compiler staging an array that fits VMEM at the test's size, which
    no deployment's pool does."""
    staged = set()
    for line in text.splitlines():
        m = _COPY_START.match(line)
        if m and ("S(1)" in m.group(2)) != ("S(1)" in m.group(3)):
            staged.add(m.group(1))
    found = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, _, dims, op, arg = m.groups()
        if op == "copy-done" and arg in staged:
            continue
        n = int(np.prod([int(d) for d in dims.split(",") if d]))
        kind = op if op != "fusion" else name
        if n in sizes and any(kind.startswith(c) or f"_{c}" in kind
                              for c in _POOL_COPIES):
            found.append(line.strip()[:160])
    return found


def test_paged_pool_is_updated_in_place_for_v5e(one_chip, monkeypatch):
    """The fused step and the largest suffix-prefill bucket carry the paged
    pool through the layer scan and write it in place: compiled for the
    described chip (2 layers), neither holds a copy, slice, write-back or
    relayout of the pool or of one layer of it, and their temporaries are
    a small fraction of the pool.  512 blocks (67 MB): at 64 the compiler
    keeps the whole pool in VMEM and the temporaries cannot tell."""
    import repro.kernels.flash_prefill.ops as fp_ops
    import repro.kernels.tree_attention.paged as ta_paged
    for mod in (fp_ops, ta_paged):      # the Mosaic kernels, not interpret
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    L, nb, bs = 2, 512, 64
    layer = nb * bs * K * DH
    pool_bytes = 2 * L * layer * 2                  # K and V, bf16
    for compiled in _compile_step_and_suffix(one_chip, n_layers=L, nb=nb,
                                             lanes=4, prefill_len=256,
                                             text=False):
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        copies = _pool_copies(text, {layer, L * layer})
        assert not copies, copies
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 0.05 * pool_bytes, (temp, pool_bytes)


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
