"""The decoder family gives what the harness gave before families existed:
the same weights, by hash, and the same work on the same calls.

The constants were recorded from the harness's own ``make_weights``,
``step_work`` and ``prefill_work`` before they moved into
``bench/families/decoder.py``."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from harness.spec import family_module

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
dec = family_module({})          # no "family" key: the decoder
LANES = [(100, [1, 2, 3]), (7, [1]), (1000, list(range(1, 34)))]


def qwen2(rehearsal=False):
    with open(os.path.join(HERE, "configs", "qwen2-1.5b.json")) as f:
        cfg = json.load(f)
    cfg.setdefault("name", "qwen2-1.5b")
    return dec.shape_of(cfg, rehearsal=rehearsal)


def weights_hash(shape, seed):
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(
        dec.make_weights(shape, seed))[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,digest", [
    (4000000019,
     "6d07ccd4e00295d77d1739805dc2ad1a7fa9463f631f8b4adcdd57184f74a5f8"),
    (7, "07407a8d243ec7ca081c862b89c0d139dfd05cc8efb6d5b2a157b182a9118185"),
])
def test_weights_as_before(seed, digest):
    """Same leaves, same sorted fold_in order, same dtypes, same draws."""
    assert weights_hash(qwen2(rehearsal=True), seed) == digest


def test_shape_as_before():
    assert qwen2() == {
        "name": "qwen2-1.5b", "layers": 28, "d_model": 1536, "heads": 12,
        "kv_heads": 2, "head_dim": 128, "d_ff": 8960, "vocab": 151936,
        "qkv_bias": True, "tied": True, "rope_theta": 1000000.0,
        "norm_eps": 1e-06, "act": "silu", "context": 1536,
        "dtype": "bfloat16", "init_std": 0.02}
    r = qwen2(rehearsal=True)
    assert (r["layers"], r["d_model"], r["heads"], r["kv_heads"],
            r["head_dim"], r["d_ff"], r["vocab"]) == (2, 256, 4, 1, 64, 512,
                                                      4096)


def test_work_as_before():
    full, small = qwen2(), qwen2(rehearsal=True)
    assert dec.step_work(full, LANES) == {
        "flops": 120051720192.0, "attn_flops": 5827584000.0,
        "attn_bytes": 39165952.0, "rows": 37}
    assert dec.step_work(small, LANES) == {
        "flops": 229414912.0, "attn_flops": 69376000.0,
        "attn_bytes": 661504.0, "rows": 37}
    assert [dec.prefill_work(full, n, o)
            for n, o in [(10, 64), (1152, 0), (37, 600)]] == [
        {"flops": 26790223872.0, "attn_flops": 119562240.0,
         "attn_bytes": 3842048.0, "rows": 10},
        {"flops": 3133408935936.0, "attn_flops": 114251268096.0,
         "attn_bytes": 231211008.0, "rows": 1152},
        {"flops": 101361278976.0, "attn_flops": 3940048896.0,
         "attn_bytes": 24629248.0, "rows": 37}]
    assert [dec.prefill_work(small, n, o) for n, o in [(10, 64), (160, 0)]] \
        == [{"flops": 25802752.0, "attn_flops": 1423360.0,
             "attn_bytes": 58368.0, "rows": 10},
            {"flops": 384991232.0, "attn_flops": 26378240.0,
             "attn_bytes": 409600.0, "rows": 160}]


def test_rooflines_for_every_counted_kernel():
    """A family may count any kernel: each ``<k>_flops`` / ``<k>_bytes``
    pair gets a ``<k>_roof_s``; the call's own ``flops`` gets none."""
    from harness.driver import Probe
    peaks = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    probe = Probe(dec, qwen2(), 0, peaks)
    probe._add("fused_step", {"flops": 1e6, "attn_flops": 300.0,
                              "attn_bytes": 20.0, "experts_flops": 50.0,
                              "experts_bytes": 40.0, "rows": 3})
    w = probe.work["fused_step"]
    assert w["attn_roof_s"] == pytest.approx(3.0)       # FLOP-bound
    assert w["experts_roof_s"] == pytest.approx(4.0)    # bandwidth-bound
    assert "_roof_s" not in w and "roof_s" not in w
