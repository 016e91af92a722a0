"""FLOP and byte functions against hand counts at the cells' shapes."""
import json
import os

import pytest

from harness import flops
from harness.spec import family_module

dec = family_module({"family": "decoder"})
shape_of = dec.shape_of

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def shape(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return shape_of(json.load(f))


def test_qwen2_counts():
    s = shape("qwen2-1.5b")
    # per layer: q 1536*1536, k and v 1536*256 each, o 1536*1536,
    # MLP 3*1536*8960
    per_layer = 2359296 + 786432 + 2359296 + 41287680
    assert dec.linear_params(s) == 28 * per_layer == 1310195712
    assert dec.head_params(s) == 1536 * 151936
    # one token through the whole model: 3.09 GFLOP
    tok = 2 * (dec.linear_params(s) + dec.head_params(s))
    assert tok == pytest.approx(3.087e9, rel=1e-3)


# microsoft/Phi-3-mini-4k-instruct config.json: MHA with a 96-wide head
PHI3 = {"name": "phi3-mini-3.8b", "num_hidden_layers": 32,
        "hidden_size": 3072, "num_attention_heads": 32,
        "num_key_value_heads": 32, "intermediate_size": 8192,
        "vocab_size": 32064, "tie_word_embeddings": False,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "hidden_act": "silu",
        "max_position_embeddings": 4096}


def test_phi3_counts():
    s = shape_of(PHI3)
    per_layer = 9437184 + 18874368 + 9437184 + 75497472
    assert dec.linear_params(s) == 32 * per_layer
    assert dec.head_params(s) == 3072 * 32064
    # K and V of one token, all layers, head size 96: 393,216 bytes
    assert dec.kv_bytes(s, 1) == 393216


def test_step_work_counts_live_rows_only():
    s = shape("qwen2-1.5b")
    w = dec.step_work(s, [(100, [1, 2, 3]), (7, [1])])
    rows, keys = 4, (100 * 3 + 6) + (7 + 1)
    per_row = 2 * (dec.linear_params(s) + dec.head_params(s))
    attn = 4 * 28 * 12 * 128 * keys
    assert w["rows"] == rows
    assert w["attn_flops"] == attn
    assert w["flops"] == per_row * rows + attn
    kv = 2 * 28 * 2 * 128 * ((100 + 3) + (7 + 1)) * 2
    qo = 2 * 28 * 12 * 128 * rows * 2
    assert w["attn_bytes"] == kv + qo


def test_prefill_work():
    s = shape_of(PHI3)
    w = dec.prefill_work(s, 10, offset=64)
    keys = 10 * 64 + 55
    assert w["attn_flops"] == 4 * 32 * 32 * 96 * keys
    assert w["flops"] == (2 * dec.linear_params(s) * 10
                          + 2 * dec.head_params(s) + w["attn_flops"])


def test_roofline_picks_the_binding_peak():
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    assert flops.roofline_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert flops.roofline_seconds(1.0, 819e9, peaks) == pytest.approx(1.0)
