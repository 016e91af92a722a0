"""The trace reduction on a small synthetic trace."""
import pytest

from harness.trace import (Op, Trace, idle_gaps, label_at, module_name,
                           op_family, summarize, union_seconds)

MS = 1e6   # ns


def trace():
    # device: fused step 0-10 ms (attention kernel 2-4, matmul 4-9), idle
    # 10-14 (host drafting), prefill 14-20 (flash kernel 15-18), idle
    # 20-21, fused step 21-25
    mods = [Op("jit__fused_step(7)", 0, 10 * MS),
            Op("jit__prefill_into_slot(3)", 14 * MS, 20 * MS),
            Op("jit__fused_step(7)", 21 * MS, 25 * MS)]
    ops = [Op("custom-call.12", 2 * MS, 4 * MS),
           Op("fusion.3", 4 * MS, 9 * MS),
           Op("fusion.4", 0, 2 * MS),
           Op("custom-call.2", 15 * MS, 18 * MS),
           Op("fusion.9", 14 * MS, 15 * MS),
           Op("fusion.10", 18 * MS, 20 * MS),
           Op("fusion.3", 21 * MS, 25 * MS)]
    spans = [("bench.step", 9 * MS, 14.5 * MS),
             ("bench.draft", 10.5 * MS, 13.5 * MS),
             ("bench.admit", 19 * MS, 21.5 * MS)]
    tr = Trace(ops=ops, modules=mods, spans=spans, window_s=0.030)
    from harness.trace import _assign_modules
    _assign_modules(tr.ops, tr.modules)
    return tr


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (6, 7), (9, 10)]
    assert union_seconds(iv) == pytest.approx(6e-9)
    assert idle_gaps(iv) == [(3, 5), (7, 9)]


def test_names():
    assert module_name("jit__fused_step(123)") == "jit__fused_step"
    assert op_family("fusion.123") == "fusion"
    assert op_family("custom-call.7") == "custom-call"


def test_labels_pick_innermost_span():
    spans = trace().spans
    assert label_at(12 * MS, spans) == "draft"
    assert label_at(9.5 * MS, spans) == "step"
    assert label_at(20.5 * MS, spans) == "admit"
    assert label_at(30 * MS, spans) == "idle host"


def test_summary():
    s = summarize(trace())
    assert s.busy_s == pytest.approx(0.019)            # 10 + 6 + 4 ms
    assert s.window_s == pytest.approx(0.030)
    assert s.module_s["jit__fused_step"] == pytest.approx(0.014)
    assert s.module_calls["jit__fused_step"] == 2
    assert s.kernel_time(["jit__fused_step"], ["custom"]) == \
        pytest.approx(0.002)
    assert s.kernel_time(["jit__prefill_into_slot"], ["custom"]) == \
        pytest.approx(0.003)
    assert s.op_s[("jit__fused_step", "fusion")] == pytest.approx(0.011)
    gaps = dict((k, v) for k, v in s.top_gaps)
    assert gaps["draft"] == pytest.approx(0.005)       # 9-14 ms
    assert gaps["admit"] == pytest.approx(0.001)       # 20-21 ms
    assert s.top_ops[0] == ["_fused_step:fusion", pytest.approx(0.011)]
