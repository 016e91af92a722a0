"""Host spans of the serving loop (serving/spans.py): off by default and
free when off; when on, one serve.step per decode step whose phases tile it
and split the scheduler's own totals, request spans that carry their rid,
compile spans, and a profiler copy of every span."""
import glob

import jax
import numpy as np
import pytest

from repro.core.request import Request, SamplingParams
from repro.models.transformer import TransformerConfig, init_params
from repro.serving import spans as spans_mod
from repro.serving.api import EngineConfig, build_engine
from repro.serving.spans import (STEP_PHASES, Span, SpanLog, request_table,
                                 step_table)


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=128,
                            max_seq_len=256, kv_layout="paged",
                            kv_block_size=16)
    return cfg, init_params(cfg, jax.random.key(0))


def _prompts(n=6, seed=0, shared_len=40, tail=12):
    rng = np.random.RandomState(seed)
    shared = rng.randint(1, 128, size=shared_len).tolist()
    return [shared + rng.randint(1, 128, size=tail).tolist()
            for _ in range(n)]


def _serve(model, *, spans: bool, n=6, overlap=True):
    """Two lanes, six requests sharing a prefix: mid-flight admissions
    (suffix prefills after copy-on-write forks) settle inside decode
    steps in overlap mode."""
    cfg, params = model
    ecfg = EngineConfig(lanes=2, prefill_len=64, decoding_length=4,
                        branch_length=4, kv_layout="paged", block_size=16,
                        prefix_cache=True, overlap_drafts=overlap,
                        default_params=SamplingParams(max_new_tokens=10))
    eng = build_engine(ecfg, cfg, params)
    if spans:
        eng.scheduler.spans.enable()
    handles = [eng.submit(Request(prompt=p)) for p in _prompts(n)]
    eng.run()
    eng.scheduler.spans.disable()       # stop listening for compiles
    return eng, [h.result() for h in handles]


def test_off_records_nothing_and_builds_no_annotation(model, monkeypatch):
    built = []

    class Counting:
        def __init__(self, name, **kw):
            built.append(name)

    monkeypatch.setattr(spans_mod, "TraceAnnotation", Counting)
    eng, _ = _serve(model, spans=False)
    assert eng.stats.decode_steps > 0
    assert eng.scheduler.spans.records() == [] and built == []


def test_one_step_span_per_decode_step_tiled_by_its_phases(model):
    eng, _ = _serve(model, spans=True)
    recs = eng.scheduler.spans.records()
    steps = {s.id: s for s in recs if s.name == "serve.step"}
    assert len(steps) == eng.stats.decode_steps
    kids = {}
    for s in recs:
        if s.parent:
            assert s.parent in steps, s
            assert s.name[len("serve."):] in STEP_PHASES
            kids.setdefault(s.parent, []).append(s)
    for sid, step in steps.items():
        ph = sorted(kids[sid], key=lambda s: s.start_ns)
        assert ph[0].start_ns == step.start_ns and ph[-1].end_ns == step.end_ns
        assert all(a.end_ns == b.start_ns for a, b in zip(ph, ph[1:]))
        assert step.attr and all(isinstance(r, int) for r in step.attr)
    names = {s.name for s in recs}
    assert {"serve.settle", "serve.retire", "serve.dispatch"} <= names


def test_request_spans_carry_rid_in_order(model):
    eng, results = _serve(model, spans=True)
    recs = eng.scheduler.spans.records()
    table = request_table(recs)
    for res in results:
        mine = {s.name: s for s in recs if s.rid == res.rid}
        q, a, p = (mine[f"serve.{n}"] for n in ("queue", "admit", "prefill"))
        assert q.end_ns == a.start_ns and a.end_ns == p.start_ns
        assert q.start_ns <= p.end_ns and not (q.parent or a.parent
                                               or p.parent)
        row = table[res.rid]
        assert row["queue_ms"] * 1e-3 == pytest.approx(res.queue_s, abs=1e-6)
        assert row["engine_ttft_ms"] * 1e-3 == pytest.approx(res.ttft_s,
                                                             abs=1e-6)
    kinds = {r.stats.prefill for r in results}
    assert kinds >= {"prefill", "prefill_suffix"}


@pytest.mark.parametrize("overlap", [False, True])
def test_phases_sum_to_the_scheduler_totals(model, overlap):
    eng, _ = _serve(model, spans=True, overlap=overlap)
    rows = step_table(eng.scheduler.spans.records())
    st = eng.stats
    assert len(rows) == st.decode_steps
    assert sum(r["draft_ms"] + r["settle_ms"] + r["pack_ms"]
               for r in rows) == pytest.approx(st.host_draft_ms, rel=1e-6)
    assert sum(r["dispatch_ms"] + r["pull_ms"] for r in rows) == \
        pytest.approx(st.device_step_ms, rel=1e-6)
    assert sum(r["accept_ms"] for r in rows) == pytest.approx(
        st.accept_commit_ms, rel=1e-6)
    assert sum(r["retire_ms"] for r in rows) == pytest.approx(
        st.hidden_host_ms, rel=1e-6, abs=1e-9)
    assert all(r["step_ms"] == pytest.approx(
        sum(r[f"{p}_ms"] for p in STEP_PHASES), rel=1e-9) for r in rows)


def test_compile_spans_name_the_program():
    log = SpanLog()

    def fresh_program(x):
        return x * 3 + 1

    jax.jit(fresh_program)(np.arange(3.0))       # before enabling: unheard
    assert log.records() == []
    log.enable()
    jax.jit(fresh_program)(np.arange(5.0))       # a new shape compiles
    log.disable()
    jax.jit(fresh_program)(np.arange(7.0))       # after disabling: unheard
    comp = [s for s in log.records() if s.name == "serve.compile"]
    assert len(comp) == 1 and "fresh_program" in comp[0].attr
    assert comp[0].end_ns > comp[0].start_ns


def test_cap_drops_and_counts():
    log = SpanLog(cap=3)
    log.enable()
    for _ in range(5):
        log.open("serve.queue", rid=1).close()
    log.disable()
    assert len(log.records()) == 3 and log.dropped == 2
    log.clear()
    assert log.records() == [] and log.dropped == 0


def test_step_table_and_request_table_on_synthetic_records():
    recs = [Span("serve.draft", 0, 2_000_000, 2, parent=1),
            Span("serve.settle", 2_000_000, 5_000_000, 3, parent=1),
            Span("serve.pull", 5_000_000, 6_000_000, 4, parent=1),
            Span("serve.step", 0, 6_000_000, 1, attr=(7, 8)),
            Span("serve.queue", 0, 1_000_000, 5, rid=7),
            Span("serve.admit", 1_000_000, 1_500_000, 6, rid=7),
            Span("serve.prefill", 1_500_000, 9_000_000, 8, rid=7),
            Span("serve.queue", 0, 4_000_000, 9, rid=9)]
    (row,) = step_table(recs)
    assert row["riders"] == 2 and row["step_ms"] == pytest.approx(6.0)
    assert (row["draft_ms"], row["settle_ms"], row["pull_ms"],
            row["pack_ms"]) == pytest.approx((2.0, 3.0, 1.0, 0.0))
    t = request_table(recs)
    assert t[7]["engine_ttft_ms"] == pytest.approx(9.0)
    assert t[7]["admit_ms"] == pytest.approx(0.5)
    assert "engine_ttft_ms" not in t[9]          # not admitted yet


def test_profiler_copies_match_the_log(model, tmp_path):
    """Every span reaches a running profiler's trace under its own name:
    as many serve.step events there as in the log."""
    from jax.profiler import ProfileData
    _serve(model, spans=True, n=2)                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, _ = _serve(model, spans=True)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    counts = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    counts[ev.name] = counts.get(ev.name, 0) + 1
    logged = {}
    for s in eng.scheduler.spans.records():
        if s.name != "serve.compile":
            logged[s.name] = logged.get(s.name, 0) + 1
    assert counts == logged and counts["serve.step"] == eng.stats.decode_steps
