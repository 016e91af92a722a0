"""End-to-end arithmetic on made-up timestamps."""
import pytest

from harness.e2e import (Record, lateness_ms, percentile, summarize,
                         tokens_between, tpot_ms, ttft_ms)


def rec(due, submit, deltas, max_new):
    r = Record(due=due, max_new=max_new, submit=submit)
    for t, n in deltas:
        if r.first is None:
            r.first = t
        r.last = t
        r.n_tokens += n
        r.deltas.append((t, n))
    return r


def test_ttft_counts_from_the_due_time():
    # due at 1.0, the generator only handed it over at 1.5, first token 2.0
    r = rec(1.0, 1.5, [(2.0, 1), (2.5, 3)], 4)
    assert ttft_ms([r]) == [pytest.approx(1000.0)]
    assert lateness_ms([r])["max"] == pytest.approx(500.0)


def test_tpot_per_request():
    a = rec(0.0, 0.0, [(1.0, 1), (1.3, 2), (1.6, 2)], 5)   # 0.6 s / 4
    b = rec(0.0, 0.0, [(1.0, 1), (3.0, 1)], 2)             # 2.0 s / 1
    c = rec(0.0, 0.0, [(1.0, 1)], 1)                       # no gap
    assert tpot_ms([a, b, c]) == [pytest.approx(150.0),
                                  pytest.approx(2000.0)]


def test_rate_counts_tokens_inside_the_window():
    a = rec(0.0, 0.0, [(0.5, 1), (1.5, 4), (10.5, 3)], 8)
    assert tokens_between([a], 1.0, 10.0) == 4
    s = summarize([a], 1.0, 10.0)
    assert s["output_tok_s"] == pytest.approx(4 / 9.0)


def test_percentiles_over_all_requests():
    recs = [rec(0.0, 0.0, [(t, 1), (t + 1.0, 1)], 2)
            for t in [0.1 * i for i in range(1, 101)]]
    s = summarize(recs, 0.0, 100.0)
    assert s["ttft_p50_ms"] == pytest.approx(percentile(
        [100.0 * i for i in range(1, 101)], 50))
    assert s["ttft_p95_ms"] == pytest.approx(9505.0)
    assert s["tpot_p95_ms"] == pytest.approx(1000.0)
