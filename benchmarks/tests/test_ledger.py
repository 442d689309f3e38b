"""The metric arithmetic on a hand-made ledger."""
import pytest

from benchmarks.harness.ledger import Record, finalize, percentile, spread, summarize

LIMITS = {"ttft_ms": 1000, "mean_gap_ms": 100}


def rec(due, send, tokens, max_tokens=None, status="ok", tag="load"):
    r = Record(session=0, turn=0, due_t=due, n_prompt=10,
               max_tokens=max_tokens or len(tokens), tag=tag)
    r.send_t, r.token_t, r.status = send, list(tokens), status
    r.n_output, r.ids_ok = len(tokens), True
    return r


def test_ttft_counts_from_the_due_time_not_the_send_time():
    r = rec(due=10.0, send=10.4, tokens=[10.5, 10.6])
    assert r.ttft_s == pytest.approx(0.5)  # not 0.1
    s = summarize([r], 0.0, 100.0, LIMITS)
    assert s["ttft_p95_ms"] == pytest.approx(500.0)
    assert s["gen_late_p95_ms"] == pytest.approx(400.0)


def test_gaps_are_pooled_over_requests_and_tokens_counted_by_arrival():
    a = rec(1.0, 1.0, [1.1, 1.2, 1.4])  # gaps 100, 200
    b = rec(2.0, 2.0, [2.1, 2.6])  # gap 500
    s = summarize([a, b], 0.0, 10.0, LIMITS)
    assert s["n_gaps"] == 3
    assert s["itl_p50_ms"] == pytest.approx(200.0)
    assert s["tokens_per_s"] == pytest.approx(5 / 10.0)


def test_window_membership():
    before = rec(due=4.0, send=4.0, tokens=[4.5, 5.5, 6.5])  # due before t0
    inside = rec(due=5.0, send=5.0, tokens=[5.2, 9.9, 10.3])  # last token after t1
    warm = rec(due=6.0, send=6.0, tokens=[6.1, 6.2], tag="warmup")
    s = summarize([before, inside, warm], 5.0, 10.0, LIMITS)
    assert s["attempted"] == 1  # only `inside` was due in [5, 10)
    # tokens that ARRIVED in the window: 5.5, 6.5 of `before`; 5.2, 9.9 of `inside`
    assert s["tokens_per_s"] == pytest.approx(4 / 5.0)
    # gaps with both ends inside: 5.5->6.5 and 5.2->9.9; not 4.5->5.5, not 9.9->10.3
    assert s["n_gaps"] == 2


def test_a_failed_request_misses_every_limit():
    ok = rec(1.0, 1.0, [1.1, 1.15])
    slow_first = rec(2.0, 2.0, [3.5, 3.55])  # 1500 ms to the first token
    slow_gaps = rec(3.0, 3.0, [3.1, 3.5, 3.9])  # mean gap 400 ms
    shed = rec(4.0, 4.0, [], max_tokens=8, status="shed")
    unfinished = rec(5.0, 5.0, [5.1], max_tokens=8, status="unfinished")
    s = summarize([ok, slow_first, slow_gaps, shed, unfinished], 0.0, 10.0, LIMITS)
    assert s["attempted"] == 5 and s["failed"] == 2
    assert s["slo_share"] == pytest.approx(100.0 * 1 / 5)
    assert s["failed_by_status"] == {"shed": 1, "unfinished": 1}
    assert s["n_ttft"] == 3  # the tail is over requests that answered


def test_finalize_statuses():
    r = Record(0, 0, 0.0, 4, max_tokens=3)
    r.token_t = [1, 2, 3]
    finalize(r, 100, [5, 6, 7], None)
    assert r.ok
    short = Record(0, 0, 0.0, 4, max_tokens=3)
    short.token_t = [1, 2]
    finalize(short, 100, [5, 6], None)
    assert short.status == "short"
    bad = Record(0, 0, 0.0, 4, max_tokens=2)
    bad.token_t = [1, 2]
    finalize(bad, 100, [5, 100], None)
    assert bad.status.startswith("error")
    cut = Record(0, 0, 0.0, 4, max_tokens=2, status="unfinished")
    finalize(cut, 100, [1, 2], None)
    assert cut.status == "unfinished"  # settled records stay settled


def test_percentile_and_spread():
    assert percentile([], 95) is None
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert spread([10, 10, 10, 10, 10, 10]) == 0
    assert spread([9, 10, 10, 10, 10, 11]) > 0
