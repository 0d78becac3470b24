"""End-to-end arithmetic on hand-made stamps: latencies are taken from
the due time, lateness is reported, and a stall shows."""

import pytest

from perfbench.sources import host_clock as hc
from perfbench.sources.host_clock import Stamps


def req(due, sent, prompt_len, max_new, tokens, ended=None, failed=False):
    return Stamps(index=0, due=due, sent=sent, prompt_len=prompt_len,
                  max_new=max_new, tokens=list(tokens), ended=ended,
                  failed=failed)


def test_ttft_is_taken_from_the_due_time_not_the_send_time():
    r = req(due=10.0, sent=10.4, prompt_len=8, max_new=2,
            tokens=[11.0, 11.1], ended=11.1)
    assert hc.ttfts([r], 10.0, 20.0) == [pytest.approx(1.0)]
    assert hc.lateness([r]) == [pytest.approx(0.4)]


def test_ttft_counts_failures_as_the_window_and_waiters_so_far():
    failed = req(12.0, 12.0, 8, 2, [], ended=13.0, failed=True)
    waiting = req(17.0, 17.0, 8, 2, [])
    late_first = req(18.0, 18.0, 8, 2, [21.0])  # first token after t1
    outside = req(25.0, 25.0, 8, 2, [26.0])
    got = hc.ttfts([failed, waiting, late_first, outside], 10.0, 20.0)
    assert got == [pytest.approx(10.0), pytest.approx(3.0),
                   pytest.approx(2.0)]


def test_gaps_are_all_gaps_of_all_requests_in_the_window():
    a = req(0.0, 0.0, 4, 4, [1.0, 1.1, 1.3, 1.6], ended=1.6)
    b = req(0.0, 0.0, 4, 3, [2.0, 2.5, 9.0], ended=9.0)
    got = sorted(hc.gaps([a, b], 0.5, 5.0))
    # b's last gap ends outside the window; b still waits at t1 = 5.0,
    # so its open gap (5.0 - 2.5) counts
    assert got == [pytest.approx(x) for x in (0.1, 0.2, 0.3, 0.5, 2.5)]


def test_a_stall_shows_in_the_gap_tail_and_in_the_rate():
    steady = [req(0.0, 0.0, 100, 101,
                  [1.0 + 0.1 * i for i in range(101)], ended=11.0)]
    stalled_times = [1.0 + 0.1 * i for i in range(50)]
    stalled_times += [t + 4.0 for t in
                      [1.0 + 0.1 * i for i in range(50, 61)]]
    stalled = [req(0.0, 0.0, 100, 101, stalled_times)]
    p95 = lambda rs: hc.percentile(hc.gaps(rs, 0.0, 11.0), 95)  # noqa: E731
    assert p95(steady) == pytest.approx(0.1)
    assert hc.percentile(hc.gaps(stalled, 0.0, 11.0), 100) \
        == pytest.approx(4.1)
    # over ALL the window's seconds: the stall costs tokens per second
    rate = lambda rs: (hc.prompt_tokens(rs, 0.0, 11.0)  # noqa: E731
                       + hc.output_tokens(rs, 0.0, 11.0)) / 11.0
    assert rate(steady) == pytest.approx((100 + 101) / 11.0)
    assert rate(stalled) == pytest.approx((100 + 61) / 11.0)
    assert rate(stalled) < 0.85 * rate(steady)


def test_prompt_tokens_are_credited_between_due_and_first_token():
    # half of the span [8, 12] lies inside the window [10, 20]
    r = req(8.0, 8.0, 400, 4, [12.0, 12.1])
    assert hc.prompt_tokens([r], 10.0, 20.0) == pytest.approx(200.0)
    # no first token yet: nothing is known, nothing is credited
    assert hc.prompt_tokens([req(15.0, 15.0, 400, 4, [])], 10.0, 20.0) == 0
    # first token after the close: the part before it counts
    r = req(18.0, 18.0, 300, 4, [21.0])
    assert hc.prompt_tokens([r], 10.0, 20.0) == pytest.approx(200.0)


def test_percentile_of_nothing_is_nothing():
    assert hc.percentile([], 95) is None
    assert hc.percentile([1.0, 2.0, 3.0], 50) == pytest.approx(2.0)
