"""End-to-end arithmetic: rates over the whole window, tails over every
request, open-loop latency from the due time, a failure counted as missing."""
import pytest

from bench import e2e
from bench.client import Job, Sent


def _sent(due, first=None, times=(), error=None):
    s = Sent("r", 0, Job(None, 1, ""), t_due=due)
    s.t_first, s.token_times, s.error = first or 0.0, list(times), error
    return s


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert e2e.percentile(v, 90) == 90 and e2e.percentile(v, 99) == 99
    assert e2e.percentile([5.0], 99) == 5.0
    assert e2e.percentile(list(range(1, 11)), 90) == 9


def test_rate_counts_tokens_inside_the_window_only():
    s = _sent(0.5, 1.0, times=[0.9, 1.0, 2.0, 3.0, 10.5])
    assert e2e.end_to_end([s], 1.0, 11.0, 100.0)["output_tok_s"] == pytest.approx(4 / 10)


def test_ttft_from_due_time_over_requests_due_in_the_window():
    early = _sent(0.0, 5.0, times=[5.0])          # due before the window: left out
    late_send = _sent(2.0, 2.9, times=[2.9])      # open loop: due 2.0, sent later
    fast = [_sent(3.0 + i * 0.5, 3.1 + i * 0.5, times=[3.1 + i * 0.5]) for i in range(10)]
    got = e2e.ttfts([early, late_send] + fast, 1.0, 11.0, 99.0)
    assert len(got) == 11 and max(got) == pytest.approx(0.9)
    assert e2e.end_to_end([early, late_send] + fast, 1.0, 11.0, 99.0)["ttft_p90_s"] == \
        pytest.approx(0.1)


def test_failed_and_never_served_requests_count_as_missing():
    ok = [_sent(2.0, 2.1, times=[2.1])] * 8
    failed = _sent(3.0, error="rejected")
    never = _sent(4.0)
    ttft = e2e.ttfts(ok + [failed, never], 1.0, 11.0, 70.0)
    assert sorted(ttft)[-2:] == [70.0, 70.0]
    assert e2e.percentile(ttft, 90) == 70.0
    assert e2e.missing(failed) and e2e.missing(never)


def test_itl_tail_over_every_gap_in_the_window():
    a = _sent(1.0, 1.0, times=[1.0, 1.1, 1.2, 1.3])
    b = _sent(1.0, 1.0, times=[0.5, 1.05, 1.55])  # first gap ends at 1.05: in the window
    g = sorted(e2e.gaps([a, b], 1.0, 2.0))
    assert g == pytest.approx([0.1, 0.1, 0.1, 0.5, 0.55])
    assert e2e.end_to_end([a, b], 1.0, 2.0, 9.0)["itl_p99_ms"] == pytest.approx(550.0)
