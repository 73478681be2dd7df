"""Window accounting on a synthetic event stream."""
import pytest

from harness.window import (CounterSnapshot, RequestRecord, Window,
                            counter_rate, counters_account_for,
                            failed_requests, generator_lateness, percentile,
                            token_gaps, ttft_from_due)

TOKENS = ("prefill_tokens_computed", "prefill_tokens_cached",
          "tokens_generated")


def _window(t0, t1, before, after):
    keys = TOKENS
    return Window(CounterSnapshot(t0, dict(zip(keys, before))),
                  CounterSnapshot(t1, dict(zip(keys, after))))


def test_throughput_is_counted_token_by_token():
    """A request that is cut by the window's edge still counts for the
    tokens made inside it: the rate comes from the counters' growth, not
    from completed requests."""
    w = _window(10.0, 20.0, (1000, 64, 300), (1640, 128, 500))
    # 640 computed + 64 adopted + 200 generated in 10 s
    assert counter_rate(w, TOKENS) == pytest.approx(90.4)
    assert w.seconds == pytest.approx(10.0)


def test_ttft_runs_from_the_due_time_over_requests_due_in_the_window():
    w = _window(10.0, 20.0, (0, 0, 0), (0, 0, 0))
    rs = [
        # due before the window: left out, however late it answered
        RequestRecord(0, 8, 2, due=9.5, submitted=9.5, token_times=[12.0]),
        # sent 0.4 s late by the generator: the wait counts
        RequestRecord(1, 8, 2, due=11.0, submitted=11.4,
                      token_times=[11.9, 12.0]),
        # due inside, first token after the window closed: still counted
        RequestRecord(2, 8, 2, due=19.5, submitted=19.5,
                      token_times=[21.0]),
        # due at the closing instant: outside (half-open window)
        RequestRecord(3, 8, 2, due=20.0, submitted=20.0, token_times=[20.3]),
    ]
    assert ttft_from_due(rs, w) == pytest.approx([0.9, 1.5])
    assert generator_lateness(rs) == pytest.approx([0.0, 0.4, 0.0, 0.0])


def test_gaps_are_pooled_and_belong_to_the_window_of_their_later_token():
    w = _window(10.0, 20.0, (0, 0, 0), (0, 0, 0))
    rs = [RequestRecord(0, 8, 4, due=9.0,
                        token_times=[9.5, 9.9, 10.2, 10.25]),
          RequestRecord(1, 8, 3, due=19.0, token_times=[19.8, 19.9, 20.1])]
    # 9.5->9.9 ends before the window; 19.9->20.1 ends after it
    assert token_gaps(rs, w) == pytest.approx([0.3, 0.05, 0.1])


@pytest.mark.parametrize("q,want", [(50, 5), (95, 10), (90, 9), (100, 10),
                                    (1, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert percentile(list(range(1, 11)), q) == want


def test_percentile_of_nothing_is_none():
    assert percentile([], 50) is None


def test_counters_must_equal_the_finished_requests_exactly():
    rs = [RequestRecord(0, 100, 3, 0.0, token_times=[1, 2, 3],
                        finish_reason="length"),
          RequestRecord(1, 50, 2, 0.0, token_times=[1, 2],
                        finish_reason="length")]
    good = {"prefill_tokens_computed": 134, "prefill_tokens_cached": 16,
            "tokens_generated": 5}
    assert counters_account_for(rs, good)[0]
    for key in good:                      # one token astray is a failure
        assert not counters_account_for(rs, dict(good, **{key: good[key] + 1}))[0]


def test_failed_requests():
    ok = RequestRecord(0, 4, 2, 0.0, token_times=[1, 2],
                       finish_reason="length")
    refused = RequestRecord(1, 4, 2, 0.0, refused="QueueFull")
    expired = RequestRecord(2, 4, 2, 0.0, token_times=[1],
                            finish_reason="deadline")
    unanswered = RequestRecord(3, 4, 2, 0.0)
    short = RequestRecord(4, 4, 2, 0.0, token_times=[1],
                          finish_reason="length")
    assert failed_requests([ok, refused, expired, unanswered, short]) == 4
