"""End-to-end arithmetic on a hand-made event log."""

import pytest

from harness.metrics import (RequestLog, end_to_end, percentile,
                             pooled_gaps, stream_fault)

LO, HI = 10.0, 20.0


def _log(index, start, times, asked=None, due=True, ended=None,
         reason="length", error=None, tokens=None):
    asked = len(times) if asked is None else asked
    return RequestLog(index=index, prompt_len=8, asked=asked,
                      due=start if due else None, sent=start + 0.001,
                      token_times=list(times),
                      tokens=list(range(len(times))) if tokens is None
                      else tokens, ended=ended, reason=reason if ended else None,
                      error=error)


def test_percentile_interpolates():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 90) == pytest.approx(4.6)
    assert percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_gaps_belong_to_the_window_of_their_later_event():
    a = _log(0, 9.0, [9.5, 9.9, 10.1, 10.4], ended=10.4)
    b = _log(1, 19.0, [19.5, 19.9, 20.2], ended=20.2)
    gaps = pooled_gaps([a, b], LO, HI)
    assert gaps == pytest.approx([0.2, 0.3, 0.4])


def test_window_edges_tokens_ttft_and_rate():
    logs = [
        _log(0, 9.0, [9.5, 10.0, 10.5], ended=10.5),      # due before lo
        _log(1, 10.0, [10.3, 10.6], ended=10.6),          # due at lo: in
        _log(2, 19.9, [20.4, 20.5], ended=20.5),          # first token late
        _log(3, 20.0, [20.1], ended=20.1),                # due at hi: out
    ]
    out = end_to_end(logs, LO, HI, vocab=100)
    # token events at 10.0, 10.5, 10.3, 10.6 are inside [10, 20)
    assert out["tokens_in_window"] == 4
    assert out["tokens_per_s"] == pytest.approx(0.4)
    assert out["attempted"] == 2 and out["failed"] == 0
    # TTFT counts from the DUE time, for requests due in the window,
    # whenever the first token comes
    assert out["samples"]["ttft"] == 2
    assert out["ttft_p50_ms"] == pytest.approx(1e3 * (0.3 + 0.5) / 2)
    assert out["completed_in_window"] == 2               # 0 and 1 ended inside
    assert out["generator_lateness_p95_ms"] == pytest.approx(1.0)


def test_closed_loop_counts_from_the_send_time():
    r = _log(0, 12.0, [12.5], due=False, ended=12.5)
    queued = _log(1, 19.0, [], due=False)        # still waiting for a slot
    out = end_to_end([r, queued], LO, HI, vocab=100)
    assert out["ttft_p90_ms"] == pytest.approx(1e3 * (12.5 - 12.001))
    assert "generator_lateness_p95_ms" not in out
    assert out["failed"] == 0 and out["attempted"] == 2


def test_failures_short_streams_and_open_requests():
    logs = [
        _log(0, 11.0, [11.2, 11.4], asked=3, ended=11.4),          # short
        _log(1, 11.0, [11.2], ended=11.2, reason="cancelled"),
        _log(2, 11.0, [11.2], ended=11.2, tokens=[100]),            # vocab
        _log(3, 11.0, [], error="HTTP 429"),                        # refused
        _log(4, 19.0, [19.5, 19.8], asked=50),                      # open
        _log(5, 19.5, []),                                  # no first token
        _log(6, 12.0, [12.1, 12.2], ended=12.2),                    # good
    ]
    out = end_to_end(logs, LO, HI, vocab=100)
    assert set(out["faults"]) == {0, 1, 2, 3, 5}
    assert out["failed"] == 5 and out["attempted"] == 7
    assert out["completed_in_window"] == 1
    assert stream_fault(logs[4], 100) is None     # still streaming: no verdict
    assert "asked 3" in stream_fault(logs[0], 100)
