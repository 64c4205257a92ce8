"""End-to-end arithmetic: from the client's event log to the metrics.

Everything here reads times taken at the client (``client.py``): when a
request was due, when it was sent, and when each SSE ``data:`` event
arrived. No program histogram feeds an end-to-end metric.

Window rules, fixed here for every cell:

* a token counts when its event arrived in ``[lo, hi)``;
* a gap counts when the LATER of its two events arrived in ``[lo, hi)``;
* time to first token is taken over requests that were DUE (open loop) or
  sent (closed loop) in ``[lo, hi)``, from that instant to the first token
  event, whenever that event arrives (in an open loop the driver keeps the
  load on after ``hi`` until every such request has its first token, or
  gives up after the mix's ``grace_s`` and the request counts as failed);
* a request still streaming when the run stops is neither completed nor
  failed.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence


@dataclass
class RequestLog:
    """What the client saw of one request. Times are ``time.perf_counter()``
    seconds in the benchmark's process."""
    index: int
    prompt_len: int
    asked: int
    due: Optional[float] = None          # open loop: when it should go out
    sent: Optional[float] = None         # when the request was written
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    ended: Optional[float] = None        # when the done event arrived
    reason: Optional[str] = None         # the done event's reason
    error: Optional[str] = None          # transport / HTTP / protocol fault

    @property
    def start(self) -> Optional[float]:
        """The instant latency is counted from: due time if it has one."""
        return self.due if self.due is not None else self.sent


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, ``q`` in [0, 100]; raises on empty."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    i = int(pos)
    frac = pos - i
    if i + 1 >= len(xs):
        return float(xs[-1])
    return float(xs[i] + (xs[i + 1] - xs[i]) * frac)


def stream_fault(r: RequestLog, vocab: int) -> Optional[str]:
    """Why a FINISHED request is not a good stream, or None. Rule (a) of the
    correctness gate: exactly the asked number of tokens, all inside the
    vocabulary, reason ``length``."""
    if r.error is not None:
        return r.error
    if r.ended is None:
        return None                              # still open: no verdict
    if r.reason != "length":
        return f"finished {r.reason!r}, not 'length'"
    if len(r.tokens) != r.asked:
        return f"{len(r.tokens)} tokens, asked {r.asked}"
    if not all(isinstance(t, int) and 0 <= t < vocab for t in r.tokens):
        return "token outside the vocabulary"
    return None


def in_window(t: Optional[float], lo: float, hi: float) -> bool:
    return t is not None and lo <= t < hi


def pooled_gaps(logs: Sequence[RequestLog], lo: float, hi: float
                ) -> List[float]:
    """Seconds between consecutive token events of one request, pooled over
    all requests; a gap belongs to the window its later event falls in."""
    out = []
    for r in logs:
        ts = r.token_times
        out.extend(ts[i] - ts[i - 1] for i in range(1, len(ts))
                   if lo <= ts[i] < hi)
    return out


def end_to_end(logs: Sequence[RequestLog], lo: float, hi: float,
               vocab: int) -> Dict[str, Any]:
    """Every end-to-end quantity a cell may report, with its sample count.
    A quantity with no sample is absent (the caller decides whether the cell
    needed it)."""
    out: Dict[str, Any] = {"samples": {}}
    tokens = sum(1 for r in logs for t in r.token_times if lo <= t < hi)
    out["tokens_in_window"] = tokens
    out["tokens_per_s"] = tokens / (hi - lo)
    gaps = pooled_gaps(logs, lo, hi)
    out["samples"]["itl"] = len(gaps)
    if gaps:
        out["itl_p50_ms"] = 1e3 * percentile(gaps, 50)
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    started = [r for r in logs if in_window(r.start, lo, hi)]
    ttft = [r.token_times[0] - r.start for r in started if r.token_times]
    out["samples"]["ttft"] = len(ttft)
    if ttft:
        out["ttft_p90_ms"] = 1e3 * percentile(ttft, 90)
        out["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
    faults = {r.index: f for r in logs
              if (f := stream_fault(r, vocab)) is not None}
    # open loop only: a request that was due in the window and never got a
    # first token (the driver waited grace_s for it) missed every limit. In a
    # closed loop half the clients are queueing by design when the run stops.
    no_first = [r.index for r in started if r.due is not None
                and not r.token_times and r.error is None]
    for i in no_first:
        faults[i] = "no first token before the run stopped"
    completed = [r for r in logs if in_window(r.ended, lo, hi)
                 and r.index not in faults]
    out["attempted"] = len(started)
    out["failed"] = len(faults)
    out["faults"] = faults
    out["completed_in_window"] = len(completed)
    out["requests_per_s"] = len(completed) / (hi - lo)
    lateness = [r.sent - r.due for r in logs
                if r.due is not None and r.sent is not None]
    if lateness:
        out["generator_lateness_p95_ms"] = 1e3 * percentile(lateness, 95)
    return out
