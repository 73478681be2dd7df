"""How a measured window is accounted.  Pure Python over recorded
clocks and counters, so it is tested on synthetic streams.

* Throughput is counted token by token: the difference of the engine's
  token counters between the window's first and last instant over the
  window's seconds.  The window's edge cuts one chunk and one decode
  step, never a request.
* Time to first token runs from the instant a request was DUE, not from
  when the generator got round to sending it, over the requests due in
  the window.
* Gaps between consecutive output tokens of one request are pooled over
  all requests; a gap belongs to the window if its later token does.
* A percentile is the nearest-rank one, so it is a value that occurred,
  and it comes with its sample count.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in 0..100) of ``values``; None when
    there are none."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[min(rank, len(ordered)) - 1]


@dataclass
class RequestRecord:
    """One request as the benchmark saw it, all times on one host clock
    (seconds)."""
    index: int
    prompt_tokens: int
    answer_tokens: int                  # asked for
    due: float                          # when it was due to be sent
    submitted: Optional[float] = None   # when the generator sent it
    token_times: List[float] = field(default_factory=list)
    finish_reason: Optional[str] = None
    refused: Optional[str] = None       # the error, if submit raised
    queue_wait_secs: Optional[float] = None
    out_tokens: List[int] = field(default_factory=list)

    @property
    def first_token(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None

    def finished_ok(self) -> bool:
        return (self.refused is None
                and self.finish_reason in ("length", "stop")
                and len(self.token_times) > 0)


@dataclass
class CounterSnapshot:
    at: float
    values: Dict[str, float]


@dataclass
class Window:
    opened: CounterSnapshot
    closed: CounterSnapshot

    @property
    def seconds(self) -> float:
        return self.closed.at - self.opened.at

    def delta(self, name: str) -> float:
        return self.closed.values[name] - self.opened.values[name]

    def contains(self, t: Optional[float]) -> bool:
        return t is not None and self.opened.at <= t < self.closed.at


def counter_rate(window: Window, names: Sequence[str]) -> float:
    return sum(window.delta(n) for n in names) / window.seconds


def due_in_window(records: Sequence[RequestRecord], window: Window
                  ) -> List[RequestRecord]:
    return [r for r in records if window.contains(r.due)]


def ttft_from_due(records: Sequence[RequestRecord], window: Window
                  ) -> List[float]:
    """Seconds from due to first token, over the requests due in the
    window that got a first token (one that never did has failed and is
    counted there)."""
    return [r.first_token - r.due for r in due_in_window(records, window)
            if r.first_token is not None]


def token_gaps(records: Sequence[RequestRecord], window: Window
               ) -> List[float]:
    gaps: List[float] = []
    for r in records:
        t = r.token_times
        gaps.extend(b - a for a, b in zip(t, t[1:]) if window.contains(b))
    return gaps


def generator_lateness(records: Sequence[RequestRecord]) -> List[float]:
    return [r.submitted - r.due for r in records if r.submitted is not None]


def counters_account_for(records: Sequence[RequestRecord],
                         since_start: Dict[str, float]
                         ) -> Tuple[bool, Dict[str, float]]:
    """Hold the engine's counters to account: over the whole run (warm-up
    excluded) the prompt tokens made ready and the output tokens emitted
    must equal, exactly, the sums over the requests the benchmark saw
    finish.  Only meaningful once every request has finished."""
    done = [r for r in records if r.finished_ok()]
    want_prompt = sum(r.prompt_tokens for r in done)
    want_out = sum(len(r.token_times) for r in done)
    got_prompt = (since_start["prefill_tokens_computed"]
                  + since_start["prefill_tokens_cached"])
    got_out = since_start["tokens_generated"]
    detail = {"prompt_tokens_counted": got_prompt,
              "prompt_tokens_finished": want_prompt,
              "output_tokens_counted": got_out,
              "output_tokens_finished": want_out}
    return (got_prompt == want_prompt and got_out == want_out), detail


def failed_requests(records: Sequence[RequestRecord]) -> int:
    """Refused, expired, ended for another reason than length or stop,
    short of the tokens asked for, or unanswered after the drain."""
    return sum(1 for r in records
               if not r.finished_ok()
               or (r.finish_reason == "length"
                   and len(r.token_times) != r.answer_tokens))
