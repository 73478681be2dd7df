"""The one traffic generator: a traffic file's parameters in, requests out.

Steadiness rule: **the seed neither resamples nor reorders.**  A traffic
file fixes a multiset — N prompt lengths, N answer lengths and N arrival
gaps, taken as evenly spaced quantiles of the stated distributions — and,
with its ``order_seed``, their order and pairing.  Every run of a cell
replays that one schedule; ``--seed`` fills in the token ids (and, in the
program, the weights).  So a cell's time to first token is the time to
first token of ONE schedule.  (Why: with some 50 requests in a window,
the ORDER of heavy-tailed prompts and bursty gaps moved the median time
to first token by 58% from seed to seed and the tokens per second by
3.6%, while two runs of one order agreed to 1-3% and 0.1%; my chip runs,
PR 23.  A cell that wants another order is another traffic file.)

The permutation is stratified: the sorted multiset is dealt round-robin
into strata of about ``strata_requests`` values, each stratum is
shuffled and the strata are laid end to end (in shuffled order), so any
stretch of a few seconds holds close to the whole distribution and a
window's last seconds look alike from seed to seed.

No JAX here: the program receives only the generated inputs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np
from scipy import stats


def quantile_values(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles ((i + 0.5) / n) of ``dist``, sorted."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        v = dist["median"] * np.exp(dist["sigma"] * stats.norm.ppf(q))
    elif kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        v = np.exp(lo + (hi - lo) * q)
    elif kind == "uniform":
        v = dist["min"] + (dist["max"] - dist["min"]) * q
    elif kind == "gamma":               # mean 1; the caller scales it
        v = stats.gamma.ppf(q, dist["shape"]) / dist["shape"]
    elif kind == "constant":
        v = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        v = np.maximum(v, dist["min"])
    if "max" in dist:
        v = np.minimum(v, dist["max"])
    return np.asarray(v, dtype=np.float64)


def token_lengths(dist: dict, n: int) -> np.ndarray:
    return np.maximum(np.rint(quantile_values(dist, n)), 1).astype(np.int64)


def stratified_order(n: int, per_stratum: int, rng) -> np.ndarray:
    """A permutation of range(n) (indices into a SORTED multiset) in
    which every run of about ``per_stratum`` positions samples the whole
    range evenly."""
    k = max(1, int(round(n / max(per_stratum, 1))))
    strata = [np.arange(j, n, k) for j in range(k)]
    for s in strata:
        rng.shuffle(s)
    order = rng.permutation(k)
    return np.concatenate([strata[j] for j in order]) if n else \
        np.zeros(0, np.int64)


@dataclass
class Planned:
    """One request as the generator hands it to the driver."""
    index: int
    due: Optional[float]        # seconds after the schedule's start; None
                                # in a closed loop
    prompt: List[int]
    answer_tokens: int
    section: str                # 'lead_in', 'window' or 'after' (open loop)
    gap: Optional[float] = None  # the arrival gap drawn for it (open loop)


def _token_ids(rng, n: int, vocab: int) -> List[int]:
    # ids 1 .. vocab-2: never 0 (padding) and never the tokenizer's eod
    return rng.integers(1, vocab - 1, size=n).tolist()


def _prompts(lengths, rng, vocab: int, shared_prefix: int,
             repeats: int) -> List[List[int]]:
    """Token ids for each length.  ``shared_prefix`` leading tokens are
    common to all; with ``repeats`` r > 1 the requests come in groups of
    r that ask the same document (group g = index // r takes the ids of
    its first member, cut or extended to each member's length)."""
    prefix = _token_ids(rng, shared_prefix, vocab) if shared_prefix else []
    out: List[List[int]] = []
    doc: List[int] = []
    for i, n in enumerate(lengths):
        n = int(n)
        if repeats <= 1 or i % repeats == 0:
            doc = _token_ids(rng, n, vocab)
        elif len(doc) < n:
            doc = doc + _token_ids(rng, n - len(doc), vocab)
        body = doc[:n]
        out.append((prefix + body)[:max(n, 1)] if prefix else body)
    return out


def order_rng(spec: dict):
    """The generator that orders and pairs the multiset: the traffic
    file's own, never the run's."""
    return np.random.default_rng(int(spec["order_seed"]))


def _section(spec: dict, name: str, start: float, seconds: float,
             rate: float, order, rng, vocab: int, first_index: int
             ) -> List[Planned]:
    n = int(round(rate * seconds))
    if n <= 0:
        return []
    per = int(spec.get("strata_requests", 16))
    prompts_sorted = token_lengths(spec["prompt_tokens"], n)
    answers_sorted = token_lengths(spec["answer_tokens"], n)
    gaps_sorted = quantile_values(spec["arrival_gaps"], n)
    gaps_sorted = gaps_sorted * (seconds / gaps_sorted.sum())
    p = prompts_sorted[stratified_order(n, per, order)]
    a = answers_sorted[stratified_order(n, per, order)]
    g = gaps_sorted[stratified_order(n, per, order)]
    # the last arrival is half a mean gap before the section's end
    due = start + np.maximum(np.cumsum(g) - 0.5 * seconds / n, 0.0)
    ids = _prompts(p, rng, vocab, int(spec.get("shared_prefix_tokens", 0)),
                   int(spec.get("repeats", 1)))
    return [Planned(first_index + i, float(due[i]), ids[i], int(a[i]), name,
                    float(g[i])) for i in range(n)]


def open_loop_schedule(spec: dict, seconds: float, seed: int, vocab: int,
                       after_seconds: float = 0.0) -> List[Planned]:
    """Lead-in, window and (for a traced run's profiler) a stretch after
    it, each a multiset of its own: every run of the cell has exactly the
    same requests due inside the window, traced or not."""
    rng = np.random.default_rng(int(seed))
    order = order_rng(spec)
    rate = float(spec["requests_per_second"])
    lead = float(spec.get("lead_in_seconds", 0.0))
    plan: List[Planned] = []
    start = 0.0
    for name, length in (("lead_in", lead), ("window", float(seconds)),
                         ("after", float(after_seconds))):
        plan += _section(spec, name, start, length, rate, order, rng, vocab,
                         len(plan))
        start += length
    return plan


class ClosedLoopSource:
    """Documents for a closed loop of callers: the multiset of
    ``documents_per_cycle`` lengths, dealt again (in a new order) each
    time it runs out.  ``next()`` is called under the driver's lock."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec, self.vocab = spec, vocab
        self.rng = np.random.default_rng(int(seed))
        self.order = order_rng(spec)
        self.n = int(spec["documents_per_cycle"])
        self.per = int(spec.get("strata_requests", spec["callers"]))
        self.prompts_sorted = token_lengths(spec["prompt_tokens"], self.n)
        self.answers_sorted = token_lengths(spec["answer_tokens"], self.n)
        self._queue: List[Planned] = []
        self.handed_out = 0

    def _deal(self):
        p = self.prompts_sorted[stratified_order(self.n, self.per,
                                                 self.order)]
        a = self.answers_sorted[stratified_order(self.n, self.per,
                                                 self.order)]
        ids = _prompts(p, self.rng, self.vocab,
                       int(self.spec.get("shared_prefix_tokens", 0)),
                       int(self.spec.get("repeats", 1)))
        self._queue = [Planned(self.handed_out + i, None, ids[i], int(a[i]),
                               "closed_loop") for i in range(self.n)]

    def next(self) -> Planned:
        if not self._queue:
            self._deal()
        self.handed_out += 1
        return self._queue.pop(0)


def multiset(plan: List[Planned], section: Optional[str] = None
             ) -> Dict[str, list]:
    """What a schedule offers, order left out (for tests and logs)."""
    rows = [r for r in plan if section is None or r.section == section]
    dues = sorted(r.due for r in rows if r.due is not None)
    return {
        "prompt_tokens": sorted(len(r.prompt) for r in rows),
        "answer_tokens": sorted(r.answer_tokens for r in rows),
        "arrival_gaps": sorted(round(r.gap, 9) for r in rows
                               if r.gap is not None),
        "requests": len(rows),
        "last_due": dues[-1] if dues else None,
    }
