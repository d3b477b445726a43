"""The arithmetic from a run's timeline to its end-to-end numbers.

A timeline is the load generator's record of each request: when it was
sent, and the arrival time of each token line at the client, all on one
monotonic clock. Everything counts only what happened inside the window
[w0, w1); nothing waits for what is unfinished at w1.
"""

from __future__ import annotations

import math


def pct(values, p: float) -> float:
    """Percentile by linear interpolation between closest ranks
    (p in [0, 1]); NaN on no values."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = p * (len(xs) - 1)
    lo = int(math.floor(k))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def tokens_in_window(records, w0: float, w1: float) -> int:
    """Token lines that reached a client inside the window."""
    return sum(1 for r in records for t in r["token_times"]
               if w0 <= t < w1)


def ttft_samples(records, w0: float, w1: float) -> tuple[list[float], int]:
    """Seconds from a request's send to its first token line, for every
    request sent in the window. A request with no first token by w1 is
    censored: it counts at its waiting time so far, w1 - start, a lower
    bound that can only raise the tail. Returns (samples, number
    censored)."""
    out, censored = [], 0
    for r in records:
        start = r.get("sent")
        if start is None or not (w0 <= start < w1):
            continue
        first = r["token_times"][0] if r["token_times"] else None
        if first is not None and first < w1:
            out.append(first - start)
        else:
            censored += 1
            out.append(w1 - start)
    return out, censored


def gap_samples(records, w0: float, w1: float) -> list[float]:
    """Gaps between successive token lines of one request, pooled over
    requests, for gaps that end inside the window."""
    out = []
    for r in records:
        ts = r["token_times"]
        for a, b in zip(ts, ts[1:]):
            if w0 <= b < w1 and a >= w0:
                out.append(b - a)
    return out


def count_attempted_failed(records, w0: float, w1: float) -> tuple[int, int]:
    """Requests started in the window, and those of them that failed
    inside it: refused, broken, or ended by the server with an error
    before w1 (`ended`, where the generator recorded it). One that is
    merely unfinished at w1 has not failed, whatever the tear-down after
    w1 does to it; one with no first token by w1 shows in the tail
    (`ttft_samples`)."""
    attempted = failed = 0
    for r in records:
        start = r.get("sent")
        if start is None or not (w0 <= start < w1):
            continue
        attempted += 1
        ended = r.get("ended")
        if r.get("error") and (ended is None or ended < w1):
            failed += 1
    return attempted, failed
