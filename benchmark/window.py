"""Window accounting over the load generator's token log.

Every statistic is taken from client-side token timestamps inside the
window ``[0, T]`` (seconds since the window opened): thousands of
readings, not per-request summaries of the few tens of requests that
happen to complete.

- TTFT: from the instant a request was *due* to its first streamed line,
  over the requests due in ``[-guard, T - guard)``: one whole period of the
  traffic's cycle (``schedule.py``), so every seed's population is the same
  multiset of requests, ending ``guard`` seconds before the window closes
  so that their first tokens can arrive inside it.  One with no first
  token by ``T`` is a failure (it also counts at ``T - due`` in the mean,
  which is a floor on its true wait).
- gaps: between consecutive streamed lines of one request, every request,
  counted when the *later* line arrives inside ``[0, T]``.
- tokens: every output token whose line arrives inside ``[0, T]``, whether
  or not its request finished.
"""

from __future__ import annotations

import statistics


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank-interpolated percentile (``q`` in 0..100)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def account(logs: list[dict], seconds: float, guard_s: float,
            judge_ttft: bool = True) -> dict:
    """Client-side statistics of one window.  Times in ``logs`` are seconds
    relative to the window's opening.  ``judge_ttft`` is for a cell below
    capacity; above it the queue grows by design, a request still queued
    at ``T`` is no failure, and TTFT is only recorded."""
    ttft, gaps, late = [], [], []
    tokens = 0
    attempted = failed = refused = 0
    for r in logs:
        times = r["token_times"]
        if 0 <= r["due"] < seconds and r["sent"] is not None:
            late.append(r["sent"] - r["due"])
        refused += r["status"] not in (None, 200)
        bad = r["status"] not in (None, 200) or r["error"] is not None
        in_population = -guard_s <= r["due"] < seconds - guard_s
        if in_population or bad:
            attempted += 1
        if bad:
            # refused or broken, in the warm-in and the guard too
            failed += 1
        elif in_population:
            if times and times[0] <= seconds:
                ttft.append(times[0] - r["due"])
            elif judge_ttft:
                failed += 1
                ttft.append(seconds - r["due"])
        for i, t in enumerate(times):
            if 0 <= t <= seconds:
                tokens += r["token_counts"][i]
                if i > 0:
                    gaps.append(t - times[i - 1])
    out = {"attempted": attempted, "failed": failed, "refused": refused,
           "n_ttft": len(ttft), "n_gaps": len(gaps), "tokens": tokens,
           "serve_tok_per_s": tokens / seconds}
    if ttft:
        out["ttft_mean_ms"] = 1e3 * statistics.fmean(ttft)
        out["ttft_p90_ms"] = 1e3 * percentile(ttft, 90)
    if gaps:
        out["itl_mean_ms"] = 1e3 * statistics.fmean(gaps)
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
        out["itl_p99_ms"] = 1e3 * percentile(gaps, 99)
    if late:
        out["loadgen_late_p95_ms"] = 1e3 * percentile(late, 95)
    return out
