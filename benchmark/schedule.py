"""Stratified open-loop schedules: the same work for every seed.

For a window of ``T`` seconds at ``rate`` requests a second the number of
requests ``N = round(rate * T)`` is fixed.  The ``N`` prompt lengths,
output lengths and arrival gaps are the ``N`` mid-quantiles
``(i + 0.5) / N`` of their distributions (gaps rescaled to sum to ``T``),
each list shuffled once by the mix's own ``order_seed``.  That makes one
cycle of period ``T``; traffic is the cycle repeated for ever.  ``--seed``
chooses where in the cycle the window opens (a rotation; a mix may switch
that off with ``"rotate_by_seed": false``) and draws the token ids.  So every seed offers the same multiset of lengths and gaps in
the same cyclic order, started elsewhere, and a server in its periodic
steady state does the same work in every window: a Poisson draw of ~48
arrivals would vary by +-14 % in count alone, and an independent shuffle
per seed moved the mean TTFT by +-9 % (PERF.md section 6, PR 23).

The warm-in is the stretch of the same cycle that precedes the window.

Above capacity the server works through a backlog, so what it serves in
the window is an earlier stretch of the cycle, shorter than a period, and
which stretch depends on the rotation: six rotations read 96.6-112.0
tokens/s while each repeated to 0.6 % (PERF.md section 6, PR 23).  The
saturated mix therefore does not rotate: its seeds differ in token ids
and weights only.  Nor does the steady mix since PR 27: where the window
opens moved the mean TTFT of 192 requests by 1.7 ms (sd) between seeds,
as much again as two runs of one seed differ by (PERF.md section 2).
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def quantile(dist: dict, q: float) -> float:
    """Quantile ``q`` of a distribution given as data."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * NormalDist().inv_cdf(q))
    elif kind == "exponential":
        x = -math.log1p(-q) * dist.get("mean", 1.0)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(max(x, dist.get("min", -math.inf)), dist.get("max", math.inf))


def mid_quantiles(dist: dict, n: int) -> list[float]:
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def cycle(traffic: dict, seconds: float) -> list[dict]:
    """One period: ``round(rate * seconds)`` entries of prompt length,
    output length and gap to the next arrival, in the mix's fixed order."""
    n = round(traffic["rate_per_s"] * seconds)
    if n < 1:
        raise ValueError("the window holds no request at this rate")
    order = random.Random(traffic.get("order_seed", 0))
    prompts = [int(round(x)) for x in mid_quantiles(traffic["prompt_len"], n)]
    outputs = [int(round(x)) for x in mid_quantiles(traffic["output_len"], n)]
    gaps = mid_quantiles(traffic["gaps"], n)
    scale = seconds / sum(gaps)
    gaps = [g * scale for g in gaps]
    for seq in (prompts, outputs, gaps):
        order.shuffle(seq)
    return [{"prompt_tokens": p, "max_new_tokens": o, "gap": g}
            for p, o, g in zip(prompts, outputs, gaps)]


def build(traffic: dict, seconds: float, seed: int, vocab: int) -> list[dict]:
    """Requests with ``due`` in seconds from the window's opening: the
    window's ``N`` (due in ``[0, T)``, the first at 0), before them the
    cycle's preceding ``warm_in_s`` seconds (due negative), and before
    those ``warm_in_burst`` further entries due together at the start of
    the warm-in (a saturated cell fills its slots before the window)."""
    rng = random.Random(seed)
    entries = cycle(traffic, seconds)
    n = len(entries)
    start = rng.randrange(n) if traffic.get("rotate_by_seed", True) else 0
    placed, t = [], 0.0
    for i in range(n):
        placed.append((f"r{i}", t, entries[(start + i) % n]))
        t += entries[(start + i) % n]["gap"]
    warm = float(traffic.get("warm_in_s", 0))
    back, t = 1, 0.0
    while True:
        entry = entries[(start - back) % n]
        t -= entry["gap"]
        if t < -warm:
            break
        placed.append((f"w{back}", t, entry))
        back += 1
    for j in range(int(traffic.get("warm_in_burst", 0))):
        placed.append((f"b{j}", -warm, entries[(start - back - j) % n]))
    placed.sort(key=lambda p: p[1])
    return [{"id": rid, "due": due, "gap": e["gap"],
             "max_new_tokens": e["max_new_tokens"],
             "prompt": [rng.randrange(vocab)
                        for _ in range(e["prompt_tokens"])]}
            for rid, due, e in placed]
