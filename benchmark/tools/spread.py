#!/usr/bin/env python3
"""Spreads of a measurement (``measure.py``'s ``results.jsonl``) the way
the driver's check reads them, so that the builder sees what the driver
will: per cell and end-to-end metric, the runs split in two sets in the
order they were made, and for each set

- the *trimmed range*: max - min of the set after leaving out the run
  farthest from the set's median, where that narrows it, in the metric's
  own unit.  The mean of the two sets' trimmed ranges may take at most
  ``TIGHT`` (50 %) of ``bound x median``, or the driver refuses the
  benchmark as too noisy for its own bound (PR 22, PR 26); ``MARGIN``
  (40 %) is what a bound is set to here;
- the *whole range*: max - min of all the set's runs.  A bound over
  ``LOOSE`` (8) times the wider whole range, as a share of the median, is
  refused as too loose, unless it is the least bound there is, 1 %;
- the interquartile spread (Q3 - Q1) / median the builder's contract
  words the rule of five in, for the record.

The second set's median may differ from the first's by at most the bound.
``setup_s`` is judged by its median only, never by its spread.

    python benchmark/tools/spread.py [--manifest BENCHMARK.json] \\
        chiprun_out/NAME/results.jsonl [...]

Exit code 1 if any line says FAIL.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

TIGHT = 0.5
MARGIN = 0.4
LOOSE = 8.0
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def iqr_spread(values: list[float]) -> float:
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def whole_range(values: list[float]) -> float:
    return max(values) - min(values)


def trimmed_range(values: list[float]) -> float:
    """Range of the set without the run farthest from its median, where
    leaving that run out narrows the range (an inner run never does)."""
    if len(values) < 3:
        return whole_range(values)
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = values[:far] + values[far + 1:]
    return min(whole_range(values), whole_range(rest))


def judge(sets: list[list[float]], bound: float) -> dict:
    """The driver's reading of two (or more) sets of one metric in one
    cell against ``bound`` (a share of the first set's median)."""
    median = statistics.median(sets[0])
    room = bound * median
    trimmed = [trimmed_range(s) for s in sets]
    share = statistics.fmean(trimmed) / room
    widest = max(whole_range(s) for s in sets)
    medians = [statistics.median(s) for s in sets]
    drift = max(abs(m - medians[0]) for m in medians) / room
    return {"median": median, "room": room, "trimmed": trimmed,
            "share_of_bound": share, "tight_ok": share <= TIGHT,
            "within_margin": share <= MARGIN,
            "loose_ok": bound <= 0.01 or widest <= 0 or (
                room <= LOOSE * widest),
            "times_widest": room / widest if widest > 0 else None,
            "medians": medians, "drift_share": drift,
            "drift_ok": drift <= 1.0,
            "iqr": [iqr_spread(s) for s in sets]}


def least_bound(sets: list[list[float]], step: float = 0.005) -> float:
    """Smallest bound >= 1 %, in steps of ``step``, whose share is within
    ``MARGIN``."""
    bound = 0.01
    while not judge(sets, bound)["within_margin"]:
        bound = round(bound + step, 6)
    return bound


def load_runs(paths: list[str]) -> dict[str, list[dict]]:
    """Untraced, finished runs of each cell, in the order they were made."""
    runs: dict[str, list[dict]] = {}
    for path in paths:
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                res = row.get("result")
                if row["rc"] == 0 and res and row["run"].endswith(":0"):
                    runs.setdefault(row["run"].split(":")[0], []).append(res)
    return runs


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--skip-first", type=int, default=0,
                   help="leave out a cell's first N runs (the compiling one)")
    p.add_argument("paths", nargs="+")
    args = p.parse_args(argv)
    with open(args.manifest) as f:
        bounds = {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}
    failed = False
    for cell, results in load_runs(args.paths).items():
        results = results[args.skip_first:]
        half = len(results) // 2
        sets = [results[:half], results[half:]] if half >= 3 else [results]
        print(f"{cell}: {len(results)} runs, sets of "
              f"{[len(s) for s in sets]}, all correct: "
              f"{all(r['correct'] for r in results)}, failed: "
              f"{sum(r['failed'] for r in results)}")
        for name in results[0]["metrics"]:
            values = [[r["metrics"][name]["value"] for r in s] for s in sets]
            bound = bounds.get(name)
            if bound is None:
                print(f"  {name}: no bound in the manifest")
                continue
            j = judge(values, bound)
            flat = [v for s in values for v in s]
            line = (f"  {name:16s} medians "
                    f"{[round(m, 4) for m in j['medians']]} min "
                    f"{min(flat):.4f} max {max(flat):.4f}; bound {bound} = "
                    f"{j['room']:.4f}; trimmed ranges "
                    f"{[round(t, 4) for t in j['trimmed']]} = "
                    f"{100 * j['share_of_bound']:.0f} % of bound; ")
            if name == "setup_s":
                ok = j["drift_ok"]
                line += (f"set medians differ by "
                         f"{100 * j['drift_share']:.0f} % of bound: "
                         f"{'PASS' if ok else 'FAIL'} (median only)")
            else:
                ok = j["tight_ok"] and j["loose_ok"] and j["drift_ok"]
                times = j["times_widest"]
                line += (
                    f"{'PASS' if j['tight_ok'] else 'FAIL'} at "
                    f"{100 * TIGHT:.0f} %"
                    f"{'' if j['within_margin'] else ' (over the margin)'}; "
                    f"bound = {'inf' if times is None else round(times, 1)} x "
                    f"widest range: {'PASS' if j['loose_ok'] else 'FAIL'} at "
                    f"{LOOSE:.0f} x; medians differ by "
                    f"{100 * j['drift_share']:.0f} % of bound: "
                    f"{'PASS' if j['drift_ok'] else 'FAIL'}; IQR spreads "
                    f"{[round(100 * s, 3) for s in j['iqr']]} %; least "
                    f"bound within {100 * MARGIN:.0f} %: "
                    f"{least_bound(values)}")
            failed |= not ok
            print(line)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
