#!/usr/bin/env python3
"""Spreads of a measurement (``measure.py``'s ``results.jsonl``) the way
the builder's contract reads them: per cell and metric, the runs split in
two sets in the order they were made, each set's spread = (Q3 - Q1) /
median with ``statistics.quantiles(values, n=4)``, the wider of the two,
and the bound that follows (about five times it, never under 1 %).

    python benchmark/tools/spread.py chiprun_out/NAME/results.jsonl [...]
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths: list[str]) -> int:
    runs: dict[str, list[dict]] = {}
    for path in paths:
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                res = row.get("result")
                if row["rc"] == 0 and res and row["run"].endswith(":0"):
                    runs.setdefault(row["run"].split(":")[0], []).append(res)
    for cell, results in runs.items():
        half = len(results) // 2
        sets = [results[:half], results[half:]] if half >= 3 else [results]
        print(f"{cell}: {len(results)} runs, sets of "
              f"{[len(s) for s in sets]}, all correct: "
              f"{all(r['correct'] for r in results)}, failed: "
              f"{sum(r['failed'] for r in results)}")
        for name in results[0]["metrics"]:
            per_set = []
            for s in sets:
                vals = [r["metrics"][name]["value"] for r in s]
                per_set.append((statistics.median(vals), spread(vals)))
            widest = max(sp for _, sp in per_set)
            allv = [r["metrics"][name]["value"] for r in results]
            print(f"  {name:18s} medians "
                  f"{[round(m, 4) for m, _ in per_set]} spreads "
                  f"{[round(100 * sp, 3) for _, sp in per_set]} % "
                  f"min {min(allv):.4f} max {max(allv):.4f} -> bound "
                  f"{max(0.01, 5 * widest):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
