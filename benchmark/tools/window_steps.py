#!/usr/bin/env python3
"""Queue depth and occupancy through a serving run's window, by quarter,
from the logs ``measure.py --keep-logs`` keeps: is the steady cell's queue
flat, does the saturated cell's grow with every slot taken?

    python benchmark/tools/window_steps.py chiprun_out/NAME/0_CELL_SEED_0 [...]
"""

from __future__ import annotations

import json
import os
import statistics
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402


def quarters(run_dir: str) -> dict:
    log = harness.load_json(os.path.join(run_dir, "client_log.json"))
    t0, seconds = log["epoch_zero"], log["seconds"]
    steps = harness.read_jsonl(os.path.join(run_dir, "serve_steps.jsonl"))
    out = {"run": os.path.basename(run_dir.rstrip("/"))}
    for field in ("queue_depth", "occupancy"):
        means = []
        for i in range(4):
            a, b = t0 + seconds * i / 4, t0 + seconds * (i + 1) / 4
            vals = [r[field] for r in steps if a <= r["t"] < b]
            means.append(round(statistics.fmean(vals), 2) if vals else None)
        out[field] = means
    inside = [r["queue_depth"] for r in steps if t0 <= r["t"] <= t0 + seconds]
    out["queue_depth_max"] = max(inside) if inside else None
    return out


if __name__ == "__main__":
    for d in sys.argv[1:]:
        print(json.dumps(quarters(d)))
