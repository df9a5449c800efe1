#!/usr/bin/env python3
"""Re-do the window accounting of serving runs kept by ``measure.py
--keep-logs`` with today's ``window.account`` (no chip needed): the
client's token log is the measurement, the accounting is arithmetic.

    python benchmark/tools/reaccount.py chiprun_out/NAME > results.jsonl

Prints ``results.jsonl`` rows ``spread.py`` reads; ``setup_s`` is copied
from the original line.
"""

from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import window  # noqa: E402


def main(out_dir: str) -> int:
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    with open(os.path.join(out_dir, "results.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    for i, row in enumerate(rows):
        cell, seed, trace = row["run"].split(":")
        log = os.path.join(out_dir, f"{i}_{cell}_{seed}_{trace}",
                           "client_log.json")
        if row["rc"] or trace != "0" or not os.path.exists(log):
            continue
        traffic = harness.load_json(os.path.join(
            BENCH, "traffic", cells[cell]["traffic"] + ".json"))
        kept = harness.load_json(log)
        stats = window.account(kept["requests"], kept["seconds"],
                               float(traffic.get("guard_s", 0)),
                               bool(traffic.get("judge_ttft", True)))
        stats["setup_s"] = row["result"]["metrics"]["setup_s"]["value"]
        metrics = {m["name"]: {"value": stats[m["name"]], "unit": m["unit"]}
                   for m in manifest["end_to_end"]
                   if harness.applies(m, cell)}
        print(json.dumps({"run": row["run"], "rc": 0, "result": {
            **row["result"], "metrics": metrics,
            "attempted": stats["attempted"], "failed": stats["failed"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
