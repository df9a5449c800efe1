#!/usr/bin/env python3
"""Find the serving knee once, on the chip: one server, a ladder of rates.

    python benchmark/tools/knee_sweep.py --config gpt2-medium-serve \\
        --traffic chat-steady --rates 3.0,3.5,4.0,4.4,4.8,5.2,5.6 \\
        --seconds 50 --out chiprun_out/knee

For each rate: the traffic file's mix at that rate, its warm-in, a window
of ``--seconds``, then a drain to an idle server.  A rate is sustained
when no request is refused, the queue depth (``steps.jsonl``) over the
window's last quarter is no higher than over its second quarter, and the
tokens served in the window are at least 98.5 % of those the window's
requests ask for (a stratified cycle has lulls in which a backlog shrinks:
5.2 req/s passed the queue test with every slot taken and 97.3 % served,
PR 27).  Prints one JSON row per rate; the knee is the highest sustained
rate.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import urllib.request

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import loadgen  # noqa: E402
import schedule  # noqa: E402
import window  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=40)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--out", required=True)
    args = p.parse_args()
    kind = harness.load_module(os.path.join(
        BENCH, "traffic_kinds", "open-loop-stratified.py"))
    config = harness.load_json(os.path.join(
        BENCH, "configs", args.config + ".json"))
    traffic = harness.load_json(os.path.join(
        BENCH, "traffic", args.traffic + ".json"))
    out = harness.fresh_dir(os.path.join(harness.OUT, "knee"))
    os.makedirs(args.out, exist_ok=True)
    gen_cpus, child_cpus = harness.split_cpus()
    argv = [*config["argv"], "--seed", "1", "--port", "0", "--logdir",
            os.path.join(out, "serve")]
    child = harness.Child(out, config["entry"], argv, cpus=child_cpus)
    rows = []
    try:
        os.sched_setaffinity(0, gen_cpus)
        started = kind.startup_line(child, timeout=900)
        harness.require_device(started["device"], config, 1)
        host, port = "127.0.0.1", started["port"]
        warm = {"id": "warm", "due": 0.0, "max_new_tokens": 2,
                "prompt": [1] * (config["prefill_chunk"] + 1)}
        loadgen.run(host, port, [warm], time.monotonic(), 900,
                    {"temperature": 0.0}, until_done=True)
        for rate in [float(x) for x in args.rates.split(",")]:
            mix = {**traffic, "rate_per_s": rate}
            plan = schedule.build(mix, args.seconds, args.seed,
                                  config["vocab_size"])
            warm_in = float(mix.get("warm_in_s", 0))
            t_zero = time.monotonic() + warm_in + 0.25
            epoch_zero = time.time() + (t_zero - time.monotonic())
            logs = loadgen.run(host, port, plan, t_zero, args.seconds,
                               mix.get("sampling"))
            stats = window.account(logs, args.seconds,
                                   float(mix.get("guard_s", 0)), False)
            steps = [r for r in harness.read_jsonl(
                os.path.join(out, "serve", "steps.jsonl"))
                if epoch_zero <= r["t"] <= epoch_zero + args.seconds]

            def quarter(i, field):
                a = epoch_zero + args.seconds * i / 4
                b = epoch_zero + args.seconds * (i + 1) / 4
                vals = [r[field] for r in steps if a <= r["t"] < b]
                return statistics.fmean(vals) if vals else 0.0

            due = [r for r in plan if r["due"] >= 0]
            row = {"rate": rate, "offered": len(due),
                   "offered_tok_per_s": sum(
                       r["max_new_tokens"] for r in due) / args.seconds,
                "refused": stats["refused"],
                "queue_q2": quarter(1, "queue_depth"),
                "queue_q4": quarter(3, "queue_depth"),
                "occ_q2": quarter(1, "occupancy"),
                "occ_q4": quarter(3, "occupancy"),
                "tok_per_s": stats["serve_tok_per_s"],
                "ttft_mean_ms": stats.get("ttft_mean_ms"),
                "ttft_p90_ms": stats.get("ttft_p90_ms"),
                "itl_mean_ms": stats.get("itl_mean_ms"),
                "itl_p95_ms": stats.get("itl_p95_ms")}
            row["sustained"] = bool(
                row["refused"] == 0
                and row["queue_q4"] <= row["queue_q2"] + 0.5
                and row["tok_per_s"] >= 0.985 * row["offered_tok_per_s"])
            rows.append(row)
            print(json.dumps(row), flush=True)
            with open(os.path.join(args.out, "knee.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:   # drain to idle
                with urllib.request.urlopen(
                        f"http://{host}:{port}/generatez", timeout=30) as r:
                    st = json.load(r)
                if st["queue_depth"] == 0 and st["active_slots"] == 0:
                    break
                time.sleep(1.0)
    finally:
        child.stop(grace=20)
    good = [r["rate"] for r in rows if r["sustained"]]
    print(json.dumps({"knee": max(good) if good else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
