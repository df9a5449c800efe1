"""Traffic kind ``train-steps``: back-to-back optimizer steps of the
trainer's own loop for the window, after warm-up log rows that reach a
steady step time.

The trainer is started through the configuration's entry point on the
mesh the traffic file names (``"mesh": "data={chips}"``), with far more
steps than the run needs; the parent tails ``metrics.jsonl`` and stamps
each row with its own clock as it arrives (the rows carry no timestamp).
The window opens at the arrival of warm-up row ``warmup_rows`` and closes
at the first row that arrives ``--seconds`` or more later, so it holds
whole log intervals and every step and second between the two rows counts.
Then the child is stopped.
"""

from __future__ import annotations

import json
import math
import os
import re
import time

import flops
import harness
import preflight
from harness import BenchError


def _device_line(child: harness.Child) -> dict | None:
    with open(child.log_path, errors="replace") as f:
        for line in f:
            m = re.search(r"device: (\{.*\})\s*$", line)
            if m:
                return json.loads(m.group(1))
    return None


def run(ctx: dict) -> dict:
    config, traffic, out = ctx["config"], ctx["traffic"], ctx["out"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    child_seed = seed % (2 ** 31 - 1)
    per_chip = config["per_chip_batch"]
    seq = config["seq_len"]
    logdir = os.path.join(out, "train")
    argv = [*config["argv"], "--mesh", traffic["mesh"].format(chips=chips),
            "--batch-size", str(per_chip * chips),
            "--log-every", str(traffic["log_every"]),
            "--steps", str(traffic["max_steps"]), "--seed", str(child_seed),
            "--logdir", logdir]
    child = harness.Child(out, config["entry"], argv,
                          preflight=preflight.spec_for(
                              config, ctx["roots"], child_seed))
    metrics_path = os.path.join(logdir, "metrics.jsonl")
    rows: list[dict] = []        # every loss row, with arrival time "t"
    trace_dir = os.path.join(out, "trace")
    trace_sent = False
    t_open = t_close = None
    try:
        pos = 0
        partial = ""
        device = None
        limit = time.monotonic() + traffic["setup_limit_s"] + seconds
        while t_close is None:
            if time.monotonic() > limit:
                raise BenchError(f"window not finished in time\n{child.tail()}")
            child.require_alive("training")
            if device is None:
                # the device line precedes every metrics row by a minute
                device = _device_line(child)
                if device is None:
                    time.sleep(0.2)
                    continue
                harness.require_device(device, config, chips)
            if os.path.exists(metrics_path) and (
                    os.path.getsize(metrics_path) > pos):
                with open(metrics_path) as f:
                    f.seek(pos)
                    chunk = f.read()
                    pos = f.tell()
                now = time.time()
                partial += chunk
                *lines, partial = partial.split("\n")
                for line in lines:
                    if not line.strip():
                        continue
                    row = json.loads(line)
                    if not isinstance(row.get("loss"), (int, float)):
                        continue
                    row["t"] = now
                    rows.append(row)
                    if len(rows) == traffic["warmup_rows"]:
                        t_open = now
                    elif t_open is not None and now - t_open >= seconds:
                        t_close = now
            if ctx["trace"] and t_open is not None and not trace_sent and (
                    time.time() - t_open >= traffic["trace_at_s"]):
                child.send("trace", {"dir": trace_dir,
                                     "seconds": traffic["trace_seconds"]})
                trace_sent = True
            time.sleep(0.002)
        if device is None:
            raise BenchError("the trainer logged no device line")
        mem = child.command("mem", {}, timeout=30)
        trace_done = child.result("trace_done", 180) if trace_sent else None
        pre = child.result("preflight")
    finally:
        child.stop(grace=5)

    first = traffic["warmup_rows"] - 1
    win = rows[first:]           # win[0] is the row that opened the window
    steps = win[-1]["step"] - win[0]["step"]
    elapsed = win[-1]["t"] - win[0]["t"]
    tokens_per_s = steps * per_chip * chips * seq / elapsed
    # a rehearsal names its own nominal peak: its line is not a measurement
    peak = config.get("rehearsal_peak_flops_per_s") or flops.peaks(
        device["kind"])["flops_per_s"]
    mfu = 100.0 * ctx["counts"].train_flops_per_token(
        config, seq) * tokens_per_s / (chips * peak)
    losses = [r["loss"] for r in rows]
    falling = all(math.isfinite(x) for x in losses) and (
        win[-1]["loss"] < win[0]["loss"])
    verdict = {"preflight": pre, "loss_open": win[0]["loss"],
               "loss_close": win[-1]["loss"], "finite_and_falling": falling}
    with open(os.path.join(out, "window_rows.jsonl"), "w") as f:
        for r in win[1:]:
            f.write(json.dumps(r) + "\n")
    return {
        "setup_s": t_open - ctx["t_process_start"],
        "end_to_end": {"train_mfu": mfu, "train_tokens_per_s": tokens_per_s},
        "correct": bool(falling and pre and pre["ok"]),
        "correct_detail": verdict,
        "attempted": steps, "failed": 0,
        "device": {**device, "memory_peak_bytes": harness.memory_peak(mem)},
        "layer": {
            "window": (t_open, t_close), "trace_dir": trace_dir,
            "trace_done": trace_done,
            "compiles": os.path.join(child.ctl, "compiles.jsonl"),
        },
    }
