"""Traffic kind ``open-loop-stratified``: a generation server under an
open-loop, stratified schedule of streaming requests (``schedule.py``),
timed token by token (``loadgen.py``), accounted in a window that opens on
a server already at its steady occupancy (``window.py``).

Phases, all but the last counted as set-up: start the server through its
entry point (and beside it the reference process, on the CPU); one small
request to compile the programs the traffic uses (one prefill-chunk
program, one decode program); the correctness requests and the reference's
scoring of what came back; the warm-in phase of the same traffic; then the
window.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import harness
import loadgen
import schedule
import window
from harness import BenchError


def startup_line(child: harness.Child, timeout: float) -> dict:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        child.require_alive("starting the server")
        with open(child.stdout_path, errors="replace") as f:
            for line in f:
                if line.startswith('{"serving": true'):
                    return json.loads(line)
        time.sleep(0.1)
    raise BenchError(f"no start-up line in {timeout}s\n{child.tail()}")


def _check_requests(spec: dict, seed: int, vocab: int) -> list[dict]:
    """The seeded correctness requests, 20 ms apart: sent in one instant
    they overflow the server's accept queue, and the kernel then retries
    the lost connections 1, 3 and 7 s later (PR 27)."""
    import random

    rng = random.Random(seed ^ 0x5EED)
    return [{"id": f"c{i}", "due": 0.02 * i,
             "max_new_tokens": spec["new_tokens"],
             "prompt": [rng.randrange(vocab)
                        for _ in range(spec["prompt_tokens"])]}
            for i in range(spec["requests"])]


def _compare(served: list[dict], scored: list[list], check: dict) -> dict:
    """The server's greedy tokens against the reference's scoring of them
    (``reference/serve_check.py``).  The number compared is
    ``mean_regret``: over every served token of the check, the logit by
    which the reference prefers its own arg-max under the same prefix, 0
    where the two agree; its limit is ``mean_regret_limit``.  bf16 rounding
    flips a token only where two logits are nearly tied, which costs almost
    nothing; a lost bit of precision flips more and dearer ones, a wrong
    formula nearly all."""
    regrets = [r for steps in scored for _, _, r in steps]
    short = sum(len(got.get("tokens") or []) != got["max_new_tokens"]
                for got in served)
    mean = sum(regrets) / len(regrets) if regrets else float("inf")
    return {"ok": bool(mean <= check["mean_regret_limit"] and short == 0
                       and len(regrets) >= check["min_positions"]),
            "mean_regret": mean,
            "mean_regret_limit": check["mean_regret_limit"],
            "largest_regret": max(regrets, default=0.0),
            "positions_checked": len(regrets),
            "positions_differing": sum(r > 0 for r in regrets),
            "min_positions": check["min_positions"],
            "requests_short_of_tokens": short}


def check_served(served: list[dict], out: str, ref: subprocess.Popen,
                 check: dict, wait_s: float = 300.0) -> dict:
    """Hand the served tokens to the waiting reference process and compare
    when it has scored them (a few seconds: it has compiled already)."""
    path = os.path.join(out, "reference_out.json")
    tmp = os.path.join(out, "served.tmp")
    with open(tmp, "w") as f:
        json.dump({"tokens": [r.get("tokens") or [] for r in served]}, f)
    os.replace(tmp, os.path.join(out, "served.json"))
    deadline = time.monotonic() + wait_s
    while not os.path.exists(path):
        if ref.poll() is not None and not os.path.exists(path):
            raise BenchError("the reference process produced nothing; see "
                             f"{out}/reference.log")
        if time.monotonic() > deadline:
            raise BenchError(f"the reference did not answer in {wait_s} s")
        time.sleep(0.05)
    reference = harness.load_json(path)
    return {**_compare(served, reference["scored"], check),
            "reference_file": reference["reference_file"]}


def start_reference(ctx: dict, check_reqs: list[dict], child_seed: int,
                    cpus: set[int]) -> tuple[subprocess.Popen, object]:
    """The reference process: it makes the weights and compiles on the CPU
    while the server starts, then waits for ``served.json``.  It shares the
    server's cores and keeps off the load generator's."""
    config, out = ctx["config"], ctx["out"]
    ref_in = os.path.join(out, "reference_in.json")
    with open(ref_in, "w") as f:
        json.dump({"config": config, "seed": child_seed, "wait_s": 1200,
                   "requests": check_reqs,
                   "reference_file": harness.find_file(
                       ctx["roots"], "reference", config["reference"],
                       ".py")}, f)
    ref_env = {**harness.child_env(out), "JAX_PLATFORMS": "cpu"}
    ref_log = open(os.path.join(out, "reference.log"), "w")
    ref = subprocess.Popen(
        [sys.executable,
         os.path.join(harness.BENCH, "reference", "serve_check.py"),
         ref_in, os.path.join(out, "served.json"),
         os.path.join(out, "reference_out.json")], cwd=harness.ROOT,
        env=ref_env, stdout=ref_log, stderr=subprocess.STDOUT)
    os.sched_setaffinity(ref.pid, cpus)
    return ref, ref_log


def run(ctx: dict) -> dict:
    config, traffic, out = ctx["config"], ctx["traffic"], ctx["out"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    vocab = config["vocab_size"]
    check = config["correctness"]
    child_seed = seed % (2 ** 31 - 1)
    gen_cpus, child_cpus = harness.split_cpus()

    check_reqs = _check_requests(check, seed, vocab)
    argv = [*config["argv"], "--seed", str(child_seed), "--port", "0",
            "--logdir", os.path.join(out, "serve")]
    child = harness.Child(out, config["entry"], argv, cpus=child_cpus)
    ref, ref_log = start_reference(ctx, check_reqs, child_seed, child_cpus)
    try:
        os.sched_setaffinity(0, gen_cpus)
        started = startup_line(child, timeout=900)
        device = started["device"]
        harness.require_device(device, config, ctx["chips"])
        host, port = "127.0.0.1", started["port"]
        sampling = traffic.get("sampling", {"temperature": 0.0})

        # compile the two programs the traffic uses, then the check
        warm = {"id": "warm", "due": 0.0, "max_new_tokens": 2,
                "prompt": [1] * (config["prefill_chunk"] + 1)}
        got = loadgen.run(host, port, [warm], time.monotonic(), 900,
                          sampling, keep_tokens=True, until_done=True)
        if got[0]["status"] != 200 or len(got[0].get("tokens", [])) != 2:
            raise BenchError(f"warm-up request failed: {got[0]}")
        served = loadgen.run(host, port, check_reqs, time.monotonic(), 300,
                             sampling, keep_tokens=True, until_done=True)
        verdict = check_served(served, out, ref, check)

        plan = schedule.build(traffic, seconds, seed, vocab)
        warm_in = float(traffic.get("warm_in_s", 0))
        t_zero = time.monotonic() + warm_in + 0.25
        epoch_zero = time.time() + (t_zero - time.monotonic())
        trace_dir = os.path.join(out, "trace")
        if ctx["trace"]:
            # the control thread polls every 50 ms; the trace opens
            # `trace_at_s` into the window
            delay = warm_in + 0.25 + traffic["trace_at_s"]
            threading.Timer(delay, child.send, args=(
                "trace", {"dir": trace_dir,
                          "seconds": traffic["trace_seconds"]})).start()
        logs = loadgen.run(host, port, plan, t_zero, seconds, sampling)
        # closing a trace with the Python tracer on takes tens of seconds
        # (the more slots stream, the longer), and the child's one control
        # thread answers nothing else until it has
        trace_done = child.result("trace_done", 240) if ctx["trace"] else None
        mem = child.command("mem", {}, timeout=30)
    finally:
        child.stop(grace=20)
        ref.kill()      # done long ago, unless the run failed before it
        ref.wait()
        ref_log.close()

    stats = window.account(logs, seconds, float(traffic.get("guard_s", 0)),
                           bool(traffic.get("judge_ttft", True)))
    correct = verdict["ok"] and stats["refused"] == 0
    with open(os.path.join(out, "client_log.json"), "w") as f:
        json.dump({"epoch_zero": epoch_zero, "seconds": seconds,
                   "requests": logs}, f)
    return {
        "setup_s": epoch_zero - ctx["t_process_start"],
        "end_to_end": stats,
        "correct": bool(correct), "correct_detail": verdict,
        "attempted": stats["attempted"], "failed": stats["failed"],
        "device": {**device, "memory_peak_bytes": harness.memory_peak(mem)},
        "layer": {
            "client": stats, "logs": logs, "epoch_zero": epoch_zero,
            "window": (epoch_zero, epoch_zero + seconds),
            "trace_dir": trace_dir,
            "trace_done": trace_done, "compiles": os.path.join(
                child.ctl, "compiles.jsonl"),
        },
    }
