"""Traffic kind ``open-loop-stratified``: a generation server under an
open-loop, stratified schedule of streaming requests (``schedule.py``),
timed token by token (``loadgen.py``), accounted in a window that opens on
a server already at its steady occupancy (``window.py``).

Phases, all but the last counted as set-up: start the server through its
entry point; one small request to compile the programs the traffic uses
(one prefill-chunk program, one decode program); the correctness requests;
the warm-in phase of the same traffic; then the window.
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
    """The seeded correctness requests (the reference process derives the
    same ones from the same numbers)."""
    import random

    rng = random.Random(seed ^ 0x5EED)
    return [{"id": f"c{i}", "due": 0.0, "max_new_tokens": spec["new_tokens"],
             "prompt": [rng.randrange(vocab)
                        for _ in range(spec["prompt_tokens"])]}
            for i in range(spec["requests"])]


def _compare(served: list[dict], reference: list[dict], tol: float) -> dict:
    """Walk each request's greedy tokens beside the reference's.  Where the
    reference's top-two margin exceeds ``tol`` the server must agree; at a
    smaller margin a different token is rounding, and the rest of that
    request is no longer comparable."""
    checked = wrong = short = 0
    for got, want in zip(served, reference):
        tokens = got.get("tokens") or []
        short += len(tokens) != got["max_new_tokens"]
        for tok, (top1, _, margin) in zip(tokens, want["steps"]):
            if margin > tol:
                checked += 1
                wrong += tok != top1
            if tok != top1:
                break
    return {"ok": wrong == 0 and short == 0, "positions_checked": checked,
            "positions_wrong": wrong, "requests_short_of_tokens": short,
            "margin_tolerance": tol}


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
    # the reference works on the CPU while the server starts
    ref_in = os.path.join(out, "reference_in.json")
    ref_out = os.path.join(out, "reference_out.json")
    with open(ref_in, "w") as f:
        json.dump({"config": config, "seed": child_seed,
                   "requests": check_reqs}, f)
    ref_env = {**harness.child_env(out), "JAX_PLATFORMS": "cpu"}
    ref_log = open(os.path.join(out, "reference.log"), "w")
    ref = subprocess.Popen(
        [sys.executable,
         os.path.join(harness.BENCH, "reference", "serve_check.py"),
         ref_in, ref_out], cwd=harness.ROOT, env=ref_env,
        stdout=ref_log, stderr=subprocess.STDOUT)
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
        mem = child.command("mem", {}, timeout=30)
        # closing a trace with the Python tracer on takes ~20 s
        trace_done = child.result("trace_done", 180) if ctx["trace"] else None
    finally:
        child.stop(grace=20)
        try:
            ref.wait(timeout=300)
        except subprocess.TimeoutExpired:
            ref.kill()
            ref.wait()
        ref_log.close()

    stats = window.account(logs, seconds, float(traffic.get("guard_s", 0)),
                           bool(traffic.get("judge_ttft", True)))
    if not os.path.exists(ref_out):
        raise BenchError("the reference process produced nothing; see "
                         f"{out}/reference.log")
    verdict = _compare(served, harness.load_json(ref_out)["requests"],
                       check["margin_tolerance"])
    verdict["min_positions"] = check["min_positions"]
    correct = (verdict["ok"]
               and verdict["positions_checked"] >= check["min_positions"]
               and stats["refused"] == 0)
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
