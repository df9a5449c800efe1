#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width of GPT-2 small (12 layers, d 768, vocab 50,257, bf16; random
weights from a seed), and checks what comes out:

1. ``probe``      one child prints what JAX sees; anything but a TPU stops
                  the smoke here, in seconds, with a non-zero exit;
2. ``gpt_lm``     ``train.py --workload gpt_lm --seq-len 1024 --batch-size
                  16`` for 60 steps: loss finite and falling from ~ln 50257,
                  ``metrics.jsonl`` + TensorBoard events written, and the
                  lowered train step holds Mosaic custom calls for flash
                  attention, the fused loss head and LayerNorm with
                  per-device operand shapes (read from the JAX IR dump —
                  a kernel in interpret mode or replaced by its XLA
                  reference is not there);
3. ``serve``      ``serve.py --config gpt_small --port 0``: blocking,
                  streamed and concurrent ``POST /generatez`` of different
                  prompt lengths; streamed greedy tokens equal the blocking
                  reply; no KV block leaked; the decode program attends
                  through the ``paged_attn`` kernel, not its silent fallback
                  (``decode_attention``); SIGTERM drains to exit 0 with
                  ``requests.jsonl`` and the final ``metrics.jsonl`` row;
4. ``pool_form``  ``python -m distributedtensorflow_tpu.serve.pool_check`` at
                  the shapes of the benchmark's serving cells (GPT-2
                  medium, 32 slots, 2048 K/V blocks of 16 tokens): the five
                  programs that take the paged K/V pool compile, and none
                  holds — outside scope ``paged_attn`` — a ``copy``,
                  ``transpose`` or ``convert`` of a layer of the pool or
                  more; all five return the pool and alias it in place;
                  the pool's resident layout is printed;
5. ``resnet50``   ``train.py --workload imagenet_resnet50 --batch-size 128``
                  (the BASELINE metric's model: conv path + image input);
6. with four devices: ``gpt_lm`` on ``--mesh data=4 --batch-size 64`` —
                  every Mosaic call sees a quarter of the batch, the step
                  takes about what one chip takes for batch 16, and the
                  loss matches a one-chip run of the same global batch
                  (``--mesh data=1 --accum-steps 4``).

One process uses the chip at a time: this parent imports nothing that
imports jax, runs its children strictly one after another and waits for
each to exit.  Every failed check raises; nothing is caught.  The last
line of stdout is ``{"ok": true, "device": {...}}`` with the device as
JAX reported it.  Outputs go to ``chiprun_out/chip_smoke/run<N>/``.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
VOCAB = 50257
SEQ = 1024
#: Every gpt_lm leg puts 16 sequences on a chip per (micro)step.
PER_DEVICE_BATCH = 16
#: Band for the first logged gpt_lm loss: ln(50257) = 10.82 at step 0; by
#: the first boundary (step 20) it has moved, so the band is wide below.
FIRST_LOSS = (6.0, 11.5)
#: Log marker of a persistent-compile-cache hit / miss (jax._src.compiler).
CACHE_HIT = "Persistent compilation cache hit for"
CACHE_MISS = "PERSISTENT COMPILATION CACHE MISS for"


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def child_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    # count compile-cache hits from jax's own log lines
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    env.update(extra or {})
    return env


def tail(path: str, n: int = 40) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def run_child(name: str, argv: list[str], logdir: str, *, timeout: float,
              env: dict | None = None) -> tuple[str, float]:
    """Run one child to completion; returns (its log path, wall seconds).
    Its stdout and stderr go to ``<logdir>/child.log``."""
    os.makedirs(logdir, exist_ok=True)
    log = os.path.join(logdir, "child.log")
    t0 = time.monotonic()
    with open(log, "w") as f:
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=REPO, stdout=f,
            stderr=subprocess.STDOUT, env=child_env(env),
        )
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            fail(f"{name}: no exit within {timeout:.0f}s\n{tail(log)}")
    check(rc == 0, f"{name}: exit code {rc}\n{tail(log)}")
    return log, time.monotonic() - t0


def cache_counts(log: str) -> dict:
    """Persistent-compile-cache hits and misses the child logged.  Only
    the JAX_DEBUG_LOG_MODULES handler's lines ("DEBUG:...") count: the
    child's root logger prints each record a second time.  A module that
    compiles in under a second is never stored, so it misses every run."""
    counts = {"hits": 0, "misses": 0}
    with open(log, errors="replace") as f:
        for line in f:
            if line.startswith("DEBUG:"):
                counts["hits"] += CACHE_HIT in line
                counts["misses"] += CACHE_MISS in line
    return counts


def device_line(log: str) -> dict:
    """The ``device: {...}`` line train.py / serve.py log at start-up."""
    with open(log, errors="replace") as f:
        for line in f:
            m = re.search(r"device: (\{.*\})\s*$", line)
            if m:
                return json.loads(m.group(1))
    fail(f"no 'device:' line in {log}")


def require_tpu(name: str, device: dict, count: int | None = None) -> None:
    check(device.get("platform") == "tpu",
          f"{name}: ran on {device}, not on a TPU")
    if count is not None:
        check(device["count"] == count, f"{name}: {device} != {count} chips")


# --- leg 1: what does JAX see ----------------------------------------------


def probe(out: str) -> dict:
    code = ("import json; from distributedtensorflow_tpu import runtime; "
            "print('device: ' + json.dumps(runtime.device_summary()))")
    log, _ = run_child("probe", ["-c", code], os.path.join(out, "probe"),
                       timeout=180)
    device = device_line(log)
    check(device["platform"] == "tpu",
          f"no chip: JAX found {device}; this smoke only passes on a TPU")
    return device


# --- trainer legs ------------------------------------------------------------


def read_rows(path: str) -> list[dict]:
    check(os.path.exists(path), f"missing output file {path}")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def mosaic_calls(ir_dir: str) -> list[tuple[str, list[int]]]:
    """(kernel name, first operand's shape) of every Mosaic custom call in
    the lowered train step, from the JAX IR dump."""
    paths = glob.glob(os.path.join(ir_dir, "*jit_step*.mlir"))
    check(len(paths) >= 1, f"no lowered train step under {ir_dir}")
    with open(max(paths, key=os.path.getsize)) as f:
        text = f.read()
    calls = re.findall(
        r'stablehlo\.custom_call @tpu_custom_call\(.*?kernel_name = '
        r'"(\w+)".*?\}\s*:\s*\(tensor<([0-9x]+)x\w+>', text)
    return [(name, [int(d) for d in shape.split("x")])
            for name, shape in calls]


def check_kernels(name: str, ir_dir: str) -> str:
    calls = mosaic_calls(ir_dir)
    names = {n for n, _ in calls}
    need = {"flash_fwd", "layer_norm_fwd", "layer_norm_bwd",
            "fused_xent_fwd", "fused_xent_bwd_dx", "fused_xent_bwd_dw"}
    check(need <= names, f"{name}: train step lacks Mosaic calls "
                         f"{sorted(need - names)} (has {sorted(names)})")
    check("flash_bwd" in names or {"flash_bwd_dq", "flash_bwd_dkv"} <= names,
          f"{name}: no flash-attention backward kernel in {sorted(names)}")
    # leading dim: batch for attention (B, H, S, D); tokens for the rest
    # (the loss head sees S-1 positions)
    ok = {PER_DEVICE_BATCH, PER_DEVICE_BATCH * SEQ,
          PER_DEVICE_BATCH * (SEQ - 1)}
    bad = sorted({(n, tuple(s)) for n, s in calls if s[0] not in ok})
    check(not bad, f"{name}: Mosaic calls not at the per-device batch "
                   f"{PER_DEVICE_BATCH}: {bad}")
    shapes = sorted({(n, tuple(s)) for n, s in calls})
    return ", ".join(f"{n}{list(s)}" for n, s in shapes)


def train_leg(name: str, out: str, args: list[str], *, steps: int,
              lm: bool = False, expect_count: int | None = None) -> dict:
    """One ``train.py`` run with three log boundaries: the first window
    holds the compile, the other two are steady state.  ``lm`` adds the
    gpt_lm checks: loss band and fall, Mosaic kernels in the lowered step."""
    logdir = os.path.join(out, name)
    ir_dir = os.path.join(logdir, "ir")
    log, wall = run_child(
        name,
        ["train.py", *args, "--device", "tpu", "--steps", str(steps),
         "--log-every", str(steps // 3), "--logdir", logdir],
        logdir, timeout=600, env={"JAX_DUMP_IR_TO": ir_dir} if lm else {},
    )
    require_tpu(name, device_line(log), expect_count)
    rows = read_rows(os.path.join(logdir, "metrics.jsonl"))
    rows = [r for r in rows if isinstance(r.get("loss"), (int, float))]
    check(len(rows) >= 3, f"{name}: {len(rows)} metric rows, want >= 3 "
                          "(two log boundaries after the compile)")
    check(rows[-1]["step"] == steps, f"{name}: last row is step "
                                     f"{rows[-1]['step']}, not {steps}")
    losses = [r["loss"] for r in rows]
    check(all(math.isfinite(x) for x in losses),
          f"{name}: non-finite loss in {losses}")
    if lm:
        lo, hi = FIRST_LOSS
        check(lo <= losses[0] <= hi,
              f"{name}: first logged loss {losses[0]:.3f} outside "
              f"[{lo}, {hi}]")
        check(losses[-1] < losses[0],
              f"{name}: loss did not fall: {losses}")
    check(glob.glob(os.path.join(logdir, "events.out.tfevents.*")),
          f"{name}: no TensorBoard event file in {logdir} (tensorflow "
          "failed to write beside the chip?)")
    result = {
        "wall_s": wall,
        # the first window's row: what JAX traced, lowered and compiled
        # (or loaded) inside its steps, by the program's own compile log
        "compile_s": rows[0]["compile_s"],
        "steady_step_s": rows[-1]["t_step"],
        "losses": {r["step"]: r["loss"] for r in rows},
        **cache_counts(log),
    }
    kernels = " kernels: " + check_kernels(name, ir_dir) if lm else ""
    report(name, result, f"loss {losses[0]:.3f} -> {losses[-1]:.3f}"
           + kernels)
    return result


def report(name: str, r: dict, extra: str = "") -> None:
    steady = (f" steady_step={1e3 * r['steady_step_s']:.1f}ms"
              if "steady_step_s" in r else "")
    print(f"chip_smoke: {name}: ok wall={r['wall_s']:.1f}s "
          f"compile={r['compile_s']:.1f}s{steady} compile_cache: "
          f"{r['hits']} hit(s) {r['misses']} miss(es)  {extra}", flush=True)


# --- server leg -----------------------------------------------------------------


def http_json(url: str, payload: dict | None = None, timeout: float = 600):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    # a non-200 reply raises urllib.error.HTTPError: the smoke's failure
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        check(resp.status == 200, f"{url}: HTTP {resp.status}")
        return resp.read()


def generate(url: str, prompt: list[int], new: int, stream: bool = False):
    body = http_json(url, {"prompt": prompt, "max_new_tokens": new,
                           "temperature": 0.0, "timeout_s": 600,
                           "stream": stream})
    if not stream:
        reply = json.loads(body)
        tokens = reply["tokens"]
    else:
        lines = [json.loads(x) for x in body.decode().splitlines() if x]
        reply = lines[-1]
        check(reply.get("done") is True and reply.get("status") == "ok",
              f"stream ended with {reply}")
        tokens = [t for line in lines[:-1] for t in line["tokens"]]
    check(len(tokens) == new, f"asked for {new} tokens, got {len(tokens)}")
    check(all(isinstance(t, int) and 0 <= t < VOCAB for t in tokens),
          f"token ids outside [0, {VOCAB}): {tokens}")
    return tokens


def wait_for_startup_line(log: str, proc, timeout: float) -> dict:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        check(proc.poll() is None,
              f"serve: exited with {proc.returncode} before serving\n"
              f"{tail(log)}")
        with open(log, errors="replace") as f:
            for line in f:
                if line.startswith('{"serving": true'):
                    return json.loads(line)
        time.sleep(0.5)
    fail(f"serve: no start-up line within {timeout:.0f}s\n{tail(log)}")


def serve_leg(out: str) -> dict:
    logdir = os.path.join(out, "serve")
    os.makedirs(logdir, exist_ok=True)
    log = os.path.join(logdir, "child.log")
    t0 = time.monotonic()
    with open(log, "w") as f:
        proc = subprocess.Popen(
            [sys.executable, "serve.py", "--config", "gpt_small", "--port",
             "0", "--logdir", logdir],
            cwd=REPO, stdout=f, stderr=subprocess.STDOUT, env=child_env(),
        )
    try:
        started = wait_for_startup_line(log, proc, timeout=300)
        require_tpu("serve", started["device"])
        url = f"http://127.0.0.1:{started['port']}/generatez"
        p_short = [11, 22, 33, 44, 55]
        p_mid = [(7 * i + 3) % VOCAB for i in range(37)]
        p_long = [(13 * i + 5) % VOCAB for i in range(100)]

        t_first = time.monotonic()
        blocking = generate(url, p_short, 8)  # no warm-up: compiles
        first_s = time.monotonic() - t_first
        t_warm = time.monotonic()
        streamed = generate(url, p_short, 8, stream=True)
        warm_s = time.monotonic() - t_warm
        check(streamed == blocking, "greedy streamed tokens differ from "
              f"the blocking reply: {streamed} != {blocking}")

        results: dict = {}

        def one(key, prompt, new):
            # a failed check ends only this thread; the missing key below
            # is what fails the smoke
            results[key] = generate(url, prompt, new)

        threads = [threading.Thread(target=one, args=("mid", p_mid, 12)),
                   threading.Thread(target=one, args=("long", p_long, 6))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        check(set(results) == {"mid", "long"},
              f"a concurrent request failed; finished: {sorted(results)}")

        state = json.loads(http_json(url))
        kv = state["kv"]
        check(state["active_slots"] == 0 and state["queue_depth"] == 0,
              f"server not idle after the requests: {state['slots']}")
        check(kv["blocks_free"] == kv["blocks_total"],
              f"leaked KV blocks: {kv}")
        check(state["decode_attention"] == "paged_attn",
              "the decode program fell back to the plain gather of every "
              f"table column: decode_attention={state['decode_attention']!r}")
        counters = state["counters"]
        check(counters["ok"] == 4 and counters["error"] == 0,
              f"request counters: {counters}")
        check(counters["admits_into_freed_slot"] >= 1,
              f"no slot was reused: {counters}")
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    check(proc.returncode == 0, f"serve: exit code {proc.returncode} after "
                                f"SIGTERM\n{tail(log)}")
    rows = read_rows(os.path.join(logdir, "requests.jsonl"))
    ok_rows = [r for r in rows if r.get("status") == "ok"]
    check(len(ok_rows) == 4, f"requests.jsonl has {len(ok_rows)} ok rows")
    check(read_rows(os.path.join(logdir, "metrics.jsonl")),
          "serve: no final metrics.jsonl row (not a clean drain)")
    result = {"wall_s": time.monotonic() - t0, "compile_s": first_s - warm_s,
              **cache_counts(log)}
    report("serve", result,
           f"first request {first_s:.1f}s (it compiles prefill + decode, or "
           f"loads them from the cache), same request streamed "
           f"{warm_s:.2f}s; peak occupancy "
           f"{state['occupancy_max']}")
    return result


# --- the run ------------------------------------------------------------------------


# --- leg 4: the paged K/V pool stays in place --------------------------------

#: ``serve.py``'s settings in the benchmark's two serving cells
#: (benchmark/configs/gpt2-medium-serve.json), and a draft width for the
#: speculative program.
POOL_CELL = ["--config", "gpt_medium", "--max-slots", "32", "--kv-blocks",
             "2048", "--block-size", "16", "--max-context", "1024",
             "--prefill-chunk", "16", "--speculate", "4"]


def pool_form_leg(out: str) -> dict:
    """The mechanism's counter (PERF.md, PR 25): pool-sized layout or
    dtype changes in the five pool programs, compiled for this chip.  It is
    0 or it is not; the child exits non-zero and names them if it is not."""
    log, wall = run_child(
        "pool_form",
        ["-m", "distributedtensorflow_tpu.serve.pool_check", *POOL_CELL],
        os.path.join(out, "pool_form"), timeout=1200,
    )
    with open(log) as f:
        rows = [line for line in f if line.startswith('{"device"')]
    check(len(rows) == 1, f"pool_form: no report line\n{tail(log)}")
    r = json.loads(rows[0])
    require_tpu("pool_form", r["device"])
    check(sorted(r["programs"]) == sorted(
        ["prefill_chunk", "decode", "fused_decode", "fused_decode_spec",
         "copy_block"]),
        f"pool_form: checked only {sorted(r['programs'])}")
    compiles = ", ".join(f"{name} {p['compile_s']:.1f}s"
                         for name, p in r["programs"].items())
    print(f"chip_smoke: pool_form ok in {wall:.0f}s: pool "
          f"{r['pool_dtype']}{r['pool_shape']} = {r['pool_bytes'] / 1e9:.3f} "
          f"GB each, resident layout {r['resident_layout']}, taken as "
          f"{r['programs']['decode']['k_pool']} by all five programs; no "
          f"pool-sized copy, transpose or convert outside paged_attn; "
          f"k_pool and v_pool aliased in place by all five "
          f"(compile: {compiles})", flush=True)
    return r


def next_run_dir() -> str:
    root = os.path.join(REPO, "chiprun_out", "chip_smoke")
    n = 1
    while os.path.exists(os.path.join(root, f"run{n}")):
        n += 1
    path = os.path.join(root, f"run{n}")
    os.makedirs(path)
    return path


def main() -> None:
    for entry in ("train.py", "serve.py", "distributedtensorflow_tpu"):
        check(os.path.exists(os.path.join(REPO, entry)),
              f"{entry} is not beside chip_smoke.py: nothing to run")
    wanted = os.environ.get("JAX_PLATFORMS", "")
    check(not wanted or "tpu" in wanted.split(","),
          f"no chip: JAX_PLATFORMS={wanted!r} selects no TPU")
    t0 = time.monotonic()
    out = next_run_dir()
    device = probe(out)
    print(f"chip_smoke: device {device}; outputs in {out}", flush=True)

    gpt_args = ["--workload", "gpt_lm", "--seq-len", str(SEQ)]
    one_chip = train_leg(
        "gpt_lm", out, [*gpt_args, "--mesh", "data=1", "--batch-size", "16"],
        steps=60, lm=True,
    )
    serve_leg(out)
    pool_form_leg(out)
    train_leg(
        "resnet50", out,
        ["--workload", "imagenet_resnet50", "--batch-size", "128"], steps=30,
    )
    if device["count"] >= 4:
        four = train_leg(
            "gpt_lm_4chip", out,
            [*gpt_args, "--mesh", "data=4", "--batch-size", "64"], steps=60,
            lm=True, expect_count=device["count"],
        )
        ref = train_leg(
            "gpt_lm_1chip_ref", out,
            [*gpt_args, "--mesh", "data=1", "--batch-size", "64",
             "--accum-steps", "4"], steps=60, lm=True,
        )
        ratio = four["steady_step_s"] / one_chip["steady_step_s"]
        gaps = {s: abs(four["losses"][s] - ref["losses"][s])
                for s in four["losses"]}
        print(f"chip_smoke: 4 chips x batch 64 takes {ratio:.2f}x the "
              f"1-chip x batch 16 step; |loss(4 chips) - loss(1 chip, same "
              f"global batch)| by step: {gaps}", flush=True)
        check(ratio < 1.5, f"4-chip step is {ratio:.2f}x the 1-chip step at "
                           "equal per-chip batch: the chips do not share "
                           "the work")
        check(max(gaps.values()) < 0.05,
              f"4-chip loss departs from the 1-chip run: {gaps}")
    print(f"chip_smoke: all legs passed in {time.monotonic() - t0:.0f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
