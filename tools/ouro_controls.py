#!/usr/bin/env python3
"""The mechanism controls of the Ouro serving check, on the chip: a server
that is wrong in ONE way about the loop, its tokens scored as a run's are.

    python tools/ouro_controls.py [--config benchmark/configs/ouro-2.6b-serve.json] \\
        [--which sound,shared_slot,three_passes] SEED [SEED ...]

``benchmark/tools/control_served.py --quant fp8`` answers whether the check
sees a lost bit of precision.  These answer whether it sees the two faults a
looped stack invites and a program that is otherwise sound can have:

- ``shared_slot``: one cache layer slot a layer, shared by all passes — pool
  layer ``l`` in place of ``u * L + l`` (``serve.model._slots_a_pass``
  answering 0): every pass overwrites the rows the pass before it wrote, so
  pass ``u`` of a later token attends the *last* pass's rows of the earlier
  ones.  One prefill chunk from an empty context is right all the same (it
  writes its rows before it reads them: ``tests/test_ouro.py``), which is why
  the check's prompt is three chunks and its tokens are decoded;
- ``three_passes``: the stack run three times, not four.

Each serves the configuration's check requests through the configuration's
own programs (``control_served.serve_tokens``: ``Engine``, in this process)
and is scored by ``reference/serve_check.py``'s scorer, the plain float32
reference with four passes and 192 slots, on this machine's CPU.  Prints a
JSON row per seed; exit 1 if a faulty server passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "reference"))
sys.path.insert(0, ROOT)

import harness  # noqa: E402


def faulty(which: str, config: dict):
    """Context manager: the system wrong in the way ``which`` names."""
    import contextlib
    from unittest import mock

    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.serve import model

    if which == "sound":
        return contextlib.nullcontext()
    if which == "shared_slot":
        real = model._slots_a_pass
        return mock.patch.object(
            model, "_slots_a_pass",
            lambda cfg, layers: dict.fromkeys(real(cfg, layers), 0))
    if which == "three_passes":
        preset = getattr(models, config["system_config"])
        return mock.patch.object(
            models, config["system_config"],
            lambda: dataclasses.replace(preset(), total_ut_steps=3))
    raise SystemExit(f"no control {which!r}")


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default=os.path.join(
        BENCH, "configs", "ouro-2.6b-serve.json"))
    p.add_argument("--which", default="sound,shared_slot,three_passes")
    p.add_argument("seeds", nargs="+", type=int)
    args = p.parse_args(argv)

    import jax

    import serve_check

    served_tool = harness.load_module(os.path.join(
        BENCH, "tools", "control_served.py"))
    config = harness.load_json(args.config)
    kind = harness.load_module(os.path.join(
        BENCH, "traffic_kinds", "open-loop-stratified.py"))
    reference = harness.load_module(harness.find_file(
        [BENCH], "reference", config["reference"], ".py"))
    check = config["correctness"]
    n_new = check["new_tokens"]
    cpu = jax.devices("cpu")[0]
    passed = 0
    for seed in args.seeds:
        requests = kind._check_requests(check, seed, config["vocab_size"])
        prompts = [r["prompt"] for r in requests]
        with jax.default_device(cpu):
            params = reference.init_params(config, seed % (2 ** 31 - 1))
            score = serve_check.scorer(reference, config, len(prompts[0]))
        row = {"seed": seed, "limit": check["mean_regret_limit"]}
        for which in args.which.split(","):
            t0 = time.time()
            on_device = jax.device_put(params, jax.devices()[0])
            jax.block_until_ready(on_device)
            with faulty(which, config):
                tokens, _, _ = served_tool.serve_tokens(
                    config, on_device, prompts, n_new)
            del on_device
            gc.collect()
            t1 = time.time()
            served = [{"tokens": t, "max_new_tokens": n_new} for t in tokens]
            with jax.default_device(cpu):
                scored = serve_check.score_requests(
                    score, params, prompts, tokens, n_new)
            verdict = kind._compare(served, scored, check)
            row[which] = {k: verdict[k] for k in (
                "mean_regret", "largest_regret", "positions_differing",
                "positions_checked")}
            row[which + "_ok"] = verdict["ok"]
            row[which + "_serve_s"] = round(t1 - t0, 1)
            row[which + "_score_s"] = round(time.time() - t1, 1)
            if which != "sound":
                passed += verdict["ok"]
        print(json.dumps(row), flush=True)
    print(json.dumps({"faulty_servers_that_passed": passed}))
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
