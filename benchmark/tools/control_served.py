#!/usr/bin/env python3
"""The fp8 control of a serving comparison whose check prompts are long:
the *system* in the server's place, with its matrices rounded to fp8.

    python benchmark/tools/control_served.py [--quant fp8|int8] [--sound] \\
        [--busy] CONFIG.json SEED [SEED ...]

``tools/control.py serve`` makes its control's tokens with the reference,
the whole forward recomputed for every token: at check prompts of 4144
tokens that is 48 forwards of 71 TFLOP a weight set, and its float32 copy
of the matrices does not fit beside them on the chip.  This tool takes the
other generator there is: the configuration's own serving programs
(``Engine``, in this process, the check's requests through ``submit`` and
``step``), given weights rounded per output channel (symmetric, absmax) to
what an fp8 (e4m3) or int8 server would hold — every array of two or more
dimensions, the stacked experts included — and stored back in the
configuration's type.  The tokens it serves are scored exactly as a run's
are: ``reference/serve_check.py``'s scorer, the plain float32 reference on
the *unrounded* weights, on this machine's CPU.  ``--sound`` also serves
and scores the unrounded weights (every benchmark run's ``detail`` has that
side already).  Prints a JSON row per seed; exit 1 if a control passed.

``--busy`` is the check a run cannot afford (``correct`` comes from the
check's few long requests, alone on the server: scoring 64 slots of 4,400
tokens would take the reference 20 minutes).  It serves the check's
requests in the configuration's own engine — its ``max_slots`` and its two
pools — with every other slot decoding a short seeded request (32 + 320
tokens) beside them, so that the scored tokens come from full batches:
several tokens on one expert, paged attention over 64 live slots, rings
turning in a pool the other slots hold most of.  The long requests and a
sample of 8 short ones are scored; the row says how many slots decoded
together (``busy_occupancy``: least and mean over the decode iterations of
the long requests' last 128 tokens).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "reference"))
sys.path.insert(0, os.path.dirname(BENCH))

import harness  # noqa: E402


def round_arrays(params, quant: str):
    """Every array of two or more dimensions rounded per output channel
    (the last axis; absmax over the one before it), in its own dtype."""
    import jax
    import jax.numpy as jnp

    def one(a):
        if a.ndim < 2:
            return a
        x = a.astype(jnp.float32)
        top = jnp.maximum(jnp.max(jnp.abs(x), axis=-2, keepdims=True), 1e-30)
        if quant == "int8":
            y = jnp.round(x / (top / 127.0)) * (top / 127.0)
        else:
            y = (x / (top / 448.0)).astype(jnp.float8_e4m3fn).astype(
                jnp.float32) * (top / 448.0)
        return y.astype(a.dtype)
    return jax.tree.map(jax.jit(one), params)


SHORT_PROMPT, SHORT_NEW, SHORT_SCORED = 32, 320, 8


def short_new(config: dict) -> int:
    return min(SHORT_NEW, config["max_context"] - SHORT_PROMPT)


def serve_tokens(config: dict, on_device, prompts, n_new: int,
                 fillers=()):
    """The check's requests through the configuration's own programs, on
    weights already on the serving device.  With ``fillers`` (short
    prompts, ``--busy``) the engine is the configuration's — every slot,
    both pools — and they decode beside the check's requests; returns the
    check's tokens, the fillers', and the (least, mean) slots decoding
    over the iterations of the check's last 128 tokens."""
    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.serve.engine import Engine

    cfg = getattr(models, config["system_config"])()
    size = dict(max_slots=len(prompts))
    if fillers:
        size = dict(max_slots=config["max_slots"],
                    num_blocks=config.get("kv_blocks"),
                    window_blocks=config.get("kv_window_blocks"))
    engine = Engine(on_device, cfg, block_size=config["block_size"],
                    prefill_chunk=config["prefill_chunk"],
                    max_context=config["max_context"], **size)
    reqs = [engine.submit(p, max_new_tokens=n_new) for p in prompts]
    fills = [engine.submit(p, max_new_tokens=short_new(config))
             for p in fillers]
    occupancy = []
    while not all(r._done.is_set() for r in reqs + fills):
        engine.step()
        if all(max(n_new - 128, 1) <= len(r.tokens) < n_new for r in reqs):
            occupancy.append(engine.step_records(1)[0]["occupancy"])
    busy = (min(occupancy), sum(occupancy) / len(occupancy)) \
        if occupancy else None
    return ([list(r.tokens) for r in reqs], [list(r.tokens) for r in fills],
            busy)


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--quant", choices=("fp8", "int8"), default="fp8")
    p.add_argument("--sound", action="store_true")
    p.add_argument("--busy", action="store_true")
    p.add_argument("config")
    p.add_argument("seeds", nargs="+", type=int)
    args = p.parse_args(argv)

    import jax

    import serve_check

    config = harness.load_json(args.config)
    extra = os.path.dirname(os.path.dirname(os.path.abspath(args.config)))
    roots = [BENCH] if extra == BENCH else [extra, BENCH]
    kind = harness.load_module(os.path.join(
        BENCH, "traffic_kinds", "open-loop-stratified.py"))
    reference = harness.load_module(harness.find_file(
        roots, "reference", config["reference"], ".py"))
    check = config["correctness"]
    n_new = check["new_tokens"]
    cpu = jax.devices("cpu")[0]
    passed = 0
    for seed in args.seeds:
        requests = kind._check_requests(check, seed, config["vocab_size"])
        prompts = [r["prompt"] for r in requests]
        fillers = []
        if args.busy:
            fillers = [r["prompt"] for r in kind._check_requests(
                {"requests": config["max_slots"] - len(prompts),
                 "prompt_tokens": SHORT_PROMPT, "new_tokens": 0},
                seed + 1, config["vocab_size"])]
        with jax.default_device(cpu):
            params = reference.init_params(config, seed % (2 ** 31 - 1))
            score = serve_check.scorer(reference, config, len(prompts[0]))
            score_short = serve_check.scorer(reference, config, SHORT_PROMPT)
        row = {"seed": seed, "quant": args.quant,
               "limit": check["mean_regret_limit"]}
        for name in ["control"] + (["sound"] if args.sound else []):
            # one copy of the weights on the host and one on the chip at a
            # time: 8.6 GB each beside the scorer's 13 GB of temporaries
            t0 = time.time()
            if name == "control":
                with jax.default_device(cpu):
                    weights = round_arrays(params, args.quant)
            else:
                weights = params
            on_device = jax.device_put(weights, jax.devices()[0])
            jax.block_until_ready(on_device)
            del weights
            tokens, filled, busy = serve_tokens(
                config, on_device, prompts, n_new, fillers)
            del on_device
            gc.collect()
            t1 = time.time()
            served = [{"tokens": t, "max_new_tokens": n_new} for t in tokens]
            with jax.default_device(cpu):
                scored = serve_check.score_requests(
                    score, params, prompts, tokens, n_new)
                if fillers:
                    served += [
                        {"tokens": t, "max_new_tokens": short_new(config)}
                        for t in filled[:SHORT_SCORED]]
                    scored += serve_check.score_requests(
                        score_short, params, fillers[:SHORT_SCORED],
                        filled[:SHORT_SCORED], short_new(config))
                    row[name + "_busy_occupancy"] = busy
            verdict = kind._compare(served, scored, check)
            row[name] = {k: verdict[k] for k in (
                "mean_regret", "largest_regret", "positions_differing",
                "positions_checked")}
            if fillers:     # the two classes apart, beside the mean of all
                for cls, part in (("check", scored[:len(prompts)]),
                                  ("fillers", scored[len(prompts):])):
                    regrets = [r for steps in part for _, _, r in steps]
                    row[name]["mean_regret_" + cls] = (
                        sum(regrets) / len(regrets))
            row[name + "_ok"] = verdict["ok"]
            row[name + "_serve_s"] = round(t1 - t0, 1)
            row[name + "_score_s"] = round(time.time() - t1, 1)
        passed += row["control_ok"]
        print(json.dumps(row), flush=True)
    print(json.dumps({"controls": len(args.seeds),
                      "controls_that_passed": passed}))
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
