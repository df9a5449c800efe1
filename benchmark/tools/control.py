#!/usr/bin/env python3
"""The controls of the two correctness comparisons: each must come out as
*not correct*, or the comparison proves nothing (PERF.md section 2).

    python benchmark/tools/control.py train|serve [--quant fp8|int8] \\
        CONFIG.json SEED [SEED ...]

One process, which holds the chip; no timed window.  Prints a JSON row per
seed with the number each comparison compares, for the sound side where
this process can make it, and for the control.

The configurations state bf16 compute; the nearest precision below it is
fp8 or int8.  fp8 is the control: both comparisons fail it on every seed,
with room (PERF.md section 2).  ``--quant int8`` is kept to show what they
cannot see: int8 reads two to three times what bf16 reads, too close to
set a limit between.

- ``train``: the configuration's on-chip check (``checks/<name>.py``) as a
  run makes it, and again with the system's own quantised matmul path
  switched on (``"quant"`` in the spec).  Compared: ``abs_diff`` of the mean
  loss against the float32 reference, limit ``tolerance``, and
  ``token_rms_diff`` of single positions, limit ``token_tolerance``.
- ``serve``: ``serve.py`` has no quantised path, so the control is the
  reference in the program's place with its matrices rounded to fp8 (or
  int8) per output channel: the tokens it generates greedily are scored
  by the float32 reference exactly as a server's are.  Compared:
  ``mean_regret``; limit ``mean_regret_limit``.  The sound side of this
  one is every serving run's ``detail``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "reference"))
sys.path.insert(0, os.path.dirname(BENCH))

import harness  # noqa: E402
import preflight  # noqa: E402


def _roots(config_path: str) -> list[str]:
    extra = os.path.dirname(os.path.dirname(os.path.abspath(config_path)))
    return [BENCH] if extra == BENCH else [extra, BENCH]


def train(config: dict, roots: list[str], seed: int,
          quant: str = "fp8") -> dict:
    check = config["correctness"]["preflight"]
    spec = preflight.spec_for(config, roots, seed % (2 ** 31 - 1))
    sound = preflight.run(spec)
    control = preflight.run({**spec, "quant": quant})
    keys = ("abs_diff", "token_rms_diff", "token_max_diff")
    return {"seed": seed, "quant": quant, "limits": {
        "abs_diff": check["tolerance"],
        "token_rms_diff": check["token_tolerance"]},
        "sound": {k: sound[k] for k in keys}, "sound_ok": sound["ok"],
        "control": {k: control[k] for k in keys},
        "control_ok": control["ok"]}


def round_matrices(params, quant: str):
    """Every matrix rounded per output channel (symmetric, absmax) to what
    an fp8 (e4m3) or int8 server would hold, kept in float32."""
    import jax
    import jax.numpy as jnp

    def one(a):
        if a.ndim != 2:
            return a
        top = jnp.max(jnp.abs(a), axis=0, keepdims=True)
        if quant == "int8":
            return jnp.round(a / (top / 127.0)) * (top / 127.0)
        scaled = (a / (top / 448.0)).astype(jnp.float8_e4m3fn)
        return scaled.astype(jnp.float32) * (top / 448.0)
    if quant not in ("fp8", "int8"):
        raise ValueError(f"unknown control precision {quant!r}")
    return jax.tree.map(one, params)


def greedy_tokens(reference, params, config: dict,
                  prompts: list[list[int]], n_new: int) -> list[list[int]]:
    """Greedy continuations by ``reference.logits`` on ``params``, the whole
    forward recomputed per token (no cache): the reference as a server."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_prompt = len(prompts[0])
    ids = np.zeros((len(prompts), n_prompt + n_new), np.int32)
    ids[:, :n_prompt] = prompts
    step = jax.jit(lambda p, x, t: jnp.argmax(
        reference.logits(p, x, config)[:, t - 1], -1))
    for t in range(n_prompt, n_prompt + n_new):
        # positions >= t hold zeros, which a causal model cannot see
        ids[:, t] = np.asarray(step(params, ids, t))
    return ids[:, n_prompt:].tolist()


def serve(config: dict, roots: list[str], seed: int,
          quant: str = "fp8") -> dict:
    import serve_check

    kind = harness.load_module(os.path.join(
        BENCH, "traffic_kinds", "open-loop-stratified.py"))
    reference = harness.load_module(harness.find_file(
        roots, "reference", config["reference"], ".py"))
    check = config["correctness"]
    requests = kind._check_requests(check, seed, config["vocab_size"])
    prompts = [r["prompt"] for r in requests]
    n_new = check["new_tokens"]
    params = reference.init_params(config, seed % (2 ** 31 - 1))
    score = serve_check.scorer(reference, config, len(prompts[0]))
    row = {"seed": seed, "quant": quant,
           "limit": check["mean_regret_limit"]}
    for name, weights in (("control", round_matrices(params, quant)),
                          ("float32", params)):
        tokens = greedy_tokens(reference, weights, config, prompts, n_new)
        served = [{"tokens": t, "max_new_tokens": n_new} for t in tokens]
        verdict = kind._compare(served, serve_check.score_requests(
            score, params, prompts, tokens, n_new), check)
        row[name] = {k: verdict[k] for k in (
            "mean_regret", "largest_regret", "positions_differing",
            "positions_checked")}
        row[name + "_ok"] = verdict["ok"]
    return row


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("mode", choices=("train", "serve"))
    p.add_argument("--quant", choices=("fp8", "int8"), default="fp8")
    p.add_argument("config")
    p.add_argument("seeds", nargs="+", type=int)
    args = p.parse_args(argv)
    config = harness.load_json(args.config)
    roots = _roots(args.config)
    passed = 0
    for seed in args.seeds:
        row = {"train": train, "serve": serve}[args.mode](
            config, roots, seed, args.quant)
        passed += row["control_ok"]
        print(json.dumps(row), flush=True)
    print(json.dumps({"controls": len(args.seeds),
                      "controls_that_passed": passed}))
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
