#!/usr/bin/env python3
"""A serving configuration's check with EVERY slot live, sound and fp8
control, where ``benchmark/tools/control_served.py --busy --sound`` does not
fit the host: at 11 GB of weights that tool's one process holds the weights,
their rounded copy and two scorers' temporaries and passes the one-chip
machine's 40 GiB; so did one process that scored both classes (PERF.md
section 2, PR 32).

    chiprun -- python tools/busy_served.py CONFIG.json SEED [--quant fp8]

The same check, a process a phase, this one starting them and touching no
backend.  The first serves: the weights are made on the chip (the family's
``init_params``, as ``serve.py`` makes them), the check's requests and a
short seeded request in every other slot go through the configuration's own
engine (``control_served.serve_tokens``, imported, with its fillers), first
on the sound weights, then on the same weights rounded to fp8 a leaf at a
time on the host (``control_served.round_arrays``).  Two more on the CPU
score the check's requests and the fillers as every benchmark run's are
scored (``reference/serve_check.py``'s scorer, the plain float32 reference on
the unrounded weights).  Prints one JSON row: ``sound``
and ``control`` with ``mean_regret`` (all, check, fillers) and the slots that
decoded together.  Exit 1 if the control passed the limit or the sound run
did not.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (ROOT, BENCH, os.path.join(BENCH, "reference")):
    sys.path.insert(0, path)

import harness  # noqa: E402


def _tool():
    return harness.load_module(os.path.join(BENCH, "tools",
                                            "control_served.py"))


def _requests(config: dict, seed: int):
    kind = harness.load_module(os.path.join(
        BENCH, "traffic_kinds", "open-loop-stratified.py"))
    tool = _tool()
    check = config["correctness"]
    prompts = [r["prompt"] for r in kind._check_requests(
        check, seed, config["vocab_size"])]
    fillers = [r["prompt"] for r in kind._check_requests(
        {"requests": config["max_slots"] - len(prompts),
         "prompt_tokens": tool.SHORT_PROMPT, "new_tokens": 0},
        seed + 1, config["vocab_size"])]
    return kind, tool, prompts, fillers


def serve(config: dict, seed: int, quant: str) -> dict:
    """Both token sets, from weights that never leave the chip."""
    import functools

    import jax

    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.serve import engine
    from distributedtensorflow_tpu.serve.model import family_of

    _, tool, prompts, fillers = _requests(config, seed)
    # every request is submitted before the first step: the engine's queue
    # (64 unless told) must hold a configuration of more slots than that
    engine.Engine = functools.partial(
        engine.Engine, max_queue=max(64, config["max_slots"]))
    cfg = getattr(models, config["system_config"])()
    params = family_of(cfg).init_params(
        cfg, jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    n_new = config["correctness"]["new_tokens"]
    out = {}
    for name in ("sound", "control"):
        if name == "control":
            # a leaf at a time through the host, the unrounded one let go as
            # it is replaced: two copies of the weights fit neither the chip
            # nor, beside a scorer, the host.  Rounded on the CPU as the
            # tool rounds: the chip's compiler takes a convert to fp8 and
            # back for excess precision and drops it (a control rounded on
            # the chip read 0.0154 against the sound 0.0143: my chip run,
            # PR 32)
            cpu, chip = jax.devices("cpu")[0], jax.devices()[0]
            leaves, tree = jax.tree.flatten(params)
            del params
            for i in range(len(leaves)):
                with jax.default_device(cpu):
                    rounded = tool.round_arrays(
                        jax.device_put(leaves[i], cpu), quant)
                leaves[i] = jax.device_put(rounded, chip)
            params = jax.tree.unflatten(tree, leaves)
            del leaves, rounded
        jax.block_until_ready(params)
        tokens, filled, busy = tool.serve_tokens(
            config, params, prompts, n_new, fillers)
        out[name] = {"tokens": tokens, "filled": filled, "busy": busy}
    return out


def score(config: dict, seed: int, served: dict, part: str) -> dict:
    """On the CPU: ``[arg-max, margin, regret]`` of every served token of one
    class of request (``"check"`` or ``"fillers"``) under the reference, for
    the sound and the control tokens."""
    import serve_check

    _, tool, prompts, fillers = _requests(config, seed)
    reference = harness.load_module(harness.find_file(
        [BENCH], "reference", config["reference"], ".py"))
    asked, key, n = {
        "check": (prompts, "tokens", config["correctness"]["new_tokens"]),
        "fillers": (fillers[:tool.SHORT_SCORED], "filled",
                    tool.short_new(config))}[part]
    params = reference.init_params(config, seed % (2 ** 31 - 1))
    scorer = serve_check.scorer(reference, config, len(asked[0]))
    return {name: serve_check.score_requests(
        scorer, params, asked, got[key][:len(asked)], n)
        for name, got in served.items()}


def verdicts(config: dict, seed: int, served: dict, scored: dict) -> dict:
    """The row: both classes compared as a run's check is."""
    kind, tool, prompts, fillers = _requests(config, seed)
    check = config["correctness"]
    sizes = {"check": ("tokens", len(prompts), check["new_tokens"]),
             "fillers": ("filled", tool.SHORT_SCORED,
                         tool.short_new(config))}
    row = {"seed": seed, "limit": check["mean_regret_limit"]}
    for name, got in served.items():
        rows = [{"tokens": t, "max_new_tokens": n}
                for key, count, n in sizes.values()
                for t in got[key][:count]]
        parts = [scored[part][name] for part in sizes]
        verdict = kind._compare(rows, parts[0] + parts[1], check)
        row[name] = {k: verdict[k] for k in (
            "mean_regret", "largest_regret", "positions_differing",
            "positions_checked")}
        for part, steps in zip(sizes, parts):
            regrets = [r for request in steps for _, _, r in request]
            row[name]["mean_regret_" + part] = sum(regrets) / len(regrets)
        row[name + "_ok"] = verdict["ok"]
        row[name + "_busy_occupancy"] = got["busy"]
    return row


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("config")
    p.add_argument("seed", type=int)
    p.add_argument("--quant", choices=("fp8", "int8"), default="fp8")
    p.add_argument("--phase", choices=("serve", "check", "fillers"),
                   default=None, help="(a child) one phase, into --dir")
    p.add_argument("--dir", default=None)
    args = p.parse_args(argv)
    config = harness.load_json(args.config)
    if args.phase:
        served = os.path.join(args.dir, "served.json")
        if args.phase == "serve":
            result, dst = serve(config, args.seed, args.quant), served
        else:
            result = score(config, args.seed, harness.load_json(served),
                           args.phase)
            dst = os.path.join(args.dir, args.phase + ".json")
        with open(dst, "w") as f:
            json.dump(result, f)
        return 0
    # this process touches no backend: a child holds the chip, then two on
    # the CPU score a class of request each (one scorer's temporaries beside
    # 11 GB of weights at a time)
    out = harness.fresh_dir(os.path.join(harness.OUT, "busy_served"))
    for phase in ("serve", "check", "fillers"):
        env = dict(os.environ)
        if phase != "serve":
            env["JAX_PLATFORMS"] = "cpu"
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), args.config,
             str(args.seed), "--quant", args.quant, "--phase", phase,
             "--dir", out], env=env)
        if done.returncode:
            return done.returncode
    row = verdicts(config, args.seed,
                   harness.load_json(os.path.join(out, "served.json")),
                   {part: harness.load_json(os.path.join(out, part + ".json"))
                    for part in ("check", "fillers")})
    print(json.dumps({**row, "quant": args.quant}), flush=True)
    return 0 if row["sound_ok"] and not row["control_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
