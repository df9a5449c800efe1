#!/usr/bin/env python3
"""Does a serving configuration's check see the state group's state?

    chiprun -- python tools/state_dropped_control.py [--decode-too] \\
        CONFIG.json SEED [...]

The control of a family with a state group (jamba, lfm2) that the fp8 control
cannot stand in for: the configuration's own programs and *sound* weights,
but every prefill chunk starts from a zero state — the state a slot's last
chunk left is not carried over the chunk boundary
(``serve.model._ChunkState._get`` answers zeros whatever ``start`` is; decode
steps carry the state as they do).  The check's requests go through the
configuration's engine (``benchmark/tools/control_served.serve_tokens``) and
are scored as every benchmark run's are (``reference/serve_check.py``'s
scorer, the plain float32 reference on this machine's CPU).  If the mean
regret stays under the configuration's ``mean_regret_limit`` the check does
not see the state, and its prompts are wrong: they must end so that the
served tokens depend on what crossed a chunk boundary.  ``--decode-too``
drops the state at every program boundary instead (a decode step starts from
zeros as well: nothing is carried at all), which every served token after the
first sees.  Prints a JSON row a seed; exit 1 if a control passed.
"""

from __future__ import annotations

import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for path in (ROOT, BENCH, os.path.join(BENCH, "reference")):
    sys.path.insert(0, path)

import harness  # noqa: E402


def run_control(argv: list[str], control: str) -> int:
    """The configuration's check (``argv[0]``) served by its engine as the
    process now stands — the caller has already taken ``control`` away from
    the programs — and scored as a benchmark run's is, a JSON row a seed
    (``argv[1:]``); 1 if a control passed."""
    import jax

    import serve_check
    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.serve.model import family_of

    config = harness.load_json(argv[0])
    tool = harness.load_module(os.path.join(BENCH, "tools",
                                            "control_served.py"))
    kind = harness.load_module(os.path.join(
        BENCH, "traffic_kinds", "open-loop-stratified.py"))
    reference = harness.load_module(harness.find_file(
        [BENCH], "reference", config["reference"], ".py"))
    cfg = getattr(models, config["system_config"])()
    check = config["correctness"]
    n_new = check["new_tokens"]
    passed = 0
    for seed in (int(s) for s in argv[1:]):
        prompts = [r["prompt"] for r in kind._check_requests(
            check, seed, config["vocab_size"])]
        key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
        params = family_of(cfg).init_params(cfg, key)   # on the chip
        tokens, _, _ = tool.serve_tokens(config, params, prompts, n_new)
        del params
        gc.collect()
        with jax.default_device(jax.devices("cpu")[0]):
            scored = serve_check.score_requests(
                serve_check.scorer(reference, config, len(prompts[0])),
                reference.init_params(config, seed % (2 ** 31 - 1)),
                prompts, tokens, n_new)
        served = [{"tokens": t, "max_new_tokens": n_new} for t in tokens]
        verdict = kind._compare(served, scored, check)
        first = [[round(r, 3) for _, _, r in steps[:16]] for steps in scored]
        print(json.dumps({
            "seed": seed, "control": control,
            "limit": check["mean_regret_limit"], "control_ok": verdict["ok"],
            **{k: verdict[k] for k in ("mean_regret", "largest_regret",
                                       "positions_differing",
                                       "positions_checked")},
            "regret_first_16": first}), flush=True)
        passed += verdict["ok"]
    return 1 if passed else 0


def main(argv: list[str]) -> int:
    import jax.numpy as jnp

    from distributedtensorflow_tpu.serve import model

    decode_too = "--decode-too" in argv

    def dropped(self, array):       # the state is not carried
        return jnp.zeros_like(array[self.li, 0])

    model._ChunkState._get = dropped
    if decode_too:
        model._StepState._get = lambda self, array: jnp.zeros_like(
            array[self.li])
    return run_control(
        [a for a in argv if a != "--decode-too"], "state dropped at " + (
            "every program boundary" if decode_too else "chunk boundaries"))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
