"""Reference side of the serving check; runs on the CPU beside the server.

    python benchmark/reference/serve_check.py IN.json SERVED.json OUT.json

``IN.json`` names the configuration's reference module (``reference_file``,
found by the configuration's ``"reference"`` key), the seed and the check
requests' prompts.  While the server starts, this process makes the weights
the server makes — the system's own random init from the seed, in one
jitted call — and compiles the reference's plain float32 forward for the
check's shape.  Then it waits for ``SERVED.json``, the tokens the server
returned, and scores every one of them in one batched forward: under the
same prefix (the prompt and the served tokens before it), the reference's
arg-max, its top-two margin, and the served token's *regret* — the
arg-max's logit less the served token's, 0 where they agree.  Scoring each
token under the server's own prefix keeps every position comparable after
a disagreement.  This file knows no architecture.
"""

from __future__ import annotations

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def scorer(reference, config: dict, n_prompt: int):
    """Jitted ``(params, ids (B, S)) -> (arg-max, margin, regret)``, each
    ``(B, S - n_prompt)``: one entry per token after the prompt."""
    import jax
    import jax.numpy as jnp

    def score(params, ids):
        logits = reference.logits(params, ids, config)[:, n_prompt - 1:-1]
        top, idx = jax.lax.top_k(logits, 2)
        served = jnp.take_along_axis(
            logits, ids[:, n_prompt:, None], -1)[..., 0]
        return idx[..., 0], top[..., 0] - top[..., 1], top[..., 0] - served
    return jax.jit(score)


def score_requests(score, params, prompts: list[list[int]],
                   tokens: list[list[int]], n_new: int) -> list[list]:
    """Per request, ``[arg-max, margin, regret]`` for each served token.  A
    request that came back short is padded for the forward and scored as
    far as it got."""
    import numpy as np

    ids = np.zeros((len(prompts), len(prompts[0]) + n_new), np.int32)
    for i, (p, t) in enumerate(zip(prompts, tokens)):
        ids[i, :len(p)] = p
        ids[i, len(p):len(p) + len(t)] = t[:n_new]
    top1, margin, regret = (np.asarray(a) for a in score(params, ids))
    return [[[int(top1[i, j]), float(margin[i, j]), float(regret[i, j])]
             for j in range(min(len(t), n_new))]
            for i, t in enumerate(tokens)]


def main(src: str, served_path: str, dst: str) -> None:
    import numpy as np

    import harness

    job = harness.load_json(src)
    config = job["config"]
    reference = harness.load_module(job["reference_file"])
    prompts = [r["prompt"] for r in job["requests"]]
    n_new = job["requests"][0]["max_new_tokens"]
    params = reference.init_params(config, job["seed"])
    # compiled for the check's shape before the served tokens exist; the
    # one forward it then runs is most of what the check adds to set-up
    score = scorer(reference, config, len(prompts[0])).lower(
        params, np.zeros((len(prompts), len(prompts[0]) + n_new),
                         np.int32)).compile()
    deadline = time.monotonic() + job["wait_s"]
    while not os.path.exists(served_path):
        if time.monotonic() > deadline:
            raise SystemExit(f"no {served_path} in {job['wait_s']} s")
        time.sleep(0.05)
    served = harness.load_json(served_path)
    scored = score_requests(score, params, prompts, served["tokens"], n_new)
    tmp = dst + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"reference_file": reference.__file__, "scored": scored}, f)
    os.replace(tmp, dst)


if __name__ == "__main__":
    main(*sys.argv[1:4])
