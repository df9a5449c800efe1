"""Reference side of the serving check; runs on the CPU, beside the server
while it starts.

    python benchmark/reference/serve_check.py IN.json OUT.json

Makes the weights the server makes — the system's own random init from the
seed, here in one jitted call on the CPU — and continues each seeded prompt
greedily with the plain float32 forward of ``gpt2.py``, recording at every
step the arg-max, the runner-up and the logit margin between them.
"""

from __future__ import annotations

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(src: str, dst: str) -> None:
    import jax
    import numpy as np

    import gpt2
    from distributedtensorflow_tpu import models

    with open(src) as f:
        job = json.load(f)
    config = job["config"]
    cfg = getattr(models, config["system_config"])()
    params = jax.jit(lambda k: models.GPTLM(cfg).init(
        k, np.zeros((1, 1), np.int32), deterministic=True)["params"])(
            jax.random.PRNGKey(job["seed"]))
    m = config
    out = []
    for req in job["requests"]:
        steps = gpt2.greedy(params, req["prompt"], req["max_new_tokens"],
                            m["n_layer"], m["n_head"])
        out.append({"id": req["id"], "steps": steps})
    tmp = dst + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"requests": out}, f)
    os.replace(tmp, dst)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
