"""Checks that run in the child, on the device, before the entry point.

``train_loss``: the system's forward and loss (its model and loss function,
built through ``get_workload`` exactly as ``train.py`` builds them, Pallas
kernels and all) on a seeded batch of token sequences, against the plain
float32 reference of ``reference/gpt2.py`` on the same weights and batch,
both on this process's device.  The weights are the system's own random
init from the seed, made in one jitted call.
"""

from __future__ import annotations

import time


def train_loss(spec: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu.workloads import get_workload
    from reference import gpt2

    t0 = time.time()
    wl = get_workload(spec["workload"], seq_len=spec["seq_len"],
                      test_size=spec.get("test_size", False))
    rng = np.random.default_rng(spec["seed"])
    ids = jnp.asarray(rng.integers(0, spec["vocab_size"],
                                   (spec["sequences"], spec["seq_len"])),
                      jnp.int32)
    params = jax.jit(lambda k: wl.init_fn(k)["params"])(
        jax.random.PRNGKey(spec["seed"]))
    system = jax.jit(
        lambda p, x: wl.loss_fn(p, {}, {"input_ids": x},
                                jax.random.PRNGKey(0))[0])
    reference = jax.jit(gpt2.loss, static_argnums=(2, 3))
    got = float(system(params, ids))
    want = float(reference(params, ids, spec["n_layer"], spec["n_head"]))
    del params
    return {"check": "train_loss", "system_loss": got,
            "reference_loss": want, "abs_diff": abs(got - want),
            "tolerance": spec["tolerance"],
            "ok": abs(got - want) <= spec["tolerance"],
            "seconds": time.time() - t0}


def run(spec: dict) -> dict:
    checks = {"train_loss": train_loss}
    return checks[spec["check"]](spec)
