"""Check ``gqa_loss``: as ``benchmark/checks/train_loss.py``, for a
workload built with the configuration's ``n_kv_head`` K/V heads; found only
under this rehearsal root."""

from __future__ import annotations


def run(spec: dict, reference) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu.workloads import get_workload

    config = spec["config"]
    wl = get_workload(spec["workload"], seq_len=config["seq_len"],
                      test_size=True, kv_heads=config["n_kv_head"])
    ids = jnp.asarray(np.random.default_rng(spec["seed"]).integers(
        0, config["vocab_size"], (spec["sequences"], config["seq_len"])),
        jnp.int32)
    params = jax.jit(lambda k: wl.init_fn(k)["params"])(
        jax.random.PRNGKey(spec["seed"]))
    got = float(jax.jit(lambda p, x: wl.loss_fn(
        p, {}, {"input_ids": x}, jax.random.PRNGKey(0))[0])(params, ids))
    want = float(jax.jit(lambda p, x: reference.loss(
        p, {"input_ids": x}, config))(params, ids))
    return {"system_loss": got, "reference_loss": want,
            "abs_diff": abs(got - want), "tolerance": spec["tolerance"],
            "ok": abs(got - want) <= spec["tolerance"],
            "kv_heads": config["n_kv_head"]}
