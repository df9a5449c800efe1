"""Check ``train_loss``: the system's forward and loss (its model and loss
function, built through ``get_workload`` exactly as ``train.py`` builds
them, Pallas kernels and all) on a seeded batch of token sequences, against
the configuration's plain float32 reference on the same weights and batch,
both on this process's device.  The weights are the system's own random
init from the seed, made in one jitted call.  A model that takes something
other than ``input_ids`` brings a check file of its own.

Two numbers are compared.  ``abs_diff``: the mean loss of the batch, limit
``tolerance`` — a mean over two thousand positions, in which rounding
averages out, so it catches a wrong formula and not a lost bit.
``token_rms_diff``: the system's loss at ``token_positions`` single
positions (its own ``mask`` picks one at a time), each against the
reference's negative log-likelihood there, root-mean-square; limit
``token_tolerance``.  That one separates bf16 compute from the int8 control
(``tools/control.py``; PERF.md section 2 has both readings).

``spec["quant"]`` (never set by a benchmark run) switches the system's own
quantised matmul path on: the control.
"""

from __future__ import annotations


def run(spec: dict, reference) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu.workloads import get_workload

    config = spec["config"]
    seq, n_seq = config["seq_len"], spec["sequences"]
    wl = get_workload(spec["workload"], seq_len=seq,
                      test_size=spec.get("test_size", False),
                      quant=spec.get("quant"))
    rng = np.random.default_rng(spec["seed"])
    ids = jnp.asarray(rng.integers(0, config["vocab_size"], (n_seq, seq)),
                      jnp.int32)
    params = jax.jit(lambda k: wl.init_fn(k)["params"])(
        jax.random.PRNGKey(spec["seed"]))
    system = jax.jit(
        lambda p, x: wl.loss_fn(p, {}, {"input_ids": x},
                                jax.random.PRNGKey(0))[0])
    masked = jax.jit(
        lambda p, x, m: wl.loss_fn(p, {}, {"input_ids": x, "mask": m},
                                   jax.random.PRNGKey(0))[0])
    plain = jax.jit(
        lambda p, x: reference.token_nll(p, {"input_ids": x}, config))
    got = float(system(params, ids))
    want_tokens = np.asarray(plain(params, ids))
    want = float(want_tokens.mean())
    # the position whose next token is predicted: row, column 0..S-2
    rows = rng.integers(0, n_seq, spec["token_positions"])
    cols = rng.integers(0, seq - 1, spec["token_positions"])
    diffs = []
    for r, c in zip(rows, cols):
        mask = np.zeros((n_seq, seq), np.float32)
        mask[r, c + 1] = 1.0
        diffs.append(float(masked(params, ids, mask)) - want_tokens[r, c])
    del params
    rms = float(np.sqrt(np.mean(np.square(diffs))))
    return {"system_loss": got, "reference_loss": want,
            "abs_diff": abs(got - want), "tolerance": spec["tolerance"],
            "token_rms_diff": rms, "token_max_diff": float(np.max(np.abs(
                diffs))), "token_tolerance": spec["token_tolerance"],
            "ok": bool(abs(got - want) <= spec["tolerance"]
                       and rms <= spec["token_tolerance"])}
