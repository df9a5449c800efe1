#!/usr/bin/env python3
"""Does a ling configuration's check see the matrix state's precision and the
delta rule's correction?

    chiprun -- python tools/kda_controls.py --control bf16_state|dropped_delta|sound \\
        [--check tokens|state] CONFIG.json SEED [...]

Two controls of the family's check that the fp8 control cannot stand in for,
both with the configuration's own programs otherwise and *sound* weights:

- ``bf16_state``: the matrix state a head is rounded to bfloat16 wherever a
  program hands it on (after every prefill chunk's scan and every decode
  step), as a state *stored* in bfloat16 would be;
- ``dropped_delta``: the rule without its correction, ``S = S' + beta k
  v^T`` (the prediction ``S'^T k`` is not subtracted: plain gated linear
  attention), token by token in both programs.

``sound`` patches nothing and reads the same check through the same tool.
``--check tokens`` (the default): the served check's requests go through the
configuration's engine and are scored as every benchmark run's are
(``tools/state_dropped_control.run_control``); a control whose mean regret
stays under the configuration's ``mean_regret_limit`` is not seen by it — the
bfloat16 state is not (PERF.md section 2).  ``--check state``: the
configuration's on-device check (``correctness.preflight``:
``benchmark/checks/kda_state.py`` through ``benchmark/preflight.py``, as
traffic kind ``open-loop-stratified-preflight`` runs it before the server
starts) with the control patched in under its probe; ``ok`` is what the cell's
``correct`` takes.  Prints a JSON row a seed; exit 1 if a control passed (or
``sound`` failed).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import state_dropped_control as base  # noqa: E402


def dropped_delta():
    """``(chunk_scan, step)`` of the rule without its prediction term, by the
    signatures of ``ops.kda.kda_chunk_scan`` and ``kda_step``."""
    import jax.numpy as jnp
    from jax import lax

    from distributedtensorflow_tpu.ops import kda

    f32 = jnp.float32

    def token(st, q, k, v, g, beta):
        st = st * jnp.exp(g)[..., None, :]
        st = st + (beta[..., None] * v)[..., :, None] * k[..., None, :]
        return st, (st * q[..., None, :]).sum(-1)

    def chunk_scan(q, k, v, g, beta, state, valid):
        g, beta = kda._pad_identity(g.astype(f32), beta.astype(f32), valid)
        state, o = lax.scan(lambda st, xs: token(st, *xs), state.astype(f32),
                            (q.astype(f32), k.astype(f32), v.astype(f32), g,
                             beta))
        return o, state

    def step(q, k, v, g, beta, pool, layer, *, impl="auto"):
        st, o = token(pool[layer], *(x.astype(f32) for x in (q, k, v, g,
                                                              beta)))
        return o, pool.at[layer].set(st)

    return chunk_scan, step


def patch(control: str) -> str:
    """Take ``control`` away from the programs of ``serve.model`` (the names
    it calls ``ops.kda`` through); returns what was taken, in words."""
    from jax import lax

    from distributedtensorflow_tpu.serve import model

    if control == "bf16_state":
        chunk_scan, step = model.kda_chunk_scan, model.kda_step

        def rounded(x):
            # the rounding as an operation of its own: a convert to bfloat16
            # and back is excess precision to the TPU compiler, which drops
            # the pair (the first reading of this control was the sound one)
            return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

        def chunk_rounded(*a, **kw):
            o, state = chunk_scan(*a, **kw)
            return o, rounded(state)

        def step_rounded(q, k, v, g, beta, pool, layer, **kw):
            o, pool = step(q, k, v, g, beta, pool, layer, **kw)
            return o, pool.at[layer].set(rounded(pool[layer]))

        model.kda_chunk_scan, model.kda_step = chunk_rounded, step_rounded
        return "the matrix state rounded to bfloat16 at every program's end"
    if control == "dropped_delta":
        model.kda_chunk_scan, model.kda_step = dropped_delta()
        return "the delta rule without its correction (S' + beta k v^T)"
    if control == "sound":
        return "sound: nothing taken away"
    raise SystemExit(f"unknown control {control!r}")


def run_state_check(argv: list[str], control: str) -> int:
    """The configuration's preflight check (``argv[0]``) as the process now
    stands, a JSON row a seed (``argv[1:]``); 1 if a control passed."""
    import json

    import preflight

    config = base.harness.load_json(argv[0])
    extra = os.path.dirname(os.path.dirname(os.path.abspath(argv[0])))
    roots = [base.BENCH] if extra == base.BENCH else [extra, base.BENCH]
    passed = 0
    for seed in (int(s) for s in argv[1:]):
        row = preflight.run(preflight.spec_for(config, roots,
                                               seed % (2 ** 31 - 1)))
        print(json.dumps({"seed": seed, "control": control,
                          "control_ok": row["ok"], **row}), flush=True)
        passed += row["ok"]
    return 1 if passed else 0


def main(argv: list[str]) -> int:
    control = argv[argv.index("--control") + 1]
    check = argv[argv.index("--check") + 1] if "--check" in argv else "tokens"
    rest = [a for a in argv if a not in ("--control", control, "--check",
                                         check)]
    run = {"tokens": base.run_control, "state": run_state_check}[check]
    passed = run(rest, patch(control))
    return passed if control != "sound" else 1 - passed


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
