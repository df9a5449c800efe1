#!/usr/bin/env python3
"""Does a qwen3_next configuration's check see the matrix state's precision,
the decay, the routed experts, and a chunk body that is unsound for an
unbounded gate?

    chiprun -- python tools/gdn_controls.py \\
        --control bf16_state|dropped_decay|dropped_experts|channel_body|sound \\
        [--check tokens|state] CONFIG.json SEED [...]

Four controls of the family's check that the fp8 control cannot stand in for,
all with the configuration's own programs otherwise and *sound* weights:

- ``bf16_state``: the matrix state a head is rounded to bfloat16 wherever a
  program hands it on (after every prefill chunk's scan and every decode
  step), as a state *stored* in bfloat16 would be;
- ``dropped_decay``: the rule without its decay, ``g = 0`` (the state never
  forgets), in both programs;
- ``dropped_experts``: the routed experts' term left out of every layer (the
  gated shared expert alone);
- ``channel_body``: every gate drawn down to -60 a token by the weights
  (``A_log = log 3``, ``dt_bias = 20``: what nothing in the published
  equations forbids; the reference draws the same) and the prefill chunk's
  scan sent through the per-channel chunk body (``ops.kda._chunk``, the gate
  broadcast along the key's channels), whose precondition is ``g >= -5``: the
  reason the scalar body exists.  Under ``--control strong_gates`` the same
  gates go through the scalar body: that reading must be sound.

``sound`` patches nothing and reads the same check through the same tool.
``--check tokens`` (the default): the served check's requests go through the
configuration's engine and are scored as every benchmark run's are
(``tools/state_dropped_control.run_control``).  ``--check state``: the
configuration's on-device check (``correctness.preflight``:
``benchmark/checks/gdn_state.py`` through ``benchmark/preflight.py``) with
the control patched in under its probe; ``ok`` is what the cell's ``correct``
takes (``tools/kda_controls.run_state_check`` runs it).  Prints a JSON row a
seed; exit 1 if a control passed (or ``sound`` / ``strong_gates`` failed).
"""

from __future__ import annotations

import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import kda_controls  # noqa: E402
import state_dropped_control as base  # noqa: E402

#: the controls that must read sound
SOUND = ("sound", "strong_gates")


def patch(control: str) -> str:
    """Take ``control`` away from the programs (``serve.model``'s names for
    ``ops.kda``, ``models.qwen3_next``'s for the expert layer); returns what
    was taken, in words."""
    import jax.numpy as jnp
    from jax import lax

    from distributedtensorflow_tpu.models import qwen3_next
    from distributedtensorflow_tpu.ops import kda
    from distributedtensorflow_tpu.serve import model

    chunk_scan, step = model.kda_chunk_scan, model.kda_step
    if control == "bf16_state":
        def rounded(x):
            # the rounding as an operation of its own: a convert to bfloat16
            # and back is excess precision to the TPU compiler, which drops
            # the pair (PR 52)
            return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

        def chunk_rounded(*a, **kw):
            o, state = chunk_scan(*a, **kw)
            return o, rounded(state)

        def step_rounded(q, k, v, g, beta, pool, layer, **kw):
            o, pool = step(q, k, v, g, beta, pool, layer, **kw)
            return o, pool.at[layer].set(rounded(pool[layer]))

        model.kda_chunk_scan, model.kda_step = chunk_rounded, step_rounded
        return "the matrix state rounded to bfloat16 at every program's end"
    if control == "dropped_decay":
        model.kda_chunk_scan = lambda q, k, v, g, *rest, **kw: chunk_scan(
            q, k, v, 0.0 * g, *rest, **kw)
        model.kda_step = lambda q, k, v, g, *rest, **kw: step(
            q, k, v, 0.0 * g, *rest, **kw)
        return "the rule without its decay (g = 0)"
    if control == "dropped_experts":
        routed = qwen3_next.dropless_moe

        def none_routed(*a, **kw):
            out, counters = routed(*a, **kw)
            return jnp.zeros_like(out), counters

        qwen3_next.dropless_moe = none_routed
        return "the routed experts' term dropped (the gated shared expert " \
               "alone)"
    if control in ("channel_body", "strong_gates"):
        # the gates are the weights': A = 3 and softplus(a + 20) ~ 20, so the
        # system and the reference both draw them, token after token
        init = qwen3_next.init_params

        def strong_init(cfg, key, *a, **kw):
            params = init(cfg, key, *a, **kw)
            for layer in params.values():
                if isinstance(layer, dict) and "gdn" in layer:
                    gdn = layer["gdn"]
                    gdn["a_log"] = jnp.full_like(gdn["a_log"], math.log(3.0))
                    gdn["dt_bias"] = jnp.full_like(gdn["dt_bias"], 20.0)
            return params

        qwen3_next.init_params = strong_init
        if control == "strong_gates":
            return "every gate drawn to -60 a token, through the scalar body"

        def chunk_channel(q, k, v, g, beta, state, valid):
            q, k = kda._share_heads(q, k, v.shape[1])
            return chunk_scan(q, k, v, jnp.broadcast_to(g, q.shape), beta,
                              state, valid)

        model.kda_chunk_scan = chunk_channel
        return "every gate drawn to -60 a token, the chunk's scan through " \
               "the per-channel body (precondition g >= -5)"
    if control == "sound":
        return "sound: nothing taken away"
    raise SystemExit(f"unknown control {control!r}")


def main(argv: list[str]) -> int:
    control = argv[argv.index("--control") + 1]
    check = argv[argv.index("--check") + 1] if "--check" in argv else "tokens"
    rest = [a for a in argv if a not in ("--control", control, "--check",
                                         check)]
    run = {"tokens": base.run_control,
           "state": kda_controls.run_state_check}[check]
    passed = run(rest, patch(control))
    return passed if control not in SOUND else 1 - passed


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
