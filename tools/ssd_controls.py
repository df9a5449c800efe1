#!/usr/bin/env python3
"""Does a nemotron_h configuration's check see the matrix state's precision,
the decay and the routed experts?

    chiprun -- python tools/ssd_controls.py \\
        --control bf16_state|dropped_decay|dropped_experts|sound \\
        [--check tokens|state] CONFIG.json SEED [...]

Three controls of the family's check that the fp8 control cannot stand in
for, all with the configuration's own programs otherwise and *sound* weights:

- ``bf16_state``: the matrix state a head is rounded to bfloat16 wherever a
  program hands it on (after every prefill chunk's scan and every decode
  step), as a state *stored* in bfloat16 would be;
- ``dropped_decay``: the recurrence without its decay, ``a = 1`` (``S = S +
  dt x (outer) B``: the state never forgets), in both programs;
- ``dropped_experts``: the routed experts' term left out of every expert
  layer (the shared expert alone).

``sound`` patches nothing and reads the same check through the same tool.
``--check tokens`` (the default): the served check's requests go through the
configuration's engine and are scored as every benchmark run's are
(``tools/state_dropped_control.run_control``).  ``--check state``: the
configuration's on-device check (``correctness.preflight``:
``benchmark/checks/ssd_state.py`` through ``benchmark/preflight.py``) with
the control patched in under its probe; ``ok`` is what the cell's ``correct``
takes (``tools/kda_controls.run_state_check`` runs it).  Prints a JSON row a
seed; exit 1 if a control passed (or ``sound`` failed).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import kda_controls  # noqa: E402
import state_dropped_control as base  # noqa: E402


def patch(control: str) -> str:
    """Take ``control`` away from the programs (``serve.model``'s names for
    ``ops.ssd``, ``models.nemotron_h``'s for the expert layer); returns what
    was taken, in words."""
    import jax.numpy as jnp
    from jax import lax

    from distributedtensorflow_tpu.models import nemotron_h
    from distributedtensorflow_tpu.serve import model

    chunk_scan, step = model.ssd_chunk_scan, model.ssd_step
    if control == "bf16_state":
        def rounded(x):
            # the rounding as an operation of its own: a convert to bfloat16
            # and back is excess precision to the TPU compiler, which drops
            # the pair (PR 52)
            return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

        def chunk_rounded(*a, **kw):
            y, state = chunk_scan(*a, **kw)
            return y, rounded(state)

        def step_rounded(x, dt, a, b, c, d, pool, layer, **kw):
            y, pool = step(x, dt, a, b, c, d, pool, layer, **kw)
            return y, pool.at[layer].set(rounded(pool[layer]))

        model.ssd_chunk_scan, model.ssd_step = chunk_rounded, step_rounded
        return "the matrix state rounded to bfloat16 at every program's end"
    if control == "dropped_decay":
        model.ssd_chunk_scan = lambda x, dt, a, *rest, **kw: chunk_scan(
            x, dt, 0.0 * a, *rest, **kw)
        model.ssd_step = lambda x, dt, a, *rest, **kw: step(
            x, dt, 0.0 * a, *rest, **kw)
        return "the recurrence without its decay (a = 1)"
    if control == "dropped_experts":
        routed = nemotron_h.dropless_moe

        def none_routed(*a, **kw):
            out, counters = routed(*a, **kw)
            return jnp.zeros_like(out), counters

        nemotron_h.dropless_moe = none_routed
        return "the routed experts' term dropped (the shared expert alone)"
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
    return passed if control != "sound" else 1 - passed


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
