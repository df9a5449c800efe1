"""What the bench scripts share: start-up, result files, the timed loop.

Every ``bench*.py`` that measures the device begins with :func:`start`:
it places the compile cache and refuses to go on without a TPU.  There is
no probe process, no retry and no fallback — a chip belongs to one
process at a time, and a run without one exits non-zero and prints no
result.  The ``*_TEST=1`` sizes are a quick wiring check on the chip, not
a CPU mode.
"""

from __future__ import annotations

import json
import os
import sys

RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_RESULTS")


def start(name: str) -> dict:
    """Compile cache + device check for one bench process: exits unless
    the platform is ``tpu``; returns ``runtime.device_summary()``."""
    from distributedtensorflow_tpu import runtime

    runtime.init_compile_cache()
    summary = runtime.require_tpu()
    print(f"{name}: {summary}", file=sys.stderr)
    return summary


def persist_result(prefix: str, result: dict) -> str:
    """Write a benchmark result to BENCH_RESULTS/<prefix>_<ts>.json."""
    import time

    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(
        RESULTS_DIR, f"{prefix}_{time.strftime('%Y%m%d_%H%M%S')}.json"
    )
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"{prefix}: persisted {path}", file=sys.stderr)
    return path


def state_bytes_fields(state) -> dict:
    """Per-device params/optimizer-state bytes for a bench result JSON.

    The worst (max) device's resident bytes — the number cross-replica
    weight-update sharding (``--zero``, parallel/zero.py) divides by the
    ZeRO degree, emitted by every bench row so a sharding win shows up in
    the result stream as a number.
    """
    from distributedtensorflow_tpu.obs import memory

    return memory.state_bytes_record_fields(
        memory.state_bytes_report(state.params, state.opt_state)
    )


def timed_steps(compiled, state, batch, rng, *, n_steps: int, warmup: int):
    """Run warmup + timed steps of a compiled ``(state, batch, rng) ->
    (state, metrics)`` executable; the window ends when the last step's
    loss is ready.  Returns ``(state, dt_seconds)``."""
    import time

    import jax

    for _ in range(warmup):
        state, metrics = compiled(state, batch, rng)
    jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = compiled(state, batch, rng)
    jax.block_until_ready(metrics["loss"])
    return state, time.perf_counter() - t0
