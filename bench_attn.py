#!/usr/bin/env python
"""Attention kernel benchmark: Pallas flash vs XLA dense, fwd and fwd+bwd.

Produces the evidence behind ``ops/flash_attention.py``'s
``MIN_SEQ_FOR_PALLAS`` dispatch threshold (round-1 verdict: the threshold
was load-bearing but unevidenced).  Runs both implementations at a range of
sequence lengths on the TPU (it exits non-zero without one), persists
per-run JSON to ``BENCH_RESULTS/attn_<ts>.json``, and prints one JSON line
with the crossover summary.  The one failure it records instead of raising
is the dense XLA path running out of memory at long sequences — that is
the finding; a kernel that fails to compile ends the run non-zero.

Knobs: ``BENCH_ATTN_SEQS`` (comma list, default "1024,2048,4096,8192"),
``BENCH_ATTN_STEPS`` (default 10), ``BENCH_ATTN_IMPLS`` (comma subset of
"flash,xla", default both).
"""

from __future__ import annotations

import json
import os
import sys
import time

from bench_common import persist_result, start


def bench_one(fn, args, n_steps: int, repeats: int = 3) -> float:
    """Best-of-``repeats`` timing (min filters host-side noise): warmup
    twice, then time ``n_steps`` chained dispatches per repeat, each
    window ending when the last output is ready."""
    import jax

    out = None
    for _ in range(2):
        out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / n_steps)
    return best


def _is_oom(e: Exception) -> bool:
    text = str(e)
    return "Ran out of memory" in text or "RESOURCE_EXHAUSTED" in text


def main() -> None:
    start("bench_attn")

    import jax
    import jax.numpy as jnp

    from distributedtensorflow_tpu.ops.attention import xla_attention
    from distributedtensorflow_tpu.ops.flash_attention import flash_attention

    seqs = [
        int(s)
        for s in os.environ.get("BENCH_ATTN_SEQS", "1024,2048,4096,8192").split(",")
    ]
    n_steps = int(os.environ.get("BENCH_ATTN_STEPS", "10"))
    impls = [
        s.strip()
        for s in os.environ.get("BENCH_ATTN_IMPLS", "flash,xla").split(",")
        if s.strip()
    ]
    unknown = set(impls) - {"flash", "xla"}
    if unknown or not impls:
        raise SystemExit(
            f"BENCH_ATTN_IMPLS must be a non-empty subset of flash,xla; "
            f"got {os.environ.get('BENCH_ATTN_IMPLS')!r}"
        )
    from distributedtensorflow_tpu.ops import flash_tuning
    from distributedtensorflow_tpu.ops.flash_attention import (
        _default_chain,
        _resolve_blocks,
        DEFAULT_BLOCK_K,
        DEFAULT_BLOCK_Q,
    )

    b, h, d = 4, 8, 64
    platform = jax.devices()[0].platform

    rows = []
    for seq in seqs:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (
            jax.random.normal(kk, (b, seq, h, d), jnp.bfloat16) for kk in ks
        )

        # Resolved tiling (env > autotune cache > default chain) vs the
        # default chain, recorded per row so the autotuner's pick is
        # auditable; when they differ, BOTH are timed.
        res_bq, res_bk = _resolve_blocks(b, h, seq, d, jnp.bfloat16,
                                         None, None)
        def_bq = _default_chain(seq, DEFAULT_BLOCK_Q)
        def_bk = _default_chain(seq, DEFAULT_BLOCK_K)
        tuned = flash_tuning.lookup(
            platform=jax.default_backend(), dtype="bfloat16",
            seq=seq, depth=d, batch=b, heads=h,
        )

        flash_f = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=True)
        )
        flash_default_f = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=def_bq, block_k=def_bk,
            )
        )
        xla_f = jax.jit(lambda q, k, v: xla_attention(q, k, v, causal=True))

        def loss(fn):
            return jax.jit(
                jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
                         argnums=(0, 1, 2))
            )

        # At 8k+ the XLA dense path runs out of memory; that is a result
        # ({"xla_fwd": "oom"}) and must not stop the flash timings that
        # follow.  Every other failure raises.
        measurements = []
        if "flash" in impls:
            measurements += [
                ("flash_fwd_ms", flash_f, (q, k, v)),
                ("flash_bwd_ms",
                 loss(lambda q, k, v: flash_attention(
                     q, k, v, causal=True)),
                 (q, k, v)),
            ]
        if "xla" in impls:
            measurements += [
                ("xla_fwd_ms", xla_f, (q, k, v)),
                ("xla_bwd_ms",
                 loss(lambda q, k, v: xla_attention(q, k, v, causal=True)),
                 (q, k, v)),
            ]
        if "flash" in impls and (res_bq, res_bk) != (def_bq, def_bk):
            # An autotuned (or env-pinned) tiling is in force: time the
            # default chain too so the pick is auditable as a delta.
            measurements.append(
                ("flash_fwd_default_ms", flash_default_f, (q, k, v))
            )
        row = {
            "seq": seq,
            "block_q": res_bq, "block_k": res_bk,
            "default_block_q": def_bq, "default_block_k": def_bk,
            "autotuned": tuned is not None and (res_bq, res_bk) == tuned,
        }
        for key, fn, fargs in measurements:
            try:
                row[key] = round(1e3 * bench_one(fn, fargs, n_steps), 3)
            except jax.errors.JaxRuntimeError as e:
                if not (key.startswith("xla_") and _is_oom(e)):
                    raise
                row[key.removesuffix("_ms")] = "oom"
        if "flash_fwd_ms" in row and "flash_fwd_default_ms" in row:
            row["tuned_vs_default"] = round(
                row["flash_fwd_default_ms"] / row["flash_fwd_ms"], 3
            )
        if "flash_fwd_ms" in row and "xla_fwd_ms" in row:
            row["fwd_speedup"] = round(row["xla_fwd_ms"] / row["flash_fwd_ms"], 3)
        if "flash_bwd_ms" in row and "xla_bwd_ms" in row:
            row["bwd_speedup"] = round(row["xla_bwd_ms"] / row["flash_bwd_ms"], 3)
        rows.append(row)
        print(f"bench_attn: {row}", file=sys.stderr)

    result = {
        "metric": "flash_attention_speedup_vs_xla",
        "rows": rows,
        "batch": b, "heads": h, "head_dim": d,
        "impls": impls,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    persist_result("attn", result)

    ok_rows = [r for r in rows if "fwd_speedup" in r]
    best = max((r["fwd_speedup"] for r in ok_rows), default=0.0)
    print(json.dumps({
        "metric": "flash_attention_speedup_vs_xla",
        "value": best,
        "unit": "x",
        "vs_baseline": best,
        "rows": rows,
        "platform": platform,
    }))


if __name__ == "__main__":
    main()
