#!/usr/bin/env python3
"""Time the mimo family's attention alone on the chip: the decode kernel
``paged_attn`` at keys of 192 over values of 128 by layer kind and context (a
K head's last 64 values two a tile after the heads' whole tiles, as the pools
store them; the same kernel over a head padded to two tiles, 3,072 B a token
a full layer against 2,560, timed within 1 % of it — ``PERF.md`` section 6,
PR 41 — and was taken out again), a whole prefill chunk by context (its K/V
rows take the plain loop) and a decode iteration by context.

    chiprun -- python tools/wide_key_forms.py [--contexts 1024,16384,32768]
        [--starts 0,15360,64512] [--decode 2048,8192,20000,32000]

No engine, no HTTP: the kernel over pools of the cell's size, then the
programs of ``serve/model.py:make_programs``, each call timed to
``block_until_ready`` (median of ``--reps``).  One JSON row a measurement;
``PERF.md`` sections 4 and 6 have the tables this fills.  Exits non-zero
without a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="mimo_v25_ep16")
    p.add_argument("--contexts", default="1024,16384,32768")
    p.add_argument("--starts", default="0,15360,64512")
    p.add_argument("--decode", default="2048,8192,20000,32000")
    p.add_argument("--slots", type=int, default=32)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=67584)
    p.add_argument("--kv-blocks", type=int, default=65536)
    p.add_argument("--chunk", type=int, default=1024)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--impl", default="auto",
                   help="the config's kernel_impl: auto, pallas or xla")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.ops import attention as A
    from distributedtensorflow_tpu.serve import kv_cache
    from distributedtensorflow_tpu.serve.model import (family_of,
                                                       make_programs)

    runtime.init_compile_cache()
    if not runtime.on_tpu():
        print("wide_key_forms: no TPU", file=sys.stderr)
        return 1
    base = dataclasses.replace(getattr(models, args.config)(),
                               max_seq=args.max_context,
                               kernel_impl=args.impl)
    bs, slots = args.block_size, args.slots
    cols = args.max_context // bs
    rng = np.random.default_rng(0)

    def timed(call, *xs):
        walls = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            jax.block_until_ready(call(*xs))
            walls.append(time.perf_counter() - t0)
        return 1e3 * statistics.median(walls[1:])

    # -- the kernel alone, by layer kind and context -------------------------
    heads, d, dv = base.num_heads, base.head_dim, base.v_head_dim
    per_slot = args.kv_blocks // slots
    full_table = jnp.asarray(
        (np.arange(slots)[:, None] * per_slot
         + np.arange(cols)[None, :] % per_slot), jnp.int32)
    rows = (args.kv_blocks + 1) * bs

    kinds = (("full", base.num_kv_heads, None),
             ("window", base.swa_num_kv_heads, base.sliding_window))
    # (a toy preset's heads fill no tile: it has the programs only)
    for kind, h_kv, window in kinds if d > A.LANES else ():
        q = jnp.asarray(rng.standard_normal((slots, heads, d)), base.dtype)
        sink = (jnp.asarray(rng.standard_normal(heads), jnp.float32)
                if window else None)
        call = jax.jit(lambda q, k, v, t, n, s, w=window: (
            A.paged_window_decode_attention(
                q, k, v, t, n, layer=0, block_size=bs, window=w, sink=s)))
        k_pool = jnp.zeros((1, rows, h_kv * d), base.dtype)
        v_pool = jnp.zeros((1, rows, h_kv * dv), base.dtype)
        for context in (int(c) for c in args.contexts.split(",")):
            context = min(context, per_slot * bs)
            lens = jnp.full((slots,), context, jnp.int32)
            ms = timed(call, q, k_pool, v_pool, full_table, lens, sink)
            row_bytes = 2 * h_kv * (d + dv)
            read = slots * min(context, window or context) * row_bytes
            print(json.dumps({
                "program": "paged_attn", "kind": kind, "kv_heads": h_kv,
                "context": context, "row_bytes": row_bytes,
                "ms": round(ms, 3),
                "gb_per_s": round(read / ms / 1e6, 1)}), flush=True)
        del k_pool, v_pool

    # -- the programs --------------------------------------------------------
    cache = kv_cache.make_grouped_cache(
        base, max_slots=slots, block_size=bs, max_context=args.max_context,
        num_blocks={"full": args.kv_blocks}, write_ahead=args.chunk)
    layers = cache.layers
    pools = cache.pools()
    params = family_of(base).init_params(base, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    prog = make_programs(base, chunk=args.chunk, block_size=bs, layers=layers)
    print(json.dumps({"program": "formulations", **prog.formulations}),
          flush=True)
    ring = cache.groups["window"].allocator.num_blocks // slots
    window_table = jnp.asarray(
        (np.arange(slots)[:, None] * ring + np.arange(cols)[None, :] % ring),
        jnp.int32)
    tables = {"full": full_table, "window": window_table}

    def run(call):
        nonlocal pools
        walls = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            out, pools = call(pools)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        return 1e3 * statistics.median(walls[1:])

    tokens = rng.integers(0, base.vocab_size, args.chunk)
    # one slot's chunk may walk the whole pool: its own table row
    table_row = {"full": jnp.arange(cols, dtype=jnp.int32) % args.kv_blocks,
                 "window": window_table[0]}
    for start in (int(x) for x in args.starts.split(",") if x):
        start = min(start, args.max_context - args.chunk)
        ms = run(lambda pools: prog.prefill(
            params, pools, tokens, start, table_row, args.chunk))
        print(json.dumps({
            "program": "prefill_chunk", "chunk": args.chunk,
            "context": start + args.chunk, "ms": round(ms, 3),
            "us_per_token": round(1e3 * ms / args.chunk, 2)}), flush=True)
    last = jnp.asarray(rng.integers(0, base.vocab_size, slots), jnp.int32)
    active = jnp.ones((slots,), bool)
    for length in (int(x) for x in args.decode.split(",") if x):
        length = min(length, per_slot * bs - 1)
        lens = jnp.full((slots,), length, jnp.int32)

        def decode(pools):
            logits, greedy, pools, _ = prog.decode(
                params, pools, last, tables, lens, active)
            return greedy, pools

        ms = run(decode)
        print(json.dumps({
            "program": "decode", "slots": slots, "context": length,
            "ms": round(ms, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
