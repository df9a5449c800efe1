#!/usr/bin/env python3
"""Time the lfm2 family's programs alone on the chip: a whole prefill chunk by
width and context (its attention layers take the plain loop at heads of 64:
``chunk_attention``) and a decode iteration by slots and context (every slot
live, so ``slots x top k / experts`` rows an expert: six at 96 slots).

    chiprun -- python tools/conv_forms.py [--chunks 1024,2048]
        [--starts 0,2048,4096,6144] [--slots 64,96,128] [--decode 1024,2800]

No engine, no HTTP: the programs of ``serve/model.py:make_programs`` over a
K/V pool of the cell's size and the state group's tails, each call timed to
``block_until_ready`` (median of ``--reps``).  One JSON row a measurement;
``PERF.md`` section 4 has the table this fills.  Exits non-zero without a
TPU.
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
    p.add_argument("--config", default="lfm2_24b_a2b")
    p.add_argument("--chunks", default="1024,2048")
    p.add_argument("--starts", default="0,2048,4096,6144")
    p.add_argument("--slots", default="64,96,128")
    p.add_argument("--decode", default="1024,2800")
    p.add_argument("--kv-blocks", type=int, default=40000)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=9216)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.serve import kv_cache
    from distributedtensorflow_tpu.serve.model import (family_of,
                                                       make_programs)

    runtime.init_compile_cache()
    if not runtime.on_tpu():
        print("conv_forms: no TPU", file=sys.stderr)
        return 1
    base = dataclasses.replace(getattr(models, args.config)(),
                               max_seq=args.max_context)
    bs, cols = args.block_size, args.max_context // args.block_size
    params = family_of(base).init_params(base, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    rng = np.random.default_rng(0)

    def timed(call, pools):
        walls = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            out, pools = call(pools)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        return 1e3 * statistics.median(walls[1:]), pools

    def make_cache(slots: int):
        return kv_cache.make_grouped_cache(
            base, max_slots=slots, block_size=bs,
            max_context=args.max_context,
            num_blocks={"full": args.kv_blocks}, write_ahead=2048)

    cache = make_cache(1)
    pools = cache.pools()
    table_row = {"full": jnp.arange(cols, dtype=jnp.int32),
                 "state": jnp.zeros((1,), jnp.int32)}
    for chunk in (int(x) for x in args.chunks.split(",") if x):
        prog = make_programs(base, chunk=chunk, block_size=bs,
                             layers=cache.layers)
        tokens = rng.integers(0, base.vocab_size, chunk)
        for start in (int(s) for s in args.starts.split(",")):
            start = min(start, args.max_context - chunk)
            ms, pools = timed(lambda pools: prog.prefill(
                params, pools, tokens, start, table_row, chunk), pools)
            print(json.dumps({
                "program": "prefill_chunk", "chunk": chunk,
                "chunk_attention": prog.chunk_attention,
                "state_form": prog.state_form, "start": start,
                "ms": round(ms, 3),
                "us_per_token": round(1e3 * ms / chunk, 2)}), flush=True)
    del pools

    for slots in (int(x) for x in args.slots.split(",") if x):
        cache = make_cache(slots)
        pools = cache.pools()
        prog = make_programs(base, chunk=2048, block_size=bs,
                             layers=cache.layers)
        last = jnp.asarray(rng.integers(0, base.vocab_size, slots), jnp.int32)
        active = jnp.ones((slots,), bool)
        for length in (int(x) for x in args.decode.split(",") if x):
            per_slot = -(-(length + 1) // bs)
            if slots * per_slot > args.kv_blocks:
                continue
            # each slot its own blocks, as far as its context reaches
            table = np.zeros((slots, cols), np.int32)
            table[:, :per_slot] = (np.arange(slots)[:, None] * per_slot
                                   + np.arange(per_slot)[None, :])
            tables = {"full": jnp.asarray(table),
                      "state": jnp.asarray(cache.state.block_tables)}
            lens = jnp.full((slots,), length, jnp.int32)

            def decode(pools):
                _, greedy, pools, routed = prog.decode(
                    params, pools, last, tables, lens, active)
                return (greedy, routed), pools

            ms, pools = timed(decode, pools)
            print(json.dumps({
                "program": "decode", "slots": slots, "context": length,
                "decode_attention": prog.decode_attention,
                "ms": round(ms, 3),
                "tokens_per_s_device": round(1e3 * slots / ms)}), flush=True)
        del pools
    return 0


if __name__ == "__main__":
    sys.exit(main())
