#!/usr/bin/env python3
"""Time the joyai family's two programs alone on the chip: a prefill chunk by
chunk width, context, the formulation of its latent attention and the row
tile of the grouped expert matmuls, and a decode iteration by context.

    chiprun -- python tools/latent_forms.py [--chunks 512,1024,2048]
        [--impl latent_chunk_attn,plain] [--kernel-tiles 8x256x512,4x512x512]
        [--attn 1] [--tiles 16,32,64,128] [--starts 0,4096,8192,14336]
        [--decode 2000,9000,15000]
    chiprun -- python tools/latent_forms.py --decode-attn 2700 \
        --config ling3_flash_ep8 --slots 128 --kv-blocks 98304 \
        --max-context 20480

``--decode-attn CONTEXTS`` times one layer's
``ops.attention.paged_latent_decode_attention`` alone and nothing else (any
configuration whose ``cache_rows`` are latent rows: joyai's, ling's): ragged
slots of ``context / 2`` to ``3 context / 2`` rows over scattered blocks, a
table of ``--max-context`` (cut it to the contexts to see what the table's
empty columns cost), ``--calls`` calls launched back to back and timed
together — a single call's wall carries ~0.5 ms of dispatch (PERF.md, PRs
40, 53).

No engine, no HTTP: the programs of ``serve/model.py:make_programs`` over a
pool of the cell's size, each call timed to ``block_until_ready`` (median of
``--reps``).  ``--attn 1`` times one layer's
``ops.attention.paged_latent_chunk_attention`` alone as well (``2``: that
only), which is where the kernel's tiles (heads a grid step x queries a tile x rows a stretch) are
compared.  One JSON row a measurement; ``PERF.md`` section 4 has the table
this fills.  Exits non-zero without a TPU.
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
    p.add_argument("--config", default="joyai_llm_flash")
    p.add_argument("--chunks", default="512,1024,2048")
    p.add_argument("--impl", default="",
                   help="formulations of the chunk's latent attention to "
                        "time, of ops.attention.paged_latent_chunk_"
                        "formulation's names (latent_chunk_attn, plain); "
                        "default: as built")
    p.add_argument("--kernel-tiles", default="",
                   help="HEADSxQUERIESxSTRETCH of the chunk kernel to time "
                        "(ops.attention.LATENT_CHUNK_HEADS, _QUERIES, "
                        "LATENT_STRETCH); default: as built")
    p.add_argument("--attn", type=int, default=0,
                   help="1: time one layer's chunk attention alone too; "
                        "2: that alone, no whole chunk")
    p.add_argument("--tiles", default="",
                   help="rows of the wide tile of the grouped matmuls to "
                        "time (parallel.moe.GROUP_TILE_WIDE; the small tile "
                        "times a chunk with no wide tile); default: as built")
    p.add_argument("--starts", default="0,4096,8192,14336")
    p.add_argument("--decode", default="2000,9000,15000")
    p.add_argument("--slots", type=int, default=32)
    p.add_argument("--kv-blocks", type=int, default=24576)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=16384)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--decode-attn", default="",
                   help="mean contexts at which to time one layer's decode "
                        "attention alone; nothing else is timed")
    p.add_argument("--calls", type=int, default=8,
                   help="calls a timed run of --decode-attn launches back "
                        "to back")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.ops import attention
    from distributedtensorflow_tpu.parallel import moe
    from distributedtensorflow_tpu.serve import kv_cache
    from distributedtensorflow_tpu.serve.model import (family_of,
                                                       make_programs)

    runtime.init_compile_cache()
    if not runtime.on_tpu():
        print("latent_forms: no TPU", file=sys.stderr)
        return 1
    base = dataclasses.replace(getattr(models, args.config)(),
                               max_seq=args.max_context)
    bs, cols = args.block_size, args.max_context // args.block_size
    if args.decode_attn:
        return _decode_attention_alone(args, base)
    layers = {"full": tuple(range(base.num_layers))}
    params = family_of(base).init_params(base, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    pools = {"full": tuple(
        jnp.zeros(kv_cache.pool_shape(base.num_layers, args.kv_blocks, bs,
                                      width), base.dtype)
        for width in base.cache_rows.widths)}
    rng = np.random.default_rng(0)

    def timed(call):
        nonlocal pools
        walls = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            out, pools = call(pools)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        return 1e3 * statistics.median(walls[1:])

    table_row = {"full": jnp.arange(cols, dtype=jnp.int32)}
    form = base.cache_rows
    formulation = attention.paged_latent_chunk_formulation
    as_built = (attention.LATENT_CHUNK_HEADS, attention.LATENT_CHUNK_QUERIES,
                attention.LATENT_STRETCH)
    # all three are read when a program is traced: a program a setting
    settings = [(impl or None, tiles)
                for impl in args.impl.split(",")
                for tiles in ([as_built] if impl == "plain" else [
                    tuple(int(x) for x in t.split("x"))
                    for t in args.kernel_tiles.split(",") if t] or [as_built])]
    starts = [int(s) for s in args.starts.split(",")]
    for chunk in (int(c) for c in args.chunks.split(",")):
        tokens = rng.integers(0, base.vocab_size, chunk)
        for impl, tiles in settings:
            (attention.LATENT_CHUNK_HEADS, attention.LATENT_CHUNK_QUERIES,
             attention.LATENT_STRETCH) = tiles
            attention.paged_latent_chunk_formulation = (
                formulation if impl is None else lambda *a, impl=impl: impl)
            tag = {"chunk": chunk, "chunk_attention": form.chunk_formulation(
                bs, chunk, base.kernel_impl), "kernel_tiles": "x".join(
                    str(x) for x in tiles)}
            if args.attn:
                h = base.num_heads
                q = [jnp.asarray(rng.standard_normal((chunk, h, d)),
                                 base.dtype)
                     for d in (base.qk_nope_head_dim, base.qk_rope_head_dim)]
                w = params["h1"]["attn"]
                one = jax.jit(lambda pools, start: form.chunk(
                    q, start, pools["full"], table_row["full"], layer=1,
                    block_size=bs, impl=base.kernel_impl,
                    w_uk=w["w_uk"], w_uv=w["w_uv"]))
                for start in starts:
                    start = min(start, args.max_context - chunk)
                    ms = timed(lambda pools: (
                        one(pools, jnp.int32(start)), pools))
                    print(json.dumps({
                        "program": "chunk_attention_one_layer", **tag,
                        "start": start, "ms": round(ms, 3)}), flush=True)
            for tile in [] if args.attn == 2 else [
                    int(t) for t in args.tiles.split(",") if t] or [
                        moe.GROUP_TILE_WIDE]:
                moe.GROUP_TILE_WIDE = tile
                prog = make_programs(base, chunk=chunk, block_size=bs,
                                     layers=layers)
                for start in starts:
                    start = min(start, args.max_context - chunk)
                    ms = timed(lambda pools: prog.prefill(
                        params, pools, tokens, start, table_row, chunk))
                    print(json.dumps({
                        "program": "prefill_chunk", **tag,
                        "group_tile": moe.group_tile(
                            chunk, base.experts_per_token, base.num_experts),
                        "start": start, "ms": round(ms, 3),
                        "us_per_token": round(1e3 * ms / chunk, 2)}),
                        flush=True)

    prog = make_programs(base, chunk=512, block_size=bs, layers=layers)
    per_slot = args.kv_blocks // args.slots
    tables = {"full": jnp.asarray(
        np.arange(args.slots)[:, None] * per_slot
        + np.minimum(np.arange(cols), per_slot - 1)[None, :], jnp.int32)}
    last = jnp.asarray(rng.integers(0, base.vocab_size, args.slots),
                       jnp.int32)
    active = jnp.ones((args.slots,), bool)
    for n in (int(x) for x in args.decode.split(",") if x):
        n = min(n, per_slot * bs - 1)
        lens = jnp.full((args.slots,), n, jnp.int32)

        def decode(pools):
            logits, greedy, pools, routed = prog.decode(
                params, pools, last, tables, lens, active)
            return (greedy, routed), pools

        ms = timed(decode)
        print(json.dumps({
            "program": "decode", "slots": args.slots, "context": n,
            "decode_attention": prog.decode_attention,
            "ms": round(ms, 3)}), flush=True)
    return 0


def _decode_attention_alone(args, base) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu.ops import attention
    from distributedtensorflow_tpu.serve import kv_cache

    form, bs = base.cache_rows, args.block_size
    cols, slots, heads = args.max_context // bs, args.slots, base.num_heads
    stretch = attention.PAGED_LATENT_STRETCH
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    pool = jax.random.normal(keys[0], kv_cache.pool_shape(
        1, args.kv_blocks, bs, form.widths[0]), base.dtype)
    pool = pool.at[..., form.values[0]:].set(0)
    q_nope, q_rope, w_uk, w_uv = (
        jax.random.normal(k, shape, base.dtype) for k, shape in zip(keys[1:], (
            (slots, heads, base.qk_nope_head_dim),
            (slots, heads, form.rope_dim),
            (form.rank, heads, base.qk_nope_head_dim),
            (form.rank, heads, base.v_head_dim))))
    one = jax.jit(lambda pool, q_nope, tables, lens: form.decode(
        (q_nope, q_rope), (pool,), tables, lens, layer=0, block_size=bs,
        impl=base.kernel_impl, w_uk=w_uk, w_uv=w_uv))
    queries = [q_nope * (1 + 0.001 * i) for i in range(args.calls)]
    for context in (int(x) for x in args.decode_attn.split(",")):
        lens = np.minimum(rng.integers(
            context // 2, 3 * context // 2 + 1, slots), args.max_context)
        need = -(-lens // bs)
        if need.sum() > args.kv_blocks:
            print(f"latent_forms: {need.sum()} blocks at context {context}, "
                  f"--kv-blocks {args.kv_blocks}", file=sys.stderr)
            return 2
        tables = np.full((slots, cols), args.kv_blocks, np.int32)
        scattered = rng.permutation(args.kv_blocks)
        for i, end in enumerate(np.cumsum(need)):
            tables[i, :need[i]] = scattered[end - need[i]:end]
        tables, lens_dev = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
        jax.block_until_ready(one(pool, q_nope, tables, lens_dev))
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            for q in queries:
                out = one(pool, q, tables, lens_dev)
            jax.block_until_ready(out)
            walls.append((time.perf_counter() - t0) / args.calls)
        ms = 1e3 * statistics.median(walls)
        moved = int(lens.sum()) * form.widths[0] * jnp.dtype(
            base.dtype).itemsize
        print(json.dumps({
            "program": "decode_attention_one_layer", "slots": slots,
            "context": context, "max_context": args.max_context,
            "decode_attention": form.decode_formulation(bs, base.kernel_impl),
            "rows": int(lens.sum()),
            "stretches_walked": int((-(-lens // stretch)).sum()),
            "stretches_capacity": slots * -(-args.max_context // stretch),
            "ms": round(ms, 4),
            "row_gb_per_s": round(moved / ms / 1e6, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
