#!/usr/bin/env python3
"""Time a K/V-row family's attention alone on the chip: the decode kernel
``paged_attn`` by layer kind and context (at mimo's keys of 192 over values of
128 a K head's last 64 values lie two a tile after the heads' whole tiles, as
the pools store them; the same kernel over a head padded to two tiles, 3,072 B
a token a full layer against 2,560, timed within 1 % of it — ``PERF.md``
section 6, PR 41 — and was taken out again), a prefill chunk's attention alone
by layer kind and context in both formulations (the kernel ``kv_chunk_attn``
and the plain loop, ``impl="xla"``), a whole prefill chunk by context in both,
and a decode iteration by context.

    chiprun -- python tools/wide_key_forms.py
        [--config mimo_v25_ep16|trinity_large_ep8|jamba2_3b]
        [--only kernel,attn,chunk,decode] [--impl auto,xla]
        [--contexts 1024,16384,32768] [--starts 0,15360,64512]
        [--decode 2048,8192,20000,32000] [--kernel-tiles 8x512x512,4x256x512]

No engine, no HTTP: the kernels over pools of the cell's size (``--slots``,
``--max-context``, ``--kv-blocks``, ``--chunk`` default to the cell's of the
config), then the programs of ``serve/model.py:make_programs``, each call
timed to ``block_until_ready`` (median of ``--reps``).  ``--kernel-tiles``
times the chunk kernel at other tiles (heads a grid step x queries a tile x
rows a stretch: ``ops.attention.KV_CHUNK_HEADS``, ``_QUERIES``,
``_STRETCH``).  One JSON row a measurement; ``PERF.md`` sections 4 and 6 have
the tables this fills, and ``ops.attention.paged_chunk_formulation``'s rule
the threshold.  Exits non-zero without a TPU.
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

#: the serving cells' argv (``benchmark/configs/*.json``)
CELLS = {
    "mimo_v25_ep16": dict(slots=32, max_context=67584, kv_blocks=65536,
                          chunk=1024),
    "trinity_large_ep8": dict(slots=64, max_context=8192, kv_blocks=24576,
                              chunk=512),
    "jamba2_3b": dict(slots=32, max_context=33792, kv_blocks=67584,
                      chunk=1024),
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="mimo_v25_ep16")
    p.add_argument("--only", default="kernel,attn,chunk,decode",
                   help="the measurements to take: kernel (paged_attn "
                        "alone), attn (a chunk's attention alone a layer "
                        "kind), chunk (a whole prefill chunk), decode")
    p.add_argument("--impl", default="auto,xla",
                   help="the kernel_impl a chunk and its attention are "
                        "timed under: auto (the kernels), xla (the loop)")
    p.add_argument("--kernel-tiles", default="",
                   help="HEADSxQUERIESxSTRETCH of the chunk kernel to time "
                        "(ops.attention.KV_CHUNK_HEADS, _QUERIES, _STRETCH)"
                        "; default: as built")
    p.add_argument("--contexts", default="1024,16384,32768")
    p.add_argument("--starts", default="0,15360,64512")
    p.add_argument("--decode", default="2048,8192,20000,32000")
    p.add_argument("--slots", type=int)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int)
    p.add_argument("--kv-blocks", type=int)
    p.add_argument("--chunk", type=int)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args(argv)
    for name, value in CELLS.get(args.config, CELLS["mimo_v25_ep16"]).items():
        if getattr(args, name) is None:
            setattr(args, name, value)
    only = set(args.only.split(","))

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
                               max_seq=args.max_context)
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

    layers = kv_cache.layer_groups(base)
    shapes = jax.eval_shape(
        lambda: family_of(base).init_params(base, jax.random.PRNGKey(0)))
    # (group, its row form, its window, whether its heads have a sink)
    kinds = [(name, kv_cache.group_rows(base, name), base.window_of(ls[0]),
              "sink" in shapes[f"h{ls[0]}"]["attn"])
             for name, ls in layers.items() if name != "state"]
    per_slot = args.kv_blocks // slots
    full_table = jnp.asarray(
        (np.arange(slots)[:, None] * per_slot
         + np.arange(cols)[None, :] % per_slot), jnp.int32)
    rows = (args.kv_blocks + 1) * bs
    # one slot's chunk may walk the whole pool: its own table row
    own_row = jnp.arange(cols, dtype=jnp.int32) % args.kv_blocks

    def one_layer_pools(form):
        return tuple(jnp.zeros((1, rows, w), base.dtype)
                     for w in form.widths)

    # -- the decode kernel alone, by layer kind and context ------------------
    # (a toy preset's heads fill no tile: it has the programs only)
    for kind, form, window, has_sink in kinds if "kernel" in only else ():
        if form.decode_formulation(bs, "auto") == "plain":
            continue
        d, dv = form.head_dim, form.value_dim or form.head_dim
        q = jnp.asarray(rng.standard_normal((slots, form.heads, d)),
                        base.dtype)
        sink = (jnp.asarray(rng.standard_normal(form.heads), jnp.float32)
                if has_sink else None)
        call = jax.jit(lambda q, k, v, t, n, s, w=window: (
            A.paged_window_decode_attention(
                q, k, v, t, n, layer=0, block_size=bs, window=w, sink=s)))
        k_pool, v_pool = one_layer_pools(form)
        for context in (int(c) for c in args.contexts.split(",")):
            context = min(context, per_slot * bs)
            lens = jnp.full((slots,), context, jnp.int32)
            ms = timed(call, q, k_pool, v_pool, full_table, lens, sink)
            row_bytes = 2 * form.kv_heads * (d + dv)
            read = slots * min(context, window or context) * row_bytes
            print(json.dumps({
                "program": "paged_attn", "kind": kind,
                "kv_heads": form.kv_heads, "context": context,
                "row_bytes": row_bytes, "ms": round(ms, 3),
                "gb_per_s": round(read / ms / 1e6, 1)}), flush=True)
        del k_pool, v_pool

    # -- a chunk's attention alone, by layer kind, formulation and context ---
    as_built = (A.KV_CHUNK_HEADS, A.KV_CHUNK_QUERIES, A.KV_CHUNK_STRETCH)
    impls = [i for i in args.impl.split(",") if i]
    # the tiles are read when a call is traced: a call a setting
    settings = [(impl, tiles) for impl in impls
                for tiles in ([as_built] if impl == "xla" else [
                    tuple(int(x) for x in t.split("x"))
                    for t in args.kernel_tiles.split(",") if t] or [as_built])]
    starts = [min(int(x), args.max_context - args.chunk)
              for x in args.starts.split(",") if x]
    for kind, form, window, has_sink in kinds if "attn" in only else ():
        d, dv = form.head_dim, form.value_dim or form.head_dim
        q = jnp.asarray(rng.standard_normal((args.chunk, form.heads, d)),
                        base.dtype)
        weights = ({"sink": jnp.asarray(rng.standard_normal(form.heads),
                                        jnp.float32)} if has_sink else {})
        pools = one_layer_pools(form)
        for impl, tiles in settings:
            A.KV_CHUNK_HEADS, A.KV_CHUNK_QUERIES, A.KV_CHUNK_STRETCH = tiles
            one = jax.jit(lambda pools, start, impl=impl: form.chunk(
                q, start, pools, own_row, layer=0, block_size=bs,
                window=window, impl=impl, **weights))
            for start in starts:
                # a window layer attends window + chunk rows whatever the
                # context: two of them say so
                if window is not None and start not in starts[:2]:
                    continue
                ms = timed(one, pools, jnp.int32(start))
                attended = (args.chunk * (start + (args.chunk + 1) / 2)
                            if window is None else args.chunk * min(
                                window, start + args.chunk / 2))
                flops = 2 * form.heads * attended * (d + dv)
                print(json.dumps({
                    "program": "chunk_attention_one_layer", "kind": kind,
                    "kv_heads": form.kv_heads, "chunk": args.chunk,
                    "chunk_attention": form.chunk_formulation(
                        bs, args.chunk, impl),
                    "kernel_tiles": "x".join(str(x) for x in tiles),
                    "context": start + args.chunk, "ms": round(ms, 3),
                    "tflop_per_s": round(flops / ms / 1e9, 1)}), flush=True)
        del pools
    A.KV_CHUNK_HEADS, A.KV_CHUNK_QUERIES, A.KV_CHUNK_STRETCH = as_built
    if not only & {"chunk", "decode"}:
        return 0

    # -- the programs --------------------------------------------------------
    cache = kv_cache.make_grouped_cache(
        base, max_slots=slots, block_size=bs, max_context=args.max_context,
        num_blocks={"full": args.kv_blocks}, write_ahead=args.chunk)
    pools = cache.pools()
    params = family_of(base).init_params(base, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    tables = {"full": full_table}
    table_row = {"full": own_row}
    if "window" in layers:
        ring = cache.groups["window"].allocator.num_blocks // slots
        tables["window"] = jnp.asarray(
            (np.arange(slots)[:, None] * ring
             + np.arange(cols)[None, :] % ring), jnp.int32)
        table_row["window"] = tables["window"][0]
    if "state" in layers:
        table_row["state"] = jnp.zeros((1,), jnp.int32)
        tables["state"] = jnp.asarray(cache.groups["state"].block_tables)

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
    for impl in impls if "chunk" in only else ():
        prog = make_programs(dataclasses.replace(base, kernel_impl=impl),
                             chunk=args.chunk, block_size=bs, layers=layers)
        print(json.dumps({"program": "formulations", "impl": impl,
                          **prog.formulations}), flush=True)
        for start in starts:
            ms = run(lambda pools: prog.prefill(
                params, pools, tokens, start, table_row, args.chunk))
            print(json.dumps({
                "program": "prefill_chunk", "chunk": args.chunk,
                "chunk_attention": prog.chunk_attention,
                "context": start + args.chunk, "ms": round(ms, 3),
                "us_per_token": round(1e3 * ms / args.chunk, 2)}),
                flush=True)
    if "decode" not in only:
        return 0
    prog = make_programs(base, chunk=args.chunk, block_size=bs, layers=layers)
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
