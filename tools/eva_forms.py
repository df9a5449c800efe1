#!/usr/bin/env python3
"""Time the evabyte family's programs alone on the chip: a decode step by
slots and context (every slot live; a context of ``t`` reads ``t % 2048 + 1``
ring rows and ``t // 2048 * 128`` summary rows a layer) and a prefill chunk by
the windows closed before it (0 / 4 / 14: 0 / 512 / 1,792 summary rows seen),
each through the kernels (two walks of ``paged_attn`` / ``kv_chunk_attn``
merged by their log-sum-exp) and through the plain formulation
(``kernel_impl="xla"``: the gather of the open window and of every summary
column under one softmax).

    chiprun -- python tools/eva_forms.py [--slots 16,24,32]
        [--contexts 1024,8192,30000] [--closed 0,4,14] [--impls auto,xla]
    chiprun -- python tools/eva_forms.py --decode-attn 9500,30000 --slots 28

``--decode-attn CONTEXTS`` times one layer's decode attention alone and
nothing else (``EvaRows.decode``: the ring's walk and the summary pool's,
merged): ragged slots of ``context / 2`` to ``3 context / 2`` tokens over
scattered blocks, ``--calls`` calls launched back to back and timed together
— a single call's wall carries ~0.5 ms of dispatch (PERF.md, PRs 40, 53).

No engine, no HTTP: the programs of ``serve/model.py:make_programs`` over the
cell's two pools, each call timed to ``block_until_ready`` (median of
``--reps``).  The tables name each slot's own blocks as far as the pools
reach and wrap beyond (a timing reads rows, not meanings).  One JSON row a
measurement; ``PERF.md`` section 5 has the table this fills.  Exits non-zero
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
    p.add_argument("--config", default="evabyte_6_5b")
    p.add_argument("--slots", default="16,24,32")
    p.add_argument("--contexts", default="1024,8192,30000")
    p.add_argument("--closed", default="0,4,14")
    p.add_argument("--impls", default="auto,xla")
    p.add_argument("--chunk", type=int, default=2048)
    p.add_argument("--kv-blocks", type=int, default=1200)
    p.add_argument("--ring-slots", type=int, default=24,
                   help="slots the ring pool is sized for (more share it)")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=32768)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--decode-attn", default="",
                   help="contexts: time one layer's two walks alone")
    p.add_argument("--calls", type=int, default=20,
                   help="--decode-attn: calls timed together")
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
        print("eva_forms: no TPU", file=sys.stderr)
        return 1
    base = dataclasses.replace(getattr(models, args.config)(),
                               max_seq=args.max_context)
    bs, w, c = args.block_size, base.window_size, base.chunk_size
    if args.decode_attn:
        return _decode_attention_alone(args, base)
    params = family_of(base).init_params(base, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    rng = np.random.default_rng(0)
    ints = lambda text: [int(x) for x in text.split(",") if x]  # noqa: E731

    def timed(call, pools):
        walls = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            out, pools = call(pools)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        return 1e3 * statistics.median(walls[1:]), pools

    def make_cache(slots: int):
        ring = min(slots, args.ring_slots) * (w // bs + 1)
        return kv_cache.make_grouped_cache(
            base, max_slots=slots, block_size=bs,
            max_context=args.max_context,
            num_blocks={"full": args.kv_blocks, "window": ring},
            write_ahead=args.chunk)

    def tables_of(cache, slots: int):
        """Every column mapped: a slot's own blocks, wrapped at the pool."""
        out = {}
        for name, g in cache.paged.items():
            cols = g.blocks_per_slot
            out[name] = jnp.asarray(
                (np.arange(slots)[:, None] * cols + np.arange(cols)[None, :])
                % g.allocator.num_blocks, jnp.int32)
        return out

    for impl in args.impls.split(","):
        cfg = dataclasses.replace(base, kernel_impl=impl)
        cache = make_cache(1)
        pools = cache.pools()
        prog = make_programs(cfg, chunk=args.chunk, block_size=bs,
                             layers=cache.layers)
        rows = {n: t[0] for n, t in tables_of(cache, 1).items()}
        tokens = rng.integers(0, cfg.vocab_size, args.chunk)
        for closed in ints(args.closed):
            start = closed * w
            ms, pools = timed(lambda pools: prog.prefill(
                params, pools, tokens, start, rows, args.chunk), pools)
            print(json.dumps({
                "program": "prefill_chunk", "impl": impl,
                "chunk_attention": prog.chunk_attention,
                "windows_closed": closed, "summary_rows_seen": start // c,
                "ms": round(ms, 3),
                "us_per_token": round(1e3 * ms / args.chunk, 2)}), flush=True)
        del pools, cache

        for slots in ints(args.slots):
            cache = make_cache(slots)
            pools = cache.pools()
            prog = make_programs(cfg, chunk=args.chunk, block_size=bs,
                                 layers=cache.layers)
            tables = tables_of(cache, slots)
            last = jnp.asarray(rng.integers(0, cfg.vocab_size, slots),
                               jnp.int32)
            active = jnp.ones((slots,), bool)
            for length in ints(args.contexts):
                lens = jnp.full((slots,), length, jnp.int32)

                def decode(pools):
                    _, greedy, pools, _ = prog.decode(
                        params, pools, last, tables, lens, active)
                    return greedy, pools

                ms, pools = timed(decode, pools)
                print(json.dumps({
                    "program": "decode", "impl": impl, "slots": slots,
                    "context": length,
                    "decode_attention": prog.decode_attention,
                    "ring_rows": length % w + 1,
                    "summary_rows": length // w * (w // c),
                    "ms": round(ms, 3),
                    "tokens_per_s_device": round(1e3 * slots / ms)}),
                    flush=True)
            del pools, cache
    return 0


def _decode_attention_alone(args, base) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu.ops import attention
    from distributedtensorflow_tpu.serve import kv_cache

    form, bs, w = base.cache_rows, args.block_size, base.window_size
    rng = np.random.default_rng(0)
    ints = lambda text: [int(x) for x in text.split(",") if x]  # noqa: E731
    for slots in ints(args.slots):
        blocks = {form.token_group: slots * (w // bs + 1),
                  form.summary_group: args.kv_blocks}
        cols = {form.token_group: args.max_context // bs,
                form.summary_group: -(-args.max_context // base.chunk_size
                                      // bs)}
        keys = iter(jax.random.split(jax.random.PRNGKey(0), 5))
        pools = {name: tuple(
            jax.random.normal(next(keys), kv_cache.pool_shape(
                1, blocks[name], bs, width), base.dtype)
            for width in rows.widths) for name, rows in form.groups.items()}
        q = jax.random.normal(next(keys), (slots, form.heads, form.head_dim),
                              base.dtype)
        one = jax.jit(lambda q, pools, tables, lens: form.decode(
            q, pools, tables, lens, layer=0, block_size=bs,
            impl=base.kernel_impl))
        queries = [q * (1 + 0.001 * i) for i in range(args.calls)]
        for context in ints(args.decode_attn):
            lens = np.minimum(rng.integers(
                context // 2, 3 * context // 2 + 1, slots),
                args.max_context)
            first = (lens - 1) // w * w
            held = {form.token_group: (first // bs, -(-lens // bs)),
                    form.summary_group: (0 * lens, -(-(
                        first // base.chunk_size) // bs))}
            tables = {}
            for name, (lo, hi) in held.items():
                # a slot's own blocks, scattered; wrapped where slots share
                # the pool (a timing reads rows, not meanings)
                table = np.full((slots, cols[name]), blocks[name], np.int32)
                scattered, o = rng.permutation(blocks[name]), 0
                for i in range(slots):
                    n = hi[i] - lo[i]
                    table[i, lo[i]:hi[i]] = scattered[
                        (o + np.arange(n)) % blocks[name]]
                    o += n
                tables[name] = jnp.asarray(table)
            lens_dev = jnp.asarray(lens, jnp.int32)
            jax.block_until_ready(one(q, pools, tables, lens_dev))
            walls = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                for x in queries:
                    out = one(x, pools, tables, lens_dev)
                jax.block_until_ready(out)
                walls.append((time.perf_counter() - t0) / args.calls)
            ms = 1e3 * statistics.median(walls)
            ring = int((lens - first).sum())
            summary = int((first // base.chunk_size).sum())
            stretch = attention.PAGED_STRETCH
            moved = (ring + summary) * sum(
                form.groups[form.token_group].widths) * jnp.dtype(
                    base.dtype).itemsize
            print(json.dumps({
                "program": "decode_attention_one_layer", "slots": slots,
                "context": context,
                "decode_attention": form.groups[
                    form.token_group].decode_formulation(
                        bs, base.kernel_impl),
                "ring_rows": ring, "summary_rows": summary,
                "stretches_walked": int(
                    (-(-lens // stretch) - first // stretch).sum()
                    + (-(-(first // base.chunk_size) // stretch)).sum()),
                "ms": round(ms, 4),
                "row_gb_per_s": round(moved / ms / 1e6, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
