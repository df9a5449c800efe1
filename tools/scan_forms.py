#!/usr/bin/env python3
"""Time the jamba family's programs alone on the chip: one layer's selective
scan by form (the ``ssm_chunk_scan`` kernel against the plain ``lax.scan``)
and chunk width, a whole prefill chunk by form, width and context, a decode
iteration by context, and the chip's float32 multiply-add rate on the vector
unit (the scan's own yardstick: the published compute peak is the matrix
unit's).

    chiprun -- python tools/scan_forms.py [--chunks 512,1024,2048]
        [--forms ssm_chunk_scan,plain] [--starts 0,8192,31744]
        [--decode 2000,10500,30000] [--fma 1]

No engine, no HTTP: the programs of ``serve/model.py:make_programs`` over
pools and state arrays of the cell's size, each call timed to
``block_until_ready`` (median of ``--reps``).  One JSON row a measurement;
``PERF.md`` section 4 has the table this fills.  Exits non-zero without a
TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fma_rate(jax, jnp, reps: int) -> float:
    """float32 multiply-adds a second (as FLOP/s, 2 a multiply-add) of a
    Pallas loop over 64 vector registers resident in VMEM."""
    from jax.experimental import pallas as pl

    steps, blocks = 4096, 16

    def kernel(x_ref, a_ref, b_ref, o_ref):
        a, b = a_ref[...], b_ref[...]

        def eight(_, x):
            for _ in range(8):
                x = x * a + b
            return x

        o_ref[...] = jax.lax.fori_loop(0, steps // 8, eight, x_ref[...])

    spec = pl.BlockSpec((64, 1024), lambda i: (i, 0))
    call = jax.jit(pl.pallas_call(
        kernel, grid=(blocks,), in_specs=[spec, spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((64 * blocks, 1024), jnp.float32)))
    x = jnp.full((64 * blocks, 1024), 0.5, jnp.float32)
    a, b = x * 1.0001, x * 0.25
    walls = []
    for _ in range(reps + 1):
        t0 = time.perf_counter()
        jax.block_until_ready(call(x, a, b))
        walls.append(time.perf_counter() - t0)
    return 2.0 * x.size * steps / statistics.median(walls[1:])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="jamba2_3b")
    p.add_argument("--chunks", default="512,1024,2048")
    p.add_argument("--forms", default="ssm_chunk_scan,plain")
    p.add_argument("--starts", default="0,8192,31744")
    p.add_argument("--decode", default="2000,10500,30000")
    p.add_argument("--fma", type=int, default=1)
    p.add_argument("--slots", type=int, default=32)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=33792)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.ops import ssm
    from distributedtensorflow_tpu.serve import kv_cache
    from distributedtensorflow_tpu.serve.model import (family_of,
                                                       make_programs)

    runtime.init_compile_cache()
    if not runtime.on_tpu():
        print("scan_forms: no TPU", file=sys.stderr)
        return 1
    if args.fma:
        print(json.dumps({"program": "vpu_f32_fma", "tflops": round(
            _fma_rate(jax, jnp, args.reps) / 1e12, 3)}), flush=True)
    base = dataclasses.replace(getattr(models, args.config)(),
                               max_seq=args.max_context)
    bs, cols = args.block_size, args.max_context // args.block_size
    cache = kv_cache.make_grouped_cache(
        base, max_slots=args.slots, block_size=bs,
        max_context=args.max_context, num_blocks={}, write_ahead=1024)
    layers = cache.layers
    pools = cache.pools()
    params = family_of(base).init_params(base, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
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

    rows = base.state_rows
    c, n = rows.channels, rows.d_state
    formulation = ssm.chunk_scan_formulation
    forms = [f for f in args.forms.split(",") if f]
    starts = [int(s) for s in args.starts.split(",")]
    table_row = {"full": jnp.arange(cols, dtype=jnp.int32),
                 "state": jnp.zeros((1,), jnp.int32)}
    for chunk in (int(x) for x in args.chunks.split(",")):
        tokens = rng.integers(0, base.vocab_size, chunk)
        u = jnp.asarray(rng.standard_normal((chunk, c)), base.dtype)
        delta = jnp.asarray(rng.uniform(0.01, 0.1, (chunk, c)), jnp.float32)
        bc = jnp.asarray(rng.standard_normal((2, chunk, n)), base.dtype)
        a = -jnp.exp(jnp.asarray(rng.standard_normal((n, c)), jnp.float32))
        d = jnp.ones((c,), jnp.float32)
        s0 = jnp.zeros((n, c), jnp.float32)
        for form in forms:
            # read when a program is traced: a program a form
            ssm.chunk_scan_formulation = (
                lambda *_, form=form, **__: form)
            one = jax.jit(functools.partial(
                ssm.ssm_chunk_scan, impl=base.kernel_impl))
            ms = timed(lambda pools: (
                one(u, delta, a, bc[0], bc[1], d, s0, jnp.int32(chunk)),
                pools))
            print(json.dumps({
                "program": "scan_one_layer", "chunk": chunk,
                "chunk_scan": form, "ms": round(ms, 3),
                "us_per_token": round(1e3 * ms / chunk, 3)}), flush=True)
            prog = make_programs(base, chunk=chunk, block_size=bs,
                                 layers=layers)
            for start in starts:
                start = min(start, args.max_context - chunk)
                ms = timed(lambda pools: prog.prefill(
                    params, pools, tokens, start, table_row, chunk))
                print(json.dumps({
                    "program": "prefill_chunk", "chunk": chunk,
                    "chunk_scan": prog.chunk_scan,
                    "chunk_attention": prog.chunk_attention, "start": start,
                    "ms": round(ms, 3),
                    "us_per_token": round(1e3 * ms / chunk, 2)}), flush=True)
    ssm.chunk_scan_formulation = formulation

    prog = make_programs(base, chunk=512, block_size=bs, layers=layers)
    tables = {name: jnp.asarray(g.block_tables)
              for name, g in cache.groups.items()}
    tables["full"] = jnp.asarray(
        np.arange(args.slots)[:, None] * cols + np.arange(cols)[None, :],
        jnp.int32)
    last = jnp.asarray(rng.integers(0, base.vocab_size, args.slots),
                       jnp.int32)
    active = jnp.ones((args.slots,), bool)
    for length in (int(x) for x in args.decode.split(",") if x):
        length = min(length, args.max_context - 1)
        lens = jnp.full((args.slots,), length, jnp.int32)

        def decode(pools):
            logits, greedy, pools, _ = prog.decode(
                params, pools, last, tables, lens, active)
            return greedy, pools

        ms = timed(decode)
        print(json.dumps({
            "program": "decode", "slots": args.slots, "context": length,
            "decode_attention": prog.decode_attention,
            "ms": round(ms, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
