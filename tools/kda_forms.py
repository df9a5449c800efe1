#!/usr/bin/env python3
"""Time the ling family's programs alone on the chip, and hold the chunked
scan and the step kernel to the recurrence there: one layer's scan by form
(``chunked``, the ``lax.scan`` recurrence ``plain``) and chunk width, one
layer's decode step by form and slots, a whole prefill chunk by context (and,
``--program-forms``, by the scan's form inside it), a decode iteration by
slots and context.

    chiprun -- python tools/kda_forms.py [--chunks 1024,2048]
        [--forms chunked,plain] [--starts 0,8192]
        [--slots 128,256] [--decode 2000,8000]

No engine, no HTTP: the programs of ``serve/model.py:make_programs`` over
pools and state arrays of the cell's size, each call timed to
``block_until_ready`` (median of ``--reps``).  One JSON row a measurement;
the rows with ``"against": "recurrence"`` carry the largest absolute
difference of a kernel's outputs and state from :func:`ops.kda.kda_recurrent`
on the same device and inputs (``g`` drawn down to the lower bound), and two
rows with ``"control"`` the same difference of a scan whose state is handed
on in bfloat16 a chunk and of the rule without its correction: what a
tolerance between the two kinds of row refuses.  Exits non-zero without a
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
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="ling3_flash_ep8")
    p.add_argument("--chunks", default="1024,2048")
    p.add_argument("--forms", default="chunked,plain")
    p.add_argument("--starts", default="0,8192")
    p.add_argument("--program-forms", default="auto",
                   help="the scan's form inside the whole prefill chunk: "
                        "auto (what the program takes) or one forced")
    p.add_argument("--slots", default="128,256")
    p.add_argument("--decode", default="2000,8000")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=20480)
    p.add_argument("--kv-blocks", type=int, default=0)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import kda_controls
    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.ops import kda
    from distributedtensorflow_tpu.serve import kv_cache
    from distributedtensorflow_tpu.serve.model import (family_of,
                                                       make_programs)

    runtime.init_compile_cache()
    if not runtime.on_tpu():
        print("kda_forms: no TPU", file=sys.stderr)
        return 1
    base = dataclasses.replace(getattr(models, args.config)(),
                               max_seq=args.max_context)
    rows = base.state_rows
    h, dk, dv = rows.heads, rows.key_dim, rows.value_dim
    rng = np.random.default_rng(0)

    def timed(call, state=None):
        walls = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            out, state = call(state)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        return 1e3 * statistics.median(walls[1:]), out, state

    def inputs(t):
        f32 = jnp.float32
        q, k, v = (jnp.asarray(rng.standard_normal((t, h, dk)), f32)
                   for _ in range(3))
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        x = jnp.asarray(rng.standard_normal((t, h, dk)) * 4.0, f32)
        g = base.kda_lower_bound * jax.nn.sigmoid(x)     # to the bound
        beta = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((t, h)), f32))
        return q, k, v, g, beta

    def worst(a, b):
        return float(jnp.abs(a - b).max())

    # -- one layer's scan, by form and width ------------------------------
    formulation = kda.chunk_scan_formulation
    for chunk in (int(x) for x in args.chunks.split(",") if x):
        xs = inputs(chunk)
        s0 = jnp.asarray(rng.standard_normal((h, dv, dk)) * 0.1, jnp.float32)
        valid = jnp.int32(chunk - 37)
        want = jax.jit(kda.kda_recurrent)(*xs, s0, valid)
        for form in (f for f in args.forms.split(",") if f):
            kda.chunk_scan_formulation = lambda *_, form=form, **__: form
            one = jax.jit(kda.kda_chunk_scan)
            ms, (o, s1), _ = timed(lambda _: (one(*xs, s0, valid), None))
            print(json.dumps({
                "program": "kda_scan_one_layer", "chunk": chunk,
                "chunk_scan": form, "ms": round(ms, 3),
                "us_per_token": round(1e3 * ms / chunk, 3),
                "against": "recurrence",
                "o_abs_err": worst(o[:chunk - 37], want[0][:chunk - 37]),
                "o_abs_max": float(jnp.abs(want[0]).max()),
                "state_abs_err": worst(s1, want[1]),
                "state_abs_max": float(jnp.abs(want[1]).max())}), flush=True)
    kda.chunk_scan_formulation = formulation

    # -- what the tolerance of the rows above refuses -----------------------
    # the same chunk with the state handed on in bfloat16 a chunk of 64, and
    # with the delta rule's correction left out: both must read orders of
    # magnitude over the kernels' own difference from the recurrence
    xs = inputs(1024)
    s0 = jnp.asarray(rng.standard_normal((h, dv, dk)) * 0.1, jnp.float32)
    want = jax.jit(kda.kda_recurrent)(*xs, s0)

    def rounded_every_chunk(state):
        outs = []
        for c0 in range(0, 1024, kda.CHUNK):
            o, state = kda.kda_chunk_scan(
                *(x[c0:c0 + kda.CHUNK] for x in xs), state, None)
            # not a convert pair: the TPU compiler drops one as excess
            # precision
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
            outs.append(o)
        return jnp.concatenate(outs), state

    def no_correction(state):       # tools/kda_controls.py's, one form
        return kda_controls.dropped_delta()[0](*xs, state, None)

    for control, fn in (("bf16_state", rounded_every_chunk),
                        ("dropped_delta", no_correction)):
        o, s1 = jax.jit(fn)(s0)
        print(json.dumps({
            "program": "kda_scan_one_layer", "chunk": 1024,
            "control": control, "against": "recurrence",
            "o_abs_err": worst(o, want[0]),
            "state_abs_err": worst(s1, want[1])}), flush=True)

    # -- one layer's step, by form and slots ------------------------------
    for slots in (int(x) for x in args.slots.split(",") if x):
        xs = inputs(slots)
        pool = jnp.asarray(rng.standard_normal((2, slots, h, dv, dk)) * 0.1,
                           jnp.float32)
        for impl in ("pallas", "xla"):
            one = jax.jit(functools.partial(kda.kda_step, layer=1, impl=impl),
                          donate_argnums=(5,))
            ms, o, after = timed(lambda pool: one(*xs, pool), pool + 0.0)
            row = {"program": "kda_step_one_layer", "slots": slots,
                   "step": kda.step_formulation(h, dk, dv, impl),
                   "ms": round(ms, 3),
                   "gb_per_s": round(2 * slots * h * dv * dk * 4 / ms / 1e6,
                                     1)}
            if impl == "pallas":
                o1, p1 = one(*xs, pool + 0.0)
                o2, p2 = jax.jit(functools.partial(
                    kda.kda_step, layer=1, impl="xla"))(*xs, pool)
                row.update(against="recurrence", o_abs_err=worst(o1, o2),
                           state_abs_err=worst(p1, p2),
                           other_layer_untouched=bool(
                               (p1[0] == pool[0]).all()))
            print(json.dumps(row), flush=True)
            del after
        del pool

    # -- the programs -----------------------------------------------------
    bs, cols = args.block_size, args.max_context // args.block_size
    params = family_of(base).init_params(base, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    for slots in (int(x) for x in args.slots.split(",") if x):
        blocks = args.kv_blocks or slots * cols
        cache = kv_cache.make_grouped_cache(
            base, max_slots=slots, block_size=bs,
            max_context=args.max_context, num_blocks={"full": blocks},
            write_ahead=2048)
        layers, pools = cache.layers, cache.pools()
        table_row = {"full": jnp.arange(cols, dtype=jnp.int32),
                     "state": jnp.zeros((1,), jnp.int32)}
        if slots == int(args.slots.split(",")[0]):
            for chunk in (int(x) for x in args.chunks.split(",") if x):
                tokens = rng.integers(0, base.vocab_size, chunk)
                for form in args.program_forms.split(","):
                    if form != "auto":
                        kda.chunk_scan_formulation = \
                            lambda *_, form=form, **__: form
                    prog = make_programs(base, chunk=chunk, block_size=bs,
                                         layers=layers)
                    for start in (int(s) for s in args.starts.split(",")
                                  if s):
                        ms, _, pools = timed(lambda pools: prog.prefill(
                            params, pools, tokens, start, table_row, chunk),
                            pools)
                        print(json.dumps({
                            "program": "prefill_chunk", "chunk": chunk,
                            "chunk_scan": prog.chunk_scan,
                            "chunk_attention": prog.chunk_attention,
                            "start": start, "ms": round(ms, 3),
                            "us_per_token": round(1e3 * ms / chunk, 2)}),
                            flush=True)
                    kda.chunk_scan_formulation = formulation
        prog = make_programs(base, chunk=2048, block_size=bs, layers=layers)
        per = max(1, min(cols, blocks // slots))
        tables = {"state": jnp.asarray(cache.groups["state"].block_tables),
                  "full": jnp.asarray(
                      np.arange(slots)[:, None] * per
                      + np.minimum(np.arange(cols), per - 1)[None, :],
                      jnp.int32)}
        last = jnp.asarray(rng.integers(0, base.vocab_size, slots), jnp.int32)
        active = jnp.ones((slots,), bool)
        for length in (int(x) for x in args.decode.split(",") if x):
            length = min(length, per * bs - 1)
            lens = jnp.full((slots,), length, jnp.int32)

            def decode(pools):
                _, greedy, pools, _ = prog.decode(
                    params, pools, last, tables, lens, active)
                return greedy, pools

            ms, _, pools = timed(decode, pools)
            print(json.dumps({
                "program": "decode", "slots": slots, "context": length,
                "decode_attention": prog.decode_attention,
                "state_step": base.state_rows.step_formulation(
                    base.kernel_impl),
                "ms": round(ms, 3),
                "tokens_per_s": round(1e3 * slots / ms)}), flush=True)
        del pools, cache
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"program": "memory",
                      "peak_gb": round(stats.get("peak_bytes_in_use", 0)
                                       / 1e9, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
