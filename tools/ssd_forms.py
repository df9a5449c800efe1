#!/usr/bin/env python3
"""Time the nemotron_h family's programs alone on the chip, and hold the
chunked scan and the in-place step to the recurrence there: one layer's scan by
form (``chunked``, the ``lax.scan`` recurrence ``plain``) and chunk width, one
layer's decode step by slots, one latent expert layer by tokens, a whole
prefill chunk by context, a decode iteration by slots and context.

    chiprun -- python tools/ssd_forms.py [--chunks 2048] [--forms chunked,plain]
        [--starts 0,8192] [--slots 96,128,160] [--decode 3300]

No engine, no HTTP: the programs of ``serve/model.py:make_programs`` over
pools and state arrays of the cell's size, each call timed to
``block_until_ready`` (median of ``--reps``).  One JSON row a measurement;
the rows with ``"against": "recurrence"`` carry the largest absolute
difference of a form's outputs and state from :func:`ops.ssd.ssd_recurrent`
on the same device and inputs, and the rows with ``"control"`` the same
difference of a scan whose state is handed on in bfloat16 a chunk and of one
without its decay: what a tolerance between the two kinds of row refuses.
Exits non-zero without a TPU.
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


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="nemotron3_super_ep4")
    p.add_argument("--chunks", default="2048")
    p.add_argument("--forms", default="chunked,plain")
    p.add_argument("--starts", default="0,8192")
    p.add_argument("--slots", default="96,128,160")
    p.add_argument("--decode", default="3300")
    p.add_argument("--experts", default="128,2048",
                   help="tokens of one latent expert layer timed alone")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=18432)
    p.add_argument("--kv-blocks", type=int, default=49152)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.models import nemotron_h
    from distributedtensorflow_tpu.ops import ssd
    from distributedtensorflow_tpu.serve import kv_cache
    from distributedtensorflow_tpu.serve.model import (family_of,
                                                       make_programs)

    runtime.init_compile_cache()
    if not runtime.on_tpu():
        print("ssd_forms: no TPU", file=sys.stderr)
        return 1
    base = dataclasses.replace(getattr(models, args.config)(),
                               max_seq=args.max_context)
    rows = base.state_rows
    h, dim, g, n = rows.heads, rows.head_dim, rows.groups, rows.d_state
    rng = np.random.default_rng(0)
    f32 = jnp.float32

    def ints(text):
        return [int(x) for x in text.split(",") if x]

    def timed(call, state=None):
        walls = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            out, state = call(state)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        return 1e3 * statistics.median(walls[1:]), out, state

    def inputs(t):
        """What a layer hands the scan: bf16-rounded x, B, C, ``dt`` of 0.001
        to 0.3 and ``A`` of 1 to 16."""
        def draw(*shape):
            return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                            (t, h))), f32)
        a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), f32)
        d = jnp.asarray(rng.uniform(0.9, 1.1, (h,)), f32)
        return draw(t, h, dim), dt, a, draw(t, g, n), draw(t, g, n), d

    def worst(a, b):
        return float(jnp.abs(a - b).max())

    # -- one layer's scan, by form and width ------------------------------
    formulation = ssd.chunk_scan_formulation
    for chunk in ints(args.chunks):
        xs = inputs(chunk)
        s0 = jnp.asarray(rng.standard_normal((h, dim, n)) * 0.1, f32)
        valid = jnp.int32(chunk - 37)
        want = jax.jit(ssd.ssd_recurrent)(*xs, s0, valid)
        for form in (f for f in args.forms.split(",") if f):
            ssd.chunk_scan_formulation = lambda *_, form=form, **__: form
            one = jax.jit(ssd.ssd_chunk_scan)
            ms, (y, s1), _ = timed(lambda _: (one(*xs, s0, valid), None))
            print(json.dumps({
                "program": "ssd_scan_one_layer", "chunk": chunk,
                "chunk_scan": form, "ms": round(ms, 3),
                "us_per_token": round(1e3 * ms / chunk, 3),
                "against": "recurrence",
                "y_abs_err": worst(y[:chunk - 37], want[0][:chunk - 37]),
                "y_abs_max": float(jnp.abs(want[0]).max()),
                "state_abs_err": worst(s1, want[1]),
                "state_abs_max": float(jnp.abs(want[1]).max())}), flush=True)
        ssd.chunk_scan_formulation = formulation

        # what the tolerance of the rows above refuses: the state handed on
        # in bfloat16 a chunk of 128, and the scan without its decay
        def rounded_every_chunk(state):
            outs = []
            for c0 in range(0, chunk, ssd.CHUNK):
                x, dt, a, b, c, d = xs
                y, state = ssd.ssd_chunked(
                    x[c0:c0 + ssd.CHUNK], dt[c0:c0 + ssd.CHUNK], a,
                    b[c0:c0 + ssd.CHUNK], c[c0:c0 + ssd.CHUNK], d, state)
                # not a convert pair: the TPU compiler drops one as excess
                # precision
                state = jax.lax.reduce_precision(state, exponent_bits=8,
                                                 mantissa_bits=7)
                outs.append(y)
            return jnp.concatenate(outs), state

        def no_decay(state):
            x, dt, a, b, c, d = xs
            return ssd.ssd_chunked(x, dt, 0.0 * a, b, c, d, state)

        want = jax.jit(ssd.ssd_recurrent)(*xs, s0)
        for control, fn in (("bf16_state", rounded_every_chunk),
                            ("dropped_decay", no_decay)):
            y, s1 = jax.jit(fn)(s0)
            print(json.dumps({
                "program": "ssd_scan_one_layer", "chunk": chunk,
                "control": control, "against": "recurrence",
                "y_abs_err": worst(y, want[0]),
                "state_abs_err": worst(s1, want[1]),
                "state_abs_max": float(jnp.abs(want[1]).max())}), flush=True)

    # -- one layer's step, by slots ---------------------------------------
    for slots in ints(args.slots):
        xs = inputs(slots)
        pool = jnp.asarray(rng.standard_normal((2, slots, h, dim, n)) * 0.1,
                           f32)
        want = jax.jit(jax.vmap(
            lambda x, dt, b, c, st: ssd.ssd_recurrent(
                x[None], dt[None], xs[2], b[None], c[None], xs[5], st),
            in_axes=(0, 0, 0, 0, 0)))(xs[0], xs[1], xs[3], xs[4], pool[1])
        for impl in ("pallas", "xla"):
            one = jax.jit(functools.partial(ssd.ssd_step, layer=1, impl=impl),
                          donate_argnums=(6,))
            y1, p1 = one(*xs, pool + 0.0)
            row = {"program": "ssd_step_one_layer", "slots": slots,
                   "step": ssd.step_formulation(h, dim, g, n, impl),
                   "against": "recurrence",
                   "y_abs_err": worst(y1, want[0][:, 0]),
                   "state_abs_err": worst(p1[1], want[1]),
                   "other_layer_untouched": bool((p1[0] == pool[0]).all())}
            del p1
            ms, _, after = timed(lambda pool: one(*xs, pool), pool + 0.0)
            row.update(ms=round(ms, 3), gb_per_s=round(
                2 * slots * h * dim * n * 4 / ms / 1e6, 1))
            print(json.dumps(row), flush=True)
            del after
        del pool, want

    # -- the programs -----------------------------------------------------
    bs, cols = args.block_size, args.max_context // args.block_size
    params = family_of(base).init_params(base, jax.random.PRNGKey(0))
    jax.block_until_ready(params)

    # one latent expert layer alone, by tokens
    first = next(i for i, kind in enumerate(base.pattern) if kind == "E")
    for tokens in ints(args.experts):
        hidden = jnp.asarray(rng.standard_normal((tokens, base.hidden_size)),
                             base.dtype)
        layer = jax.jit(lambda p, x: nemotron_h._latent_moe(
            p, x, base, None))
        ms, (_, counters), _ = timed(lambda _: (layer(
            params[f"h{first}"]["moe"], hidden), None))
        print(json.dumps({
            "program": "latent_moe_one_layer", "tokens": tokens,
            "ms": round(ms, 3),
            **{k: int(v) for k, v in counters.items()}}), flush=True)

    for slots in ints(args.slots):
        cache = kv_cache.make_grouped_cache(
            base, max_slots=slots, block_size=bs,
            max_context=args.max_context,
            num_blocks={"full": args.kv_blocks}, write_ahead=2048)
        layers, pools = cache.layers, cache.pools()
        table_row = {"full": jnp.arange(cols, dtype=jnp.int32),
                     "state": jnp.zeros((1,), jnp.int32)}
        if slots == ints(args.slots)[0]:
            for chunk in ints(args.chunks):
                tokens = rng.integers(0, base.vocab_size, chunk)
                prog = make_programs(base, chunk=chunk, block_size=bs,
                                     layers=layers)
                for start in ints(args.starts):
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
        prog = make_programs(base, chunk=2048, block_size=bs, layers=layers)
        per = max(1, min(cols, args.kv_blocks // slots))
        tables = {"state": jnp.asarray(cache.groups["state"].block_tables),
                  "full": jnp.asarray(
                      np.arange(slots)[:, None] * per
                      + np.minimum(np.arange(cols), per - 1)[None, :],
                      jnp.int32)}
        last = jnp.asarray(rng.integers(0, base.vocab_size, slots), jnp.int32)
        active = jnp.ones((slots,), bool)
        for length in ints(args.decode):
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
