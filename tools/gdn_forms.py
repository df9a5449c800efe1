#!/usr/bin/env python3
"""Time the qwen3_next family's programs alone on the chip, and hold the
scalar-gate chunked scan and the in-place step to the recurrence there: one
layer's scan by form (``chunked``, the ``lax.scan`` recurrence ``plain``) and
chunk width, also at gates of -60 a token through the scalar body and through
the per-channel body (which must not survive them), one layer's decode step by
slots, one expert layer by tokens, a whole prefill chunk by context, a decode
iteration by slots and context.

    chiprun -- python tools/gdn_forms.py [--chunks 2048] [--forms chunked,plain]
        [--starts 0,4096,32768] [--slots 32,48] [--decode 13000]

No engine, no HTTP: the programs of ``serve/model.py:make_programs`` over
pools and state arrays of the cell's size, each call timed to
``block_until_ready`` (median of ``--reps``).  One JSON row a measurement;
the rows with ``"against": "recurrence"`` carry the largest absolute
difference of a form's outputs and state from :func:`ops.kda.kda_recurrent`
on the same device and inputs.  Exits non-zero without a TPU.
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
    p.add_argument("--config", default="qwen3_next_ep4")
    p.add_argument("--chunks", default="2048")
    p.add_argument("--forms", default="chunked,plain")
    p.add_argument("--starts", default="0,4096,32768")
    p.add_argument("--slots", default="32,48")
    p.add_argument("--decode", default="13000")
    p.add_argument("--experts", default="48,2048",
                   help="tokens of one expert layer timed alone")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=67584)
    p.add_argument("--kv-blocks", type=int, default=73728)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.models import qwen3_next
    from distributedtensorflow_tpu.ops import kda
    from distributedtensorflow_tpu.serve import kv_cache
    from distributedtensorflow_tpu.serve.model import (family_of,
                                                       make_programs)

    runtime.init_compile_cache()
    if not runtime.on_tpu():
        print("gdn_forms: no TPU", file=sys.stderr)
        return 1
    base = dataclasses.replace(getattr(models, args.config)(),
                               max_seq=args.max_context)
    rows = base.state_rows
    hk, h, dk, dv = rows.key_heads, rows.heads, rows.key_dim, rows.value_dim
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

    def inputs(t, strongest=1.6):
        """What a layer hands the rule: L2-normalised q and k of bf16-rounded
        draws, v, a gate in ``(-strongest, 0)`` and beta in (0, 1)."""
        def draw(*shape):
            return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16
                               ).astype(f32)

        def unit(x):
            return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)
        g = -jnp.asarray(rng.uniform(0.0, strongest, (t, h, 1)), f32)
        beta = jnp.asarray(rng.uniform(0.05, 0.95, (t, h)), f32)
        return (unit(draw(t, hk, dk)) * dk ** -0.5, unit(draw(t, hk, dk)),
                draw(t, h, dv), g, beta)

    def worst(a, b):
        return float(jnp.abs(a - b).max())

    # -- one layer's scan, by form, width and the gate's strength ----------
    formulation = kda.chunk_scan_formulation
    for chunk in ints(args.chunks):
        for strongest in (1.6, 60.0):
            xs = inputs(chunk, strongest)
            s0 = jnp.asarray(rng.standard_normal((h, dv, dk)) * 0.1, f32)
            valid = jnp.int32(chunk - 37)
            want = jax.jit(kda.kda_recurrent)(*xs, s0, valid)
            for form in (f for f in args.forms.split(",") if f):
                kda.chunk_scan_formulation = lambda *_, form=form, **__: form
                # a function of its own a form: jit would answer the second
                # form from the first one's trace
                one = jax.jit(lambda *a: kda.kda_chunk_scan(*a))
                ms, (o, s1), _ = timed(lambda _: (one(*xs, s0, valid), None))
                print(json.dumps({
                    "program": "gdn_scan_one_layer", "chunk": chunk,
                    "strongest_gate": -strongest, "chunk_scan": form,
                    "ms": round(ms, 3),
                    "us_per_token": round(1e3 * ms / chunk, 3),
                    "against": "recurrence",
                    "o_abs_err": worst(o[:chunk - 37], want[0][:chunk - 37]),
                    "o_abs_max": float(jnp.abs(want[0]).max()),
                    "state_abs_err": worst(s1, want[1]),
                    "state_abs_max": float(jnp.abs(want[1]).max())}),
                    flush=True)
            kda.chunk_scan_formulation = formulation
            # the per-channel body under the same gates, broadcast: sound
            # under KDA's bound of -5 a token, not at -60
            q, k = kda._share_heads(xs[0], xs[1], h)
            o, s1 = jax.jit(kda.kda_chunked)(
                q, k, xs[2], jnp.broadcast_to(xs[3], q.shape), xs[4], s0,
                valid)
            print(json.dumps({
                "program": "gdn_scan_one_layer", "chunk": chunk,
                "strongest_gate": -strongest, "control": "channel_body",
                "against": "recurrence", "finite": bool(
                    jnp.isfinite(o).all() & jnp.isfinite(s1).all()),
                "state_abs_err": worst(s1, want[1])}), flush=True)

    # -- one layer's step, by slots ---------------------------------------
    for slots in ints(args.slots):
        xs = inputs(slots, 60.0)
        pool = jnp.asarray(rng.standard_normal((2, slots, h, dv, dk)) * 0.1,
                           f32)
        want = jax.jit(jax.vmap(lambda q, k, v, g, b, st: kda.kda_recurrent(
            q[None], k[None], v[None], g[None], b[None], st)))(*xs, pool[1])
        for impl in ("pallas", "xla"):
            one = jax.jit(functools.partial(kda.kda_step, layer=1, impl=impl),
                          donate_argnums=(5,))
            o1, p1 = one(*xs, pool + 0.0)
            row = {"program": "gdn_step_one_layer", "slots": slots,
                   "step": kda.step_formulation(h, dk, dv, impl),
                   "against": "recurrence",
                   "o_abs_err": worst(o1, want[0][:, 0]),
                   "state_abs_err": worst(p1[1], want[1]),
                   "other_layer_untouched": bool((p1[0] == pool[0]).all())}
            del p1
            ms, _, after = timed(lambda pool: one(*xs, pool), pool + 0.0)
            row.update(ms=round(ms, 3), gb_per_s=round(
                2 * slots * h * dv * dk * 4 / ms / 1e6, 1))
            print(json.dumps(row), flush=True)
            del after
        del pool, want

    # -- the programs -----------------------------------------------------
    bs, cols = args.block_size, args.max_context // args.block_size
    params = family_of(base).init_params(base, jax.random.PRNGKey(0))
    jax.block_until_ready(params)

    for tokens in ints(args.experts):
        hidden = jnp.asarray(rng.standard_normal((tokens, base.hidden_size)),
                             base.dtype)
        layer = jax.jit(lambda p, x: qwen3_next._moe(p, x, base, None))
        ms, (_, counters), _ = timed(lambda _: (layer(
            params["h0"]["moe"], hidden), None))
        print(json.dumps({
            "program": "moe_one_layer", "tokens": tokens, "ms": round(ms, 3),
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
                "formulations": prog.formulations,
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
