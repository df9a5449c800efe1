#!/usr/bin/env python3
"""Time one training attention block alone on the chip in both forms the
flash kernels take it (``ops/flash_attention.py``): ``qkv_tiles`` (the
kernels read the fused projection as it lies and rotate in VMEM) against
``bhsd`` (split, ``rope``, transposes, the (B, H, S, D) kernels), forward and
backward under ``jax.checkpoint`` as the trainer's block remat runs it, and
hold the two to each other; and ``qkv_tiles`` again by the width of the row
sub-tiles its kernels walk a block on the causal diagonal in
(``flash_attention.CAUSAL_TILE``: the whole block beside 128 / 256 / 512),
the table the module's rule cites.

    chiprun -- python tools/flash_forms.py [--batch 64] [--seq 1024]
        [--heads 16] [--depth 64] [--reps 10] [--forms qkv_tiles,bhsd]
        [--causal-tiles whole,128,256,512]

One JSON row a form (a forward + backward's share of ``--reps`` calls
launched back to back, the median of three such runs; the count of
whole-tensor ``copy`` ops the compiled block holds; ``DTFT_FLASH_BLOCK_Q``
/ ``_K`` in the environment time another tiling), one a sub-tile width
(``form`` ``qkv_tiles``, ``causal_tile`` the width asked for and
``causal_share`` the share of a diagonal block's square it computes; the
``qkv_tiles`` row itself is the module's rule) and one for their agreement
with ``bhsd`` (o and the three gradients, max absolute difference over the
largest magnitude), to stdout and
``chiprun_out/flash_forms.jsonl``.  Exits non-zero without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--depth", type=int, default=64)
    p.add_argument("--reps", type=int, default=10)
    p.add_argument("--forms", default="qkv_tiles,bhsd")
    p.add_argument("--causal-tiles", default="whole,128,256,512")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from distributedtensorflow_tpu.models.gpt import (rope, rope_lane_tables,
                                                      rope_tables)
    from distributedtensorflow_tpu.ops import flash_attention as fa
    from distributedtensorflow_tpu.ops.flash_attention import (
        flash_attention, flash_attention_qkv)

    if jax.devices()[0].platform != "tpu":
        print("flash_forms: no TPU", file=sys.stderr)
        return 1
    b, s, h, d = args.batch, args.seq, args.heads, args.depth
    e = h * d
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (b, s, e), jnp.bfloat16)
    w_qkv = (jax.random.normal(keys[1], (e, 3 * e)) * e ** -0.5).astype(
        jnp.bfloat16)
    w_proj = (jax.random.normal(keys[2], (e, e)) * e ** -0.5).astype(
        jnp.bfloat16)
    g = jax.random.normal(keys[3], (b, s, e), jnp.bfloat16)
    positions = jnp.arange(s)[None]
    lane_tabs = rope_lane_tables(positions, d, 1e4)
    tabs = rope_tables(jnp.broadcast_to(positions, (b, s)), d, 1e4,
                       jnp.bfloat16)

    def tiles(qkv):
        return flash_attention_qkv(qkv, h, rope=lane_tabs, causal=True)

    def bhsd(qkv):
        q, k, v = (t.reshape(b, s, h, d) for t in jnp.split(qkv, 3, axis=-1))
        q, k = rope(q, None, 1e4, tabs), rope(k, None, 1e4, tabs)
        return flash_attention(q, k, v, causal=True).reshape(b, s, e)

    def block(attend):
        @jax.checkpoint
        def fwd(x, w_qkv, w_proj):
            return jnp.einsum("bse,ef->bsf", attend(
                jnp.einsum("bse,ef->bsf", x, w_qkv)), w_proj)

        def loss(x, w_qkv, w_proj):
            o = fwd(x, w_qkv, w_proj)
            return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32)), o

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/flash_forms.jsonl", "a")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    results = {}
    rule = fa.CAUSAL_TILE
    forms = [(name, name, rule) for name in args.forms.split(",")]
    if "qkv_tiles" in args.forms.split(",") and args.causal_tiles:
        forms += [("qkv_tiles", f"qkv_tiles/{t}", 0 if t == "whole" else
                   int(t)) for t in args.causal_tiles.split(",")]
    for form, name, width in forms:
        # read when the call is traced (the rule has no other knob)
        fa.CAUSAL_TILE = width
        fn = block({"qkv_tiles": tiles, "bhsd": bhsd}[form])
        t0 = time.perf_counter()
        compiled = fn.lower(x, w_qkv, w_proj).compile()
        compile_s = time.perf_counter() - t0
        whole = re.findall(
            rf"= \w+\[{b},{s},(?:{e}|{h},{d})\]\S* copy\(",
            compiled.as_text())
        walls = []
        for _ in range(3):  # a call's wall holds the host's launch too; a
            # run of them, launched back to back, is the device's time
            results[name] = jax.block_until_ready(fn(x, w_qkv, w_proj))
            t0 = time.perf_counter()
            for _ in range(args.reps):
                last = fn(x, w_qkv, w_proj)
            jax.block_until_ready(last)
            walls.append((time.perf_counter() - t0) / args.reps)
        took = fa.causal_tile(s, s, True) if form == "qkv_tiles" else None
        emit({"form": form, "batch": b, "seq": s, "heads": h, "depth": d,
              **({"causal_tile": took,
                  "causal_share": fa.causal_share(s, took)}
                 if form == "qkv_tiles" else {}),
              "blocks_env": [os.environ.get(f"DTFT_FLASH_BLOCK_{a}")
                             for a in "QK"],
              "fwd_bwd_ms": statistics.median(walls) * 1e3,
              "whole_tensor_copies": len(whole), "compile_s": compile_s,
              "device": jax.devices()[0].device_kind})

    def rel(a, c):
        a, c = a.astype(jnp.float32), c.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - c)) / jnp.max(jnp.abs(c)))

    fa.CAUSAL_TILE = rule
    if "bhsd" not in results:
        return 0
    (_, o_b), grads_b = results.pop("bhsd")
    for name, ((_, o_t), grads_t) in results.items():
        emit({"agreement": f"{name} against bhsd", "o": rel(o_t, o_b),
              **{f"d_{n}": rel(a, c) for n, a, c in zip(
                  ("x", "w_qkv", "w_proj"), grads_t, grads_b)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
