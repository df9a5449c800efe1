#!/usr/bin/env python3
"""Time GLM-5's sparse latent attention alone on the chip, piece by piece and
against the dense formulation, then its two programs.

    chiprun -- python tools/sparse_forms.py
        [--contexts 2048,4096,8192,16384,32768]
        [--pieces 1] [--programs 1] [--decode 2000,15000,30000]

No engine, no HTTP.  One layer's pieces at a prefill chunk's shape (``--chunk``
queries of one slot whose chunk ends at each of ``--contexts``) and at a
decode step's (``--slots`` queries, each slot ``--decode`` tokens long):
``index_scores`` (kernel and plain loop), ``select_rows`` (``lax.top_k`` of
``index_topk``) and ``select_bias`` (the same set by the bisection kernel,
as a mask: on distinct scores and with a tie planted across every query's
cut, ``variant``), ``sparse_latent_attention`` (gather + kernel, gather + plain
products), the whole sparse form and the dense form (``LatentRows.chunk``:
every cached row, decompressed) over the same pool; then
``serve/model.py:make_programs``'s prefill chunk and decode iteration.  The
pools hold random values, so the selection is scattered as a random
indexer's is.  Each call timed to ``block_until_ready`` (median of
``--reps``).  One JSON row a measurement; ``PERF.md`` sections 4 and 6 have
the table this fills.  Exits non-zero without a TPU.
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
    p.add_argument("--config", default="glm5_ep16")
    p.add_argument("--chunk", type=int, default=1024)
    p.add_argument("--contexts", default="2048,4096,8192,16384,32768")
    p.add_argument("--decode", default="2000,15000,30000")
    p.add_argument("--pieces", type=int, default=1)
    p.add_argument("--programs", type=int, default=1)
    p.add_argument("--slots", type=int, default=24)
    p.add_argument("--kv-blocks", type=int, default=45056)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-context", type=int, default=33792)
    p.add_argument("--reps", type=int, default=3)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.ops import attention
    from distributedtensorflow_tpu.serve import kv_cache
    from distributedtensorflow_tpu.serve.model import (family_of,
                                                       make_programs)

    runtime.init_compile_cache()
    if not runtime.on_tpu():
        print("sparse_forms: no TPU", file=sys.stderr)
        return 1
    cfg = dataclasses.replace(getattr(models, args.config)(),
                              max_seq=args.max_context)
    form, dt = cfg.cache_rows, cfg.dtype
    bs, cols = args.block_size, args.max_context // args.block_size
    t, slots, k = args.chunk, args.slots, form.topk
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)

    def fill(i, shape):
        return jax.jit(lambda: jax.random.normal(
            jax.random.fold_in(key, i), shape, dt))()

    pools = {"full": tuple(
        fill(i, kv_cache.pool_shape(cfg.num_layers, args.kv_blocks, bs, w))
        for i, w in enumerate(form.widths))}
    jax.block_until_ready(pools)

    def timed(call, *a):
        walls = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            jax.block_until_ready(call(*a))
            walls.append(time.perf_counter() - t0)
        return round(1e3 * statistics.median(walls[1:]), 3)

    def row(**kw):
        print(json.dumps(kw), flush=True)

    def draw(*shape, dtype=dt):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def tied(scores, counts):
        """``scores`` with each row's ``k``-th largest candidate value also
        at the two ranks above and the two below it: a tie across the cut
        (on the device before anything is timed)."""
        x, n = np.array(scores), np.asarray(counts)[:, None]
        live = np.arange(x.shape[1])[None, :] < n
        near = np.argsort(np.where(live, -x, np.inf), axis=1,
                          kind="stable")[:, k - 3:k + 2]
        np.put_along_axis(x, near, np.where(
            n > k + 2, np.take_along_axis(x, near[:, 2:3], 1),
            np.take_along_axis(x, near, 1)), 1)
        return jax.block_until_ready(jnp.asarray(x))

    contexts = [int(c) for c in args.contexts.split(",")]
    decodes = [int(c) for c in args.decode.split(",") if c]
    table_row = jnp.asarray(rng.permutation(cols), jnp.int32)
    per_slot = args.kv_blocks // slots
    tables = jnp.asarray(
        np.arange(slots)[:, None] * per_slot
        + np.minimum(np.arange(cols), per_slot - 1)[None, :], jnp.int32)
    h = cfg.num_heads
    hi, di = cfg.index_heads, cfg.index_head_dim
    rank, width = form.rank, form.widths[0]
    s_all = cols * bs

    if args.pieces:
        pool, index_pool = pools["full"]
        # -- a prefill chunk's shape ------------------------------------
        q_index, w_index = draw(1, t, hi, di), draw(1, t, hi,
                                                    dtype=jnp.float32)
        keys = form._keys(index_pool, 1, table_row, bs)[None]
        for impl in ("pallas", "xla"):
            f = jax.jit(lambda q, w, kk, n, impl=impl: attention.index_scores(
                q, w, kk, n, impl=impl))
            for end in contexts:
                row(piece="index_scores", shape="chunk", impl=impl,
                    context=end, ms=timed(
                        f, q_index, w_index, keys,
                        jnp.asarray([end], jnp.int32)))
        scores = draw(t, s_all, dtype=jnp.float32)
        sel = jax.jit(lambda s, n: attention.select_rows(s, n, k))
        for end in contexts:
            counts = end - t + 1 + jnp.arange(t, dtype=jnp.int32)
            row(piece="select_rows", shape="chunk", context=end,
                ms=timed(sel, scores, counts))
        bias = jax.jit(lambda s, n: attention.select_bias(
            s, n, k, impl=cfg.kernel_impl))
        for end in contexts:
            counts = end - t + 1 + jnp.arange(t, dtype=jnp.int32)
            for variant, x in (("distinct", scores),
                               ("tie", tied(scores, counts))):
                row(piece="select_bias", shape="chunk", variant=variant,
                    name=attention.select_formulation(t, cfg.kernel_impl),
                    context=end, ms=timed(bias, x, counts))
        q_abs = draw(t, h, width)
        for impl in ("pallas", "xla"):
            f = jax.jit(lambda q, pl_, r, c, impl=impl:
                        attention.sparse_latent_attention(
                            q, pl_, r, c, layer=1, rank=rank,
                            scale=form.scale, impl=impl))
            for end in contexts:
                rows = jnp.asarray(rng.integers(0, end, (t, k)), jnp.int32)
                row(piece="sparse_latent_attention", shape="chunk",
                    impl=impl, context=end, ms=timed(
                        f, q_abs, pool, rows, jnp.full((t,), k, jnp.int32)))
        gather = jax.jit(lambda pl_, r: jax.lax.map(
            lambda rr: pl_[1, rr].astype(jnp.float32).sum(1),
            r.reshape(-1, min(t, attention.SPARSE_QUERIES), k)))
        rows = jnp.asarray(rng.integers(0, contexts[-1], (t, k)), jnp.int32)
        row(piece="gather_rows_only", shape="chunk", context=contexts[-1],
            ms=timed(gather, pool, rows))
        # the whole form of one layer, sparse against dense
        q = (draw(t, h, cfg.qk_nope_head_dim), draw(t, h, form.rope_dim))
        w_uk = draw(rank, h, cfg.qk_nope_head_dim)
        w_uv = draw(rank, h, cfg.v_head_dim)
        kw = dict(layer=1, block_size=bs, impl=cfg.kernel_impl, w_uk=w_uk,
                  w_uv=w_uv)
        dense_form = attention.LatentRows(
            rank=rank, rope_dim=form.rope_dim, scale=form.scale)
        sparse = jax.jit(lambda pools, start: form.chunk(
            (*q, q_index[0], w_index[0]), start, pools, table_row, **kw))
        dense = jax.jit(lambda pools, start: dense_form.chunk(
            q, start, pools[:1], table_row, **kw))
        for end in contexts:
            start = jnp.int32(end - t)
            row(piece="chunk_form_one_layer", form="sparse",
                name=form.chunk_formulation(bs, t, cfg.kernel_impl),
                context=end, ms=timed(sparse, pools["full"], start))
            row(piece="chunk_form_one_layer", form="dense",
                name=dense_form.chunk_formulation(bs, t, cfg.kernel_impl),
                context=end, ms=timed(dense, pools["full"], start))
        # -- a decode step's shape --------------------------------------
        qd = (draw(slots, h, cfg.qk_nope_head_dim),
              draw(slots, h, form.rope_dim))
        qi, wi = draw(slots, hi, di), draw(slots, hi, dtype=jnp.float32)
        sparse = jax.jit(lambda pools, lens: form.decode(
            (*qd, qi, wi), pools, tables, lens, **kw))
        dense = jax.jit(lambda pools, lens: dense_form.decode(
            qd, pools[:1], tables, lens, **kw))
        keys = jax.jit(lambda ip: form._keys(ip, 1, tables, bs))(index_pool)
        step_scores = jax.jit(lambda q, w, kk, n: attention.index_scores(
            q, w, kk, n, impl="pallas"))
        sel = jax.jit(lambda s, n: attention.select_rows(s, n, k))
        step_attn = jax.jit(lambda q, pl_, r, c:
                            attention.sparse_latent_attention(
                                q, pl_, r, c, layer=1, rank=rank,
                                scale=form.scale, impl="pallas"))
        for n in decodes:
            n = min(n, per_slot * bs - 1)
            lens = jnp.full((slots,), n, jnp.int32)
            row(piece="index_scores", shape="step", context=n, ms=timed(
                step_scores, qi[:, None], wi[:, None], keys, lens))
            row(piece="select_rows", shape="step", context=n, ms=timed(
                sel, draw(slots, s_all, dtype=jnp.float32), lens))
            rows = jnp.asarray(rng.integers(0, n, (slots, k)), jnp.int32)
            row(piece="sparse_latent_attention", shape="step", context=n,
                ms=timed(step_attn, draw(slots, h, width), pool, rows,
                         jnp.full((slots,), k, jnp.int32)))
            row(piece="decode_form_one_layer", form="sparse",
                name=form.decode_formulation(bs, cfg.kernel_impl),
                context=n, ms=timed(sparse, pools["full"], lens))
            row(piece="decode_form_one_layer", form="dense",
                name=dense_form.decode_formulation(bs, cfg.kernel_impl),
                context=n, ms=timed(dense, pools["full"], lens))
        del keys

    if not args.programs:
        return 0
    layers = {"full": tuple(range(cfg.num_layers))}
    params = family_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    prog = make_programs(cfg, chunk=t, block_size=bs, layers=layers)
    tokens = rng.integers(0, cfg.vocab_size, t)

    def timed_pools(call):
        nonlocal pools
        walls = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            out, pools = call(pools)
            jax.block_until_ready(out)
            walls.append(time.perf_counter() - t0)
        return round(1e3 * statistics.median(walls[1:]), 3)

    for end in [t] + contexts:
        ms = timed_pools(lambda pools: prog.prefill(
            params, pools, tokens, end - t, {"full": table_row}, t))
        row(program="prefill_chunk", chunk=t, context=end,
            chunk_attention=prog.chunk_attention, ms=ms)
    last = jnp.asarray(rng.integers(0, cfg.vocab_size, slots), jnp.int32)
    active = jnp.ones((slots,), bool)
    for n in decodes:
        n = min(n, per_slot * bs - 1)
        lens = jnp.full((slots,), n, jnp.int32)

        def decode(pools):
            logits, greedy, pools, routed = prog.decode(
                params, pools, last, {"full": tables}, lens, active)
            return (greedy, routed), pools

        row(program="decode", slots=slots, context=n,
            decode_attention=prog.decode_attention, ms=timed_pools(decode))
    stats = jax.devices()[0].memory_stats() or {}
    row(memory_peak_bytes=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
