#!/usr/bin/env python3
"""What a slot nobody holds costs a decode kernel, on the chip: one layer's
decode attention alone (``KVRows.decode`` through ``paged_attn``,
``LatentRows.decode`` through ``paged_latent_attn``) at three cells' shapes —
Ouro's (16 slots, ~7 live of ~410 rows, MHA 16 x 128), GPT-2 medium's (32
slots, 3 live, heads of 64), heads of 128 at GQA 4 (64 slots, 39 live) and
joyai's latent rows (32 slots, half or all live at 9,000 rows) — with the idle
slots handed a length of 0 (what ``serve/model.py:_attend_lens`` sends) and of
1 (a whole trip over a row of the scratch block), and with every slot live.

    chiprun -- python tools/empty_slot_forms.py

``CALLS`` calls are chained inside ONE jit (each call's query takes a
thousandth of the call before's output), so a timing carries one dispatch, not
one a call (a launch costs the host ~0.2 ms, a call 30-130 us).  One JSON row
a case: us a call at either length, the us saved an empty slot, and whether,
on the chip, the live slots' rows of the two lengths are equal bit for bit and
the empty ones zeros.  ``PERF.md`` section 6 (PR 62) has the table this
fills.  ``--tiny`` rehearses the plumbing here on the CPU, the kernels
interpreted.  Exits non-zero without a TPU otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BS = 16
#: calls chained in one timed jit (the latent cases: 16), and timings a median
CALLS, LATENT_CALLS, REPS = 96, 16, 7


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.ops import attention
    from distributedtensorflow_tpu.runtime import on_tpu

    if not (on_tpu() or args.tiny):
        print("empty_slot_forms: no TPU (--tiny rehearses)", file=sys.stderr)
        return 1
    impl = "pallas" if args.tiny else "auto"
    calls = 2 if args.tiny else CALLS

    def scattered(rng, lens, cols):
        """Tables of ``cols`` columns over a pool of just the blocks the
        slots hold (an idle slot one: the old length read it), scattered."""
        need = -(-np.maximum(lens, 1) // BS)
        nb = int(need.sum()) + 8
        tables = np.full((len(lens), cols), nb, np.int32)
        perm = rng.permutation(nb)
        for i, end in enumerate(np.cumsum(need)):
            tables[i, :need[i]] = perm[end - need[i]:end]
        return jnp.asarray(tables), nb

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        if not on_tpu():        # a CPU timing is no device number
            return None
        walls = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            walls.append((time.perf_counter() - t0) / calls)
        return round(1e6 * statistics.median(walls), 2)

    def report(name, lens, decode, q, layers, width):
        """``decode(q, lens, layer)`` chained ``calls`` times, the idle
        slots at a length of 0 and of 1."""
        live = lens > 0

        @jax.jit
        def chain(q, lens):
            out = jnp.zeros((*q.shape[:-1], width), q.dtype)
            for i in range(calls):
                out = decode(q + out[..., :q.shape[-1]] * 1e-3, lens,
                             i % layers)
            return out

        one = jax.jit(lambda q, lens: decode(q, lens, layers - 1))
        row = {"case": name, "slots": len(lens), "live": int(live.sum()),
               "rows": int(lens.sum())}
        outs = {}
        for idle in (0, 1):
            at = jnp.asarray(np.where(live, lens, idle), jnp.int32)
            row[f"us_a_call_idle_len_{idle}"] = timed(chain, q, at)
            outs[idle] = np.asarray(one(q, at).astype(jnp.float32))
        if on_tpu():
            row["saved_us_an_empty_slot"] = round(
                (row["us_a_call_idle_len_1"] - row["us_a_call_idle_len_0"])
                / max(int((~live).sum()), 1), 3)
        row["live_rows_bit_for_bit"] = bool(
            np.array_equal(outs[0][live], outs[1][live]))
        row["empty_rows_zero"] = bool((outs[0][~live] == 0).all())
        row["finite"] = bool(np.isfinite(outs[0]).all())
        print(json.dumps(row), flush=True)

    def lens_of(rng, slots, live_lens):
        lens = np.zeros(slots, np.int64)
        lens[rng.permutation(slots)[:len(live_lens)]] = live_lens
        return lens

    def kv_case(name, slots, live_lens, heads, kv_heads, d):
        rng = np.random.default_rng(0)
        lens = lens_of(rng, slots, live_lens)
        tables, nb = scattered(rng, lens, 96)
        form = attention.KVRows(heads, kv_heads, d)
        assert form.decode_formulation(BS, impl) == "paged_attn"
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        pools = tuple(jax.random.normal(
            k, (4, (nb + 1) * BS, kv_heads * d), jnp.bfloat16)
            for k in keys[:2])
        q = jax.random.normal(keys[2], (slots, heads, d), jnp.bfloat16)
        report(name, lens, lambda q, lens, layer: form.decode(
            q, pools, tables, lens, layer=layer, block_size=BS, impl=impl),
            q, 4, d)

    def latent_case(name, slots, live_lens):
        cfg = models.joyai.joyai_llm_flash()
        form, h = cfg.cache_rows, cfg.num_heads
        rng = np.random.default_rng(0)
        lens = lens_of(rng, slots, live_lens)
        tables, nb = scattered(rng, lens, 1024)
        assert form.decode_formulation(BS, impl) == "paged_latent_attn"
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        pool = jax.random.normal(
            keys[0], (2, (nb + 1) * BS, form.widths[0]), jnp.bfloat16)
        pool = pool.at[..., form.values[0]:].set(0)
        q_nope, q_rope, w_uk, w_uv = (
            jax.random.normal(k, shape, jnp.bfloat16) for k, shape in zip(
                keys[1:], ((slots, h, cfg.qk_nope_head_dim),
                           (slots, h, form.rope_dim),
                           (form.rank, h, cfg.qk_nope_head_dim),
                           (form.rank, h, cfg.v_head_dim))))
        report(name, lens, lambda q, lens, layer: form.decode(
            (q, q_rope), (pool,), tables, lens, layer=layer, block_size=BS,
            impl=impl, w_uk=w_uk, w_uv=w_uv), q_nope, 2, cfg.v_head_dim)

    print(json.dumps({"device": str(jax.devices()[0])}))
    if args.tiny:
        kv_case("tiny", 4, [130, 20], 4, 4, 128)
        kv_case("tiny64", 4, [130, 20], 4, 4, 64)
        latent_case("tiny_latent", 4, [600, 20])
        return 0
    kv_case("ouro", 16, [300, 350, 380, 410, 440, 470, 520], 16, 16, 128)
    kv_case("ouro_full", 16, [410] * 16, 16, 16, 128)
    kv_case("gpt2m", 32, [200, 330, 450], 16, 16, 64)
    kv_case("gpt2m_full", 32, [330] * 32, 16, 16, 64)
    kv_case("gqa128", 64, list(range(300, 300 + 39 * 20, 20)), 8, 2, 128)
    calls = LATENT_CALLS
    latent_case("joyai_half", 32, [9000] * 16)
    latent_case("joyai_full", 32, [9000] * 32)
    return 0


if __name__ == "__main__":
    sys.exit(main())
