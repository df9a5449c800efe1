"""Pre-checks of the Pallas kernels for the TPU that need no chip.

Interpret mode (what every other kernel test runs) accepts programs the
real compiler refuses, and chip time is budgeted.  Two cheaper gates sit
in between:

1. ``jax.export`` for ``platforms=["tpu"]`` with ``interpret=False`` runs
   the JAX-side Pallas -> Mosaic lowering of each kernel family at GPT-2
   shapes: a lowering break is caught before any chip call;
2. compiling for a *described* v5e topology
   (``jax.experimental.topologies``) runs libtpu's own Mosaic compiler —
   scoped-VMEM limits, tile alignment — and XLA's partitioner on a 2x2
   mesh, still without a chip (skipped where this libtpu cannot describe
   a topology).  It has already refused one thing interpret mode took:
   the loss head with fp32 operands overflows the 16 MiB scoped VMEM.

What neither can show is that the compiled kernel computes the right
numbers on the chip; ``chip_smoke.py`` is for that.

Here: each kernel family alone.  Whole programs compiled the same way are
in ``test_kernel_export_gpt2.py`` (GPT-2 medium's training step, attention
block and serving programs) and ``test_kernel_export_families.py`` (the
other serving families' pool and state programs); ``pytest
tests/test_kernel_export*.py -k NAME`` selects over all three.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from kernel_export_cases import BF16, F32, sds, sum32, v5e_mesh

from distributedtensorflow_tpu.ops.attention import (
    index_scores,
    select_bias,
    sparse_latent_attention,
    paged_latent_chunk_attention,
    paged_latent_decode_attention,
    paged_window_chunk_attention,
    paged_window_decode_attention,
)
from distributedtensorflow_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_qkv,
)
from distributedtensorflow_tpu.ops import fused_xent
from distributedtensorflow_tpu.ops.fused_xent import fused_softmax_xent
from distributedtensorflow_tpu.ops.grouped_matmul import (
    combine_rows,
    grouped_relu2,
    grouped_swiglu,
)
from distributedtensorflow_tpu.ops.layernorm import layer_norm
from distributedtensorflow_tpu.ops.ssm import ssm_chunk_scan
from distributedtensorflow_tpu.ops.kda import kda_step
from distributedtensorflow_tpu.ops.ssd import ssd_step
from distributedtensorflow_tpu.parallel import moe

# GPT-2 small at the trainer leg's shapes: batch 16, seq 1024, 12 heads of
# 64, d 768, vocab 50,257, bf16 activations.
B, S, H, D, V = 16, 1024, 12, 64, 50257


def _flash(**kw):
    return jax.value_and_grad(
        lambda q, k, v: sum32(flash_attention(
            q, k, v, causal=True, interpret=False, **kw)),
        argnums=(0, 1, 2),
    )


def _flash_qkv(heads=H, **kw):
    # the training block's entry: the fused projection as the matmul wrote
    # it, the rotation's tables as float32 lane tiles
    return jax.value_and_grad(
        lambda qkv, cos, sin: sum32(flash_attention_qkv(
            qkv, heads, rope=(cos, sin), causal=True, interpret=False,
            **kw)))


def _fused(seq=S, batch=B, heads=H, d=D, table_rows=1):
    tile = max(128, d)
    return (sds((batch, seq, 3 * heads * d), BF16),
            sds((table_rows, seq, tile), F32),
            sds((table_rows, seq, tile), F32))


def _xent(h, w, t):
    # as models/gpt.py calls it: fp32 table, bf16 compute
    return jax.value_and_grad(
        lambda h, w: fused_softmax_xent(h, w, t, compute_dtype=BF16,
                                        interpret=False),
        argnums=(0, 1),
    )(h, w)


def _ln(x, g, b):
    return jax.value_and_grad(
        lambda x, g, b: sum32(layer_norm(
            x, g, b, impl="pallas", interpret=False)),
        argnums=(0, 1, 2),
    )(x, g, b)


def _paged(window, slots=64, heads=48, d=128, columns=512, layers=2,
           width=1024, blocks=2048):
    # the afmoe serving shapes: 64 slots, 48 query / 8 K/V heads of 128,
    # blocks of 16, a table of 512 columns, one layer group's pool
    def fn(q, k_pool, v_pool, tables, lens):
        return paged_window_decode_attention(
            q, k_pool, v_pool, tables, lens, layer=1, block_size=16,
            window=window, impl="pallas", interpret=False)
    pool = sds((layers, (blocks + 1) * 16, width), BF16)
    return fn, (sds((slots, heads, d), BF16), pool, pool,
                sds((slots, columns), jnp.int32), sds((slots,), jnp.int32))


def _paged_eva(columns, blocks, lo):
    # the evabyte serving shapes: 28 slots, 32 heads of 128 each with rows of
    # its own (a row of 8 KB a pool: the kernel's four buffers are 16 MB),
    # the log of the denominator as a second output; the ring's walk starts
    # where the caller says
    def fn(q, k_pool, v_pool, tables, lens, *lo):
        return paged_window_decode_attention(
            q, k_pool, v_pool, tables, lens, layer=1, block_size=16,
            window=2048 if lo else None, impl="pallas", interpret=False,
            lo=lo[0] if lo else None, with_lse=True)
    pool = sds((8, blocks * 16, 4096), BF16)
    return fn, (sds((28, 32, 128), BF16), pool, pool,
                sds((28, columns), jnp.int32), sds((28,), jnp.int32),
                *((sds((28,), jnp.int32),) if lo else ()))


def _paged_wide(window, kv_heads, slots=32, heads=64, columns=4224):
    # the mimo serving shapes: 32 slots, 64 query heads on 4 (full) or 8
    # (window) K/V heads, keys 192 wide over values 128, a table of 4,224
    # columns (contexts to 67,584); the window layers' heads have a sink
    def fn(q, k_pool, v_pool, tables, lens, *sink):
        return paged_window_decode_attention(
            q, k_pool, v_pool, tables, lens, layer=1, block_size=16,
            window=window, impl="pallas", interpret=False,
            sink=sink[0] if sink else None)
    sink = (sds((heads,), F32),) if window else ()
    return fn, (sds((slots, heads, 192), BF16),
                sds((2, 2049 * 16, kv_heads * 192), BF16),
                sds((2, 2049 * 16, kv_heads * 128), BF16),
                sds((slots, columns), jnp.int32), sds((slots,), jnp.int32),
                *sink)


def _kv_chunk(chunk, heads, kv_heads, d, dv, window, columns, blocks,
              sink=False):
    # a prefill chunk of a K/V-row family at its cell's shapes: the chunk's
    # queries against the slot's page-table row, blocks of 16, one layer
    # group's pools (K rows as ``lay_heads`` stores them)
    def fn(q, start, k_pool, v_pool, table_row, *sink):
        return paged_window_chunk_attention(
            q, start, k_pool, v_pool, table_row, layer=1, block_size=16,
            window=window, impl="pallas", interpret=False,
            sink=sink[0] if sink else None)
    return fn, (sds((chunk, heads, d), BF16), sds((), jnp.int32),
                sds((2, (blocks + 1) * 16, kv_heads * d), BF16),
                sds((2, (blocks + 1) * 16, kv_heads * dv), BF16),
                sds((columns,), jnp.int32),
                *((sds((heads,), F32),) if sink else ()))


def _latent(slots=32, heads=32, rank=512, rope=64, nope=128, columns=1024):
    # the joyai serving shapes: 32 slots, 32 heads over one latent row of
    # 512 + 64 (stored 640 wide), blocks of 16, contexts to 16,384
    def fn(q_nope, q_rope, pool, tables, lens, w_uk, w_uv):
        return paged_latent_decode_attention(
            q_nope, q_rope, pool, tables, lens, w_uk=w_uk, w_uv=w_uv,
            layer=1, block_size=16, scale=(nope + rope) ** -0.5,
            impl="pallas", interpret=False)
    return fn, (sds((slots, heads, nope), BF16),
                sds((slots, heads, rope), BF16),
                sds((2, 2049 * 16, 640), BF16),
                sds((slots, columns), jnp.int32), sds((slots,), jnp.int32),
                sds((rank, heads, nope), BF16),
                sds((rank, heads, nope), BF16))


def _latent_chunk(chunk=1024, heads=32, rank=512, rope=64, nope=128,
                  columns=1024):
    # a joyai prefill chunk: 1024 queries of 32 heads against the slot's
    # page-table row, contexts to 16,384, the cell's pool of 24,576 blocks
    def fn(q_nope, q_rope, start, pool, table_row, w_uk, w_uv):
        return paged_latent_chunk_attention(
            q_nope, q_rope, start, pool, table_row, w_uk=w_uk, w_uv=w_uv,
            layer=1, block_size=16, scale=(nope + rope) ** -0.5,
            impl="pallas", interpret=False)
    return fn, (sds((chunk, heads, nope), BF16),
                sds((chunk, heads, rope), BF16), sds((), jnp.int32),
                sds((5, 24577 * 16, 640), BF16),
                sds((columns,), jnp.int32),
                sds((rank, heads, nope), BF16),
                sds((rank, heads, nope), BF16))


def _index(queries, slots, columns=2112, heads=32, dim=128):
    # GLM-5's indexer: 32 index heads of 128 against one key a token,
    # contexts to 33,792: a prefill chunk of one slot, or a query a slot
    def fn(q, w, keys, lens):
        return index_scores(q, w, keys, lens, impl="pallas",
                            interpret=False)
    return fn, (sds((slots, queries, heads, dim), BF16),
                sds((slots, queries, heads), F32),
                sds((slots, columns * 16, dim), BF16),
                sds((slots,), jnp.int32))


def _sparse_latent(queries, heads=64, k=2048, rank=512):
    # GLM-5's sparse attention: 64 heads over each query's own 2048 rows of
    # 640, gathered out of the cell's pool
    def fn(q, pool, rows, counts):
        return sparse_latent_attention(
            q, pool, rows, counts, layer=1, rank=rank, scale=256 ** -0.5,
            impl="pallas", interpret=False)
    return fn, (sds((queries, heads, 640), BF16),
                sds((5, 45057 * 16, 640), BF16),
                sds((queries, k), jnp.int32), sds((queries,), jnp.int32))


def _select(queries=1024, rows=33792, k=2048):
    # GLM-5's selection: the top 2048 of a chunk's scores, as a bias
    def fn(scores, counts):
        return select_bias(scores, counts, k, impl="pallas", interpret=False)
    return fn, (sds((queries, rows), F32), sds((queries,), jnp.int32))


def _masked_latent_chunk(chunk=1024, heads=64, rank=512, rope=64, nope=192,
                         v=256, columns=2112):
    # a GLM-5 prefill chunk past index_topk: the dense walk of the slot's
    # pages under the selection's bias, 64 heads of 192 + 64 / 256
    def fn(q_nope, q_rope, start, pool, table_row, w_uk, w_uv, bias):
        return paged_latent_chunk_attention(
            q_nope, q_rope, start, pool, table_row, w_uk=w_uk, w_uv=w_uv,
            layer=1, block_size=16, scale=(nope + rope) ** -0.5,
            impl="pallas", interpret=False, bias=bias)
    return fn, (sds((chunk, heads, nope), BF16),
                sds((chunk, heads, rope), BF16), sds((), jnp.int32),
                sds((5, 45057 * 16, 640), BF16),
                sds((columns,), jnp.int32),
                sds((rank, heads, nope), BF16),
                sds((rank, heads, v), BF16),
                sds((chunk, columns * 16), F32))


def _ssm_scan(chunk=1024, channels=5120, states=16):
    # a jamba prefill chunk's scan of one Mamba layer at the published
    # channel shape: u' in bf16, delta float32, the state in and out
    def fn(u, delta, a, b, c, d, state, valid):
        return ssm_chunk_scan(u, delta, a, b, c, d, state, valid,
                              impl="pallas", interpret=False)
    return fn, (sds((chunk, channels), BF16), sds((chunk, channels), F32),
                sds((states, channels), F32), sds((chunk, states), BF16),
                sds((chunk, states), BF16), sds((channels,), F32),
                sds((states, channels), F32), sds((), jnp.int32))


def _kda_step(slots=128, heads=32, d=128, layers=2):
    # a ling decode step of one KDA layer: every slot's state of the layer
    # through VMEM once, the group's array aliased in and out
    def fn(q, k, v, g, beta, pool):
        return kda_step(q, k, v, g, beta, pool, 1, impl="pallas",
                        interpret=False)
    rows = sds((slots, heads, d), F32)
    return fn, (rows, rows, rows, rows, sds((slots, heads), F32),
                sds((layers, slots, heads, d, d), F32))


def _ssd_step(slots=128, heads=128, dim=64, groups=8, states=128, layers=2):
    # a nemotron_h decode step of one Mamba-2 layer at the published widths:
    # every slot's state of the layer through VMEM once, a group's 16 heads a
    # grid step, the group's array aliased in and out
    def fn(x, dt, a, b, c, d, pool):
        return ssd_step(x, dt, a, b, c, d, pool, 1, impl="pallas",
                        interpret=False)
    bc = sds((slots, groups, states), BF16)
    return fn, (sds((slots, heads, dim), BF16), sds((slots, heads), F32),
                sds((heads,), F32), bc, bc, sds((heads,), F32),
                sds((layers, slots, heads, dim, states), F32))


def _grouped_ungated(tile, rows):
    # nemotron_h's latent experts at the published widths (1,024 -> 2,688 ->
    # 1,024, relu^2, no gate): the up kernel takes its weight block whole
    # (2,688 is no multiple of 256), 16 of the 128 held experts
    def fn(x, w_up, w_down, tile_expert, tiles_used):
        return grouped_relu2(x, w_up, w_down, tile_expert, tiles_used,
                             tile=tile, interpret=False)
    return fn, (sds((rows, 1024), BF16), sds((16, 1024, 2688), BF16),
                sds((16, 2688, 1024), BF16),
                sds((rows // tile,), jnp.int32), sds((), jnp.int32))


def _pick(tokens, k, rows, d=1024):
    # nemotron_h's combine at the published widths: the experts' rows of a
    # chunk of 2,048 tokens top 22 of 512 (a buffer of 53,248 rows: its two
    # index arrays and the weights 606 KB of SMEM), every token's float32
    # sum resident in VMEM
    def fn(y_rows, src, pair, w, rows_used):
        return combine_rows(y_rows, src, pair, w, rows_used, interpret=False)
    return fn, (sds((rows, d), BF16), sds((rows,), jnp.int32),
                sds((rows,), jnp.int32), sds((tokens, k), F32),
                sds((), jnp.int32))


def _grouped(tile):
    def fn(x, w_gate, w_up, w_down, tile_expert, tiles_used):
        return grouped_swiglu(x, w_gate, w_up, w_down, tile_expert,
                              tiles_used, tile=tile, interpret=False)
    return fn


def _grouped_args(rows=768, experts=8, d=3072, m=3072, tile=16):
    # a decode iteration's row buffer at the published expert widths
    return (sds((rows, d), BF16), sds((experts, d, m), BF16),
            sds((experts, d, m), BF16), sds((experts, m, d), BF16),
            sds((rows // tile,), jnp.int32), sds((), jnp.int32))


def _qkv(seq=S, kv_heads=H, batch=B):
    return (sds((batch, seq, H, D), BF16),
            sds((batch, seq, kv_heads, D), BF16),
            sds((batch, seq, kv_heads, D), BF16))


FAMILIES = {
    "flash_1024": (_flash(), _qkv()),
    "flash_8192": (_flash(), _qkv(seq=8192, batch=2)),
    "flash_window": (_flash(window=256), _qkv(seq=2048, batch=4)),
    "flash_gqa": (_flash(), _qkv(kv_heads=4)),
    # GPT-2 medium's training block: 16 heads of 64, two a 128-lane tile,
    # one q and one k block: a (1024, 1024) float32 score tile a head, a
    # (1024, 128) accumulator, the whole tile's dq, the tables fetched once
    "flash_qkv_gpt2m": (_flash_qkv(16), _fused(heads=16)),
    # per-row positions; eight blocks a side, so the running softmax and
    # the split backward (the dq of a whole tile no longer fits)
    "flash_qkv_8192": (_flash_qkv(), _fused(seq=8192, batch=2,
                                            table_rows=2)),
    "flash_qkv_window": (_flash_qkv(window=256), _fused(seq=2048, batch=4)),
    "flash_qkv_d128": (_flash_qkv(8), _fused(heads=8, d=128)),
    "flash_qkv_d32": (_flash_qkv(16), _fused(heads=16, d=32)),
    "fused_xent": (_xent, (sds((B, S, H * D), BF16),
                           sds((V, H * D), F32),
                           sds((B, S), jnp.int32))),
    "layer_norm": (_ln, (sds((B, S, H * D), BF16),
                         sds((H * D,), F32), sds((H * D,), F32))),
    "paged_attn_full": _paged(None),
    "paged_attn_window": _paged(4096),
    # GPT-2 medium's serving shapes: 32 slots, 16 heads of 64 (two a
    # 128-lane tile of the pool's row), contexts to 1024, 24 layers
    "paged_attn_gpt2m": _paged(None, slots=32, heads=16, d=64, columns=64,
                               layers=24),
    "moe_grouped": (_grouped(16), _grouped_args()),
    # joyai's widths (d 2048, m 768) under a prefill chunk's wide tile
    "moe_grouped_wide": (_grouped(moe.GROUP_TILE_WIDE), _grouped_args(
        rows=16 * moe.GROUP_TILE_WIDE, experts=8, d=2048, m=768,
        tile=moe.GROUP_TILE_WIDE)),
    "paged_latent_attn": _latent(),
    # ling's serving shapes: 128 slots, a table of 1,280 columns (contexts to
    # 20,480: 655 KB of page tables in SMEM)
    "paged_latent_attn_128_slots": _latent(slots=128, columns=1280),
    "latent_chunk_attn": _latent_chunk(),
    # the widest chunk the cell's sweep served: fewer heads a grid step
    "latent_chunk_attn_2048": _latent_chunk(chunk=2048),
    # jamba2_3b's serving shapes: 32 slots, 20 query heads on ONE K/V head of
    # 128 (24 rows a tile), a table of 2,112 columns (contexts to 33,792)
    "paged_attn_20_on_1": _paged(None, slots=32, heads=20, d=128,
                                 columns=2112, width=128),
    "select_rows": _select(),
    # a chunk of no whole tile of 32 queries: one tile of 24
    "select_rows_24": _select(queries=24, rows=8448),
    "masked_latent_chunk_attn": _masked_latent_chunk(),
    "index_scores_chunk": _index(1024, 1),
    "index_scores_step": _index(1, 24),
    "sparse_latent_attn_chunk": _sparse_latent(1024),
    "sparse_latent_attn_step": _sparse_latent(24),
    # mimo_v25_ep16: 16 query heads a K/V head, a K head a tile and a half
    "paged_attn_wide_full": _paged_wide(None, 4),
    # ... 8 a K/V head, a window of 128 and a sink a head
    "paged_attn_wide_window_sink": _paged_wide(128, 8),
    "ssm_chunk_scan": _ssm_scan(),
    "ssm_chunk_scan_2048": _ssm_scan(chunk=2048),
    "kda_step": _kda_step(),
    "ssd_step": _ssd_step(),
    # a decode batch of 128 slots top 22 of 512 (the small tile), and a
    # prefill chunk's wide tile
    "moe_grouped_ungated": _grouped_ungated(16, 128 * 22 + 16 * 16),
    "moe_grouped_ungated_wide": _grouped_ungated(
        moe.GROUP_TILE_WIDE, 24 * moe.GROUP_TILE_WIDE),
    "moe_pick_chunk": _pick(2048, 22, 2048 * 22 + 128 * 64),
    "moe_pick_step": _pick(128, 22, 128 * 22 + 128 * 16),
    # glm5's widths: a chunk of 1,024 tokens' sums in four column blocks
    "moe_pick_d6144": _pick(1024, 8, 1024 * 8 + 16 * 64, d=6144),
    # a prefill chunk's attention over K/V rows.  mimo_v25_ep16: 1024
    # queries of 64 heads, 16 a K/V head, keys 192 over values 128, a table
    # of 4,224 columns over the cell's pool of 65,536 blocks
    "kv_chunk_attn_wide_full": _kv_chunk(1024, 64, 4, 192, 128, None, 4224,
                                         65536),
    # ... 8 a K/V head, a window of 128 and a sink a head
    "kv_chunk_attn_wide_window_sink": _kv_chunk(1024, 64, 8, 192, 128, 128,
                                                4224, 2336, sink=True),
    # trinity_large_ep8: 512 queries of 48 heads of 128, 6 a K/V head, a
    # window of 4,096 in a table of 512 columns
    "kv_chunk_attn_d128_window": _kv_chunk(512, 48, 8, 128, 128, 4096, 512,
                                           11264),
    # jamba2_3b: 1024 queries of 20 heads on ONE K/V head of 128
    "kv_chunk_attn_20_on_1": _kv_chunk(1024, 20, 1, 128, 128, None, 2112,
                                       67584),
    # qwen3_next_ep4's serving shapes: 48 slots, 16 query heads on 2 K/V
    # heads of 256 (two lane tiles a head, 8 query rows a K/V head), a table
    # of 4,224 columns (contexts to 67,584: 811 KB of page tables in SMEM)
    "paged_attn_d256": _paged(None, slots=48, heads=16, d=256, columns=4224,
                              width=512),
    # evabyte_6_5b's two walks: the ring (a table of 2,048 columns, contexts
    # to 32,768) from the open window's start, the summary pool's 128 columns
    "paged_attn_eva_ring": _paged_eva(2048, 3612, True),
    "paged_attn_eva_summary": _paged_eva(128, 1200, False),
    # ... a prefill chunk of 2,048 queries: 4 query heads a grid step
    "kv_chunk_attn_d256": _kv_chunk(2048, 16, 2, 256, 256, None, 4224,
                                    73728),
    # ouro_2_6b's serving shapes: 16 slots, 16 query = 16 K/V heads of 128
    # (one query row a tile, rows of 4 KB a pool), a table of 96 columns
    # (contexts to 1,536) over 192 layer slots of a pool of 320 blocks
    "paged_attn_mha_192_slots": _paged(None, slots=16, heads=16, d=128,
                                       columns=96, layers=192, width=2048,
                                       blocks=320),
    # ... a prefill chunk of 256 queries, one query head a K/V head
    "kv_chunk_attn_mha": _kv_chunk(256, 16, 16, 128, 128, None, 96, 320),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernel_lowers_to_mosaic_for_tpu(family):
    fn, args = FAMILIES[family]
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module(), family


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernel_compiles_for_one_v5e_chip(family):
    fn, args = FAMILIES[family]
    repl = NamedSharding(v5e_mesh(1), P())
    args = [sds(a.shape, a.dtype, repl) for a in args]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), family


def test_training_kernels_compile_per_shard_on_a_2x2_mesh():
    """On four chips each Mosaic call must sit in a shard_map and see the
    per-device batch: GSPMD refuses to partition one ("Mosaic kernels
    cannot be automatically partitioned"), which is what this step did
    before ``parallel.sharding.shard_kernel``."""
    n_chips = 4
    mesh = v5e_mesh(n_chips)
    batch = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    gb = B * n_chips

    def step(qkv, cos, sin, q, k, v, x, g, b, w, t):
        return (_flash_qkv()(qkv, cos, sin), _flash()(q, k, v),
                _ln(x, g, b), _xent(x, w, t))

    args = (
        sds((gb, S, 3 * H * D), BF16, batch),
        sds((1, S, 128), F32, repl), sds((1, S, 128), F32, repl),
        *(sds((gb, S, H, D), BF16, batch) for _ in range(3)),
        sds((gb, S, H * D), BF16, batch),
        sds((H * D,), F32, repl), sds((H * D,), F32, repl),
        sds((V, H * D), F32, repl),
        sds((gb, S), jnp.int32, batch),
    )
    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(step).lower(*args)
        compiled = lowered.compile()
    shapes = re.findall(
        r'kernel_name = "(\w+)".*?\}\s*:\s*\(tensor<([0-9x]+)x\w+>',
        lowered.as_text(),
    )
    names = {name for name, _ in shapes}
    assert {"flash_fwd", "flash_bwd", "layer_norm_fwd", "layer_norm_bwd",
            "fused_xent_fwd", "fused_xent_bwd_dx",
            "fused_xent_bwd_dw"} <= names, names
    first_dims = {int(s.split("x")[0]) for _, s in shapes}
    # per device, never global; the head's backward a dlog chunk of a
    # shard's tokens (dx) and the padded vocabulary's dlog rows (dw)
    chunk = fused_xent.dlog_chunk_tokens(B * S, H * D, V)
    assert chunk == B * S // 4
    assert first_dims == {B, B * S, chunk, V + (-V) % 512}, first_dims
    # both forms of the flash kernels: (B, S, 3*H*D) and (B, H, S, D)
    assert {s for n, s in shapes if n == "flash_fwd"} == {
        f"{B}x{S}x{3 * H * D}", f"{B}x{H}x{S}x{D}"}, shapes
    assert "tpu_custom_call" in compiled.as_text()
