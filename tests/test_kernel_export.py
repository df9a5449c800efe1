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
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

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
BF16, F32 = jnp.bfloat16, jnp.float32


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sum32(x):
    return jnp.sum(x.astype(F32))


def _flash(**kw):
    return jax.value_and_grad(
        lambda q, k, v: _sum32(flash_attention(
            q, k, v, causal=True, interpret=False, **kw)),
        argnums=(0, 1, 2),
    )


def _flash_qkv(heads=H, **kw):
    # the training block's entry: the fused projection as the matmul wrote
    # it, the rotation's tables as float32 lane tiles
    return jax.value_and_grad(
        lambda qkv, cos, sin: _sum32(flash_attention_qkv(
            qkv, heads, rope=(cos, sin), causal=True, interpret=False,
            **kw)))


def _fused(seq=S, batch=B, heads=H, d=D, table_rows=1):
    tile = max(128, d)
    return (_sds((batch, seq, 3 * heads * d), BF16),
            _sds((table_rows, seq, tile), F32),
            _sds((table_rows, seq, tile), F32))


def _xent(h, w, t):
    # as models/gpt.py calls it: fp32 table, bf16 compute
    return jax.value_and_grad(
        lambda h, w: fused_softmax_xent(h, w, t, compute_dtype=BF16,
                                        interpret=False),
        argnums=(0, 1),
    )(h, w)


def _ln(x, g, b):
    return jax.value_and_grad(
        lambda x, g, b: _sum32(layer_norm(
            x, g, b, impl="pallas", interpret=False)),
        argnums=(0, 1, 2),
    )(x, g, b)


def _paged(window, slots=64, heads=48, d=128, columns=512, layers=2,
           width=1024):
    # the afmoe serving shapes: 64 slots, 48 query / 8 K/V heads of 128,
    # blocks of 16, a table of 512 columns, one layer group's pool
    def fn(q, k_pool, v_pool, tables, lens):
        return paged_window_decode_attention(
            q, k_pool, v_pool, tables, lens, layer=1, block_size=16,
            window=window, impl="pallas", interpret=False)
    pool = _sds((layers, 2049 * 16, width), BF16)
    return fn, (_sds((slots, heads, d), BF16), pool, pool,
                _sds((slots, columns), jnp.int32), _sds((slots,), jnp.int32))


def _paged_wide(window, kv_heads, slots=32, heads=64, columns=4224):
    # the mimo serving shapes: 32 slots, 64 query heads on 4 (full) or 8
    # (window) K/V heads, keys 192 wide over values 128, a table of 4,224
    # columns (contexts to 67,584); the window layers' heads have a sink
    def fn(q, k_pool, v_pool, tables, lens, *sink):
        return paged_window_decode_attention(
            q, k_pool, v_pool, tables, lens, layer=1, block_size=16,
            window=window, impl="pallas", interpret=False,
            sink=sink[0] if sink else None)
    sink = (_sds((heads,), F32),) if window else ()
    return fn, (_sds((slots, heads, 192), BF16),
                _sds((2, 2049 * 16, kv_heads * 192), BF16),
                _sds((2, 2049 * 16, kv_heads * 128), BF16),
                _sds((slots, columns), jnp.int32), _sds((slots,), jnp.int32),
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
    return fn, (_sds((chunk, heads, d), BF16), _sds((), jnp.int32),
                _sds((2, (blocks + 1) * 16, kv_heads * d), BF16),
                _sds((2, (blocks + 1) * 16, kv_heads * dv), BF16),
                _sds((columns,), jnp.int32),
                *((_sds((heads,), F32),) if sink else ()))


def _latent(slots=32, heads=32, rank=512, rope=64, nope=128, columns=1024):
    # the joyai serving shapes: 32 slots, 32 heads over one latent row of
    # 512 + 64 (stored 640 wide), blocks of 16, contexts to 16,384
    def fn(q_nope, q_rope, pool, tables, lens, w_uk, w_uv):
        return paged_latent_decode_attention(
            q_nope, q_rope, pool, tables, lens, w_uk=w_uk, w_uv=w_uv,
            layer=1, block_size=16, scale=(nope + rope) ** -0.5,
            impl="pallas", interpret=False)
    return fn, (_sds((slots, heads, nope), BF16),
                _sds((slots, heads, rope), BF16),
                _sds((2, 2049 * 16, 640), BF16),
                _sds((slots, columns), jnp.int32), _sds((slots,), jnp.int32),
                _sds((rank, heads, nope), BF16),
                _sds((rank, heads, nope), BF16))


def _latent_chunk(chunk=1024, heads=32, rank=512, rope=64, nope=128,
                  columns=1024):
    # a joyai prefill chunk: 1024 queries of 32 heads against the slot's
    # page-table row, contexts to 16,384, the cell's pool of 24,576 blocks
    def fn(q_nope, q_rope, start, pool, table_row, w_uk, w_uv):
        return paged_latent_chunk_attention(
            q_nope, q_rope, start, pool, table_row, w_uk=w_uk, w_uv=w_uv,
            layer=1, block_size=16, scale=(nope + rope) ** -0.5,
            impl="pallas", interpret=False)
    return fn, (_sds((chunk, heads, nope), BF16),
                _sds((chunk, heads, rope), BF16), _sds((), jnp.int32),
                _sds((5, 24577 * 16, 640), BF16),
                _sds((columns,), jnp.int32),
                _sds((rank, heads, nope), BF16),
                _sds((rank, heads, nope), BF16))


def _index(queries, slots, columns=2112, heads=32, dim=128):
    # GLM-5's indexer: 32 index heads of 128 against one key a token,
    # contexts to 33,792: a prefill chunk of one slot, or a query a slot
    def fn(q, w, keys, lens):
        return index_scores(q, w, keys, lens, impl="pallas",
                            interpret=False)
    return fn, (_sds((slots, queries, heads, dim), BF16),
                _sds((slots, queries, heads), F32),
                _sds((slots, columns * 16, dim), BF16),
                _sds((slots,), jnp.int32))


def _sparse_latent(queries, heads=64, k=2048, rank=512):
    # GLM-5's sparse attention: 64 heads over each query's own 2048 rows of
    # 640, gathered out of the cell's pool
    def fn(q, pool, rows, counts):
        return sparse_latent_attention(
            q, pool, rows, counts, layer=1, rank=rank, scale=256 ** -0.5,
            impl="pallas", interpret=False)
    return fn, (_sds((queries, heads, 640), BF16),
                _sds((5, 45057 * 16, 640), BF16),
                _sds((queries, k), jnp.int32), _sds((queries,), jnp.int32))


def _select(queries=1024, rows=33792, k=2048):
    # GLM-5's selection: the top 2048 of a chunk's scores, as a bias
    def fn(scores, counts):
        return select_bias(scores, counts, k, impl="pallas", interpret=False)
    return fn, (_sds((queries, rows), F32), _sds((queries,), jnp.int32))


def _masked_latent_chunk(chunk=1024, heads=64, rank=512, rope=64, nope=192,
                         v=256, columns=2112):
    # a GLM-5 prefill chunk past index_topk: the dense walk of the slot's
    # pages under the selection's bias, 64 heads of 192 + 64 / 256
    def fn(q_nope, q_rope, start, pool, table_row, w_uk, w_uv, bias):
        return paged_latent_chunk_attention(
            q_nope, q_rope, start, pool, table_row, w_uk=w_uk, w_uv=w_uv,
            layer=1, block_size=16, scale=(nope + rope) ** -0.5,
            impl="pallas", interpret=False, bias=bias)
    return fn, (_sds((chunk, heads, nope), BF16),
                _sds((chunk, heads, rope), BF16), _sds((), jnp.int32),
                _sds((5, 45057 * 16, 640), BF16),
                _sds((columns,), jnp.int32),
                _sds((rank, heads, nope), BF16),
                _sds((rank, heads, v), BF16),
                _sds((chunk, columns * 16), F32))


def _ssm_scan(chunk=1024, channels=5120, states=16):
    # a jamba prefill chunk's scan of one Mamba layer at the published
    # channel shape: u' in bf16, delta float32, the state in and out
    def fn(u, delta, a, b, c, d, state, valid):
        return ssm_chunk_scan(u, delta, a, b, c, d, state, valid,
                              impl="pallas", interpret=False)
    return fn, (_sds((chunk, channels), BF16), _sds((chunk, channels), F32),
                _sds((states, channels), F32), _sds((chunk, states), BF16),
                _sds((chunk, states), BF16), _sds((channels,), F32),
                _sds((states, channels), F32), _sds((), jnp.int32))


def _kda_step(slots=128, heads=32, d=128, layers=2):
    # a ling decode step of one KDA layer: every slot's state of the layer
    # through VMEM once, the group's array aliased in and out
    def fn(q, k, v, g, beta, pool):
        return kda_step(q, k, v, g, beta, pool, 1, impl="pallas",
                        interpret=False)
    rows = _sds((slots, heads, d), F32)
    return fn, (rows, rows, rows, rows, _sds((slots, heads), F32),
                _sds((layers, slots, heads, d, d), F32))


def _ssd_step(slots=128, heads=128, dim=64, groups=8, states=128, layers=2):
    # a nemotron_h decode step of one Mamba-2 layer at the published widths:
    # every slot's state of the layer through VMEM once, a group's 16 heads a
    # grid step, the group's array aliased in and out
    def fn(x, dt, a, b, c, d, pool):
        return ssd_step(x, dt, a, b, c, d, pool, 1, impl="pallas",
                        interpret=False)
    bc = _sds((slots, groups, states), BF16)
    return fn, (_sds((slots, heads, dim), BF16), _sds((slots, heads), F32),
                _sds((heads,), F32), bc, bc, _sds((heads,), F32),
                _sds((layers, slots, heads, dim, states), F32))


def _grouped_ungated(tile, rows):
    # nemotron_h's latent experts at the published widths (1,024 -> 2,688 ->
    # 1,024, relu^2, no gate): the up kernel takes its weight block whole
    # (2,688 is no multiple of 256), 16 of the 128 held experts
    def fn(x, w_up, w_down, tile_expert, tiles_used):
        return grouped_relu2(x, w_up, w_down, tile_expert, tiles_used,
                             tile=tile, interpret=False)
    return fn, (_sds((rows, 1024), BF16), _sds((16, 1024, 2688), BF16),
                _sds((16, 2688, 1024), BF16),
                _sds((rows // tile,), jnp.int32), _sds((), jnp.int32))


def _grouped(tile):
    def fn(x, w_gate, w_up, w_down, tile_expert, tiles_used):
        return grouped_swiglu(x, w_gate, w_up, w_down, tile_expert,
                              tiles_used, tile=tile, interpret=False)
    return fn


def _grouped_args(rows=768, experts=8, d=3072, m=3072, tile=16):
    # a decode iteration's row buffer at the published expert widths
    return (_sds((rows, d), BF16), _sds((experts, d, m), BF16),
            _sds((experts, d, m), BF16), _sds((experts, m, d), BF16),
            _sds((rows // tile,), jnp.int32), _sds((), jnp.int32))


def _qkv(seq=S, kv_heads=H, batch=B):
    return (_sds((batch, seq, H, D), BF16),
            _sds((batch, seq, kv_heads, D), BF16),
            _sds((batch, seq, kv_heads, D), BF16))


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
    "fused_xent": (_xent, (_sds((B, S, H * D), BF16),
                           _sds((V, H * D), F32),
                           _sds((B, S), jnp.int32))),
    "layer_norm": (_ln, (_sds((B, S, H * D), BF16),
                         _sds((H * D,), F32), _sds((H * D,), F32))),
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
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernel_lowers_to_mosaic_for_tpu(family):
    fn, args = FAMILIES[family]
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module(), family


def _v5e_mesh(n):
    from jax.experimental import topologies

    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason means "not here"
        pytest.skip(f"this libtpu cannot describe a v5e topology: {e}")
    return build_mesh(MeshSpec(data=n), topo.devices[:n])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernel_compiles_for_one_v5e_chip(family):
    fn, args = FAMILIES[family]
    repl = NamedSharding(_v5e_mesh(1), P())
    args = [_sds(a.shape, a.dtype, repl) for a in args]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), family


def test_training_kernels_compile_per_shard_on_a_2x2_mesh():
    """On four chips each Mosaic call must sit in a shard_map and see the
    per-device batch: GSPMD refuses to partition one ("Mosaic kernels
    cannot be automatically partitioned"), which is what this step did
    before ``parallel.sharding.shard_kernel``."""
    n_chips = 4
    mesh = _v5e_mesh(n_chips)
    batch = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    gb = B * n_chips

    def step(qkv, cos, sin, q, k, v, x, g, b, w, t):
        return (_flash_qkv()(qkv, cos, sin), _flash()(q, k, v),
                _ln(x, g, b), _xent(x, w, t))

    args = (
        _sds((gb, S, 3 * H * D), BF16, batch),
        _sds((1, S, 128), F32, repl), _sds((1, S, 128), F32, repl),
        *(_sds((gb, S, H, D), BF16, batch) for _ in range(3)),
        _sds((gb, S, H * D), BF16, batch),
        _sds((H * D,), F32, repl), _sds((H * D,), F32, repl),
        _sds((V, H * D), F32, repl),
        _sds((gb, S), jnp.int32, batch),
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


def _as_on_the_chip(monkeypatch):
    """The programs choose their kernels by ``runtime.on_tpu()``, which
    sees this sandbox's CPU: answer for the described chip, in the test."""
    import sys

    for name, module in list(sys.modules.items()):
        if name.startswith("distributedtensorflow_tpu") \
                and hasattr(module, "on_tpu"):
            monkeypatch.setattr(module, "on_tpu", lambda: True)


def _attention_block_text(one_chip, batch=16):
    """GPT-2 medium's attention block (``models/gpt.py:
    CausalSelfAttention``: qkv -> attention -> proj) as the trainer's step
    holds it: forward and backward under ``jax.checkpoint``, the rotation's
    tables made once outside, compiled for the described chip."""
    import dataclasses

    from distributedtensorflow_tpu.models import gpt

    cfg = dataclasses.replace(gpt.gpt_medium(), max_seq=1024)
    attn = gpt.CausalSelfAttention(cfg)
    x = _sds((batch, 1024, cfg.hidden_size), BF16, one_chip)
    positions = jnp.broadcast_to(jnp.arange(1024), (batch, 1024))
    params = jax.eval_shape(
        lambda: attn.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, BF16),
                          positions, True))
    params = jax.tree.map(lambda p: _sds(p.shape, p.dtype, one_chip), params)

    def loss(params, x):
        tabs = gpt.block_rope_tables(
            cfg, None, x.shape[:2],
            fused=gpt.attention_layout(cfg, 1024) == "qkv_tiles")
        block = jax.checkpoint(
            lambda p, x: attn.apply(p, x, positions, True, tabs))
        return _sum32(block(params, block(params, x)))

    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    return fn.lower(params, x).compile().as_text()


def _whole_tensor_moves(text, elems, head_dim):
    """``(moves, matrices)``: the ``copy`` / ``transpose`` / ``slice`` ops
    of the compiled program whose result holds at least ``elems`` values,
    and the (head_dim, head_dim) arrays it holds (the rotary's half-swap
    is a product against one)."""
    moves, matrices = [], []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"([\w\-]+)\(", line)
        if not m:
            continue
        dims = [int(n) for n in m.group(1).split(",")]
        size = 1
        for n in dims:
            size *= n
        if dims == [head_dim, head_dim]:
            matrices.append(line.strip()[:120])
        elif m.group(2) in ("copy", "transpose", "slice") and size >= elems:
            moves.append(line.strip()[:120])
    return moves, matrices


@pytest.mark.parametrize("form", ["qkv_tiles", "bhsd"])
def test_attention_block_moves_no_whole_tensor_on_a_v5e(form, monkeypatch):
    """Between the qkv product and the flash kernels, and between them and
    the output projection, q, k, v, o and their gradients take no trip
    through HBM: the compiled block holds no ``copy``, ``transpose`` or
    ``slice`` of a (B, S, H*D)-sized array and no product against a (D, D)
    matrix (the rotary's half-swap).  The (B, H, S, D) form, which a shape
    that fills no lane tile falls back to in silence, holds both: the check
    can see them (ten copies and four products a block pass, 241 ms of a
    1995 ms step: ``PERF.md`` section 6, PR 35)."""
    import distributedtensorflow_tpu.ops.flash_attention as fa

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    if form == "bhsd":
        monkeypatch.setattr(fa, "tile_heads", lambda *a: None)
    text = _attention_block_text(one_chip)
    assert text.count("tpu_custom_call") >= 6   # 2 x (fwd, fwd again, bwd)
    moves, matrices = _whole_tensor_moves(text, 16 * 1024 * 1024, 64)
    if form == "qkv_tiles":
        assert moves == [] and matrices == [], (moves, matrices)
    else:
        assert len(moves) >= 10 and matrices, (moves, matrices)


def _gpt2m_pool_programs(one_chip, **changes):
    import dataclasses

    from distributedtensorflow_tpu.models import gpt_medium
    from distributedtensorflow_tpu.serve import pool_check

    cfg = dataclasses.replace(gpt_medium(), max_seq=1024, **changes)
    return pool_check.pool_programs(
        cfg, max_slots=32, num_blocks=2048, block_size=16, chunk=16, draft=4,
        sharding=one_chip)


@pytest.mark.parametrize("program", [
    "prefill_chunk", "decode", "fused_decode", "fused_decode_spec",
    "copy_block"])
def test_serving_program_keeps_the_pool_in_place_on_a_v5e(program,
                                                          monkeypatch):
    """GPT-2 medium's widths and the benchmark cells' pool (2048 blocks of
    16 tokens, 32 slots), two layers deep and with a small vocabulary to
    keep the compile short (the fused sampler's is most of it), built as
    on the chip (``decode`` attends through the ``paged_attn`` kernel): the
    v5e compiler takes the pool in the form it is stored in, converts no
    layer of it outside ``paged_attn`` and hands the donated pools back in
    place.  ``chip_smoke.py`` makes the same check at full depth on the
    chip."""
    from distributedtensorflow_tpu.serve import kv_cache, pool_check

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    programs = _gpt2m_pool_programs(one_chip, num_layers=2, vocab_size=1024)
    _, rows, width = kv_cache.pool_shape(2, 2048, 16, 16 * 64)
    report = pool_check.check_pool_programs(
        {program: programs[program]}, layer_elems=rows * width)
    assert pool_check.failures(report) == []
    # rows of all heads, minor dimension a multiple of 128: no padding
    assert report[program]["k_pool"] == \
        "bf16[2,32784,1024]{2,1,0:T(8,128)(2,1)}"


@pytest.mark.parametrize("program", ["prefill_chunk", "decode",
                                     "copy_block"])
def test_latent_program_keeps_the_pool_in_place_on_a_v5e(program,
                                                         monkeypatch):
    """The joyai family at its published widths, two layers deep (one dense,
    one of 8 experts) and with a small vocabulary: its three programs that
    take the one pool of latent rows (it is refused the fused ones) convert
    no layer of it outside ``paged_attn`` and hand it back in place, in the
    row form: 512 + 64 values in five lane tiles."""
    import dataclasses

    from distributedtensorflow_tpu.models import joyai_llm_flash
    from distributedtensorflow_tpu.serve import kv_cache, pool_check

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(
        joyai_llm_flash(), max_seq=2048, num_layers=2, num_experts=8,
        vocab_size=1024)
    programs = pool_check.pool_programs(
        cfg, max_slots=8, num_blocks=1024, block_size=16, chunk=256, draft=4,
        sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    _, rows, width = kv_cache.pool_shape(2, 1024, 16, 640)
    report = pool_check.check_pool_programs(
        {program: programs[program]}, layer_elems=rows * width)
    assert pool_check.failures(report, pools=1) == []
    assert report[program]["k_pool"] == \
        "bf16[2,16400,640]{2,1,0:T(8,128)(2,1)}"
    if program == "prefill_chunk":
        # both layers attend through the chunk kernel, lowered once, the
        # layer a prefetched scalar (the fallback to the plain loop is
        # silent: 37 of a chunk's 64 ms)
        fn, args = programs[program]
        text = fn.lower(*args).as_text()
        calls = re.findall(r"call @(\w*latent_chunk\w*)\(", text)
        assert len(calls) == 2 and len(set(calls)) == 1, calls
        assert text.count('kernel_name = "latent_chunk_attn"') == 1


@pytest.mark.parametrize("program", ["prefill_chunk", "decode",
                                     "copy_block"])
def test_sparse_latent_program_keeps_both_pools_in_place_on_a_v5e(
        program, monkeypatch):
    """GLM-5 (the joyai family with its indexer on) at its published widths,
    two layers deep with 8 experts held and a small vocabulary: its three
    programs take the pool of latent rows AND the pool of index keys,
    convert no layer of either outside ``paged_attn`` / ``indexer`` and hand
    both back in place; the indexer, the selection and the sparse kernel are
    in the lowered programs, each kernel lowered once.  (The pool is 335 MB:
    one of 42 MB the compiler moves whole into the 128 MiB of VMEM and back,
    which reads as a pool-sized copy.)"""
    import dataclasses

    from distributedtensorflow_tpu.models import glm5_ep16
    from distributedtensorflow_tpu.serve import kv_cache, pool_check

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(
        glm5_ep16(), max_seq=4096, num_layers=2, experts_held=8,
        vocab_size=1024)
    programs = pool_check.pool_programs(
        cfg, max_slots=8, num_blocks=8192, block_size=16, chunk=256, draft=4,
        sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    for width in cfg.cache_rows.widths:         # 640, then 128
        _, rows, _ = kv_cache.pool_shape(2, 8192, 16, width)
        report = pool_check.check_pool_programs(
            {program: programs[program]}, layer_elems=rows * width)
        assert pool_check.failures(report, pools=2) == []
    assert report[program]["k_pool"] == \
        "bf16[2,131088,640]{2,1,0:T(8,128)(2,1)}"
    if program != "copy_block":
        fn, args = programs[program]
        text = fn.lower(*args).as_text()
        kernels = {"decode": ("index_scores", "sparse_latent_attn"),
                   "prefill_chunk": ("index_scores", "select_rows",
                                     "masked_latent_chunk_attn",
                                     "latent_chunk_attn")}[program]
        for kernel in kernels:
            assert text.count(f'kernel_name = "{kernel}"') == 1, kernel


@pytest.mark.parametrize("program", ["prefill_chunk", "decode",
                                     "copy_block"])
def test_two_form_program_keeps_both_groups_pools_in_place_on_a_v5e(
        program, monkeypatch):
    """The mimo family at its published widths, three layers deep (full,
    window, window) with 8 experts held and a small vocabulary: its programs
    take the full group's pools (4 K/V heads: rows of 768 and 512) AND the
    window group's (8: 1536 and 1024), copy or convert no layer of any
    outside ``paged_attn`` and hand all four back in place; decode attends
    both groups through the ``paged_attn`` kernel, lowered once a group (the
    window group's with the sink), and the prefill chunk through
    ``kv_chunk_attn``, lowered once a group too (the window group's two
    layers share one body).  (It is refused the fused programs.)"""
    import dataclasses

    from distributedtensorflow_tpu.models import mimo_v25_ep16
    from distributedtensorflow_tpu.serve import kv_cache, pool_check
    from distributedtensorflow_tpu.serve.model import make_programs

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(
        mimo_v25_ep16(), max_seq=4096, layer_pattern=(0, 1, 1),
        moe_layers=(0, 1, 1), experts_held=8, vocab_size=1024)
    programs = pool_check.pool_programs(
        cfg, max_slots=8, num_blocks=8192, window_blocks=2048, block_size=16,
        chunk=256, draft=4, sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    window = program != "copy_block"        # which takes the full group's
    for name, blocks in (("full", 8192), ("window", 2048)):
        for width in kv_cache.group_rows(cfg, name).widths:
            _, rows, _ = kv_cache.pool_shape(1, blocks, 16, width)
            report = pool_check.check_pool_programs(
                {program: programs[program]}, layer_elems=rows * width)
            assert pool_check.failures(report, window=window) == []
    assert report[program]["k_pool"] == \
        "bf16[1,131088,768]{2,1,0:T(8,128)(2,1)}"
    if window:
        fn, args = programs[program]
        text = fn.lower(*args).as_text()
        kernels = {name: text.count(f'kernel_name = "{name}"')
                   for name in ("paged_attn", "kv_chunk_attn")}
        assert kernels == ({"paged_attn": 2, "kv_chunk_attn": 0}
                           if program == "decode"
                           else {"paged_attn": 0, "kv_chunk_attn": 2})
        assert make_programs(
            cfg, chunk=256, block_size=16,
            layers=kv_cache.layer_groups(cfg)).formulations == {
            "full": {"decode": "paged_attn", "chunk": "kv_chunk_attn"},
            "window": {"decode": "paged_attn", "chunk": "kv_chunk_attn"}}


@pytest.mark.parametrize("program", ["prefill_chunk", "decode"])
def test_two_rate_program_keeps_ring_and_summary_pools_in_place_on_a_v5e(
        program, monkeypatch):
    """The evabyte family at its published widths (32 heads of 128, chunks
    of 16 in windows of 2,048, contexts to 32,768), two layers deep: its
    programs take the ring's pools AND the summary pool's — the same layers'
    rows in two groups at two rates —, copy or convert no layer of either
    outside ``paged_attn`` and hand all four back in place; decode walks both
    through the ``paged_attn`` kernel and a chunk of 2,048 through
    ``kv_chunk_attn`` (one body a group: the ring's walk starts at the
    window's first row, the summaries' is unmasked), each walk handing out
    its log-sum-exp; a pool row of 4,096 lanes fits the decode kernel's
    VMEM."""
    import dataclasses

    from distributedtensorflow_tpu.models import evabyte_6_5b
    from distributedtensorflow_tpu.serve import kv_cache, pool_check
    from distributedtensorflow_tpu.serve.model import make_programs

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(evabyte_6_5b(), num_layers=2)
    programs = pool_check.pool_programs(
        cfg, max_slots=8, num_blocks=512, window_blocks=8 * 129,
        block_size=16, chunk=2048, draft=0, sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    for blocks in (512, 8 * 129):
        _, rows, width = kv_cache.pool_shape(2, blocks, 16, 4096)
        report = pool_check.check_pool_programs(
            {program: programs[program]}, layer_elems=rows * width)
        assert pool_check.failures(report, window=True) == []
    fn, args = programs[program]
    text = fn.lower(*args).as_text()
    kernels = {name: text.count(f'kernel_name = "{name}"')
               for name in ("paged_attn", "kv_chunk_attn")}
    assert kernels == ({"paged_attn": 2, "kv_chunk_attn": 0}
                       if program == "decode"
                       else {"paged_attn": 0, "kv_chunk_attn": 2})
    assert make_programs(
        cfg, chunk=2048, block_size=16,
        layers=kv_cache.layer_groups(cfg)).formulations == {
        "full": {"decode": "paged_attn", "chunk": "kv_chunk_attn"},
        "window": {"decode": "paged_attn", "chunk": "kv_chunk_attn"}}


@pytest.mark.parametrize("program", ["prefill_chunk", "decode"])
def test_state_program_keeps_pools_and_state_in_place_on_a_v5e(program,
                                                               monkeypatch):
    """The jamba family at its published widths, four layers deep (layer 1
    attending, three Mamba) and with a small vocabulary: the programs take
    the K/V pools and the state group's arrays as they are stored, copy or
    transpose no layer of either, and hand all four back in place; the
    prefill chunk scans through ``ssm_chunk_scan``, lowered once for the
    three layers, and attends 20 heads on one K/V head through
    ``kv_chunk_attn``; decode attends them through ``paged_attn``.  (It is
    refused the fused programs.)"""
    import dataclasses

    from distributedtensorflow_tpu.models import jamba2_3b
    from distributedtensorflow_tpu.serve import kv_cache, pool_check

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(
        jamba2_3b(), max_seq=2048, num_layers=4, attn_layer_period=4,
        attn_layer_offset=1, vocab_size=1024)
    programs = pool_check.pool_programs(
        cfg, max_slots=32, num_blocks=16384, block_size=16, chunk=256, draft=4,
        sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    _, rows, width = kv_cache.pool_shape(1, 16384, 16, 128)
    report = pool_check.check_pool_programs(
        {program: programs[program]}, layer_elems=rows * width,
        state=(32, cfg.state_rows.arrays(cfg.dtype)))
    assert pool_check.failures(
        report, state=cfg.state_rows.names) == []
    assert report[program]["donated"] == ["conv_tail", "k_pool", "scan_state",
                                          "v_pool"]
    fn, args = programs[program]
    text = fn.lower(*args).as_text()
    if program == "prefill_chunk":
        assert len(args) == 7       # the count of real tokens
        calls = re.findall(r"call @(\w*scan_call\w*)\(", text)
        assert len(calls) == 3 and len(set(calls)) == 1, calls
        assert text.count('kernel_name = "ssm_chunk_scan"') == 1
        assert text.count('kernel_name = "kv_chunk_attn"') == 1
    else:
        assert text.count('kernel_name = "paged_attn"') == 1


@pytest.mark.parametrize("program", ["prefill_chunk", "decode"])
def test_delta_state_program_keeps_pool_and_state_in_place_on_a_v5e(
        program, monkeypatch):
    """The ling family at its published widths, three layers deep (a dense
    KDA layer, an MLA layer and a KDA layer with 16 of 128 experts held) and
    with a small vocabulary: a state group beside a latent full group.  The
    programs take the latent pool and the state group's four arrays (three
    tails, the matrix states) as they are stored, copy or transpose no layer
    of either, and hand all five back in place; a prefill chunk scans
    in plain ``jax.numpy`` (the chunked form: no kernel), decode
    steps through ``kda_step`` over the group's whole array, a layer an
    index; the latent rows go through joyai's kernels.  (It is refused the
    fused programs.)"""
    import dataclasses

    from distributedtensorflow_tpu.models import ling3_flash_ep8
    from distributedtensorflow_tpu.serve import kv_cache, pool_check

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(
        ling3_flash_ep8(), max_seq=2048, vocab_size=1024, num_experts=128,
        experts_held=16, layer_types=("kda", "mla", "kda"),
        swiglu_limits=())
    names = cfg.state_rows.names
    assert names == ("q_tail", "k_tail", "v_tail", "delta_state")
    # the cell's 128 slots: at 32 the compiler leaves the tails as they lie;
    # at 128 it wrote the q, k, v product with the slots across lanes and
    # re-laid all three tail arrays out, a copy in and a copy out, until
    # models/ling.py pinned the product's rows
    programs = pool_check.pool_programs(
        cfg, max_slots=128, num_blocks=4096, block_size=16, chunk=256,
        draft=4, sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    _, rows, width = kv_cache.pool_shape(1, 4096, 16, 640)
    report = pool_check.check_pool_programs(
        {program: programs[program]}, layer_elems=rows * width,
        state=(128, cfg.state_rows.arrays(cfg.dtype)), state_names=names)
    assert pool_check.failures(report, pools=1, state=names) == []
    assert report[program]["donated"] == sorted(("k_pool",) + names)
    fn, args = programs[program]
    text = fn.lower(*args).as_text()
    if program == "prefill_chunk":
        assert len(args) == 7       # the count of real tokens
        assert "kda_chunk_scan" not in text     # plain jax.numpy: no kernel
        assert text.count('kernel_name = "latent_chunk_attn"') == 1
    else:
        assert text.count('kernel_name = "kda_step"') == 2  # a layer an index
        assert text.count('kernel_name = "paged_latent_attn"') == 1


@pytest.mark.parametrize("program", ["prefill_chunk", "decode"])
def test_ssd_state_program_keeps_pools_and_state_in_place_on_a_v5e(
        program, monkeypatch):
    """The nemotron_h family at its published widths, four layers deep (a
    Mamba-2 layer, an expert layer with 16 of 64 experts held, an attention
    layer, a Mamba-2 layer) and with a small vocabulary: a state group beside
    a K/V full group, the expert layer in neither.  The programs take the K/V
    pools and the state group's two arrays (the tail, the matrix states) as
    they are stored, copy or transpose no layer of either, and hand all four
    back in place at the cell's 128 slots; a prefill chunk scans in plain
    ``jax.numpy`` (the chunked form: no kernel), decode steps through
    ``ssd_step`` over the group's whole array, a layer an index; 16 query
    heads a K/V head go through ``paged_attn`` and ``kv_chunk_attn``, the
    ungated experts through the grouped kernels.  (It is refused the fused
    programs.)"""
    import dataclasses

    from distributedtensorflow_tpu.models import nemotron3_super_ep4
    from distributedtensorflow_tpu.serve import kv_cache, pool_check

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(
        nemotron3_super_ep4(), max_seq=2048, vocab_size=1024, num_experts=64,
        experts_held=16, pattern="ME*M")
    names = cfg.state_rows.names
    assert names == ("conv_tail", "ssd_state")
    programs = pool_check.pool_programs(
        cfg, max_slots=128, num_blocks=16384, block_size=16, chunk=256,
        draft=4, sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    # a pool the compiler does not move whole into faster memory (at 4,096
    # blocks of 2 K/V heads it does: a prefetch, not a re-layout)
    _, rows, width = kv_cache.pool_shape(1, 16384, 16, 256)
    report = pool_check.check_pool_programs(
        {program: programs[program]}, layer_elems=rows * width,
        state=(128, cfg.state_rows.arrays(cfg.dtype)), state_names=names)
    assert pool_check.failures(report, pools=2, state=names) == []
    assert report[program]["donated"] == sorted(("k_pool", "v_pool") + names)
    fn, args = programs[program]
    text = fn.lower(*args).as_text()
    assert text.count('kernel_name = "moe_grouped_up"') == 1
    if program == "prefill_chunk":
        assert len(args) == 7       # the count of real tokens
        assert "ssd_step" not in text       # plain jax.numpy: no kernel
        assert text.count('kernel_name = "kv_chunk_attn"') == 1
    else:
        assert text.count('kernel_name = "ssd_step"') == 2  # a layer an index
        assert text.count('kernel_name = "paged_attn"') == 1


@pytest.mark.parametrize("program", ["prefill_chunk", "decode"])
def test_conv_tail_program_keeps_pools_and_tails_in_place_on_a_v5e(
        program, monkeypatch):
    """The lfm2 family at its published widths, four layers deep (a dense
    conv layer, an attention layer and two conv layers with all 64 experts)
    and with a small vocabulary: the programs take the K/V pools and the
    state group's one array, the convolution tails, as they are stored, copy
    or transpose no layer of either, and hand all three back in place; decode
    attends heads of 64 (four query heads a K/V head) through ``paged_attn``
    and routes through the grouped kernels; a prefill chunk attends through
    the plain loop (the chunk kernel wants a head of 128) and takes the count
    of real tokens.  (It is refused the fused programs.)"""
    import dataclasses

    from distributedtensorflow_tpu.models import lfm2_24b_a2b
    from distributedtensorflow_tpu.serve import kv_cache, pool_check

    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(
        lfm2_24b_a2b(), max_seq=2048, vocab_size=1024,
        layer_types=("conv", "full_attention", "conv", "conv"))
    assert cfg.state_rows.names == ("conv_tail",)
    programs = pool_check.pool_programs(
        cfg, max_slots=96, num_blocks=4096, block_size=16, chunk=256, draft=4,
        sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    _, rows, width = kv_cache.pool_shape(1, 4096, 16, 8 * 64)
    report = pool_check.check_pool_programs(
        {program: programs[program]}, layer_elems=rows * width,
        state=(96, cfg.state_rows.arrays(cfg.dtype)))
    assert pool_check.failures(report, state=cfg.state_rows.names) == []
    assert report[program]["donated"] == ["conv_tail", "k_pool", "v_pool"]
    fn, args = programs[program]
    text = fn.lower(*args).as_text()
    # one call an expert layer
    assert text.count('kernel_name = "moe_grouped_up"') == 3
    if program == "prefill_chunk":
        assert len(args) == 7       # the count of real tokens
        assert text.count('kernel_name = "kv_chunk_attn"') == 0
    else:
        assert text.count('kernel_name = "paged_attn"') == 1


def test_decode_program_attends_through_the_kernel_on_a_v5e(monkeypatch):
    """``jit_decode`` of GPT-2 medium as the chip builds it (24 layers, the
    cells' shapes; lowered for the TPU, which needs no compile): every
    layer attends through the ``paged_attn`` kernel — one body, lowered
    once, the layer a prefetched scalar — and nothing gathers every table
    column of every slot.  The fallback to the plain formulation is silent
    (a block size that stops dividing 128, a head size the kernel does not
    take), and costs 50 ms an iteration: it fails here, not in a
    benchmark."""
    one_chip = NamedSharding(_v5e_mesh(1), P())
    _as_on_the_chip(monkeypatch)
    fn, args = _gpt2m_pool_programs(one_chip)["decode"]
    text = fn.lower(*args).as_text()
    calls = re.findall(r"call @(\w*paged_attn\w*)\(", text)
    assert len(calls) == 24 and len(set(calls)) == 1, calls
    assert text.count('kernel_name = "paged_attn"') == 1
    # (slots, table columns, block, row) or (slots, max_context, row)
    gathered = re.findall(r"tensor<32x(?:64x16|1024)x1024xbf16>", text)
    assert not gathered, gathered[:3]

    plain = _gpt2m_pool_programs(one_chip, attn_impl="xla")["decode"]
    text = plain[0].lower(*plain[1]).as_text()
    assert "paged_attn\"" not in text
    assert re.search(r"tensor<32x(?:64x16|1024)x1024xbf16>", text)


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "2x2"])
def test_gpt2_medium_step_runs_flash_fwd_once_a_layer_and_fits_a_v5e(
        chips, monkeypatch):
    """The benchmark's training step (``gpt_medium_lm``, 64 x 1024 tokens a
    chip, state and step as ``train.py`` makes them) compiled for the
    described chip, and per shard on the 2x2 mesh: a remat'd block keeps o
    and the log-sum-exp of its flash kernel, so the step holds one
    ``flash_fwd`` a layer — the backward's second run is gone — beside one
    ``flash_bwd``; and what that keeps (138 MB a layer) still leaves the
    step under 14.0 GB of the chip's 16 by ``memory_analysis`` (13.11 GB on
    one chip, 12.75 a shard of four; 9.92 / 9.55 with nothing kept:
    ``PERF.md`` section 4, PR 37)."""
    import os
    import sys

    import distributedtensorflow_tpu.models  # noqa: F401 — for on_tpu
    import distributedtensorflow_tpu.workloads  # noqa: F401

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    monkeypatch.syspath_prepend(tools)
    import train_step_memory

    devices = list(_v5e_mesh(chips).devices.flat)
    _as_on_the_chip(monkeypatch)
    compiled, mesh, wl = train_step_memory.compile_step(
        "gpt_medium_lm", 64, 1024, devices)
    row = train_step_memory.report(compiled, mesh, wl)
    layers = wl.model.cfg.num_layers
    assert layers == 24 and wl.global_batch_size == 64 * chips
    assert row["kernels"]["flash_fwd"] == layers, row["kernels"]
    assert row["kernels"]["flash_bwd"] == layers, row["kernels"]
    assert row["total_bytes"] <= 14.0e9, row
    assert row["flash_layout"] == "qkv_tiles"
    # a device's o (64, 1024, 16 * 64) bf16 and LSE (64, 16, 1024) float32
    assert (row["attn_residuals"], row["attn_residual_bytes_per_layer"]) == (
        "saved", 64 * 1024 * (1024 * 2 + 16 * 4))
    # the one 1024 x 1024 block a sequence is walked in row sub-tiles
    assert (row["flash_causal_tile"], row["flash_causal_share"]) == (
        256, 0.625)
    # the head's backward forms its dlogits once, a chunk of 4,096 of a
    # device's 64 x 1023 tokens at a time: one lowering of each kernel
    assert (row["xent_products_per_step"], row["xent_dlog_chunk_tokens"]
            ) == (4, 4096)
    assert {k: n for k, n in row["kernels"].items() if "xent" in k} == {
        "fused_xent_fwd": 2, "fused_xent_bwd_dx": 1, "fused_xent_bwd_dw": 1}
    # the update is a region of the step of its own
    # (``train.engine.separate_update``, PR 49): no fusion of the optimized
    # module holds both a product and an op of scope ``optimizer`` (96 did,
    # ``qkv``, ``proj``, ``fc_in`` and ``fc_out`` of 24 layers, and ran a
    # fifth slower than product and update apart)
    fusions = re.findall(r"^%?fused_computation[\w.]* [^\n]*\{\n(.*?)^\}",
                         compiled.as_text(), re.S | re.M)
    products = [f for f in fusions if " convolution(" in f]
    updates = [f for f in fusions if "/optimizer/" in f]
    assert len(products) >= 4 * layers and len(updates) >= 4 * layers
    assert not [f for f in products if "/optimizer/" in f]
