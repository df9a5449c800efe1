"""GPT-2 medium compiled for a described v5e, no chip: the attention block
moves no whole tensor, the serving programs keep the K/V pool in place and
decode attends through the kernel, the training step runs ``flash_fwd`` once
a layer and fits the chip (``test_kernel_export.py`` has the how and the
kernels alone).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from kernel_export_cases import BF16, as_on_the_chip, sds, sum32, v5e_mesh

def _attention_block_text(one_chip, batch=16):
    """GPT-2 medium's attention block (``models/gpt.py:
    CausalSelfAttention``: qkv -> attention -> proj) as the trainer's step
    holds it: forward and backward under ``jax.checkpoint``, the rotation's
    tables made once outside, compiled for the described chip."""
    import dataclasses

    from distributedtensorflow_tpu.models import gpt

    cfg = dataclasses.replace(gpt.gpt_medium(), max_seq=1024)
    attn = gpt.CausalSelfAttention(cfg)
    x = sds((batch, 1024, cfg.hidden_size), BF16, one_chip)
    positions = jnp.broadcast_to(jnp.arange(1024), (batch, 1024))
    params = jax.eval_shape(
        lambda: attn.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, BF16),
                          positions, True))
    params = jax.tree.map(lambda p: sds(p.shape, p.dtype, one_chip), params)

    def loss(params, x):
        tabs = gpt.block_rope_tables(
            cfg, None, x.shape[:2],
            fused=gpt.attention_layout(cfg, 1024) == "qkv_tiles")
        block = jax.checkpoint(
            lambda p, x: attn.apply(p, x, positions, True, tabs))
        return sum32(block(params, block(params, x)))

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

    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
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

    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
    programs = _gpt2m_pool_programs(one_chip, num_layers=2, vocab_size=1024)
    _, rows, width = kv_cache.pool_shape(2, 2048, 16, 16 * 64)
    report = pool_check.check_pool_programs(
        {program: programs[program]}, layer_elems=rows * width)
    assert pool_check.failures(report) == []
    # rows of all heads, minor dimension a multiple of 128: no padding
    assert report[program]["k_pool"] == \
        "bf16[2,32784,1024]{2,1,0:T(8,128)(2,1)}"


def test_decode_program_attends_through_the_kernel_on_a_v5e(monkeypatch):
    """``jit_decode`` of GPT-2 medium as the chip builds it (24 layers, the
    cells' shapes; lowered for the TPU, which needs no compile): every
    layer attends through the ``paged_attn`` kernel — one body, lowered
    once, the layer a prefetched scalar — and nothing gathers every table
    column of every slot.  The fallback to the plain formulation is silent
    (a block size that stops dividing 128, a head size the kernel does not
    take), and costs 50 ms an iteration: it fails here, not in a
    benchmark."""
    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
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

    devices = list(v5e_mesh(chips).devices.flat)
    as_on_the_chip(monkeypatch)
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
