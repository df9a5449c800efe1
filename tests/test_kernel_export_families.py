"""The serving families past GPT-2 compiled for a described v5e, no chip:
each family's programs, a few layers deep at its published widths, take
their pools (and a state group's arrays) as they are stored, copy or
transpose no layer of either, hand all of them back in place and reach the
kernels they should (``test_kernel_export.py`` has the how and the kernels
alone).
"""

import re

import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from kernel_export_cases import as_on_the_chip, v5e_mesh

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

    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
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

    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
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

    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
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

    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
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


#: A state-group family a case, at its published widths, a few layers deep and
#: with a small vocabulary: ``preset`` and what is cut (``cut``), the slots,
#: blocks and K/V (or latent) row width its programs are built at, the state
#: group's arrays by name (``cfg.state_rows.names``), the pools beside them,
#: how many times each program's lowered text names a kernel (0: that name
#: is nowhere in it), and ``scan_calls``, the calls of the one lowered scan
#: body in a prefill chunk where the scan is a kernel.
_STATE_FAMILIES = {
    # layer 1 attending, three Mamba: the prefill chunk scans through
    # ``ssm_chunk_scan``, lowered once for the three layers, and attends 20
    # heads on one K/V head through ``kv_chunk_attn``
    "jamba-scan_state": dict(
        preset="jamba2_3b",
        cut=dict(num_layers=4, attn_layer_period=4, attn_layer_offset=1),
        slots=32, blocks=16384, row=128,
        names=("conv_tail", "scan_state"), pools=("k_pool", "v_pool"),
        scan_calls=3,
        kernels={"prefill_chunk": {"ssm_chunk_scan": 1, "kv_chunk_attn": 1},
                 "decode": {"paged_attn": 1}}),
    # a dense KDA layer, an MLA layer and a KDA layer with 16 of 128 experts
    # held: a state group (three tails, the matrix states) beside a LATENT
    # full group.  A prefill chunk scans in plain ``jax.numpy`` (the chunked
    # form: no kernel), decode steps through ``kda_step`` over the group's
    # whole array, a layer an index; the latent rows go through joyai's
    # kernels.  The cell's 128 slots: at 32 the compiler leaves the tails as
    # they lie; at 128 it wrote the q, k, v product with the slots across
    # lanes and re-laid all three tail arrays out, a copy in and a copy out,
    # until models/ling.py pinned the product's rows
    "ling-delta_state": dict(
        preset="ling3_flash_ep8",
        cut=dict(num_experts=128, experts_held=16,
                 layer_types=("kda", "mla", "kda"), swiglu_limits=()),
        slots=128, blocks=4096, row=640,
        names=("q_tail", "k_tail", "v_tail", "delta_state"),
        pools=("k_pool",),
        kernels={"prefill_chunk": {"kda_chunk_scan": 0,
                                   "latent_chunk_attn": 1},
                 "decode": {"kda_step": 2, "paged_latent_attn": 1}}),
    # a Mamba-2 layer, an expert layer with 16 of 64 experts held, an
    # attention layer, a Mamba-2 layer: a state group (the tail, the matrix
    # states) beside a K/V full group, the expert layer in neither.  A
    # prefill chunk scans in plain ``jax.numpy``, decode steps through
    # ``ssd_step``, a layer an index; 16 query heads a K/V head go through
    # ``paged_attn`` and ``kv_chunk_attn``, the ungated experts through the
    # grouped kernels.  A pool the compiler does not move whole into faster
    # memory (at 4,096 blocks of 2 K/V heads it does: a prefetch, not a
    # re-layout)
    "nemotron_h-ssd_state": dict(
        preset="nemotron3_super_ep4",
        cut=dict(num_experts=64, experts_held=16, pattern="ME*M"),
        slots=128, blocks=16384, row=256,
        names=("conv_tail", "ssd_state"), pools=("k_pool", "v_pool"),
        kernels={"prefill_chunk": {"moe_grouped_up": 1, "ssd_step": 0,
                                   "kv_chunk_attn": 1},
                 "decode": {"moe_grouped_up": 1, "ssd_step": 2,
                            "paged_attn": 1}}),
    # G G G A with 16 of 64 experts held in every layer: a state group (one
    # tail over [q | k | v], the value heads' matrix states) beside a K/V
    # full group at heads of 256.  A prefill chunk scans in plain
    # ``jax.numpy`` (the scalar-gate body), decode steps through ling's
    # ``kda_step`` with the gate broadcast into its rows; 8 query heads a
    # K/V head of two lane tiles go through ``paged_attn`` and
    # ``kv_chunk_attn``, the experts through the grouped kernels
    "qwen3_next-delta_state": dict(
        preset="qwen3_next_ep4",
        cut=dict(num_experts=64, experts_held=16, num_layers=4),
        slots=48, blocks=16384, row=512,
        names=("conv_tail", "delta_state"), pools=("k_pool", "v_pool"),
        kernels={"prefill_chunk": {"moe_grouped_up": 4, "kda_step": 0,
                                   "kv_chunk_attn": 1},
                 "decode": {"moe_grouped_up": 4, "kda_step": 3,
                            "paged_attn": 1}}),
    # a dense conv layer, an attention layer and two conv layers with all 64
    # experts (one grouped call an expert layer): a state group of ONE array,
    # the convolution tails.  Decode attends heads of 64 (four query heads a
    # K/V head) through ``paged_attn``; a prefill chunk attends through the
    # plain loop (the chunk kernel wants a head of 128)
    "lfm2-conv_tail": dict(
        preset="lfm2_24b_a2b",
        cut=dict(layer_types=("conv", "full_attention", "conv", "conv")),
        slots=96, blocks=4096, row=8 * 64,
        names=("conv_tail",), pools=("k_pool", "v_pool"),
        kernels={"prefill_chunk": {"moe_grouped_up": 3, "kv_chunk_attn": 0},
                 "decode": {"moe_grouped_up": 3, "paged_attn": 1}}),
}


@pytest.mark.parametrize("program", ["prefill_chunk", "decode"])
@pytest.mark.parametrize("family", list(_STATE_FAMILIES))
def test_state_program_keeps_pools_and_state_in_place_on_a_v5e(
        family, program, monkeypatch):
    """Every family that keeps a state a slot (``_STATE_FAMILIES``): its
    programs take the pools and the state group's arrays as they are stored,
    copy or transpose no layer of either, and hand all of them back in
    place; each reaches the kernels its case names, and a prefill chunk
    takes the count of its real tokens.  (All are refused the fused
    programs.)"""
    import dataclasses

    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.serve import kv_cache, pool_check

    case = _STATE_FAMILIES[family]
    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(getattr(models, case["preset"])(),
                              max_seq=2048, vocab_size=1024, **case["cut"])
    names, slots = cfg.state_rows.names, case["slots"]
    assert names == case["names"]
    programs = pool_check.pool_programs(
        cfg, max_slots=slots, num_blocks=case["blocks"], block_size=16,
        chunk=256, draft=4, sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    _, rows, width = kv_cache.pool_shape(1, case["blocks"], 16, case["row"])
    report = pool_check.check_pool_programs(
        {program: programs[program]}, layer_elems=rows * width,
        state=(slots, cfg.state_rows.arrays(cfg.dtype)), state_names=names)
    assert pool_check.failures(
        report, pools=len(case["pools"]), state=names) == []
    assert report[program]["donated"] == sorted(case["pools"] + names)
    fn, args = programs[program]
    text = fn.lower(*args).as_text()
    for kernel, count in case["kernels"][program].items():
        if count:
            assert text.count(f'kernel_name = "{kernel}"') == count, kernel
        else:
            assert kernel not in text
    if program == "prefill_chunk":
        assert len(args) == 7       # the count of real tokens
        if "scan_calls" in case:
            calls = re.findall(r"call @(\w*scan_call\w*)\(", text)
            assert len(calls) == case["scan_calls"], calls
            assert len(set(calls)) == 1, calls


@pytest.mark.parametrize("program", ["prefill_chunk", "decode",
                                     "copy_block"])
def test_looped_program_carries_the_pools_through_its_loop_in_place_on_a_v5e(
        program, monkeypatch):
    """The ouro family at its published widths, two layers deep and with a
    small vocabulary, four passes: 8 layer slots a pool.  The pools are the
    device loop's carry (``serve.model._through_passes``): the programs copy
    or convert no layer slot of either outside ``paged_attn`` and hand both
    back in place, and each kernel is lowered ONCE — two call sites in the
    loop's one body, the pool layer ``u * 2 + l`` a traced scalar —, not
    once a pass.  (It is refused the fused programs.)"""
    import dataclasses

    from distributedtensorflow_tpu.models import ouro_2_6b
    from distributedtensorflow_tpu.serve import kv_cache, pool_check

    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(ouro_2_6b(), num_layers=2, vocab_size=1024)
    programs = pool_check.pool_programs(
        cfg, max_slots=16, num_blocks=2048, block_size=16, chunk=256, draft=4,
        sharding=one_chip)
    assert sorted(programs) == ["copy_block", "decode", "prefill_chunk"]
    slots, rows, width = kv_cache.pool_shape(
        len(kv_cache.layer_groups(cfg)["full"]), 2048, 16, 2048)
    assert slots == 8
    report = pool_check.check_pool_programs(
        {program: programs[program]}, layer_elems=rows * width)
    assert pool_check.failures(report) == []
    assert report[program]["k_pool"] == \
        "bf16[8,32784,2048]{2,1,0:T(8,128)(2,1)}"
    if program != "copy_block":
        fn, args = programs[program]
        text = fn.lower(*args).as_text()
        kernel, call = {"decode": ("paged_attn", "_paged_attn_call"),
                        "prefill_chunk": ("kv_chunk_attn", "_kv_chunk_call")
                        }[program]
        assert text.count(f'kernel_name = "{kernel}"') == 1
        calls = re.findall(rf"call @(\w*{call}\w*)\(", text)
        assert len(calls) == 2 and len(set(calls)) == 1, calls
        assert text.count("stablehlo.while") >= 1
