"""Serving engine tests: allocator, paged KV, scheduler invariants.

The load-bearing checks: (1) the paged decode path produces the SAME
tokens as the dense ``models.generate`` loop (cache correctness is
equivalence, not plausibility — same bar as test_generate.py); (2) the
scheduler never leaks a slot or a block, admits strictly FIFO, and
actually batches continuously (a freed slot is refilled while other
sequences keep decoding).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import GPTLM, generate, gpt_tiny
from distributedtensorflow_tpu.ops import attention
from distributedtensorflow_tpu.ops.attention import KVRows
from distributedtensorflow_tpu.serve import (
    BlockAllocator,
    Engine,
    OutOfBlocksError,
    PagedKVCache,
    QueueFullError,
)

# ---------------------------------------------------------------- allocator


def test_allocator_all_or_nothing():
    a = BlockAllocator(4)
    got = a.alloc(3)
    assert got is not None and len(got) == 3 and len(set(got)) == 3
    assert a.alloc(2) is None  # only 1 free: no partial grant
    assert a.free_blocks == 1 and a.used_blocks == 3
    a.free(got)
    assert a.free_blocks == 4 and a.used_blocks == 0
    assert a.alloc(4) is not None


def test_allocator_double_free_raises():
    a = BlockAllocator(2)
    got = a.alloc(1)
    a.free(got)
    with pytest.raises(OutOfBlocksError, match="double free|not allocated"):
        a.free(got)
    with pytest.raises(OutOfBlocksError):
        a.free([99])


def test_allocator_exhaustion_and_reuse():
    a = BlockAllocator(3)
    x = a.alloc(3)
    assert a.alloc(1) is None
    a.free(x[:1])
    y = a.alloc(1)
    assert y == x[:1]  # the freed block is reused


# ------------------------------------------------------------- paged kv cache


def _kv(num_blocks=8, block_size=4, max_context=16, max_slots=2):
    return PagedKVCache(
        num_layers=1, rows=KVRows(heads=2, kv_heads=2, head_dim=4),
        max_slots=max_slots,
        num_blocks=num_blocks, block_size=block_size,
        max_context=max_context,
    )


def test_kv_admit_release_no_leak():
    kv = _kv()
    assert kv.admit(0, tokens=6)  # 2 blocks of 4
    assert kv.allocator.used_blocks == 2
    assert (kv.block_tables[0, :2] != kv.scratch_block).all()
    assert (kv.block_tables[0, 2:] == kv.scratch_block).all()
    kv.note_written(0, 5)
    stats = kv.stats()
    assert stats["slots_occupied"] == 1
    assert stats["allocated_tokens"] == 8 and stats["resident_tokens"] == 5
    assert stats["fragmentation"] == pytest.approx(3 / 8)
    kv.release(0)
    assert kv.allocator.used_blocks == 0
    assert (kv.block_tables == kv.scratch_block).all()
    assert kv.stats()["fragmentation"] == 0.0


def test_kv_admit_pressure_and_guards():
    kv = _kv(num_blocks=3, block_size=4, max_context=16)
    assert kv.admit(0, tokens=12)  # 3 blocks: pool drained
    assert not kv.admit(1, tokens=4)  # pressure: all-or-nothing False
    with pytest.raises(OutOfBlocksError, match="occupied"):
        kv.admit(0, tokens=4)
    with pytest.raises(ValueError, match="max_context"):
        kv.release(0) or kv.admit(0, tokens=32)
    kv.admit(0, tokens=4)
    with pytest.raises(OutOfBlocksError, match="capacity"):
        kv.note_written(0, 5)


# ------------------------------------------------- paged attention equivalence


def _stored_form(pool, rng):
    """A hand-built ``(blocks, block_size, Hkv, D)`` pool as layer 1 of a
    two-layer pool in the stored form (``serve.kv_cache``: token rows of
    all heads); layer 0 is noise that attention must not read."""
    rows = pool.reshape(-1, pool.shape[2] * pool.shape[3])
    return jnp.asarray(np.stack(
        [rng.standard_normal(rows.shape).astype(rows.dtype), rows]))


#: lengths around the kernel's edges (a block of 16, a stretch of 128, a
#: grid step of 512, the table's 1024), mixed in one batch with inactive
#: slots (0: length 1 on the scratch block, as ``make_decode_fn`` feeds them)
KERNEL_LENS = [1, 15, 16, 17, 0, 127, 128, 129, 511, 512, 513, 0, 1023, 1024]

#: (H, Hkv, dtype of the kernel case or None for the plain one): the plain
#: formulation against numpy at toy shapes, then the kernel (interpreted)
#: against the plain formulation at GPT-2's heads of 64
PAGED_CASES = [(4, 4, None), (4, 2, None), (16, 16, "float32"),
               (16, 8, "float32"), (16, 2, "float32"), (16, 16, "bfloat16")]


@pytest.mark.parametrize(
    "h,h_kv,kernel", PAGED_CASES,
    ids=[f"{h}-{kv}" + (f"-kernel-d64-{k}" if k else "")
         for h, kv, k in PAGED_CASES])
def test_paged_decode_attention_matches_dense(h, h_kv, kernel):
    """Gather-through-page-table attention == plain masked attention over
    the same (contiguously laid out) K/V, incl. GQA grouping; and the
    kernel that reads only the blocks a slot holds == that formulation at
    heads of 64 (two heads a 128-lane tile), every table entry past a
    slot's length pointing at the scratch block."""
    from distributedtensorflow_tpu.ops.attention import (
        paged_decode_attention,
        paged_window_decode_attention,
    )

    if kernel:
        d, bs, max_blocks, lens = 64, 16, 64, KERNEL_LENS
    else:
        d, bs, max_blocks, lens = 8, 4, 3, [5, 9]
    dtype = jnp.dtype(kernel or "float32")
    b = len(lens)
    rng = np.random.default_rng(0)
    cap = max_blocks * bs
    k_seq = rng.standard_normal((b, cap, h_kv, d)).astype(np.float32)
    v_seq = rng.standard_normal((b, cap, h_kv, d)).astype(np.float32)
    q = rng.standard_normal((b, h, d)).astype(np.float32)

    # scatter the sequences into a shuffled pool (+1 scratch block); only
    # the blocks a sequence holds are mapped
    held = [-(-n // bs) for n in lens]
    num_blocks = sum(held)
    perm = iter(rng.permutation(num_blocks))
    k_pool = rng.standard_normal((num_blocks + 1, bs, h_kv, d)).astype(
        np.float32)
    v_pool = rng.standard_normal(k_pool.shape).astype(np.float32)
    tables = np.full((b, max_blocks), num_blocks, np.int32)
    for i in range(b):
        for j in range(held[i]):
            phys = int(next(perm))
            tables[i, j] = phys
            k_pool[phys] = k_seq[i, j * bs: (j + 1) * bs]
            v_pool[phys] = v_seq[i, j * bs: (j + 1) * bs]
    seq_lens = jnp.asarray(np.maximum(lens, 1), jnp.int32)
    args = (jnp.asarray(q, dtype), _stored_form(k_pool, rng).astype(dtype),
            _stored_form(v_pool, rng).astype(dtype), jnp.asarray(tables),
            seq_lens)

    if kernel:
        want, got = (np.asarray(paged_window_decode_attention(
            *args, layer=1, block_size=bs, impl=impl), np.float32)
            for impl in ("xla", "pallas"))
        # bf16: one rounding of the output (2**-8 relative; the values
        # are of order 1) and the running softmax's other order of sums
        np.testing.assert_allclose(
            got, want, rtol=0, atol=2e-5 if kernel == "float32" else 2e-2)
        return
    out = np.asarray(paged_decode_attention(*args, layer=1, block_size=bs))
    g = h // h_kv
    for i in range(b):
        n = lens[i]
        for head in range(h):
            kh = k_seq[i, :n, head // g]       # (n, d)
            vh = v_seq[i, :n, head // g]
            s = kh @ q[i, head] / np.sqrt(d)
            w = np.exp(s - s.max())
            w /= w.sum()
            np.testing.assert_allclose(
                out[i, head], w @ vh, rtol=1e-5, atol=1e-5
            )


# ------------------------------------------------------ the pool's stored form

#: block size x dtype x kv heads (gpt_tiny has 4 heads: MHA and GQA)
POOL_FORMS = [(bs, dt, kv) for bs in (4, 16)
              for dt in (jnp.float32, jnp.bfloat16) for kv in (4, 2)]
_POOL_IDS = [f"bs{bs}-{jnp.dtype(dt).name}-kv{kv}" for bs, dt, kv in POOL_FORMS]


@pytest.mark.parametrize("block_size,dtype,kv_heads", POOL_FORMS,
                         ids=_POOL_IDS)
def test_pool_prefill_scatter_gather_round_trip(block_size, dtype, kv_heads):
    """What ``prefill_chunk`` writes into the pool, through a shuffled page
    table, is the K/V of the plain forward for those positions; no row
    outside the slot's blocks is written; and a chunk reads its slot's
    earlier chunks from the pool alone: the chunk at 16 gives the same
    last-row logits whether or not another slot's chunk ran in between."""
    from distributedtensorflow_tpu.models.generate import prefill
    from distributedtensorflow_tpu.serve.kv_cache import pool_shape
    from distributedtensorflow_tpu.serve.model import make_programs

    cfg = dataclasses.replace(gpt_tiny(), dtype=dtype, max_seq=32,
                              num_kv_heads=kv_heads)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                             cfg.vocab_size)
    params = GPTLM(cfg).init(jax.random.PRNGKey(0), ids)["params"]
    num_blocks, chunk = 8, 8
    shape = pool_shape(cfg.num_layers, num_blocks, block_size,
                       cfg.kv_heads * cfg.head_dim)
    assert shape == (cfg.num_layers, (num_blocks + 1) * block_size,
                     cfg.kv_heads * cfg.head_dim)

    def table_row(blocks):
        # a slot's pages, out of order; the rest point at scratch
        row = np.full((32 // block_size,), num_blocks, np.int32)
        row[: len(blocks)] = blocks
        return {"full": jnp.asarray(row)}

    blocks = [5, 2, 7, 0, 3, 6][: -(-24 // block_size)]
    mine, other = table_row(blocks), table_row([1, 4][: -(-chunk // block_size)])
    prefill_chunk = make_programs(
        cfg, chunk=chunk, block_size=block_size,
        layers={"full": tuple(range(cfg.num_layers))}).prefill_chunk

    def run(pools, tokens, start, row):
        # the pools are donated: hand the program its own copy
        pools = jax.tree.map(jnp.array, pools)
        return prefill_chunk(params, pools, tokens, jnp.int32(start), row,
                             jnp.int32(chunk - 1))

    pools = {"full": (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))}
    for start in (0, 8):
        _, pools = run(pools, ids[0, start:start + chunk], start, mine)
    logits, filled = run(pools, ids[0, 16:], 16, mine)
    _, between = run(pools, ids[0, 3:3 + chunk], 0, other)
    again, _ = run(between, ids[0, 16:], 16, mine)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(logits))

    _, dense = prefill(params, ids, jnp.arange(24)[None], cfg=cfg)
    rows = np.concatenate([b * block_size + np.arange(block_size)
                           for b in blocks])[:24]
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    for pool, name in zip(filled["full"], ("cached_key", "cached_value")):
        pool = np.asarray(pool, np.float32)
        written = np.zeros(shape[1], bool)
        written[rows] = True
        assert np.all(pool[:, ~written] == 0)
        assert np.all(np.any(pool[:, written] != 0, axis=-1))
        for i in range(cfg.num_layers):
            # the flax decode cache is (1, Hkv, max_seq, D)
            want = np.asarray(dense[f"h{i}"]["attn"][name], np.float32)[
                0, :, :24].transpose(1, 0, 2).reshape(24, -1)
            np.testing.assert_allclose(pool[i, rows], want, atol=tol, rtol=0)


def _dense_forward(family, params, ids, cfg):
    """Logits (S, V) of one sequence through a family's layer functions
    under plain causal attention, no cache."""
    from distributedtensorflow_tpu.ops.attention import xla_attention

    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    x = family.embed(params, ids, cfg)
    for i in range(cfg.num_layers):
        def attend(q, k, v, window=cfg.window_of(i)):
            return xla_attention(q[None], k[None], v[None], causal=True,
                                 window=window)[0]
        x, _ = family.block(params[f"h{i}"], x, cfg, i, positions, attend)
    return family.head(params, x, cfg)


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_serving_block_matches_the_training_block(dtype, kv_heads):
    """The two definitions of the GPT block: the layer functions the serving
    programs are built from (``models.gpt.embed`` / ``block`` / ``head``)
    give the logits of flax ``GPTLM`` on the same parameters."""
    from distributedtensorflow_tpu.models import gpt

    cfg = dataclasses.replace(gpt_tiny(), dtype=dtype, max_seq=64,
                              num_kv_heads=kv_heads)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0,
                             cfg.vocab_size)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    # default init gives logits of ~0.02: scale the matrices up so that a
    # wrong block would show
    params = jax.tree.map(lambda p: p * 4 if p.ndim == 2 else p, params)
    want = np.asarray(GPTLM(cfg).apply({"params": params}, ids)[0])
    got = np.asarray(_dense_forward(gpt, params, ids[0], cfg))
    assert got.dtype == want.dtype == np.float32
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    else:
        assert np.isfinite(got).all()
        assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.75
        assert np.median(np.abs(got - want)) < 0.1


def test_one_set_of_programs_for_every_family():
    """``make_programs`` gives a GPT-2 and an afmoe configuration the same
    class over their family modules, and neither keeps state a slot's next
    tenant would have to be told about."""
    from distributedtensorflow_tpu.models import afmoe, afmoe_tiny, gpt
    from distributedtensorflow_tpu.serve.model import make_programs

    built = {
        family: make_programs(cfg, chunk=8, block_size=4, layers=layers)
        for family, cfg, layers in (
            (gpt, gpt_tiny(), {"full": (0, 1)}),
            (afmoe, afmoe_tiny(), {"window": (0, 1), "full": (2,)}))}
    assert type(built[gpt]) is type(built[afmoe])
    for family, programs in built.items():
        assert programs.family is family
        assert not hasattr(programs, "forget")


@pytest.mark.parametrize("block_size,dtype,kv_heads", POOL_FORMS,
                         ids=_POOL_IDS)
def test_pool_copy_block_copies_one_block_of_every_layer(block_size, dtype,
                                                         kv_heads):
    from distributedtensorflow_tpu.serve.kv_cache import (
        _copy_block_fn,
        pool_shape,
    )

    shape = pool_shape(3, 6, block_size, kv_heads * 32)
    rng = np.random.default_rng(0)
    k0 = rng.standard_normal(shape).astype(jnp.dtype(dtype))
    v0 = rng.standard_normal(shape).astype(jnp.dtype(dtype))
    src, dst = 4, 1
    k1, v1 = _copy_block_fn(block_size)(
        (jnp.asarray(k0), jnp.asarray(v0)), jnp.int32(src), jnp.int32(dst))
    for before, after in ((k0, np.asarray(k1)), (v0, np.asarray(v1))):
        want = before.copy()
        want[:, dst * block_size:(dst + 1) * block_size] = \
            before[:, src * block_size:(src + 1) * block_size]
        np.testing.assert_array_equal(after, want)
        assert not np.array_equal(after, before)


@pytest.mark.parametrize("program", ["prefill_chunk", "decode",
                                     "fused_decode", "fused_decode_spec",
                                     "copy_block"])
def test_pool_is_donated_in_place(program):
    """The compiled module's ``input_output_alias`` names both pools: the
    donated input is the output's buffer, for every program that returns
    the pool."""
    from distributedtensorflow_tpu.serve import pool_check

    cfg = dataclasses.replace(gpt_tiny(), max_seq=32)
    fn, args = pool_check.pool_programs(
        cfg, max_slots=2, num_blocks=6, block_size=16, chunk=8, draft=2,
    )[program]
    text = fn.lower(*args).compile().as_text()
    assert pool_check.donated_pools(text) == {"k_pool", "v_pool"}


def test_pool_relayouts_reads_the_old_forms_copies():
    """``pool_relayouts`` on the lines the 5-D pool compiled to on the v5e
    (PERF.md §5, PR 25): the entry and exit copies count, what attention
    does inside its scope and a weight's cast do not."""
    from distributedtensorflow_tpu.serve.pool_check import pool_relayouts

    layer = 2049 * 16 * 16 * 64
    pool = "bf16[24,2049,16,16,64]"
    hlo = "\n".join([
        f'  %copy.180 = {pool}{{4,3,2,1,0:T(8,128)(2,1)}} copy(%k_pool.1), '
        'metadata={op_name="k_pool"}',
        f'  %copy.323 = {pool}{{1,4,3,2,0:T(8,128)(2,1)}} copy(%bitcast.201)',
        '  %convert.9 = f32[1,2049,16,16,64]{4,3,2,1,0:T(8,128)} '
        'convert(%slice.3), metadata={op_name="jit(decode)/h0/paged_attn/x"}',
        '  %convert.1 = bf16[50257,1024]{1,0:T(8,128)(2,1)} convert(%wte), '
        'metadata={op_name="jit(decode)/embed/cast_params/convert"}',
        '  %convert.2 = f32[32,1024,16,64]{3,2,1,0:T(8,128)} convert(%g.1)',
        f'  %fusion.5 = {pool}{{4,3,2,1,0:T(8,128)(2,1)}} fusion(%copy.180), '
        'kind=kLoop, metadata={op_name="jit(decode)/h0/kv_write/scatter"}',
    ])
    assert pool_relayouts(hlo, layer) == [
        "copy bf16[24,2049,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} k_pool",
        "copy bf16[24,2049,16,16,64]{1,4,3,2,0:T(8,128)(2,1)}",
    ]


# ---------------------------------------------------------------- the engine


@pytest.fixture(scope="module")
def served_model():
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, max_seq=64)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size)
    params = GPTLM(cfg).init(rng, ids)["params"]
    return cfg, params, ids


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_queue", 8)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("max_context", 64)
    return Engine(params, cfg, **kw)


def _drain(engine, reqs, max_steps=500):
    """Drive the scheduler synchronously until every request is terminal."""
    for _ in range(max_steps):
        if all(r._done.is_set() for r in reqs):
            return
        engine.step()
    raise AssertionError("engine did not finish within max_steps")


def test_engine_matches_dense_generate(served_model):
    """Continuous-batching greedy output == the dense whole-batch scan,
    token for token, for BOTH batch rows served as separate requests."""
    cfg, params, ids = served_model
    dense = np.asarray(generate(params, ids, cfg=cfg, max_new_tokens=6))
    eng = _engine(cfg, params)
    reqs = [
        eng.submit([int(t) for t in np.asarray(ids)[i]], max_new_tokens=6)
        for i in range(2)
    ]
    _drain(eng, reqs)
    for i, r in enumerate(reqs):
        assert r.status == "ok"
        assert r.tokens == list(dense[i, 8:])


def test_decode_program_hands_back_the_arg_max_of_its_logits(served_model):
    """``jit_decode`` returns, beside the float32 logits, their arg-max a
    slot as int32 — the token the engine takes for a greedy slot without
    fetching the logits.  Under a head whose every column stands twice
    each row's maximum is a tie, and the first of the two is taken, as
    ``np.argmax`` takes it: the served tokens are the parent's."""
    cfg, params, ids = served_model
    half = cfg.vocab_size // 2
    emb = params["wte"]["embedding"]
    tied = {**params, "wte": {
        "embedding": emb.at[half:2 * half].set(emb[:half])}}
    eng = _engine(cfg, tied, max_slots=3)
    decode, checked = eng.programs.decode, set()

    def spy(*args):
        out = decode(*args)
        logits, greedy = np.asarray(out[0]), np.asarray(out[1])
        assert greedy.dtype == np.int32 and greedy.shape == (3,)
        for slot, req in enumerate(eng._slots):
            if req is not None and req._prefill_done:
                g = int(greedy[slot])
                assert g == int(np.argmax(logits[slot])) < half
                assert logits[slot, g] == logits[slot, g + half]
                checked.add(slot)
        return out

    eng.programs.decode = spy
    reqs = [eng.submit([int(t) for t in np.asarray(ids)[i]][:n],
                       max_new_tokens=m)
            for i, (n, m) in enumerate(((8, 9), (5, 6)))]
    _drain(eng, reqs)
    assert checked == {0, 1}        # the third slot never held a request
    assert all(r.status == "ok" and max(r.tokens) < half for r in reqs)
    assert eng.counters["logit_fetches"] == 0


def test_engine_refuses_a_model_of_window_layers_only(served_model):
    cfg, params, _ = served_model
    with pytest.raises(ValueError, match="window layers only"):
        _engine(dataclasses.replace(cfg, attn_window=16), params)


def test_engine_matches_dense_generate_bf16():
    """The same equivalence at the PRODUCTION dtype: the hand-rolled
    paged decode program's bf16/fp32 recipe must track models/gpt.py
    exactly (gpt_tiny's default dtype is bfloat16)."""
    cfg = dataclasses.replace(gpt_tiny(), max_seq=64)  # default bf16
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (1, 8), 0, cfg.vocab_size)
    params = GPTLM(cfg).init(rng, ids)["params"]
    dense = np.asarray(generate(params, ids, cfg=cfg, max_new_tokens=5))
    eng = _engine(cfg, params)
    req = eng.submit([int(t) for t in np.asarray(ids)[0]], max_new_tokens=5)
    _drain(eng, [req])
    assert req.tokens == list(dense[0, 8:])


def test_engine_kernel_decode_matches_plain_decode_at_heads_of_64():
    """The decode program of a GPT with heads of 64 serves the same greedy
    tokens through the kernel that reads only the blocks a slot holds
    (interpreted) as through the plain formulation, two requests side by
    side whose contexts cross a stretch of 128 rows and a grid step of 512;
    the engine says which formulation its decode program was built with."""
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, num_heads=2,
                              max_seq=640)
    rng = jax.random.PRNGKey(0)
    ids = np.asarray(jax.random.randint(rng, (2, 504), 0, cfg.vocab_size))
    params = GPTLM(cfg).init(rng, jnp.asarray(ids[:, :8]))["params"]
    served = {}
    for impl in ("xla", "pallas"):
        eng = _engine(dataclasses.replace(cfg, attn_impl=impl), params,
                      block_size=16, prefill_chunk=64, max_context=640)
        reqs = [eng.submit([int(t) for t in ids[0, :120]], max_new_tokens=16),
                eng.submit([int(t) for t in ids[1]], max_new_tokens=16)]
        _drain(eng, reqs)
        assert [r.status for r in reqs] == ["ok", "ok"]
        served[impl] = ([r.tokens for r in reqs],
                        eng.state()["decode_attention"],
                        [r for r in eng.step_records() if r["occupancy"]])
    assert served["pallas"][0] == served["xla"][0]
    assert served["pallas"][1] == "paged_attn"
    assert served["xla"][1] == "plain"
    # the walk's census, where the kernel walks: a slot's trips of a
    # stretch's rows over the layers (both requests' contexts cross a
    # stretch's end on the way), beside the most trips two slots' tables of
    # 640 rows can take
    stretch = attention.PAGED_STRETCH
    assert [r["paged_stretches_walked"] for r in served["pallas"][2]] == [
        cfg.num_layers * sum(-(-(n + i + 1) // stretch) for n in (120, 504))
        for i in range(15)]
    assert {r["paged_stretches_capacity"] for r in served["pallas"][2]} == {
        cfg.num_layers * 2 * -(-640 // stretch)}
    assert not any("paged_stretches_walked" in r for r in served["xla"][2])
    # a block size that does not divide the kernel's 128 rows: the silent
    # fallback is named
    assert _engine(dataclasses.replace(cfg, attn_impl="pallas"), params,
                   block_size=24, prefill_chunk=24, max_context=624
                   ).state()["decode_attention"] == "plain"


def test_continuous_batching_freed_slot_admission(served_model):
    """A short request's slot is refilled while the long one still
    decodes: occupancy hits 2, the queued request is admitted into the
    freed slot, and nothing leaks."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, max_slots=2)
    long_req = eng.submit(prompt, max_new_tokens=24)
    short = eng.submit(prompt, max_new_tokens=2)
    queued = eng.submit(prompt, max_new_tokens=2)
    _drain(eng, [long_req, short, queued])
    assert [r.status for r in (long_req, short, queued)] == ["ok"] * 3
    assert eng.occupancy_max == 2
    assert eng.counters["admits_into_freed_slot"] >= 1
    # the queued request joined while the long one was still active
    assert queued.t_done < long_req.t_done
    # no slot / block leak
    assert all(s is None for s in eng._slots)
    assert eng.kv.allocator.used_blocks == 0
    assert eng.kv.allocator.free_blocks == eng.kv.allocator.num_blocks


def test_fifo_admission_under_backpressure(served_model):
    """One slot, three requests: admission (and completion) strictly
    follows arrival order — a later small request never jumps the head."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, max_slots=1)
    a = eng.submit(prompt, max_new_tokens=8)
    b = eng.submit(prompt[:3], max_new_tokens=2)  # smaller, arrives later
    c = eng.submit(prompt[:2], max_new_tokens=2)
    _drain(eng, [a, b, c])
    assert a.t_admit <= b.t_admit <= c.t_admit
    assert a.t_done <= b.t_done <= c.t_done


def test_block_pressure_blocks_admission_head_of_line(served_model):
    """With a pool too small for two concurrent requests, the second
    waits for the first's eviction even though a slot is free."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]  # 8 tokens
    # footprint(8 prompt, 4 new) = 12 tokens = 3 blocks of 4; pool of 4
    # blocks fits one request plus nothing.
    eng = _engine(cfg, params, max_slots=2, num_blocks=4)
    a = eng.submit(prompt, max_new_tokens=4)
    b = eng.submit(prompt, max_new_tokens=4)
    eng.step()  # admits a only (b would need 3 more blocks)
    assert a.status == "active" and b.status == "queued"
    assert eng.occupancy_max <= 1
    _drain(eng, [a, b])
    assert a.status == "ok" and b.status == "ok"
    assert b.t_admit >= a.t_done  # strictly after the eviction freed blocks
    assert eng.kv.allocator.used_blocks == 0


def test_queue_full_rejects(served_model, tmp_path):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, max_queue=2, logdir=str(tmp_path))
    r1 = eng.submit(prompt, max_new_tokens=2)
    r2 = eng.submit(prompt, max_new_tokens=2)
    with pytest.raises(QueueFullError, match="queue full"):
        eng.submit(prompt, max_new_tokens=2)
    assert eng.counters["rejected"] == 1
    _drain(eng, [r1, r2])
    eng.stop()
    rows = [json.loads(line) for line in
            open(os.path.join(tmp_path, "requests.jsonl"))]
    statuses = [r["status"] for r in rows]
    assert statuses.count("rejected") == 1
    assert statuses.count("ok") == 2


def test_eos_finishes_early_and_frees_blocks(served_model):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params)
    probe = eng.submit(prompt, max_new_tokens=4)
    _drain(eng, [probe])
    eos = probe.tokens[1]  # a token the greedy run provably emits early
    req = eng.submit(prompt, max_new_tokens=16, eos_token_id=eos)
    _drain(eng, [req])
    assert req.status == "ok"
    assert req.finish_reason == "eos"
    assert req.tokens[-1] == eos
    assert len(req.tokens) <= 2 + 1  # stopped at the eos, not at length
    assert eng.kv.allocator.used_blocks == 0


def test_submit_validation(served_model):
    cfg, params, _ = served_model
    eng = _engine(cfg, params)
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit([], max_new_tokens=2)
    with pytest.raises(ValueError, match="vocab|in \\[0"):
        eng.submit([cfg.vocab_size + 1], max_new_tokens=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError, match="max_context"):
        eng.submit([1] * 60, max_new_tokens=30)
    eng2 = _engine(cfg, params, max_new_cap=4)
    with pytest.raises(ValueError, match="cap"):
        eng2.submit([1, 2], max_new_tokens=8)
    # sampling params are rejected at submit, never on the loop thread
    with pytest.raises(ValueError, match="top_k"):
        eng.submit([1, 2], max_new_tokens=2, top_k=cfg.vocab_size + 1)
    with pytest.raises(ValueError, match="temperature"):
        eng.submit([1, 2], max_new_tokens=2, temperature=float("nan"))
    with pytest.raises(ValueError, match="temperature"):
        eng.submit([1, 2], max_new_tokens=2, temperature=-1.0)
    # a request the WHOLE (oversubscribed) pool can't hold is rejected at
    # the door — otherwise it would wedge the FIFO head forever
    eng3 = _engine(cfg, params, num_blocks=2)  # 8-token pool, ctx 64
    with pytest.raises(ValueError, match="pool"):
        eng3.submit([1] * 10, max_new_tokens=8)
    # an unservable configuration fails at construction, not per request
    with pytest.raises(ValueError, match="prefill_chunk"):
        _engine(cfg, params, prefill_chunk=128, max_context=64)


def test_stopped_engine_refuses_work(served_model):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params)
    r = eng.submit(prompt, max_new_tokens=2)
    _drain(eng, [r])
    eng.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        eng.submit(prompt, max_new_tokens=2)
    with pytest.raises(RuntimeError, match="restarted"):
        eng.start()
    assert eng.healthy is False


def test_sampling_deterministic_by_seed(served_model):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params)
    kw = dict(max_new_tokens=8, temperature=1.0, top_k=16)
    a = eng.submit(prompt, seed=1, **kw)
    b = eng.submit(prompt, seed=1, **kw)
    c = eng.submit(prompt, seed=2, **kw)
    _drain(eng, [a, b, c])
    assert a.tokens == b.tokens
    assert a.tokens != c.tokens


def test_requests_jsonl_passes_schema_checker(served_model, tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import check_metrics_schema as checker

    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, logdir=str(tmp_path), log_every=2)
    reqs = [eng.submit(prompt, max_new_tokens=n) for n in (2, 5, 3)]
    _drain(eng, reqs)
    eng.stop()
    req_path = os.path.join(tmp_path, "requests.jsonl")
    errors, _ = checker.check_file(req_path)
    assert errors == [], errors
    # the metrics stream the engine writes is schema-clean too
    errors, _ = checker.check_file(os.path.join(tmp_path, "metrics.jsonl"))
    assert errors == [], errors
    assert checker.main([req_path]) == 0


def test_engine_state_is_json_safe(served_model):
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params)
    r = eng.submit(prompt, max_new_tokens=3)
    eng.step()  # mid-flight state with an occupied slot
    mid = eng.state()
    json.dumps(mid)  # must serialize as-is
    assert mid["active_slots"] in (0, 1)
    _drain(eng, [r])
    final = eng.state()
    json.dumps(final)
    assert final["counters"]["ok"] == 1
    assert final["kv"]["blocks_used"] == 0


def test_run_report_serving_section(served_model, tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import run_report

    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, logdir=str(tmp_path), log_every=1)
    reqs = [eng.submit(prompt, max_new_tokens=n) for n in (4, 2)]
    _drain(eng, reqs)
    eng.stop()
    report = run_report.build_report(str(tmp_path))
    srv = report["serving"]
    assert srv["requests"] == 2
    assert srv["by_status"]["ok"] == 2
    assert srv["tokens_generated"] == 6
    assert srv["e2e_s"]["p99"] > 0
    assert srv["ttft_s"]["p99"] > 0
    text = run_report.render(report)
    assert "serving: 2 request(s)" in text
    assert report["parse_errors"] == 0


def test_engine_emits_request_trace_spans(served_model, tmp_path):
    """ISSUE 11 distributed tracing: a completed request leaves
    serve.request/queue/prefill/decode rows in trace.jsonl under the
    request's trace_id (client-supplied or generated)."""
    from distributedtensorflow_tpu.obs.tracing import TraceRecorder

    cfg, params, ids = served_model
    rec = TraceRecorder(str(tmp_path / "trace.jsonl")).install()
    try:
        eng = _engine(cfg, params)
        prompt = [int(t) for t in np.asarray(ids)[0]]
        traced = eng.submit(prompt, max_new_tokens=4, trace_id="client-abc")
        generated = eng.submit(prompt, max_new_tokens=4)
        assert generated.trace_id and generated.trace_id != "client-abc"
        _drain(eng, [traced, generated])
    finally:
        rec.uninstall()
        rec.close()
    rows = [json.loads(l)
            for l in (tmp_path / "trace.jsonl").read_text().splitlines()]
    spans = [r for r in rows if r.get("kind") == "span"]
    mine = [s for s in spans if s["trace_id"] == "client-abc"]
    assert {s["name"] for s in mine} == {
        "serve.request", "serve.queue", "serve.prefill", "serve.decode",
    }
    root = next(s for s in mine if s["name"] == "serve.request")
    assert all(s["parent_id"] == root["span_id"]
               for s in mine if s is not root)
    assert root["request"] == traced.id
    # phase durations tile the request: queue+prefill+decode ~ e2e
    parts = sum(s["dur_s"] for s in mine if s is not root)
    assert parts == pytest.approx(root["dur_s"], abs=0.005)
    # the untraced request got its own generated trace
    other = [s for s in spans if s["trace_id"] == generated.trace_id]
    assert {s["name"] for s in other} >= {"serve.request", "serve.queue"}
    # requests.jsonl rows carry the id too (written by _log_request when
    # a logdir engine is used) — validated via the row shape here
    assert traced.trace_id == "client-abc"


def test_engine_submit_rejects_bad_trace_id(served_model):
    cfg, params, ids = served_model
    eng = _engine(cfg, params)
    prompt = [int(t) for t in np.asarray(ids)[0]]
    with pytest.raises(ValueError):
        eng.submit(prompt, max_new_tokens=2, trace_id="x" * 65)
    with pytest.raises(ValueError):
        eng.submit(prompt, max_new_tokens=2, trace_id="")


# ----------------------------------------------- refcounts / CoW (ISSUE 14)


def test_allocator_refcount_sharing():
    """A double-mapped block frees only at its LAST decref."""
    a = BlockAllocator(4)
    (b,) = a.alloc(1)
    a.incref(b)
    assert a.refcount(b) == 2
    assert a.total_refs == 2 and a.used_blocks == 1
    a.decref(b)
    assert a.refcount(b) == 1 and a.free_blocks == 3  # still held
    a.decref(b)
    assert a.refcount(b) == 0 and a.free_blocks == 4
    with pytest.raises(OutOfBlocksError, match="double free|not allocated"):
        a.decref(b)
    with pytest.raises(OutOfBlocksError, match="neither active nor cached"):
        a.incref(b)  # a free block cannot be mapped


def test_allocator_release_to_cached_vs_free():
    """refcount->0: a registered block parks in the cached LRU (contents
    stay reusable), an unregistered one goes straight to the free list."""
    a = BlockAllocator(4)
    reg, plain = a.alloc(2)
    a.register(reg)
    a.free([reg, plain])
    assert a.cached_blocks == 1 and a.free_blocks == 3
    assert a.used_blocks == 0
    # a cached block reactivates through incref (prefix-cache hit)
    a.incref(reg)
    assert a.refcount(reg) == 1 and a.cached_blocks == 0
    # unregistering a refcount-0 cached block releases it for real
    a.decref(reg)
    assert a.cached_blocks == 1
    a.unregister(reg)
    assert a.cached_blocks == 0 and a.free_blocks == 4


def test_allocator_eviction_lru_never_touches_mapped():
    """Under pressure alloc evicts cached blocks LRU-first — and can
    NEVER evict a mapped block, no matter the pressure."""
    evicted = []
    a = BlockAllocator(4, on_evict=evicted.append)
    blocks = a.alloc(4)
    for b in blocks[:3]:
        a.register(b)
    a.decref(blocks[0])  # LRU order: 0 then 2 (1 stays mapped)
    a.decref(blocks[2])
    assert a.cached_blocks == 2 and a.free_blocks == 0
    got = a.alloc(1)  # grantable via eviction of the LRU cached block
    assert got is not None
    assert evicted == [blocks[0]]
    assert a.evictions == 1
    # two mapped blocks + one cached remain; a 3-block grant is impossible
    # even though 1 free + ... no: 0 free, 1 cached -> alloc(2) must fail
    assert a.alloc(2) is None
    assert a.refcount(blocks[1]) == 1  # the mapped blocks were untouched
    assert a.refcount(blocks[3]) == 1
    got2 = a.alloc(1)  # evicts the remaining cached block
    assert got2 is not None and evicted == [blocks[0], blocks[2]]


def _tokens(rng, n, vocab=512):
    return [int(t) for t in rng.integers(0, vocab, size=n)]


def test_kv_prefix_lookup_register_and_cap():
    """register_prefix indexes whole prompt blocks; lookup walks the
    chained hashes and is capped so >= 1 token is always left to
    prefill."""
    kv = _kv(num_blocks=8, block_size=4, max_context=32)
    rng = np.random.default_rng(0)
    prompt = _tokens(rng, 10)  # 2 full blocks + 2 tail tokens
    pages = kv.admit(0, tokens=12, prompt=prompt)
    assert pages is not None and pages.prefix_tokens == 0  # cold index
    kv.register_prefix(0, prompt)
    assert kv.stats()["prefix_blocks_indexed"] == 2
    # identical prompt: both full blocks match
    assert kv.lookup_prefix(prompt) == pages.blocks[:2]
    # divergence INSIDE block 2 invalidates block 2's chain, keeps block 1
    fork = prompt[:5] + [(prompt[5] + 1) % 512] + prompt[6:]
    assert kv.lookup_prefix(fork) == pages.blocks[:1]
    # a prompt that IS exactly the indexed blocks: the cap keeps the last
    # block out so its final token still runs through prefill
    assert kv.lookup_prefix(prompt[:8]) == pages.blocks[:1]
    assert kv.lookup_prefix(prompt[:4]) == []  # 4 tokens: cap -> 0 blocks


def test_kv_admit_maps_prefix_and_rolls_back_under_pressure():
    kv = _kv(num_blocks=6, block_size=4, max_context=24, max_slots=3)
    rng = np.random.default_rng(1)
    prompt = _tokens(rng, 9)  # blocks: 2 full + tail
    first = kv.admit(0, tokens=12, prompt=prompt)
    kv.register_prefix(0, prompt)
    kv.release(0)  # -> both full blocks parked cached
    assert kv.allocator.cached_blocks == 2
    # hit: the new request maps the 2 cached blocks + allocs 1 fresh
    hit = kv.admit(1, tokens=12, prompt=prompt)
    assert hit is not None and hit.prefix_tokens == 8
    assert hit.blocks[:2] == first.blocks[:2]
    assert kv.allocator.refcount(first.blocks[0]) == 1
    # double-map: a THIRD identical request shares at refcount 2
    hit2 = kv.admit(2, tokens=12, prompt=prompt)
    assert hit2 is not None and hit2.prefix_tokens == 8
    assert kv.allocator.refcount(first.blocks[0]) == 2
    # pressure rollback: slot 1+2 hold 2 shared + 2 exclusive; free pool
    # is 2 blocks -> a 16-token no-prefix admission needs 4, must fail
    # WITHOUT leaking refcounts on anything
    kv.release(2)
    refs_before = kv.allocator.total_refs
    assert kv.admit(2, tokens=16, prompt=_tokens(rng, 15)) is None
    assert kv.allocator.total_refs == refs_before
    assert kv.stats()["prefix_hits"] == 2


def test_kv_cow_copies_shared_block_before_write():
    kv = _kv(num_blocks=8, block_size=4, max_context=16, max_slots=2)

    def rows(block):  # a block's token rows in the pool's stored form
        return slice(block * kv.block_size, (block + 1) * kv.block_size)

    rng = np.random.default_rng(2)
    prompt = _tokens(rng, 8)
    kv.admit(0, tokens=8, prompt=prompt)
    # give the pool recognizable contents for the copy check
    kv.pools = (kv.pools[0].at[:, rows(kv.pages[0].blocks[0])].set(7.0),
                kv.pools[1])
    kv.register_prefix(0, prompt)
    kv.release(0)
    a = kv.admit(0, tokens=8, prompt=prompt)
    b = kv.admit(1, tokens=8, prompt=prompt)
    shared = a.blocks[0]
    assert b.blocks[0] == shared
    assert kv.allocator.refcount(shared) == 2
    # a write into the shared block must copy first
    assert kv.ensure_writable(1, 0) == "cow"
    assert kv.pages[1].blocks[0] != shared
    assert kv.allocator.refcount(shared) == 1
    assert kv.allocator.refcount(kv.pages[1].blocks[0]) == 1
    assert int(kv.block_tables[1, 0]) == kv.pages[1].blocks[0]
    np.testing.assert_array_equal(
        np.asarray(kv.pools[0][:, rows(kv.pages[1].blocks[0])]),
        np.asarray(kv.pools[0][:, rows(shared)]),
    )
    assert kv.stats()["cow_copies"] == 1
    # slot 0's block is now exclusive but still INDEXED: writing it must
    # drop the index entry instead of corrupting future lookups
    assert kv.ensure_writable(0, 0) == "unregistered"
    assert kv.lookup_prefix(prompt + [1]) == []
    # and a plain exclusive unindexed block needs nothing
    assert kv.ensure_writable(1, 0) is None


def test_kv_lookup_verifies_tokens_not_just_hashes():
    """A chain-hash collision must degrade to a MISS, never map another
    prompt's blocks (hash() is 64-bit and non-cryptographic — the
    unverified-lookup failure mode is silent cross-request K/V reuse).
    Simulated by planting a colliding entry with foreign tokens."""
    kv = _kv(num_blocks=8, block_size=4, max_context=16)
    rng = np.random.default_rng(4)
    prompt = _tokens(rng, 8)
    kv.admit(0, tokens=8, prompt=prompt)
    kv.register_prefix(0, prompt)
    kv.release(0)
    assert len(kv.lookup_prefix(prompt + [1])) == 2  # honest entries hit
    h, _tok = next(iter(kv._chained_hashes(prompt)))
    block, tok = kv._hash_to_block[h]
    kv._hash_to_block[h] = (block, tuple((t + 1) % 512 for t in tok))
    assert kv.lookup_prefix(prompt + [1]) == []  # collision -> miss
    kv._hash_to_block[h] = (block, tok)
    assert len(kv.lookup_prefix(prompt + [1])) == 2


def test_kv_eviction_drops_index_entry():
    kv = _kv(num_blocks=3, block_size=4, max_context=12)
    rng = np.random.default_rng(3)
    prompt = _tokens(rng, 9)
    kv.admit(0, tokens=12, prompt=prompt)
    kv.register_prefix(0, prompt)
    kv.release(0)
    assert len(kv.lookup_prefix(prompt)) == 2
    # a full-pool admission evicts both cached blocks
    assert kv.admit(1, tokens=12) is not None
    assert kv.lookup_prefix(prompt) == []
    assert kv.stats()["prefix_evictions"] == 2
    assert kv.stats()["prefix_blocks_indexed"] == 0


# ----------------------------------- prefix caching + budget in the engine


def test_engine_prefix_cache_parity_and_accounting(served_model):
    """With prefix caching AND a prefill budget on, a repeated prompt is
    served from shared blocks — and the output stays token-for-token
    equal to the dense whole-batch scan (greedy path)."""
    cfg, params, ids = served_model
    dense = np.asarray(generate(params, ids[:1], cfg=cfg, max_new_tokens=6))
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, prefix_cache=True, prefill_budget=4)
    first = eng.submit(prompt, max_new_tokens=6)
    _drain(eng, [first])
    second = eng.submit(prompt, max_new_tokens=6)
    _drain(eng, [second])
    assert first.tokens == list(dense[0, 8:])
    assert second.tokens == list(dense[0, 8:])
    # 8-token prompt, block 4: 1 full block mapped (cap leaves the rest)
    assert first.cached_prefix_tokens == 0
    assert second.cached_prefix_tokens == 4
    assert second.prefill_tokens == 4
    st = eng.state()
    assert st["kv"]["prefix_hits"] == 1
    assert st["kv"]["prefix_lookups"] == 2
    assert st["kv"]["prefix_cached_tokens"] == 4
    assert eng.counters["prefill_tokens"] == 8 + 4
    assert st["prefix_cache"] is True
    assert st["kv"]["prefix_hit_rate"] == pytest.approx(0.5)
    assert st["kv"]["prefix_blocks_indexed"] >= 1
    # everything released cleanly: shared blocks parked cached, not leaked
    assert st["kv"]["blocks_used"] == 0
    assert st["kv"]["blocks_cached"] >= 1


def test_engine_prefix_cache_longer_prompt_reuses_header(served_model):
    """The few-shot pattern: a LONGER prompt sharing the indexed header
    maps the header blocks and prefills only its own tail — and matches
    the dense scan run on the long prompt."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    long_prompt = prompt + [int(t) for t in np.asarray(ids)[1]][:4]
    eng = _engine(cfg, params, prefix_cache=True)
    warm = eng.submit(prompt, max_new_tokens=2)
    _drain(eng, [warm])
    req = eng.submit(long_prompt, max_new_tokens=5)
    _drain(eng, [req])
    assert req.cached_prefix_tokens == 8  # both header blocks mapped
    dense = np.asarray(generate(
        params, jnp.asarray([long_prompt]), cfg=cfg, max_new_tokens=5
    ))
    assert req.tokens == list(dense[0, len(long_prompt):])


def test_engine_seeded_sampling_invariant_under_prefix_reuse(served_model):
    """Seeded temperature/top-k sampling draws identical tokens whether
    the prompt was prefilled from scratch or mapped from the prefix cache
    (logit bitwise-equality under reuse)."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    kw = dict(max_new_tokens=8, temperature=0.8, top_k=24, seed=5)
    eng = _engine(cfg, params, prefix_cache=True, prefill_budget=4)
    warm = eng.submit(prompt, **kw)  # cold: full prefill, no mapping
    _drain(eng, [warm])
    hit = eng.submit(prompt, **kw)   # identical seed, cached prefix
    _drain(eng, [hit])
    assert warm.cached_prefix_tokens == 0
    assert hit.cached_prefix_tokens > 0
    assert hit.tokens == warm.tokens


def test_budget_long_prompt_cannot_stall_decode(served_model):
    """Fairness bound: with a prefill budget of one chunk, an admitted
    long prompt delays the running request's next token by at most one
    chunk per iteration — the victim gains exactly one token every
    scheduler iteration while the intruder fills."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    intruder_prompt = [int(t) for t in
                       np.asarray(ids).reshape(-1)] * 3  # 48 tokens
    eng = _engine(cfg, params, prefill_budget=4, max_context=64)
    victim = eng.submit(prompt, max_new_tokens=40)
    while not victim.tokens:
        eng.step()
    intruder = eng.submit(intruder_prompt, max_new_tokens=2)
    # 48-token prompt / 4-token chunks = 12 fill iterations
    for i in range(12):
        before = len(victim.tokens)
        eng.step()
        assert len(victim.tokens) == before + 1, (
            f"victim stalled at fill iteration {i}"
        )
    assert intruder.tokens, "intruder prefill should have completed"
    _drain(eng, [victim, intruder])
    assert victim.status == "ok" and intruder.status == "ok"
    # and the budget actually spread the fill: >= 12 prefill iterations
    assert eng.prefill_iters >= 12


def test_unbudgeted_engine_prefills_to_completion(served_model):
    """prefill_budget=None keeps the PR-6 behavior: the whole prompt
    fills in one iteration (all chunks), then decode resumes."""
    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params)
    victim = eng.submit(prompt, max_new_tokens=8)
    while not victim.tokens:
        eng.step()
    intruder = eng.submit(prompt * 4, max_new_tokens=2)  # 32 tokens
    eng.step()  # ONE iteration runs all 8 chunks
    assert intruder.tokens  # first token already sampled
    _drain(eng, [victim, intruder])


def test_prefix_requests_jsonl_fields_and_schema(served_model, tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import check_metrics_schema as checker

    from distributedtensorflow_tpu.obs.registry import Registry

    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    # isolated registry: the engine's metrics.prom must carry only the
    # serve_* families, not whatever earlier tests left in the default
    eng = _engine(cfg, params, prefix_cache=True, prefill_budget=8,
                  logdir=str(tmp_path), log_every=1, registry=Registry())
    warm = eng.submit(prompt, max_new_tokens=3)
    _drain(eng, [warm])  # indexes the prompt's full blocks
    reqs = [eng.submit(prompt, max_new_tokens=3) for _ in range(2)]
    _drain(eng, reqs)
    eng.stop()
    rows = [json.loads(line) for line in
            open(os.path.join(tmp_path, "requests.jsonl"))]
    ok = [r for r in rows if r["status"] == "ok"]
    assert all(
        r["cached_prefix_tokens"] + r["prefill_tokens"]
        == r["prompt_tokens"] for r in ok
    )
    assert sum(r["cached_prefix_tokens"] > 0 for r in ok) == 2
    for path in ("requests.jsonl", "metrics.jsonl", "metrics.prom"):
        errors, _ = checker.check_file(os.path.join(tmp_path, path))
        assert errors == [], (path, errors)
    # a mangled split must be CAUGHT by the checker
    bad = dict(ok[0], cached_prefix_tokens=ok[0]["cached_prefix_tokens"] + 1)
    p = tmp_path / "requests_bad.jsonl"
    p.write_text(json.dumps(bad) + "\n")
    errors, _ = checker.check_file(str(p))
    assert any("prompt_tokens" in e for e in errors)


def test_run_report_prefix_section(served_model, tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import run_report

    cfg, params, ids = served_model
    prompt = [int(t) for t in np.asarray(ids)[0]]
    eng = _engine(cfg, params, prefix_cache=True, prefill_budget=4,
                  logdir=str(tmp_path), log_every=1)
    warm = eng.submit(prompt, max_new_tokens=3)
    _drain(eng, [warm])  # indexes the prompt's full blocks
    reqs = [eng.submit(prompt, max_new_tokens=3) for _ in range(2)]
    _drain(eng, reqs)
    eng.stop()
    report = run_report.build_report(str(tmp_path))
    srv = report["serving"]
    pc = srv["prefix_cache"]
    assert pc["requests_with_hits"] == 2
    assert pc["cached_tokens"] == 8
    assert 0 < pc["cached_token_share"] < 1
    ts = srv["token_split"]
    assert ts["prompt_cached"] == 8
    assert ts["prompt_prefilled"] == 3 * 8 - 8
    assert ts["decode"] == 9
    bu = srv["prefill_budget"]
    assert bu["budget_tokens"] == 4
    assert 0 < bu["utilization"] <= 1.0
    text = run_report.render(report)
    assert "prefix cache: hit rate" in text
    assert "tokens/iteration" in text
    assert report["parse_errors"] == 0


# ------------------------------- the decode kernel's walk (``paged_attn``)

#: rows a trip of the kernel's walk holds, and of a walk of four trips
STRETCH = attention.PAGED_STRETCH
WALK = 4 * STRETCH
#: table columns a slot: 10 trips (a table its long slots fill over half of)
#: and 40 (a table far wider than any slot uses)
WALK_COLS = {"table_of_10_trips": 10 * STRETCH // 16,
             "table_of_40_trips": 40 * STRETCH // 16}
#: rows a slot attends; None is the table's whole capacity
WALK_RAGGED = {
    "nothing": [0],
    "one_row": [1],
    "a_row_short_of_a_stretch": [STRETCH - 1],
    "a_stretch": [STRETCH],
    "a_row_into_the_next": [STRETCH + 1],
    "a_row_into_the_fifth": [WALK + 1],
    "capacity": [None],
    # the first stretch of the slot after an empty one is started by the
    # empty slot's step, not under a last trip; the walk ends on empties
    "mixed": [2 * WALK + 5, 0, WALK + 200, 0, 0, 17, None, 0],
    "empty_first": [0, 0, WALK + 1, 3],
}
#: GPT-2's heads of 64, two a lane tile
WALK_HEADS = dict(heads=4, kv_heads=4, d=64)


@pytest.mark.parametrize("cols", list(WALK_COLS.values()), ids=list(WALK_COLS))
@pytest.mark.parametrize("lens", list(WALK_RAGGED.values()),
                         ids=list(WALK_RAGGED))
def test_paged_walk_over_ragged_slots(lens, cols, check_paged_walk):
    """The walk, interpreted, against each slot's dense sum and the plain
    gather: a slot's trips are counted from its length, the next slot's first
    stretch is started under this slot's last one, and a slot that attends
    nothing returns zeros."""
    check_paged_walk(lens=lens, cols=cols, **WALK_HEADS)


#: where a walk starts (``window``: the last rows of ``lens``; ``lo``: the
#: rows from there, a tumbling ring's way) beside the kernel's stretches
WALK_STARTS = {
    "window_wider_than_any_slot": dict(lens=[1, 300, 700], window=1024),
    "lo_inside_a_stretch": dict(lens=[700, 450, 90], window=300),
    "lo_at_a_stretch's_first_row": dict(lens=[WALK + 300, 812], window=300),
    "lo_past_the_first_stretch": dict(lens=[3 * WALK - 36, 0, 2 * WALK + 1],
                                      window=300),
    "lo_in_the_first_part's_last_block": dict(lens=[127 + 200], window=200),
    "a_window_of_several_trips": dict(lens=[4 * WALK + 77, 5 * WALK],
                                    window=2 * WALK + 100),
    "ring_just_opened": dict(lens=[2048 + 1, 1], lo=[2048, 0]),
    "ring_of_several_trips": dict(lens=[2048 + 1300, 4096 + 513, 0],
                                lo=[2048, 4096, 0]),
    "ring_from_inside_a_stretch": dict(lens=[1328 + 600], lo=[1328]),
    "ring_with_nothing_to_attend": dict(lens=[1024, 77], lo=[1024, 0]),
}


@pytest.mark.parametrize("case", list(WALK_STARTS.values()),
                         ids=list(WALK_STARTS))
def test_paged_walk_starts_at_the_first_attended_row(case, check_paged_walk):
    """A window layer's walk and a tumbling ring's start in the stretch that
    holds their first row, whose earlier blocks are freed (their columns name
    the scratch block, which holds NaN): nothing before it is read, the
    rows of its block before it are masked."""
    check_paged_walk(cols=WALK_COLS["table_of_40_trips"], with_lse=True,
                     **case, **WALK_HEADS)


def test_paged_walk_over_slots_that_share_blocks(check_paged_walk):
    """Two slots whose tables are one shared prefix: a shorter length over
    the same blocks reads its own rows, whatever slot walked before it."""
    check_paged_walk(lens=[WALK + 40, 300, WALK + 40, WALK - 7],
                     cols=WALK_COLS["table_of_10_trips"],
                     shared=[(0, 2), (0, 3)], **WALK_HEADS)


@pytest.mark.parametrize("case", [
    dict(lens=WALK_RAGGED["mixed"]),
    dict(lens=[3 * WALK - 36, 0, 2 * WALK + 1, 40], window=300),
], ids=["ragged", "window"])
def test_paged_walk_waits_for_every_copy_it_starts(case, check_paged_walk):
    """Under the TPU interpreter a copy lands when it is waited for, memory
    starts as NaN and races are looked for: the walk's result is the plain
    interpreter's, bit for bit (which finishes a copy at its start and cannot
    see a missing wait)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    kw = dict(cols=WALK_COLS["table_of_40_trips"], **case, **WALK_HEADS)
    got, _ = check_paged_walk(**kw, interpret=pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True,
        uninitialized_memory="nan"))
    races = interpret_pallas_call.races
    assert races is None or not races.races_found
    plain, _ = check_paged_walk(**kw)
    np.testing.assert_array_equal(got, plain)
