"""The joyai family (latent attention over a latent paged cache, a wide
sigmoid router over narrow experts) on the CPU at a tiny size, seeded
weights, logits compared: the serving path (one latent pool, chunked prefill
in the decompressed form, absorbed paged decode) against
``benchmark/reference/joyai.py``'s full non-absorbed forward; the interleaved
rotary; the router; the kernel in interpret mode at the published head shape.

With float32 parameters the system and the reference do the same float32
arithmetic in another order (absorbed products, a running softmax over key
chunks, experts summed pair by pair): logits of size ~5 agree to 1e-4.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu import models
from distributedtensorflow_tpu.models import joyai
from distributedtensorflow_tpu.ops import attention
from distributedtensorflow_tpu.parallel import moe
from distributedtensorflow_tpu.serve.engine import Engine
from distributedtensorflow_tpu.serve.kv_cache import make_grouped_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-4


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "joyai.py")
    spec = importlib.util.spec_from_file_location("ref_joyai", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _config_dict(cfg: joyai.JoyaiConfig) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        n_routed_experts=cfg.num_experts, n_group=cfg.n_group,
        num_experts_per_tok=cfg.experts_per_token,
        norm_topk_prob=cfg.route_norm,
        routed_scaling_factor=cfg.route_scale,
        num_hidden_layers=cfg.num_layers,
        first_k_dense_replace=cfg.num_dense_layers)


@pytest.fixture(scope="module")
def f32_model():
    cfg = joyai.joyai_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~5, and a selection bias that decides picks
    params = joyai.init_params(cfg, jax.random.PRNGKey(32), std=0.2)
    return cfg, params


def _record_logits(eng):
    """``{request id: [the logits of every served position]}``, filled as
    ``eng`` runs (``tests/test_afmoe.py`` has the same spy)."""
    seen = {}
    sample, decode = eng._sample, eng.programs.decode

    def first(req, logits):
        if not req.tokens:
            seen.setdefault(req.id, []).append(np.array(logits))
        return sample(req, logits)

    def spy(*args):
        out = decode(*args)
        logits = np.asarray(out[0])
        for slot, req in enumerate(eng._slots):
            if req is not None and req._prefill_done:
                seen.setdefault(req.id, []).append(logits[slot].copy())
        return out

    eng._sample, eng.programs.decode = first, spy
    return seen


def _serve(cfg, params, jobs, **engine_kw):
    """Run ``jobs`` [(prompt, n_new)] through an Engine together; returns
    per job (tokens, logits of every served position)."""
    kw = dict(max_slots=3, block_size=4, prefill_chunk=8, max_context=128)
    eng = Engine(params, cfg, **{**kw, **engine_kw})
    seen = _record_logits(eng)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in jobs]
    for _ in range(2000):
        if all(r._done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.status == "ok" for r in reqs)
    for r in reqs:      # greedy: each token the arg-max of its row
        assert r.tokens == [int(np.argmax(row)) for row in seen[r.id]]
    return eng, [(r.tokens, np.stack(seen[r.id])) for r in reqs]


def _reference_logits(cfg, params, prompt, tokens):
    ids = jnp.asarray([list(prompt) + list(tokens)])
    full = REF.logits(params, ids, _config_dict(cfg))[0]
    return np.asarray(full)[len(prompt) - 1:-1]


def _prompt(seed, n, cfg):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n).tolist()


# (a) chunks, then decode through the latent cache, against the reference

@pytest.mark.parametrize("prompt_len,n_new", [
    (1, 3),      # a prompt of one token
    (3, 6),      # inside one chunk and one block
    (8, 9),      # exactly one chunk: the first decoded token opens a block
    (9, 25),     # a second chunk of one token
    (16, 16),    # two whole chunks, four whole blocks
    (21, 12),    # chunks of 8 end mid-block; decoding crosses block edges
    (33, 5),     # a fifth chunk of one token, decoding inside its block
    (40, 30),    # five whole chunks, ten blocks, then eight more
    (57, 20),    # eight chunks, the last of one token
])
def test_served_logits_match_the_reference(f32_model, prompt_len, n_new):
    cfg, params = f32_model
    prompt = _prompt(prompt_len, prompt_len, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, n_new)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert len(tokens) == n_new
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def _latent_case(seed, *, t, heads, rank, rope, nope, v, blocks, bs, layers=2):
    """Random queries, up-projections and a latent pool of ``blocks`` blocks
    (plus scratch) in the row form, lane-padded as the family pads it."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    form = attention.LatentRows(rank=rank, rope_dim=rope,
                                scale=(nope + rope) ** -0.5)
    pool = jax.random.normal(ks[0], (layers, (blocks + 1) * bs,
                                     form.widths[0]))
    pool = pool.at[..., rank + rope:].set(0.0)
    return dict(
        form=form, pool=pool,
        q_nope=jax.random.normal(ks[1], (t, heads, nope)),
        q_rope=jax.random.normal(ks[2], (t, heads, rope)),
        w_uk=jax.random.normal(ks[3], (rank, heads, nope)) * 0.1,
        w_uv=jax.random.normal(ks[4], (rank, heads, v)) * 0.1)


def _dense_latent(case, rows, qpos):
    """Non-absorbed causal attention of queries at ``qpos`` over the latent
    ``rows`` (S, width) at positions 0..S-1, from the definition."""
    form = case["form"]
    c_kv = rows[:, :form.rank]
    k_rope = rows[:, form.rank:form.rank + form.rope_dim]
    k_nope = jnp.einsum("kr,rhn->khn", c_kv, case["w_uk"])
    v = jnp.einsum("kr,rhv->khv", c_kv, case["w_uv"])
    s = (jnp.einsum("qhn,khn->hqk", case["q_nope"], k_nope)
         + jnp.einsum("qhr,kr->hqk", case["q_rope"], k_rope)) * form.scale
    ok = jnp.arange(rows.shape[0])[None, :] <= qpos[:, None]
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khv->qhv", p, v)


@pytest.mark.parametrize("start", [0, 3, 24, 37, 50, 64])
def test_chunk_formulation_matches_dense_causal_attention(start, monkeypatch):
    """A chunk of 16 queries from ``start`` against the slot's latent pages
    (scattered blocks of 4, stretches of 8 rows decompressed inside the
    loop): the running softmax against the dense definition."""
    bs, t, nb = 4, 16, 20
    monkeypatch.setattr(attention, "LATENT_STRETCH", 8)
    case = _latent_case(start, t=t, heads=4, rank=32, rope=8, nope=16, v=16,
                        blocks=nb, bs=bs)
    row = jnp.asarray(np.random.default_rng(start).permutation(nb), jnp.int32)
    got = attention.paged_latent_chunk_attention(
        case["q_nope"], case["q_rope"], jnp.int32(start), case["pool"], row,
        w_uk=case["w_uk"], w_uv=case["w_uv"], layer=1, block_size=bs,
        scale=case["form"].scale)
    rows = case["pool"].reshape(2, -1, bs, case["pool"].shape[-1])[1, row]
    rows = rows.reshape(nb * bs, -1)[:start + t]
    want = _dense_latent(case, rows, start + jnp.arange(t))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_absorbed_attention_is_the_non_absorbed_on_one_block():
    """``(q_nope W_UK^T) . c_kv = q_nope . (c_kv W_UK)`` and ``(sum p c_kv)
    W_UV = sum p (c_kv W_UV)``: one block of 16 rows, one query a slot."""
    bs = 16
    case = _latent_case(5, t=3, heads=4, rank=128, rope=8, nope=16, v=16,
                        blocks=3, bs=bs)
    tables = jnp.asarray([[2], [0], [1]], jnp.int32)
    lens = jnp.asarray([16, 5, 1], jnp.int32)
    got = attention.paged_latent_decode_attention(
        case["q_nope"], case["q_rope"], case["pool"], tables, lens,
        w_uk=case["w_uk"], w_uv=case["w_uv"], layer=0, block_size=bs,
        scale=case["form"].scale, impl="xla")
    for i in range(3):
        rows = case["pool"][0, int(tables[i, 0]) * bs:][:int(lens[i])]
        one = {**case, "q_nope": case["q_nope"][i:i + 1],
               "q_rope": case["q_rope"][i:i + 1]}
        want = _dense_latent(one, rows, jnp.asarray([int(lens[i]) - 1]))
        np.testing.assert_allclose(got[i], want[0], atol=2e-5, rtol=0)


@pytest.mark.parametrize("offset", [0, 5, 1000])
def test_interleaved_rotary_is_the_definition_at_an_offset(offset):
    """Pairs ``(x[2i], x[2i+1])`` rotated by ``pos * theta ** (-i / 32)``,
    the results laid out ``[firsts | seconds]``: complex multiplication,
    written out; and the score of a rotated query and key depends on their
    distance only."""
    d, theta = 64, 32e6
    x = jax.random.normal(jax.random.PRNGKey(offset), (7, 2, d))
    pos = offset + jnp.arange(7, dtype=jnp.int32)
    got = np.asarray(joyai.rope_interleaved(x, pos, theta), np.float64)
    z = np.asarray(x[..., 0::2], np.float64) \
        + 1j * np.asarray(x[..., 1::2], np.float64)
    ang = np.asarray(pos, np.float64)[:, None, None] \
        * theta ** (-np.arange(d // 2) / (d // 2))
    want = z * np.exp(1j * ang)
    # float32 angles: a thousand radians are known to 6e-5
    tol = 2e-5 if offset < 100 else 1e-3
    np.testing.assert_allclose(got[..., :d // 2], want.real, atol=tol)
    np.testing.assert_allclose(got[..., d // 2:], want.imag, atol=tol)
    far = np.asarray(joyai.rope_interleaved(x, pos + 123, theta), np.float64)
    np.testing.assert_allclose((got[0, 0] * got[3, 1]).sum(),
                               (far[0, 0] * far[3, 1]).sum(), atol=1e-3)


# (b) the kernel, interpreted, against the plain formulation

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 7e-2)])
def test_latent_decode_kernel_matches_the_plain_formulation(dtype, tol):
    """The published head shape — 32 heads over rows of 512 + 64 (640 with
    the lane padding), blocks of 16 — at lengths inside one block, across
    blocks, across the kernel's 128-row stretches and 512-row steps, and one
    row of the scratch block.  (bfloat16 outputs of size 4 to 8 step by
    0.031: two steps.)"""
    bs, nb, heads = 16, 80, 32
    case = _latent_case(1, t=5, heads=heads, rank=512, rope=64, nope=128,
                        v=128, blocks=nb, bs=bs)
    assert case["pool"].shape[-1] == 640
    case = {k: a.astype(dtype) if hasattr(a, "astype") else a
            for k, a in case.items()}
    lens = np.array([1, 16, 130, 600, 1], np.int32)
    tables = np.full((5, 40), nb, np.int32)         # unmapped -> scratch
    perm, o = np.random.default_rng(0).permutation(nb), 0
    for i, n in enumerate(lens[:4]):
        need = -(-int(n) // bs)
        tables[i, :need] = perm[o:o + need]
        o += need
    kw = dict(w_uk=case["w_uk"], w_uv=case["w_uv"], layer=1, block_size=bs,
              scale=case["form"].scale)
    args = (case["q_nope"], case["q_rope"], case["pool"],
            jnp.asarray(tables), jnp.asarray(lens))
    assert attention.paged_latent_formulation(bs, 640, 512, "pallas") \
        == "paged_latent_attn"
    want = attention.paged_latent_decode_attention(*args, impl="xla", **kw)
    got = attention.paged_latent_decode_attention(
        *args, impl="pallas", interpret=True, **kw)
    assert got.shape == (5, heads, 128) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)


#: rows a trip of the decode kernel's walk holds
WALK = attention.PAGED_LATENT_STRETCH
#: table columns a slot: 2.5 trips (joyai's cell in small: the long slots fill
#: over half of it) and 10 (ling's: a table far wider than any slot uses)
WALK_COLS = {"table_of_2.5_trips": 5 * WALK // 2 // 16,
             "table_of_10_trips": 10 * WALK // 16}

#: rows a slot attends; None is the table's whole capacity
RAGGED = {
    "nothing": [0],
    "one_row": [1],
    "a_row_short_of_a_stretch": [WALK - 1],
    "a_stretch": [WALK],
    "a_row_into_the_next": [WALK + 1],
    "capacity": [None],
    # the first stretch of the slot after an empty one is started by the
    # empty slot's step, not under a last trip; the walk ends on empties
    "mixed": [2 * WALK + 5, 0, WALK + 200, 0, 0, 17, None, 0],
    "empty_first": [0, 0, WALK + 1, 3],
}


def _ragged_case(lens, cols, shared=()):
    """Both cells' head shape — 32 heads over rows of 512 + 64, 640 with the
    lane padding — blocks of 16, scattered tables of ``cols`` columns; every
    block no slot attends holds NaN (the kernel must not let one into a
    product), unmapped columns point at the scratch block.  ``shared``:
    pairs of slots (a, b), b taking a's table row."""
    bs, heads = 16, 32
    lens = [cols * bs if n is None else n for n in lens]
    need = [-(-n // bs) for n in lens]
    nb = sum(need) + 3
    case = _latent_case(len(lens), t=len(lens), heads=heads, rank=512,
                        rope=64, nope=128, v=128, blocks=nb, bs=bs)
    tables = np.full((len(lens), cols), nb, np.int32)
    perm, o = np.random.default_rng(len(lens)).permutation(nb), 0
    for i, k in enumerate(need):
        tables[i, :k] = perm[o:o + k]
        o += k
    for a, b in shared:
        tables[b] = tables[a]
    attended = np.zeros(nb + 1, bool)
    for i, k in enumerate(need):
        attended[tables[i, :k]] = True
    pool = case["pool"].reshape(2, nb + 1, bs, -1)
    pool = jnp.where(jnp.asarray(attended)[None, :, None, None], pool,
                     jnp.nan).reshape(case["pool"].shape)
    kw = dict(w_uk=case["w_uk"], w_uv=case["w_uv"], layer=1, block_size=bs,
              scale=case["form"].scale)
    args = (case["q_nope"], case["q_rope"], pool, jnp.asarray(tables),
            jnp.asarray(lens, jnp.int32))
    return args, kw


@pytest.mark.parametrize("cols", list(WALK_COLS.values()), ids=list(WALK_COLS))
@pytest.mark.parametrize("lens", list(RAGGED.values()), ids=list(RAGGED))
def test_latent_decode_walk_over_ragged_slots(lens, cols):
    """The walk, interpreted, against the plain gather: a slot's trips are
    counted from its length, the next slot's first stretch is started under
    this slot's last one, and a slot that attends nothing returns zeros."""
    args, kw = _ragged_case(lens, cols)
    got = np.asarray(attention.paged_latent_decode_attention(
        *args, impl="pallas", interpret=True, **kw))
    # the gather reads every column: give it the attended rows alone
    want = np.asarray(attention.paged_latent_decode_attention(
        *args[:2], jnp.nan_to_num(args[2]), *args[3:], impl="xla", **kw))
    live = np.asarray(args[4]) > 0
    assert not np.isnan(got).any()
    np.testing.assert_array_equal(got[~live], 0.0)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=0)


def test_latent_decode_slots_that_share_blocks_read_the_same_rows():
    """Two slots whose tables are one shared prefix: the same query over the
    same length gives the same output, bit for bit, whatever slot walked
    before it; a shorter length over the same blocks reads its rows."""
    lens = [WALK + 40, 300, WALK + 40, WALK - 7]
    args, kw = _ragged_case(lens, WALK_COLS["table_of_2.5_trips"],
                            shared=[(0, 2), (0, 3)])
    q_nope, q_rope = (x.at[2].set(x[0]) for x in args[:2])
    args = (q_nope, q_rope, *args[2:])
    got = np.asarray(attention.paged_latent_decode_attention(
        *args, impl="pallas", interpret=True, **kw))
    want = np.asarray(attention.paged_latent_decode_attention(
        q_nope, q_rope, jnp.nan_to_num(args[2]), *args[3:], impl="xla", **kw))
    np.testing.assert_array_equal(got[0], got[2])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_latent_decode_walk_waits_for_every_copy_it_starts():
    """Under the TPU interpreter a copy lands when it is waited for, memory
    starts as NaN and races are looked for: the walk's result is the plain
    interpreter's, bit for bit (which finishes a copy at its start and cannot
    see a missing wait)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    args, kw = _ragged_case(RAGGED["mixed"], WALK_COLS["table_of_10_trips"])
    got = np.asarray(attention.paged_latent_decode_attention(
        *args, impl="pallas", interpret=pltpu.InterpretParams(
            dma_execution_mode="on_wait", detect_races=True,
            uninitialized_memory="nan"), **kw))
    races = interpret_pallas_call.races
    assert races is None or not races.races_found
    plain = np.asarray(attention.paged_latent_decode_attention(
        *args, impl="pallas", interpret=True, **kw))
    assert not np.isnan(got).any()
    np.testing.assert_array_equal(got, plain)


def _chunk_case(seed, dtype, *, t, heads, rank, rope, nope, v, nb, start,
                bs=16):
    """A chunk of ``t`` queries from ``start`` over scattered blocks; every
    block the chunk does not attend (the table's later columns, the rest of
    the pool, the scratch block) holds NaN and inf.  Returns the arguments,
    the keywords and the attended rows in order."""
    case = _latent_case(seed, t=t, heads=heads, rank=rank, rope=rope,
                        nope=nope, v=v, blocks=nb, bs=bs)
    row = np.random.default_rng(seed).permutation(nb).astype(np.int32)
    attended = row[:-(-(start + t) // bs)]
    poison = np.ones(nb + 1, bool)
    poison[attended] = False
    bad = jnp.where(jnp.arange(case["pool"].shape[-1]) % 2, jnp.nan, jnp.inf)
    pool = case["pool"].reshape(2, nb + 1, bs, -1)
    pool = jnp.where(jnp.asarray(poison)[None, :, None, None], bad, pool)
    case = {k: a.astype(dtype) if hasattr(a, "astype") else a
            for k, a in {**case, "pool": pool.reshape(2, -1, pool.shape[-1])
                         }.items()}
    rows = pool[1, attended].reshape(len(attended) * bs, -1)[:start + t]
    args = (case["q_nope"], case["q_rope"], jnp.int32(start), case["pool"],
            jnp.asarray(row))
    kw = dict(w_uk=case["w_uk"], w_uv=case["w_uv"], layer=1, block_size=bs,
              scale=case["form"].scale)
    return case, args, kw, rows.astype(dtype)


#: stretches of 128 rows, query tiles of 16, two heads a grid step: a chunk
#: of 32 queries of 4 heads is two tiles and two steps
SMALL_TILES = {"LATENT_STRETCH": 128, "LATENT_CHUNK_QUERIES": 16,
               "LATENT_CHUNK_HEADS": 2}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("start", [
    0,       # the chunk alone: every stretch masked
    21,      # not a multiple of the block
    112,     # one block short of a stretch: the chunk crosses into the next
    300,     # several stretches, the last two masked
    496,     # a query tile that precedes the last stretch skips it
    608,     # the chunk ends with the table's last block
])
def test_latent_chunk_kernel_matches_the_plain_loop(start, dtype, tol,
                                                   monkeypatch):
    """The chunk kernel, interpreted, against the plain loop and (float32)
    the dense definition: scattered page-table rows, NaN and inf in every
    block the chunk does not attend (blocks the kernel does not copy, rows
    of the last block it masks), heads and values padded to whole lane
    tiles."""
    for name, value in SMALL_TILES.items():
        monkeypatch.setattr(attention, name, value)
    t, nb = 32, 40
    case, args, kw, rows = _chunk_case(
        start, dtype, t=t, heads=4, rank=128, rope=8, nope=16, v=16, nb=nb,
        start=start)
    assert attention.paged_latent_chunk_formulation(
        16, case["pool"].shape[-1], 128, t, "pallas") == "latent_chunk_attn"
    got = attention.paged_latent_chunk_attention(
        *args, impl="pallas", interpret=True, **kw)
    assert got.shape == (t, 4, 16) and got.dtype == dtype
    # the plain loop multiplies a masked row's zero weight with its values:
    # it is the yardstick over a pool whose unattended rows are finite
    clean = jnp.nan_to_num(args[3], nan=0.0, posinf=0.0)
    want = attention.paged_latent_chunk_attention(
        *args[:3], clean, args[4], impl="xla", **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)
    if dtype == jnp.float32:
        dense = _dense_latent(case, rows, start + jnp.arange(t))
        np.testing.assert_allclose(got, dense, atol=tol, rtol=0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)])
def test_latent_chunk_kernel_at_the_published_head_shape(dtype, tol):
    """32 heads over rows of 512 + 64 (640 with the lane padding), heads of
    128 + 64 and values of 128 — nothing padded —, the tiles as built: a
    chunk of 64 queries from 500 crosses from the first stretch of 512 rows
    into the second."""
    t, nb, start = 64, 40, 500
    case, args, kw, rows = _chunk_case(
        7, dtype, t=t, heads=32, rank=512, rope=64, nope=128, v=128, nb=nb,
        start=start)
    assert case["pool"].shape[-1] == 640
    got = attention.paged_latent_chunk_attention(
        *args, impl="pallas", interpret=True, **kw)
    clean = jnp.nan_to_num(args[3], nan=0.0, posinf=0.0)
    want = attention.paged_latent_chunk_attention(
        *args[:3], clean, args[4], impl="xla", **kw)
    assert got.shape == (t, 32, 128) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)


@pytest.mark.parametrize("prompt_len,n_new", [(40, 6), (150, 4)])
def test_served_through_the_kernels_matches_the_reference(prompt_len, n_new,
                                                          monkeypatch):
    """The family at a rank of one lane tile and blocks of 16, both latent
    kernels interpreted (chunks of 32 through ``latent_chunk_attn`` in
    stretches of 128 rows, decode through ``paged_latent_attn``): the served
    logits against the reference's, and the engine says which formulation
    each program was built with."""
    for name, value in SMALL_TILES.items():
        monkeypatch.setattr(attention, name, value)
    cfg = joyai.joyai_tiny(dtype=jnp.float32, kv_lora_rank=128, max_seq=256,
                           kernel_impl="pallas")
    params = joyai.init_params(cfg, jax.random.PRNGKey(33), std=0.2)
    prompt = _prompt(prompt_len, prompt_len, cfg)
    eng, [(tokens, logits)] = _serve(
        cfg, params, [(prompt, n_new)], block_size=16, prefill_chunk=32,
        max_context=256)
    state = eng.state()
    assert state["chunk_attention"] == "latent_chunk_attn"
    assert state["decode_attention"] == "paged_latent_attn"
    want = _reference_logits(cfg, params, prompt, tokens)
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)
    # the walk's census: iteration i's one live slot attends prompt + i + 1
    # rows, the two idle slots nothing (not counted); a table row of 256
    # tokens holds one stretch
    stretch = attention.PAGED_LATENT_STRETCH
    decodes = [r for r in eng.step_records() if r["occupancy"]]
    assert [r["latent_stretches_walked"] for r in decodes] == [
        cfg.num_layers * -(-(prompt_len + i + 1) // stretch)
        for i in range(len(decodes))]
    assert {r["latent_stretches_capacity"] for r in decodes} == {
        cfg.num_layers * 3 * -(-256 // stretch)}


@pytest.mark.parametrize("block_size,width,rank,chunk,impl,why", [
    (16, 640, 512, 1024, "xla", "impl says so"),
    (24, 640, 512, 1024, "pallas", "a block that does not divide a stretch"),
    (16, 128, 32, 1024, "pallas", "c_kv of no whole lane tile"),
    (16, 576, 512, 1024, "pallas", "k_rope of no whole lane tile"),
    (16, 512, 512, 1024, "pallas", "no k_rope"),
    (16, 640, 512, 8, "pallas", "a chunk of half a bf16 sublane tile"),
    (16, 640, 512, 768, "pallas", "a chunk its query tiles do not divide"),
])
def test_chunk_formulation_falls_back_where_the_kernel_does_not_fit(
        block_size, width, rank, chunk, impl, why):
    assert attention.paged_latent_chunk_formulation(
        block_size, width, rank, chunk, impl) == "plain", why


@pytest.mark.parametrize("chunk", [16, 256, 1024, 2048])
def test_chunk_formulation_takes_the_kernel_at_the_served_shapes(chunk):
    form = joyai.joyai_llm_flash().cache_rows
    assert form.chunk_formulation(16, chunk, "pallas") == "latent_chunk_attn"
    # off the TPU "auto" is the plain loop; K/V rows have a kernel of their
    # own (``kv_chunk_attn``), which GPT-2's heads of 64 do not fit
    assert form.chunk_formulation(16, chunk, "auto") == "plain"
    assert models.gpt_tiny().cache_rows.chunk_formulation(
        16, chunk, "pallas") == "plain"
    assert models.gpt_medium().cache_rows.chunk_formulation(
        16, chunk, "pallas") == "plain"


def test_latent_formulation_falls_back_where_the_kernel_does_not_fit():
    assert attention.paged_latent_formulation(16, 640, 512, "xla") == "plain"
    assert attention.paged_latent_formulation(24, 640, 512, "pallas") \
        == "plain"                      # a block that does not divide 128
    assert attention.paged_latent_formulation(16, 128, 32, "pallas") \
        == "plain"                      # values of no whole lane tile
    with pytest.raises(ValueError, match="window"):
        joyai.joyai_tiny().cache_rows.decode(
            None, (), None, None, window=8)


# (c) the router

def test_router_takes_the_top_8_of_score_plus_bias_and_weighs_by_score():
    cfg = joyai.joyai_llm_flash()
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    h = jax.random.normal(ks[0], (24, 64))
    router = jax.random.normal(ks[1], (64, cfg.num_experts)) * 0.3
    bias = jax.random.normal(ks[2], (cfg.num_experts,)) * 0.5
    idx, w = moe.sigmoid_topk_route(
        h, router, bias, top_k=cfg.experts_per_token,
        route_norm=cfg.route_norm, route_scale=cfg.route_scale)
    s = jax.nn.sigmoid(h @ router)
    want_idx = np.argsort(-np.asarray(s + bias), axis=-1)[:, :8]
    assert idx.shape == (24, 8)
    assert (np.sort(idx, -1) == np.sort(want_idx, -1)).all()
    picked = np.take_along_axis(np.asarray(s), np.asarray(idx), -1)
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * 2.5, rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    # the bias selects only: other picks without it, the same weights rule
    no_bias, w0 = moe.sigmoid_topk_route(
        h, router, jnp.zeros_like(bias), top_k=8, route_scale=2.5)
    assert (np.sort(no_bias, -1) != np.sort(idx, -1)).any()
    np.testing.assert_allclose(w0.sum(-1), 2.5, rtol=1e-5)


def test_group_limited_routing_is_taken_by_the_config_refused_by_its_reference():
    """The family routes in groups since PR 52 (``tests/test_ling.py`` holds
    the selection to a brute-force one); groups that do not divide the
    experts are refused, and the joyai cell's own reference, whose published
    config has one group, still refuses rather than guess."""
    cfg = joyai.joyai_tiny(n_group=4, topk_group=2)
    assert (cfg.n_group, cfg.topk_group) == (4, 2)
    with pytest.raises(ValueError, match="equal groups"):
        joyai.joyai_tiny(n_group=3)
    with pytest.raises(ValueError, match="equal groups"):
        joyai.joyai_tiny(n_group=2, topk_group=3)
    with pytest.raises(NotImplementedError, match="n_group > 1"):
        REF._experts(None, None, {"n_routed_experts": 16, "n_group": 4,
                                  "num_experts_per_tok": 4})


# (d) many slots

def test_slots_of_different_lengths_decode_together(f32_model):
    cfg, params = f32_model
    jobs = [(_prompt(n, n, cfg), m) for n, m in ((5, 40), (60, 20), (33, 12))]
    _, served = _serve(cfg, params, jobs)
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_every_slot_live_under_load(f32_model):
    """Every slot decoding at once, several tokens on one expert an
    iteration, a queue behind the slots and a pool that admission waits on:
    each served logit still the reference's."""
    cfg, params = f32_model
    rng = np.random.default_rng(64)
    shapes = [(70, 30), (45, 50)] + [(int(rng.integers(3, 30)),
                                      int(rng.integers(20, 45)))
                                     for _ in range(14)]
    jobs = [(_prompt(i, n, cfg), m) for i, (n, m) in enumerate(shapes)]
    eng, served = _serve(cfg, params, jobs, max_slots=8, num_blocks=140)
    rows = [r for r in eng.step_records() if r["occupancy"]]
    assert max(r["occupancy"] for r in rows) == 8
    assert max(r["moe_max_load"] for r in rows) >= 3
    assert eng.kv.stats()["blocks_free"] == 140
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_bfloat16_preset_serves_finite_logits_near_the_reference():
    cfg = joyai.joyai_tiny()
    params = joyai.init_params(cfg, jax.random.PRNGKey(3), std=0.2)
    assert params["h1"]["moe"]["experts"]["w_up"].dtype == jnp.bfloat16
    assert params["h1"]["attn"]["w_uk"].dtype == jnp.bfloat16
    assert params["h1"]["moe"]["router"].dtype == jnp.float32
    prompt = list(range(1, 45))
    eng, [(tokens, logits)] = _serve(cfg, params, [(prompt, 24)])
    assert eng.kv.groups["full"].pools[0].dtype == jnp.bfloat16
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.isfinite(logits).all()
    assert (logits.argmax(-1) == want.argmax(-1)).mean() >= 0.75
    assert np.median(np.abs(logits - want)) < 0.1


# (e) what is cached, logged and refused

def test_cache_row_is_1152_bytes_a_layer_at_the_published_widths():
    """One pool, no V pool: 512 + 64 bf16 values a token a layer (in rows
    padded to five lane tiles, which the TPU's layout pads a 576-wide row
    to in any case); 32 heads of K and V would be 16,384 bytes."""
    cfg = joyai.joyai_llm_flash()
    assert (cfg.num_experts, cfg.experts_per_token, cfg.vocab_size) \
        == (256, 8, 129280)
    kv = make_grouped_cache(cfg, max_slots=2, block_size=16, max_context=64,
                            num_blocks={"full": 8}, write_ahead=16)
    (pool,) = kv.groups["full"].pools
    assert list(kv.groups) == ["full"] and kv.latent_layers == 5
    assert pool.shape == (5, 9 * 16, 640) and pool.dtype == jnp.bfloat16
    assert cfg.cache_rows.values == (576,)
    assert kv.groups["full"].row_bytes == 1152
    assert kv.row_bytes == 1152 * cfg.num_layers
    # the K/V families' census is what it was
    gpt = make_grouped_cache(models.gpt_tiny(), max_slots=2, block_size=16,
                             max_context=64, num_blocks={}, write_ahead=16)
    tiny = models.gpt_tiny()
    assert gpt.latent_layers == 0 and gpt.row_bytes == (
        2 * tiny.kv_heads * tiny.head_dim * tiny.num_layers
        * jnp.dtype(tiny.dtype).itemsize)


def test_step_log_carries_the_family_counters(f32_model):
    cfg, params = f32_model
    eng, [(tokens, _)] = _serve(cfg, params, [(list(range(40)), 12)])
    state = eng.state()
    assert state["decode_attention"] == "plain"
    assert state["chunk_attention"] == "plain"
    assert state["cache_row_bytes"] == (32 + 8) * 4 * cfg.num_layers
    decodes = [r for r in eng.step_records() if r["occupancy"]]
    assert decodes and all(
        {"moe_pairs", "moe_experts_hit", "moe_max_load", "latent_rows_read",
         "kv_blocks_used_full"} <= set(r) for r in decodes)
    # two expert layers of 16 held experts; one token, 4 choices a layer
    assert all(r["moe_pairs"] == 8 == r["moe_experts_hit"] for r in decodes)
    # the plain gather walks nothing
    assert not any("latent_stretches_walked" in r for r in decodes)
    assert not any("paged_stretches_walked" in r for r in decodes)
    # iteration i attends the prompt, the tokens before it and its own
    assert [r["latent_rows_read"] for r in decodes] == [
        cfg.num_layers * (40 + i + 1) for i in range(len(decodes))]
    chunks = [r["context_tokens"] for r in eng.step_records()
              if r["prefill_chunks"]]
    # no prefill budget: the prompt's five chunks in one iteration, each
    # walking the context up to its own end
    assert chunks == [8 + 16 + 24 + 32 + 40]


@pytest.mark.parametrize("flag,kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("fused_sampling", {"fused_sampling": True}),
    ("speculate", {"fused_sampling": True, "speculate": 2}),
])
def test_family_refuses_what_it_cannot_run_yet(f32_model, flag, kw):
    cfg, params = f32_model
    want = "fused_sampling" if flag == "speculate" else flag
    with pytest.raises(ValueError,
                       match=f"{want} is not implemented for the joyai"):
        Engine(params, cfg, max_slots=2, block_size=4, prefill_chunk=8,
               max_context=128, **kw)
