"""GLM-5 — the joyai family with its indexer on (learned sparse attention:
an index key a token in a second pool, the top ``index_topk`` positions a
query, only those latent rows attended), a held share of the experts and two
leading dense layers — on the CPU at a tiny size, seeded weights, logits
compared: the serving path (chunks, then decode through both pools) against
``benchmark/reference/glm5.py``'s full forward with the selection as a mask
over a dense score array; the selection against ``lax.top_k`` with ties; the
kernels in interpret mode at the published head shape; the expert shares
against the uncut layer; ``counts/glm5.py`` against the parameter tree.

With float32 parameters the system and the reference do the same float32
arithmetic in another order: logits of size ~5 agree to 1e-4, and a selection
that is not the exact top ``index_topk`` moves them by far more.
"""

import importlib.util
import json
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

from test_joyai import F32_TOL, _prompt, _serve
from test_joyai import _config_dict as _joyai_config_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _module(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "glm5_" + parts[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _module("reference", "glm5.py")
COUNTS = _module("counts", "glm5.py")


def _config_dict(cfg: joyai.JoyaiConfig) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        _joyai_config_dict(cfg), n_routed_experts=cfg.held[1],
        n_routed_experts_published=cfg.num_experts,
        expert_first=cfg.held[0], index_n_heads=cfg.index_heads,
        index_head_dim=cfg.index_head_dim, index_topk=cfg.index_topk,
        rope_parameters={"rope_theta": cfg.rope_theta})


@pytest.fixture(scope="module")
def f32_model():
    cfg = joyai.glm5_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~5, index scores that decide picks
    params = joyai.init_params(cfg, jax.random.PRNGKey(39), std=0.2)
    return cfg, params


def _reference_logits(cfg, params, prompt, tokens):
    ids = jnp.asarray([list(prompt) + list(tokens)])
    full = REF.logits(params, ids, _config_dict(cfg))[0]
    return np.asarray(full)[len(prompt) - 1:-1]


# (a) chunks, then decode through both pools, against the reference

@pytest.mark.parametrize("prompt_len,n_new", [
    (3, 6),      # far below index_topk (24): every row selected
    (16, 7),     # decoding reaches index_topk and stops at it
    (20, 10),    # decoding crosses index_topk
    (24, 3),     # the prompt is index_topk long: the first decoded token
                 # is the first that drops a row
    (25, 12),    # the last prefill chunk (of one token) selects
    (40, 30),    # two chunks dense, three sparse, then decode
    (57, 20),    # eight chunks, the last of one token
    (96, 8),     # four times index_topk
])
def test_served_logits_match_the_reference(f32_model, prompt_len, n_new):
    cfg, params = f32_model
    assert cfg.index_topk == 24 and cfg.num_dense_layers == 2
    prompt = _prompt(prompt_len, prompt_len, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, n_new)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert len(tokens) == n_new
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_the_selection_decides_the_logits(f32_model):
    """The test above would not see a sloppy selection if the selection did
    not matter: with ``index_topk`` one less the reference's logits move by
    a thousand tolerances."""
    cfg, params = f32_model
    prompt = _prompt(40, 40, cfg)
    ids = jnp.asarray([prompt])
    config = _config_dict(cfg)
    full = REF.logits(params, ids, config)[0]
    less = REF.logits(params, ids, dict(config, index_topk=23))[0]
    # positions 0..22 have 23 candidates at most: both keep them all
    assert np.abs(np.asarray(full - less))[:23].max() == 0.0
    assert np.abs(np.asarray(full - less))[23:].max() > 1000 * F32_TOL


# (b) the sparse formulation against dense attention under a mask

def _sparse_case(seed, *, t, heads, rank, rope, nope, v, hi, di, blocks, bs,
                 dtype=jnp.float32, layers=2):
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0, dt=dtype):
        return jnp.asarray(rng.standard_normal(shape) * scale, dt)

    width = -(-(rank + rope) // 128) * 128
    rows = (blocks + 1) * bs
    pool = draw(layers, rows, width).at[..., rank + rope:].set(0)
    return dict(
        q_nope=draw(t, heads, nope), q_rope=draw(t, heads, rope),
        q_index=draw(t, hi, di), w_index=draw(t, hi, dt=jnp.float32),
        pool=pool, index_pool=draw(layers, rows, di),
        w_uk=draw(rank, heads, nope, scale=rank ** -0.5),
        w_uv=draw(rank, heads, v, scale=rank ** -0.5),
        table=jnp.asarray(rng.permutation(blocks), jnp.int32))


def _dense_under_mask(case, form, qpos, table, bs, layer):
    """Every head's keys and values decompressed, the index scores a dense
    array, ``lax.top_k`` and a mask: the reference's formulation over the
    pool's rows."""
    rank = form.rank
    rows = (table[:, None] * bs + jnp.arange(bs)[None]).reshape(-1)
    x = case["pool"][layer, rows].astype(jnp.float32)
    keys = case["index_pool"][layer, rows].astype(jnp.float32)
    s = x.shape[0]
    dots = jnp.einsum("thd,sd->ths", case["q_index"].astype(jnp.float32),
                      keys, precision="highest")
    scores = (jnp.maximum(dots, 0) * case["w_index"][..., None]).sum(1)
    causal = jnp.arange(s)[None] <= qpos[:, None]
    k = min(form.topk, s)
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(len(qpos))[:, None], idx].set(True) & causal
    c_kv, k_rope = x[:, :rank], x[:, rank:rank + form.rope_dim]
    k_nope = jnp.einsum("sr,rhn->shn", c_kv, case["w_uk"].astype(jnp.float32),
                        precision="highest")
    values = jnp.einsum("sr,rhv->shv", c_kv,
                        case["w_uv"].astype(jnp.float32), precision="highest")
    att = (jnp.einsum("thn,shn->hts", case["q_nope"].astype(jnp.float32),
                      k_nope, precision="highest")
           + jnp.einsum("thr,sr->hts", case["q_rope"].astype(jnp.float32),
                        k_rope, precision="highest")) * form.scale
    p = jax.nn.softmax(jnp.where(mask[None], att, -jnp.inf), -1)
    return jnp.einsum("hts,shv->thv", p, values, precision="highest"), mask


@pytest.mark.parametrize("start", [0, 8, 24, 37, 56])
def test_sparse_chunk_is_dense_attention_under_the_selected_mask(start):
    bs, t = 4, 8
    form = attention.SparseLatentRows(rank=32, rope_dim=8, scale=24 ** -0.5,
                                      index_dim=16, topk=24)
    case = _sparse_case(start, t=t, heads=4, rank=32, rope=8, nope=16, v=16,
                        hi=4, di=16, blocks=16, bs=bs)
    got = form.chunk(
        (case["q_nope"], case["q_rope"], case["q_index"], case["w_index"]),
        jnp.int32(start), (case["pool"], case["index_pool"]), case["table"],
        layer=1, block_size=bs, impl="xla", w_uk=case["w_uk"],
        w_uv=case["w_uv"])
    qpos = start + jnp.arange(t)
    want, mask = _dense_under_mask(case, form, qpos, case["table"], bs, 1)
    assert int(mask.sum(-1).max()) == min(24, start + t)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_sparse_decode_is_dense_attention_under_the_selected_mask():
    """One query a slot, each slot its own table row and length: below, at
    and above ``topk``."""
    bs = 4
    form = attention.SparseLatentRows(rank=32, rope_dim=8, scale=24 ** -0.5,
                                      index_dim=16, topk=24)
    lens = jnp.asarray([5, 24, 25, 61], jnp.int32)
    case = _sparse_case(7, t=4, heads=4, rank=32, rope=8, nope=16, v=16,
                        hi=4, di=16, blocks=64, bs=bs)
    tables = jnp.asarray(np.random.default_rng(1).permutation(64).reshape(
        4, 16), jnp.int32)
    got = form.decode(
        (case["q_nope"], case["q_rope"], case["q_index"], case["w_index"]),
        (case["pool"], case["index_pool"]), tables, lens, layer=0,
        block_size=bs, impl="xla", w_uk=case["w_uk"], w_uv=case["w_uv"])
    for b in range(4):
        one = {k: (v[b:b + 1] if k.startswith(("q_", "w_index")) else v)
               for k, v in case.items()}
        want, mask = _dense_under_mask(one, form, lens[b:b + 1] - 1,
                                       tables[b], bs, 0)
        assert int(mask.sum()) == min(24, int(lens[b]))
        np.testing.assert_allclose(got[b:b + 1], want, atol=2e-5, rtol=0)


# (c) the selection is lax.top_k's set, ties included

def test_selection_is_the_top_k_set_with_ties_to_the_lowest_position():
    rng = np.random.default_rng(0)
    # few distinct values: every row has ties across its boundary
    scores = jnp.asarray(rng.integers(0, 6, (16, 64)), jnp.float32)
    counts = jnp.asarray(rng.integers(1, 65, 16), jnp.int32)
    pos, real = attention.select_rows(scores, counts, 24)
    pos, real = np.asarray(pos), np.asarray(real)
    for i in range(16):
        n = int(counts[i])
        assert real[i] == min(n, 24)
        # by score, best first, equal scores by position: a stable sort
        order = np.argsort(-np.asarray(scores[i, :n]), kind="stable")[:24]
        assert pos[i, :real[i]].tolist() == order.tolist()
        assert (pos[i, real[i]:] >= n).all()


def _few_values(rows, k):
    """Few distinct values, so every row has ties across its cut; a row of
    one value, and one that alternates -0.0 with numbers."""
    rng = np.random.default_rng(rows)
    scores = jnp.asarray(rng.integers(-3, 4, (16, rows))
                         * rng.choice([0.5, 1.0], (16, rows)), jnp.float32)
    scores = scores.at[3].set(-0.0).at[4, ::2].set(-0.0)
    counts = jnp.asarray(rng.integers(1, rows + 1, 16), jnp.int32).at[
        0].set(1).at[1].set(rows).at[2].set(min(k, rows))
    return scores, counts, min(k, rows)


def _distinct(queries=8, rows=1024, seed=7):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (queries, rows)), jnp.float32)


def _with_a_tie_across_the_cut(scores, counts, k, row):
    """``scores`` with row ``row``'s ``k``-th largest candidate value also at
    the two ranks above it and the two below."""
    x = np.array(scores)
    order = np.argsort(-x[row, :int(counts[row])], kind="stable")
    x[row, order[k - 3:k + 2]] = x[row, order[k - 1]]
    return jnp.asarray(x)


def _dead_positions_set_to(value, queries=8, rows=1024):
    """Distinct scores with ``value`` at and past every row's ``counts``."""
    counts = jnp.asarray(np.random.default_rng(11).integers(
        1, rows, queries), jnp.int32).at[0].set(256).at[1].set(257)
    dead = jnp.arange(rows)[None, :] >= counts[:, None]
    return jnp.where(dead, value, _distinct(queries, rows)), counts, 64


#: scores, counts, k; the kernel's walks differ by 256 positions here
_SELECTIONS = {
    "256-24": lambda: _few_values(256, 24),
    "384-128": lambda: _few_values(384, 128),
    "1024-2048": lambda: _few_values(1024, 2048),
    "distinct": lambda: (_distinct(), jnp.asarray(
        np.random.default_rng(8).integers(1, 1025, 8), jnp.int32), 64),
    "counts_on_a_stretch_boundary": lambda: (
        _distinct(), jnp.full((8,), 512, jnp.int32), 64),
    "counts_one_under_a_boundary": lambda: (
        _distinct(), jnp.full((8,), 511, jnp.int32), 64),
    "counts_one_over_a_boundary": lambda: (
        _distinct(), jnp.full((8,), 513, jnp.int32), 64),
    "an_extent_of_one_stretch": lambda: (_distinct(), jnp.asarray(
        [1, 7, 64, 65, 128, 200, 255, 256], jnp.int32), 64),
    "an_extent_of_the_whole_row": lambda: (_distinct(), jnp.asarray(
        [3, 100, 300, 1024, 513, 64, 900, 1], jnp.int32), 64),
    "a_chunk_of_consecutive_counts_over_three_tiles": lambda: (
        _distinct(96), 200 + jnp.arange(96, dtype=jnp.int32) * 8, 64),
    "a_tie_across_the_cut_in_one_row_of_eight": lambda: (
        _with_a_tie_across_the_cut(
            _distinct(), np.full(8, 700), 64, row=5),
        jnp.full((8,), 700, jnp.int32), 64),
    "a_row_of_one_value": lambda: (
        _distinct().at[2].set(1.5), jnp.asarray(
            [600, 600, 600, 10, 64, 65, 1000, 300], jnp.int32), 64),
    "nan_at_and_past_counts": lambda: _dead_positions_set_to(jnp.nan),
    "inf_at_and_past_counts": lambda: _dead_positions_set_to(jnp.inf),
    "minus_inf_at_and_past_counts": lambda: _dead_positions_set_to(-jnp.inf),
    "3e38_at_and_past_counts": lambda: _dead_positions_set_to(3e38),
}


@pytest.mark.parametrize("case", sorted(_SELECTIONS))
def test_selection_kernel_is_the_top_k_set_with_ties(case, monkeypatch):
    """The kernel finds the k-th largest score a bit at a time, over the
    stretches up to its tile's last candidate alone, and takes the equal ones
    by position where a tile has more of them than it wants: the same set as
    ``lax.top_k``'s, as a bias, whatever lies at and past ``counts``."""
    monkeypatch.setattr(attention, "SELECT_STRETCH", 256)
    scores, counts, k = _SELECTIONS[case]()
    n, rows = scores.shape
    assert attention.select_formulation(n, "pallas") == "select_rows"
    got = attention.select_bias(scores, counts, k, impl="pallas")
    want = attention.select_bias(scores, counts, k, impl="xla")
    assert got.dtype == jnp.float32 and set(np.unique(got).tolist()) <= {
        0.0, attention.NEG_INF}
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        (np.asarray(got) == 0).sum(-1), np.minimum(counts, k))
    # what a dead position holds decides nothing
    live = jnp.arange(rows)[None, :] < counts[:, None]
    np.testing.assert_array_equal(want, attention.select_bias(
        jnp.where(live, scores, 0.0), counts, k, impl="xla"))


def test_selection_walk_ends_with_the_stretch_of_the_last_candidate():
    assert attention.SELECT_STRETCH % attention.LANES == 0
    walks = [attention.select_walk(e, 33792) for e in (
        1, attention.SELECT_STRETCH, attention.SELECT_STRETCH + 1, 33792)]
    assert walks == [attention.SELECT_STRETCH, attention.SELECT_STRETCH,
                     2 * attention.SELECT_STRETCH, 33792]
    assert attention.select_walk(500, 384) == 384


@pytest.mark.parametrize("start", [112, 360, 600, 1008])
def test_masked_chunk_kernels_match_the_gathered_plain_formulation(
        start, monkeypatch):
    """A chunk past ``topk`` through the kernels (the indexer, the selection
    as a bias, the dense walk under it) against the plain formulation (the
    selected rows gathered by index): the same rows attended.  The chunks
    from 360 and 600 end inside a stretch of the selection's, the last on
    one."""
    monkeypatch.setattr(attention, "SELECT_STRETCH", 128)
    bs, t = 16, 16
    form = attention.SparseLatentRows(rank=128, rope_dim=8,
                                      scale=24 ** -0.5, index_dim=128,
                                      topk=128)
    case = _sparse_case(start, t=t, heads=4, rank=128, rope=8, nope=16, v=16,
                        hi=4, di=128, blocks=64, bs=bs)
    q = (case["q_nope"], case["q_rope"], case["q_index"], case["w_index"])
    kw = dict(layer=1, block_size=bs, w_uk=case["w_uk"], w_uv=case["w_uv"])
    pools = (case["pool"], case["index_pool"])
    assert form.chunk_formulation(bs, t, "pallas").startswith("masked_")
    got = form.chunk(q, jnp.int32(start), pools, case["table"],
                     impl="pallas", **kw)
    want = form.chunk(q, jnp.int32(start), pools, case["table"], impl="xla",
                      **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# (d) the kernels against the plain formulations, interpreted, at the
# published head shape: 32 index heads of 128, 64 heads over rows of 640

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 0.0)])
def test_index_kernel_matches_the_plain_loop_on_a_chunk(dtype, tol):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 32, 32, 128)), dtype)
    w = jnp.asarray(rng.standard_normal((1, 32, 32)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((1, 1024, 128)), dtype)
    assert attention.index_formulation(32, 1, 1024, 128, "pallas") \
        == "index_scores"
    lens = jnp.asarray([500], jnp.int32)
    got = attention.index_scores(q, w, keys, lens, impl="pallas")
    want = attention.index_scores(q, w, keys, lens, impl="xla")
    assert got.shape == (1, 32, 1024) and got.dtype == jnp.float32
    # a stretch past the chunk's end is left at zero
    assert np.abs(np.asarray(got[..., 1024 - 512:])).max() == 0
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got[..., :512], want[..., :512],
                               atol=(tol or 2e-2) * scale / 10, rtol=0)


def test_index_kernel_matches_the_plain_loop_on_a_decode_step():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((3, 1, 32, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 1, 32)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((3, 1024, 128)), jnp.float32)
    lens = jnp.asarray([1, 512, 700], jnp.int32)
    got = attention.index_scores(q, w, keys, lens, impl="pallas")
    want = attention.index_scores(q, w, keys, lens, impl="xla")
    live = np.arange(1024)[None] < (-(-np.asarray(lens) // 512) * 512)[:, None]
    np.testing.assert_allclose(np.asarray(got[:, 0])[live],
                               np.asarray(want[:, 0])[live], atol=2e-3,
                               rtol=0)
    assert np.abs(np.asarray(got[:, 0])[~live]).max() == 0


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_sparse_kernel_matches_the_plain_formulation(dtype, tol):
    rng = np.random.default_rng(5)
    n, heads, k, width, rank = 3, 64, 256, 640, 512
    q = jnp.asarray(rng.standard_normal((n, heads, width)) * 0.1, dtype)
    pool = jnp.asarray(rng.standard_normal((2, 4096, width)), dtype)
    rows = jnp.asarray(rng.integers(0, 4096, (n, k)), jnp.int32)
    counts = jnp.asarray([1, 200, 256], jnp.int32)
    assert attention.sparse_latent_formulation(width, rank, k, "pallas") \
        == "sparse_latent_attn"
    kw = dict(layer=1, rank=rank, scale=256 ** -0.5)
    got = attention.sparse_latent_attention(q, pool, rows, counts,
                                            impl="pallas", **kw)
    want = attention.sparse_latent_attention(q, pool, rows, counts,
                                             impl="xla", **kw)
    assert got.shape == (n, heads, rank)
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)


def test_served_through_the_kernels_matches_the_reference(monkeypatch):
    """The whole path with every kernel interpreted: rows and index keys of
    whole lane tiles, ``index_topk`` 128 of contexts to 640."""
    monkeypatch.setattr(attention, "INDEX_STRETCH", 128)
    monkeypatch.setattr(attention, "SELECT_STRETCH", 256)
    cfg = joyai.glm5_tiny(
        dtype=jnp.float32, kernel_impl="pallas", kv_lora_rank=128,
        qk_rope_head_dim=8, index_head_dim=128, index_topk=128, max_seq=640,
        num_layers=3)
    params = joyai.init_params(cfg, jax.random.PRNGKey(5), std=0.2)
    prompt = _prompt(150, 150, cfg)
    eng, [(tokens, logits)] = _serve(
        cfg, params, [(prompt, 4)], block_size=16, prefill_chunk=16,
        max_context=640, max_slots=2)
    state = eng.state()
    assert state["decode_attention"] == "sparse_latent_attn"
    assert state["chunk_attention"] \
        == "masked_latent_chunk_attn+latent_chunk_attn"
    want = _reference_logits(cfg, params, prompt, tokens)
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)
    # a chunk past index_topk records how far its selection walked: the
    # stretch its end lies in, of the table's 640 positions, x 3 layers.
    # One iteration prefilled the ten chunks: two end past 128, at 144, 160
    [chunks] = [r for r in eng.step_records() if "context_tokens" in r]
    assert chunks["context_tokens"] == sum(range(16, 161, 16))
    assert chunks["select_positions_walked"] == 2 * 3 * 256


def test_formulations_fall_back_where_the_kernels_do_not_fit():
    form = joyai.glm5_ep16().cache_rows
    assert form.decode_formulation(16, "pallas") == "sparse_latent_attn"
    assert form.chunk_formulation(16, 1024, "pallas") \
        == "masked_latent_chunk_attn+latent_chunk_attn"
    assert form.chunk_formulation(16, 1024, "xla") == "plain+plain"
    assert attention.select_formulation(1024, "pallas") \
        == "select_rows"
    assert attention.select_formulation(20, "pallas") == "plain"
    assert form.decode_formulation(16, "xla") == "plain"
    tiny = joyai.glm5_tiny().cache_rows
    assert tiny.decode_formulation(4, "pallas") == "plain"      # rank 32
    assert attention.index_formulation(1024, 1, 33792, 128, "pallas") \
        == "index_scores"
    assert attention.index_formulation(1, 24, 33792, 128, "pallas") \
        == "index_scores"
    for why, args in {"keys of no lane tile": (16, 1, 1024, 16),
                      "a context of no whole stretch": (16, 1, 1000, 128),
                      "several slots' chunks": (16, 2, 1024, 128)}.items():
        assert attention.index_formulation(*args, "pallas") == "plain", why


# (e) the shares add up

def test_the_expert_shares_add_up_to_the_uncut_layer(f32_model):
    """Four chips of four experts each: the routed parts every share
    computes for its own experts, and the shared expert counted once, are
    the uncut layer the reference computes with every expert held."""
    cfg, _ = f32_model
    whole = joyai.glm5_tiny(dtype=jnp.float32, experts_held=None,
                            expert_first=0)
    params = joyai.init_params(whole, jax.random.PRNGKey(1), std=0.2)
    p = params["h2"]["moe"]
    h = jnp.asarray(np.random.default_rng(2).standard_normal((40, 64)),
                    jnp.float32)
    config = _config_dict(whole)
    assert config["n_routed_experts"] == 16 == whole.num_experts
    with jax.default_matmul_precision("highest"):
        uncut = REF._swiglu(p["shared"], h) + REF._experts(p, h, config)
    total = np.asarray(REF._swiglu(p["shared"], h))
    hit = 0
    for first in range(0, 16, 4):
        share = jax.tree.map(lambda a: a[first:first + 4], p["experts"])
        routed, counters = moe.dropless_moe(
            h, p["router"], p["bias"], share, held=(first, 4),
            top_k=whole.experts_per_token, route_norm=whole.route_norm,
            route_scale=whole.route_scale, impl="xla")
        with jax.default_matmul_precision("highest"):
            mine = REF._experts(
                dict(p, experts=share), h,
                dict(config, n_routed_experts=4, expert_first=first))
        np.testing.assert_allclose(routed, mine, atol=F32_TOL, rtol=0)
        total = total + np.asarray(routed)
        hit += int(counters["pairs"])
    assert hit == 40 * whole.experts_per_token     # every pair on one share
    np.testing.assert_allclose(total, uncut, atol=F32_TOL, rtol=0)


# (f) many slots

def test_slots_of_different_lengths_decode_together(f32_model):
    cfg, params = f32_model
    jobs = [(_prompt(n, n, cfg), m) for n, m in ((5, 40), (60, 20), (33, 12))]
    _, served = _serve(cfg, params, jobs)
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_every_slot_live_under_load(f32_model):
    cfg, params = f32_model
    rng = np.random.default_rng(64)
    shapes = [(70, 30), (45, 50)] + [(int(rng.integers(3, 40)),
                                      int(rng.integers(20, 45)))
                                     for _ in range(10)]
    jobs = [(_prompt(i, n, cfg), m) for i, (n, m) in enumerate(shapes)]
    eng, served = _serve(cfg, params, jobs, max_slots=6, num_blocks=120)
    rows = [r for r in eng.step_records() if r["occupancy"]]
    assert max(r["occupancy"] for r in rows) == 6
    assert eng.kv.stats()["blocks_free"] == 120
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


# (g) what is cached, counted, logged and refused

def test_cache_is_1536_bytes_a_token_a_layer_in_two_pools():
    cfg = joyai.glm5_ep16()
    assert (cfg.num_experts, cfg.held, cfg.experts_per_token,
            cfg.vocab_size) == (256, (0, 16), 8, 19360)
    kv = make_grouped_cache(cfg, max_slots=2, block_size=16, max_context=64,
                            num_blocks={"full": 8}, write_ahead=16)
    rows, keys = kv.groups["full"].pools
    assert list(kv.groups) == ["full"] and kv.latent_layers == 5
    assert kv.index_topk == 2048
    assert rows.shape == (5, 9 * 16, 640) and keys.shape == (5, 9 * 16, 128)
    assert rows.dtype == keys.dtype == jnp.bfloat16
    assert cfg.cache_rows.values == (576, 128)
    # values stored, and as laid out
    assert kv.groups["full"].row_bytes == 2 * (576 + 128)
    assert (rows.shape[-1] + keys.shape[-1]) * 2 == 1536
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "glm5-ep16-serve.json")))
    assert COUNTS.cache_bytes_per_token_layer(config) == 1536
    # a copied block carries both pools
    kv.admit(0, 20)
    group = kv.groups["full"]
    group.pools = tuple(p.at[:, :16].set(1) for p in group.pools)
    from distributedtensorflow_tpu.serve.kv_cache import _copy_block_fn
    group.pools = _copy_block_fn(16)(group.pools, 0, 3)
    assert all(float(p[:, 48:64].min()) == 1 for p in group.pools)
    # joyai's cache is what it was
    assert make_grouped_cache(
        joyai.joyai_tiny(), max_slots=2, block_size=16, max_context=64,
        num_blocks={"full": 8}, write_ahead=16).index_topk is None


def test_counts_equal_the_parameter_tree():
    """``counts/glm5.py`` over the configuration file is the tree
    ``init_params`` builds for the preset (shapes only: nothing is drawn),
    3,909.6 M parameters."""
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "glm5-ep16-serve.json")))
    cfg = getattr(models, config["system_config"])()
    tree = jax.eval_shape(lambda: joyai.init_params(
        cfg, jax.random.PRNGKey(0)))
    matrices = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree)
                   if a.ndim >= 2)
    assert COUNTS.matmul_params(config) == matrices
    assert round(matrices / 1e6, 1) == 3909.6
    assert COUNTS.attention_params(config) + COUNTS.indexer_params(config) \
        + COUNTS.expert_params(config) + 6144 * 256 == 213_712_896
    for key, want in {"vocab_size": cfg.vocab_size,
                      "n_routed_experts": cfg.held[1],
                      "n_routed_experts_published": cfg.num_experts,
                      "index_topk": cfg.index_topk,
                      "index_n_heads": cfg.index_heads,
                      "num_hidden_layers": cfg.num_layers,
                      "first_k_dense_replace": cfg.num_dense_layers,
                      "rms_norm_eps": cfg.rms_norm_eps}.items():
        assert config[key] == want, key
    assert config["rope_parameters"]["rope_theta"] == cfg.rope_theta
    lives = [100, 5000]
    need = COUNTS.decode_kernel(config, "sparse_latent_attn", lives)
    assert need["flops"] == 5 * (100 + 2048) * 2 * 64 * (576 + 512)
    scored = COUNTS.decode_kernel(config, "index_scores", lives)
    assert scored["flops"] == 5 * 5100 * 2 * 32 * 128


def test_step_log_carries_the_indexer_counters(f32_model):
    cfg, params = f32_model
    from distributedtensorflow_tpu.obs.registry import Registry
    eng, [(tokens, _)] = _serve(cfg, params, [(list(range(20)), 12)],
                                registry=Registry())
    state = eng.state()
    assert state["decode_attention"] == "plain"
    assert state["chunk_attention"] == "plain+plain"
    assert state["cache_row_bytes"] == (32 + 8 + 16) * 4 * cfg.num_layers
    decodes = [r for r in eng.step_records() if r["occupancy"]]
    assert decodes and all(
        {"index_rows_scored", "latent_rows_read", "moe_pairs"} <= set(r)
        for r in decodes)
    # the plain formulation selects with lax.top_k: no kernel walked
    assert not any("select_positions_walked" in r
                   for r in eng.step_records())
    # iteration i scores the prompt, the tokens before it and its own, and
    # attends index_topk of them at most
    assert [r["index_rows_scored"] for r in decodes] == [
        cfg.num_layers * (20 + i + 1) for i in range(len(decodes))]
    assert [r["latent_rows_read"] for r in decodes] == [
        cfg.num_layers * min(24, 20 + i + 1) for i in range(len(decodes))]
    # the registry counters beside them: the totals over the iterations
    scalars = eng._registry.scalars()
    assert scalars["serve_index_rows_scored_total"] == sum(
        r["index_rows_scored"] for r in decodes)
    assert scalars["serve_latent_rows_read_total"] == sum(
        r["latent_rows_read"] for r in decodes)


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
