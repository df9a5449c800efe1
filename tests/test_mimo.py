"""The mimo family on the CPU at a tiny size, seeded weights, logits
compared: the dense forward and the serving path (two cache groups whose
rows differ, chunked prefill, paged decode) against
``benchmark/reference/mimo.py``'s full forward; the decode kernel
interpreted at keys of 192 over values of 128, with and without the sink,
against the plain formulation; the terms a wrong program would leave out;
the expert share; the counts against the parameter tree.

Tolerances.  With float32 parameters the system and the reference do the
same float32 arithmetic in another order (a running softmax over key chunks
with the sink added at the end, where the reference concatenates a column;
experts summed pair by pair): logits of size ~6 agree to 1.4e-5 in every
case below, and are held to ``F32_TOL`` = 1e-4.  bfloat16 in place of
float32 moves them by ~0.05 (``test_bfloat16_...`` holds that it is *over*
the tolerance, so the tolerance tells the two apart); each left-out term
moves them by more than 0.01.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import mimo
from distributedtensorflow_tpu.ops import attention
from distributedtensorflow_tpu.parallel import moe
from distributedtensorflow_tpu.serve.kv_cache import group_rows
from test_afmoe import _serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-4


def _bench_module(sub, name):
    path = os.path.join(ROOT, "benchmark", sub, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"{sub}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module("reference", "mimo")
COUNTS = _bench_module("counts", "mimo")


def _config_dict(cfg: mimo.MimoConfig) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        swa_num_key_value_heads=cfg.swa_num_kv_heads,
        head_dim=cfg.head_dim, v_head_dim=cfg.v_head_dim,
        # int(head_dim * factor) is the rotated width, as published (0.334)
        partial_rotary_factor=(cfg.rotary_dim + 0.01) / cfg.head_dim,
        rope_theta=cfg.rope_theta, swa_rope_theta=cfg.swa_rope_theta,
        attention_value_scale=cfg.value_scale,
        sliding_window=cfg.sliding_window,
        hybrid_layer_pattern=list(cfg.layer_pattern),
        moe_layer_freq=list(cfg.moe_layers),
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        n_routed_experts=cfg.held[1], expert_first=cfg.held[0],
        n_routed_experts_published=cfg.num_experts,
        num_experts_per_tok=cfg.experts_per_token, norm_topk_prob=True,
        routed_scaling_factor=None, layernorm_epsilon=cfg.rms_norm_eps,
        add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
        num_hidden_layers=cfg.num_layers, vocab_size=cfg.vocab_size)


@pytest.fixture(scope="module")
def f32_model():
    cfg = mimo.mimo_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~6, and a selection bias that decides picks
    params = mimo.init_params(cfg, jax.random.PRNGKey(41), std=0.2)
    return cfg, params


def _reference_logits(cfg, params, prompt, tokens):
    ids = jnp.asarray([list(prompt) + list(tokens)])
    full = REF.logits(params, ids, _config_dict(cfg))[0]
    return np.asarray(full)[len(prompt) - 1:-1]


# (a) the dense forward, and prefill then decode through both groups

def test_dense_forward_matches_the_reference(f32_model):
    cfg, params = f32_model
    ids = jnp.asarray(np.random.default_rng(9).integers(
        0, cfg.vocab_size, (2, 70)))
    want = REF.logits(params, ids, _config_dict(cfg))
    assert float(jnp.abs(want).max()) > 3.0
    np.testing.assert_allclose(mimo.forward(params, ids, cfg), want,
                               atol=F32_TOL, rtol=0)


@pytest.mark.parametrize("prompt_len,n_new", [
    (10, 8),     # under the window of 32 throughout
    (50, 8),     # the prompt crosses the window: chunks attend across it
    (20, 30),    # decoding crosses the window
    (41, 40),    # both, and the ring of the window group turns
])
def test_served_logits_match_the_reference(f32_model, prompt_len, n_new):
    cfg, params = f32_model
    prompt = np.random.default_rng(prompt_len).integers(
        0, cfg.vocab_size, prompt_len).tolist()
    eng, [(tokens, logits)] = _serve(cfg, params, [(prompt, n_new)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert len(tokens) == n_new
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)
    # the two groups' rows differ, and the step log counts their reads
    groups = eng.kv_groups()
    assert groups["full"]["kv_heads"] == 1 and groups["full"]["layers"] == 2
    assert groups["window"]["kv_heads"] == 2
    assert groups["window"]["row_bytes"] == 2 * groups["full"]["row_bytes"] \
        == 2 * (24 + 16) * 4 * 1 * 2 // 2
    assert eng.kv.row_bytes == 2 * 160 + 2 * 320
    last = [r for r in eng.step_records() if r["occupancy"]][-1]
    total = prompt_len + n_new - 1
    assert last["full_rows_read"] == 2 * total
    assert last["window_rows_read"] == 2 * min(total, 32)


def test_slots_of_different_lengths_decode_together(f32_model):
    cfg, params = f32_model
    rng = np.random.default_rng(7)
    jobs = [(rng.integers(0, cfg.vocab_size, n).tolist(), m)
            for n, m in ((5, 40), (60, 20), (33, 12))]
    eng, served = _serve(cfg, params, jobs)
    assert eng.kv.blocks_recycled > 0
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_bfloat16_in_place_of_float32_is_outside_the_tolerance():
    """The preset's bfloat16 serves finite logits near the reference's, and
    far enough from them that ``F32_TOL`` tells the precisions apart."""
    cfg = mimo.mimo_tiny()
    params = mimo.init_params(cfg, jax.random.PRNGKey(3), std=0.2)
    assert params["h1"]["moe"]["experts"]["w_up"].dtype == jnp.bfloat16
    assert params["h1"]["attn"]["sink"].dtype == jnp.float32
    prompt = list(range(1, 45))
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, 24)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.isfinite(logits).all()
    assert np.abs(logits - want).max() > 20 * F32_TOL
    assert np.median(np.abs(logits - want)) < 0.1


# (b) what a wrong program would leave out

def _without_sink(cfg, params):
    gone = dict(params)
    for i, kind in enumerate(cfg.layer_pattern):
        if kind == mimo.WINDOW:
            attn = params[f"h{i}"]["attn"]
            gone[f"h{i}"] = {**params[f"h{i}"], "attn": {
                **attn, "sink": jnp.full_like(attn["sink"], -1e9)}}
    return cfg, gone


def _without_value_scale(cfg, params):
    return dataclasses.replace(cfg, value_scale=1.0), params


def _full_base_on_window_layers(cfg, params):
    return dataclasses.replace(cfg, swa_rope_theta=cfg.rope_theta), params


def _rotary_on_the_whole_head(cfg, params):
    return dataclasses.replace(cfg, rotary_dim=cfg.head_dim), params


@pytest.mark.parametrize("wrong", [
    _without_sink, _without_value_scale, _full_base_on_window_layers,
    _rotary_on_the_whole_head])
def test_a_program_that_leaves_a_term_out_fails(f32_model, wrong):
    """The served logits of a program without the sink, without the value
    scale, with the full layers' rotary base on the window layers, or with
    rotary over the whole head are a hundred tolerances from the
    reference's: the comparison above would fail."""
    cfg, params = f32_model
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 50).tolist()
    _, [(tokens, logits)] = _serve(*wrong(cfg, params), [(prompt, 8)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.abs(logits - want).max() > 100 * F32_TOL


# (c) the decode kernel at the published head shapes, interpreted

def _paged_case(h_kv, lens, block_size, seed=0):
    """Pools of two layers in the stored form (K rows of 192-wide heads as
    ``lay_heads`` lays them), a page table and queries, 64 heads."""
    rng = np.random.default_rng(seed)
    heads, d, dv, per_slot = 64, 192, 128, 320 // block_size
    b = len(lens)
    blocks = b * per_slot
    k = jnp.asarray(rng.standard_normal((2, (blocks + 1) * block_size, h_kv,
                                         d)), jnp.float32)
    k_pool = jax.vmap(attention.lay_heads)(k)
    v_pool = jnp.asarray(rng.standard_normal(
        (2, (blocks + 1) * block_size, h_kv * dv)), jnp.float32)
    tables = jnp.asarray(rng.permutation(blocks).reshape(b, per_slot),
                         jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, heads, d)), jnp.float32)
    sink = jnp.asarray(rng.standard_normal(heads) + 2.0, jnp.float32)
    return q, k, k_pool, v_pool, tables, jnp.asarray(lens, jnp.int32), sink


def _dense_decode(q, k, v_pool, tables, lens, layer, block_size, window,
                  sink):
    """One query a slot against its gathered pages, by the definition."""
    out = []
    for s in range(q.shape[0]):
        n = int(lens[s])
        at = (np.asarray(tables[s])[:, None] * block_size
              + np.arange(block_size)).reshape(-1)[:n]
        lo = 0 if window is None else max(n - window, 0)
        keys = np.asarray(k[layer])[at][lo:]                # (n, h_kv, d)
        vals = np.asarray(v_pool[layer])[at][lo:].reshape(
            len(at) - lo, keys.shape[1], -1)
        g = q.shape[1] // keys.shape[1]
        heads = []
        for h in range(q.shape[1]):
            sc = keys[:, h // g] @ np.asarray(q[s, h]) * 192 ** -0.5
            if sink is not None:
                sc = np.append(sc, float(sink[h]))
            p = np.exp(sc - sc.max())
            p = p / p.sum()
            heads.append(p[:len(keys)] @ vals[:, h // g])
        out.append(np.stack(heads))
    return np.stack(out)


@pytest.mark.parametrize("h_kv", [4, 8], ids=["16-on-1", "8-on-1"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("window,block_size,lens", [
    (None, 16, (1, 130, 300, 17)),        # full layers
    (128, 16, (5, 128, 129, 300)),        # the window's edge on a block's
    (128, 16, (120, 135, 250, 313)),      # ... inside a block
    (128, 64, (64, 190, 200, 320)),       # ... inside a block of 64
])
def test_decode_kernel_takes_wide_keys_and_the_sink(h_kv, with_sink, window,
                                                    block_size, lens):
    q, k, k_pool, v_pool, tables, lens, sink = _paged_case(
        h_kv, lens, block_size)
    sink = sink if with_sink else None
    assert attention.paged_decode_formulation(
        64, h_kv, 192, block_size, "pallas", 128) == "paged_attn"
    kw = dict(layer=1, block_size=block_size, window=window, sink=sink)
    want = _dense_decode(q, k, v_pool, tables, lens, 1, block_size, window,
                         sink)
    plain = attention.paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                             **kw)
    kernel = attention.paged_window_decode_attention(
        q, k_pool, v_pool, tables, lens, impl="pallas", **kw)
    assert kernel.shape == (4, 64, 128)
    np.testing.assert_allclose(plain, want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(kernel, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("h_kv,window,sink", [
    (4, None, False), (4, None, True), (8, 128, True), (8, 700, False)],
    ids=["full", "full-sink", "window-sink", "window-of-two-trips"])
def test_decode_walk_of_several_trips_takes_wide_keys(h_kv, window, sink,
                                                      check_paged_walk):
    """The walk past its first stretch at keys a tile and a half wide (a
    head's scores two products, the remainder tile at ``rest_at`` in each of
    the kernel's two buffers): slots of over three trips, of one row and of
    nothing, the window layers' early blocks freed."""
    check_paged_walk(lens=[3 * 512 + 70, 1, 0, 2 * 512, 640], cols=112,
                     heads=64, kv_heads=h_kv, d=192, dv=128, window=window,
                     sink=sink, slots=5, nb=256)


@pytest.mark.parametrize("window", [None, 128])
def test_chunk_loop_takes_wide_keys_and_the_sink(window):
    """The prefill chunk's plain loop over the same pools: 40 queries from
    position 200 of one slot, against each query's own dense sum."""
    q1, k, k_pool, v_pool, tables, _, sink = _paged_case(8, (240,), 16, 3)
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((40, 64, 192)), jnp.float32)
    sink = sink if window else None
    got = attention.paged_chunk_attention(
        q, jnp.int32(200), k_pool, v_pool, tables[0], layer=0, block_size=16,
        window=window, kv_chunk=64, sink=sink)
    for i in (0, 17, 39):
        want = _dense_decode(q[i][None], k, v_pool, tables,
                             jnp.asarray([201 + i]), 0, 16, window, sink)
        np.testing.assert_allclose(got[i], want[0], atol=2e-5, rtol=0)


#: a chunk of 32 queries from here: alone (every stretch on the diagonal);
#: inside the window and the first stretch; several stretches in, on no
#: stretch's boundary, the window layers' early blocks freed (their table
#: columns name the scratch block, which holds NaN); ending with a stretch
CHUNK_STARTS = [0, 100, 300, 608]


@pytest.mark.parametrize("start", CHUNK_STARTS)
@pytest.mark.parametrize("kv_heads,window", [(4, None), (8, 128)],
                         ids=["full-16-on-1", "window-sink-8-on-1"])
def test_chunk_kernel_takes_wide_keys_and_the_sink(kv_heads, window, start,
                                                   check_kv_chunk_kernel):
    """``kv_chunk_attn``, interpreted, at the published head shapes: 64
    heads, keys 192 wide (a head's whole tile and its half of a remainder
    tile) over values of 128; the window layers' heads have a sink."""
    check_kv_chunk_kernel(heads=64, kv_heads=kv_heads, d=192, dv=128,
                          window=window, sink=window is not None,
                          start=start)


@pytest.mark.parametrize("kv_heads,window", [(4, None), (8, 128)],
                         ids=["full", "window-sink"])
def test_chunk_kernel_in_bfloat16_matches_the_loop(kv_heads, window,
                                                   check_kv_chunk_kernel):
    """The stored type: the kernel rounds the probabilities where the loop
    does, so the two agree to bf16's own rounding of the output."""
    check_kv_chunk_kernel(heads=64, kv_heads=kv_heads, d=192, dv=128,
                          window=window, sink=window is not None, start=300,
                          dtype=jnp.bfloat16, tol=4e-2)


@pytest.mark.parametrize("why,args", [
    ("impl says so", (64, 4, 192, 128, 16, 1024, "xla")),
    ("the CPU under auto", (64, 4, 192, 128, 16, 1024, "auto")),
    ("values of no whole tile", (64, 4, 192, 192, 16, 1024, "pallas")),
    ("remainders of an odd count of heads", (63, 3, 192, 128, 16, 1024,
                                             "pallas")),
    ("a block that does not divide a stretch", (64, 4, 192, 128, 48, 1024,
                                                "pallas")),
    ("a chunk of half a bf16 sublane tile", (64, 4, 192, 128, 16, 8,
                                             "pallas")),
    ("a chunk its query tiles do not divide", (64, 4, 192, 128, 16, 768,
                                               "pallas")),
    ("GPT-2: heads of 64, two a lane tile", (16, 16, 64, 64, 16, 16,
                                             "pallas")),
    ("16 query rows a K/V head", (16, 16, 128, 128, 16, 16, "pallas")),
])
def test_chunk_kernel_shapes_that_do_not_fit_fall_back(why, args):
    assert attention.paged_chunk_formulation(*args) == "plain", why


def test_kernel_shapes_that_do_not_fit_fall_back():
    # values as wide as keys at 192, an odd count of K/V heads, a block of
    # 48: the plain formulation, silently (the programs report it)
    assert attention.paged_decode_formulation(
        64, 4, 192, 16, "pallas") == "plain"
    assert attention.paged_decode_formulation(
        63, 3, 192, 16, "pallas", 128) == "plain"
    assert attention.paged_decode_formulation(
        64, 4, 192, 48, "pallas", 128) == "plain"
    assert attention.paged_decode_formulation(
        64, 8, 128, 16, "pallas") == "paged_attn"


def test_a_form_a_group_reaches_the_pools_and_the_programs():
    cfg = mimo.mimo_v25_ep16()
    full, window = group_rows(cfg, "full"), group_rows(cfg, "window")
    assert full.widths == (4 * 192, 4 * 128) and sum(full.widths) * 2 == 2560
    assert window.widths == (8 * 192, 8 * 128)
    assert sum(window.widths) * 2 == 5120
    for form in (full, window):
        assert form.decode_formulation(16, "pallas") == "paged_attn"
        assert form.chunk_formulation(16, 1024, "pallas") == "kv_chunk_attn"
        assert form.chunk_formulation(16, 1024, "xla") == "plain"
        assert form.chunk_formulation(16, 1024, "auto") == "plain"  # the CPU
    k = jnp.arange(2 * 4 * 192, dtype=jnp.float32).reshape(2, 4, 192)
    row, v = full.stored(k, k[..., :128])
    assert row.shape == (2, 768) and v.shape == (2, 4, 128)
    # a head's whole tile first, head after head, then the remainders
    np.testing.assert_array_equal(row[0, 128:256], k[0, 1, :128])
    np.testing.assert_array_equal(row[0, 512 + 64:512 + 128], k[0, 1, 128:])


# (d) the share, and the counts

def test_the_shares_sum_to_the_uncut_layer():
    """The two shares of ``mimo_tiny``'s expert layer (8 of 16 experts
    each; sixteen shares of 16 at the published size) add up to the
    reference's whole layer, which has no shared expert to add."""
    cfg = mimo.mimo_tiny(dtype=jnp.float32, experts_held=None,
                         expert_first=0)
    p = mimo.init_params(cfg, jax.random.PRNGKey(5), std=0.2)["h1"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(1), (48, cfg.hidden_size))
    want = REF._experts(p, h, _config_dict(cfg))
    total = 0
    for first in (0, 8):
        share = jax.tree.map(lambda a: a[first:first + 8], p["experts"])
        out, _ = moe.dropless_moe(
            h, p["router"], p["bias"], share, held=(first, 8),
            top_k=cfg.experts_per_token, impl="xla")
        total = total + out
    np.testing.assert_allclose(total, want, atol=1e-4, rtol=0)
    one_share = REF._experts(
        {**p, "experts": jax.tree.map(lambda a: a[8:], p["experts"])}, h,
        {**_config_dict(cfg), "n_routed_experts": 8, "expert_first": 8})
    np.testing.assert_allclose(out, one_share, atol=1e-4, rtol=0)


def _leaves(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def test_counts_match_the_parameter_tree():
    """``counts/mimo.py`` from the configuration file's keys against the
    abstract parameter tree of the preset, and the figures PERF.md quotes."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2.5-ep16-serve.json")) as f:
        config = json.load(f)
    cfg = mimo.mimo_v25_ep16()
    tree = jax.eval_shape(lambda: mimo.init_params(cfg, jax.random.PRNGKey(0)))
    assert COUNTS.weight_params(config) == _leaves(tree) \
        == config["parameters"] == 3_429_955_392
    assert COUNTS.attention_params(config, 0) == _leaves(tree["h0"]["attn"])
    assert COUNTS.attention_params(config, 1) == _leaves(tree["h1"]["attn"])
    assert COUNTS.expert_params(config) * 16 == _leaves(
        tree["h1"]["moe"]["experts"])
    assert COUNTS.kv_bytes_per_token_layer(config, 0) == 2560
    assert COUNTS.kv_bytes_per_token_layer(config, 1) == 5120
    assert config["cache_bytes_per_token"] == {
        "full": {"layers": 2, "values": 2560, "laid_out": 2560},
        "window": {"layers": 5, "values": 5120, "laid_out": 5120}}
    need = COUNTS.decode_kernel(config, "paged_attn", [1000, 50])
    assert need["bytes"] == 2 * 1050 * 2560 + 5 * (128 + 50) * 5120
    assert need["flops"] == 2 * 64 * 320 * (2 * 1050 + 5 * 178)
    both = [COUNTS.decode_kernel(config, f"paged_attn_{k}", [1000, 50])
            for k in ("full", "window")]
    assert sum(b["bytes"] for b in both) == need["bytes"]
    # the tiny preset the same way: the formulas, not one size
    tiny = mimo.mimo_tiny()
    tiny_tree = jax.eval_shape(
        lambda: mimo.init_params(tiny, jax.random.PRNGKey(0)))
    assert COUNTS.weight_params(_config_dict(tiny)) == _leaves(tiny_tree)
