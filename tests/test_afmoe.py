"""The afmoe family on the CPU at a tiny size, seeded weights, logits
compared: the serving path (two-group paged cache, chunked prefill, paged
decode) against ``benchmark/reference/trinity.py``'s full forward; the
expert share; dropless routing; the window group's ring; the kernels in
interpret mode against their plain formulations.

Tolerances.  With float32 parameters the system and the reference do the
same float32 arithmetic in another order (a running softmax over key
chunks, experts summed pair by pair): logits of size ~5 agree to 2e-4.
With the preset's bfloat16 a rounding now and then flips a routed expert,
and the sandwich norm passes the flip on whole, so bfloat16 is held only
to "finite, and mostly the same arg-max".
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import afmoe
from distributedtensorflow_tpu import runtime
from distributedtensorflow_tpu.ops import attention
from distributedtensorflow_tpu.parallel import moe
from distributedtensorflow_tpu.serve.engine import Engine
from distributedtensorflow_tpu.serve.kv_cache import (
    OutOfBlocksError,
    WindowKVGroup,
    make_grouped_cache,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 2e-4


def _reference():
    path = os.path.join(ROOT, "benchmark", "reference", "trinity.py")
    spec = importlib.util.spec_from_file_location("ref_trinity", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _config_dict(cfg: afmoe.AfmoeConfig) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        sliding_window=cfg.sliding_window, expert_first=cfg.held[0],
        num_experts=cfg.held[1], num_experts_published=cfg.num_experts,
        num_experts_per_tok=cfg.experts_per_token,
        route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        mup_enabled=cfg.mup_enabled, num_hidden_layers=cfg.num_layers,
        layer_types=list(cfg.layer_types),
        num_dense_layers=cfg.num_dense_layers)


@pytest.fixture(scope="module")
def f32_model():
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~5, and a selection bias that decides picks
    params = afmoe.init_params(cfg, jax.random.PRNGKey(28), std=0.2)
    return cfg, params


def _record_logits(eng):
    """``{request id: [the logits of every served position]}``, filled as
    ``eng`` runs: a request's first row as the host sampler is handed it,
    a decode iteration's rows as the program returns them (they stay on the
    device for the engine, which takes the program's arg-max)."""
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


# (a) prefill then decode through the two-group cache against the reference

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
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, n_new)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert len(tokens) == n_new
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_dense_forward_of_the_same_block_matches_the_reference(f32_model):
    """``afmoe.forward`` (the block under dense causal attention, what a
    trainer would call) against the reference, across the window."""
    cfg, params = f32_model
    ids = jnp.asarray(np.random.default_rng(9).integers(
        0, cfg.vocab_size, (2, 70)))
    want = REF.logits(params, ids, _config_dict(cfg))
    np.testing.assert_allclose(afmoe.forward(params, ids, cfg), want,
                               atol=F32_TOL, rtol=0)


def test_decode_program_hands_back_the_arg_max_of_its_logits(f32_model):
    """The family's ``jit_decode`` returns the arg-max of its own float32
    logits a slot, the first of equal maxima (a head whose every column
    stands twice ties every row's maximum): what ``np.argmax`` gave the
    engine when it fetched the logits."""
    cfg, params = f32_model
    half = cfg.vocab_size // 2
    head = params["head"]
    tied = {**params,
            "head": head.at[:, half:2 * half].set(head[:, :half])}
    eng = Engine(tied, cfg, max_slots=3, block_size=4, prefill_chunk=8,
                 max_context=128)
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
    rng = np.random.default_rng(31)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                       max_new_tokens=m) for n, m in ((40, 12), (9, 20))]
    while not all(r._done.is_set() for r in reqs):
        eng.step()
    assert checked == {0, 1}        # the third slot never held a request
    assert all(r.status == "ok" and max(r.tokens) < half for r in reqs)
    assert eng.counters["logit_fetches"] == 0


def test_slots_of_different_lengths_decode_together(f32_model):
    cfg, params = f32_model
    rng = np.random.default_rng(7)
    jobs = [(rng.integers(0, cfg.vocab_size, n).tolist(), m)
            for n, m in ((5, 40), (60, 20), (33, 12))]
    _, served = _serve(cfg, params, jobs)
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


@pytest.mark.parametrize("pools", [
    {},                                         # every slot's worst case
    {"num_blocks": 130, "window_blocks": 60},   # admission waits for blocks
])
def test_every_slot_live_under_load(f32_model, pools):
    """What the chip's check cannot afford to score (64 slots of 4,400
    tokens): every slot decoding at once, several tokens on one expert an
    iteration, rings turning while other slots hold the rest of the window
    pool, a queue behind the slots — each served logit still the
    reference's.  With the smaller pools eight jobs do not fit at once (a
    long one's ring is 11 blocks of the window pool's 60): slots stand
    empty while the queue's head waits for blocks."""
    cfg, params = f32_model
    rng = np.random.default_rng(64)
    shapes = [(70, 30), (45, 50)] + [(int(rng.integers(3, 30)),
                                      int(rng.integers(20, 45)))
                                     for _ in range(18)]
    jobs = [(rng.integers(0, cfg.vocab_size, n).tolist(), m)
            for n, m in shapes]
    eng, served = _serve(cfg, params, jobs, max_slots=8, **pools)
    rows = [r for r in eng.step_records() if r["occupancy"]]
    fullest = max(r["occupancy"] for r in rows)
    assert fullest == 8 if not pools else 4 <= fullest < 8
    assert max(r["moe_max_load"] for r in rows) >= 3
    assert eng.kv.blocks_recycled > 0
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_the_selection_bias_decides_picks(f32_model):
    """The bias is not invisible: without it other experts are picked."""
    cfg, params = f32_model
    p = params["h1"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(0), (64, cfg.hidden_size))
    with_bias, _ = moe.sigmoid_topk_route(
        h, p["router"], p["bias"], top_k=4)
    without, _ = moe.sigmoid_topk_route(
        h, p["router"], jnp.zeros_like(p["bias"]), top_k=4)
    assert (np.sort(with_bias, -1) != np.sort(without, -1)).any()


def test_bfloat16_preset_serves_finite_logits_near_the_reference():
    cfg = afmoe.afmoe_tiny()
    params = afmoe.init_params(cfg, jax.random.PRNGKey(3), std=0.2)
    assert params["h1"]["moe"]["experts"]["w_up"].dtype == jnp.bfloat16
    assert params["h1"]["moe"]["router"].dtype == jnp.float32
    prompt = list(range(1, 45))
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, 24)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.isfinite(logits).all()
    assert (logits.argmax(-1) == want.argmax(-1)).mean() >= 0.75
    assert np.median(np.abs(logits - want)) < 0.1


# (b) the share

def _joyai_layer():
    """The joyai family's expert layer at top 8 of 16, its reference's uncut
    layer, and the layer as that family calls it: every expert held."""
    from distributedtensorflow_tpu.models import joyai

    path = os.path.join(ROOT, "benchmark", "reference", "joyai.py")
    spec = importlib.util.spec_from_file_location("ref_joyai", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    cfg = joyai.joyai_tiny(dtype=jnp.float32, experts_per_token=8)
    p = joyai.init_params(cfg, jax.random.PRNGKey(5), std=0.2)["h1"]["moe"]
    config = dict(n_routed_experts=16, num_experts_per_tok=8, n_group=1,
                  norm_topk_prob=True, routed_scaling_factor=cfg.route_scale)
    return cfg, p, lambda h: ref._swiglu(p["shared"], h) + ref._experts(
        p, h, config)


def _afmoe_layer():
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32, experts_held=None,
                           expert_first=0)
    p = afmoe.init_params(cfg, jax.random.PRNGKey(5), std=0.2)["h1"]["moe"]
    config = _config_dict(cfg)
    return cfg, p, lambda h: (REF._swiglu(p["shared"], h[None])
                              + REF._experts(p, h[None], config))[0]


@pytest.mark.parametrize("layer", [_afmoe_layer, _joyai_layer],
                         ids=["afmoe-top4", "joyai-top8"])
def test_the_shares_sum_to_the_uncut_layer(layer):
    """Every share's held-expert terms, plus the shared expert once, are
    the reference's uncut layer (16 experts in 4 shares of 4), and so is
    the layer told it holds them all (``held=(0, E)``: joyai's call)."""
    cfg, p, uncut = layer()
    k = cfg.experts_per_token
    h = jax.random.normal(jax.random.PRNGKey(1), (48, cfg.hidden_size))
    shared = afmoe.swiglu(p["shared"], h)
    total = shared
    for first in range(0, 16, 4):
        share = jax.tree.map(lambda a: a[first:first + 4], p["experts"])
        out, counters = moe.dropless_moe(
            h, p["router"], p["bias"], share, held=(first, 4), top_k=k,
            route_scale=cfg.route_scale, impl="xla")
        total = total + out
    whole, counters = moe.dropless_moe(
        h, p["router"], p["bias"], p["experts"], held=(0, 16), top_k=k,
        route_scale=cfg.route_scale, impl="xla")
    assert int(counters["pairs"]) == 48 * k
    want = uncut(h)
    np.testing.assert_allclose(total, want, atol=1e-4, rtol=0)
    np.testing.assert_allclose(shared + whole, want, atol=1e-4, rtol=0)


# (c) dropless routing

def test_no_token_loses_an_expert_where_capacity_slots_drop():
    """All 96 tokens pick the same 4 experts: 96 a expert.  A capacity-slot
    router at factor 1.25 has 96 * 1.25 * 4 / 16 = 30 slots an expert and
    drops two thirds; the dropless layer computes every pair."""
    t, d, m, e, k = 96, 32, 48, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    h = jax.random.normal(ks[0], (t, d))
    router = jax.random.normal(ks[1], (d, e)) * 0.01
    bias = jnp.zeros((e,)).at[jnp.array([3, 5, 6, 9])].set(10.0)
    experts = {"w_gate": jax.random.normal(ks[2], (e, d, m)) * 0.2,
               "w_up": jax.random.normal(ks[3], (e, d, m)) * 0.2,
               "w_down": jax.random.normal(ks[4], (e, m, d)) * 0.2}
    out, counters = moe.dropless_moe(h, router, bias, experts, held=(0, e),
                                     top_k=k, impl="xla")
    assert int(counters["pairs"]) == t * k
    assert int(counters["max_load"]) == t
    assert int(counters["experts_hit"]) == 4
    idx, w = moe.sigmoid_topk_route(h, router, bias, top_k=k)
    want = sum(
        w[:, j, None] * jax.vmap(
            lambda x, i: (jax.nn.silu(x @ experts["w_gate"][i])
                          * (x @ experts["w_up"][i])) @ experts["w_down"][i]
        )(h, idx[:, j]) for j in range(k))
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=0)
    # the old layer at that load: most assignments have no slot
    logits = h @ router + bias
    dispatch, _, _ = moe.top2_route(logits, int(t * 1.25 * 2 / e))
    assert float(dispatch.sum()) < 0.5 * t * 2


def test_masked_tokens_route_nowhere():
    idx = jnp.array([[0, 1], [1, 2], [0, 3]])
    plan = moe.group_plan(idx, (0, 2), token_mask=jnp.array(
        [True, False, True]))
    assert int(plan["pairs"]) == 3 and int(plan["max_load"]) == 2
    assert (np.asarray(plan["dest"])[1] == plan["rows"]).all()


# (d) the window group's ring

def _window_group(**kw):
    args = dict(window=32, write_ahead=8, num_layers=2,
                rows=attention.KVRows(heads=2, kv_heads=2, head_dim=8),
                max_slots=2, num_blocks=24, block_size=4, max_context=128)
    return WindowKVGroup(**{**args, **kw})


def test_window_group_reserves_a_ring_and_reuses_its_blocks():
    g = _window_group()
    ring = (32 + 8) // 4 + 1
    assert g.reservation(128) == ring and g.reservation(20) == 5
    assert g.admit(0, 128) is not None
    assert g.allocator.used_blocks == ring
    held = set(g.pages[0].blocks)
    for pos in range(0, 128, 8):          # chunks of 8, as a prefill
        g.prepare_write(0, pos + 8)
        g.note_written(0, pos + 8)
        assert g.mapped_blocks(0) <= ring
        mapped = set(int(b) for b in g.block_tables[0]
                     if b != g.scratch_block)
        assert mapped <= held             # only ever its own reservation
        first_kept = max(pos + 8 - 32 + 1, 0) // 4
        assert (g.block_tables[0, :first_kept] == g.scratch_block).all()
    assert g.blocks_recycled == 128 // 4 - (32 // 4)
    assert g.allocator.used_blocks == ring     # nothing went back early
    g.release(0)
    assert g.allocator.used_blocks == 0


def test_window_group_refuses_a_write_past_its_ring():
    g = _window_group()
    g.admit(0, 128)
    with pytest.raises(OutOfBlocksError, match="ring exhausted"):
        g.prepare_write(0, 128)           # 32 blocks at once, ring is 11


def test_admission_reasons_over_both_groups():
    cfg = afmoe.afmoe_tiny()
    kv = make_grouped_cache(cfg, max_slots=2, block_size=4, max_context=128,
                            num_blocks={"full": 40, "window": 12},
                            write_ahead=8)
    assert set(kv.groups) == {"full", "window"}
    assert kv.layers == {"full": (2,), "window": (0, 1)}
    assert kv.admit(0, 128) is not None       # 32 full, 11 window
    assert kv.admit(1, 24) is None            # window pool: 1 block left
    # nothing of the refused request is left in the full group
    assert kv.groups["full"].allocator.used_blocks == 32
    kv.release(0)
    assert kv.admit(1, 24) is not None
    with pytest.raises(ValueError, match="group 'full'"):
        kv.check_fits(4 * 41)


def test_freed_blocks_are_never_read(f32_model):
    """Past the window every physical block no table names — the freed
    ones among them — is overwritten with 1e4 in both pools, every few
    iterations; the logits still match the reference."""
    cfg, params = f32_model
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, 44).tolist()
    eng = Engine(params, cfg, max_slots=2, block_size=4, prefill_chunk=8,
                 max_context=128)
    seen = _record_logits(eng)
    req = eng.submit(prompt, max_new_tokens=40)
    steps = 0
    while not req._done.is_set():
        eng.step()
        steps += 1
        if steps % 3 == 0:
            for g in eng.kv.groups.values():
                named = set(g.block_tables.ravel().tolist())
                rows = np.concatenate([
                    np.arange(b * 4, b * 4 + 4)
                    for b in range(g.allocator.num_blocks)
                    if b not in named])
                g.pools = tuple(p.at[:, rows].set(1e4) for p in g.pools)
    assert eng.kv.blocks_recycled > 0
    want = _reference_logits(cfg, params, prompt, req.tokens)
    np.testing.assert_allclose(np.stack(seen[req.id]), want, atol=F32_TOL,
                               rtol=0)


def test_step_log_carries_the_family_counters(f32_model):
    cfg, params = f32_model
    eng, _ = _serve(cfg, params, [(list(range(40)), 12)])
    rows = [r for r in eng.step_records() if r["occupancy"]]
    assert rows and all(
        {"moe_pairs", "moe_experts_hit", "moe_max_load", "kv_blocks_freed",
         "kv_blocks_used_full", "kv_blocks_used_window"} <= set(r)
        for r in rows)
    # two expert layers of 8 held experts; one token, 4 choices a layer
    assert all(0 <= r["moe_pairs"] <= 8 and r["moe_experts_hit"]
               == r["moe_pairs"] for r in rows)
    assert sum(r["kv_blocks_freed"] for r in eng.step_records()) \
        == eng.kv.blocks_recycled > 0


@pytest.mark.parametrize("flag", ["prefix_cache", "fused_sampling"])
def test_family_refuses_what_it_cannot_run_yet(f32_model, flag):
    cfg, params = f32_model
    with pytest.raises(ValueError, match=f"{flag} is not implemented"):
        Engine(params, cfg, max_slots=2, block_size=4, prefill_chunk=8,
               max_context=128, **{flag: True})


# the kernels, interpreted, against their plain formulations

@pytest.mark.parametrize("window", [None, 64, 700])
def test_paged_decode_kernel_matches_the_plain_formulation(window):
    """Slots of 70, 1210 (three grid steps of 512 rows, the window starting
    inside one) and 5 tokens, their blocks scattered over the pool."""
    layers, blocks, bs, h_kv, d, g = 2, 100, 16, 2, 128, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (layers, (blocks + 1) * bs, h_kv * d)
    k_pool = jax.random.normal(ks[0], shape)
    v_pool = jax.random.normal(ks[1], shape)
    q = jax.random.normal(ks[2], (3, h_kv * g, d))
    tables = np.full((3, 80), blocks, np.int32)
    perm = np.random.default_rng(0).permutation(blocks)
    tables[0, :5], tables[1, :76], tables[2, :1] = \
        perm[:5], perm[5:81], perm[81:82]
    lens = jnp.array([70, 1210, 5], jnp.int32)
    kw = dict(layer=1, block_size=bs, window=window)
    want = attention.paged_window_decode_attention(
        q, k_pool, v_pool, jnp.asarray(tables), lens, impl="xla", **kw)
    got = attention.paged_window_decode_attention(
        q, k_pool, v_pool, jnp.asarray(tables), lens, impl="pallas", **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("window", [None, 48])
def test_chunk_attention_matches_dense_attention(window):
    bs, h_kv, d, g, t, start = 16, 2, 32, 2, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    shape = (1, 21 * bs, h_kv * d)
    k_pool = jax.random.normal(ks[0], shape)
    v_pool = jax.random.normal(ks[1], shape)
    q = jax.random.normal(ks[2], (t, h_kv * g, d))
    row = jnp.asarray(np.random.default_rng(2).permutation(20), jnp.int32)
    got = attention.paged_chunk_attention(
        q, jnp.int32(start), k_pool, v_pool, row, layer=0, block_size=bs,
        window=window, kv_chunk=32)
    k = attention._gather_pages(k_pool, 0, row[None], bs, d)[0].transpose(
        1, 0, 2)[:start + t]
    v = attention._gather_pages(v_pool, 0, row[None], bs, d)[0].transpose(
        1, 0, 2)[:start + t]
    want = attention.xla_attention(q[None], k[None], v[None], causal=True,
                                   window=window)[0]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("window,start", [
    (None, 0),       # a full layer, the chunk alone
    (None, 300),     # ... several stretches in, the last two on the diagonal
    (4096, 100),     # a window layer inside its window: every row attended
    (4096, 4200),    # ... past it: on no stretch's boundary, early blocks
                     # freed, stretches inside every query's window unmasked
])
def test_chunk_kernel_matches_the_loop_at_heads_of_128(window, start,
                                                       check_kv_chunk_kernel):
    """``kv_chunk_attn``, interpreted, at trinity's head shape: heads of
    128, 6 a K/V head, a window of 4,096 on the window layers."""
    form = afmoe.trinity_large_ep8().cache_rows
    assert form.chunk_formulation(16, 512, "pallas") == "kv_chunk_attn"
    assert form.chunk_formulation(16, 512, "xla") == "plain"
    assert form.chunk_formulation(16, 512, "auto") == "plain"    # the CPU
    check_kv_chunk_kernel(heads=12, kv_heads=2, d=128, dv=128,
                          window=window, sink=False, start=start)


@pytest.mark.parametrize("t,tile", [(40, 16), (72, 64)],
                         ids=["tile16", "wide-tile"])
def test_grouped_matmul_kernel_matches_the_plain_loop(t, tile):
    """40 tokens top 4 of 16 spread 10 rows an expert: tiles of 16; 72
    spread 18: the wide tile (``moe.group_tile``), an expert read once."""
    d, m, e, held, k = 128, 256, 16, 4, 4
    assert moe.group_tile(t, k, e) == tile
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    h = jax.random.normal(ks[0], (t, d))
    router = jax.random.normal(ks[1], (d, e)) * 0.1
    bias = jax.random.normal(ks[2], (e,)) * 0.1
    experts = {"w_gate": jax.random.normal(ks[3], (held, d, m)) * 0.05,
               "w_up": jax.random.normal(ks[4], (held, d, m)) * 0.05,
               "w_down": jax.random.normal(ks[5], (held, m, d)) * 0.05}
    outs = [moe.dropless_moe(h, router, bias, experts, held=(4, held),
                             top_k=k, impl=impl)[0]
            for impl in ("xla", "pallas")]
    np.testing.assert_allclose(outs[1], outs[0], atol=1e-5, rtol=0)
    assert runtime.use_kernel("pallas")
    assert not runtime.use_kernel("xla")
