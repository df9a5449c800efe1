"""The ling family (Kimi-Delta-Attention layers keeping a matrix state a head
a slot, a latent-attention layer amid them, group-limited sigmoid experts of
which a share is held) on the CPU at a tiny size, seeded weights, logits
compared: the serving path (chunked prefill that scans from the state the
slot's last chunk left, decode that steps every slot's state, latent rows in
the full group) against ``benchmark/reference/ling.py``'s token-by-token
recurrence from zeros over the whole sequence and non-absorbed attention; the
cases a recurrence adds (padding, interleaving, slot re-use); group-limited
routing against a brute-force selection; the shares of the 8 chips adding up
to the uncut layer.

With float32 parameters the system and the reference do the same float32
arithmetic in another order: logits of size ~5 agree to 1e-4.
"""

import copy
import dataclasses
import functools
import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu import models
from distributedtensorflow_tpu.models import joyai, ling
from distributedtensorflow_tpu.ops import kda
from distributedtensorflow_tpu.parallel import moe
from distributedtensorflow_tpu.serve.engine import Engine
from distributedtensorflow_tpu.serve.kv_cache import make_grouped_cache
from distributedtensorflow_tpu.serve import engine as engine_module
from distributedtensorflow_tpu.serve import model as model_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-4


def _bench_module(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3] + "_ling", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module("reference", "ling.py")
COUNTS = _bench_module("counts", "ling.py")


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


_MADE = {}


def make_programs(cfg, *, chunk, block_size, layers):
    """``serve.model.make_programs`` once a (configuration, shape): every
    engine and test of this file that asks for the same programs shares their
    jitted functions (a copy of the object: a test may spy on its own), so
    each is traced and compiled once a run of the file.  The tests that patch
    what a program calls build their own (``_fresh_programs``)."""
    key = (cfg, chunk, block_size, tuple(sorted(layers.items())))
    if key not in _MADE:
        _MADE[key] = model_module.make_programs(
            cfg, chunk=chunk, block_size=block_size, layers=layers)
    return copy.copy(_MADE[key])


@pytest.fixture(autouse=True)
def _programs_compiled_once(monkeypatch):
    monkeypatch.setattr(engine_module, "make_programs", make_programs)


@pytest.fixture
def _fresh_programs(monkeypatch):
    monkeypatch.setattr(engine_module, "make_programs",
                        model_module.make_programs)


def _config_dict(cfg: ling.LingConfig) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        head_dim=cfg.head_dim, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        num_experts=cfg.held[1], expert_first=cfg.held[0],
        num_experts_published=cfg.num_experts,
        num_experts_per_tok=cfg.experts_per_token, n_group=cfg.n_group,
        topk_group=cfg.topk_group, norm_topk_prob=cfg.route_norm,
        routed_scaling_factor=cfg.route_scale,
        layer_types=list(cfg.layer_types),
        num_hidden_layers=cfg.num_layers,
        first_k_dense_replace=cfg.num_dense_layers,
        short_conv_kernel_size=cfg.conv_kernel,
        kda_lower_bound=cfg.kda_lower_bound, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps, vocab_size=cfg.vocab_size)


@pytest.fixture(scope="module")
def f32_model():
    cfg = ling.ling_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~5
    params = ling.init_params(cfg, jax.random.PRNGKey(52), std=0.2)
    return cfg, params


def _record_logits(eng):
    """``{request id: [the logits of every served position]}``, filled as
    ``eng`` runs (``tests/test_jamba.py`` has the same spy)."""
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


def _engine(cfg, params, **engine_kw):
    kw = dict(max_slots=3, block_size=4, prefill_chunk=8, max_context=256)
    return Engine(params, cfg, **{**kw, **engine_kw})


def _drive(eng, reqs):
    for _ in range(4000):
        if all(r._done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.status == "ok" for r in reqs)


def _serve(cfg, params, jobs, **engine_kw):
    """Run ``jobs`` [(prompt, n_new)] through an Engine together; returns
    per job (tokens, logits of every served position)."""
    eng = _engine(cfg, params, **engine_kw)
    seen = _record_logits(eng)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in jobs]
    _drive(eng, reqs)
    for r in reqs:      # greedy: each token the arg-max of its row
        assert r.tokens == [int(np.argmax(row)) for row in seen[r.id]]
    return eng, [(r.tokens, np.stack(seen[r.id])) for r in reqs]


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg):
    config = _config_dict(cfg)
    return jax.jit(lambda params, ids: REF.logits(params, ids, config))


def _reference_logits(cfg, params, prompt, tokens):
    """The reference's logits of the served positions.  The sequence is
    padded to whole 64s (a causal model's logits do not see what follows), so
    the reference is traced for a few lengths and not for every test's."""
    ids = list(prompt) + list(tokens)
    padded = ids + [0] * (-len(ids) % 64)
    full = _reference_fn(cfg)(params, jnp.asarray([padded]))[0]
    return np.asarray(full)[len(prompt) - 1:len(ids) - 1]


def _prompt(seed, n, cfg):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n).tolist()


def _assert_served_is_reference(cfg, params, jobs, served):
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


# (a) chunks, then decode through both groups, against the reference

@pytest.mark.parametrize("prompt_len,n_new,chunk", [
    (1, 3, 8),       # a prompt of one token: tails mostly the zeros before
    (3, 6, 8),       # shorter than the convolutions' reach
    (8, 9, 8),       # exactly one chunk: no padding at all
    (9, 12, 8),      # a second chunk of one real token (the plain form)
    (21, 12, 8),     # ends mid-chunk; decoding crosses latent block edges
    (64, 5, 64),     # one scan chunk whole (the chunked form)
    (65, 7, 64),     # one token into a second prefill and scan chunk
    (140, 6, 128),   # a prefill chunk of two scan chunks, then 12 real tokens
    (81, 4, 64),     # a sub-block boundary inside the padded chunk
])
def test_served_logits_match_the_reference(f32_model, prompt_len, n_new,
                                           chunk):
    cfg, params = f32_model
    prompt = _prompt(prompt_len, prompt_len, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, n_new)],
                                   prefill_chunk=chunk)
    want = _reference_logits(cfg, params, prompt, tokens)
    assert len(tokens) == n_new
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_whole_forward_is_the_reference(f32_model):
    cfg, params = f32_model
    ids = jnp.asarray([_prompt(5, 37, cfg), _prompt(6, 37, cfg)])
    got = np.asarray(ling.forward(params, ids, cfg))
    want = np.asarray(REF.logits(params, ids, _config_dict(cfg)))
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def test_served_through_the_interpreted_kernel_matches_the_reference(
        _fresh_programs):
    """Heads of 128 so that the step kernel takes its tiles: prefill chunks
    of 64 through the chunked form at the published head width, decode
    through ``kda_step`` interpreted (16 heads a grid step), the latent rows
    through the plain forms."""
    cfg = ling.ling_tiny(dtype=jnp.float32, num_heads=16, head_dim=128,
                         num_experts=8, experts_per_token=2, n_group=2,
                         topk_group=1, experts_held=4, expert_first=0,
                         layer_types=("kda", "mla"), vocab_size=64)
    params = ling.init_params(cfg, jax.random.PRNGKey(3), std=0.1)
    orig = kda.use_kernel
    kda.use_kernel = lambda impl: True      # the KDA step kernel alone
    try:
        prompt = _prompt(1, 70, cfg)
        eng, [(tokens, logits)] = _serve(cfg, params, [(prompt, 4)],
                                         prefill_chunk=64, max_slots=2)
        assert eng.programs.chunk_scan == "chunked"
        assert cfg.state_rows.step_formulation("auto") == "kda_step"
    finally:
        kda.use_kernel = orig
    want = _reference_logits(cfg, params, prompt, tokens)
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


@pytest.mark.parametrize("control", ["bf16_state", "dropped_delta"])
def test_the_tolerance_refuses_a_bfloat16_state_and_a_dropped_delta(
        f32_model, control, monkeypatch, _fresh_programs):
    """``F32_TOL`` is tight enough: with the matrix state handed on in
    bfloat16 between programs, or the rule without its correction (``S' +
    beta k v^T``), the same served logits miss the reference by 10 to 1000
    times the tolerance (``tools/kda_controls.py`` has the two on the
    chip)."""
    from distributedtensorflow_tpu.serve import model

    monkeypatch.syspath_prepend(os.path.join(ROOT, "tools"))
    controls = importlib.import_module("kda_controls")
    # undone after the test: the tool patches the names for a whole process
    monkeypatch.setattr(model, "kda_chunk_scan", model.kda_chunk_scan)
    monkeypatch.setattr(model, "kda_step", model.kda_step)
    controls.patch(control)
    cfg, params = f32_model
    prompt = _prompt(7, 70, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, 6)],
                                   prefill_chunk=64)
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.abs(logits - want).max() > 10 * F32_TOL


# (b) what a recurrence adds: padding, interleaving, slot re-use

def _programs(cfg, max_slots=3, chunk=8):
    kv = make_grouped_cache(cfg, max_slots=max_slots, block_size=4,
                            max_context=64, num_blocks={}, write_ahead=chunk)
    progs = make_programs(cfg, chunk=chunk, block_size=4, layers=kv.layers)
    return kv, progs


def _chunk(progs, params, kv, slot, tokens, start, real):
    """One prefill chunk of ``slot`` straight through the program, the slot's
    blocks ``slot * 16 ...``; returns the state arrays after it."""
    table = {"full": jnp.arange(16, dtype=jnp.int32) + 16 * slot,
             "state": jnp.asarray([slot], jnp.int32)}
    padded = np.zeros((progs.chunk,), np.int32)
    padded[:len(tokens)] = tokens
    _, pools = progs.prefill(params, kv.pools(), padded, start, table, real)
    kv.set_pools(pools)
    return [np.asarray(a) for a in pools["state"]]


@pytest.mark.parametrize("n", [1, 2, 5, 8])
def test_padding_is_the_identity(f32_model, n):
    """A chunk of ``n`` real tokens (the rest padding, of any value) leaves
    the matrix state and the three tails that ``n`` tokens leave, also for
    ``n`` under the convolutions' reach; the other slots' stay zero."""
    cfg, params = f32_model
    tokens = _prompt(n, n, cfg)
    kv, progs = _programs(cfg)
    padded = tokens + _prompt(99, 8 - n, cfg)      # the padding is not zeros
    got = _chunk(progs, params, kv, 1, padded, 0, n)
    kv2, progs2 = _programs(cfg)
    want = _chunk(progs2, params, kv2, 1, tokens + [0] * (8 - n), 0, n)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a[:, 1], b[:, 1], atol=2e-5)
        assert not a[:, [0, 2]].any()
    # and the state is the recurrence's over exactly n tokens
    rows = cfg.state_rows
    states = []

    class Exact(ling._FreshState):
        def delta(self, q, k, v, g, beta):
            o, s = kda.kda_recurrent(q, k, v, g, beta, jnp.zeros(
                (rows.heads, rows.value_dim, rows.key_dim)))
            states.append(s)
            return o

    @jax.jit
    def two_layers(params, tokens):
        x = ling.embed(params, tokens, cfg)
        for i in (0, 1):        # the two leading layers are KDA
            x, _ = ling.block(params[f"h{i}"], x, cfg, i, None, Exact(cfg),
                              token_mask=jnp.ones((n,), bool))
        return jnp.stack(states)

    np.testing.assert_allclose(
        got[3][:2, 1], two_layers(params, jnp.asarray(tokens)), atol=2e-5)


def test_interleaved_requests_are_each_served_alone(f32_model):
    """Chunks of A between decode steps of B and chunks of C: each
    request's logits are the reference's for that request alone."""
    cfg, params = f32_model
    jobs = [(_prompt(1, 5, cfg), 40), (_prompt(2, 60, cfg), 12),
            (_prompt(3, 29, cfg), 20)]
    eng = _engine(cfg, params, prefill_budget=8)
    seen = _record_logits(eng)
    first = eng.submit(*jobs[0][:1], max_new_tokens=jobs[0][1])
    for _ in range(6):          # B decodes before A and C arrive
        eng.step()
    reqs = [first] + [eng.submit(p, max_new_tokens=n) for p, n in jobs[1:]]
    _drive(eng, reqs)
    mixed = [r for r in eng.step_records()
             if r["prefill_chunks"] and r["occupancy"]]
    assert len(mixed) >= 8      # chunks and decode steps in one iteration
    served = [(r.tokens, np.stack(seen[r.id])) for r in reqs]
    _assert_served_is_reference(cfg, params, jobs, served)


def test_decode_leaves_an_inactive_slots_state_untouched(f32_model):
    """Bit for bit, tails and matrices: a slot between two of its prefill
    chunks is inactive while the others decode."""
    cfg, params = f32_model
    kv, progs = _programs(cfg)
    _chunk(progs, params, kv, 0, _prompt(0, 8, cfg), 0, 8)
    before = _chunk(progs, params, kv, 1, _prompt(1, 8, cfg), 0, 8)
    tables = {"full": jnp.arange(48, dtype=jnp.int32).reshape(3, 16),
              "state": jnp.arange(3, dtype=jnp.int32)[:, None]}
    active = jnp.asarray([True, False, False])
    _, _, pools, _ = progs.decode(
        params, kv.pools(), jnp.asarray([7, 8, 9], jnp.int32), tables,
        jnp.asarray([8, 8, 0], jnp.int32), active)
    after = [np.asarray(a) for a in pools["state"]]
    for b, a in zip(before, after):
        assert np.array_equal(b[:, 1:], a[:, 1:])       # slots 1 and 2
        assert not np.array_equal(b[:, 0], a[:, 0])     # slot 0 stepped


def test_a_reused_slot_starts_from_zeros(f32_model):
    """One slot, three requests one after the other: the second and third
    find the state and tails their predecessor left and must not see
    them."""
    cfg, params = f32_model
    jobs = [(_prompt(i, n, cfg), m)
            for i, (n, m) in enumerate([(30, 10), (3, 12), (17, 8)])]
    eng, served = _serve(cfg, params, jobs, max_slots=1)
    assert eng.counters["admits_into_freed_slot"] >= 2
    assert all(np.asarray(a).any() for a in eng.kv.state.pools)
    _assert_served_is_reference(cfg, params, jobs, served)


# (c) group-limited routing

def _brute_force_route(scores, bias, top_k, n_group, topk_group):
    """Per token, in Python: the groups by the sum of their two largest
    biased scores, the best ``topk_group`` (the first of equals), the top
    ``top_k`` inside them."""
    out = []
    per = scores.shape[1] // n_group
    for s in scores:
        c = s + bias
        group = [sum(sorted(c[g * per:(g + 1) * per])[-2:])
                 for g in range(n_group)]
        keep = sorted(range(n_group), key=lambda g: (-group[g], g))
        keep = set(keep[:topk_group])
        allowed = [e for e in range(len(c)) if e // per in keep]
        out.append(sorted(allowed, key=lambda e: (-c[e], e))[:top_k])
    return np.asarray(out)


@pytest.mark.parametrize("experts,n_group,topk_group,top_k", [
    (16, 4, 2, 4), (64, 8, 4, 8), (32, 4, 1, 2), (16, 4, 4, 4)])
def test_group_limited_routing_is_the_brute_force_selection(
        experts, n_group, topk_group, top_k):
    ks = jax.random.split(jax.random.PRNGKey(experts), 3)
    h = jax.random.normal(ks[0], (40, 24))
    router = jax.random.normal(ks[1], (24, experts))
    bias = jax.random.normal(ks[2], (experts,)) * 0.3
    idx, w = moe.sigmoid_topk_route(
        h, router, bias, top_k=top_k, route_scale=2.5, n_group=n_group,
        topk_group=topk_group)
    scores = np.asarray(jax.nn.sigmoid(h @ router))
    want = _brute_force_route(scores, np.asarray(bias), top_k, n_group,
                              topk_group)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want, -1))
    picked = np.take_along_axis(scores, np.asarray(idx), -1)
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * 2.5, rtol=1e-6)
    # the reference's own selection, by ranks and masks
    ref = REF.route({"router": router, "bias": bias}, h, dict(
        num_experts_published=experts, num_experts_per_tok=top_k,
        n_group=n_group, topk_group=topk_group, norm_topk_prob=True,
        routed_scaling_factor=2.5))
    dense = np.zeros_like(scores)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(w), -1)
    np.testing.assert_allclose(ref, dense, atol=1e-6)


def test_one_group_is_todays_selection_bit_for_bit():
    """``n_group=1`` (every caller before this family) takes the same
    indices and weights as the selection without groups did."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    h = jax.random.normal(ks[0], (64, 32)).astype(jnp.bfloat16)
    router = jax.random.normal(ks[1], (32, 48))
    bias = jax.random.normal(ks[2], (48,)) * 0.1
    idx, w = moe.sigmoid_topk_route(h, router, bias, top_k=6,
                                    route_scale=2.5)
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST))
    _, want = jax.lax.top_k(scores + bias, 6)
    want_w = jnp.take_along_axis(scores, want, -1)
    want_w = want_w / (want_w.sum(-1, keepdims=True) + 1e-20) * 2.5
    assert np.array_equal(idx, want) and np.array_equal(w, want_w)
    same = moe.sigmoid_topk_route(h, router, bias, top_k=6, route_scale=2.5,
                                  n_group=1, topk_group=1)
    assert np.array_equal(same[0], idx) and np.array_equal(same[1], w)


def test_joyai_family_routes_in_groups_where_its_config_says_so():
    """What ``JoyaiConfig`` refused until this family: ``n_group`` 4, the
    best 2.  Every chosen expert lies in at most 2 groups of 4 experts."""
    cfg = joyai.joyai_tiny(dtype=jnp.float32, n_group=4, topk_group=2)
    params = joyai.init_params(cfg, jax.random.PRNGKey(4), std=0.2)
    h = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden_size))
    p = params["h1"]["moe"]
    idx, _ = moe.sigmoid_topk_route(
        h, p["router"], p["bias"], top_k=cfg.experts_per_token,
        n_group=cfg.n_group, topk_group=cfg.topk_group)
    groups = np.asarray(idx) // (cfg.num_experts // cfg.n_group)
    assert max(len(set(row)) for row in groups) <= 2
    free, _ = moe.sigmoid_topk_route(h, p["router"], p["bias"],
                                     top_k=cfg.experts_per_token)
    assert not np.array_equal(np.sort(idx, -1), np.sort(free, -1))
    x, counters = jax.jit(lambda p, h: joyai.block(
        p, h, cfg, 1, jnp.arange(24, dtype=jnp.int32),
        lambda q, row, **kw: jnp.zeros((24, cfg.num_heads, cfg.v_head_dim)))
    )(params["h1"], h)
    assert int(counters["groups_hit"]) <= 24 * 2
    with pytest.raises(ValueError, match="equal groups"):
        joyai.joyai_tiny(n_group=3)


def test_the_shares_add_up(f32_model):
    """The expert layer's terms of the 4 chips of the tiny deployment (a
    group of 4 experts each), the shared expert counted once, sum to the
    uncut layer's output: nothing is lost or counted twice by holding a
    share."""
    cfg, params = f32_model
    whole = dataclasses.replace(cfg, experts_held=None, expert_first=0)
    key = jax.random.PRNGKey(9)
    p_whole = ling.init_params(whole, key, std=0.2)["h1"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(10), (24, cfg.hidden_size))
    kw = dict(top_k=cfg.experts_per_token, route_scale=cfg.route_scale,
              n_group=cfg.n_group, topk_group=cfg.topk_group, impl="xla")
    full, full_counters = moe.dropless_moe(
        h, p_whole["router"], p_whole["bias"], p_whole["experts"],
        held=(0, cfg.num_experts), **kw)
    total, pairs = 0.0, 0
    for first in range(0, cfg.num_experts, 4):
        share = jax.tree.map(lambda a: a[first:first + 4],
                             p_whole["experts"])
        part, counters = moe.dropless_moe(
            h, p_whole["router"], p_whole["bias"], share, held=(first, 4),
            **kw)
        total = total + part
        pairs += int(counters["pairs"])
    np.testing.assert_allclose(total, full, atol=1e-5)
    assert pairs == int(full_counters["pairs"]) == 24 * cfg.experts_per_token
    # every token's choices lie in exactly topk_group groups or fewer
    assert int(full_counters["groups_hit"]) <= 24 * cfg.topk_group
    # and the reference's share is the same share
    config = {**_config_dict(cfg)}
    want = REF._experts(params["h1"]["moe"], h, config)
    got, _ = moe.dropless_moe(
        h, params["h1"]["moe"]["router"], params["h1"]["moe"]["bias"],
        params["h1"]["moe"]["experts"], held=cfg.held, **kw)
    np.testing.assert_allclose(got, want, atol=1e-5)


# (d) the engine's view

def test_every_slot_live_under_load(f32_model):
    cfg, params = f32_model
    jobs = [(_prompt(i, 5 + 7 * i, cfg), 10 + i) for i in range(6)]
    eng, served = _serve(cfg, params, jobs, max_slots=3)
    assert eng.state()["occupancy_max"] == 3
    _assert_served_is_reference(cfg, params, jobs, served)
    kv = eng.kv.stats()
    assert kv["blocks_free"] == kv["blocks_total"]
    assert kv["state"]["slots_live"] == 0


def test_bfloat16_preset_serves_finite_logits_near_the_reference():
    cfg = ling.ling_tiny()
    params = ling.init_params(cfg, jax.random.PRNGKey(7), std=0.2)
    prompt = _prompt(4, 19, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, 8)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.isfinite(logits).all()
    assert np.abs(logits - want).max() < 0.25 * np.abs(want).max()


def test_groups_census_and_what_a_state_group_refuses(f32_model):
    cfg, params = f32_model
    eng = _engine(cfg, params)
    assert eng.kv.layers == {"full": (2,), "state": (0, 1, 3)}
    state = eng.state()
    assert state["state_form"] == "q_tail+k_tail+v_tail+delta_state"
    assert state["chunk_scan"] == "plain"       # a chunk of 8
    assert state["decode_attention"] == "plain"
    rows = cfg.state_rows
    assert eng.kv.stats()["state"]["slot_bytes"] == 3 * rows.slot_bytes(
        jnp.float32)
    # the latent row, five... here (32 + 8) values a token in one layer
    assert eng.kv.row_bytes == eng.kv.groups["full"].row_bytes
    for flag, kw in (("prefix_cache", dict(prefix_cache=True)),
                     ("fused_sampling", dict(fused_sampling=True)),
                     ("speculate", dict(speculate=2))):
        with pytest.raises(ValueError, match=flag.split("_")[0]):
            _engine(cfg, params, **kw)


def test_step_log_carries_the_family_counters(f32_model):
    cfg, params = f32_model
    eng, _ = _serve(cfg, params, [(_prompt(1, 20, cfg), 6),
                                  (_prompt(2, 9, cfg), 6)])
    decoded = [r for r in eng.step_records() if r["occupancy"]]
    assert decoded
    layers = 3      # expert layers
    for r in decoded:
        occ = r["occupancy"]
        assert 0 <= r["moe_pairs"] <= occ * cfg.experts_per_token * layers
        assert occ * layers <= r["moe_groups_hit"] \
            <= occ * cfg.topk_group * layers
        assert "state_bytes_step" not in r      # a constant is no counter
        assert r["latent_rows_read"] > 0
    # counted after the iteration's releases: the last record sees none live
    assert max(r["state_slots_used"] for r in decoded) == 2
    chunks = [r for r in eng.step_records() if r["prefill_chunks"]]
    assert sum(r["scan_tokens"] for r in chunks) >= 29


def test_published_widths_2871m_parameters_12_6_mb_a_slot_1152_b_a_token():
    """``ling3_flash_ep8`` by shapes alone (nothing is allocated): the
    parameter tree against ``counts/ling.py`` and the issue's arithmetic."""
    cfg = models.ling3_flash_ep8()
    assert cfg.layer_types == ("kda",) * 4 + ("mla",) + ("kda",) * 2
    assert cfg.held == (0, 64) and cfg.num_experts == 512
    assert cfg.num_experts // cfg.n_group == cfg.held[1]   # one group held
    assert cfg.expert_first % 64 == 0
    tree = jax.eval_shape(lambda: ling.init_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    total = sum(int(np.prod(leaf.shape)) for _, leaf in leaves)
    config = _harness_config()
    counted = COUNTS.params(config)
    norms = sum(int(np.prod(leaf.shape)) for path, leaf in leaves
                if "norm" in jax.tree_util.keystr(path)
                or "ln_" in jax.tree_util.keystr(path))
    assert total - norms == counted
    assert 2.86e9 < counted < 2.88e9
    rows = cfg.state_rows
    assert 6 * rows.slot_bytes(cfg.dtype) == COUNTS.state_bytes_per_slot(
        config) == 6 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    assert cfg.cache_rows.values == (576,)
    assert COUNTS.kv_bytes_per_token(config) == 1152
    with pytest.raises(ValueError, match="SwiGLU limit"):
        dataclasses.replace(cfg, swiglu_limits=(0, 0, 0, 0, 0, 4, 4))


def _harness_config() -> dict:
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-vl-ep8-serve.json")) as f:
        return json.load(f)


def test_configuration_file_says_what_the_preset_is():
    """The benchmark's file and ``ling3_flash_ep8`` are one configuration."""
    config, cfg = _harness_config(), models.ling3_flash_ep8()
    want = _config_dict(cfg)
    for key, value in want.items():
        assert config[key] == value, key
    assert config["max_position_embeddings"] == cfg.max_seq
    assert not any(config["expert_swiglu_limit_list"])
    assert not any(config["share_expert_swiglu_limit_list"])
    assert len(config["expert_swiglu_limit_list"]) == cfg.num_layers
