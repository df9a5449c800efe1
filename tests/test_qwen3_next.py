"""The qwen3_next family (Gated DeltaNet layers keeping a matrix state a value
head a slot under one unbounded scalar gate, fewer key heads than value heads;
gated attention with rotary on a quarter of the head; softmax-routed experts of
which a share is held and a gated shared expert in every layer) on the CPU at a
tiny size, seeded weights, logits compared: the serving path (chunked prefill
that scans from the state the slot's last chunk left, decode that steps every
slot's state in place, K/V rows in the full group) against
``benchmark/reference/qwen3_next.py``'s token-by-token recurrence from zeros
over the whole sequence and dense attention; the cases a recurrence adds
(padding, interleaving, slot re-use); the shares of the 4 chips adding up to
the uncut layer; the counts against the parameter tree and the model's
published name.  The three forms of the scalar-gated rule are held to each
other in ``tests/test_kda.py``.

With float32 parameters the system and the reference do the same float32
arithmetic in another order: logits of size ~4 agree to 1e-4.
"""

import copy
import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu import models
from distributedtensorflow_tpu.models import qwen3_next
from distributedtensorflow_tpu.parallel import moe
from distributedtensorflow_tpu.serve import engine as engine_module
from distributedtensorflow_tpu.serve import model as model_module
from distributedtensorflow_tpu.serve.engine import Engine
from distributedtensorflow_tpu.serve.kv_cache import make_grouped_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-4


def _bench_module(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3] + "_qwen3_next", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module("reference", "qwen3_next.py")
COUNTS = _bench_module("counts", "qwen3_next.py")


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


_MADE = {}


def make_programs(cfg, *, chunk, block_size, layers):
    """``serve.model.make_programs`` once a (configuration, shape): every
    engine and test of this file that asks for the same programs shares their
    jitted functions (``tests/test_ling.py`` has the same)."""
    key = (cfg, chunk, block_size, tuple(sorted(layers.items())))
    if key not in _MADE:
        _MADE[key] = model_module.make_programs(
            cfg, chunk=chunk, block_size=block_size, layers=layers)
    return copy.copy(_MADE[key])


@pytest.fixture(autouse=True)
def _programs_compiled_once(monkeypatch):
    monkeypatch.setattr(engine_module, "make_programs", make_programs)


def _config_dict(cfg: qwen3_next.Qwen3NextConfig) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        hidden_size=cfg.hidden_size, num_hidden_layers=cfg.num_layers,
        full_attention_interval=cfg.full_attention_interval,
        linear_num_key_heads=cfg.linear_key_heads,
        linear_num_value_heads=cfg.linear_value_heads,
        linear_key_head_dim=cfg.linear_key_dim,
        linear_value_head_dim=cfg.linear_value_dim,
        linear_conv_kernel_dim=cfg.conv_kernel,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        partial_rotary_factor=cfg.rotary_dim / cfg.head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        moe_intermediate_size=cfg.moe_intermediate_size,
        shared_expert_intermediate_size=cfg.shared_intermediate_size,
        num_experts=cfg.held[1], expert_first=cfg.held[0],
        num_experts_published=cfg.num_experts,
        num_experts_per_tok=cfg.experts_per_token,
        norm_topk_prob=cfg.route_norm, vocab_size=cfg.vocab_size)


@pytest.fixture(scope="module")
def f32_model():
    cfg = qwen3_next.qwen3_next_tiny(dtype=jnp.float32)
    # std 0.12: logits of size ~4.  (At the other families' 0.2 this one is
    # a high-gain map: L2-normalised q and k, a gated norm over the rule's
    # output and gates of -60 a token carry 1e-6 of relative noise on the
    # embedding to 6e-4 on the logits, in the reference itself.)
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(58), std=0.12)
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


# (1) what the family is made of

def test_tiny_has_every_mechanism():
    cfg = qwen3_next.qwen3_next_tiny()
    assert [cfg.keeps_state(i) for i in range(5)] == [True] * 3 + [False,
                                                                   True]
    assert cfg.linear_key_heads < cfg.linear_value_heads
    assert cfg.held == (4, 4) and cfg.num_experts == 16
    assert cfg.rotary_dim < cfg.head_dim
    rows = cfg.state_rows
    assert rows.names == ("conv_tail", "delta_state")
    assert rows.chunk_formulation(256, "auto") == "chunked"
    assert rows.chunk_formulation(8, "auto") == "plain"


# (2) chunks, then decode through both groups, against the reference

@pytest.mark.parametrize("prompt_len,n_new,chunk", [
    (1, 3, 8),       # a prompt of one token: the tail mostly the zeros before
    (3, 6, 8),       # shorter than the convolution's reach
    (8, 9, 8),       # exactly one chunk: no padding at all
    (21, 12, 8),     # ends mid-chunk; decoding crosses K/V block edges
    (64, 4, 64),     # one scan chunk whole (the chunked form)
    (65, 5, 64),     # one token into a second prefill and scan chunk
    (150, 4, 128),   # a prefill chunk of two scan chunks, then 22 real tokens
])
def test_served_logits_match_the_reference(f32_model, prompt_len, n_new,
                                           chunk):
    cfg, params = f32_model
    prompt = _prompt(prompt_len, prompt_len, cfg)
    eng, [(tokens, logits)] = _serve(cfg, params, [(prompt, n_new)],
                                     prefill_chunk=chunk, max_context=512)
    assert eng.programs.chunk_scan == ("plain" if chunk == 8 else "chunked")
    want = _reference_logits(cfg, params, prompt, tokens)
    assert len(tokens) == n_new
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_whole_forward_is_the_reference(f32_model):
    cfg, params = f32_model
    ids = jnp.asarray([_prompt(5, 37, cfg), _prompt(6, 37, cfg)])
    got = np.asarray(qwen3_next.forward(params, ids, cfg))
    want = np.asarray(REF.logits(params, ids, _config_dict(cfg)))
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def test_interleaved_requests_are_each_served_alone(f32_model):
    """Chunks of A between decode steps of B and chunks of C, two slots
    decoding side by side: each request's logits are the reference's for that
    request alone."""
    cfg, params = f32_model
    jobs = [(_prompt(1, 5, cfg), 40), (_prompt(2, 60, cfg), 12),
            (_prompt(3, 29, cfg), 20)]
    eng = _engine(cfg, params, prefill_budget=8)
    seen = _record_logits(eng)
    first = eng.submit(*jobs[0][:1], max_new_tokens=jobs[0][1])
    for _ in range(6):          # A decodes before B and C arrive
        eng.step()
    reqs = [first] + [eng.submit(p, max_new_tokens=n) for p, n in jobs[1:]]
    _drive(eng, reqs)
    mixed = [r for r in eng.step_records()
             if r["prefill_chunks"] and r["occupancy"]]
    assert len(mixed) >= 8      # chunks and decode steps in one iteration
    served = [(r.tokens, np.stack(seen[r.id])) for r in reqs]
    _assert_served_is_reference(cfg, params, jobs, served)


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


@pytest.mark.parametrize("n", [1, 2, 5])
def test_padding_is_the_identity(f32_model, n):
    """A chunk of ``n`` real tokens (the rest padding, of any value) leaves
    the matrix state and the tail that ``n`` tokens leave, also for ``n``
    under the convolution's reach; the other slots' stay zero."""
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


def test_decode_leaves_an_inactive_slots_state_untouched(f32_model):
    """Bit for bit, tail and matrices: a slot between two of its prefill
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
    find the state and tail their predecessor left and must not see them."""
    cfg, params = f32_model
    jobs = [(_prompt(i, n, cfg), m)
            for i, (n, m) in enumerate([(30, 10), (3, 12), (17, 8)])]
    eng, served = _serve(cfg, params, jobs, max_slots=1)
    assert eng.counters["admits_into_freed_slot"] >= 2
    assert all(np.asarray(a).any() for a in eng.kv.state.pools)
    _assert_served_is_reference(cfg, params, jobs, served)


# (3) the shares add up

def test_the_shares_add_up(f32_model):
    """The routed terms of the 4 chips of the tiny deployment (4 experts
    each), with the gated shared expert counted once, sum to the uncut layer
    of the reference: nothing is lost or counted twice by holding a share."""
    cfg, _ = f32_model
    whole = dataclasses.replace(cfg, experts_held=None, expert_first=0)
    p = qwen3_next.init_params(whole, jax.random.PRNGKey(9), std=0.2)["h1"][
        "moe"]
    h = jax.random.normal(jax.random.PRNGKey(10), (24, cfg.hidden_size))
    uncut = REF.routed(p, h, _config_dict(whole)) + REF.shared(p, h)
    gated = np.asarray(REF.shared(p, h))
    total, pairs = gated, 0
    for first in range(0, cfg.num_experts, 4):
        share = dataclasses.replace(cfg, experts_held=4, expert_first=first)
        mine = {**p, "experts": jax.tree.map(lambda a: a[first:first + 4],
                                             p["experts"])}
        out, counters = qwen3_next._moe(mine, h, share, None)
        np.testing.assert_allclose(     # every chip's shared term is alike
            out - REF.routed(mine, h, _config_dict(share)), gated, atol=2e-5)
        total = total + out - gated
        pairs += int(counters["pairs"])
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    assert pairs == 24 * cfg.experts_per_token


# (4) the counts

def _harness_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-ep4-serve.json")) as f:
        return json.load(f)


def test_published_widths_3667m_parameters_12_9_mb_a_slot_4096_b_a_token():
    """``qwen3_next_ep4`` by shapes alone (nothing is allocated): the
    parameter tree against ``counts/qwen3_next.py`` and the issue's
    arithmetic; the published keys give the model's name, 80B-A3B."""
    cfg = models.qwen3_next_ep4()
    assert [cfg.keeps_state(i) for i in range(8)] == [
        True, True, True, False] * 2
    assert cfg.held == (0, 128) and cfg.num_experts == 512
    tree = jax.eval_shape(
        lambda: qwen3_next.init_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    total = sum(int(np.prod(leaf.shape)) for _, leaf in leaves)
    norms = sum(int(np.prod(leaf.shape)) for path, leaf in leaves
                if "norm" in jax.tree_util.keystr(path)
                or "'ln" in jax.tree_util.keystr(path))
    config = _harness_config()
    counted = COUNTS.params(config)
    assert total - norms == counted == config["parameters"] == 3667214720
    assert COUNTS.gdn_params(config) == 33718336
    assert COUNTS.attention_params(config) == 27262976
    assert 79.6e9 < COUNTS.published_params(config) < 79.75e9
    assert 3.2e9 < COUNTS.published_active_params(config) < 3.4e9
    rows = cfg.state_rows
    assert 6 * rows.slot_bytes(cfg.dtype) == COUNTS.state_bytes_per_slot(
        config) == 6 * (2097152 + 49152)
    assert COUNTS.kv_bytes_per_token(config) \
        == config["cache_bytes_per_token"] == 4096


def test_configuration_file_says_what_the_preset_is():
    """The benchmark's file and ``qwen3_next_ep4`` are one configuration, and
    the file keeps every published width."""
    config, cfg = _harness_config(), models.qwen3_next_ep4()
    for key, value in _config_dict(cfg).items():
        assert config[key] == value, key
    assert config["max_position_embeddings"] == cfg.max_seq
    assert config["num_hidden_layers_published"] == 48
    assert config["num_experts_published"] == 512
    assert config["vocab_size_published"] == 151936
    assert set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"}
    assert {"norm", "head_order", "gates"} <= set(config["assumed"])
    assert "multi-token prediction" in config["left_out"]


# (5) what the tolerance refuses

@pytest.mark.parametrize("variant", [
    "rotary_on_the_whole_head", "plain_norm", "value_head_mod_key_heads",
    "no_attention_gate", "no_shared_gate", "sigmoid_router",
    "no_renormalisation", "no_decay"])
def test_the_tolerance_refuses_another_mathematics(f32_model, variant,
                                                   monkeypatch):
    """``F32_TOL`` is tight enough: a program with rotary on the whole head,
    without the ``1 +`` of a norm, with value head ``h`` on key head ``h %
    Hk``, without the gate on attention or on the shared expert, with sigmoid
    routing, without the renormalisation, or without the decay misses the
    reference by more than 100 tolerances."""
    cfg, params = f32_model
    ids = _prompt(11, 40, cfg)
    if variant == "rotary_on_the_whole_head":
        cfg = dataclasses.replace(cfg, rotary_dim=cfg.head_dim)
    elif variant == "plain_norm":
        monkeypatch.setattr(
            qwen3_next, "_norm",
            lambda x, w, eps: qwen3_next.rms_norm(x, w, eps))
    elif variant in ("value_head_mod_key_heads", "no_decay"):
        real = qwen3_next.kda_recurrent

        def changed(q, k, v, g, beta, state, valid=None):
            if variant == "no_decay":
                g = 0.0 * g
            else:
                reps = v.shape[1] // q.shape[1]
                q, k = jnp.tile(q, (1, reps, 1)), jnp.tile(k, (1, reps, 1))
            return real(q, k, v, g, beta, state, valid)
        monkeypatch.setattr(qwen3_next, "kda_recurrent", changed)
    elif variant == "no_attention_gate":
        # the gate's columns of W_q zeroed and W_o doubled: 2 sigmoid(0) = 1
        params = dict(params)
        for i in range(cfg.num_layers):
            if not cfg.keeps_state(i):
                attn = params[f"h{i}"]["attn"]
                w = attn["w_q"].reshape(cfg.hidden_size, cfg.num_heads, 2,
                                        cfg.head_dim).at[:, :, 1].set(0.0)
                params[f"h{i}"] = {**params[f"h{i}"], "attn": {
                    **attn, "w_q": w.reshape(cfg.hidden_size, -1),
                    "w_o": 2.0 * attn["w_o"]}}
    elif variant == "no_shared_gate":
        # the gate's vector zeroed and the shared W_down doubled
        params = dict(params)
        for i in range(cfg.num_layers):
            m = params[f"h{i}"]["moe"]
            params[f"h{i}"] = {**params[f"h{i}"], "moe": {
                **m, "w_shared_gate": jnp.zeros_like(m["w_shared_gate"]),
                "shared": {**m["shared"],
                           "w_down": 2.0 * m["shared"]["w_down"]}}}
    elif variant == "sigmoid_router":
        def sigmoid_route(h, router, *, top_k, route_norm):
            return moe.sigmoid_topk_route(
                h, router, jnp.zeros((router.shape[-1],)), top_k=top_k,
                route_norm=route_norm)
        monkeypatch.setattr(moe, "softmax_topk_route", sigmoid_route)
    else:
        cfg = dataclasses.replace(cfg, route_norm=False)
    got = np.asarray(qwen3_next.forward(params, jnp.asarray([ids]), cfg))[0]
    want = np.asarray(REF.logits(
        f32_model[1], jnp.asarray([ids]), _config_dict(f32_model[0])))[0]
    assert np.abs(got - want).max() > 100 * F32_TOL


# (6) the softmax router

def test_softmax_route_is_the_reference_route(f32_model):
    cfg, _ = f32_model
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    h = jax.random.normal(k[0], (12, cfg.hidden_size))
    router = jax.random.normal(k[1], (cfg.hidden_size, cfg.num_experts))
    idx, w = moe.softmax_topk_route(h, router, top_k=3)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    dense = np.zeros((12, cfg.num_experts), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(w), -1)
    want = REF.route({"router": router}, h, {
        **_config_dict(cfg), "num_experts_published": cfg.num_experts})
    np.testing.assert_allclose(dense, want, atol=1e-6)
    _, raw = moe.softmax_topk_route(h, router, top_k=3, route_norm=False)
    assert (np.asarray(raw.sum(-1)) < 1.0).all()


# (7) the engine's view

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
    cfg = qwen3_next.qwen3_next_tiny()
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(7), std=0.2)
    prompt = _prompt(4, 19, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, 8)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.isfinite(logits).all()
    assert np.abs(logits - want).max() < 0.25 * np.abs(want).max()


@pytest.mark.parametrize("flag,kw,why", [
    ("prefix_cache", dict(prefix_cache=True),
     "a shared prefix has no snapshot of the state"),
    ("fused_sampling", dict(fused_sampling=True), "has no state formulation"),
    ("speculate", dict(fused_sampling=True, speculate=2),
     "has no state formulation"),
])
def test_what_a_state_group_refuses(f32_model, flag, kw, why):
    """jamba's refusals, in ``_STATE_LACKS``' words (the engine asks for the
    sampled program first, so ``--speculate`` meets that refusal)."""
    cfg, params = f32_model
    want = "fused_sampling" if flag == "speculate" else flag
    where = ("over a state group" if flag == "prefix_cache"
             else "for the qwen3_next family")
    with pytest.raises(ValueError,
                       match=f"{want} is not implemented {where} yet .*{why}"):
        _engine(cfg, params, **kw)


def test_speculation_is_refused_for_what_a_state_cannot_do(f32_model):
    cfg, _ = f32_model
    _, progs = _programs(cfg)
    with pytest.raises(ValueError, match="speculate is not implemented for "
                       "the qwen3_next family yet .a rejected draft cannot "
                       "be rolled back out of a state"):
        progs.fused(2)


def test_groups_census_and_step_log(f32_model):
    """The engine says what the state group keeps and logs the family's
    counters: the routed pairs, the state's slots, the tokens through the
    rule and the pairs a prefill chunk's real queries attend."""
    cfg, params = f32_model
    eng, _ = _serve(cfg, params, [(_prompt(1, 20, cfg), 6),
                                  (_prompt(2, 9, cfg), 6)])
    assert eng.kv.layers == {"full": (3,), "state": (0, 1, 2, 4)}
    state = eng.state()
    assert state["state_form"] == "conv_tail+delta_state"
    assert state["chunk_scan"] == "plain"       # a chunk of 8
    assert state["decode_attention"] == "plain"
    rows = cfg.state_rows
    assert eng.kv.stats()["state"]["slot_bytes"] == 4 * rows.slot_bytes(
        jnp.float32)
    decoded = [r for r in eng.step_records() if r["occupancy"]]
    assert decoded
    for r in decoded:
        occ = r["occupancy"]
        assert 0 <= r["moe_pairs"] <= occ * cfg.experts_per_token * 5
        assert r["moe_experts_hit"] <= 5 * cfg.held[1]
    assert max(r["state_slots_used"] for r in decoded) == 2
    chunks = [r for r in eng.step_records() if r["prefill_chunks"]]
    # the rule's tokens: the chunks' real ones and one a decoding slot
    assert sum(r["scan_tokens"] for r in eng.step_records()) \
        == 29 + sum(r["occupancy"] for r in decoded)
    assert sum(r["chunk_tokens"] for r in eng.step_records()) == 20 + 9
    # a prompt of n tokens attends n (n + 1) / 2 pairs, however it is chunked
    assert sum(r["chunk_pairs"] for r in eng.step_records()) \
        == 20 * 21 // 2 + 9 * 10 // 2


def _shapes(jaxpr):
    """The shape of every value ``jaxpr`` and the jaxprs inside it compute."""
    from jax._src import core

    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(getattr(var.aval, "shape", ()))
        for sub in core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("preset", ["qwen3_next_tiny", "ling_tiny"])
def test_a_chunk_reads_one_slots_matrices_not_the_layers(preset, monkeypatch):
    """The delta rule's hook slices layer and slot in one step
    (``_ChunkState._get_slot``), for every family that calls it: no value of
    the prefill program is a layer's matrices of every slot.  ``array[layer]``
    first (``_get``) computes one, a copy of the whole layer a chunk (PR 54
    measured it on the chip; on the CPU only the shape tells them apart)."""
    cfg = getattr(models, preset)()
    params = model_module.family_of(cfg).init_params(
        cfg, jax.random.PRNGKey(0))
    kv = make_grouped_cache(cfg, max_slots=3, block_size=4, max_context=64,
                            num_blocks={}, write_ahead=8)
    progs = model_module.make_programs(cfg, chunk=8, block_size=4,
                                       layers=kv.layers)
    tables = {name: jnp.asarray(g.block_tables[1])
              for name, g in kv.groups.items()}
    args = (params, kv.pools(), jnp.zeros((8,), jnp.int32), jnp.int32(8),
            tables, jnp.int32(4), jnp.int32(5))     # last real token, valid
    a_layer = kv.state.pools[-1].shape[1:]

    def computes_a_layer():
        # a function of its own a call: a trace is cached by its function
        jaxpr = jax.make_jaxpr(
            lambda *a: progs.prefill_chunk.__wrapped__(*a))(*args)
        return a_layer in set(_shapes(jaxpr.jaxpr))

    assert not computes_a_layer()
    monkeypatch.setattr(model_module._ChunkState, "_get_slot",
                        model_module._ChunkState._get)
    assert computes_a_layer()


def test_published_shapes_take_the_kernels():
    """What ``Programs.formulations`` will say on the chip: no ``"plain"``
    decode attention at a head of 256, the chunk kernel, the chunked scan and
    the in-place step."""
    from distributedtensorflow_tpu.ops import attention

    cfg = models.qwen3_next_ep4()
    rows = cfg.cache_rows
    assert rows.decode_formulation(16, "pallas") == "paged_attn"
    assert rows.chunk_formulation(16, 2048, "pallas") == "kv_chunk_attn"
    assert cfg.state_rows.chunk_formulation(2048, "pallas") == "chunked"
    assert cfg.state_rows.step_formulation("pallas") == "kda_step"
    # 8 query heads a K/V head of 256 at a chunk of 2,048: 4 a grid step
    assert attention._kv_chunk_heads(8, 2048, 256, 256, 2) == 4


# (8) attention at a head of 256; what the other families take is what they took

@pytest.mark.parametrize("sink", [False, True])
def test_paged_attn_takes_a_head_of_256(sink):
    """``paged_attn``, interpreted, at the served head shape (16 query heads
    on 2 K/V heads of 256: two lane tiles a head, 8 query rows a K/V head)
    against the plain formulation; lengths inside the first stretch, across
    stretches and across grid steps; with a sink, a head's bias in both of
    its tiles."""
    from distributedtensorflow_tpu.ops import attention

    assert attention.paged_decode_formulation(16, 2, 256, 16, "pallas") \
        == "paged_attn"
    assert attention.paged_decode_formulation(16, 2, 384, 16, "pallas") \
        == "plain"
    rng = np.random.default_rng(256)
    b, h, kv, d, bs, nb = 3, 16, 2, 256, 16, 40
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (2, (b * nb + 1) * bs, kv * d)), jnp.float32) for _ in range(2))
    tables = jnp.asarray(rng.permutation(b * nb).reshape(b, nb), jnp.int32)
    lens = jnp.asarray([1, 197, 640], jnp.int32)
    kw = dict(layer=1, block_size=bs,
              sink=jnp.asarray(rng.standard_normal(h) + 2.0, jnp.float32)
              if sink else None)
    got = attention.paged_window_decode_attention(
        q, k_pool, v_pool, tables, lens, impl="pallas", **kw)
    want = attention.paged_decode_attention(
        q, k_pool, v_pool, tables, lens, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("sink", [False, True])
def test_paged_attn_walks_several_trips_at_a_head_of_256(sink,
                                                         check_paged_walk):
    """The walk past its first stretch at the served head shape (two lane
    tiles a head in each of the kernel's two buffers): slots of over three
    trips, of one row and of nothing beside a table far wider than they
    use (the older case above stays inside two trips)."""
    check_paged_walk(lens=[3 * 512 + 70, 1, 0, 2 * 512, 640], cols=264,
                     heads=16, kv_heads=2, d=256, sink=sink, slots=5, nb=256)


@pytest.mark.parametrize("start", [0, 300, 608])
def test_chunk_kernel_takes_a_head_of_256(start, check_kv_chunk_kernel):
    check_kv_chunk_kernel(heads=16, kv_heads=2, d=256, dv=256, window=None,
                          sink=False, start=start)


#: ``{preset: (block size, prefill chunk, Programs.formulations, chunk_scan,
#: the state's step)}`` under the kernels' ``impl``, at each serving cell's
#: argv: read off the parent commit (b2f20e3) before ``ops/attention.py``,
#: ``ops/kda.py`` and ``parallel/moe.py`` were touched
_KV = {"chunk": "kv_chunk_attn", "decode": "paged_attn"}
PARENT_FORMULATIONS = {
    "evabyte_6_5b": (16, 2048, {"full": _KV, "window": _KV}, None, None),
    "glm5_ep16": (16, 1024, {"full": {
        "chunk": "masked_latent_chunk_attn+latent_chunk_attn",
        "decode": "sparse_latent_attn"}}, None, None),
    "gpt_medium": (16, 16, {"full": {"chunk": "plain",
                                     "decode": "paged_attn"}}, None, None),
    "jamba2_3b": (16, 1024, {"full": _KV}, "ssm_chunk_scan", None),
    "joyai_llm_flash": (16, 1024, {"full": {
        "chunk": "latent_chunk_attn", "decode": "paged_latent_attn"}}, None,
        None),
    "lfm2_24b_a2b": (16, 2048, {"full": {"chunk": "plain",
                                         "decode": "paged_attn"}}, None,
                     None),
    "ling3_flash_ep8": (16, 2048, {"full": {
        "chunk": "latent_chunk_attn", "decode": "paged_latent_attn"}},
        "chunked", "kda_step"),
    "mimo_v25_ep16": (16, 1024, {"full": _KV, "window": _KV}, None, None),
    "nemotron3_super_ep4": (16, 2048, {"full": _KV}, "chunked", "ssd_step"),
    "trinity_large_ep8": (16, 512, {"full": _KV, "window": _KV}, None, None),
    # and this PR's own
    "qwen3_next_ep4": (16, 2048, {"full": _KV}, "chunked", "kda_step"),
}


@pytest.mark.parametrize("preset", sorted(PARENT_FORMULATIONS))
def test_every_family_takes_the_formulations_it_took(preset):
    from distributedtensorflow_tpu.serve.kv_cache import layer_groups

    bs, chunk, forms, scan, step = PARENT_FORMULATIONS[preset]
    cfg = getattr(models, preset)()
    names = {f.name for f in dataclasses.fields(cfg)}
    cfg = dataclasses.replace(cfg, **{
        n: "pallas" for n in ("kernel_impl", "attn_impl") if n in names})
    layers = layer_groups(cfg)
    progs = model_module.make_programs(cfg, chunk=chunk, block_size=bs,
                                       layers=layers)
    assert progs.formulations == forms
    assert progs.chunk_scan == scan
    if step is not None:
        assert cfg.state_rows.step_formulation("pallas") == step
