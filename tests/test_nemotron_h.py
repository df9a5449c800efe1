"""The nemotron_h family (Mamba-2 layers keeping a matrix state a head a slot,
latent expert layers of which a share is held, attention layers without
rotary; one part a layer) on the CPU at a tiny size, seeded weights, logits
compared: the serving path (chunked prefill that scans from the state the
slot's last chunk left, decode that steps every slot's state in place, K/V
rows in the full group, expert layers in no group) against
``benchmark/reference/nemotron_h.py``'s token-by-token recurrence from zeros
over the whole sequence and dense attention; the three forms of ``ops.ssd``
against each other; the cases a recurrence adds (padding, interleaving, slot
re-use); the shares of the 4 chips adding up to the uncut layer; the counts
against the parameter tree and the model's published name.

With float32 parameters the system and the reference do the same float32
arithmetic in another order: logits of size ~5 agree to 1e-4.
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
from distributedtensorflow_tpu.models import nemotron_h
from distributedtensorflow_tpu.ops import grouped_matmul, ssd
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
        "bench_" + parts[-1][:-3] + "_nemotron_h", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module("reference", "nemotron_h.py")
COUNTS = _bench_module("counts", "nemotron_h.py")


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


def _config_dict(cfg: nemotron_h.NemotronHConfig) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        hidden_size=cfg.hidden_size, hybrid_override_pattern=cfg.pattern,
        num_hidden_layers=cfg.num_layers,
        mamba_num_heads=cfg.mamba_num_heads,
        mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.n_groups,
        ssm_state_size=cfg.ssm_state_size, conv_kernel=cfg.conv_kernel,
        chunk_size=cfg.chunk_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        moe_intermediate_size=cfg.moe_intermediate_size,
        moe_latent_size=cfg.moe_latent_size,
        moe_shared_expert_intermediate_size=cfg.shared_intermediate_size,
        n_routed_experts=cfg.held[1], expert_first=cfg.held[0],
        n_routed_experts_published=cfg.num_experts,
        num_experts_per_tok=cfg.experts_per_token,
        norm_topk_prob=cfg.route_norm,
        routed_scaling_factor=cfg.route_scale,
        layer_norm_epsilon=cfg.norm_eps, vocab_size=cfg.vocab_size)


@pytest.fixture(scope="module")
def f32_model():
    cfg = nemotron_h.nemotron_h_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~5
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(54), std=0.2)
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


# (1) the three forms of ops.ssd

def _ssd_inputs(seed, t, heads=8, dim=4, groups=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (t, heads, dim))
    dt = jax.nn.softplus(jax.random.normal(k[1], (t, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=2.77))
    b = jax.random.normal(k[3], (t, groups, n))
    c = jax.random.normal(k[4], (t, groups, n))
    d = 1.0 + 0.1 * jax.random.normal(k[5], (heads,))
    state = jax.random.normal(k[6], (heads, dim, n))
    return (x, dt, a, b, c, d), state


@pytest.mark.parametrize("t,valid,chunk,carried", [
    (8, None, 8, False),     # one whole chunk from zeros
    (32, None, 8, True),     # four chunks from a carried state
    (24, 19, 8, True),       # a ragged last chunk: 3 real tokens of 8
    (24, 8, 8, True),        # two chunks of padding only
    (16, 1, 16, False),      # one real token
    (256, 200, 128, True),   # the published chunk of 128
])
def test_chunked_form_is_the_recurrence(t, valid, chunk, carried):
    xs, state = _ssd_inputs(t, t)
    if not carried:
        state = jnp.zeros_like(state)
    want_y, want_s = ssd.ssd_recurrent(*xs, state, valid)
    got_y, got_s = ssd.ssd_chunked(*xs, state, valid, chunk=chunk)
    real = slice(0, valid)
    np.testing.assert_allclose(got_y[real], want_y[real], atol=1e-5 * float(
        jnp.abs(want_y[real]).max()))
    np.testing.assert_allclose(got_s, want_s, atol=1e-5 * float(
        jnp.abs(want_s).max()))
    # the padding is the identity: the state of the real tokens alone
    if valid is not None:
        alone = ssd.ssd_recurrent(*(v[:valid] if v.shape[:1] == (t,) else v
                                    for v in xs), state)[1]
        np.testing.assert_allclose(got_s, alone, atol=1e-5 * float(
            jnp.abs(alone).max()))


def test_two_chunked_calls_are_one():
    """A state carried from one call into the next (two prefill chunks) is
    the state of one call over both."""
    xs, state = _ssd_inputs(3, 32)
    first = ssd.ssd_chunked(*(v[:16] if v.shape[:1] == (32,) else v
                              for v in xs), state, chunk=8)
    second = ssd.ssd_chunked(*(v[16:] if v.shape[:1] == (32,) else v
                               for v in xs), first[1], chunk=8)
    whole = ssd.ssd_chunked(*xs, state, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([first[0], second[0]]),
                               whole[0], atol=1e-5)
    np.testing.assert_allclose(second[1], whole[1], atol=1e-5)


def test_step_is_one_token_of_the_recurrence_and_leaves_the_idle_alone():
    """Every slot one token, in place in the layer's rows of the group's
    array: a slot with ``dt = 0`` keeps its state bit for bit, the other
    layer's rows are untouched."""
    (x, dt, a, b, c, d), state = _ssd_inputs(5, 3)
    pool = jnp.stack([jnp.stack([state + i + 10 * layer for i in range(3)])
                      for layer in range(2)])
    dt = dt.at[1].set(0.0)
    y, after = ssd.ssd_step(x, dt, a, b, c, d, pool, 1)
    for slot in (0, 2):
        want_y, want_s = ssd.ssd_recurrent(
            x[slot:slot + 1], dt[slot:slot + 1], a, b[slot:slot + 1],
            c[slot:slot + 1], d, pool[1, slot])
        np.testing.assert_allclose(y[slot], want_y[0], atol=1e-5)
        np.testing.assert_allclose(after[1, slot], want_s, atol=1e-5)
    assert np.array_equal(after[1, 1], pool[1, 1])
    assert np.array_equal(after[0], pool[0])


def test_step_kernel_is_the_plain_step():
    """The Pallas step (interpreted) against the plain form at a state of 128
    lanes: outputs and states agree, an idle slot's state and the other
    layer's rows stay bit for bit."""
    (x, dt, a, b, c, d), state = _ssd_inputs(6, 3, heads=8, dim=8, groups=2,
                                             n=128)
    pool = jnp.stack([jnp.stack([state + i + 10 * layer for i in range(3)])
                      for layer in range(2)])
    dt = dt.at[1].set(0.0)
    assert ssd.step_formulation(8, 8, 2, 128, "pallas") == "ssd_step"
    assert ssd.step_formulation(8, 8, 2, 16, "pallas") == "plain"
    want_y, want = ssd.ssd_step(x, dt, a, b, c, d, pool, 1, impl="xla")
    got_y, got = ssd.ssd_step(x, dt, a, b, c, d, pool, 1, impl="pallas",
                              interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.array_equal(got[1, 1], pool[1, 1])
    assert np.array_equal(got[0], pool[0])


def test_served_through_the_interpreted_step_kernel_matches_the_reference(
        monkeypatch):
    """A state of 128 lanes so that the step kernel takes its tiles: a
    prefill chunk through the chunked form, decode through ``ssd_step``
    interpreted, attention and the experts through the plain forms."""
    cfg = nemotron_h.nemotron_h_tiny(dtype=jnp.float32, ssm_state_size=128,
                                     pattern="ME*M", vocab_size=64)
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(3), std=0.2)
    monkeypatch.setattr(engine_module, "make_programs",
                        model_module.make_programs)
    monkeypatch.setattr(ssd, "use_kernel", lambda impl: True)
    prompt = _prompt(1, 140, cfg)
    eng, [(tokens, logits)] = _serve(cfg, params, [(prompt, 4)],
                                     prefill_chunk=128, max_slots=2)
    assert eng.programs.chunk_scan == "chunked"
    assert cfg.state_rows.step_formulation("auto") == "ssd_step"
    monkeypatch.undo()
    want = _reference_logits(cfg, params, prompt, tokens)
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_formulation_says_what_a_chunk_takes():
    assert ssd.chunk_scan_formulation(2048) == "chunked"
    assert ssd.chunk_scan_formulation(8) == "plain"
    rows = nemotron_h.nemotron_h_tiny().state_rows
    assert rows.names == ("conv_tail", "ssd_state")
    assert rows.chunk_formulation(256, "auto") == "chunked"


# (2) chunks, then decode through both groups, against the reference

@pytest.mark.parametrize("prompt_len,n_new,chunk", [
    (1, 3, 8),       # a prompt of one token: the tail mostly the zeros before
    (3, 6, 8),       # shorter than the convolution's reach
    (8, 9, 8),       # exactly one chunk: no padding at all
    (21, 12, 8),     # ends mid-chunk; decoding crosses K/V block edges
    (128, 4, 128),   # one scan chunk whole (the chunked form)
    (129, 5, 128),   # one token into a second prefill and scan chunk
    (300, 4, 256),   # a prefill chunk of two scan chunks, then 44 real tokens
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
    got = np.asarray(nemotron_h.forward(params, ids, cfg))
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
    each), every one through ``W_up``, with the shared expert counted once,
    sum to the uncut layer of the reference: nothing is lost or counted twice
    by holding a share."""
    cfg, _ = f32_model
    whole = dataclasses.replace(cfg, experts_held=None, expert_first=0)
    p = nemotron_h.init_params(whole, jax.random.PRNGKey(9), std=0.2)["h1"][
        "moe"]
    h = jax.random.normal(jax.random.PRNGKey(10), (24, cfg.hidden_size))
    config = {**_config_dict(whole)}
    uncut = REF.routed(p, h, config) + REF._relu2(p["shared"], h)
    total, pairs = nemotron_h.relu2(p["shared"], h), 0
    for first in range(0, cfg.num_experts, 4):
        share = dataclasses.replace(cfg, experts_held=4, expert_first=first)
        mine = {**p, "experts": jax.tree.map(lambda a: a[first:first + 4],
                                             p["experts"])}
        out, counters = nemotron_h._latent_moe(mine, h, share, None)
        total = total + out - nemotron_h.relu2(p["shared"], h)
        pairs += int(counters["pairs"])
        # and the reference's share is the same share
        np.testing.assert_allclose(
            out - nemotron_h.relu2(p["shared"], h),
            REF.routed(mine, h, _config_dict(share)), atol=2e-5)
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    assert pairs == 24 * cfg.experts_per_token


# (4) the counts

def _harness_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3-super-ep4-serve.json")) as f:
        return json.load(f)


def test_published_widths_4648m_parameters_21_3_mb_a_slot_1024_b_a_token():
    """``nemotron3_super_ep4`` by shapes alone (nothing is allocated): the
    parameter tree against ``counts/nemotron_h.py`` and the issue's
    arithmetic; the published keys give the model's name, 120B-A12B."""
    cfg = models.nemotron3_super_ep4()
    assert cfg.pattern == "MEMEMEM*EME"
    assert cfg.held == (0, 128) and cfg.num_experts == 512
    tree = jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    total = sum(int(np.prod(leaf.shape)) for _, leaf in leaves)
    norms = sum(int(np.prod(leaf.shape)) for path, leaf in leaves
                if "norm" in jax.tree_util.keystr(path)
                or "'ln" in jax.tree_util.keystr(path))
    config = _harness_config()
    counted = COUNTS.params(config)
    assert total - norms == counted == config["parameters"]
    assert 4.64e9 < counted < 4.66e9
    assert 120.6e9 < COUNTS.published_params(config) < 120.7e9
    assert 12.1e9 < COUNTS.published_active_params(config) < 12.3e9
    rows = cfg.state_rows
    assert 5 * rows.slot_bytes(cfg.dtype) == COUNTS.state_bytes_per_slot(
        config) == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert COUNTS.kv_bytes_per_token(config) \
        == config["cache_bytes_per_token"] == 1024


def test_configuration_file_says_what_the_preset_is():
    """The benchmark's file and ``nemotron3_super_ep4`` are one
    configuration, and the file keeps every published width."""
    config, cfg = _harness_config(), models.nemotron3_super_ep4()
    for key, value in _config_dict(cfg).items():
        assert config[key] == value, key
    assert config["max_position_embeddings"] == cfg.max_seq
    published = config["hybrid_override_pattern_published"]
    assert published.startswith(cfg.pattern) and len(published) == 88
    assert (published.count("M"), published.count("E"),
            published.count("*")) == (40, 40, 8)
    assert {"no_rotary", "router_input", "dt_clamp"} <= set(config["assumed"])


# (5) what the tolerance refuses

@pytest.mark.parametrize("variant", [
    "rotary", "no_decay", "no_d", "ungrouped_norm", "no_route_scale",
    "router_on_latent"])
def test_the_tolerance_refuses_another_mathematics(f32_model, variant,
                                                   monkeypatch):
    """``F32_TOL`` is tight enough: a program with rotary applied, without
    the decay, without ``D``, with an ungrouped gated norm, without the
    routing scale, or with the router on the latent misses the reference by
    more than 100 tolerances."""
    cfg, params = f32_model
    ids = _prompt(11, 40, cfg)
    if variant == "rotary":
        def rotary(q, k, positions):
            half = q.shape[-1] // 2
            freqs = 10000.0 ** (-jnp.arange(half) / half)
            ang = positions[:, None, None] * freqs

            def turn(x):
                x1, x2 = x[..., :half], x[..., half:]
                return jnp.concatenate(
                    [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)
            return turn(q), turn(k)
        monkeypatch.setattr(nemotron_h, "_positions", rotary)
    elif variant in ("no_decay", "no_d"):
        real = nemotron_h.ssd_recurrent

        def changed(x, dt, a, b, c, d, state, valid=None):
            if variant == "no_decay":
                a = 0.0 * a
            else:
                d = 0.0 * d
            return real(x, dt, a, b, c, d, state, valid)
        monkeypatch.setattr(nemotron_h, "ssd_recurrent", changed)
    elif variant == "ungrouped_norm":
        real_norm = nemotron_h.gated_group_norm
        monkeypatch.setattr(
            nemotron_h, "gated_group_norm",
            lambda y, z, scale, groups, eps: real_norm(y, z, scale, 1, eps))
    elif variant == "no_route_scale":
        cfg = dataclasses.replace(cfg, route_scale=1.0)
    else:
        real_moe = nemotron_h.dropless_moe

        def on_latent(h, router, *rest, experts_in, **kw):
            wide = jnp.pad(experts_in, ((0, 0), (
                0, h.shape[1] - experts_in.shape[1])))
            return real_moe(wide, router, *rest, experts_in=experts_in, **kw)
        monkeypatch.setattr(nemotron_h, "dropless_moe", on_latent)
    got = np.asarray(nemotron_h.forward(params, jnp.asarray([ids]), cfg))[0]
    want = np.asarray(REF.logits(
        params, jnp.asarray([ids]), _config_dict(f32_model[0])))[0]
    assert np.abs(got - want).max() > 100 * F32_TOL


# (6) the ungated grouped form

@pytest.mark.parametrize("tokens,top_k", [(6, 3), (40, 5)])
def test_ungated_grouped_kernel_is_the_loop(tokens, top_k):
    """``relu(x W_up)^2 W_down`` a row tile: the kernels interpreted against
    the XLA loop, through ``dropless_moe`` with the experts' input a latent
    of its own (the router reads the token)."""
    k = jax.random.split(jax.random.PRNGKey(tokens), 6)
    d, lat, m, e = 256, 128, 384, 16
    h = jax.random.normal(k[0], (tokens, d), jnp.bfloat16)
    u = jax.random.normal(k[1], (tokens, lat), jnp.bfloat16)
    router = jax.random.normal(k[2], (d, e)) * 0.1
    bias = jnp.zeros((e,))
    experts = {"w_up": jax.random.normal(k[3], (8, lat, m), jnp.bfloat16) * .1,
               "w_down": jax.random.normal(k[4], (8, m, lat), jnp.bfloat16)
               * .1}
    mask = jnp.arange(tokens) < tokens - 1
    kw = dict(held=(4, 8), top_k=top_k, route_scale=5.0, token_mask=mask,
              experts_in=u)
    want, counters = moe.dropless_moe(h, router, bias, experts, impl="xla",
                                      **kw)
    got, counters2 = moe.dropless_moe(h, router, bias, experts,
                                      impl="pallas", **kw)
    assert want.shape == (tokens, lat) and int(counters["pairs"]) > 0
    assert jax.tree.map(int, counters) == jax.tree.map(int, counters2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.05,
                               rtol=0.02)
    assert not np.asarray(got[-1], np.float32).any()    # the masked token


def test_swiglu_callers_trace_what_they_traced():
    """A caller that hands ``dropless_moe`` gated experts and no latent
    traces the program it traced before the ungated form existed: the same
    jaxpr as the gated loop called by hand."""
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    d, m, e = 64, 48, 8
    h = jax.random.normal(k[0], (10, d))
    router, bias = jax.random.normal(k[1], (d, e)), jnp.zeros((e,))
    experts = {"w_gate": jax.random.normal(k[2], (4, d, m)),
               "w_up": jax.random.normal(k[3], (4, d, m)),
               "w_down": jax.random.normal(k[4], (4, m, d))}

    def by_hand(h, router, bias, experts):
        tile = moe.group_tile(10, 2, e)
        idx, w = moe.sigmoid_topk_route(h, router, bias, top_k=2)
        plan = moe.group_plan(idx, (2, 4), None, tile)
        x_rows = jnp.concatenate([h, jnp.zeros((1, d), h.dtype)])[plan["src"]]
        y_rows = moe._grouped_ffn_xla(
            x_rows, (experts["w_gate"], experts["w_up"], experts["w_down"]),
            plan["tile_expert"], plan["tiles_used"], tile)
        y_rows = jnp.concatenate([y_rows, jnp.zeros((1, d), y_rows.dtype)])
        picked = y_rows[plan["dest"]].astype(jnp.float32)
        return (picked * w[..., None]).sum(1).astype(h.dtype)

    got = moe.dropless_moe(h, router, bias, experts, held=(2, 4), top_k=2,
                           impl="xla")[0]
    assert np.array_equal(got, by_hand(h, router, bias, experts))
    assert grouped_matmul.grouped_swiglu.__name__ == "grouped_swiglu"


def test_row_buffer_holds_22_choices_of_128_experts():
    """The published shapes: a decode batch of 128 keeps the small tile, a
    chunk of 2,048 takes the wide one, and the row buffer holds every pair
    plus a tile less one of padding an expert at either."""
    for tokens, tile in ((128, moe.GROUP_TILE), (2048, moe.GROUP_TILE_WIDE)):
        assert moe.group_tile(tokens, 22, 512) == tile
        idx = jnp.zeros((tokens, 22), jnp.int32) + jnp.arange(22)
        plan = jax.eval_shape(
            lambda idx: moe.group_plan(idx, (0, 128), None, tile)["src"], idx)
        assert plan.shape[0] >= tokens * 22 + 128 * (tile - 1)


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
    cfg = nemotron_h.nemotron_h_tiny()
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(7), std=0.2)
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
             else "for the nemotron_h family")
    with pytest.raises(ValueError,
                       match=f"{want} is not implemented {where} yet .*{why}"):
        _engine(cfg, params, **kw)


def test_speculation_is_refused_for_what_a_state_cannot_do(f32_model):
    cfg, _ = f32_model
    _, progs = _programs(cfg)
    with pytest.raises(ValueError, match="speculate is not implemented for "
                       "the nemotron_h family yet .a rejected draft cannot "
                       "be rolled back out of a state"):
        progs.fused(2)


def test_groups_census_and_step_log(f32_model):
    """An expert layer is in no cache group; the engine says what the state
    group keeps and logs the family's counters."""
    cfg, params = f32_model
    eng, _ = _serve(cfg, params, [(_prompt(1, 20, cfg), 6),
                                  (_prompt(2, 9, cfg), 6)])
    assert eng.kv.layers == {"full": (3,), "state": (0, 2, 5)}
    state = eng.state()
    assert state["state_form"] == "conv_tail+ssd_state"
    assert state["chunk_scan"] == "plain"       # a chunk of 8
    assert state["decode_attention"] == "plain"
    rows = cfg.state_rows
    assert eng.kv.stats()["state"]["slot_bytes"] == 3 * rows.slot_bytes(
        jnp.float32)
    decoded = [r for r in eng.step_records() if r["occupancy"]]
    assert decoded
    for r in decoded:
        occ = r["occupancy"]
        assert 0 <= r["moe_pairs"] <= occ * cfg.experts_per_token * 2
        assert r["moe_experts_hit"] <= 2 * cfg.held[1]
    assert max(r["state_slots_used"] for r in decoded) == 2
    chunks = [r for r in eng.step_records() if r["prefill_chunks"]]
    assert sum(r["scan_tokens"] for r in chunks) >= 29
