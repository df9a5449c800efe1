"""The ouro family (a looped stack: the layers run ``total_ut_steps`` times
over shared weights, a K/V layer slot a pass a layer, sandwich norms, an exit
gate after every pass) on the CPU at a tiny size, seeded weights, logits
compared: the serving path (chunked prefill and paged decode, the passes
under ONE device loop whose pool layer is ``u * L + l``, traced) against
``benchmark/reference/ouro.py``'s plain float32 forward over the whole
sequence (a Python loop over passes and layers, no cache); four faulty
programs that the comparison must see; the cache's census; the shape of the
decode program.

With float32 parameters the system and the reference do the same float32
arithmetic in another order (a running softmax over key chunks): logits of
size ~2 agree to 1e-4.
"""

import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu import models
from distributedtensorflow_tpu.models import ouro
from distributedtensorflow_tpu.serve import model as model_module
from distributedtensorflow_tpu.serve import pool_check
from distributedtensorflow_tpu.serve.engine import Engine
from distributedtensorflow_tpu.serve.kv_cache import (layer_groups,
                                                     make_grouped_cache)
from distributedtensorflow_tpu.serve.model import Programs, make_programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-4
CHUNK, BLOCK = 8, 4


def _bench_module(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3] + "_ouro", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module("reference", "ouro.py")
COUNTS = _bench_module("counts", "ouro.py")


def _config_dict(cfg: ouro.OuroConfig) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers, vocab_size=cfg.vocab_size,
        total_ut_steps=cfg.total_ut_steps, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps)


PUBLISHED = _config_dict(models.ouro_2_6b())


@pytest.fixture(scope="module")
def f32_model():
    cfg = ouro.ouro_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~2
    params = ouro.init_params(cfg, jax.random.PRNGKey(61), std=0.2)
    return cfg, params


def _prompt(seed, n, cfg):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n).tolist()


def _reference_logits(cfg, params, prompt, tokens):
    ids = jnp.asarray([list(prompt) + list(tokens)])
    full = REF.logits(params, ids, _config_dict(cfg))[0]
    return np.asarray(full)[len(prompt) - 1:-1]


# (a) the programs themselves: three chunks, then decode, two slots interleaved

def _through_the_programs(progs, cfg, params, prompts, n_new):
    """Each prompt in its own slot through ``progs``: the chunks of the
    slots interleaved, then ``n_new - 1`` decode steps of all of them
    together.  Per slot ``(tokens, logits of every served position)``; and
    the decode steps' fourth outputs."""
    kv = make_grouped_cache(cfg, max_slots=len(prompts) + 1, block_size=BLOCK,
                            max_context=64, num_blocks={}, write_ahead=CHUNK)
    for slot, prompt in enumerate(prompts):
        assert kv.admit(slot, len(prompt) + n_new) is not None
    tables = {name: jnp.asarray(g.block_tables)
              for name, g in kv.paged.items()}
    pools = kv.pools()
    logits = [[] for _ in prompts]
    for start in range(0, max(map(len, prompts)), CHUNK):
        for slot, prompt in enumerate(prompts):
            real = min(len(prompt) - start, CHUNK)
            if real <= 0:
                continue
            chunk = np.zeros(CHUNK, np.int32)
            chunk[:real] = prompt[start:start + real]
            last, pools = progs.prefill(
                params, pools, chunk, start,
                {name: t[slot] for name, t in tables.items()}, real)
            if start + real == len(prompt):
                logits[slot].append(np.asarray(last))
    tokens = [[int(np.argmax(rows[0]))] for rows in logits]
    active = jnp.asarray([True] * len(prompts) + [False])
    lens = np.array([len(p) for p in prompts] + [0], np.int32)
    fourth = []
    for _ in range(n_new - 1):
        feed = jnp.asarray([t[-1] for t in tokens] + [0], jnp.int32)
        out, greedy, pools, stat = progs.decode(
            params, pools, feed, tables, jnp.asarray(lens), active)
        fourth.append(np.asarray(stat))
        lens[:len(prompts)] += 1
        for slot in range(len(prompts)):
            logits[slot].append(np.asarray(out[slot]))
            tokens[slot].append(int(greedy[slot]))
    return [(t, np.stack(rows)) for t, rows in zip(tokens, logits)], fourth


def _programs(family, cfg):
    return Programs(family, cfg, chunk=CHUNK, block_size=BLOCK,
                    layers=layer_groups(cfg))


#: 2 x 8 + 1: three prefill chunks, the third of one real token and padding
PROMPTS = (17, 17)


def _worst(cfg, params, served, prompts):
    return max(float(np.abs(logits - _reference_logits(
        cfg, params, prompt, tokens)).max())
        for prompt, (tokens, logits) in zip(prompts, served))


def test_three_chunks_then_decode_is_the_reference(f32_model):
    cfg, params = f32_model
    prompts = [_prompt(i, n, cfg) for i, n in enumerate(PROMPTS)]
    served, _ = _through_the_programs(
        _programs(ouro, cfg), cfg, params, prompts, 12)
    assert [len(t) for t, _ in served] == [12, 12]
    assert _worst(cfg, params, served, prompts) <= F32_TOL


_REAL_RMS_NORM = ouro.rms_norm


def _without_output_norms(p, x, cfg, layer, positions, attend,
                          token_mask=None):
    """``ouro.block`` with ``N2`` and ``N4`` the identity: a pre-norm block,
    as every other dense family's."""
    bare = {**p, "ln_attn_out": None, "ln_mlp_out": None}
    ouro.rms_norm = lambda x, scale, eps: (
        x if scale is None else _REAL_RMS_NORM(x, scale, eps))
    try:
        return ouro.block(bare, x, cfg, layer, positions, attend, token_mask)
    finally:
        ouro.rms_norm = _REAL_RMS_NORM


def _faulty(name, cfg, monkeypatch):
    """``(programs, the config they serve)`` of a server that is wrong in
    one way."""
    if name == "three passes instead of four":
        cfg = ouro.ouro_tiny(dtype=jnp.float32, total_ut_steps=3)
        return _programs(ouro, cfg), cfg
    if name == "one cache slot a layer shared by all passes":
        real = model_module._slots_a_pass
        monkeypatch.setattr(
            model_module, "_slots_a_pass",
            lambda cfg, layers: dict.fromkeys(real(cfg, layers), 0))
        return _programs(ouro, cfg), cfg
    family = types.SimpleNamespace(**{
        k: getattr(ouro, k) for k in ("embed", "block", "end_pass", "head")})
    family.__name__ = "faulty.ouro"
    if name == "the final norm once at the end":
        def end_pass(params, x, cfg):
            return x, ouro.end_pass(params, x, cfg)[1]

        def head(params, x, cfg):
            return ouro.head(params, ouro.end_pass(params, x, cfg)[0], cfg)
        family.end_pass, family.head = end_pass, head
    elif name == "a block without its two output norms":
        family.block = _without_output_norms
    return _programs(family, cfg), cfg


@pytest.mark.parametrize("fault", [
    "three passes instead of four",
    "one cache slot a layer shared by all passes",
    "the final norm once at the end",
    "a block without its two output norms",
])
def test_a_faulty_program_is_far_from_the_reference(f32_model, fault,
                                                    monkeypatch):
    cfg, params = f32_model
    progs, served_cfg = _faulty(fault, cfg, monkeypatch)
    prompts = [_prompt(i, n, cfg) for i, n in enumerate(PROMPTS)]
    served, _ = _through_the_programs(progs, served_cfg, params, prompts, 12)
    assert _worst(cfg, params, served, prompts) > 100 * F32_TOL


def test_the_shared_slot_fault_is_invisible_to_prefill_alone(f32_model,
                                                             monkeypatch):
    """Why the check decodes: a chunk's own rows are written before they are
    read, so one chunk from an empty context is right whatever slot it
    uses; only a *later* program sees a pass's rows overwritten."""
    cfg, params = f32_model
    progs, _ = _faulty("one cache slot a layer shared by all passes", cfg,
                       monkeypatch)
    prompts = [_prompt(3, 8, cfg)]
    served, _ = _through_the_programs(progs, cfg, params, prompts, 1)
    assert _worst(cfg, params, served, prompts) <= F32_TOL


# (b) through the engine

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


def _serve(cfg, params, jobs, **engine_kw):
    kw = dict(max_slots=3, block_size=BLOCK, prefill_chunk=CHUNK,
              max_context=128)
    eng = Engine(params, cfg, **{**kw, **engine_kw})
    seen = _record_logits(eng)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in jobs]
    for _ in range(4000):
        if all(r._done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.status == "ok" for r in reqs)
    for r in reqs:      # greedy: each token the arg-max of its row
        assert r.tokens == [int(np.argmax(row)) for row in seen[r.id]]
    return eng, [(r.tokens, np.stack(seen[r.id])) for r in reqs]


@pytest.mark.parametrize("prompt_len,n_new", [
    (1, 3),      # a prompt of one token
    (8, 9),      # exactly one chunk: no padding at all
    (9, 25),     # a second chunk of one real token and seven of padding
    (16, 16),    # ends on a chunk boundary
    (21, 12),    # ends mid-chunk; decoding crosses block edges
    (57, 20),    # eight chunks, the last of one token
])
def test_served_logits_match_the_reference(f32_model, prompt_len, n_new):
    cfg, params = f32_model
    prompt = _prompt(prompt_len, prompt_len, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, n_new)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert len(tokens) == n_new
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_interleaved_requests_are_each_served_alone(f32_model):
    cfg, params = f32_model
    jobs = [(_prompt(7, 19, cfg), 9), (_prompt(8, 5, cfg), 14),
            (_prompt(9, 33, cfg), 6)]
    _, served = _serve(cfg, params, jobs, prefill_budget=CHUNK)
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_a_pool_that_bounds_the_batch_serves_every_request(f32_model):
    """The cell's regime: fewer blocks than the slots' requests reserve, so
    the head of the queue waits for a release, not for a slot."""
    cfg, params = f32_model
    jobs = [(_prompt(20 + i, 9, cfg), 7) for i in range(4)]
    eng, served = _serve(cfg, params, jobs, max_slots=4, num_blocks=8,
                         max_context=32)
    assert max(r["active_slots"] for r in eng.step_records()) <= 2
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_whole_forward_is_the_reference(f32_model):
    cfg, params = f32_model
    ids = jnp.asarray([_prompt(5, 37, cfg), _prompt(6, 37, cfg)])
    got, p = ouro.forward(params, ids, cfg, with_exit=True)
    want = np.asarray(REF.logits(params, ids, _config_dict(cfg)))
    np.testing.assert_allclose(np.asarray(got), want, atol=F32_TOL, rtol=0)
    want_p = np.asarray(REF.exit_distribution(params, ids, _config_dict(cfg)))
    np.testing.assert_allclose(np.asarray(p), want_p, atol=1e-5, rtol=0)
    np.testing.assert_allclose(want_p.sum(1), 1.0, atol=1e-6)


# (c) the exit gate: computed, returned, never choosing

def test_exit_mass_is_returned_and_sums_to_one(f32_model):
    cfg, params = f32_model
    prompts = [_prompt(i, n, cfg) for i, n in enumerate(PROMPTS)]
    served, fourth = _through_the_programs(
        _programs(ouro, cfg), cfg, params, prompts, 6)
    ids = [jnp.asarray([list(p) + t]) for p, (t, _) in zip(prompts, served)]
    want = [np.asarray(REF.exit_distribution(params, i, _config_dict(cfg)))[0]
            for i in ids]
    for step, mass in enumerate(fourth):
        assert mass.shape == (4,) and mass.dtype == np.float32
        np.testing.assert_allclose(mass.sum(), 1.0, atol=1e-6)
        at = PROMPTS[0] + step        # the position both slots decode at
        np.testing.assert_allclose(
            mass, np.mean([w[:, at] for w in want], axis=0), atol=1e-5)
    # all four passes carry mass under the seeded gate: a gate that always
    # left at once, or never, would make the counter worthless
    assert min(m.min() for m in fourth) > 0.01


def test_step_log_and_state_carry_the_family_counters(f32_model):
    cfg, params = f32_model
    eng, _ = _serve(cfg, params, [(list(range(21)), 12)])
    state = eng.state()
    assert state["cache_layer_slots"] == 12
    assert state["cache_row_bytes"] == 12 * 2 * 2 * 32 * 4
    assert state["kv_groups"]["full"]["layers"] == 12
    decoded = [r for r in eng.step_records() if r["occupancy"]]
    assert decoded and all(r["ut_steps"] == 4 for r in decoded)
    for r in decoded:
        mass = [r[f"ut_exit_mass_{u}"] for u in range(4)]
        assert abs(sum(mass) - 1.0) < 1e-4 and "moe_pairs" not in r
    # a family that runs its stack once reports none of them
    gpt_cfg = models.gpt_tiny()
    assert make_programs(gpt_cfg, chunk=8, block_size=8,
                         layers=layer_groups(gpt_cfg)).passes == 1


# (d) what is kept: the census

def test_published_widths_2667974657_parameters_1572864_b_a_token():
    cfg = models.ouro_2_6b()
    shapes = jax.eval_shape(
        lambda: ouro.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 2_667_974_657 == COUNTS.params_exact(PUBLISHED)
    a_layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert a_layer == 51_388_416
    assert 48 * a_layer + 2 * 49152 * 2048 + 2048 + 2049 == 2_667_974_657
    # "2.6B": the name's figure, to the first decimal
    assert 2.6e9 <= COUNTS.params_exact(PUBLISHED) < 2.7e9
    layers = layer_groups(cfg)
    assert list(layers) == ["full"] and len(layers["full"]) == 192
    assert layers["full"][:48] == layers["full"][48:96] == tuple(range(48))
    kv = make_grouped_cache(cfg, max_slots=2, block_size=16, max_context=32,
                            num_blocks={"full": 2}, write_ahead=16)
    assert kv.layer_slots == 192
    assert kv.row_bytes == 192 * 2 * 16 * 128 * 2 == 1_572_864 \
        == COUNTS.kv_bytes_per_token(PUBLISHED)
    assert [a.shape for a in kv.pools()["full"]] == [(192, 3 * 16, 2048)] * 2
    census = kv.stats()["groups"]["full"]
    assert census["form"] == "KVRows" and census["kv_heads"] == 16
    assert census["row_bytes"] == 2 * 16 * 128 * 2


def test_admission_reserves_all_192_slots_rows_by_the_block(f32_model):
    """Blocks are shared by every layer slot: a request's reservation is its
    tokens in blocks, whatever the passes; what the passes change is the
    bytes a block holds."""
    cfg, _ = f32_model
    kv = make_grouped_cache(cfg, max_slots=4, block_size=4, max_context=32,
                            num_blocks={"full": 10}, write_ahead=8)
    assert kv.admit(0, 17) is not None          # 5 blocks
    assert kv.admit(1, 17) is not None          # 5 more: the pool is full
    assert kv.admit(2, 4) is None               # a slot is free, no block is
    kv.release(0)
    assert kv.admit(2, 4) is not None
    with pytest.raises(ValueError, match="needs 11 KV blocks"):
        kv.check_fits(41)


def test_counts_say_the_weights_are_streamed_once_a_pass():
    c = {**PUBLISHED, "max_slots": 16}
    layers = 48 * (4 * 2048 ** 2 + 3 * 2048 * 5632)
    assert COUNTS.decode_iter_bytes(c, 0, 2, slots=1) \
        == (4 * layers + 49152 * 2048) * 2 + 1_572_864
    nine = COUNTS.decode_kernel(c, "decode_iter", [410] * 9)["bytes"]
    assert nine == (4 * layers + 49152 * 2048) * 2 + (9 * 410 + 9) * 1_572_864
    attn = COUNTS.decode_kernel(c, "paged_attn", [410] * 9)
    assert attn["bytes"] == 9 * 410 * 1_572_864 + 192 * 9 * 2 * 2048 * 2
    assert attn["flops"] == 192 * 9 * 410 * 16 * 4 * 128
    chunk = COUNTS.decode_kernel(c, "kv_chunk_attn", [], {
        "prefill_chunks": 2, "chunk_tokens": 300, "chunk_pairs": 2 * 30_000})
    assert chunk["flops"] == 192 * 30_000 * 16 * 4 * 128
    assert abs(COUNTS.flops_per_token(c) - 19.9e9) < 0.1e9


# (e) the shape of the programs: one loop, traced once

def _walk(jaxpr, loops=()):
    """``(equation, the lengths of the scans around it)`` of every equation
    of ``jaxpr``, sub-jaxprs walked."""
    for eqn in jaxpr.eqns:
        yield eqn, loops
        inside = loops
        if eqn.primitive.name == "scan":
            inside = loops + (eqn.params["length"],)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, inside)


def _call_sites(jaxpr, name):
    """The scans around every call of the jitted function ``name``."""
    return [loops for eqn, loops in _walk(jaxpr)
            if eqn.primitive.name in ("jit", "pjit")
            and eqn.params["name"] == name]


def _scans(jaxpr):
    return [eqn.params["length"] for eqn, _ in _walk(jaxpr)
            if eqn.primitive.name == "scan"]


@pytest.fixture(scope="module")
def deep_programs():
    """48 layers x 4 passes at widths the kernels take (one head of 128),
    the kernels asked for by name: traced, never run."""
    cfg = ouro.ouro_tiny(hidden_size=128, num_heads=1, num_kv_heads=1,
                         head_dim=128, intermediate_size=128, num_layers=48,
                         kernel_impl="pallas")
    kv = make_grouped_cache(cfg, max_slots=2, block_size=16, max_context=256,
                            num_blocks={"full": 4}, write_ahead=128)
    progs = make_programs(cfg, chunk=128, block_size=16, layers=kv.layers)
    params = jax.eval_shape(
        lambda: ouro.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, kv, progs, params


def test_decode_holds_one_loop_of_four_and_48_attention_call_sites(
        deep_programs):
    cfg, kv, progs, params = deep_programs
    assert progs.formulations == {
        "full": {"decode": "paged_attn", "chunk": "kv_chunk_attn"}}
    tables = {name: jnp.asarray(g.block_tables)
              for name, g in kv.paged.items()}
    jaxpr = jax.make_jaxpr(lambda *a: progs.decode.__wrapped__(*a))(
        params, kv.pools(), jnp.zeros((2,), jnp.int32), tables,
        jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool)).jaxpr
    sites = _call_sites(jaxpr, "_paged_attn_call")
    assert len(sites) == 48 and set(sites) == {(4,)}      # not 192
    assert _scans(jaxpr).count(4) == 1


def test_prefill_holds_one_loop_of_four_and_48_attention_call_sites(
        deep_programs):
    cfg, kv, progs, params = deep_programs
    tables = {name: jnp.asarray(g.block_tables[0])
              for name, g in kv.paged.items()}
    jaxpr = jax.make_jaxpr(lambda *a: progs.prefill_chunk.__wrapped__(*a))(
        params, kv.pools(), jnp.zeros((128,), jnp.int32), jnp.int32(128),
        tables, jnp.int32(3)).jaxpr
    sites = _call_sites(jaxpr, "_kv_chunk_call")
    assert len(sites) == 48 and set(sites) == {(4,)}
    assert _scans(jaxpr).count(4) == 1


def test_a_stack_run_once_keeps_the_program_it_had():
    """``_through_passes`` hands a config without ``stack_passes`` a Python
    0: no loop, no pass arithmetic, the layer a Python int in the jaxpr."""
    cfg = models.gpt_tiny()
    kv = make_grouped_cache(cfg, max_slots=2, block_size=16, max_context=64,
                            num_blocks={}, write_ahead=16)
    progs = make_programs(cfg, chunk=16, block_size=16, layers=kv.layers)
    params = jax.eval_shape(lambda: model_module.family_of(cfg).init_params(
        cfg, jax.random.PRNGKey(0)))
    tables = {name: jnp.asarray(g.block_tables)
              for name, g in kv.paged.items()}
    jaxpr = jax.make_jaxpr(lambda *a: progs.decode.__wrapped__(*a))(
        params, kv.pools(), jnp.zeros((2,), jnp.int32), tables,
        jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool))
    assert _scans(jaxpr.jaxpr) == [] and "ut_loop" not in str(jaxpr)


# (f) what the family refuses, and the pool through the loop

@pytest.mark.parametrize("flag,kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("fused_sampling", {"fused_sampling": True}),
    ("fused_sampling", {"fused_sampling": True, "speculate": 2}),
])
def test_family_refuses_what_no_test_holds_yet(f32_model, flag, kw):
    cfg, params = f32_model
    with pytest.raises(ValueError, match=f"{flag} is not implemented for "
                                         "the ouro family yet"):
        Engine(params, cfg, max_slots=2, block_size=BLOCK,
               prefill_chunk=CHUNK, max_context=128, **kw)


def test_a_loop_over_a_state_group_is_refused_with_the_reason():
    cfg = types.SimpleNamespace(stack_passes=2, cache_rows=None)
    with pytest.raises(ValueError, match="stack run several times over a "
                                         "state group"):
        model_module._slots_a_pass(cfg, {"full": (0, 0), "state": (1, 1)})


def test_bfloat16_preset_serves_finite_logits_near_the_reference():
    cfg = ouro.ouro_tiny()
    params = ouro.init_params(cfg, jax.random.PRNGKey(3), std=0.2)
    prompt = _prompt(11, 21, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, 8)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.isfinite(logits).all()
    # bf16 through 12 sandwiched blocks, logits of size ~2
    assert np.abs(logits - want).mean() < 0.2
    assert np.abs(logits - want).max() < 1.0


def test_pool_check_finds_no_pool_sized_copy_through_the_loop(monkeypatch):
    """The pools are the device loop's carry: compiled for a described v5e
    (``tests/test_kernel_export_families.py`` has all three programs two
    layers deep), the decode program of one layer x four passes at the
    published widths copies no layer slot of the pools outside ``paged_attn``
    and hands both back in place."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P
    from kernel_export_cases import as_on_the_chip, v5e_mesh

    from distributedtensorflow_tpu.serve import kv_cache

    one_chip = NamedSharding(v5e_mesh(1), P())
    as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(models.ouro_2_6b(), num_layers=1,
                              vocab_size=1024)
    programs = pool_check.pool_programs(
        cfg, max_slots=16, num_blocks=2048, block_size=16, chunk=256, draft=4,
        sharding=one_chip)
    _, rows, width = kv_cache.pool_shape(4, 2048, 16, 2048)
    report = pool_check.check_pool_programs(
        {"decode": programs["decode"]}, layer_elems=rows * width)
    assert pool_check.failures(report) == []
    assert report["decode"]["donated"] == ["k_pool", "v_pool"]
