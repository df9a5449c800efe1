"""The jamba family (Mamba-1 layers keeping a state a slot, an attention
layer amid them, a dense SwiGLU in every layer) on the CPU at a tiny size,
seeded weights, logits compared: the serving path (chunked prefill that
scans from the state the slot's last chunk left, decode that steps every
slot's state) against ``benchmark/reference/jamba.py``'s one scan from zeros
over the whole sequence; the three cases a recurrence adds (padding,
interleaving, slot re-use); the three forms of the scan; the ``paged_attn``
kernel at 20 query heads on one K/V head.

With float32 parameters the system and the reference do the same float32
arithmetic in another order (a scan cut into chunks is the same scan; a
running softmax over key chunks): logits of size ~5 agree to 1e-4.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu import models
from distributedtensorflow_tpu.models import jamba
from distributedtensorflow_tpu.ops import attention, ssm
from distributedtensorflow_tpu.serve.engine import Engine
from distributedtensorflow_tpu.serve.kv_cache import make_grouped_cache
from distributedtensorflow_tpu.serve.model import make_programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-4


def _bench_module(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3] + "_jamba", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module("reference", "jamba.py")
COUNTS = _bench_module("counts", "jamba.py")


def _config_dict(cfg: jamba.JambaConfig) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers, vocab_size=cfg.vocab_size,
        attn_layer_period=cfg.attn_layer_period,
        attn_layer_offset=cfg.attn_layer_offset,
        mamba_expand=cfg.mamba_expand, mamba_d_state=cfg.mamba_d_state,
        mamba_dt_rank=cfg.mamba_dt_rank, mamba_d_conv=cfg.mamba_d_conv,
        num_experts=cfg.num_experts, rms_norm_eps=cfg.rms_norm_eps)


@pytest.fixture(scope="module")
def f32_model():
    cfg = jamba.jamba_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~5
    params = jamba.init_params(cfg, jax.random.PRNGKey(34), std=0.2)
    return cfg, params


def _record_logits(eng):
    """``{request id: [the logits of every served position]}``, filled as
    ``eng`` runs (``tests/test_joyai.py`` has the same spy)."""
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
    kw = dict(max_slots=3, block_size=4, prefill_chunk=8, max_context=128)
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


def _reference_logits(cfg, params, prompt, tokens):
    ids = jnp.asarray([list(prompt) + list(tokens)])
    full = REF.logits(params, ids, _config_dict(cfg))[0]
    return np.asarray(full)[len(prompt) - 1:-1]


def _prompt(seed, n, cfg):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n).tolist()


def _assert_served_is_reference(cfg, params, jobs, served):
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt, tokens)
        np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


# (a) chunks, then decode through the state, against the reference

@pytest.mark.parametrize("prompt_len,n_new", [
    (1, 3),      # a prompt of one token: the tail is mostly the zeros before
    (2, 4),      # shorter than the convolution's reach
    (3, 6),      # inside one chunk and one block
    (8, 9),      # exactly one chunk: no padding at all
    (9, 25),     # a second chunk of one real token and seven of padding
    (16, 16),    # ends on a chunk boundary
    (21, 12),    # ends mid-chunk; decoding crosses block edges
    (33, 5),     # a fifth chunk of one token
    (57, 20),    # eight chunks, the last of one token
])
def test_served_logits_match_the_reference(f32_model, prompt_len, n_new):
    cfg, params = f32_model
    prompt = _prompt(prompt_len, prompt_len, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, n_new)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert len(tokens) == n_new
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_whole_forward_is_the_reference(f32_model):
    cfg, params = f32_model
    ids = jnp.asarray([_prompt(5, 37, cfg), _prompt(6, 37, cfg)])
    got = np.asarray(jamba.forward(params, ids, cfg))
    want = np.asarray(REF.logits(params, ids, _config_dict(cfg)))
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


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


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])
def test_padding_is_the_identity(f32_model, n):
    """A chunk of ``n`` real tokens (the rest padding, of any value) leaves
    the state and the convolution tail that scanning ``n`` tokens leaves:
    the plain forms over exactly ``n`` tokens, also for ``n`` under the
    convolution's reach."""
    cfg, params = f32_model
    tokens = _prompt(n, n, cfg)
    kv, progs = _programs(cfg)
    padded = tokens + _prompt(99, 8 - n, cfg)      # the padding is not zeros
    tail, state = _chunk(progs, params, kv, 1, padded, 0, n)

    # the same layer inputs through the plain forms, n tokens and no more
    want_tail, want_state = [], []
    rows = cfg.state_rows

    class Exact:
        def conv(self, u, w, b):
            out, t = ssm.causal_conv(
                u, jnp.zeros(((rows.d_conv - 1) * rows.channels,)), w, b, n)
            want_tail.append(np.asarray(t))
            return out

        def scan(self, u, delta, a, b, c, d):
            y, s = ssm.selective_scan(
                u, delta, a, b, c, d,
                jnp.zeros((rows.d_state, rows.channels)))
            want_state.append(np.asarray(s))
            return y

    x = jamba.embed(params, jnp.asarray(tokens), cfg)
    for i in range(cfg.num_layers):
        def attend(q, k, v):
            return attention.xla_attention(q[None], k[None], v[None],
                                           causal=True)[0]
        mixer = Exact() if cfg.keeps_state(i) else attend
        x, _ = jamba.block(params[f"h{i}"], x, cfg, i, None, mixer)
    np.testing.assert_allclose(tail[:, 1], np.stack(want_tail), atol=2e-5)
    np.testing.assert_allclose(state[:, 1], np.stack(want_state), atol=2e-5)
    # and the other slots' state was not touched
    assert not tail[:, [0, 2]].any() and not state[:, [0, 2]].any()


def test_interleaved_requests_are_each_served_alone(f32_model):
    """Chunks of A between decode steps of B and chunks of C (a prefill
    budget of one chunk an iteration, so a long prompt's chunks interleave
    with the others' decoding): each request's logits are the reference's
    for that request alone."""
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
    """Bit for bit: a slot between two of its prefill chunks is inactive
    while the others decode."""
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
    find the state their predecessor left and must not see it."""
    cfg, params = f32_model
    jobs = [(_prompt(i, n, cfg), m)
            for i, (n, m) in enumerate([(30, 10), (3, 12), (17, 8)])]
    eng, served = _serve(cfg, params, jobs, max_slots=1)
    assert eng.counters["admits_into_freed_slot"] >= 2
    assert eng.kv.state.pools[1].any()      # the last occupant's state stays
    _assert_served_is_reference(cfg, params, jobs, served)


# (c) the three forms of the scan

def _scan_case(seed, t, c, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        u=jax.random.normal(ks[0], (t, c)).astype(dtype),
        delta=jax.nn.softplus(jax.random.normal(ks[1], (t, c)) - 3.0),
        a=-jnp.exp(jax.random.normal(ks[2], (n, c)) * 0.3 + 1.0),
        b=jax.random.normal(ks[3], (t, n)).astype(dtype),
        c=jax.random.normal(ks[4], (t, n)).astype(dtype),
        d=1.0 + 0.1 * jax.random.normal(ks[5], (c,)),
        state=jax.random.normal(ks[6], (n, c)))


@pytest.mark.parametrize("t,c,valid,dtype,tol,why", [
    (128, 256, 128, jnp.float32, 1e-5, "the same float32 sums"),
    (128, 640, 77, jnp.float32, 1e-5, "a last lane chunk of one tile; pad"),
    (64, 128, 1, jnp.float32, 1e-5, "one real token"),
    # the forms take u, B and C in bfloat16 and compute in float32 alike, so
    # they differ by float32 reordering only, scaled by the values' size
    (128, 256, 100, jnp.bfloat16, 1e-4, "bf16 inputs, float32 arithmetic"),
    (64, 5120, 50, jnp.float32, 1e-5, "the published channel shape"),
])
def test_the_three_scan_forms_agree(t, c, valid, dtype, tol, why):
    """The kernel (interpreted) against ``lax.scan`` and against ``valid``
    single steps: outputs of the real positions and the state out."""
    case = _scan_case(t + c, t, c, 16, dtype)
    args = [case[k] for k in ("u", "delta", "a", "b", "c", "d", "state")]
    y_scan, s_scan = ssm.selective_scan(*args, valid=valid)
    y_kern, s_kern = ssm.ssm_chunk_scan(*args, valid, impl="pallas")
    assert ssm.chunk_scan_formulation(c, 16, t, "pallas") == "ssm_chunk_scan"
    np.testing.assert_allclose(y_kern[:valid], y_scan[:valid], atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(s_kern, s_scan, atol=tol, rtol=tol)
    s, ys = case["state"][None], []
    for i in range(min(valid, 24)):
        y, s = ssm.ssm_step(case["u"][i:i + 1], case["delta"][i:i + 1],
                            case["a"], case["b"][i:i + 1],
                            case["c"][i:i + 1], case["d"], s)
        ys.append(y[0])
    np.testing.assert_allclose(np.stack(ys), y_scan[:len(ys)], atol=tol,
                               rtol=tol)
    if valid <= 24:
        np.testing.assert_allclose(s[0], s_scan, atol=tol, rtol=tol)


@pytest.mark.parametrize("channels,d_state,chunk,impl,want", [
    (5120, 16, 1024, "pallas", "ssm_chunk_scan"),
    (5120, 16, 1024, "xla", "plain"),
    (5120, 16, 1024, "auto", "plain"),          # here, off the TPU
    (5120, 16, 1000, "pallas", "plain"),        # not whole blocks of tokens
    (5100, 16, 1024, "pallas", "plain"),        # not whole lane tiles
    (128, 4, 64, "pallas", "plain"),            # not whole sublane tiles
])
def test_chunk_scan_formulation_says_what_is_taken(channels, d_state, chunk,
                                                   impl, want):
    assert ssm.chunk_scan_formulation(channels, d_state, chunk, impl) == want


@pytest.mark.parametrize("heads,chunk_attention", [
    (1, "plain"),             # 64 query rows a K/V head: the loop
    (2, "kv_chunk_attn"),     # two heads on the one K/V head: the kernel
])
def test_served_through_the_kernels_matches_the_reference(heads,
                                                          chunk_attention):
    """The whole path with the kernels interpreted: ``ssm_chunk_scan`` and
    (where a chunk has an MXU pass of query rows) ``kv_chunk_attn`` in
    prefill, ``paged_attn`` in decode (a head of 128 needs the width)."""
    cfg = jamba.jamba_tiny(dtype=jnp.float32, kernel_impl="pallas",
                           hidden_size=128 * heads, num_heads=heads,
                           head_dim=128, mamba_dt_rank=8)
    params = jamba.init_params(cfg, jax.random.PRNGKey(7), std=0.1)
    prompt = _prompt(70, 70, cfg)
    eng, [(tokens, logits)] = _serve(
        cfg, params, [(prompt, 4)], block_size=16, prefill_chunk=64,
        max_slots=2)
    state = eng.state()
    assert state["chunk_scan"] == "ssm_chunk_scan"
    assert state["chunk_attention"] == chunk_attention
    assert state["decode_attention"] == "paged_attn"
    want = _reference_logits(cfg, params, prompt, tokens)
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


# (d) paged attention at 20 query heads on one K/V head

def test_paged_attn_takes_20_heads_on_one_kv_head():
    assert attention.paged_decode_formulation(20, 1, 128, 16, "pallas") \
        == "paged_attn"
    assert attention.paged_decode_formulation(40, 1, 128, 16, "pallas") \
        == "plain"
    rng = np.random.default_rng(20)
    b, h, d, bs, nb = 3, 20, 128, 16, 12
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (2, (b * nb + 1) * bs, d)), jnp.float32) for _ in range(2))
    tables = jnp.asarray(rng.permutation(b * nb).reshape(b, nb), jnp.int32)
    lens = jnp.asarray([1, 97, 192], jnp.int32)
    kw = dict(layer=1, block_size=bs)
    got = attention.paged_window_decode_attention(
        q, k_pool, v_pool, tables, lens, impl="pallas", **kw)
    want = attention.paged_decode_attention(
        q, k_pool, v_pool, tables, lens, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("start", [0, 300, 608])
def test_chunk_kernel_takes_20_heads_on_one_kv_head(start,
                                                    check_kv_chunk_kernel):
    """``kv_chunk_attn``, interpreted, at the served head shape: the 20
    query heads in four grid steps of five, all reading the one K/V head."""
    assert attention.paged_chunk_formulation(
        20, 1, 128, None, 16, 1024, "pallas") == "kv_chunk_attn"
    form = models.jamba2_3b().cache_rows
    assert form.chunk_formulation(16, 1024, "pallas") == "kv_chunk_attn"
    assert form.chunk_formulation(16, 1024, "auto") == "plain"   # the CPU
    check_kv_chunk_kernel(heads=20, kv_heads=1, d=128, dv=128, window=None,
                          sink=False, start=start)


# (e) under load; what is kept, logged and refused

def test_every_slot_live_under_load(f32_model):
    """Every slot decoding at once with a queue behind the slots and a pool
    that admission waits on: each served logit still the reference's."""
    cfg, params = f32_model
    rng = np.random.default_rng(64)
    shapes = [(70, 30), (45, 50)] + [(int(rng.integers(3, 30)),
                                      int(rng.integers(20, 45)))
                                     for _ in range(10)]
    jobs = [(_prompt(i, n, cfg), m) for i, (n, m) in enumerate(shapes)]
    eng, served = _serve(cfg, params, jobs, max_slots=6, num_blocks=110)
    rows = [r for r in eng.step_records() if r["occupancy"]]
    assert max(r["occupancy"] for r in rows) == 6
    assert max(r["state_slots_used"] for r in rows) == 6
    assert eng.kv.stats()["blocks_free"] == 110
    assert eng.kv.stats()["state"]["slots_live"] == 0
    _assert_served_is_reference(cfg, params, jobs, served)


def test_bfloat16_preset_serves_finite_logits_near_the_reference():
    cfg = jamba.jamba_tiny()
    params = jamba.init_params(cfg, jax.random.PRNGKey(3), std=0.2)
    assert params["h0"]["mamba"]["w_in"].dtype == jnp.bfloat16
    assert params["h0"]["mamba"]["a_log"].dtype == jnp.float32
    prompt = list(range(1, 45))
    eng, [(tokens, logits)] = _serve(cfg, params, [(prompt, 24)])
    tail, state = eng.kv.state.pools
    assert tail.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.isfinite(logits).all()
    assert (logits.argmax(-1) == want.argmax(-1)).mean() >= 0.75
    assert np.median(np.abs(logits - want)) < 0.1


def test_published_widths_3029m_parameters_9_3_mb_a_slot_1024_b_a_token():
    """``jax.eval_shape``: nothing is allocated."""
    cfg = models.jamba2_3b()
    shapes = jax.eval_shape(
        lambda: jamba.init_params(cfg, jax.random.PRNGKey(0)))
    scales = ("ln_in", "ln_ff", "ln_f", "dt_norm", "b_norm", "c_norm")
    counted = sum(
        int(np.prod(x.shape))
        for path, x in jax.tree_util.tree_leaves_with_path(shapes)
        if path[-1].key not in scales)
    published = dict(
        _config_dict(cfg), max_slots=32, prefill_chunk=1024)
    assert counted == COUNTS.params(published) == 3_029_186_560
    assert round(counted / 1e6) == 3029
    assert [i for i in range(cfg.num_layers) if not cfg.keeps_state(i)] \
        == [7, 21]
    rows = cfg.state_rows
    assert (rows.channels, rows.d_state) == (5120, 16)
    per_slot = 26 * rows.slot_bytes(cfg.dtype)
    assert per_slot == COUNTS.state_bytes_per_slot(published) == 9_318_400
    kv = jax.eval_shape(lambda: make_grouped_cache(
        cfg, max_slots=2, block_size=16, max_context=64,
        num_blocks={"full": 8}, write_ahead=16).pools())
    assert [a.shape for a in kv["state"]] == [(26, 2, 15360),
                                              (26, 2, 16, 5120)]
    assert [a.shape for a in kv["full"]] == [(2, 9 * 16, 128)] * 2
    assert 2 * 2 * 128 * 2 == COUNTS.kv_bytes_per_token(published) == 1024


def test_groups_census_and_what_a_state_group_refuses(f32_model):
    cfg, _ = f32_model
    kv = make_grouped_cache(cfg, max_slots=2, block_size=4, max_context=32,
                            num_blocks={}, write_ahead=8)
    assert list(kv.groups) == ["full", "state"]
    assert kv.layers == {"full": (1,), "state": (0, 2, 3)}
    assert kv.state is kv.groups["state"] and list(kv.paged) == ["full"]
    assert kv.row_bytes == 2 * 16 * 4      # K and V of one head of 16, f32
    assert kv.state.slot_bytes == 3 * (3 * 128 + 16 * 128) * 4
    assert kv.admit(0, 12) is not None and kv.state.live.tolist() == [1, 0]
    assert kv.stats()["state"]["slots_live"] == 1
    for call in (lambda: kv.rollback(0, 4),
                 lambda: kv.register_prefix(0, [1, 2, 3, 4])):
        with pytest.raises(ValueError, match="not implemented over a state "
                                             "group"):
            call()
    kv.release(0)
    assert not kv.state.live.any()
    # the families without a state have no such group
    gpt = make_grouped_cache(models.gpt_tiny(), max_slots=2, block_size=16,
                             max_context=64, num_blocks={}, write_ahead=16)
    assert gpt.state is None and list(gpt.paged) == ["full"]


def test_step_log_carries_the_family_counters(f32_model):
    cfg, params = f32_model
    eng, [(tokens, _)] = _serve(cfg, params, [(list(range(21)), 12)])
    state = eng.state()
    assert state["decode_attention"] == "plain"
    assert state["chunk_attention"] == "plain"
    assert state["chunk_scan"] == "plain"
    assert state["cache_row_bytes"] == 2 * 16 * 4
    assert state["kv"]["state"]["slots_total"] == 3
    rows = eng.step_records()
    assert all({"state_slots_used", "scan_tokens", "kv_blocks_used_full"}
               <= set(r) for r in rows)
    assert "kv_blocks_used_state" not in rows[0]
    # the prompt's 21 real tokens (three chunks, the last of 5 and 3 of
    # padding) and the first decode step in one iteration, then a token a step
    assert [r["scan_tokens"] for r in rows] == [21 + 1] + [1] * 10
    assert [r["state_slots_used"] for r in rows] == [1] * 10 + [0]
    # a family without a state reports no form of the scan
    assert make_programs(models.gpt_tiny(), chunk=8, block_size=8,
                         layers={"full": (0, 1)}).chunk_scan is None


@pytest.mark.parametrize("flag,kw,why", [
    ("prefix_cache", {"prefix_cache": True},
     "a shared prefix has no snapshot of the state"),
    ("fused_sampling", {"fused_sampling": True},
     "has no state formulation"),
    ("speculate", {"fused_sampling": True, "speculate": 2},
     "has no state formulation"),
])
def test_family_refuses_what_it_cannot_run_yet(f32_model, flag, kw, why):
    cfg, params = f32_model
    want = "fused_sampling" if flag == "speculate" else flag
    with pytest.raises(ValueError, match=f"{want} is not implemented .*{why}"):
        Engine(params, cfg, max_slots=2, block_size=4, prefill_chunk=8,
               max_context=128, **kw)


def test_speculation_is_refused_for_what_a_state_cannot_do(f32_model):
    cfg, _ = f32_model
    kv, progs = _programs(cfg)
    with pytest.raises(ValueError, match="speculate is not implemented for "
                       "the jamba family yet .a rejected draft cannot be "
                       "rolled back out of a state"):
        progs.fused(2)


def test_routed_experts_in_the_family_are_refused_not_guessed():
    with pytest.raises(ValueError, match="num_experts > 1"):
        jamba.jamba_tiny(num_experts=4)
    with pytest.raises(NotImplementedError, match="num_experts > 1"):
        cfg = jamba.jamba_tiny(dtype=jnp.float32)
        params = jamba.init_params(cfg, jax.random.PRNGKey(0))
        REF.logits(params, jnp.zeros((1, 4), jnp.int32),
                   {**_config_dict(cfg), "num_experts": 2})
