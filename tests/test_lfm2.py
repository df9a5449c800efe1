"""The lfm2 family (gated short-convolution layers keeping a two-row tail a
slot, an attention layer amid them with a norm a head under rotary, routed
experts in every layer past the dense one) on the CPU at a tiny size, seeded
weights, logits compared: the dense ``forward`` and the serving path (chunked
prefill that continues the tail the slot's last chunk left, decode that steps
every slot's tail; experts routed inside both) against
``benchmark/reference/lfm2.py``'s whole-sequence forward; what a state beside
experts adds (pad tokens reach no expert and leave no trace in the tail, a
re-used slot starts from zeros, an inactive slot's tail stays bit for bit);
the one-array state group; the counters of both from one engine; and what must
hold of the benchmark's files on every later PR.

With float32 parameters the system and the reference do the same float32
arithmetic in another order (a convolution cut into chunks is the same three
products; a running softmax over key chunks; the experts' rows grouped and
padded): logits of size ~5 agree to 1e-4.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu import models
from distributedtensorflow_tpu.models import jamba, lfm2
from distributedtensorflow_tpu.ops import ssm
from distributedtensorflow_tpu.parallel.moe import sigmoid_topk_route
from distributedtensorflow_tpu.serve.engine import Engine
from distributedtensorflow_tpu.serve.kv_cache import make_grouped_cache
from distributedtensorflow_tpu.serve.model import make_prefill_fn, \
    make_programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-4


def _bench_module(*parts):
    path = os.path.join(ROOT, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1][:-3] + "_lfm2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module("reference", "lfm2.py")
COUNTS = _bench_module("counts", "lfm2.py")


def _config_dict(cfg: lfm2.Lfm2Config) -> dict:
    """What the benchmark's configuration file would say of ``cfg``."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        num_hidden_layers=cfg.num_layers, layer_types=list(cfg.layer_types),
        num_dense_layers=cfg.num_dense_layers, vocab_size=cfg.vocab_size,
        conv_L_cache=cfg.conv_kernel, norm_eps=cfg.norm_eps,
        rope_parameters={"rope_theta": cfg.rope_theta},
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.experts_per_token,
        norm_topk_prob=cfg.route_norm,
        routed_scaling_factor=cfg.route_scale)


@pytest.fixture(scope="module")
def f32_model():
    cfg = lfm2.lfm2_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~5
    params = lfm2.init_params(cfg, jax.random.PRNGKey(45), std=0.2)
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


# (a) the dense forward, and chunks then decode, against the reference

@pytest.mark.parametrize("batch,length", [(2, 37), (1, 3), (3, 1)])
def test_whole_forward_is_the_reference(f32_model, batch, length):
    cfg, params = f32_model
    ids = jnp.asarray([_prompt(5 + i, length, cfg) for i in range(batch)])
    got = np.asarray(lfm2.forward(params, ids, cfg))
    want = np.asarray(REF.logits(params, ids, _config_dict(cfg)))
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


@pytest.mark.parametrize("prompt_len,n_new", [
    (1, 3),      # a prompt of one token: the tail is the zeros before it
    (2, 4),      # as long as the tail
    (8, 9),      # exactly one chunk: no padding at all
    (9, 25),     # a second chunk of one real token and seven of padding
    (16, 16),    # ends on a chunk boundary
    (21, 12),    # ends inside its third chunk; decoding crosses block edges
    (57, 20),    # eight chunks, the last of one token
])
def test_served_logits_match_the_reference(f32_model, prompt_len, n_new):
    """Logits, not tokens, at every served position: the first from the
    prefill program, the rest from the decode program through the tails and
    the K/V pages."""
    cfg, params = f32_model
    prompt = _prompt(prompt_len, prompt_len, cfg)
    _, [(tokens, logits)] = _serve(cfg, params, [(prompt, n_new)])
    want = _reference_logits(cfg, params, prompt, tokens)
    assert len(tokens) == n_new
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)


def test_interleaved_requests_are_each_served_alone(f32_model):
    """Chunks of one request between decode steps of the others (a prefill
    budget of one chunk an iteration): each request's logits are the
    reference's for that request alone."""
    cfg, params = f32_model
    jobs = [(_prompt(1, 5, cfg), 40), (_prompt(2, 60, cfg), 12),
            (_prompt(3, 29, cfg), 20)]
    eng = _engine(cfg, params, prefill_budget=8)
    seen = _record_logits(eng)
    first = eng.submit(*jobs[0][:1], max_new_tokens=jobs[0][1])
    for _ in range(6):
        eng.step()
    reqs = [first] + [eng.submit(p, max_new_tokens=n) for p, n in jobs[1:]]
    _drive(eng, reqs)
    mixed = [r for r in eng.step_records()
             if r["prefill_chunks"] and r["occupancy"]]
    assert len(mixed) >= 8      # chunks and decode steps in one iteration
    served = [(r.tokens, np.stack(seen[r.id])) for r in reqs]
    _assert_served_is_reference(cfg, params, jobs, served)


# (b) what a state beside experts adds: padding, slot re-use, inactive slots

def _programs(cfg, max_slots=3, chunk=8):
    kv = make_grouped_cache(cfg, max_slots=max_slots, block_size=4,
                            max_context=64, num_blocks={}, write_ahead=chunk)
    progs = make_programs(cfg, chunk=chunk, block_size=4, layers=kv.layers)
    return kv, progs


def _table(slot):
    return {"full": jnp.arange(16, dtype=jnp.int32) + 16 * slot,
            "state": jnp.asarray([slot], jnp.int32)}


def _chunk(progs, params, kv, slot, tokens, start, real):
    """One prefill chunk of ``slot`` straight through the program, the slot's
    blocks ``slot * 16 ...``; returns (logits, the tails after it)."""
    padded = np.zeros((progs.chunk,), np.int32)
    padded[:len(tokens)] = tokens
    logits, pools = progs.prefill(params, kv.pools(), padded, start,
                                  _table(slot), real)
    kv.set_pools(pools)
    (tails,) = pools["state"]
    return np.asarray(logits), np.asarray(tails)


@pytest.fixture(scope="module")
def counting_prefill(f32_model):
    """``(cache, prefill program, pairs)``: the prefill program of a family
    whose block reports an expert layer's routed pairs out of the compiled
    program, appended to ``pairs`` a layer a call."""
    cfg, _ = f32_model
    pairs = []

    class Counting:
        embed, head = staticmethod(lfm2.embed), staticmethod(lfm2.head)

        @staticmethod
        def block(*args, **kw):
            x, counters = lfm2.block(*args, **kw)
            if counters is not None:
                jax.debug.callback(lambda p: pairs.append(int(p)),
                                   counters["pairs"])
            return x, counters

    kv, _ = _programs(cfg)
    return kv, make_prefill_fn(Counting, cfg, chunk=8, block_size=4,
                               layers=kv.layers), pairs


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])
def test_pad_tokens_reach_no_expert_and_leave_no_trace_in_the_tail(
        f32_model, counting_prefill, n):
    """A chunk of ``n`` real tokens, the rest padding of any value: the
    expert layers route ``n x top_k`` pairs and no more, the tail is the last
    two gated inputs of the real tokens (zeros before the sequence's start
    where ``n`` is under the tail's length), the logits are the reference's,
    and the padding's value changes nothing, bit for bit."""
    cfg, params = f32_model
    tokens = _prompt(n, n, cfg)
    kv, counted, pairs = counting_prefill
    pairs.clear()
    outs = []
    for pad_seed in (98, 99):
        padded = np.asarray(tokens + _prompt(pad_seed, 8 - n, cfg), np.int32)
        logits, pools = counted(
            params, kv.pools(), jnp.asarray(padded), jnp.int32(0), _table(1),
            jnp.int32(n - 1), jnp.int32(n))
        jax.block_until_ready(logits)
        kv.set_pools(pools)
        outs.append((np.asarray(logits), np.asarray(pools["state"][0])))
    expert_layers = cfg.num_layers - cfg.num_dense_layers
    assert pairs == [n * cfg.experts_per_token] * (2 * expert_layers)
    (logits, tails), (logits2, tails2) = outs
    assert np.array_equal(logits, logits2) and np.array_equal(tails, tails2)
    want = np.asarray(REF.logits(params, jnp.asarray([tokens]),
                                 _config_dict(cfg)))[0, -1]
    np.testing.assert_allclose(logits, want, atol=F32_TOL, rtol=0)

    # the tail of each conv layer: the gated inputs of the last two real
    # tokens, through the plain form over exactly n tokens
    want_tails = []

    class Exact:
        def conv(self, g, w, b, scope=None):
            out, t = ssm.causal_conv(
                g, jnp.zeros((2 * cfg.hidden_size,)), w, b, n)
            want_tails.append(np.asarray(t))
            return out

    x = lfm2.embed(params, jnp.asarray(tokens), cfg)
    positions = jnp.arange(n, dtype=jnp.int32)
    for i in range(cfg.num_layers):
        mixer = Exact() if cfg.keeps_state(i) else (
            lambda q, k, v: lfm2.xla_attention(
                q[None], k[None], v[None], causal=True)[0])
        x, _ = lfm2.block(params[f"h{i}"], x, cfg, i, positions, mixer)
    np.testing.assert_allclose(tails[:, 1], np.stack(want_tails), atol=2e-5)
    if n == 1:      # the older row is what lay before the sequence: zeros
        assert not tails[:, 1, :cfg.hidden_size].any()
    assert not tails[:, [0, 2]].any()       # the other slots: untouched


def test_decode_leaves_an_inactive_slots_tail_untouched(f32_model):
    """Bit for bit: a slot between two of its prefill chunks is inactive
    while the others decode, and its token reaches no expert."""
    cfg, params = f32_model
    kv, progs = _programs(cfg)
    _chunk(progs, params, kv, 0, _prompt(0, 8, cfg), 0, 8)
    _, before = _chunk(progs, params, kv, 1, _prompt(1, 8, cfg), 0, 8)
    tables = {"full": jnp.arange(48, dtype=jnp.int32).reshape(3, 16),
              "state": jnp.arange(3, dtype=jnp.int32)[:, None]}
    active = jnp.asarray([True, False, False])
    _, _, pools, routed = progs.decode(
        params, kv.pools(), jnp.asarray([7, 8, 9], jnp.int32), tables,
        jnp.asarray([8, 8, 0], jnp.int32), active)
    (after,) = (np.asarray(a) for a in pools["state"])
    assert np.array_equal(before[:, 1:], after[:, 1:])      # slots 1 and 2
    assert not np.array_equal(before[:, 0], after[:, 0])    # slot 0 stepped
    expert_layers = cfg.num_layers - cfg.num_dense_layers
    pairs, hit, load = (int(v) for v in routed)
    assert pairs == expert_layers * cfg.experts_per_token   # one live token
    assert hit == pairs and load == 1


def test_a_reused_slot_starts_from_a_zero_tail(f32_model):
    """One slot, three requests one after the other: the second and third
    find the tail their predecessor left and must not see it."""
    cfg, params = f32_model
    jobs = [(_prompt(i, n, cfg), m)
            for i, (n, m) in enumerate([(30, 10), (1, 12), (17, 8)])]
    eng, served = _serve(cfg, params, jobs, max_slots=1)
    assert eng.counters["admits_into_freed_slot"] >= 2
    assert eng.kv.state.pools[0].any()      # the last occupant's tail stays
    _assert_served_is_reference(cfg, params, jobs, served)


# (c) one engine: the state group's counters beside the expert layers'

def test_every_slot_live_under_load_logs_both_sets_of_counters(f32_model):
    """Every slot decoding at once with a queue behind the slots: each served
    logit still the reference's, and each decode row of the step log carries
    the routing counters beside the state group's."""
    cfg, params = f32_model
    rng = np.random.default_rng(45)
    shapes = [(70, 30), (45, 50)] + [(int(rng.integers(3, 30)),
                                      int(rng.integers(20, 45)))
                                     for _ in range(8)]
    jobs = [(_prompt(i, n, cfg), m) for i, (n, m) in enumerate(shapes)]
    eng, served = _serve(cfg, params, jobs, max_slots=6, num_blocks=110)
    rows = [r for r in eng.step_records() if r["occupancy"]]
    assert max(r["occupancy"] for r in rows) == 6
    assert max(r["state_slots_used"] for r in rows) == 6
    expert_layers = cfg.num_layers - cfg.num_dense_layers
    for r in rows:
        assert r["moe_pairs"] == (r["occupancy"] * expert_layers
                                  * cfg.experts_per_token)
        assert 1 <= r["moe_max_load"] <= r["occupancy"]
        assert r["moe_experts_hit"] <= expert_layers * cfg.num_experts
        assert {"state_slots_used", "scan_tokens",
                "kv_blocks_used_full"} <= set(r)
    assert eng.kv.stats()["blocks_free"] == 110
    assert eng.kv.stats()["state"]["slots_live"] == 0
    _assert_served_is_reference(cfg, params, jobs, served)


def test_engine_names_its_forms_and_the_one_array_state_group(f32_model):
    cfg, params = f32_model
    eng, _ = _serve(cfg, params, [(list(range(21)), 12)])
    state = eng.state()
    assert state["decode_attention"] == "plain"     # the CPU
    assert state["chunk_attention"] == "plain"
    assert state["state_form"] == "conv_tail"
    assert state["chunk_scan"] is None              # a tail has no scan
    assert state["cache_row_bytes"] == 2 * 2 * 16 * 4   # one attention layer
    assert state["kv"]["state"]["slots_total"] == 3
    (tails,) = eng.kv.state.pools
    assert tails.shape == (3, 3, 2 * cfg.hidden_size)
    assert eng.kv.layers == {"full": (1,), "state": (0, 2, 3)}
    assert eng.kv.state.slot_bytes == 3 * 2 * cfg.hidden_size * 4
    # jamba's form names two arrays, a family without a state none
    progs = make_programs(jamba.jamba_tiny(), chunk=8, block_size=4,
                          layers={"full": (1,), "state": (0, 2, 3)})
    assert progs.state_form == "conv_tail+scan_state"
    assert make_programs(models.gpt_tiny(), chunk=8, block_size=8,
                         layers={"full": (0, 1)}).state_form is None


def test_kernel_forms_at_the_published_heads():
    """Heads of 64, four query heads a K/V head: ``paged_attn`` decodes them
    (two heads a lane tile), a chunk takes the plain loop (the chunk kernel
    wants a head of 128)."""
    rows = models.lfm2_24b_a2b().cache_rows
    assert rows.decode_formulation(16, "pallas") == "paged_attn"
    assert rows.chunk_formulation(16, 2048, "pallas") == "plain"


@pytest.mark.parametrize("flag,kw,why", [
    ("prefix_cache", {"prefix_cache": True},
     "a shared prefix has no snapshot of the state"),
    ("fused_sampling", {"fused_sampling": True},
     "has no state formulation"),
    ("speculate", {"fused_sampling": True, "speculate": 2},
     "has no state formulation"),
])
def test_family_refuses_what_jamba_refuses_in_the_same_words(f32_model, flag,
                                                             kw, why):
    cfg, params = f32_model
    want = "fused_sampling" if flag == "speculate" else flag
    with pytest.raises(ValueError, match=f"{want} is not implemented .*{why}"):
        _engine(cfg, params, **kw)
    _, progs = _programs(cfg)
    with pytest.raises(ValueError, match="speculate is not implemented for "
                       "the lfm2 family yet .a rejected draft cannot be "
                       "rolled back out of a state"):
        progs.fused(2)


def test_jamba_still_refuses_experts_and_names_the_family_that_routes():
    with pytest.raises(ValueError, match="models.lfm2"):
        jamba.jamba_tiny(num_experts=4)


def test_router_divides_by_the_published_constant():
    """``sigmoid_topk_route`` takes lfm2's 1e-6 where the other families
    leave its 1e-20: seen on scores small enough for it to matter."""
    h = jnp.full((1, 4), -3.0)
    router = jnp.eye(4) * 5.0                   # scores sigmoid(-15) ~ 3e-7
    kw = dict(top_k=2, route_norm=True)
    _, tiny = sigmoid_topk_route(h, router, jnp.zeros((4,)), **kw)
    _, pub = sigmoid_topk_route(h, router, jnp.zeros((4,)),
                                route_norm_eps=lfm2.ROUTE_NORM_EPS, **kw)
    s = float(jax.nn.sigmoid(-15.0))
    np.testing.assert_allclose(tiny, [[0.5, 0.5]], rtol=1e-6)
    np.testing.assert_allclose(pub, [[s / (2 * s + 1e-6)] * 2], rtol=1e-5)
    assert REF.ROUTE_NORM_EPS == lfm2.ROUTE_NORM_EPS == 1e-6


def test_bfloat16_preset_serves_finite_logits_near_the_reference():
    cfg = lfm2.lfm2_tiny()
    params = lfm2.init_params(cfg, jax.random.PRNGKey(3), std=0.2)
    assert params["h0"]["conv"]["w_in"].dtype == jnp.bfloat16
    assert params["h1"]["moe"]["router"].dtype == jnp.float32
    assert params["h1"]["moe"]["bias"].dtype == jnp.float32
    prompt = list(range(1, 45))
    eng, [(tokens, logits)] = _serve(cfg, params, [(prompt, 24)])
    (tails,) = eng.kv.state.pools
    assert tails.dtype == jnp.bfloat16
    want = _reference_logits(cfg, params, prompt, tokens)
    assert np.isfinite(logits).all()
    assert (logits.argmax(-1) == want.argmax(-1)).mean() >= 0.75
    assert np.median(np.abs(logits - want)) < 0.1


# (d) what must hold of the benchmark's files on every later PR

def _bench_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_published_widths_5178m_parameters_56_kb_a_slot_4096_b_a_token():
    """``jax.eval_shape``: nothing is allocated.  The configuration file's
    numbers, the counts module and the preset's parameter tree agree."""
    cfg = models.lfm2_24b_a2b()
    conf = _bench_json("benchmark", "configs", "lfm2-24b-a2b-serve.json")
    assert conf["system_config"] == "lfm2_24b_a2b"
    file_says = _config_dict(cfg)
    del file_says["head_dim"]           # the source has none: assumed 64
    theta = file_says.pop("rope_parameters")["rope_theta"]
    assert conf["rope_parameters"]["rope_theta"] == theta
    for key, value in file_says.items():
        assert conf[key] == value, key
    assert conf["max_position_embeddings"] == cfg.max_seq == 9216
    shapes = jax.eval_shape(
        lambda: lfm2.init_params(cfg, jax.random.PRNGKey(0)))
    scales = ("ln_op", "ln_ffn", "ln_f", "q_norm", "k_norm")
    counted = sum(
        int(np.prod(x.shape))
        for path, x in jax.tree_util.tree_leaves_with_path(shapes)
        if path[-1].key not in scales)
    assert counted == COUNTS.params(conf) == 5_177_911_808
    assert COUNTS.expert_params(conf) * 64 * 8 == 4_831_838_208
    assert [i for i in range(cfg.num_layers) if not cfg.keeps_state(i)] \
        == [1, 5]
    rows = cfg.state_rows
    assert rows.names == ("conv_tail",)
    assert rows.arrays(cfg.dtype) == (((2 * 2048,), jnp.dtype(jnp.bfloat16)),)
    assert rows.slot_bytes(cfg.dtype) == 8192
    assert 7 * 8192 == COUNTS.state_bytes_per_slot(conf) == 57_344
    kv = jax.eval_shape(lambda: make_grouped_cache(
        cfg, max_slots=2, block_size=16, max_context=64,
        num_blocks={"full": 8}, write_ahead=16).pools())
    assert [a.shape for a in kv["state"]] == [(7, 2, 4096)]
    assert [a.shape for a in kv["full"]] == [(2, 9 * 16, 512)] * 2
    assert 2 * 2 * 8 * 64 * 2 == COUNTS.kv_bytes_per_token(conf) == 4096


def test_the_cell_and_its_rehearsal_manifest_resolve_to_files_that_exist():
    bench = _bench_json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "lfm2-24b-serve-assist-saturated")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("lfm2-24b-a2b-serve", "assist8k-saturated", 1)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert conf["file"] == "benchmark/configs/lfm2-24b-a2b-serve.json"
    assert sorted(conf["reduced"]) == [
        "layer_types", "max_position_embeddings", "num_dense_layers",
        "num_hidden_layers"]
    for metric in ("serve_tok_per_s", "setup_s"):
        entry = next(m for m in bench["end_to_end"] if m["name"] == metric)
        # setup_s lists no cells: every cell reports it
        assert cell["name"] in entry.get("workloads", [cell["name"]])
    file = _bench_json(conf["file"])
    for kind in ("reference", "counts"):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", kind, file[kind] + ".py"))
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    manifest = _bench_json("benchmark", "tests", "rehearsal",
                           "BENCHMARK-lfm2.json")
    assert cell["name"] in [w["name"] for w in manifest["workloads"]]
    names = [m["name"] for m in manifest["per_layer"]]
    assert {"decode_conv_ms.lfm2", "prefill_conv_ms.lfm2",
            "moe_grouped_roofline_pct.lfm2", "paged_attn_roofline_pct.lfm2",
            "decode_roofline_pct.lfm2"} <= set(names)
    for name in names:
        if name.endswith(".lfm2"):
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "layer_metrics", name + ".json")), name
