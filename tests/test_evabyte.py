"""The evabyte family (``models/evabyte.py``): EVA layers — exact attention
inside a tumbling window, one summary key/value a chunk for everything before
it, one softmax over both — whose rows live in TWO cache groups at two rates
(``serve/kv_cache.py``: a ring of token rows reused in place, a pool of
summary rows that gains one a chunk), against the plain float32 reference
(``benchmark/reference/evabyte.py``, which shares the parameter tree's layout
with the family and nothing else).

Tiny sizes: ``d`` 64, 4 heads of 16, chunks of 4 in windows of 16, 3 layers.
Tolerances, each with its reason where it is used: float32 on the CPU, so
the served logits differ from the reference's by summation order only.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu import models
from distributedtensorflow_tpu.models import evabyte
from distributedtensorflow_tpu.ops import attention as A
from distributedtensorflow_tpu.serve import kv_cache
from distributedtensorflow_tpu.serve.engine import Engine
from distributedtensorflow_tpu.serve.model import make_programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmark/reference/evabyte.py", "ref_evabyte")
COUNTS = _load("benchmark/counts/evabyte.py", "counts_evabyte")

#: float32 end to end: what is left is the order of the sums (the served
#: path's softmax runs over [window | summaries] gathered through the page
#: tables, the reference's over the whole sequence), on logits of size ~3
TOL = 2e-4


def _config_dict(cfg):
    """The reference's view of a config: the published keys."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers, vocab_size=cfg.vocab_size,
        chunk_size=cfg.chunk_size, window_size=cfg.window_size,
        num_pred_heads=cfg.num_pred_heads, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.norm_eps)


@pytest.fixture(scope="module")
def f32_model():
    cfg = evabyte.evabyte_tiny(dtype=jnp.float32)
    # std 0.2: logits of size ~3
    params = evabyte.init_params(cfg, jax.random.PRNGKey(48), std=0.2)
    return cfg, params


def _prompt(seed, n, cfg):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n).tolist()


def _reference_logits(cfg, params, ids):
    return np.asarray(REF.logits(params, jnp.asarray([ids]),
                                 _config_dict(cfg))[0])


def _record_logits(eng):
    """``{request id: [the logits of every served position]}``, filled as
    ``eng`` runs (``tests/test_lfm2.py`` has the same spy)."""
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


def _serve(cfg, params, jobs, **engine_kw):
    eng = _engine(cfg, params, **engine_kw)
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


class _Slots:
    """The programs over a cache, driven by hand: chunks and decode steps of
    chosen slots in a chosen order."""

    def __init__(self, cfg, params, *, slots=3, block=4, chunk=8,
                 context=128):
        self.cfg, self.params, self.chunk = cfg, params, chunk
        self.kv = kv_cache.make_grouped_cache(
            cfg, max_slots=slots, block_size=block, max_context=context,
            num_blocks={"full": None, "window": None}, write_ahead=chunk)
        self.programs = make_programs(cfg, chunk=chunk, block_size=block,
                                      layers=self.kv.layers)

    def prefill(self, slot, prompt, footprint):
        assert self.kv.admit(slot, footprint) is not None
        c, start, logits = self.chunk, 0, None
        while start < len(prompt):
            real = min(len(prompt) - start, c)
            buf = np.zeros(c, np.int32)
            buf[:real] = prompt[start:start + real]
            self.kv.prepare_write(slot, start + c)
            logits, pools = self.programs.prefill(
                self.params, self.kv.pools(), buf, start,
                {n: jnp.asarray(g.block_tables[slot].copy())
                 for n, g in self.kv.groups.items()}, real)
            self.kv.set_pools(pools)
            self.kv.note_written(slot, min(start + c, len(prompt)))
            start += c
        return np.asarray(logits)

    def decode(self, tokens: dict):
        """One step for the slots of ``tokens`` ``{slot: token}``; their
        logits."""
        slots = np.array(sorted(tokens))
        feed = np.zeros(self.kv.max_slots, np.int32)
        active = np.zeros(self.kv.max_slots, bool)
        for s, t in tokens.items():
            feed[s], active[s] = t, True
            self.kv.prepare_write(s, int(self.kv.seq_lens[s]) + 1)
        logits, _, pools, _ = self.programs.decode(
            self.params, self.kv.pools(), jnp.asarray(feed),
            {n: jnp.asarray(g.block_tables.copy())
             for n, g in self.kv.groups.items()},
            jnp.asarray(self.kv.seq_lens.copy()), jnp.asarray(active))
        logits = np.asarray(logits)
        self.kv.set_pools(pools)
        self.kv.note_written(slots, self.kv.seq_lens[slots] + 1)
        return {s: logits[s] for s in tokens}


# -- the equations -----------------------------------------------------------

@pytest.mark.parametrize("length", [53, 16, 7])
def test_dense_forward_matches_the_reference(f32_model, length):
    """A length that is no multiple of chunk (4) or window (16), one window
    exactly, and less than two chunks."""
    cfg, params = f32_model
    ids = _prompt(1, length, cfg)
    got = np.asarray(evabyte.forward(params, jnp.asarray([ids]), cfg)[0])
    np.testing.assert_allclose(got, _reference_logits(cfg, params, ids),
                               atol=TOL)


def test_under_one_window_it_is_plain_causal_attention(f32_model):
    cfg, _ = f32_model
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((cfg.window_size, 4, 16)),
                           jnp.float32) for _ in range(3))
    mu, phi = (jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
               for _ in range(2))
    got = evabyte.dense_attend(cfg)(q, k, v, mu=mu, phi=phi)
    want = A.xla_attention(q[None], k[None], v[None], causal=True)[0]
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_summaries_are_seen_past_the_window_only(f32_model):
    """Changing ``mu`` / ``phi`` changes nothing inside the first window and
    something in every later one."""
    cfg, params = f32_model
    ids = _prompt(3, 40, cfg)
    other = jax.tree.map(lambda a: a, params)
    other["h0"] = {**params["h0"], "attn": {
        **params["h0"]["attn"], "mu": -params["h0"]["attn"]["mu"]}}
    a, b = (np.asarray(evabyte.forward(p, jnp.asarray([ids]), cfg)[0])
            for p in (params, other))
    w = cfg.window_size
    assert np.array_equal(a[:w], b[:w])
    assert (np.abs(a[w:] - b[w:]).max(-1) > 1e-4).all()


# -- chunked prefill and cached decode --------------------------------------

@pytest.mark.parametrize("n_prompt,chunk", [(27, 8), (5, 8), (33, 16),
                                            (16, 4)])
def test_prefill_chunks_and_decode_match_the_full_forward(
        f32_model, n_prompt, chunk):
    """Prefill chunks, then decode through the cache to position 53, against
    the reference's whole-sequence logits: the decode crosses chunk ends
    (every fourth step) and window ends (32 and 48), completes a chunk that
    prefill began (27 = 6 x 4 + 3: the prompt ends three rows into chunk 6),
    and the ring is reused in place twice."""
    cfg, params = f32_model
    ids = _prompt(4, 54, cfg)
    want = _reference_logits(cfg, params, ids)
    run = _Slots(cfg, params, chunk=chunk)
    got = run.prefill(1, ids[:n_prompt], 56)
    np.testing.assert_allclose(got, want[n_prompt - 1], atol=TOL)
    for t in range(n_prompt, 53):
        got = run.decode({1: ids[t]})[1]
        np.testing.assert_allclose(got, want[t], atol=TOL, err_msg=str(t))
    # 53 tokens: 13 whole chunks, three windows closed
    assert run.kv.summary_rows_written == 13
    assert run.kv.windows_closed == 3
    ring = run.kv.groups["window"]
    assert ring.mapped_blocks(1) <= cfg.window_size // 4


def test_slots_at_different_phases_in_one_decode_step(f32_model):
    """One step in which one slot closes a chunk (position 11), one a window
    and a chunk (position 31), one neither (position 21), and the step after
    it, each against the reference."""
    cfg, params = f32_model
    run = _Slots(cfg, params)
    seqs = {s: _prompt(10 + s, n + 3, cfg)
            for s, n in ((0, 11), (1, 31), (2, 21))}
    want = {s: _reference_logits(cfg, params, ids)
            for s, ids in seqs.items()}
    for s, ids in seqs.items():
        run.prefill(s, ids[:-3], 40)
    for step in (3, 2, 1):
        got = run.decode({s: ids[-step] for s, ids in seqs.items()})
        for s, ids in seqs.items():
            np.testing.assert_allclose(got[s], want[s][len(ids) - step],
                                       atol=TOL, err_msg=f"{s} {step}")
    assert run.kv.windows_closed == 0 + 2 + 1


def test_released_ring_blocks_change_nothing_later(f32_model):
    """A closed window's exact rows are read by no one: with every ring block
    the slot has let go (and every block nobody holds) filled with NaN while
    a step runs, the logits are as they were.  (The rows are put back after
    the step: a block mapped again holds its stale rows past the newest one,
    which a masked probability of 0 must be able to multiply.)"""
    cfg, params = f32_model
    ids = _prompt(5, 45, cfg)
    want = _reference_logits(cfg, params, ids)
    run = _Slots(cfg, params)
    run.prefill(0, ids[:20], 48)
    ring = run.kv.groups["window"]
    for t in range(20, 44):
        run.kv.prepare_write(0, t + 1)      # map this step's block first
        held = set(ring.block_tables[0].tolist()) | {ring.scratch_block}
        free = [b for b in range(ring.allocator.num_blocks) if b not in held]
        rows = (np.asarray(free)[:, None] * 4 + np.arange(4)).reshape(-1)
        kept = ring.pools
        ring.pools = tuple(p.at[:, rows].set(jnp.nan) for p in kept)
        got = run.decode({0: ids[t]})[0]
        np.testing.assert_allclose(got, want[t], atol=TOL, err_msg=str(t))
        ring.pools = tuple(p.at[:, rows].set(k[:, rows])
                           for p, k in zip(ring.pools, kept))
    assert ring.blocks_recycled >= 8


def test_engine_serves_requests_of_mixed_lengths(f32_model):
    """End to end through ``Engine``: prompts under a chunk, across a window
    and of several windows, decoding together, each held to the reference
    under its own served prefix."""
    cfg, params = f32_model
    jobs = [(_prompt(20, 3, cfg), 30), (_prompt(21, 29, cfg), 24),
            (_prompt(22, 50, cfg), 20), (_prompt(23, 17, cfg), 9)]
    eng, served = _serve(cfg, params, jobs)
    for (prompt, _), (tokens, logits) in zip(jobs, served):
        want = _reference_logits(cfg, params, prompt + tokens)
        np.testing.assert_allclose(logits, want[len(prompt) - 1:-1],
                                   atol=TOL)
    state = eng.state()
    assert state["decode_attention"] == "plain"     # the CPU
    assert state["chunk_attention"] == "plain"
    groups = state["kv_groups"]
    assert groups["window"]["form"] == "TumblingKVRows"
    assert groups["full"]["form"] == "SummaryKVRows"
    # 3 layers x (K and V of 4 heads of 16 a token + the same a 4 tokens)
    assert state["cache_row_bytes"] == 3 * (512 + 512 // 4)
    assert eng.kv.layers == {"full": (0, 1, 2), "window": (0, 1, 2)}
    kv = eng.kv.stats()
    assert kv["blocks_free"] == kv["blocks_total"]      # nothing leaked
    assert kv["blocks_recycled"] > 0
    rows = eng.step_records()
    assert sum(r["summary_rows_written"] for r in rows) == sum(
        (len(p) + n - 1) // cfg.chunk_size for p, n in jobs)
    assert sum(r["windows_closed"] for r in rows) > 0
    decoded = [r for r in rows if "window_rows_read" in r]
    assert decoded and all(r["full_rows_read"] >= 0 for r in decoded)
    assert any("chunk_summary_rows_read" in r for r in rows)


def test_step_log_counts_the_two_walks_of_a_decode_step(f32_model,
                                                         monkeypatch):
    """``paged_stretches_walked`` / ``_capacity`` where the decode program
    attends through ``paged_attn`` (said to here: the tiny heads of 16 take
    the plain gather; stretches of 8 rows so that windows of 16 span
    several): a layer's ring walk runs from the stretch of the open window's
    first row to the query's, its summary walk over the closed windows'
    rows; the two idle slots attend nothing, a grid step of no trip each, and
    are not counted; the capacity is the most trips the slots' walks can take."""
    from distributedtensorflow_tpu.serve import engine, model

    cfg, params = f32_model
    monkeypatch.setattr(engine, "PAGED_STRETCH", 8)
    monkeypatch.setattr(model.Programs, "decode_attention",
                        property(lambda self: "paged_attn"))
    prompt, n_new = _prompt(24, 21, cfg), 30
    eng, _ = _serve(cfg, params, [(prompt, n_new)])
    w, per, layers = cfg.window_size, cfg.chunk_size, 3
    decodes = [r for r in eng.step_records() if r["occupancy"]]
    assert len(decodes) == n_new - 1
    for i, r in enumerate(decodes):
        pos = len(prompt) + i
        ring = -(-(pos + 1) // 8) - pos // w * w // 8
        summaries = -(-(pos // w * (w // per)) // 8)
        assert r["paged_stretches_walked"] == layers * (
            ring + summaries), (i, r)
    # 3 slots x (a ring's window + a stretch - 1 = 23 rows: 3 stretches;
    # 128 positions' 32 summary rows: 4)
    assert {r["paged_stretches_capacity"] for r in decodes} == {
        layers * 3 * (3 + 4)}
    monkeypatch.undo()
    eng, _ = _serve(cfg, params, [(prompt, 3)])
    assert not any("paged_stretches_walked" in r for r in eng.step_records())


# -- the cache ----------------------------------------------------------------

def test_cache_groups_advance_at_two_rates():
    cfg = evabyte.evabyte_tiny()
    kv = kv_cache.make_grouped_cache(
        cfg, max_slots=2, block_size=4, max_context=128,
        num_blocks={"full": None, "window": None}, write_ahead=8)
    ring, pool = kv.groups["window"], kv.groups["full"]
    assert (ring.tumbling, ring.tokens_per_row, pool.tokens_per_row) == (
        True, 1, 4)
    # a window of 16 in blocks of 4, and one block: the chunk grid lies on
    # the window grid, so nothing is written ahead
    assert ring.reservation(128) == 5 and ring.allocator.num_blocks == 10
    # 128 positions are 32 summary rows: 8 blocks a slot
    assert pool.reservation(128) == 8 and pool.blocks_per_slot == 8
    assert pool.reservation(17) == 1 and pool.reservation(3) == 0
    kv.admit(0, 67)
    for end in range(1, 68):
        kv.prepare_write(0, end)
        kv.note_written(0, end)
        assert ring.mapped_blocks(0) == -(-(end % 16) // 4)
    assert kv.summary_rows_written == 16 and kv.windows_closed == 4
    with pytest.raises(kv_cache.OutOfBlocksError, match="exceed reserved"):
        kv.note_written(0, 68)
    np.testing.assert_array_equal(
        ring.rows_attended(np.array([0, 15, 16, 37])), [1, 16, 1, 6])
    np.testing.assert_array_equal(
        pool.rows_attended(np.array([0, 15, 16, 37])), [0, 0, 4, 8])


@pytest.mark.parametrize("what", ["rollback", "register_prefix"])
def test_cache_keeps_no_earlier_position(what):
    kv = kv_cache.make_grouped_cache(
        evabyte.evabyte_tiny(), max_slots=2, block_size=4, max_context=128,
        num_blocks={"full": None, "window": None}, write_ahead=8)
    kv.admit(0, 40)
    with pytest.raises(ValueError, match="chunk summaries beside a tumbling"):
        getattr(kv, what)(0, 0 if what == "rollback" else [1, 2, 3, 4])


# -- the refusals ---------------------------------------------------------------

@pytest.mark.parametrize("kw,message", [
    (dict(prefix_cache=True), "prefix_cache is not implemented for a model "
                              "of several layer groups"),
    (dict(fused_sampling=True), "fused_sampling is not implemented for the "
                                "evabyte family yet .*ring and a summary"),
    # speculation runs on the sampled program, which is refused first
    (dict(fused_sampling=True, speculate=2),
     "fused_sampling is not implemented for the evabyte family yet"),
    (dict(prefill_chunk=12), "prefill_chunk=12 does not fit a tumbling "
                             "window of 16 summarised in chunks of 4"),
    (dict(prefill_chunk=32), "prefill_chunk=32 does not fit a tumbling"),
    (dict(prefill_chunk=2), "prefill_chunk=2 does not fit a tumbling"),
])
def test_refusals(f32_model, kw, message):
    cfg, params = f32_model
    with pytest.raises(ValueError, match=message):
        _engine(cfg, params, **kw)


def test_programs_refuse_the_prefix_cache_by_mechanism():
    cfg = evabyte.evabyte_tiny()
    progs = make_programs(cfg, chunk=8, block_size=4,
                          layers=kv_cache.layer_groups(cfg))
    with pytest.raises(ValueError, match="prefix_cache is not implemented "
                       "for the evabyte family yet .*reused in place"):
        progs.check_prefix_cache()
    with pytest.raises(ValueError, match="speculate is not implemented for "
                       "the evabyte family yet .*rolled back"):
        progs.fused(2)


# -- parameters and counts ----------------------------------------------------

@pytest.mark.parametrize("preset,config_file", [
    ("evabyte_6_5b", "benchmark/configs/evabyte-6.5b-serve.json"),
    ("evabyte_tiny",
     "benchmark/tests/rehearsal/configs/evabyte-tiny-serve.json"),
])
def test_parameter_count_matches_the_counts_module(preset, config_file):
    import json

    with open(os.path.join(ROOT, config_file)) as f:
        config = json.load(f)
    cfg = getattr(models, preset)()
    tree = jax.eval_shape(
        lambda: evabyte.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert n == COUNTS.params(config)
    if "parameters" in config:
        assert n == config["parameters"] == 1_630_932_992
    # the file states the preset's shapes
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_hidden_layers"], config["chunk_size"],
            config["window_size"], config["num_pred_heads"]) == (
        cfg.hidden_size, cfg.num_heads, cfg.num_layers, cfg.chunk_size,
        cfg.window_size, cfg.num_pred_heads)


def test_counts_of_a_decode_step_follow_the_true_lengths():
    import json

    with open(os.path.join(
            ROOT, "benchmark/configs/evabyte-6.5b-serve.json")) as f:
        config = json.load(f)
    row = 2 * 32 * 128 * 2      # K and V of 32 heads of 128 in bf16
    lives = [100, 2048, 2049, 9000]
    ring = 100 + 2048 + 1 + (9000 - 4 * 2048)
    seen = 0 + 0 + 128 + 4 * 128
    got = COUNTS.decode_kernel(config, "eva_attn", lives)
    assert got["bytes"] == 8 * ((ring + seen) * row + 4 * 2 * 4096 * 2)
    assert got["flops"] == 8 * (ring + seen) * 4 * 4096
    whole = COUNTS.decode_kernel(config, "decode_iter", lives)["bytes"]
    assert whole == COUNTS.params(config) * 2 - COUNTS.unread_params(
        config) * 2 + 8 * (ring + seen) * row
    chunk = COUNTS.decode_kernel(config, "eva_chunk_attn", lives,
                                 {"chunk_summary_rows_read": 256})
    pairs = 2048 * 2049 // 2 + 2048 * 256
    assert chunk["flops"] == 8 * pairs * 4 * 4096


# -- the kernels ----------------------------------------------------------------

def _kernel_case(closed: int, slots=3, seed=7):
    """Pools of a layer with ``closed`` windows closed a slot (window 256,
    chunk 16, 2 heads of 128, blocks of 16), filled with random rows, and the
    tables that map them in a shuffled order."""
    rng = np.random.default_rng(seed)
    w, c, bs, h, d = 256, 16, 16, 2, 128
    context = 4 * w
    ring_blocks, sum_blocks = slots * (w // bs + 1), slots * (context // c
                                                              // bs)
    pools = {}
    for name, n in (("window", ring_blocks), ("full", sum_blocks)):
        pools[name] = tuple(
            jnp.asarray(rng.standard_normal((2, (n + 1) * bs, h * d)),
                        jnp.bfloat16) for _ in range(2))
    ring = np.full((slots, context // bs), ring_blocks, np.int32)
    summ = np.full((slots, context // c // bs), sum_blocks, np.int32)
    order = rng.permutation(ring_blocks)
    for s in range(slots):
        mine = order[s * (w // bs):(s + 1) * (w // bs)]
        ring[s, closed * (w // bs):(closed + 1) * (w // bs)] = mine
    order = rng.permutation(sum_blocks)
    per = context // c // bs
    for s in range(slots):
        summ[s] = order[s * per:(s + 1) * per]
    return dict(w=w, c=c, bs=bs, h=h, d=d, pools=pools,
                tables={"window": jnp.asarray(ring),
                        "full": jnp.asarray(summ)}, rng=rng)


@pytest.mark.parametrize("closed", [0, 1, 3])
def test_decode_kernel_matches_the_plain_formulation(closed):
    """``paged_attn`` interpreted, two walks merged by their log-sum-exp,
    against the plain gather under one softmax: slots early, midway and at
    the end of the open window.  bf16 rows, float32 softmax on both sides:
    what differs is where the probabilities are rounded (tolerance 2e-2 on
    outputs of size ~1)."""
    case = _kernel_case(closed)
    w = case["w"]
    lens = jnp.asarray([closed * w + 1, closed * w + 100,
                        (closed + 1) * w], jnp.int32)
    q = jnp.asarray(case["rng"].standard_normal((3, case["h"], case["d"])),
                    jnp.bfloat16)
    kw = dict(layer=1, block_size=case["bs"], window=w,
              chunk_size=case["c"])
    args = (q, case["pools"]["window"], case["pools"]["full"],
            case["tables"]["window"], case["tables"]["full"], lens)
    plain = A.eva_decode_attention(*args, impl="xla", **kw)
    kernel = A.eva_decode_attention(*args, impl="pallas", interpret=True,
                                    **kw)
    np.testing.assert_allclose(np.asarray(kernel, np.float32),
                               np.asarray(plain, np.float32), atol=2e-2)


#: (tokens a slot holds, its open window's first row): the ring's walk
RING_WALKS = {
    "window_just_opened": ([2048 + 1, 1, 0], [2048, 0, 0]),
    "three_trips_from_a_window's_start": (
        [2048 + 1300, 3 * 2048 + 513, 2048], [2048, 3 * 2048, 0]),
    "windows_of_other_sizes": ([1328 + 600, 384 + 383], [1328, 384]),
}


@pytest.mark.parametrize("lens,lo", list(RING_WALKS.values()),
                         ids=list(RING_WALKS))
def test_ring_walk_starts_at_the_open_window(lens, lo, check_paged_walk):
    """``paged_attn`` as the ring's walk calls it, interpreted, at heads of
    128 with rows of their own: ``lo=`` names the open window's first row
    (every earlier column of the table names the scratch block, which holds
    NaN), ``with_lse=`` returns the log of the denominator that
    ``merge_softmax_parts`` needs, and a walk over nothing (an idle slot's
    summary walk) leaves it under ``NEG_INF``."""
    check_paged_walk(lens=lens, lo=lo, cols=4 * 2048 // 16, heads=4,
                     kv_heads=4, d=128, with_lse=True, slots=4, nb=256)


@pytest.mark.parametrize("closed,start,chunk", [(0, 0, 256), (1, 0, 256),
                                                (3, 128, 128), (1, 64, 64)])
def test_chunk_kernel_matches_the_plain_formulation(closed, start, chunk):
    """``kv_chunk_attn`` interpreted — the causal walk from the window's
    first row and the unmasked walk over the visible summaries, merged —
    against the plain formulation: a chunk of a whole window and chunks that
    start inside one (the window's first row lies inside the kernel's first
    stretch of 512, which it must mask)."""
    case = _kernel_case(closed, slots=1)
    w = case["w"]
    q = jnp.asarray(
        case["rng"].standard_normal((chunk, case["h"], case["d"])),
        jnp.bfloat16)
    kw = dict(layer=0, block_size=case["bs"], window=w,
              chunk_size=case["c"])
    args = (q, jnp.int32(closed * w + start), case["pools"]["window"],
            case["pools"]["full"], case["tables"]["window"][0],
            case["tables"]["full"][0])
    plain = A.eva_chunk_attention(*args, impl="xla", **kw)
    kernel = A.eva_chunk_attention(*args, impl="pallas", interpret=True,
                                   **kw)
    np.testing.assert_allclose(np.asarray(kernel, np.float32),
                               np.asarray(plain, np.float32), atol=2e-2)


def test_kernel_forms_at_the_published_heads():
    cfg = models.evabyte_6_5b()
    progs = make_programs(
        dataclasses.replace(cfg, kernel_impl="pallas"), chunk=2048,
        block_size=16, layers=kv_cache.layer_groups(cfg))
    assert progs.decode_attention == "paged_attn"
    assert progs.chunk_attention == "kv_chunk_attn"

