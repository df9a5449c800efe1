"""The next iteration's prefill, launched under the decode step in flight
(ISSUE 60).

Under a prefill budget ``Engine._to_fetch`` launches the next iteration's
chunks behind the decode step, before it fetches that step's tokens, and
the next ``step()`` collects them.  Held here: (1) the served logits are
each family's reference and the tokens those of an engine that never
launches ahead — one full group, a state group, a window group whose ring
turns under the chunks; (2) the chunks reach ``programs.prefill`` in the
order an engine without the launch ahead runs them, and every record counts
what that engine's record counts; (3) a request that leaves between its last
chunk's launch and its first token leaves nothing behind; (4) the counter;
(5) ``stop()`` with a launch pending.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_afmoe import _record_logits
from test_afmoe import _reference_logits as _afmoe_reference
from test_jamba import _reference_logits as _jamba_reference

from distributedtensorflow_tpu.models import GPTLM, afmoe, gpt_tiny, jamba
from distributedtensorflow_tpu.serve.engine import Engine

F32_TOL = 2e-4
CHUNK = 8


def _gpt():
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, max_seq=128)
    rng = jax.random.PRNGKey(0)
    params = GPTLM(cfg).init(rng, jnp.zeros((1, 8), jnp.int32))["params"]

    def reference(_cfg, params, prompt, tokens):
        ids = jnp.asarray([list(prompt) + list(tokens)])
        full = GPTLM(cfg).apply({"params": params}, ids)[0]
        return np.asarray(full)[len(prompt) - 1:-1]

    return cfg, params, reference


def _jamba():
    cfg = jamba.jamba_tiny(dtype=jnp.float32)
    return (cfg, jamba.init_params(cfg, jax.random.PRNGKey(34), std=0.2),
            _jamba_reference)


def _afmoe():
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32)
    return (cfg, afmoe.init_params(cfg, jax.random.PRNGKey(28), std=0.2),
            _afmoe_reference)


#: one full group; a state a slot beside it; a window group (window 32: the
#: prompts below cross it, so the ring turns under chunks launched ahead)
FAMILIES = {"gpt": _gpt, "jamba": _jamba, "afmoe": _afmoe}


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return FAMILIES[request.param]()


@pytest.fixture(scope="module")
def gpt():
    return _gpt()


def _engine(cfg, params, **kw):
    kw = {**dict(max_slots=3, max_queue=16, block_size=4,
                 prefill_chunk=CHUNK, prefill_budget=CHUNK, max_context=128),
          **kw}
    return Engine(params, cfg, **kw)


def _never_ahead(eng):
    """``eng`` with the launch ahead taken out of ``_to_fetch``: the
    schedule of an engine from before it."""
    def to_fetch():
        eng._tiles.to("engine.decode", "engine.decode.fetch")
        eng._cpu_leaf0 = time.thread_time()

    eng._to_fetch = to_fetch
    return eng


def _record_chunks(eng):
    """``[(slot, first position)]`` of every call of ``programs.prefill``,
    in order."""
    calls, prefill = [], eng.programs.prefill

    def stub(params, pools, ids, start, tables, real):
        # the chunk's ids are a view of its request's buffer
        slot = next(r.slot for r in eng._slots if r is not None
                    and np.shares_memory(r._fill_buf, ids))
        calls.append((slot, start))
        return prefill(params, pools, ids, start, tables, real)

    eng.programs.prefill = stub
    return calls


#: (prompt length, new tokens, temperature), in order of arrival: two up
#: front, the rest one every third iteration, so prompts fill while others
#: decode; lengths from inside one chunk to seven chunks
JOBS = [(5, 30, 0.0), (43, 12, 0.0), (17, 9, 0.8), (56, 6, 0.0),
        (8, 14, 0.0), (29, 10, 0.0)]


def _traffic(eng, cfg, jobs=JOBS, every=3):
    rng = np.random.default_rng(60)
    jobs = [(rng.integers(0, cfg.vocab_size, n).tolist(), m, t)
            for n, m, t in jobs]

    def submit(i):
        prompt, n_new, temperature = jobs[i]
        return eng.submit(prompt, max_new_tokens=n_new,
                          temperature=temperature, seed=100 + i)

    reqs = [submit(0), submit(1)]
    for i in range(4000):
        if len(reqs) < len(jobs) and i % every == 0:
            reqs.append(submit(len(reqs)))
        if len(reqs) == len(jobs) and all(r._done.is_set() for r in reqs):
            assert all(r.status == "ok" for r in reqs)
            return reqs
        eng.step()
    raise AssertionError("engine did not finish")


# ------------------------------------------------ (i) tokens and logits


def test_served_under_a_launch_ahead_is_the_reference(family):
    """Mixed prompt lengths under a budget of one chunk, one request that
    samples among greedy ones: every served position's logits are the
    family's reference for that request alone, and the tokens — the seeded
    sample's too — are bit for bit those of an engine that serves the
    requests one at a time (no decode step is in flight while its prompt
    fills, so it never launches ahead)."""
    cfg, params, reference = family
    eng = _engine(cfg, params)
    seen = _record_logits(eng)
    reqs = _traffic(eng, cfg)
    assert eng.prefill_prelaunched > 0.5 * eng.prefill_chunks
    for r in reqs:
        want = reference(cfg, params, r.prompt, r.tokens)
        np.testing.assert_allclose(np.stack(seen[r.id]), want, atol=F32_TOL,
                                   rtol=0)
        if r.temperature == 0.0:
            assert r.tokens == [int(np.argmax(row)) for row in seen[r.id]]
    assert eng.kv.stats()["blocks_free"] == eng.kv.stats()["blocks_total"]

    alone = _engine(cfg, params)
    for i, r in enumerate(reqs):
        one = alone.submit(r.prompt, max_new_tokens=r.max_new_tokens,
                           temperature=r.temperature, seed=r.seed)
        while not one._done.is_set():
            alone.step()
        assert one.tokens == r.tokens, i
    assert alone.prefill_prelaunched == 0 < alone.prefill_chunks


# ------------------------------------------- (ii) the order of the chunks


def test_chunks_rotate_as_without_the_launch_ahead(gpt):
    """A decoding request and two prompts of three and two chunks under a
    budget of one: the chunks go A0 B0 A1 B1 A2, one an iteration, the
    last four of them ahead."""
    cfg, params, _ = gpt
    eng = _engine(cfg, params)
    calls = _record_chunks(eng)
    first = eng.submit([1, 2, 3], max_new_tokens=40)
    while not first.tokens:
        eng.step()
    del calls[:]
    a = eng.submit(list(range(1, 21)), max_new_tokens=4)    # three chunks
    b = eng.submit(list(range(5, 16)), max_new_tokens=4)    # two
    rows = []
    while not (a._done.is_set() and b._done.is_set()):
        eng.step()
        rows.append(eng.step_records()[-1])
    assert calls == [(a.slot, 0), (b.slot, 0), (a.slot, 8), (b.slot, 8),
                     (a.slot, 16)]
    assert [r["prefill_chunks"] for r in rows[:6]] == [1, 1, 1, 1, 1, 0]
    assert [r["prefill_prelaunched"] for r in rows[:6]] == [0, 1, 1, 1, 1, 0]
    assert [r["prelaunch_s"] > 0 for r in rows[:6]] == [
        True, True, True, True, False, False]
    # b's first token in the record of its last chunk, a's in its own
    assert [r["first_token_s"] > 0 for r in rows[:6]] == [
        False, False, False, True, True, False]


@pytest.mark.parametrize("budget", [CHUNK, 2 * CHUNK])
def test_every_record_counts_what_it_counted_without_the_launch_ahead(
        family, budget):
    """The same traffic through the engine and through one with the launch
    ahead taken out: the chunks reach ``programs.prefill`` in one order,
    the tokens are equal bit for bit, and record n holds the same chunks,
    tokens, pairs, stall, admissions, occupancy and family counters in
    both — a chunk is its budget's iteration's, wherever it was launched."""
    cfg, params, _ = family
    runs = []
    for ahead in (True, False):
        eng = _engine(cfg, params, prefill_budget=budget)
        if not ahead:
            _never_ahead(eng)
        calls = _record_chunks(eng)
        reqs = _traffic(eng, cfg)
        runs.append((eng, calls, [r.tokens for r in reqs],
                     eng.step_records()))
    (eng, calls, tokens, rows), (base, calls0, tokens0, rows0) = runs
    assert calls == calls0 and len(calls) == eng.prefill_chunks
    assert tokens == tokens0
    assert eng.prefill_prelaunched > 0 == base.prefill_prelaunched
    assert len(rows) == len(rows0)
    same = {"phase", "occupancy", "admitted", "evicted", "prefill_chunks",
            "chunk_tokens", "chunk_pairs", "budget_stall", "filling_slots",
            "tokens_committed", "active_slots", "scan_tokens",
            "state_slots_used", "context_tokens", "kv_blocks_billed"}
    for r, r0 in zip(rows, rows0):
        assert {k: r[k] for k in same & set(r)} \
            == {k: r0[k] for k in same & set(r0)}, r["step"]
        assert r["prefill_chunks"] * CHUNK <= budget
        assert r0["prelaunch_s"] == 0 == r0["prefill_prelaunched"]
    for name in ("prefill_chunks", "prefill_iters", "prefill_budget_stalls",
                 "decode_steps"):
        assert getattr(eng, name) == getattr(base, name), name
    assert eng.kv.blocks_recycled == base.kv.blocks_recycled


# ------------------- (iii) a request that leaves before it is collected


def _pending_last_chunk(cfg, params):
    """An engine with one request decoding and another whose last chunk
    was launched ahead: ``(engine, decoding, waiting for its first
    token)``."""
    eng = _engine(cfg, params)
    first = eng.submit([1, 2, 3], max_new_tokens=40)
    while not first.tokens:
        eng.step()
    second = eng.submit(list(range(1, 14)), max_new_tokens=5)   # two chunks
    eng.step()      # admits it; chunk 0 in line, chunk 1 ahead
    assert [r for r, _ in eng._pending.finished] == [second]
    assert not second.tokens and not eng._filling
    return eng, first, second


def test_a_request_that_leaves_before_its_first_token_is_dropped(gpt):
    cfg, params, _ = gpt
    eng, first, second = _pending_last_chunk(cfg, params)
    free = eng.kv.stats()["blocks_free"]
    second.error = "cancelled"
    eng._finish(second, "error", status="error")
    assert eng._pending.finished == []
    assert eng._slots[second.slot] is None
    assert eng.kv.stats()["blocks_free"] > free
    # the launch is still this engine's next budget, spent; whoever takes
    # the slot is served as ever
    third = eng.submit(list(range(2, 30)), max_new_tokens=4)
    assert eng.step()
    row = eng.step_records()[-1]
    assert row["prefill_chunks"] == row["prefill_prelaunched"] == 1
    assert row["first_token_s"] == 0 and third.slot == second.slot
    while not (first._done.is_set() and third._done.is_set()):
        eng.step()
    assert first.status == third.status == "ok" and second.status == "error"
    assert not second.tokens and eng._pending is None
    fresh = _engine(cfg, params)
    again = fresh.submit(third.prompt, max_new_tokens=4)
    while not again._done.is_set():
        fresh.step()
    assert third.tokens == again.tokens
    assert eng.kv.stats()["blocks_free"] == eng.kv.stats()["blocks_total"]


# ------------------------------------------------------- (iv) the counter


@pytest.mark.parametrize("budget", [None, CHUNK])
def test_launch_ahead_follows_from_a_budget_and_a_waiting_filler(gpt, budget):
    """Without a budget no filler is left where the decode step goes, so
    nothing is launched ahead and no record has the leaf; under one, with
    two prompts filling, most chunks are."""
    cfg, params, _ = gpt
    eng = _engine(cfg, params, prefill_budget=budget)
    _traffic(eng, cfg)
    rows = eng.step_records()
    state = eng.state()
    assert state["prefill_prelaunched"] == eng.prefill_prelaunched \
        == sum(r["prefill_prelaunched"] for r in rows)
    assert state["prefill_chunks"] == sum(r["prefill_chunks"] for r in rows)
    if budget is None:
        assert eng.prefill_prelaunched == 0
        assert all(r["prelaunch_s"] == 0 for r in rows)
    else:
        assert eng.prefill_prelaunched > 0.5 * eng.prefill_chunks
        assert any(r["filling_slots"] >= 2 and r["prefill_prelaunched"]
                   for r in rows)
    for r in rows:
        leaves = r["dispatch_s"] + r["prelaunch_s"] + r["fetch_s"] \
            + r["commit_s"]
        assert abs(leaves - r["decode_s"]) <= 1e-5
        assert r["unnamed_s"] <= 0.02 * r["step_s"] + 1e-5
        assert r["first_token_s"] <= r["prefill_s"] + 1e-6
        assert r["prefill_chunks"] > 0 or r["first_token_s"] == 0


# ------------------------------------------------- (v) stop() while pending


@pytest.mark.parametrize("drain", [True, False])
def test_stop_with_a_launch_pending_ends_clean(gpt, drain):
    cfg, params, _ = gpt
    eng, first, second = _pending_last_chunk(cfg, params)
    eng.start()
    eng.stop(drain=drain, timeout=60)
    assert eng._pending is None and not eng._filling
    assert first._done.is_set() and second._done.is_set()
    if drain:
        assert first.status == second.status == "ok"
        assert len(second.tokens) == 5
        assert eng.prefill_chunks == sum(
            r["prefill_chunks"] for r in eng.step_records())
    else:
        assert {first.status, second.status} <= {"ok", "error"}
    assert all(r is None for r in eng._slots)
    assert eng.kv.stats()["blocks_free"] == eng.kv.stats()["blocks_total"]
