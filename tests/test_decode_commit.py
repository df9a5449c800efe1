"""One host pass a decode iteration (PR 31): the engine takes a greedy
slot's token from the decode program's own arg-max and fetches the logits
only for a request that samples; the batch commits in one pass.

Held here to the rule it replaced, written out slot by slot in the tests:

- **tokens**: greedy = arg-max of the row the program computed; sampled =
  ``Engine._sample`` on that row with that request's seeded generator — in
  a mixed batch too — and the step records say which slots took which;
- **the commit**: every per-request field, engine counter and
  histogram after ``_commit_tokens`` of a whole batch equals what
  the slot-at-a-time ``_charge_decode`` + ``_commit_tokens`` of the parent
  left, on the one-token path and on the fused path with bursts, and an EOS
  and a length finish that fall in one iteration both happen in it;
- **the cache**: ``note_written`` for a batch of slots leaves a window
  group's tables, ring stocks, ``blocks_recycled`` and ``tables_version``
  as one call a slot did, when several slots cross a block edge at once.
"""

import dataclasses
import json
import os
import queue
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import GPTLM, afmoe, gpt_tiny
from distributedtensorflow_tpu.serve import Engine
from distributedtensorflow_tpu.serve.kv_cache import make_grouped_cache

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_metrics_schema  # noqa: E402


@pytest.fixture(scope="module")
def families():
    """A tiny float32 model a family: ``{name: (cfg, params, engine kw)}``."""
    gpt_cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, max_seq=64)
    key = jax.random.PRNGKey(0)
    gpt_params = GPTLM(gpt_cfg).init(
        key, jnp.zeros((1, 8), jnp.int32))["params"]
    af_cfg = afmoe.afmoe_tiny(dtype=jnp.float32)
    af_params = afmoe.init_params(af_cfg, jax.random.PRNGKey(28), std=0.2)
    return {
        "gpt": (gpt_cfg, gpt_params,
                dict(block_size=4, prefill_chunk=4, max_context=64)),
        "afmoe": (af_cfg, af_params,
                  dict(block_size=4, prefill_chunk=8, max_context=128)),
    }


def _drain(eng, reqs, max_steps=2000):
    for _ in range(max_steps):
        if all(r._done.is_set() for r in reqs):
            return
        eng.step()
    raise AssertionError("engine did not finish within max_steps")


def _record_rows(eng):
    """``{request id: [the logits row of every served position]}``: a
    request's first row as the host sampler is handed it, a decode
    iteration's rows as the program returns them."""
    rows = {}
    sample, decode = eng._sample, eng.programs.decode

    def first(req, logits):
        if not req.tokens:
            rows.setdefault(req.id, []).append(np.array(logits))
        return sample(req, logits)

    def spy(*args):
        out = decode(*args)
        logits = np.asarray(out[0])
        for slot, req in enumerate(eng._slots):
            if req is not None and req._prefill_done:
                rows.setdefault(req.id, []).append(logits[slot].copy())
        return out

    eng._sample, eng.programs.decode = first, spy
    return rows


# ------------------------------------------------------------------ tokens


@pytest.mark.parametrize("family", ["gpt", "afmoe"])
@pytest.mark.parametrize("mix", ["greedy", "mixed", "sampled"])
def test_tokens_are_the_parents_rule_request_by_request(families, family,
                                                        mix):
    cfg, params, kw = families[family]
    eng = Engine(params, cfg, max_slots=4, **kw)
    rows = _record_rows(eng)
    rng = np.random.default_rng(7)
    temps = {"greedy": [0, 0, 0, 0, 0], "mixed": [0, 0.9, 0, 0.7, 0],
             "sampled": [0.8, 1.1, 0.6, 0.9, 1.0]}[mix]
    jobs = [(rng.integers(0, cfg.vocab_size, n).tolist(), m)
            for n, m in ((9, 14), (5, 9), (17, 11), (6, 16), (12, 8))]
    reqs = [eng.submit(p, max_new_tokens=m, temperature=t,
                       top_k=8 if t else 0, seed=40 + i)
            for i, ((p, m), t) in enumerate(zip(jobs, temps))]
    # who decodes in which step record: the slots' requests at decode time
    decoding = {}
    run = eng._run_decode_step

    def census(prefill_s):
        decoding[eng._step_id + 1] = [
            r for r in eng._slots if r is not None and r._prefill_done]
        run(prefill_s)

    eng._run_decode_step = census
    _drain(eng, reqs)
    for req in reqs:
        # the parent's rule on the rows the programs computed: the arg-max,
        # or the host sampler with a generator of the request's own seed
        shadow = types.SimpleNamespace(
            temperature=req.temperature, top_k=req.top_k,
            _rng=np.random.default_rng(req.seed))
        assert req.status == "ok" and len(rows[req.id]) == len(req.tokens)
        assert req.tokens == [
            Engine._sample(eng, shadow, row) for row in rows[req.id]]
        if not req.temperature:
            assert req.tokens == [int(np.argmax(r)) for r in rows[req.id]]
    records = {r["step"]: r for r in eng.step_records()}
    assert decoding and set(decoding) <= set(records)
    for step, live in decoding.items():
        greedy = sum(r.temperature <= 0.0 for r in live)
        rec = records[step]
        assert rec["occupancy"] == len(live)
        assert rec["device_sampled"] == greedy
        assert rec["logits_fetched"] == int(greedy < len(live))
    for step, rec in records.items():
        if step not in decoding:
            assert rec["device_sampled"] == rec["logits_fetched"] == 0
    fetches = sum(r["logits_fetched"] for r in records.values())
    c = eng.counters
    assert c["logit_fetches"] == c["host_sample_rounds"] == fetches
    assert c["device_sampled_tokens"] == sum(
        r["device_sampled"] for r in records.values())
    if mix == "greedy":
        assert fetches == 0
        assert c["device_sampled_tokens"] == c["decode_tokens"]
    else:
        assert 0 < fetches <= eng.decode_steps
    assert eng.state()["counters"]["device_sampled_tokens"] \
        == c["device_sampled_tokens"]


def test_greedy_traffic_fetches_a_token_a_slot(families):
    """In all-greedy traffic what the engine reads of a decode iteration is
    the program's int32 arg-max, ``slots x 4`` bytes: the logits are never
    converted to a host array."""
    cfg, params, kw = families["gpt"]
    eng = Engine(params, cfg, max_slots=3, **kw)
    decode, fetched = eng.programs.decode, []

    class Unfetchable:
        """Stands where the logits did: any read of it is a failure."""

        def __array__(self, *a, **k):
            raise AssertionError("the logits were fetched")

    def spy(*args):
        logits, greedy, pools, routed = decode(*args)
        fetched.append(np.asarray(greedy).nbytes)
        return Unfetchable(), greedy, pools, routed

    eng.programs.decode = spy
    reqs = [eng.submit([3, 1, 4, 1, 5], max_new_tokens=6) for _ in range(3)]
    _drain(eng, reqs)
    assert fetched and set(fetched) == {3 * 4}
    assert all(r.status == "ok" and len(r.tokens) == 6 for r in reqs)


# -------------------------------------------------------------- the commit

_FIELDS = ("occ_sum", "occ_steps", "occ_max", "itl_max_s", "_t_last_token",
           "attr_decode_s", "attr_spec_s", "attr_stall_s", "attr_gap_s",
           "_t_attr")


def _slot_at_a_time(decoding, kept, now, decode_dt, prefill_s, spec):
    """What the parent's ``_charge_decode`` + ``_commit_tokens``, called
    once a slot, leave in each request: ``{request id: fields}``."""
    n_active = len(decoding)
    want = {}
    for (_, req), toks in zip(decoding, kept):
        st = {f: getattr(req, f) for f in _FIELDS}
        interval = max(now - st["_t_attr"], 0.0)
        d = min(interval, max(decode_dt, 0.0))
        st["attr_spec_s" if spec else "attr_decode_s"] += d
        s = min(interval - d, max(prefill_s, 0.0))
        st["attr_stall_s"] += s
        st["attr_gap_s"] += interval - d - s
        st["_t_attr"] = now
        st["occ_sum"] += n_active
        st["occ_steps"] += 1
        st["occ_max"] = max(st["occ_max"], n_active)
        if st["_t_last_token"]:
            st["itl_max_s"] = max(st["itl_max_s"], now - st["_t_last_token"])
        st["_t_last_token"] = now
        st["tokens"] = req.tokens + list(toks)
        st["finish"] = None
        if req.eos_token_id is not None and toks[-1] == req.eos_token_id:
            st["finish"] = "eos"
        elif len(st["tokens"]) >= req.max_new_tokens:
            st["finish"] = "length"
        want[req.id] = st
    return want


def _check_commits(eng):
    """Wrap ``eng._commit_tokens``: every call is compared, request by
    request and total by total, with the slot-at-a-time oracle.  Returns
    the list the finishes seen are appended to, an iteration a row."""
    commit, finishes = eng._commit_tokens, []
    tok_hist = eng._m_tok_step
    handed = []
    eng.stream_sink = handed.append

    def checked(decoding, slots, kept, now, decode_dt, prefill_s, spec):
        assert [i for i, _ in decoding] == slots.tolist()
        want = _slot_at_a_time(decoding, kept, now, decode_dt, prefill_s,
                               spec)
        tokens0 = eng.counters["decode_tokens"]
        slot_steps0 = eng.counters["slot_steps"]
        hist0 = tok_hist.stats()
        held0 = {r.id: len(r.tokens) for _, r in decoding}
        del handed[:]               # the first tokens' lines
        commit(decoding, slots, kept, now, decode_dt, prefill_s, spec)
        n_tokens = sum(len(t) for t in kept)
        assert eng.counters["decode_tokens"] - tokens0 == n_tokens
        assert eng.counters["slot_steps"] - slot_steps0 == len(decoding)
        hist = tok_hist.stats()
        assert hist["count"] - hist0["count"] == len(decoding)
        assert hist["sum"] - hist0["sum"] == n_tokens
        for (_, r), toks in zip(decoding, kept):
            assert len(r.tokens) - held0[r.id] == len(toks)
        done, lines, ends = [], [], []
        for (slot, req), toks in zip(decoding, kept):
            st = want[req.id]
            assert req.tokens == st["tokens"]
            finished = st["finish"] is not None
            for f in _FIELDS:
                if finished and f in ("attr_gap_s", "_t_attr"):
                    continue        # _finish closes the ledger past `now`
                assert getattr(req, f) == st[f], (f, req.id)
            assert req._done.is_set() == finished
            if finished:
                assert (req.status, req.finish_reason) == ("ok", st["finish"])
                assert eng._slots[slot] is None
                done.append(st["finish"])
            else:
                assert eng._last_tokens[slot] == toks[-1]
            if req.stream:
                lines.append((req, list(toks), now))    # commit's stamp
                if finished:
                    ends.append((req, None, req.t_done))
        # one hand-over an iteration for every stream's line, then one
        # for the ends of the requests that finished in it
        assert handed == [batch for batch in (lines, ends) if batch]
        finishes.append(done)

    eng._commit_tokens = checked
    return finishes


def test_batched_commit_is_the_slot_at_a_time_commit(families):
    """The one-token path, four slots of two tenants, two of them streams.
    The budget of 64 tokens prefills the first two prompts in one iteration
    and the other two in the next (so a commit holds several attribution
    frontiers), and an EOS and a length finish fall in one iteration."""
    cfg, params, kw = families["afmoe"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (30, 30, 11, 41)]
    # a dry run names the token that is to be request 1's EOS: its first
    # token value from the fourth on that it has not emitted before
    dry = Engine(params, cfg, max_slots=4, **kw)
    probe = dry.submit(prompts[1], max_new_tokens=24)
    _drain(dry, [probe])
    k = next(i for i in range(3, 24)
             if probe.tokens[i] not in probe.tokens[:i])
    eng = Engine(params, cfg, max_slots=4, prefill_budget=64, **kw)
    finishes = _check_commits(eng)
    reqs = [
        eng.submit(prompts[0], max_new_tokens=k + 1, tenant="alpha",
                   stream=True),
        eng.submit(prompts[1], max_new_tokens=24, tenant="beta",
                   eos_token_id=probe.tokens[k], stream=True),
        eng.submit(prompts[2], max_new_tokens=19, tenant="alpha"),
        eng.submit(prompts[3], max_new_tokens=13, tenant="beta"),
    ]
    _drain(eng, reqs)
    assert [r.finish_reason for r in reqs] == ["length", "eos", "length",
                                               "length"]
    assert reqs[1].tokens == probe.tokens[:k + 1]
    assert ["length", "eos"] in [sorted(f, reverse=True) for f in finishes]
    records = eng.step_records()
    assert sum(r["tokens_committed"] for r in records) \
        == eng.counters["decode_tokens"] \
        == sum(len(r.tokens) - 1 for r in reqs)
    assert sum(r["evicted"] for r in records) == 4
    # ... and a first token a request, which no decode step commits
    assert eng.counters["tokens_generated"] \
        == eng.counters["decode_tokens"] + len(reqs) \
        == sum(len(r.tokens) for r in reqs) == (k + 1) * 2 + 19 + 13
    assert eng.kv.allocator.used_blocks == 0


def test_batched_commit_of_bursts_is_the_slot_at_a_time_commit(families):
    """The fused path hands ``_commit_tokens`` several tokens a slot: the
    same oracle, accepted bursts included, and the tokens the fused path
    served before."""
    cfg, params, kw = families["gpt"]
    periodic = ([5, 9, 2, 7] * 5)[:18]
    plain = Engine(params, cfg, max_slots=2, **kw)
    want = [plain.submit(p, max_new_tokens=12)
            for p in (periodic, periodic[2:])]
    _drain(plain, want)
    eng = Engine(params, cfg, max_slots=2, fused_sampling=True, speculate=4,
                 **kw)
    _check_commits(eng)
    reqs = [eng.submit(periodic, max_new_tokens=12, tenant="alpha",
                       stream=True),
            eng.submit(periodic[2:], max_new_tokens=12, tenant="beta")]
    _drain(eng, reqs)
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    assert eng.counters["spec_accepted"] > 0      # bursts were committed
    assert eng.counters["decode_tokens"] > eng.counters["slot_steps"]
    assert eng.counters["host_sample_rounds"] == 0
    assert eng.counters["logit_fetches"] == 0
    decoded = [r for r in eng.step_records() if r["occupancy"]]
    assert all(r["device_sampled"] == r["occupancy"]
               and r["logits_fetched"] == 0 for r in decoded)


def test_one_hand_over_an_iteration_after_the_bookkeeping(families):
    """The streams' lines are handed over in one call an iteration, once
    every request of the batch is up to date: whoever the call wakes sees
    the iteration whole."""
    cfg, params, kw = families["gpt"]
    eng = Engine(params, cfg, max_slots=3, **kw)
    reqs = [eng.submit([2, 7, 1, 8], max_new_tokens=5, stream=True)
            for _ in range(3)]
    seen = []
    eng.stream_sink = lambda batch: seen.append(
        ([len(r.tokens) for r in reqs], batch))
    _drain(eng, reqs)
    # 3 first tokens (one request at a time, in prefill), then 4 decode
    # iterations of one call each: all three requests already hold the
    # iteration's token when its lines are handed over; the ends follow
    # the last iteration's lines in a call of their own
    assert len(seen) == 3 + 4 + 1
    for i, (lens, batch) in enumerate(seen[:3]):
        assert batch == [(reqs[i], [reqs[i].tokens[0]],
                          reqs[i].t_first_token)]
    for i, (lens, batch) in enumerate(seen[3:7]):
        assert lens == [2 + i] * 3
        assert [(r, t) for r, t, _ in batch] \
            == [(r, [r.tokens[1 + i]]) for r in reqs]
        assert len({stamp for _, _, stamp in batch}) == 1
    assert seen[7] == ([5] * 3, [(r, None, r.t_done) for r in reqs])


def test_lines_without_a_sink_are_dropped(families):
    """``stream=True`` with nobody to read the lines: the requests run to
    their end and nothing is kept for a reader that never comes."""
    cfg, params, kw = families["gpt"]
    eng = Engine(params, cfg, max_slots=2, **kw)
    reqs = [eng.submit([2, 7, 1, 8], max_new_tokens=4, stream=True)
            for _ in range(2)]
    _drain(eng, reqs)
    assert [len(r.tokens) for r in reqs] == [4, 4]
    assert eng._stream_out == []


# ---------------------------------------------------------------- the cache


def _note_written_one_slot(g, slot, tokens):
    """The parent's ``WindowKVGroup.note_written``, for one slot."""
    assert tokens <= g.pages[slot].capacity_tokens
    g.seq_lens[slot] = tokens
    row = g.block_tables[slot]
    keep_from = max(tokens - g.window + 1, 0) // g.block_size
    for li in range(int(g._first[slot]), min(keep_from, int(g._next[slot]))):
        g._stock[slot].append(int(row[li]))
        row[li] = g.scratch_block
        g.blocks_recycled += 1
        g.tables_version += 1
    g._first[slot] = max(g._first[slot], keep_from)
    g._next[slot] = max(g._next[slot], g._first[slot])


def test_note_written_for_a_batch_is_note_written_slot_by_slot():
    """Five slots decode past the window together; their lengths are
    staggered so that in some iterations several cross a block edge at
    once, in some one, in some none."""
    cfg = afmoe.afmoe_tiny(dtype=jnp.float32)        # window 32
    kw = dict(max_slots=5, block_size=4, max_context=128, write_ahead=8,
              num_blocks={"full": None, "window": None})
    batch = make_grouped_cache(cfg, **kw)
    single = make_grouped_cache(cfg, **kw)
    starts = np.array([33, 33, 37, 34, 41])
    slots = np.arange(5)
    for kv in (batch, single):
        for slot, n in enumerate(starts.tolist()):
            assert kv.admit(slot, 128) is not None
            kv.prepare_write(slot, n)
    batch.note_written(slots, starts)
    for slot, n in enumerate(starts.tolist()):
        for g in single.groups.values():
            if hasattr(g, "window"):
                _note_written_one_slot(g, slot, n)
        single.seq_lens[slot] = n
    crossed_together = set()
    for step in range(1, 40):
        for kv in (batch, single):
            for slot in range(5):
                kv.prepare_write(slot, int(kv.seq_lens[slot]) + 1)
        before = batch.blocks_recycled
        batch.note_written(slots, batch.seq_lens[slots] + 1)
        crossed_together.add(batch.blocks_recycled - before)
        for slot in range(5):
            n = int(single.seq_lens[slot]) + 1
            for g in single.groups.values():
                if hasattr(g, "window"):
                    _note_written_one_slot(g, slot, n)
            single.seq_lens[slot] = n
        assert batch.blocks_recycled == single.blocks_recycled
        assert batch.tables_version == single.tables_version
        for name, g in batch.groups.items():
            o = single.groups[name]
            np.testing.assert_array_equal(g.block_tables, o.block_tables)
            np.testing.assert_array_equal(g.seq_lens, o.seq_lens)
            if hasattr(g, "window"):
                assert g._stock == o._stock
                np.testing.assert_array_equal(g._first, o._first)
                np.testing.assert_array_equal(g._next, o._next)
    assert {0, 1, 4} <= crossed_together and batch.blocks_recycled > 20
    # the bound still trips, and says which slot
    with pytest.raises(Exception, match="slot 3: 129 tokens exceed"):
        batch.note_written(np.array([1, 3]), np.array([80, 129]))


# ------------------------------------------------------------- the step log


@pytest.mark.parametrize("mangle,complaint", [
    (None, None),
    ({"device_sampled": 99}, "'device_sampled' 99"),
    ({"logits_fetched": 2}, "'logits_fetched' 2"),
])
def test_schema_checker_holds_the_new_step_fields(families, tmp_path, mangle,
                                                  complaint):
    """A run's ``steps.jsonl`` passes ``tools/check_metrics_schema.py``
    with the two fields; more slots sampled than decoded, or a fetch flag
    that is no flag, does not; a log from before the fields still does."""
    cfg, params, kw = families["gpt"]
    eng = Engine(params, cfg, max_slots=2, logdir=str(tmp_path), **kw)
    reqs = [eng.submit([1, 2, 3], max_new_tokens=7),
            eng.submit([4, 5, 6, 7], max_new_tokens=3, temperature=0.7)]
    _drain(eng, reqs)
    eng.stop()
    path = os.path.join(str(tmp_path), "steps.jsonl")
    rows = [json.loads(line) for line in open(path)]
    assert {r["logits_fetched"] for r in rows} == {0, 1}
    decode = next(i for i, r in enumerate(rows) if r["occupancy"])
    if mangle:
        rows[decode].update(mangle)
    else:       # and the older form, without the fields
        for key in ("device_sampled", "logits_fetched"):
            del rows[-1][key]
    with open(path, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    errors, _ = check_metrics_schema.check_steps_file(path)
    if complaint is None:
        assert errors == []
    else:
        assert len(errors) == 1 and complaint in errors[0]
