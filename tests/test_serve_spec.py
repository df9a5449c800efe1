"""Decode fast path tests (ISSUE 15): fused on-device sampling +
self-speculative decoding over the paged KV pool.

The load-bearing checks, in the same equivalence-not-plausibility spirit
as test_serve.py:

- **greedy parity**: the fused program (sampling inside the dispatch)
  and the speculative program (drafts verified in one multi-token pass)
  emit token-for-token what the dense ``models.generate`` scan emits —
  with ``--prefix-cache`` and ``--prefill-budget`` composed on top, and
  at the production bf16 dtype;
- **exactness of rejection sampling**: the emitted distribution of
  ``sample_burst`` under a deterministic draft proposal IS the target
  model's distribution (chi-square-level frequency comparison), whether
  the draft is likely, unlikely, or absent;
- **KV discipline**: a speculative burst never writes a shared
  (refcount > 1) prefix block, EOS-mid-burst retreats the committed
  extent (``rollback``) and never into the mapped prefix, and nothing
  leaks.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import GPTLM, generate, gpt_tiny
from distributedtensorflow_tpu.ops.attention import KVRows
from distributedtensorflow_tpu.serve import Engine, OutOfBlocksError
from distributedtensorflow_tpu.serve import draft as spec_draft
from distributedtensorflow_tpu.serve import sampling
from distributedtensorflow_tpu.serve.kv_cache import PagedKVCache

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


# ------------------------------------------------------------ n-gram drafter


def test_propose_periodic_continuation():
    h = [1, 2, 3, 4] * 4
    assert spec_draft.propose(h, 4) == [1, 2, 3, 4]
    assert spec_draft.propose(h, 2) == [1, 2]


def test_propose_prefers_most_recent_match():
    # suffix (7, 8) occurs twice; the later occurrence continues with 5,
    # the earlier with 9 — locality prefers 5.
    h = [7, 8, 9, 0, 7, 8, 5, 1, 7, 8]
    assert spec_draft.propose(h, 1) == [5]


def test_propose_no_match_and_degenerate():
    assert spec_draft.propose([1, 2, 3, 4, 5, 6], 4) == []
    assert spec_draft.propose([1], 4) == []
    assert spec_draft.propose([], 4) == []
    assert spec_draft.propose([1, 2, 3], 0) == []


def test_propose_falls_back_to_shorter_ngram():
    # no 3-gram or 2-gram repeats, but token 5 repeats: 1-gram fallback
    # proposes its continuation.
    h = [5, 9, 1, 2, 5, 7]
    assert spec_draft.propose(h[:-1], 1) == [9]


# ---------------------------------------------- multi-token paged attention


@pytest.mark.parametrize("h,h_kv", [(4, 4), (4, 2)])
def test_paged_verify_attention_matches_dense(h, h_kv):
    """T>1 gather-through-page-table attention == plain masked attention
    per query position, incl. GQA grouping and the in-window causal
    rule (query t sees attend_lens + t positions)."""
    from distributedtensorflow_tpu.ops.attention import (
        paged_verify_attention,
    )

    b, t, d, bs, max_blocks = 2, 3, 8, 4, 4
    rng = np.random.default_rng(0)
    cap = max_blocks * bs
    k_seq = rng.standard_normal((b, cap, h_kv, d)).astype(np.float32)
    v_seq = rng.standard_normal((b, cap, h_kv, d)).astype(np.float32)
    q = rng.standard_normal((b, t, h, d)).astype(np.float32)
    attend_lens = np.array([5, 9], np.int32)

    # scatter the contiguous K/V into a shuffled pool through per-slot
    # tables (the same wiring idiom as the T=1 test)
    perm = rng.permutation(b * max_blocks)
    pool_k = np.zeros((b * max_blocks + 1, bs, h_kv, d), np.float32)
    pool_v = np.zeros_like(pool_k)
    tables = np.zeros((b, max_blocks), np.int32)
    for i in range(b):
        for j in range(max_blocks):
            blk = perm[i * max_blocks + j]
            tables[i, j] = blk
            pool_k[blk] = k_seq[i, j * bs:(j + 1) * bs]
            pool_v[blk] = v_seq[i, j * bs:(j + 1) * bs]

    # the pool's stored form (serve.kv_cache): token rows of all heads,
    # layers stacked; layer 0 is noise that attention must not read
    def stored(pool):
        rows = pool.reshape(-1, h_kv * d)
        return jnp.asarray(np.stack(
            [rng.standard_normal(rows.shape).astype(np.float32), rows]))

    out = np.asarray(paged_verify_attention(
        jnp.asarray(q), stored(pool_k), stored(pool_v),
        jnp.asarray(tables), jnp.asarray(attend_lens), layer=1,
        block_size=bs,
    ))
    assert out.shape == (b, t, h, d)
    g = h // h_kv
    for i in range(b):
        for tt in range(t):
            n = attend_lens[i] + tt
            for head in range(h):
                kh = k_seq[i, :n, head // g]      # (n, d)
                vh = v_seq[i, :n, head // g]
                s = kh @ q[i, tt, head] / np.sqrt(d)
                w = np.exp(s - s.max())
                w /= w.sum()
                np.testing.assert_allclose(
                    out[i, tt, head], w @ vh, rtol=1e-5, atol=1e-5
                )


# ----------------------------------------------------- sampling reference


def test_logits_to_probs_reference_np_jnp_agree():
    rng = np.random.default_rng(3)
    logits = rng.standard_normal((4, 32)).astype(np.float32)
    temp = np.array([0.7, 1.3, 0.0, 2.0], np.float32)
    topk = np.array([5, 0, 3, 32], np.int32)
    p_np = sampling.logits_to_probs(logits, temp, topk, xp=np)
    p_j = np.asarray(sampling.logits_to_probs(
        jnp.asarray(logits), jnp.asarray(temp), jnp.asarray(topk), xp=jnp))
    np.testing.assert_allclose(p_np, p_j, rtol=1e-6, atol=1e-7)
    # greedy row is an exact one-hot of the argmax
    assert p_np[2].max() == 1.0 and p_np[2].sum() == 1.0
    assert p_np[2].argmax() == logits[2].argmax()
    # top-k row keeps exactly k nonzeros
    assert (p_np[0] > 0).sum() == 5
    np.testing.assert_allclose(p_np.sum(-1), 1.0, rtol=1e-6)


def test_host_fallback_sampler_uses_fp32_reference(served_model):
    """The numpy fallback draws from exactly the shared-reference
    probabilities (no float64 re-derivation drift)."""
    cfg, params, ids = served_model
    eng = _engine(cfg, params)
    req = eng.submit([1, 2, 3], max_new_tokens=1, temperature=0.8,
                     top_k=7, seed=5)
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((cfg.vocab_size,)).astype(np.float32)
    got = eng._sample(req, logits)
    probs = sampling.logits_to_probs(
        logits, 0.8, 7, xp=np).astype(np.float64)
    want = int(np.random.default_rng(5).choice(
        len(probs), p=probs / probs.sum()))
    assert got == want


def test_rejection_sampler_distribution_is_exact():
    """Speculative verification must emit EXACTLY the target
    distribution: for a fixed logits row and a deterministic draft
    (likely, unlikely, or absent), the first emitted token's frequencies
    match softmax(logits) — the standard speculative-sampling
    correctness property, measured over many keys."""
    v = 8
    rng = np.random.default_rng(1)
    logits_row = rng.standard_normal((v,)).astype(np.float32) * 1.5
    target = sampling.logits_to_probs(logits_row, 1.0, 0, xp=np)
    n = 4000

    @jax.jit
    def run(keys, draft_tok, draft_len):
        def one(key):
            # T=2: position 0 verifies the draft (logits fixed), the
            # draft column carries draft_tok.  Only the first emitted
            # token is distribution-checked (position 1's logits would
            # come from the model in real serving).
            logits = jnp.broadcast_to(
                jnp.asarray(logits_row), (1, 2, v))
            tokens = jnp.array([[0, draft_tok]], jnp.int32)
            out, n_emit, _ = sampling.sample_burst(
                logits, tokens, jnp.full((1,), draft_len, jnp.int32),
                key[None], jnp.zeros((1,), jnp.int32),
                jnp.ones((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
                jnp.ones((1,), bool),
            )
            return out[0, 0], n_emit[0]
        return jax.vmap(one)(keys)

    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
    likely = int(np.argmax(target))
    unlikely = int(np.argmin(target))
    for draft_tok, draft_len in ((likely, 1), (unlikely, 1), (0, 0)):
        toks, n_emit = run(keys, draft_tok, draft_len)
        toks = np.asarray(toks)
        freq = np.bincount(toks, minlength=v) / n
        # ~3 sigma on the largest bins at n=4000 is ~0.025
        np.testing.assert_allclose(freq, target, atol=0.04)
        if draft_len:
            # acceptance frequency must equal the draft's target mass
            acc = (np.asarray(n_emit) == 2).mean()
            np.testing.assert_allclose(acc, target[draft_tok], atol=0.04)


# ------------------------------------------------------------ engine parity


@pytest.fixture(scope="module")
def served_model():
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, max_seq=64)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size)
    params = GPTLM(cfg).init(rng, ids)["params"]
    return cfg, params, ids


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_queue", 8)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("max_context", 64)
    return Engine(params, cfg, **kw)


def _drain(engine, reqs, max_steps=500):
    for _ in range(max_steps):
        if all(r._done.is_set() for r in reqs):
            return
        engine.step()
    raise AssertionError("engine did not finish within max_steps")


_PERIODIC = ([5, 9, 2, 7] * 5)[:18]


def test_fused_greedy_matches_dense(served_model):
    cfg, params, ids = served_model
    dense = np.asarray(generate(params, ids, cfg=cfg, max_new_tokens=6))
    eng = _engine(cfg, params, fused_sampling=True)
    reqs = [
        eng.submit([int(t) for t in np.asarray(ids)[i]], max_new_tokens=6)
        for i in range(2)
    ]
    _drain(eng, reqs)
    for i, r in enumerate(reqs):
        assert r.status == "ok"
        assert r.tokens == list(dense[i, 8:])
    # the fast-path accounting: one dispatch per step, zero host rounds
    assert eng.counters["host_sample_rounds"] == 0
    assert eng.counters["decode_dispatches"] == eng.decode_steps


def test_spec_greedy_matches_dense_with_all_flags(served_model):
    """The acceptance-criteria configuration: --fused-sampling
    --speculate 4 --prefix-cache --prefill-budget all enabled, output
    token-for-token identical to dense generate."""
    cfg, params, _ = served_model
    prompt = _PERIODIC
    dense = np.asarray(generate(
        params, jnp.asarray([prompt], jnp.int32), cfg=cfg,
        max_new_tokens=12))
    eng = _engine(cfg, params, fused_sampling=True, speculate=4,
                  prefix_cache=True, prefill_budget=8)
    r = eng.submit(prompt, max_new_tokens=12)
    _drain(eng, [r])
    assert r.status == "ok"
    assert r.tokens == list(dense[0, 18:])
    assert r.drafted > 0                      # the drafter actually fired
    assert 0 <= r.accepted <= r.drafted
    assert eng.counters["spec_drafted"] == r.drafted
    # a second identical prompt maps the cached prefix AND stays exact
    r2 = eng.submit(prompt, max_new_tokens=12)
    _drain(eng, [r2])
    assert r2.tokens == r.tokens
    assert r2.cached_prefix_tokens > 0
    # speculation never wrote into a shared prefix block
    assert eng.kv.cow_copies == 0
    # no slot/block leak
    assert eng.kv.allocator.used_blocks == 0


@pytest.mark.parametrize("speculate", [0, 2, 4])
def test_fused_greedy_matches_dense_bf16(speculate):
    """Same equivalence at the PRODUCTION dtype (gpt_tiny default
    bf16): the fused/verify program's dtype recipe must track
    models/gpt.py exactly."""
    cfg = dataclasses.replace(gpt_tiny(), max_seq=64)
    rng = jax.random.PRNGKey(0)
    prompt = _PERIODIC[:12]
    ids = jnp.asarray([prompt], jnp.int32)
    params = GPTLM(cfg).init(rng, ids)["params"]
    dense = np.asarray(generate(params, ids, cfg=cfg, max_new_tokens=5))
    eng = _engine(cfg, params, fused_sampling=True, speculate=speculate)
    req = eng.submit(prompt, max_new_tokens=5)
    _drain(eng, [req])
    assert req.tokens == list(dense[0, 12:])


def test_fused_seeded_deterministic_by_seed(served_model):
    cfg, params, _ = served_model
    eng = _engine(cfg, params, fused_sampling=True, speculate=4)
    kw = dict(max_new_tokens=8, temperature=1.0, top_k=16)
    a = eng.submit(_PERIODIC, seed=1, **kw)
    b = eng.submit(_PERIODIC, seed=1, **kw)
    c = eng.submit(_PERIODIC, seed=2, **kw)
    _drain(eng, [a, b, c])
    assert a.tokens == b.tokens
    assert a.tokens != c.tokens


def test_spec_burst_respects_max_new_tokens(served_model):
    """An accepted burst can never overshoot max_new_tokens: the draft
    window is capped at remaining - 1."""
    cfg, params, _ = served_model
    eng = _engine(cfg, params, fused_sampling=True, speculate=4)
    for n in (2, 3, 5):
        r = eng.submit(_PERIODIC, max_new_tokens=n)
        _drain(eng, [r])
        assert r.status == "ok"
        assert len(r.tokens) == n
        assert r.finish_reason in ("length", "eos")


def test_spec_eos_mid_burst_truncates_and_rolls_back(served_model):
    """An EOS landing inside an accepted burst truncates the emitted
    tokens there (nothing after the EOS ever happened) and the request
    finishes with reason eos; blocks drain fully."""
    cfg, params, _ = served_model
    # find a greedy continuation first, then declare one of its LATER
    # tokens the EOS: the speculative run must stop exactly there.
    probe = _engine(cfg, params, fused_sampling=True, speculate=4)
    r0 = probe.submit(_PERIODIC, max_new_tokens=12)
    _drain(probe, [r0])
    # pick a token that appears at index >= 2 (so a burst can straddle)
    eos = None
    for i, t in enumerate(r0.tokens):
        if i >= 2:
            eos = int(t)
            break
    want = r0.tokens[: r0.tokens.index(eos) + 1]
    eng = _engine(cfg, params, fused_sampling=True, speculate=4)
    r = eng.submit(_PERIODIC, max_new_tokens=12, eos_token_id=eos)
    _drain(eng, [r])
    assert r.status == "ok" and r.finish_reason == "eos"
    assert r.tokens == want
    assert r.tokens[-1] == eos
    assert eng.kv.allocator.used_blocks == 0
    assert eng.kv.allocator.free_blocks \
        + eng.kv.allocator.cached_blocks == eng.kv.allocator.num_blocks


def test_speculate_requires_fused_sampling(served_model):
    cfg, params, _ = served_model
    with pytest.raises(ValueError, match="fused_sampling"):
        _engine(cfg, params, speculate=2)
    with pytest.raises(ValueError, match="speculate"):
        _engine(cfg, params, fused_sampling=True, speculate=-1)


# ------------------------------------------------------- KV rollback rules


def _kv(num_blocks=8, block_size=4, max_context=32, max_slots=2):
    return PagedKVCache(
        num_layers=1, rows=KVRows(heads=2, kv_heads=2, head_dim=4),
        max_slots=max_slots,
        num_blocks=num_blocks, block_size=block_size,
        max_context=max_context,
    )


def test_kv_rollback_retreats_and_guards():
    kv = _kv()
    kv.admit(0, tokens=12)
    kv.note_written(0, 11)
    kv.rollback(0, 9)
    assert int(kv.seq_lens[0]) == 9
    kv.rollback(0, 9)  # empty retreat is a no-op
    with pytest.raises(OutOfBlocksError, match="only retreats"):
        kv.rollback(0, 10)
    kv.release(0)
    with pytest.raises(OutOfBlocksError, match="no pages"):
        kv.rollback(0, 0)


def test_kv_rollback_never_crosses_shared_or_prefix_blocks():
    """The prefix-cache composition rule: a rollback can neither retreat
    into the mapped shared prefix nor cross a refcount>1 block."""
    kv = _kv(num_blocks=8, block_size=4, max_context=32)
    prompt = list(range(9))  # 2 full blocks + 1 token
    kv.admit(0, tokens=12, prompt=prompt)
    kv.note_written(0, 9)
    kv.register_prefix(0, prompt)
    # second slot maps the 2-block prefix shared
    pages1 = kv.admit(1, tokens=12, prompt=prompt)
    assert pages1.prefix_tokens == 8
    kv.note_written(1, 10)
    with pytest.raises(OutOfBlocksError, match="shared prefix"):
        kv.rollback(1, 7)   # inside the mapped prefix
    kv.rollback(1, 9)       # past the prefix: fine
    assert int(kv.seq_lens[1]) == 9
    # force the inconsistent-scheduler case: a shared block inside the
    # retreat window must refuse loudly instead of corrupting accounting
    shared_block = pages1.blocks[0]
    assert kv.allocator.refcount(shared_block) == 2
    pages1.prefix_tokens = 0  # simulate corrupted bookkeeping
    with pytest.raises(OutOfBlocksError, match="shared block"):
        kv.rollback(1, 2)


def test_kv_ensure_writable_range_covers_every_block():
    kv = _kv(num_blocks=8, block_size=4, max_context=32)
    prompt = list(range(9))  # 2 full blocks + 1 token
    kv.admit(0, tokens=12, prompt=prompt)
    kv.note_written(0, 9)
    kv.register_prefix(0, prompt)
    pages1 = kv.admit(1, tokens=12, prompt=prompt)
    assert pages1.prefix_tokens == 8  # blocks 0 and 1 mapped shared
    # a write range [4, 10) spans blocks 1 (shared -> CoW) and 2
    # (already exclusive -> untouched)
    fixed = kv.ensure_writable_range(1, 4, 10)
    assert fixed == 1 and kv.cow_copies == 1
    assert kv.allocator.refcount(pages1.blocks[1]) == 1
    assert kv.ensure_writable_range(1, 4, 4) == 0  # empty range


# ------------------------------------------------ logs / schema / report


def test_spec_logs_pass_schema_and_run_report(served_model, tmp_path):
    import check_metrics_schema as checker
    import run_report

    cfg, params, _ = served_model
    logdir = str(tmp_path / "serve")
    from distributedtensorflow_tpu.obs.registry import Registry
    eng = _engine(cfg, params, fused_sampling=True, speculate=4,
                  prefix_cache=True, logdir=logdir, log_every=1,
                  registry=Registry())
    reqs = [eng.submit(_PERIODIC, max_new_tokens=10, seed=i)
            for i in range(3)]
    _drain(eng, reqs)
    eng.stop()
    assert eng.counters["spec_drafted"] > 0

    # requests.jsonl: drafted/accepted rows, schema-clean
    errs, _ = checker.check_requests_file(
        os.path.join(logdir, "requests.jsonl"))
    assert errs == [], errs
    rows = [json.loads(l) for l in
            open(os.path.join(logdir, "requests.jsonl"))]
    ok = [r for r in rows if r["status"] == "ok"]
    assert all("drafted" in r and "accepted" in r for r in ok)
    assert sum(r["drafted"] for r in ok) == eng.counters["spec_drafted"]

    # metrics.jsonl rows + metrics.prom gates
    errs, _ = checker.check_file(os.path.join(logdir, "metrics.jsonl"))
    assert errs == [], errs
    errs, _ = checker.check_prom_file(os.path.join(logdir, "metrics.prom"))
    assert errs == [], errs
    prom = open(os.path.join(logdir, "metrics.prom")).read()
    assert "serve_spec_drafted_total" in prom
    assert "serve_spec_accepted_total" in prom
    assert "serve_decode_tokens_per_step_bucket" in prom

    # run_report serving section grows the fast-path digest
    report = run_report.build_report(logdir)
    fp = report["serving"]["decode_fast_path"]
    assert fp["speculate"] == 4 and fp["drafted"] > 0
    assert 0.0 <= fp["acceptance_rate"] <= 1.0
    assert fp["tokens_per_step"] >= 1.0
    assert fp["dispatches_per_step"] == pytest.approx(1.0)
    text = run_report.render(report)
    assert "decode fast path" in text


def test_schema_checker_rejects_accepted_above_drafted(tmp_path):
    import check_metrics_schema as checker

    req = tmp_path / "requests.jsonl"
    req.write_text(json.dumps({
        "t": 1.0, "id": "r0", "status": "ok", "prompt_tokens": 4,
        "new_tokens": 2, "finish_reason": "length", "ttft_s": 0.1,
        "tpot_s": 0.1, "e2e_s": 0.2, "queue_s": 0.0, "slot": 0,
        "occ_mean": 1.0, "occ_max": 1, "drafted": 2, "accepted": 3,
    }) + "\n")
    errs, _ = checker.check_requests_file(str(req))
    assert any("exceeds" in e for e in errs)

    met = tmp_path / "metrics.jsonl"
    met.write_text(json.dumps({
        "step": 1, "spec_drafted_total": 1, "spec_accepted_total": 2,
    }) + "\n")
    errs, _ = checker.check_file(str(met))
    assert any("spec_accepted_total" in e for e in errs)

    prom = tmp_path / "metrics.prom"
    prom.write_text(
        "serve_spec_drafted_total 1\nserve_spec_accepted_total 2\n")
    errs, _ = checker.check_prom_file(str(prom))
    assert any("exceeds" in e for e in errs)
    prom.write_text('serve_spec_drafted_total{slot="0"} 1\n')
    errs, _ = checker.check_prom_file(str(prom))
    assert any("unlabeled" in e for e in errs)


def test_engine_state_reports_fast_path(served_model):
    cfg, params, _ = served_model
    eng = _engine(cfg, params, fused_sampling=True, speculate=3)
    r = eng.submit(_PERIODIC, max_new_tokens=6)
    _drain(eng, [r])
    st = eng.state()
    assert st["fused_sampling"] is True and st["speculate"] == 3
    assert st["tokens_per_step"] >= 1.0
    assert 0.0 <= st["spec_acceptance_rate"] <= 1.0
    json.dumps(st)  # JSON-safe
