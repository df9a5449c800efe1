"""A slot nobody holds attends nothing: the rule of ``serve.model``'s decode
programs (``_attend_lens``: a length of 0 for an inactive slot) through every
row form's ``decode`` — K/V rows through the kernel ``paged_attn``
(interpreted: plain, window, ``lo``, ``with_lse``, sink, heads of 64 / 128 /
256 and mimo's 192 over 128) and the plain gather, latent rows through
``paged_latent_attn``, latent rows under an indexer, the two-pool form of an
EVA layer and the verify form — and through the engine of a family with a
state, experts or a loop, and of the sampled / verify programs.

What is held: an empty slot's row is zeros (finite: the block's norms, the
routed counters and the exit mass meet no NaN) although every row of the pool
it could have read — the scratch block's among them — holds NaN; the log of
its denominator stays under ``NEG_INF``; and every live slot's row is the same
call's with the empty slots given the length the programs gave them before
(1, a row of the scratch block), **bit for bit** — whatever the empty slots'
walks do to the buffers' parity.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import gpt, jamba, lfm2, ouro
from distributedtensorflow_tpu.ops import attention
from distributedtensorflow_tpu.serve import model as serve_model
from distributedtensorflow_tpu.serve.engine import Engine

BS = 16
#: rows the slots attend: the live walks pass three trips of the kernel they
#: go through (``trips`` stretches and a bit); empty slots first, in the
#: middle, two in a row and last
EMPTY = np.array([1, 0, 1, 1, 0, 0, 0, 1], bool)


def _lens(stretch: int) -> np.ndarray:
    live = [3 * stretch + 37, 3 * stretch + 6, 5, 2 * stretch + 1]
    lens = np.zeros(len(EMPTY), np.int64)
    lens[~EMPTY] = live
    return lens


def _tables(rng, lens, first=None):
    """Scattered page tables for slots attending rows ``[first, lens)``, the
    pool's blocks poisoned — ``(tables, poison of a row, blocks)`` — but for
    the blocks a live slot attends and block 0, which every column that is
    not mapped names: an empty slot given the old length of 1 reads its row
    0, so it must be finite *there*; the scratch block, last, is poisoned."""
    first = np.zeros_like(lens) if first is None else first
    held = [range(a // BS, -(-n // BS)) for a, n in zip(first, lens)]
    cols = max(r.stop for r in held) + 2
    nb = sum(len(r) for r in held) + 4
    tables = np.zeros((len(lens), cols), np.int32)
    perm, o = 1 + rng.permutation(nb - 1), 0
    poison = np.ones(nb + 1, bool)
    poison[0] = False
    for i, r in enumerate(held):
        tables[i, r.start:r.stop] = perm[o:o + len(r)]
        poison[tables[i, r.start:r.stop]] = False
        o += len(r)
    return tables, np.repeat(poison, BS), nb


def _kv_case(rng, lens, *, heads, kv_heads, d, dv=None, window=None,
             lo=None):
    dv = dv or d
    first = (np.asarray(lo) if lo is not None else np.zeros_like(lens)
             if window is None else np.maximum(lens - window, 0))
    tables, bad, nb = _tables(rng, lens, first)
    k = rng.standard_normal((2, (nb + 1) * BS, kv_heads, d))
    v = rng.standard_normal((2, (nb + 1) * BS, kv_heads, dv))
    k[:, bad] = v[:, bad] = np.nan
    pools = (jax.vmap(attention.lay_heads)(jnp.asarray(k, jnp.float32)),
             jnp.asarray(v.reshape(2, -1, kv_heads * dv), jnp.float32))
    q = jnp.asarray(rng.standard_normal((len(lens), heads, d)), jnp.float32)
    return q, pools, jnp.asarray(tables)


def _kv_rows(impl, *, heads=4, kv_heads=2, d=128, dv=None, window=None,
             lo=False, sink=False, with_lse=False):
    """``KVRows.decode`` at these options: ``decode(lens) -> (out, lse)``."""
    rng = np.random.default_rng(7)
    lens = _lens(attention.PAGED_STRETCH)
    first = None
    if lo:      # a tumbling window's start, anywhere in its first stretch
        first = np.where(EMPTY, 0, np.minimum(lens - 1, 150))
    form = attention.KVRows(heads, kv_heads, d, dv)
    assert form.decode_formulation(BS, impl) == (
        "paged_attn" if impl == "pallas" else "plain")
    q, pools, tables = _kv_case(rng, lens, heads=heads, kv_heads=kv_heads,
                                d=d, dv=dv, window=window, lo=first)
    if impl == "xla":       # the gather reads every column: finite rows
        pools = tuple(jnp.nan_to_num(p) for p in pools)
    kw = dict(layer=1, block_size=BS, window=window, impl=impl)
    if sink:
        kw["sink"] = jnp.asarray(rng.standard_normal(heads) + 2.0,
                                 jnp.float32)
    if lo:
        kw["lo"] = jnp.asarray(first, jnp.int32)
    if with_lse:
        kw["with_lse"] = True

    def decode(lens):
        out = form.decode(q, pools, tables, lens, **kw)
        return out if with_lse else (out, None)

    return decode, lens


def _verify_rows():
    """``KVRows.verify``, three queries a slot: an empty slot's first query
    attends nothing (its later ones attend its own drafts' rows, which the
    programs wrote to the scratch block: not compared)."""
    rng = np.random.default_rng(11)
    lens = _lens(attention.PAGED_STRETCH)
    form = attention.KVRows(4, 2, 64)
    q, pools, tables = _kv_case(rng, lens + 2, heads=4, kv_heads=2, d=64)
    # the gather multiplies a masked row's zero weight with its values:
    # give it finite rows
    pools = tuple(jnp.nan_to_num(p) for p in pools)
    q = jnp.stack([q, q * 0.5, q * 0.25], axis=1)

    def decode(lens):
        out = form.verify(q, pools, tables, lens, layer=1, block_size=BS)
        return out[:, 0], None

    return decode, lens


def _latent_case(rng, lens, *, heads, rank, rope, nope, v, index_dim=None):
    tables, bad, nb = _tables(rng, lens)
    width = -(-(rank + rope) // 128) * 128
    pool = np.zeros((2, (nb + 1) * BS, width))
    pool[..., :rank + rope] = rng.standard_normal(
        (2, (nb + 1) * BS, rank + rope))
    pool[:, bad] = np.nan
    pools = [jnp.asarray(pool, jnp.float32)]
    if index_dim:
        keys = rng.standard_normal((2, (nb + 1) * BS, index_dim))
        keys[:, bad] = np.nan
        pools.append(jnp.asarray(keys, jnp.float32))
    f32 = lambda *shape: jnp.asarray(                         # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    b = len(lens)
    return dict(
        q=(f32(b, heads, nope), f32(b, heads, rope)), pools=tuple(pools),
        tables=jnp.asarray(tables),
        weights=dict(w_uk=f32(rank, heads, nope) * nope ** -0.5,
                     w_uv=f32(rank, heads, v) * rank ** -0.5))


def _latent_rows(impl):
    """``LatentRows.decode``: joyai's head shape in small."""
    rng = np.random.default_rng(13)
    lens = _lens(attention.PAGED_LATENT_STRETCH)
    form = attention.LatentRows(rank=128, rope_dim=64, scale=0.1)
    assert form.decode_formulation(BS, impl) == (
        "paged_latent_attn" if impl == "pallas" else "plain")
    case = _latent_case(rng, lens, heads=8, rank=128, rope=64, nope=32, v=32)
    pools = case["pools"]
    if impl == "xla":       # the gather reads every column: finite rows
        pools = tuple(jnp.nan_to_num(p) for p in pools)

    def decode(lens):
        return form.decode(case["q"], pools, case["tables"], lens, layer=1,
                           block_size=BS, impl=impl, **case["weights"]), None

    return decode, lens


def _sparse_rows(impl):
    """``SparseLatentRows.decode``: the live slots hold more rows than the
    indexer selects; counts of 0 go through ``index_scores``, the selection
    and the gather.  (The indexer reads every table column whatever a slot
    holds, and the gather rows a query did not select: finite pools.)"""
    rng = np.random.default_rng(17)
    lens = _lens(128)
    form = attention.SparseLatentRows(rank=128, rope_dim=64, scale=0.1,
                                      index_dim=128, topk=128)
    assert form.decode_formulation(BS, impl) == (
        "sparse_latent_attn" if impl == "pallas" else "plain")
    case = _latent_case(rng, lens, heads=8, rank=128, rope=64, nope=32, v=32,
                        index_dim=128)
    b, cols = case["tables"].shape
    # the index kernel wants a context of whole stretches
    tables = jnp.pad(case["tables"], (
        (0, 0), (0, -cols % (attention.INDEX_STRETCH // BS))))
    q = (*case["q"], jnp.asarray(rng.standard_normal((b, 4, 128)),
                                 jnp.float32),
         jnp.asarray(rng.random((b, 4)), jnp.float32))
    pools = tuple(jnp.nan_to_num(p) for p in case["pools"])

    def decode(lens):
        return form.decode(q, pools, tables, lens, layer=1, block_size=BS,
                           impl=impl, **case["weights"]), None

    return decode, lens


def _two_pools(impl):
    """``EvaRows.decode``: a ring of token rows (windows of 512 tumble) and a
    pool of chunk summaries, one softmax over both.  The live slots are past
    three windows: their summary walks hold rows; one sits on a window's
    first row (a ring walk of one row)."""
    rng = np.random.default_rng(19)
    window, per, heads, d = 512, 16, 2, 128
    form = attention.EvaRows(heads, d, per, window)
    lens = np.zeros(len(EMPTY), np.int64)
    lens[~EMPTY] = [3 * window + 300, 4 * window + 1, 5, 3 * window + 129]
    first = (np.maximum(lens - 1, 0)) // window * window
    seen = first // per
    tables, pools = {}, {}
    for name, n, lo in ((form.token_group, lens, first),
                        (form.summary_group, seen, None)):
        t, bad, nb = _tables(rng, n, lo)
        rows = rng.standard_normal((2, 2, (nb + 1) * BS, heads * d))
        if impl == "pallas":    # the gather reads every column it is given
            rows[:, :, bad] = np.nan
        tables[name] = jnp.asarray(t)
        pools[name] = tuple(jnp.asarray(r, jnp.float32) for r in rows)
    q = jnp.asarray(rng.standard_normal((len(lens), heads, d)), jnp.float32)

    def decode(lens):
        return form.decode(q, pools, tables, lens, layer=1, block_size=BS,
                           impl=impl), None

    return decode, lens


FORMS = {
    "kv_d128": lambda: _kv_rows("pallas"),
    "kv_window": lambda: _kv_rows("pallas", window=200),
    "kv_lo": lambda: _kv_rows("pallas", lo=True, window=512, heads=2,
                              with_lse=True),
    "kv_with_lse": lambda: _kv_rows("pallas", heads=2, with_lse=True),
    "kv_sink": lambda: _kv_rows("pallas", sink=True, window=200),
    "kv_d64": lambda: _kv_rows("pallas", heads=8, kv_heads=4, d=64),
    "kv_d256": lambda: _kv_rows("pallas", heads=4, kv_heads=1, d=256),
    "kv_d192_over_128": lambda: _kv_rows("pallas", heads=4, kv_heads=2,
                                         d=192, dv=128, sink=True),
    "kv_plain": lambda: _kv_rows("xla", d=32),
    "kv_plain_window_sink": lambda: _kv_rows("xla", d=32, window=200,
                                             sink=True),
    "kv_verify": _verify_rows,
    "latent": lambda: _latent_rows("pallas"),
    "latent_plain": lambda: _latent_rows("xla"),
    "sparse_latent": lambda: _sparse_rows("pallas"),
    "sparse_latent_plain": lambda: _sparse_rows("xla"),
    "two_pools": lambda: _two_pools("pallas"),
    "two_pools_plain": lambda: _two_pools("xla"),
}


@pytest.mark.parametrize("form", list(FORMS))
def test_an_empty_slot_attends_nothing(form):
    decode, lens = FORMS[form]()
    got, lse = decode(jnp.asarray(lens, jnp.int32))
    got = np.asarray(got)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[EMPTY], 0.0)
    assert lse is None or (np.asarray(lse)[EMPTY] < attention.NEG_INF).all()
    # what the programs sent before: an empty slot attends one row (block
    # 0's first, which is finite)
    was, _ = decode(jnp.asarray(np.where(EMPTY, 1, lens), jnp.int32))
    was = np.asarray(was)
    assert np.abs(was[EMPTY]).max() > 0        # the old length did read
    np.testing.assert_array_equal(got[~EMPTY], was[~EMPTY])


# through the engine: a family with a state, with experts, with a loop, and
# the sampled / verify programs

def _gpt_tiny(dtype):
    return dataclasses.replace(gpt.gpt_tiny(), dtype=dtype, max_seq=128)


FAMILIES = {
    "jamba": (jamba, jamba.jamba_tiny, 8, {}),
    "lfm2": (lfm2, lfm2.lfm2_tiny, 8, {}),
    "ouro": (ouro, ouro.ouro_tiny, 32, {}),
    "gpt_verify": (gpt, _gpt_tiny, 8, dict(fused_sampling=True, speculate=3)),
}


def _served(family, preset, chunk, options):
    """``(tokens a request, [(live slots' first output, fourth output) a
    decode step])`` of two requests in an engine of four slots; the first
    output is the logits, or under ``fused_sampling`` the emitted tokens and
    their count (periodic prompts there, so that drafts are verified)."""
    cfg = preset(dtype=jnp.float32)
    key = jax.random.PRNGKey(62)
    params = (family.init_params(cfg, key) if family is gpt
              else family.init_params(cfg, key, std=0.2))
    eng = Engine(params, cfg, max_slots=4, block_size=4, prefill_chunk=chunk,
                 max_context=128, **options)
    steps = []

    def spied(program, active_at, fourth_at):
        def spy(*args):
            out = program(*args)
            live = np.asarray(args[active_at])
            assert 0 < live.sum() < 3       # empty slots in every step
            fourth = None if fourth_at is None else out[fourth_at]
            steps.append((np.asarray(out[0])[live],
                          None if fourth is None else np.asarray(fourth)))
            return out
        return spy

    if options:
        eng._fused1 = spied(eng._fused1, 6, None)
        eng._fused_spec = spied(eng._fused_spec, 6, None)
    else:
        eng.programs.decode = spied(eng.programs.decode, -1, 3)
    rng = np.random.default_rng(62)
    reqs = []
    for n, new in ((11, 9), (5, 14)):
        prompt = (([5, 9, 2, 7] * 3)[:n] if options
                  else rng.integers(1, cfg.vocab_size, n).tolist())
        reqs.append(eng.submit(prompt, max_new_tokens=new))
        eng.step()      # the second arrives while the first decodes alone
    for _ in range(2000):
        if all(r._done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.status == "ok" for r in reqs)
    assert not options or eng.counters["spec_drafted"] > 0
    return [r.tokens for r in reqs], steps


@pytest.mark.parametrize("name", list(FAMILIES))
def test_served_beside_empty_slots_as_before(name, monkeypatch):
    """Two requests in an engine of four slots — two to three slots empty in
    every decode step — served with the empty slots at a length of 0 (the
    rule) and of 1 (a row of the scratch block): the same tokens, the live
    slots' logits bit for bit (under the sampled / verify programs: the
    tokens each step emitted), and the same fourth output (lfm2's routed
    counters, ouro's exit mass; jamba, a state alone, has none), which the
    empty slots' rows — zeros — do not reach."""
    tokens, steps = _served(*FAMILIES[name])
    assert (steps[0][1] is None) == (name in ("jamba", "gpt_verify"))
    monkeypatch.setattr(
        serve_model, "_attend_lens",
        lambda seq_lens, active: jnp.where(
            active, seq_lens.astype(jnp.int32) + 1, 1))
    tokens_was, steps_was = _served(*FAMILIES[name])
    assert tokens_was == tokens and len(steps_was) == len(steps)
    for (logits, fourth), (logits_was, fourth_was) in zip(steps, steps_was):
        assert np.isfinite(logits).all()
        np.testing.assert_array_equal(logits, logits_was)
        if fourth is not None:
            assert np.isfinite(fourth).all()
            np.testing.assert_array_equal(fourth, fourth_was)
