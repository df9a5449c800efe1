"""Fused Pallas LM-head cross-entropy vs the chunked golden path.

The fused kernel must be a drop-in for ``chunked_softmax_xent`` — same
scalar loss and same gradients wrt hidden states and the tied table —
for every semantic edge the chunked head supports: masked rows,
out-of-range (ignore) targets, token counts and vocab sizes that do not
divide the tile sizes.  Runs in Pallas interpret mode on the CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.ops.fused_xent import fused_softmax_xent
from distributedtensorflow_tpu.ops.xent import chunked_softmax_xent

# Small tiles so tests cover multi-block grids without big arrays.
BLOCKS = dict(block_tokens=16, block_vocab=128,
              block_tokens_dx=32, block_vocab_dx=64,
              block_tokens_dw=16, block_vocab_dw=128)


def _setup(b=2, s=24, d=32, v=300, seed=0, mask_frac=0.0, bad_frac=0.0):
    rng = np.random.default_rng(seed)
    hidden = rng.standard_normal((b, s, d)).astype(np.float32)
    targets = rng.integers(0, v, size=(b, s)).astype(np.int32)
    mask = None
    if mask_frac:
        mask = (rng.random((b, s)) > mask_frac).astype(np.float32)
    if bad_frac:
        bad = rng.random((b, s)) < bad_frac
        targets = np.where(bad, -100, targets).astype(np.int32)
    wte = (rng.standard_normal((v, d)) * 0.05).astype(np.float32)
    return jnp.asarray(hidden), jnp.asarray(wte), jnp.asarray(targets), (
        None if mask is None else jnp.asarray(mask)
    )


@pytest.mark.parametrize("mask_frac,bad_frac", [(0.0, 0.0), (0.3, 0.0),
                                                (0.2, 0.15)])
def test_fused_matches_chunked_value(mask_frac, bad_frac):
    hidden, wte, targets, mask = _setup(mask_frac=mask_frac,
                                        bad_frac=bad_frac)
    got = fused_softmax_xent(hidden, wte, targets, mask, interpret=True,
                             **BLOCKS)
    want = chunked_softmax_xent(hidden, wte, targets, mask, chunk_tokens=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_fused_matches_chunked_grads():
    hidden, wte, targets, mask = _setup(mask_frac=0.25, bad_frac=0.1)

    def loss_fused(h, w):
        return fused_softmax_xent(h, w, targets, mask, interpret=True,
                                  **BLOCKS)

    def loss_chunked(h, w):
        return chunked_softmax_xent(h, w, targets, mask, chunk_tokens=16)

    gh_f, gw_f = jax.grad(loss_fused, argnums=(0, 1))(hidden, wte)
    gh_c, gw_c = jax.grad(loss_chunked, argnums=(0, 1))(hidden, wte)
    np.testing.assert_allclose(np.asarray(gh_f), np.asarray(gh_c),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_c),
                               rtol=2e-4, atol=1e-6)


def test_fused_ragged_shapes():
    # 22 tokens (not a multiple of any tile), vocab 171 (ditto).
    hidden, wte, targets, mask = _setup(b=1, s=22, v=171, mask_frac=0.2)
    got = fused_softmax_xent(hidden, wte, targets, mask, interpret=True,
                             **BLOCKS)
    want = chunked_softmax_xent(hidden, wte, targets, mask, chunk_tokens=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_fused_bf16_compute_dtype():
    hidden, wte, targets, mask = _setup()
    got = fused_softmax_xent(hidden, wte, targets, mask,
                             compute_dtype=jnp.bfloat16, interpret=True,
                             **BLOCKS)
    want = chunked_softmax_xent(hidden, wte, targets, mask,
                                compute_dtype=jnp.bfloat16, chunk_tokens=16)
    # Same bf16 operand rounding on both paths; reduction order differs.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_fused_bf16_grads_vs_fp32_chunked():
    """Pin the bf16-operand backward's precision trade (ADVICE r4).

    The default TPU training path rounds dlogits to bf16 before the
    dx/dw matmuls (fused_xent.py backward) — a deliberate bandwidth/
    precision trade.  This test bounds its gradient error against the
    all-fp32 chunked reference with an explicitly chosen tolerance, so
    any future change that degrades the bf16 path further (e.g. bf16
    softmax statistics) fails here instead of drifting silently."""
    hidden, wte, targets, mask = _setup(mask_frac=0.25, bad_frac=0.1)

    def loss_bf16(h, w):
        return fused_softmax_xent(h, w, targets, mask,
                                  compute_dtype=jnp.bfloat16,
                                  interpret=True, **BLOCKS)

    def loss_ref(h, w):
        return chunked_softmax_xent(h, w, targets, mask, chunk_tokens=16)

    gh_b, gw_b = jax.grad(loss_bf16, argnums=(0, 1))(hidden, wte)
    gh_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(hidden, wte)
    # bf16 has ~3 decimal digits; operand rounding on logits + dlogits
    # compounds through one matmul.  2e-2 relative / 2e-3 absolute is the
    # pinned budget — measured headroom ~4x below it at these shapes.
    np.testing.assert_allclose(np.asarray(gh_b), np.asarray(gh_r),
                               rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(np.asarray(gw_b), np.asarray(gw_r),
                               rtol=2e-2, atol=2e-3)


def test_fused_forward_scratch_chunking(monkeypatch):
    """A tiny scratch budget forces the token-super-chunk path.

    The forward's VMEM scratch is O(tokens); over budget the host loop
    splits the token axis across several pallas_calls.  Value AND grads
    must be bit-identical to the single-call path (the split is purely a
    scheduling decision — every per-token quantity is independent across
    chunks).
    """
    from distributedtensorflow_tpu.ops import fused_xent as fx

    hidden, wte, targets, mask = _setup(b=2, s=40, mask_frac=0.2,
                                        bad_frac=0.1)

    def run():
        return jax.value_and_grad(
            lambda h, w: fused_softmax_xent(h, w, targets, mask,
                                            interpret=True, **BLOCKS),
            argnums=(0, 1),
        )(hidden, wte)

    loss_one, (gh_one, gw_one) = run()
    # block_tokens=16 -> per-block scratch = 3*8*16*4 = 1536 B; budget 2000
    # allows exactly 1 block per call -> 80 tokens = 5 chunks.
    monkeypatch.setenv("DTFT_XENT_FWD_SCRATCH_BYTES", "2000")
    assert fx._max_fwd_token_blocks(16) == 1
    loss_chunked, (gh_c, gw_c) = run()
    np.testing.assert_array_equal(np.asarray(loss_one),
                                  np.asarray(loss_chunked))
    np.testing.assert_array_equal(np.asarray(gh_one), np.asarray(gh_c))
    np.testing.assert_array_equal(np.asarray(gw_one), np.asarray(gw_c))


def test_fused_hbm_traffic_bound(monkeypatch):
    """Chip-free check of the kernel's headline HBM claim (VERDICT r3 #5).

    The module docstring claims ~9.7 GB/step of head HBM traffic at the
    GPT-2-small headline config vs ~17 GB for the logits-materializing
    chunked head.  estimate_hbm_bytes derives traffic by walking the
    kernels' actual (grid, index_map) pairs, so this test breaks if a
    tiling/loop-order change silently regresses the traffic pattern —
    a check that needs no chip.

    The window was ``3e9 < total < 5e9`` (4.18 GB) while both backward
    kernels recomputed the logits tile.  Since PR 42 the dx kernel writes
    the rounded ``dlog`` tile once and the dw kernel reads it once
    instead of forming it again: 2 x Vp x N x 2 B = 3.32 GB of the 9.73
    here.  The rest of the rise is the dw kernel's: 1.09 GB for the
    table gradient carried in and out of each of the four token chunks,
    and 1.23 GB because its vocab tile is 512 rows where it was the
    forward's 1,024 (``x`` is read once a vocab block; timed on the chip
    the taller tile buys nothing, ``BLOCK_TOKENS_DW``), less the 0.08 GB
    table read it no longer makes.  Bytes bought an MXU product: 9.7 GB
    is 12 ms at 819 GB/s under 26 ms of MXU time for the four products,
    where the fifth product was 6.5 ms at 197 TFLOP/s; the backward
    alone went 28.0 -> 20.9 ms on the chip at these shapes (chunks of
    8,192; PR 42).  Still 1.7x under the chunked head.
    """
    from distributedtensorflow_tpu.ops.fused_xent import (
        _max_fwd_token_blocks,
        _walk_fetches,
        estimate_hbm_bytes,
    )

    # Headline config: B=16, S=1024, GPT-2-small head.  Pin the default
    # scratch budget: an ambient DTFT_XENT_FWD_SCRATCH_BYTES would change
    # the chunking and fail the magnitude window spuriously.
    monkeypatch.delenv("DTFT_XENT_FWD_SCRATCH_BYTES", raising=False)
    e = estimate_hbm_bytes(16 * 1024, 768, 50257)
    # 9.73 GB at the on-chip-validated tiles (forward block_v 1024: the
    # 16 MB Mosaic stack limit forced it down from 2048 on 2026-08-01 —
    # see the tile-size comment in fused_xent.py; dw 2048 x 512, PR 42),
    # 3.32 of it dlog's write and read, vs 17.0 GB chunked: 1.7x less
    # head traffic.
    assert 8.5e9 < e["total_bytes"] < 10.5e9, e
    assert e["chunked_head_bytes"] > 1.6 * e["total_bytes"], e
    # four chunks of 4,096 tokens against 50,688 padded rows, written by
    # dx and read by dw once each, in the compute type
    assert e["dlog_bytes"] == 2 * 50688 * 16384 * 2, e
    assert 5.4e9 < e["total_bytes"] - e["dlog_bytes"] < 7.4e9, e

    # Structural invariants of the design (not just magnitudes):
    # fwd reads the weight table exactly ONCE per token super-chunk
    # (vocab-outer: each w block is fetched once and stays resident for
    # the whole inner token sweep).  Explicit blocks: vocab 2048 here so
    # the walk counts stay independent of the defaults.
    n_j, n_i = 25, 32  # 50257/2048 vocab blocks (padded), 16384/512 tokens
    assert _walk_fetches((n_j, n_i), lambda j, i: (j, 0)) == n_j
    # dx (token-outer) re-reads the whole table once per token block.
    assert _walk_fetches((n_i, n_j), lambda i, j: (j, 0)) == n_i * n_j
    # Token super-chunking multiplies only the fwd weight stream: at a
    # quarter of the single-call chunk size, fwd re-reads w 4x.  Budgets
    # chosen so both runs chunk WITHOUT a ragged tail (a 1-block tail
    # chunk legitimately fetches x only once, which would perturb the
    # x stream and obscure the w-only invariant).
    n_tok = 80 * 512  # 40960: multiple of both chunk sizes below
    per_block = 3 * 8 * 512 * 4
    monkeypatch.setenv("DTFT_XENT_FWD_SCRATCH_BYTES", str(80 * per_block))
    assert _max_fwd_token_blocks(512) == 80
    one = estimate_hbm_bytes(n_tok, 768, 50257)   # 1 chunk of 80
    monkeypatch.setenv("DTFT_XENT_FWD_SCRATCH_BYTES", str(20 * per_block))
    four = estimate_hbm_bytes(n_tok, 768, 50257)  # 4 chunks of 20
    w_stream = 25 * 2048 * 768 * 2  # one full bf16 table read
    assert four["fwd_bytes"] - one["fwd_bytes"] == 3 * w_stream


def test_fused_grad_under_jit_and_vjp_dtype():
    hidden, wte, targets, mask = _setup()

    @jax.jit
    def step(h, w):
        return jax.value_and_grad(
            lambda h_, w_: fused_softmax_xent(
                h_, w_, targets, mask, interpret=True, **BLOCKS
            ),
            argnums=(0, 1),
        )(h, w)

    loss, (gh, gw) = step(hidden, wte)
    assert np.isfinite(float(loss))
    assert gh.dtype == hidden.dtype and gw.dtype == wte.dtype
    assert gh.shape == hidden.shape
    assert gw.shape == wte.shape


def test_blocks_for_dim_adaptive(monkeypatch):
    """Tile defaults adapt to hidden size: the d<=768 set comes from the
    module constants (single source of truth); d>768 drops to the
    512-across set that fits Mosaic's 16 MB stack at GPT-2-medium
    (d=1024 with the d<=768 tiles VMEM-OOMs on the chip).  Env overrides
    win at every d."""
    import distributedtensorflow_tpu.ops.fused_xent as fx

    for name in ("DTFT_XENT_BLOCK_TOKENS", "DTFT_XENT_BLOCK_VOCAB",
                 "DTFT_XENT_BLOCK_TOKENS_DX", "DTFT_XENT_BLOCK_VOCAB_DX"):
        monkeypatch.delenv(name, raising=False)
    # forward, dx, dw: dw's tiles hold no fp32 temporaries, at any d
    assert fx._blocks_for_dim(768) == (
        fx.BLOCK_TOKENS, fx.BLOCK_VOCAB, fx.BLOCK_TOKENS_DX,
        fx.BLOCK_VOCAB_DX, fx.BLOCK_TOKENS_DW, fx.BLOCK_VOCAB_DW,
    )
    assert fx._blocks_for_dim(1024) == (512, 512, 512, 512,
                                        fx.BLOCK_TOKENS_DW,
                                        fx.BLOCK_VOCAB_DW)
    monkeypatch.setenv("DTFT_XENT_BLOCK_TOKENS_DX", "256")
    assert fx._blocks_for_dim(1024)[2] == 256


def test_fused_wide_hidden_matches_chunked():
    """d=1024 (> the 768 tile-default boundary) through the REAL default
    block resolution — value + grads vs the chunked golden path.  This is
    the adaptive-tile branch gpt_medium runs on TPU, exercised on CPU in
    interpret mode (small vocab keeps it fast; block shapes pad)."""
    from distributedtensorflow_tpu.ops.xent import chunked_softmax_xent

    key = jax.random.PRNGKey(5)
    n, d, v = 64, 1024, 640
    hidden = jax.random.normal(jax.random.fold_in(key, 0), (n, d)) * 0.05
    wte = jax.random.normal(jax.random.fold_in(key, 1), (v, d)) * 0.05
    targets = jax.random.randint(jax.random.fold_in(key, 2), (n,), 0, v)

    def lf(h, w):
        return fused_softmax_xent(h, w, targets, interpret=True)

    def lc(h, w):
        return chunked_softmax_xent(h[None], w, targets[None])

    vf, gf = jax.value_and_grad(lf, argnums=(0, 1))(hidden, wte)
    vc, gc = jax.value_and_grad(lc, argnums=(0, 1))(hidden, wte)
    np.testing.assert_allclose(vf, vc, rtol=1e-5, atol=1e-6)
    for a, b in zip(gf, gc):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def _kernel_dots(jaxpr, counts=None, inside=None):
    """``dot_general``s inside Pallas kernels of ``jaxpr``, by kernel name,
    a kernel counted once a call site (sub-jaxprs walked: custom_vjp,
    scan, pjit)."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        name = inside
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts.setdefault(name, 0)
        elif eqn.primitive.name == "dot_general" and inside:
            counts[inside] += 1
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_dots(sub, counts, name)
    return counts


def test_fused_grad_holds_four_products():
    """The head's forward + backward is four ``tokens x d x V`` MXU
    products: the logits tile in the forward; in the backward the logits
    tile again and ``dlog x W`` (dx), then ``dlog x x`` over the tile dx
    stored (dw).  A fifth — the dw kernel forming the logits tile for
    itself, as it did until PR 42 — is 34.5 ms a step of GPT-2 medium on a
    v5e, and nothing but this count would show it."""
    from distributedtensorflow_tpu.ops import fused_xent as fx

    hidden, wte, targets, mask = _setup(mask_frac=0.2)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda h, w: fused_softmax_xent(h, w, targets, mask, interpret=True,
                                        **BLOCKS), argnums=(0, 1)))(
        hidden, wte)
    counts = _kernel_dots(jaxpr.jaxpr)
    assert counts == {"fused_xent_fwd": 1, "fused_xent_bwd_dx": 2,
                      "fused_xent_bwd_dw": 1}, counts
    assert sum(counts.values()) == fx.PRODUCTS_PER_STEP == 4


@pytest.mark.parametrize("d, tokens, v, chunk_blocks, want", [
    # d = 768: dx walks (1024 tokens, 512 rows) tiles, dw (2048, 512):
    # the buffer is written in one blocking and read in the other
    pytest.param(768, 5000, 1100, 2, (2, 4096), id="d768_unequal_tilings"),
    pytest.param(1024, 4500, 700, 1, (3, 2048), id="d1024"),
])
def test_fused_dlog_chunking(monkeypatch, d, tokens, v, chunk_blocks, want):
    """A small ``dlog`` budget splits the backward's tokens into chunks.

    The fewest equal chunks that fit, the last padded with rows of weight
    zero: 5,000 tokens in blocks of 2,048 at two blocks a chunk are two
    chunks, the second a block of 904 real rows and a block of none.
    Gradients must be bit-identical to the single-chunk path: dx rows are
    independent across chunks, and dw starts each chunk from the sum of
    the chunks before, so its sweep over the token blocks keeps the order
    of one call over all of them (padding adds exact zeros)."""
    from distributedtensorflow_tpu.ops import fused_xent as fx

    key = jax.random.PRNGKey(d)
    hidden = (jax.random.normal(jax.random.fold_in(key, 0), (tokens, d))
              * 0.5).astype(jnp.bfloat16)
    wte = jax.random.normal(jax.random.fold_in(key, 1), (v, d)) * 0.05
    targets = jax.random.randint(jax.random.fold_in(key, 2), (tokens,),
                                 -3, v)          # a few out of range
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (tokens,))
            > 0.2).astype(jnp.float32)

    def run():
        return jax.grad(
            lambda h, w: fused_softmax_xent(
                h, w, targets, mask, compute_dtype=jnp.bfloat16,
                interpret=True), argnums=(0, 1))(hidden, wte)

    assert fx.dlog_chunk_tokens(tokens, d, v) >= tokens     # one chunk
    gh_one, gw_one = run()
    blocks = fx._blocks_for_dim(d)
    block_n = np.lcm(blocks[2], blocks[4])
    vp = v + (-v) % np.lcm(blocks[3], blocks[5])
    monkeypatch.setattr(fx, "DLOG_BUDGET_BYTES",
                        int(chunk_blocks * block_n * vp * 2))
    chunk = fx.dlog_chunk_tokens(tokens, d, v)
    assert (-(-tokens // chunk), chunk) == want
    assert tokens % chunk and chunk % block_n == 0          # ragged last
    gh_c, gw_c = run()
    np.testing.assert_array_equal(np.asarray(gh_one, np.float32),
                                  np.asarray(gh_c, np.float32))
    np.testing.assert_array_equal(np.asarray(gw_one), np.asarray(gw_c))


def test_fused_dlog_zero_where_nothing_counts():
    """Masked rows, out-of-range targets and padded vocabulary rows are
    exact zeros of the stored ``dlog`` tile, so of both gradients: dx is
    zero on those tokens, and dw does not see what their hidden states
    hold (``c = g * w_row`` is 0 there; ``row < v_true`` masks the
    padding) — the one tile now feeds both products."""
    from distributedtensorflow_tpu.ops import fused_xent as fx

    hidden, wte, targets, mask = _setup(b=1, s=48, v=171, mask_frac=0.3,
                                        bad_frac=0.2)
    dead = np.asarray((mask.reshape(-1) == 0)
                      | (targets.reshape(-1) < 0))
    assert dead.sum() > 8 and (~dead).sum() > 8

    def grads(h):
        return jax.grad(
            lambda h_, w_: fused_softmax_xent(h_, w_, targets, mask,
                                              interpret=True, **BLOCKS),
            argnums=(0, 1))(h, wte)

    gh, gw = grads(hidden)
    assert not np.asarray(gh)[0, dead].any()
    assert np.asarray(gh)[0, ~dead].any(axis=-1).all()
    # other hidden states on the dead rows: the same dw to the last bit
    other = jnp.where(dead[None, :, None], 7.0 - 3.0 * hidden, hidden)
    np.testing.assert_array_equal(np.asarray(gw), np.asarray(grads(other)[1]))

    # and the tile itself, as the dx kernel stores it
    x, t = hidden[0], targets[0]
    w = fx._pad_to(wte, 128, 0)                  # 171 rows -> 256
    lse, _ = fx._fused_fwd_arrays(x[:48], w, t, block_n=16, block_v=128,
                                  v_true=171, interpret=True)
    c = jnp.where(dead, 0.0, 1.0 / 48).astype(jnp.float32)
    _, dlog = fx._bwd_dx_call(x, w, t, lse, c, block_n=16, block_v=64,
                              v_true=171, interpret=True)
    dlog = np.asarray(dlog)
    assert dlog.shape == (256, 48)
    assert not dlog[:, dead].any() and not dlog[171:].any()
    assert dlog[:171, ~dead].any(axis=0).all()
