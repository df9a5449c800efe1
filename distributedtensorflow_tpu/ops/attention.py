"""Attention ops: XLA reference implementation + kernel dispatch point.

All attention in the framework routes through :func:`dot_product_attention`
so fused kernels (Pallas flash attention, ring attention over the ``seq``
axis — SURVEY.md §5.7) can replace the reference path without touching
models.  The plain-XLA path is itself MXU-friendly: one batched matmul per
score/value contraction, softmax in float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..runtime import on_tpu, use_kernel

NEG_INF = -1e9  # large-negative in bf16-safe range (bf16 max ~3.4e38; 1e9 fine)

def dot_product_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, H, D)
    v: jax.Array,  # (B, S, H, D)
    *,
    mask: jax.Array | None = None,  # broadcastable to (B, H, Sq, Sk); True=keep
    segment_ids: jax.Array | None = None,  # int (B, S): packed sequences
    causal: bool = False,
    window: int | None = None,  # sliding window (requires causal)
    implementation: str = "auto",  # "auto" | "xla" | "pallas"
) -> jax.Array:
    """Multi-head scaled dot-product attention, BSHD layout.

    ``implementation="auto"`` picks the Pallas flash kernel on TPU when the
    shapes allow, else the XLA path.  ``segment_ids`` restricts attention to
    within packed segments (BERT-style example packing); on the XLA path it
    lowers to a block-diagonal mask, on the Pallas path it stays O(S) memory.
    ``window`` enables causal sliding-window attention (token i sees keys
    in ``(i - window, i]``); the Pallas path skips out-of-band blocks so
    cost is O(S * window).
    """
    if implementation in ("auto", "pallas"):
        from . import flash_attention  # noqa: PLC0415 (lazy: pallas optional)

        if (
            flash_attention.supported(q, k, v, mask=mask, segment_ids=segment_ids)
            or implementation == "pallas"
        ):
            return flash_attention.flash_attention(
                q, k, v, mask=mask, segment_ids=segment_ids, causal=causal,
                window=window,
            )
    if segment_ids is not None:
        seg = (segment_ids[:, :, None] == segment_ids[:, None, :])[:, None, :, :]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    return xla_attention(q, k, v, mask=mask, causal=causal, window=window)


def cached_decode_attention(
    q: jax.Array,         # (B, s_new, H, D) new queries
    k_new: jax.Array,     # (B, s_new, Hkv, D) new keys (Hkv <= H: GQA)
    v_new: jax.Array,     # (B, s_new, Hkv, D) new values
    cached_k: jax.Array,  # (B, Hkv, max_seq, D) cache
    cached_v: jax.Array,  # (B, Hkv, max_seq, D)
    cache_index: jax.Array,  # () int32 — next write slot
    window: int | None = None,  # sliding window (matches training masking)
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One decode step over a dense ``(B, Hkv, max_seq, D)`` K/V cache.

    The dense-cache reference: ``models.generate`` and the seq2seq decoder
    step through it, and the paged server's tests hold the served tokens to
    what it produces.  It is on no served path (``serve/`` attends pages:
    :func:`paged_decode_attention` and below).

    Pure function (caller owns the cache state, e.g. a flax "cache"
    collection): writes the new K/V at ``cache_index``, attends the new
    queries against the whole static-shape cache with validity masking —
    a query at absolute position ``ix+i`` sees keys at positions
    ``<= ix+i``, which is also correct for multi-token chunked prefill —
    and returns ``(out, cached_k, cached_v, cache_index)`` updated.
    Plain XLA einsums on every platform, MHA and grouped (the cache is
    never broadcast to H); scores accumulate and softmax runs in float32,
    matching :func:`xla_attention`.
    """
    b, s_new, h, d = q.shape
    max_seq = cached_k.shape[2]
    ix = cache_index
    cached_k = jax.lax.dynamic_update_slice(
        cached_k, k_new.transpose(0, 2, 1, 3), (0, 0, ix, 0)
    )
    cached_v = jax.lax.dynamic_update_slice(
        cached_v, v_new.transpose(0, 2, 1, 3), (0, 0, ix, 0)
    )
    q_pos = ix + jnp.arange(s_new)
    k_idx = jnp.arange(max_seq)
    valid = k_idx[None, :] <= q_pos[:, None]  # (s_new, max_seq)
    if window is not None:
        # sliding window: only the last `window` positions stay visible
        valid &= k_idx[None, :] > q_pos[:, None] - window
    h_kv = cached_k.shape[1]
    # The grouped einsums take float32 operands: the CPU backend has no
    # bf16 x bf16 -> f32 product for them, and the cast changes no number
    # (a bf16 product is exact in float32; the sum was float32 already).
    f32 = jnp.float32
    if h != h_kv:  # GQA: grouped einsums, cache never broadcast to H
        g = h // h_kv
        qg = q.reshape(b, s_new, h_kv, g, d)
        scores = jnp.einsum(
            "bqhgd,bhkd->bhgqk", qg.astype(f32), cached_k.astype(f32),
        ).reshape(b, h, s_new, max_seq) / (d ** 0.5)
    else:
        scores = jnp.einsum(
            "bqhd,bhkd->bhqk", q, cached_k,
            preferred_element_type=jnp.float32,
        ) / (d ** 0.5)
    scores = jnp.where(valid[None, None, :, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    if h != h_kv:
        wg = weights.astype(q.dtype).reshape(b, h_kv, g, s_new, max_seq)
        out = jnp.einsum(
            "bhgqk,bhkd->bqhgd", wg.astype(f32), cached_v.astype(f32),
        ).reshape(b, s_new, h, d).astype(q.dtype)
    else:
        out = jnp.einsum(
            "bhqk,bhkd->bqhd", weights.astype(q.dtype), cached_v,
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)
    return out, cached_k, cached_v, ix + s_new


def _gather_pages(pool, layer, block_tables, block_size, head_dim):
    """The page-table walk: layer ``layer`` of the stacked pool, ``(L,
    num_blocks * block_size, Hkv * D)`` token rows (``serve.kv_cache``
    "Stored form"), gathered through ``block_tables`` to ``(B, Hkv, cap,
    D)``.  Whole blocks of rows are gathered (splitting the row dimension
    into (block, offset) moves no data where a block is a whole number of
    tiles) and the layer is an index of the same gather, so no layer of
    the pool is sliced out first; the heads are split on the gathered data
    only, never on the pool."""
    b, max_blocks = block_tables.shape
    num_layers, rows, width = pool.shape
    x = pool.reshape(num_layers, rows // block_size, block_size,
                     width)[layer, block_tables]
    return _row_heads(x, (b, max_blocks * block_size),
                      head_dim).transpose(0, 2, 1, 3)


def _tile_split(head_dim: int) -> int:
    """Where a row of heads ``head_dim`` wide is split: a head wider than a
    128-lane tile and not a whole number of them (MiMo-V2's keys: 192) is
    stored as its whole tiles, head after head, and then its remainders, head
    after head (``[h0[:128] | h1[:128] | ... | h0[128:] | h1[128:] | ...]``),
    so that no tile of the row holds parts of two kinds and nothing is
    padded; 0 where the heads simply follow each other."""
    return head_dim // 128 * 128 if head_dim > 128 and head_dim % 128 else 0


def lay_heads(x: jax.Array) -> jax.Array:
    """``x`` (T, heads, D) as the row a pool stores, (T, heads * D)."""
    t, split = x.shape[0], _tile_split(x.shape[-1])
    if not split:
        return x.reshape(t, -1)
    return jnp.concatenate([x[..., :split].reshape(t, -1),
                            x[..., split:].reshape(t, -1)], axis=-1)


def _row_heads(x: jax.Array, lead: tuple, head_dim: int) -> jax.Array:
    """Stored rows ``x`` (any shape that ends in the row, heads * D wide) as
    heads, ``(*lead, heads, D)``: the inverse of :func:`lay_heads`, on
    gathered rows only."""
    width, split = x.shape[-1], _tile_split(head_dim)
    heads = width // head_dim
    if not split:
        return x.reshape(*lead, heads, head_dim)
    x = x.reshape(*lead, width)
    return jnp.concatenate([
        x[..., :heads * split].reshape(*lead, heads, split),
        x[..., heads * split:].reshape(*lead, heads, head_dim - split)],
        axis=-1)


def sink_softmax(scores, sink):
    """Softmax over the last axis of float32 ``scores`` (B, H, ..., K) with
    one more key a head that has no value: the learned bias ``sink`` (H,)
    joins the denominator, ``p_j = exp(s_j) / (exp(b_h) + sum_j' exp(s_j'))``."""
    b_h = sink.astype(jnp.float32).reshape(
        (1, -1) + (1,) * (scores.ndim - 2))
    m = jnp.maximum(scores.max(-1, keepdims=True), b_h)
    e = jnp.exp(scores - m)
    return e / (e.sum(-1, keepdims=True) + jnp.exp(b_h - m))


def paged_decode_attention(
    q: jax.Array,             # (B, H, D) one new query per serving slot
    k_pool: jax.Array,        # (L, num_blocks * block_size, Hkv * D) rows
    v_pool: jax.Array,        # (L, num_blocks * block_size, Hkv * D)
    block_tables: jax.Array,  # (B, max_blocks) int32 physical block ids
    seq_lens: jax.Array,      # (B,) int32 valid tokens incl. this step's
    *,
    layer: int,               # which layer of the stacked pools to attend
    block_size: int,          # rows per physical block
    window: int | None = None,  # attend only the last `window` positions
    sink: jax.Array | None = None,  # (H,) a key without a value a head
) -> jax.Array:
    """Single-token decode attention against a paged (block-pool) KV cache.

    The serving engine's counterpart of :func:`cached_decode_attention`:
    instead of one dense ``(B, Hkv, max_seq, D)`` buffer per slot, K/V
    live in a pool of fixed-size blocks shared by every slot and each
    slot's ``block_tables`` row names the blocks that hold its sequence —
    so a finished or short sequence pins only the blocks it actually
    used (``serve.kv_cache`` owns allocation, and says why the pool is
    stored as token rows of all heads).  Blockwise layout per
    ``ops/blockwise.py``'s chunking idiom: the sequence axis is tiled in
    ``block_size`` chunks, here scattered through the pool: block ``p`` is
    rows ``[p * block_size, (p + 1) * block_size)``.

    Each slot gathers its blocks to a ``(max_blocks * block_size, Hkv,
    D)`` view, masks positions ``>= seq_lens`` (and whatever a scratch /
    unallocated table entry points at), and runs the same fp32-softmax
    scaled dot product as the dense decode path — so paged and dense
    decode agree bit-for-bit up to reduction order (tests pin this).
    The plain XLA formulation (gather + einsum): compute cost is
    O(max_blocks * block_size) per slot whatever is resident, 50 ms an
    iteration at GPT-2 medium's 32 slots of 1024 (PERF.md §6, PR 29).  The
    kernel that streams only the blocks a slot holds is
    :func:`paged_window_decode_attention`, which the decode programs call;
    this is its yardstick in the tests, its path off the TPU and at shapes
    it does not take, and what :func:`paged_verify_attention` shares.  The
    V pool's heads may be narrower than K's (the output is as wide as V's);
    ``sink`` joins each head's softmax as a key with no value.
    """
    b, h, d = q.shape
    k = _gather_pages(k_pool, layer, block_tables, block_size, d)
    h_kv, cap = k.shape[1], k.shape[2]
    v = _gather_pages(v_pool, layer, block_tables, block_size,
                      v_pool.shape[-1] // h_kv)
    valid = jnp.arange(cap)[None, :] < seq_lens[:, None]  # (B, cap)
    if window is not None:
        valid &= jnp.arange(cap)[None, :] >= seq_lens[:, None] - window
    if h != h_kv:  # GQA: grouped einsums, pool never broadcast to H
        g = h // h_kv
        qg = q.reshape(b, h_kv, g, d)
        scores = jnp.einsum(
            "bhgd,bhkd->bhgk", qg, k, preferred_element_type=jnp.float32,
        ).reshape(b, h, cap) / (d ** 0.5)
    else:
        scores = jnp.einsum(
            "bhd,bhkd->bhk", q, k, preferred_element_type=jnp.float32,
        ) / (d ** 0.5)
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    weights = (jax.nn.softmax(scores, axis=-1) if sink is None
               else sink_softmax(scores, sink))
    # a slot that attends nothing returns zeros, as the kernel's does
    weights = jnp.where(valid[:, None, :], weights, 0.0)
    if h != h_kv:
        wg = weights.astype(q.dtype).reshape(b, h_kv, g, cap)
        out = jnp.einsum(
            "bhgk,bhkd->bhgd", wg, v, preferred_element_type=jnp.float32,
        ).reshape(b, h, v.shape[-1])
    else:
        out = jnp.einsum(
            "bhk,bhkd->bhd", weights.astype(q.dtype), v,
            preferred_element_type=jnp.float32,
        )
    return out.astype(q.dtype)


def paged_verify_attention(
    q: jax.Array,             # (B, T, H, D) draft-window queries per slot
    k_pool: jax.Array,        # (L, num_blocks * block_size, Hkv * D) rows
    v_pool: jax.Array,        # (L, num_blocks * block_size, Hkv * D)
    block_tables: jax.Array,  # (B, max_blocks) int32 physical block ids
    attend_lens: jax.Array,   # (B,) int32 valid tokens for query 0
    *,
    layer: int,               # which layer of the stacked pools to attend
    block_size: int,          # rows per physical block
    sink: jax.Array | None = None,  # (H,) a key without a value a head
) -> jax.Array:
    """Multi-token decode attention against the paged pool (speculative
    verification).

    The ``T > 1`` generalization of :func:`paged_decode_attention`:
    each slot carries a window of ``T`` query positions — its last
    committed token followed by ``T - 1`` draft tokens — whose K/V this
    step wrote at consecutive positions, and query ``t`` attends
    ``attend_lens + t`` positions (causal masking *inside the draft
    window*: draft ``t`` sees everything committed plus the drafts
    before it, exactly what a sequential decode would have seen — which
    is why accepted drafts are token-for-token what the one-token path
    would have produced).  Same gather-through-page-table walk, same
    fp32-softmax scaled dot product, same GQA grouping; at ``T = 1``
    with ``attend_lens = seq_lens`` it reduces to the decode path.
    Returns ``(B, T, H, D)``, ``D`` the width of a V head.
    """
    b, t, h, d = q.shape
    k = _gather_pages(k_pool, layer, block_tables, block_size, d)
    h_kv, cap = k.shape[1], k.shape[2]
    v = _gather_pages(v_pool, layer, block_tables, block_size,
                      v_pool.shape[-1] // h_kv)
    # (B, T, cap): query t of slot b sees positions < attend_lens[b] + t
    valid = (jnp.arange(cap)[None, None, :]
             < (attend_lens[:, None] + jnp.arange(t)[None, :])[:, :, None])
    if h != h_kv:  # GQA: grouped einsums, pool never broadcast to H
        g = h // h_kv
        qg = q.reshape(b, t, h_kv, g, d)
        scores = jnp.einsum(
            "bthgd,bhkd->bhgtk", qg, k,
            preferred_element_type=jnp.float32,
        ).reshape(b, h, t, cap) / (d ** 0.5)
    else:
        scores = jnp.einsum(
            "bthd,bhkd->bhtk", q, k, preferred_element_type=jnp.float32,
        ) / (d ** 0.5)
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    weights = (jax.nn.softmax(scores, axis=-1) if sink is None
               else sink_softmax(scores, sink))
    # a query that attends nothing (an inactive slot's first) returns zeros
    weights = jnp.where(valid[:, None, :, :], weights, 0.0)
    if h != h_kv:
        wg = weights.astype(q.dtype).reshape(b, h_kv, g, t, cap)
        out = jnp.einsum(
            "bhgtk,bhkd->bthgd", wg, v, preferred_element_type=jnp.float32,
        ).reshape(b, t, h, v.shape[-1])
    else:
        out = jnp.einsum(
            "bhtk,bhkd->bthd", weights.astype(q.dtype), v,
            preferred_element_type=jnp.float32,
        )
    return out.astype(q.dtype)


def xla_attention(q, k, v, *, mask=None, causal=False, window=None):
    """BSHD attention; supports GQA (k/v with fewer heads than q, heads
    grouped ``g = Hq // Hkv``) via grouped einsums — the (Hkv, g) <->
    (Hq,) reshapes are over adjacent dims, so they are free relayouts,
    and K/V are never materialized at Hq width."""
    orig_dtype = q.dtype
    b, sq, hq, depth = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    scale = 1.0 / jnp.sqrt(depth).astype(jnp.float32)
    # (B, H, Sq, Sk) scores; contraction in input dtype (bf16 MXU), softmax fp32
    if hq != hkv:
        g = hq // hkv
        qg = q.reshape(b, sq, hkv, g, depth)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).reshape(
            b, hq, sq, sk) * scale
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = scores.astype(jnp.float32)
    if window is not None and not causal:
        raise ValueError("window (sliding-window attention) requires causal")
    if causal:
        causal_mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            # band lower edge in absolute positions (q offset for Sq < Sk)
            qp = jnp.arange(sq)[:, None] + (sk - sq)
            causal_mask &= jnp.arange(sk)[None, :] > qp - window
        scores = jnp.where(causal_mask, scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    if hq != hkv:
        wg = weights.astype(orig_dtype).reshape(b, hkv, g, sq, sk)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", wg, v).reshape(b, sq, hq, depth)
    else:
        out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(orig_dtype), v)
    return out


# ---------------------------------------------------------------------------
# Paged attention that reads what a slot attends, not ``max_context``
# ---------------------------------------------------------------------------
#
# The two functions above gather every table column of every slot: their
# cost follows slots x max_context whatever is resident (PERF.md §5).  The
# two below read what a slot attends: the decode programs of both served
# families (``serve.model``) and the chunk prefill of the one with window
# layers and long contexts (``models.afmoe``).  They take ONE layer group's
# pool (``serve.kv_cache`` row form, ``(L_group, rows, Hkv * D)``), and an
# optional ``window``: a query at position ``i`` attends keys ``j`` with
# ``i - window < j <= i``.

#: lanes of a vector register: the decode kernel takes the pool's row a
#: 128-lane tile at a time, one K/V head of 128 or two of 64
LANES = 128
#: key rows the decode kernel folds into its running softmax at a time: the
#: lane width, so the (8, rows) scores, the running maximum and sum (kept
#: replicated across lanes) and the (8, 128) accumulator all share one shape
PAGED_ROWS = 128
#: rows a trip of the decode kernel's walk holds, in one of its two buffers
#: a pool: 128 read as fast as 256 and 512 or faster at every cell's shape,
#: and a trip's code, unrolled once a buffer, is lowered at every start-up
#: (PERF.md, PR 59)
PAGED_STRETCH = PAGED_ROWS
#: rows a trip of the latent decode kernel's walk holds, in one of its two
#: buffers: 256, 512 and 1,024 read the same on the chip (PERF.md, PR 55)
PAGED_LATENT_STRETCH = 4 * PAGED_ROWS


def paged_chunk_attention(
    q: jax.Array,            # (T, H, D): one slot's chunk of queries
    start,                   # int32 scalar: position of q[0]
    k_pool: jax.Array,       # (L_group, rows, Hkv * D)
    v_pool: jax.Array,
    table_row: jax.Array,    # (max_blocks,) the slot's page-table row
    *,
    layer: int,
    block_size: int,
    window: int | None = None,
    kv_chunk: int = 512,
    sink: jax.Array | None = None,   # (H,) a key without a value a head
) -> jax.Array:
    """Chunk-prefill attention of one slot against its pages, the chunk's
    own K/V already written: a loop over ``kv_chunk``-row stretches of the
    context from the first one a query of the chunk attends to the chunk's
    end, with a running softmax — ``window + T`` rows at most on a window
    layer, the rows before the chunk's end on a full one, never the table's
    whole width.  The V pool's heads may be narrower than K's; ``sink``
    joins each head's softmax as a key with no value."""
    t, h, d = q.shape
    h_kv = k_pool.shape[-1] // d
    dv = v_pool.shape[-1] // h_kv
    g = h // h_kv
    kv_chunk = max(block_size, kv_chunk // block_size * block_size)
    bpc = kv_chunk // block_size
    nb = table_row.shape[0]
    qpos = start + jnp.arange(t, dtype=jnp.int32)
    qg = q.reshape(t, h_kv, g, d)
    lo = 0 if window is None else jnp.maximum(start - window + 1, 0)
    scale = d ** -0.5

    def pages(pool, c, dim):
        blocks = table_row[jnp.minimum(c * bpc + jnp.arange(bpc), nb - 1)]
        x = pool.reshape(pool.shape[0], -1, block_size,
                         pool.shape[-1])[layer, blocks]
        return _row_heads(x, (kv_chunk,), dim)

    def body(c, carry):
        m, l, acc = carry
        kpos = c * kv_chunk + jnp.arange(kv_chunk, dtype=jnp.int32)
        s = jnp.einsum("qhgd,khd->hgqk", qg, pages(k_pool, c, d),
                       preferred_element_type=jnp.float32) * scale
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(ok[None, None], jnp.exp(s - m_new[..., None]), 0.0)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hgqk,khd->hgqd", p.astype(q.dtype), pages(v_pool, c, dv),
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(-1), acc

    init = (jnp.full((h_kv, g, t), NEG_INF, jnp.float32),
            jnp.zeros((h_kv, g, t), jnp.float32),
            jnp.zeros((h_kv, g, t, dv), jnp.float32))
    m, l, acc = jax.lax.fori_loop(
        lo // kv_chunk, -(-(start + t) // kv_chunk), body, init)
    if sink is not None:
        l = l + jnp.exp(sink.astype(jnp.float32).reshape(h_kv, g, 1) - m)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(2, 0, 1, 3).reshape(t, h, dv).astype(q.dtype)


def _paged_decode_kernel(tables_ref, lens_ref, lo_ref, layer_ref, q_ref, k_hbm,
                         v_hbm, *refs, block_size, scale, rest_at=None,
                         with_lse=False, head_tiles=1):
    """A grid step is a slot; its rows from ``lo`` to its length are walked
    in a loop of trips read from the prefetched scalars, a stretch of the
    buffers' rows a trip, over two K and two V buffers: a trip starts the
    next stretch's copies — this slot's, or the first of the next slot's —
    before it waits for its own, so they run under its arithmetic.  The
    buffers, the semaphores and ``walked`` (trips of the slots before: the
    buffer's parity) outlive a grid step; every copy started is waited for
    by the trip that folds it.  A walk over no row (a length of 0: a slot
    nobody holds, as the decode programs hand it over) takes no trip: it
    starts the next slot's first stretch and closes an empty running softmax,
    which is zeros.

    ``head_tiles``: lane tiles a K/V head (2 at a head of 256): a head's scores
    are the sum of its tiles' products, its running maximum and sum are kept
    alike in each of its tiles' rows of the scratch, and each of its V tiles
    takes the one set of probabilities.
    ``rest_at``: where a K head is wider than its tile (:func:`lay_heads`),
    the lane at which the heads' remainders start in the K row, two of 64 a
    tile; a head's scores are then two products, one over its whole tile and
    one over its remainder's (``q_ref`` holds the two query tiles of a head
    one after the other).  With a sink, ``refs`` starts with its (tiles,
    query rows, 128) float32 block.  ``with_lse``: a second output, the log
    of each row's denominator across its lanes (a walk over no key leaves
    it under ``NEG_INF``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lse_ref = refs[-10] if with_lse else None
    sink_ref = refs[0] if len(refs) == 11 + with_lse else None
    o_ref = refs[-10 - with_lse]
    *bufs, sem, walked, m_sc, l_sc, acc_sc = refs[-9:]
    bufs = (bufs[:2], bufs[2:])                # (K, V) of each parity
    s = pl.program_id(0)
    slots, nb = tables_ref.shape
    stretch, rows = bufs[0][0].shape[0], PAGED_ROWS
    tiles, q_rows = o_ref.shape[1], o_ref.shape[2]
    parts = q_ref.shape[1] // tiles            # query tiles a K/V tile
    layer = layer_ref[0]

    def walk_of(of):
        """Slot ``of``'s table row, length, first row, first trip and the
        trip past its last; a slot past the last has no rows."""
        row = jnp.minimum(of, slots - 1)
        n = jnp.where(of < slots, lens_ref[row], 0)
        lo = lo_ref[row]
        c0 = lo // stretch
        return row, n, lo, c0, jnp.maximum((n + stretch - 1) // stretch, c0)

    _, n, lo, c0, c1 = walk_of(s)

    @pl.when(s == 0)
    def _():
        walked[0] = 0

    before = walked[0]
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)

    def copies(of, c, into, start):
        """Start, or wait for, the copies of stretch ``c`` of slot ``of``
        into buffers ``into``: the blocks of a part (``rows`` rows) together
        and without a branch each, if the part holds a row the slot attends;
        before the slot's first column its first block is copied again and
        past its last column its last (the mask discards the rows)."""
        row, live, low, _, _ = walk_of(of)
        first = low // block_size
        last = jnp.minimum(jnp.maximum(live - 1, 0) // block_size, nb - 1)

        def a_part(part):
            for j in range(rows // block_size):
                at = part * rows + j * block_size
                blk = tables_ref[row, jnp.clip(
                    (c * stretch + at) // block_size, first, last)
                ] if start else 0
                src = pl.ds(blk * block_size, block_size)
                dst = pl.ds(at, block_size)
                for i, (hbm, buf) in enumerate(zip((k_hbm, v_hbm), bufs[into])):
                    cp = pltpu.make_async_copy(
                        hbm.at[layer, src], buf.at[dst],
                        sem.at[i, into, part])
                    if start:
                        cp.start()
                    else:
                        cp.wait()

        for part in range(stretch // rows):
            at = c * stretch + part * rows
            pl.when((at < live) & (at + rows > low))(
                functools.partial(a_part, part))

    # the walk's first stretch is slot 0's; a slot that walks nothing starts
    # the next slot's, which its last trip would have
    @pl.when((s == 0) | (c1 == c0))
    def _():
        of = s + (c1 == c0).astype(jnp.int32)
        *_, first_trip, _ = walk_of(of)
        for into in range(2):
            pl.when(jax.lax.rem(before, 2) == into)(
                functools.partial(copies, of, first_trip, into, True))

    def a_trip(c, into):
        ahead = c + 1 < c1
        *_, next_first, _ = walk_of(s + 1)
        copies(jnp.where(ahead, s, s + 1),
               jnp.where(ahead, c + 1, next_first), 1 - into, True)
        copies(s, c, into, False)
        kbuf, vbuf = bufs[into]
        for part in range(stretch // rows):
            start = c * stretch + part * rows

            @pl.when((start < n) & (start + rows > lo))
            def _(part=part, start=start):
                kpos = start + jax.lax.broadcasted_iota(
                    jnp.int32, (q_rows, rows), 1)
                valid = (kpos < n) & (kpos >= lo)
                here = pl.ds(part * rows, rows)
                for hh in range(0, tiles, head_tiles):
                    lanes = pl.ds(hh * LANES, LANES)
                    sc = jax.lax.dot_general(
                        q_ref[0, hh * parts], kbuf[here, lanes],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)     # (8, rows)
                    for more in range(hh + 1, hh + head_tiles):
                        sc += jax.lax.dot_general(
                            q_ref[0, more],
                            kbuf[here, pl.ds(more * LANES, LANES)],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                    if rest_at is not None:
                        sc += jax.lax.dot_general(
                            q_ref[0, hh * parts + 1],
                            kbuf[here,
                                 pl.ds(rest_at + hh // 2 * LANES, LANES)],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                    sc = jnp.where(valid, sc * scale, NEG_INF)
                    m_prev = m_sc[hh]
                    m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
                    l_new = alpha * l_sc[hh] + p.sum(axis=1, keepdims=True)
                    for tile in range(hh, hh + head_tiles):
                        l_sc[tile] = l_new
                        acc_sc[tile] = alpha * acc_sc[tile] + jnp.dot(
                            p.astype(vbuf.dtype),
                            vbuf[here, pl.ds(tile * LANES, LANES)],
                            preferred_element_type=jnp.float32)
                        m_sc[tile] = m_new

    def trip(c, _):
        # a trip's code once a buffer: its VMEM and semaphore addresses are
        # then constants (PERF.md, PRs 55 and 59)
        parity = jax.lax.rem(before + c - c0, 2)
        for into in range(2):
            pl.when(parity == into)(functools.partial(a_trip, c, into))

    jax.lax.fori_loop(c0, c1, trip, None)
    walked[0] = before + c1 - c0

    l = l_sc[...]
    if sink_ref is not None:
        # the key without a value: one more term of the denominator
        l = l + jnp.exp(sink_ref[...] - m_sc[...])
    o_ref[0] = (acc_sc[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0] = m_sc[...] + jnp.log(jnp.maximum(l, 1e-30))


def paged_decode_formulation(heads: int, kv_heads: int, head_dim: int,
                             block_size: int, impl: str = "auto",
                             value_dim: int | None = None) -> str:
    """Which formulation :func:`paged_window_decode_attention` takes at
    these shapes: ``"paged_attn"`` (the kernel) or ``"plain"`` (the gather
    of every table column).  The kernel takes K/V heads of 64 (two a lane
    tile: gpt, lfm2), 128 (afmoe, jamba, evabyte, nemotron_h) or 256 (two
    tiles a head: qwen3_next) under values as wide, or keys of 192 over
    values of 128 (mimo); up to 32 query heads a K/V head; a block size that
    divides 128.  A test of shapes and of ``impl`` alone, so a program can
    say what it was built with (``serve.model``)."""
    if (value_dim or head_dim) == head_dim:
        # a tile is two K/V heads of 64 or one of 128; a head of 256 is two
        # tiles, side by side in the row
        fits = (head_dim in (64, LANES, 2 * LANES)
                and kv_heads * head_dim % LANES == 0)
    else:
        # keys a tile and a half wide over values of one tile: the heads'
        # remainders lie two a tile after their whole tiles (``lay_heads``)
        fits = (value_dim == LANES and _tile_split(head_dim) == LANES
                and head_dim - LANES == LANES // 2 and kv_heads % 2 == 0)
    fits = (fits and heads // kv_heads <= 32
            and PAGED_ROWS % block_size == 0)
    return "paged_attn" if use_kernel(impl) and fits else "plain"


def paged_window_decode_attention(
    q: jax.Array,             # (B, H, D) one query a slot
    k_pool: jax.Array,        # (L_group, rows, Hkv * D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32
    attend_lens: jax.Array,   # (B,) keys a slot attends, this step's included
    *,
    layer: int,
    block_size: int,
    window: int | None = None,
    impl: str = "auto",
    interpret: bool | None = None,
    sink: jax.Array | None = None,   # (H,) a key without a value a head
    lo: jax.Array | None = None,     # (B,) the first row a slot attends
    with_lse: bool = False,
) -> jax.Array:
    """Single-token decode attention that reads only what each slot
    attends: the blocks holding rows ``[max(len - window, 0), len)``, so
    ``window`` + one block at most on a window layer and the slot's resident
    blocks on a full one.  ``lo`` names each slot's first attended row
    itself (a tumbling window's start: ``window`` then only bounds the
    walk); ``with_lse`` returns ``(o, lse)``, the output in float32 and the
    log of each head's denominator (B, H), for a caller that merges several
    walks under one softmax (:func:`merge_softmax_parts`) — both are the
    kernel's alone.

    The kernel (``name="paged_attn"``) has the page tables, lengths and
    first rows prefetched into SMEM and the pools left in HBM; grid
    ``(slot,)``.  A slot's rows are walked in a loop of trips read from its
    length, from the stretch of ``PAGED_STRETCH`` rows that holds its first
    attended row to the one that holds its last — a table column no slot
    holds costs nothing — over two K and two V buffers in VMEM: a trip starts
    the next stretch's block copies, this slot's or the next slot's first,
    before it waits for its own, and folds its 128 rows
    into a running softmax, one 128-lane tile of the pool's row after the
    other on the MXU.  The blocks of 128 rows are copied under one test
    (before a slot's first column its first block again, past its last its
    last: the mask discards the rows), and only where the 128 hold a row the
    slot attends.

    A tile is one K/V
    head of 128, two of 64 or half of one of 256 (Qwen3-Next: a head's
    scores are the sum of its two tiles' products and each of its V tiles
    takes the same probabilities), and the query heads that attend it are the
    rows of one small product: head ``i`` of the tile keeps its query in
    lanes ``[i * D, (i + 1) * D)`` of its rows and zeros in the others, so
    the product over all 128 lanes is that head's scores, and its output is
    the same lanes of the same rows (the other lanes, its weights on the
    neighbour's values, are dropped).  A slot that attends nothing — every
    inactive slot of a decode program (``serve.model``) — returns zeros, in
    both formulations, and costs the kernel a grid step without a trip.
    Needs ``D`` of 64, 128 or 256, ``H // Hkv <= 32`` (a tile's
    query heads are its rows: 24 at 20 on 1), a block size that divides 128
    and a pool row of whole tiles (:func:`paged_decode_formulation`); other
    shapes, and ``impl="xla"``, take the plain formulation.

    Keys of 192 over values of 128 (MiMo-V2; the K row as :func:`lay_heads`
    stores it): a tile is one K/V head, whose scores are two products — the
    query's first 128 values against the head's whole K tile, its last 64
    against the head's half of a remainder tile (zeros in the neighbour's
    lanes) — and whose output is the one V tile.  ``sink`` (H,), a learned
    bias a query head, joins the softmax as a key with no value: ``acc / (l
    + exp(b_h - m))`` in the kernel's last division."""
    b, h, d = q.shape
    width = k_pool.shape[-1]
    h_kv = width // d
    dv = v_pool.shape[-1] // h_kv
    g = h // h_kv
    if paged_decode_formulation(h, h_kv, d, block_size, impl,
                                dv) == "plain":
        # the plain formulation (gathers every table column): the tests'
        # yardstick for the kernel and the path off the TPU
        if lo is not None or with_lse:
            raise ValueError("lo= and with_lse= are the paged_attn kernel's")
        return paged_decode_attention(
            q, k_pool, v_pool, block_tables, attend_lens, layer=layer,
            block_size=block_size, window=window, sink=sink)
    if interpret is None:
        interpret = not on_tpu()
    lens = attend_lens.astype(jnp.int32)
    if lo is None:
        lo = (jnp.zeros_like(lens) if window is None
              else jnp.maximum(lens - window, 0))
    per_tile = max(LANES // d, 1)     # K/V heads a tile: 1, or 2 at D = 64
    tiles = v_pool.shape[-1] // LANES
    q_rows = -(-per_tile * g // 8) * 8
    pad_rows = ((0, 0), (0, 0), (0, q_rows - per_tile * g), (0, 0))
    rest_at = None
    head_tiles = d // LANES if d == dv and d > LANES else 1
    if head_tiles > 1:
        # (B, head, tile of the head, query of the head, lanes)
        qt = q.reshape(b, h_kv, g, head_tiles, LANES).swapaxes(2, 3).reshape(
            b, tiles, g, LANES)
    elif d > LANES:
        # (B, head, part, query of the head, lanes): a head's first 128
        # values, then its last 64 in its own half of the remainder tile
        rest_at = h_kv * LANES
        qh = q.reshape(b, h_kv, g, d)
        own = (jnp.arange(h_kv)[:, None] % 2
               == jnp.arange(2)[None, :])[:, None, :, None]
        rest = jnp.where(own, qh[:, :, :, None, LANES:], 0)
        qt = jnp.stack([qh[..., :LANES], rest.reshape(b, h_kv, g, LANES)],
                       axis=2).reshape(b, 2 * h_kv, g, LANES)
    else:
        # (B, tile, head of the tile, query of the head, lanes of a head,
        # D): each head's query in its own lanes of the tile, zeros in the
        # others
        own = jnp.eye(per_tile, dtype=bool)[:, None, :, None]
        qt = jnp.where(own, q.reshape(b, tiles, per_tile, g, 1, d), 0)
        qt = qt.reshape(b, tiles, per_tile * g, LANES)
    qt = jnp.pad(qt, pad_rows)
    if sink is not None:
        # a row's bias across its lanes, as the running maximum and sum lie
        sink = sink.astype(jnp.float32).reshape(1, tiles // head_tiles,
                                                per_tile * g, 1)
        if head_tiles > 1:
            sink = sink.repeat(head_tiles, axis=1)
        sink = jnp.broadcast_to(jnp.pad(sink, pad_rows)[0],
                                (tiles, q_rows, PAGED_ROWS))
    out = _paged_attn_call(
        block_tables.astype(jnp.int32), lens, lo,
        jnp.full((1,), layer, jnp.int32), qt, k_pool, v_pool, sink,
        block_size=block_size, scale=d ** -0.5, interpret=interpret,
        rest_at=rest_at, with_lse=with_lse, head_tiles=head_tiles)
    lse = None
    if with_lse:
        # a row's log-denominator lies across its lanes: one lane of it
        out, lse = out
        lse = lse[:, ::head_tiles, :per_tile * g, 0].reshape(b, h)
    if head_tiles > 1:
        out = out[:, :, :g].reshape(b, h_kv, head_tiles, g, LANES).swapaxes(
            2, 3).reshape(b, h, d)
    elif d > LANES:
        out = out[:, :, :g].reshape(b, h, dv)
    else:
        out = out[:, :, :per_tile * g].reshape(
            b, tiles, per_tile, g, per_tile, d)
        out = jnp.where(own, out, 0).sum(axis=4).reshape(b, h, d)
    return (out, lse) if with_lse else out


@functools.partial(jax.jit, static_argnames=(
    "block_size", "scale", "interpret", "rest_at", "with_lse", "head_tiles"))
def _paged_attn_call(tables, lens, lo, layer, qt, k_pool, v_pool, sink=None,
                     *, block_size, scale, interpret, rest_at=None,
                     with_lse=False, head_tiles=1):
    """The kernel's call.  A jitted function of its own with the layer as a
    prefetched scalar, so that the layers of a program that call it at the
    same shapes share one trace and one lowering of the body (0.8 s a call
    otherwise: 20 s of GPT-2 medium's start-up)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, q_tiles, q_rows, _ = qt.shape
    tiles = v_pool.shape[-1] // LANES
    stretch = PAGED_STRETCH

    def blk(n):
        return pl.BlockSpec((1, n, q_rows, LANES), lambda s, *_: (s, 0, 0, 0))

    whole = [] if sink is None else [pl.BlockSpec(
        sink.shape, lambda s, *_: (0, 0, 0))]
    out_specs = blk(tiles)
    out_shape = jax.ShapeDtypeStruct((b, tiles, q_rows, LANES), qt.dtype)
    if with_lse:
        # float32 out beside the log-denominators: the caller merges walks
        out_shape = [jax.ShapeDtypeStruct(out_shape.shape, jnp.float32)] * 2
        out_specs = [out_specs] * 2
    return pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, block_size=block_size, scale=scale,
            rest_at=rest_at, with_lse=with_lse, head_tiles=head_tiles),
        name="paged_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b,),
            in_specs=[blk(q_tiles), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY), *whole],
            out_specs=out_specs,
            scratch_shapes=[
                *(pltpu.VMEM((stretch, pool.shape[-1]), pool.dtype)
                  for pool in (k_pool, v_pool, k_pool, v_pool)),
                pltpu.SemaphoreType.DMA((2, 2, stretch // PAGED_ROWS)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((tiles, q_rows, PAGED_ROWS), jnp.float32),
                pltpu.VMEM((tiles, q_rows, PAGED_ROWS), jnp.float32),
                pltpu.VMEM((tiles, q_rows, LANES), jnp.float32),
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, lens, lo, layer, qt, k_pool, v_pool,
      *(() if sink is None else (sink,)))


# ---------------------------------------------------------------------------
# Latent attention: one cached row a token that every query head shares
# ---------------------------------------------------------------------------
#
# A latent (MLA) layer caches, a token, ``c_kv`` (``rank`` values, after its
# norm) and the one rotated ``k_rope`` all heads share: a row ``[c_kv |
# k_rope]`` of ``rank + rope`` values in ONE pool (no V pool: the values are
# the first ``rank`` lanes of the same row).  Head ``i``'s key and value are
# ``c_kv W_UK_i`` and ``c_kv W_UV_i``.  Two forms of the same mathematics:
#
# - *absorbed*: ``q_abs_i = q_nope_i W_UK_i^T`` (``rank`` wide), scores ``[q_abs
#   | q_rope] . row``, output ``(sum_j p_j c_kv_j) W_UV_i``: every head reads
#   the row as it lies, 2 * (rank + rope + rank) FLOPs a head a pair;
# - *decompressed*: ``k_nope, v = c_kv W_UK, c_kv W_UV`` for a stretch of the
#   context, then plain attention at 2 * (nope + rope + v) FLOPs a head a
#   pair, plus the decompression of every context row once a chunk.
#
# Decode absorbs (a token a slot: decompressing a slot's context for one
# query would be 2 * rank * H * (nope + v) FLOPs a key); a prefill chunk
# decompresses (measured faster at every chunk width and context: PERF.md
# section 4).


def _pad_lanes(x, to: int):
    """``x`` with zeros after its last dimension, ``to`` wide."""
    pad = to - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _latent_queries(q_nope, q_rope, w_uk, width):
    """``[q_nope W_UK^T | q_rope | 0]`` (..., H, width): the query in the
    cached row's own layout, in the stored type (the absorbed query is
    rounded to it: the one rounding the non-absorbed form does not have)."""
    with jax.named_scope("absorb"):
        q_abs = jnp.einsum("...hn,rhn->...hr", q_nope, w_uk,
                           preferred_element_type=jnp.float32
                           ).astype(q_nope.dtype)
        return _pad_lanes(jnp.concatenate([q_abs, q_rope], axis=-1), width)


def _latent_values(o_lat, w_uv, dtype):
    with jax.named_scope("v_up"):
        return jnp.einsum("...hr,rhv->...hv", o_lat.astype(dtype), w_uv,
                          preferred_element_type=jnp.float32).astype(dtype)


#: context rows a step of the chunk kernel copies, decompresses and folds
#: into the running softmax: the plain loop's stretch too
LATENT_STRETCH = 512
#: heads a grid step of the chunk kernel holds, all the chunk's queries of
#: each resident: a stretch is copied once a step and decompressed once a head
LATENT_CHUNK_HEADS = 8
#: query rows of a head whose scores are formed at a time
LATENT_CHUNK_QUERIES = 512
#: VMEM the chunk kernel may take (a v5e has 128 MiB, 16 of them scoped by
#: default): the resident queries, outputs and softmax state of its heads
LATENT_CHUNK_VMEM = 96 << 20


def _plain_latent_chunk(q_nope, q_rope, start, pool, table_row, *, w_uk, w_uv,
                        layer, block_size, scale):
    """The plain formulation: a ``fori_loop`` over the stretches, each one's
    (H, T, stretch) float32 scores and probabilities through HBM.  The
    kernel's yardstick, the path off the TPU and for shapes that do not
    fit."""
    t, h, _ = q_nope.shape
    rank, rope_dim = w_uk.shape[0], q_rope.shape[-1]
    width = pool.shape[-1]
    kv_chunk = max(block_size, LATENT_STRETCH // block_size * block_size)
    bpc = kv_chunk // block_size
    nb = table_row.shape[0]
    qpos = start + jnp.arange(t, dtype=jnp.int32)
    dtype = q_nope.dtype

    def pages(c):
        blocks = table_row[jnp.minimum(c * bpc + jnp.arange(bpc), nb - 1)]
        return pool.reshape(pool.shape[0], -1, block_size, width)[
            layer, blocks].reshape(kv_chunk, width)

    def body(c, carry):
        m, l, acc = carry
        rows = pages(c)
        kpos = c * kv_chunk + jnp.arange(kv_chunk, dtype=jnp.int32)
        c_kv = rows[:, :rank]
        k_nope = jnp.einsum("kr,rhn->khn", c_kv, w_uk,
                            preferred_element_type=jnp.float32).astype(dtype)
        values = jnp.einsum("kr,rhv->khv", c_kv, w_uv,
                            preferred_element_type=jnp.float32).astype(dtype)
        s = jnp.einsum("qhn,khn->hqk", q_nope, k_nope,
                       preferred_element_type=jnp.float32) \
            + jnp.einsum("qhr,kr->hqk", q_rope,
                         rows[:, rank:rank + rope_dim],
                         preferred_element_type=jnp.float32)
        ok = (kpos[None, :] <= qpos[:, None])[None]
        s = jnp.where(ok, s * scale, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        pv = jnp.einsum("hqk,khd->hqd", p.astype(dtype), values,
                        preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(-1), acc * alpha[..., None] + pv

    init = (jnp.full((h, t), NEG_INF, jnp.float32),
            jnp.zeros((h, t), jnp.float32),
            jnp.zeros((h, t, w_uv.shape[-1]), jnp.float32))
    _, l, acc = jax.lax.fori_loop(
        0, -(-(start + t) // kv_chunk), body, init)
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).transpose(1, 0, 2)
    return out.astype(dtype)


def _latent_chunk_kernel(table_ref, start_ref, layer_ref, qn_ref, qr_ref,
                         wk_ref, wv_ref, pool_hbm, o_ref, buf, sem, q_sc,
                         wk_sc, wv_sc, k_sc, v_sc, m_sc, l_sc, acc_sc, *,
                         block_size, rank, q_tile, scale, bias=None):
    """``bias`` = (the (T, S) float32 array in HBM, its two VMEM buffers of
    (T, stretch), their semaphores): 0 where a query attends a row,
    ``NEG_INF`` where it does not (a selection; the causal mask is in it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, t, q_width = q_sc.shape
    stretch = buf.shape[1]
    bps = stretch // block_size                # blocks a stretch
    nb = table_ref.shape[0]
    nope, v = wk_sc.shape[-1], wv_sc.shape[-1]  # whole lane tiles
    rope = qr_ref.shape[-1] // heads
    start, layer = start_ref[0], layer_ref[0]
    end = start + t
    n = (end + stretch - 1) // stretch         # stretches to the chunk's end
    dtype = q_sc.dtype

    # blocks past the chunk's end are not copied and leave their rows as
    # they were: keep them finite (their probabilities are zeros)
    buf[...] = jnp.zeros_like(buf)
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    # the operands come as the model holds them, a head's values side by
    # side with the next head's: a head's own, head-major, once a step (the
    # query in the decompressed row's layout, [q_nope | q_rope | 0])
    for h in range(heads):
        q_sc[h, :, :nope] = qn_ref[:, h * nope:(h + 1) * nope]
        q_sc[h, :, nope:nope + rope] = qr_ref[:, h * rope:(h + 1) * rope]
        if nope + rope < q_width:
            q_sc[h, :, nope + rope:] = jnp.zeros(
                (t, q_width - nope - rope), dtype)
        wk_sc[h] = wk_ref[:, h * nope:(h + 1) * nope]
        wv_sc[h] = wv_ref[:, h * v:(h + 1) * v]

    def copies(c, slot, go):
        """``go`` every copy of stretch ``c`` into buffer ``slot`` whose
        block holds a row before the chunk's end."""
        for j in range(bps):
            b0 = c * stretch + j * block_size
            blk = table_ref[jnp.minimum(b0 // block_size, nb - 1)]
            cp = pltpu.make_async_copy(
                pool_hbm.at[layer, pl.ds(blk * block_size, block_size)],
                buf.at[slot, pl.ds(j * block_size, block_size)],
                sem.at[slot, j])
            pl.when(b0 < end)(functools.partial(go, cp))
        if bias is not None:
            go(pltpu.make_async_copy(
                bias[0].at[:, pl.ds(pl.multiple_of(c * stretch, stretch),
                                    stretch)],
                bias[1].at[slot], bias[2].at[slot]))

    def fold(h, i, first, masked, slot=None):
        """Query tile ``i`` of head ``h`` against the decompressed stretch."""
        rows = pl.ds(pl.multiple_of(i * q_tile, q_tile), q_tile)
        s = jax.lax.dot_general(
            q_sc[h, rows, :], k_sc[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (q_tile, stretch)
        if bias is not None:
            ok = bias[1][slot, rows, :] == 0.0
            s = jnp.where(ok, s, NEG_INF)
        elif masked:
            qpos = start + i * q_tile + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            ok = first + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1) <= qpos
            s = jnp.where(ok, s, NEG_INF)
        # the running maximum and sum are kept replicated across 128 lanes
        m_prev = m_sc[h, rows, :]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - jnp.concatenate([m_new] * (stretch // LANES), axis=1))
        if masked or bias is not None:
            p = jnp.where(ok, p, 0.0)
        l_sc[h, rows, :] = alpha * l_sc[h, rows, :] \
            + p.sum(axis=1, keepdims=True)
        m_sc[h, rows, :] = m_new
        pv = jnp.dot(p.astype(dtype), v_sc[...],
                     preferred_element_type=jnp.float32)
        acc_sc[h, rows, :] = acc_sc[h, rows, :] * jnp.concatenate(
            [alpha] * (pv.shape[1] // LANES), axis=1) + pv

    def heads_of(first, slot, masked):
        """Every head of the step against stretch ``first`` in ``slot``:
        the stretch decompressed once a head, then its query tiles."""
        def head(h, _):
            c_kv = buf[slot, :, :rank]
            k_sc[:, :nope] = jnp.dot(
                c_kv, wk_sc[h], preferred_element_type=jnp.float32
            ).astype(dtype)
            v_sc[...] = jnp.dot(
                c_kv, wv_sc[h], preferred_element_type=jnp.float32
            ).astype(dtype)

            def tile(i, _):
                if masked:
                    # a tile whose last query precedes the stretch attends
                    # none of it
                    pl.when(first <= start + (i + 1) * q_tile - 1)(
                        lambda: fold(h, i, first, True, slot))
                else:
                    fold(h, i, first, False, slot)

            jax.lax.fori_loop(0, t // q_tile, tile, None)

        jax.lax.fori_loop(0, heads, head, None)

    copies(0, 0, lambda cp: cp.start())

    def stretch_body(c, _):
        slot = jax.lax.rem(c, 2)
        first = c * stretch

        # the next stretch's copies run under this stretch's arithmetic
        @pl.when(c + 1 < n)
        def _():
            copies(c + 1, 1 - slot, lambda cp: cp.start())

        copies(c, slot, lambda cp: cp.wait())
        # k_rope (and the row's zero lanes) is every head's
        k_sc[:, nope:] = buf[slot, :, rank:]
        # only a stretch that reaches past the chunk's first query is masked
        diagonal = first + stretch - 1 > start
        pl.when(diagonal)(lambda: heads_of(first, slot, True))
        pl.when(jnp.logical_not(diagonal))(
            lambda: heads_of(first, slot, False))

    jax.lax.fori_loop(0, n, stretch_body, None)

    for h in range(heads):
        o_ref[:, h * v:(h + 1) * v] = (acc_sc[h] / jnp.concatenate(
            [jnp.maximum(l_sc[h], 1e-30)] * (v // LANES), axis=1)
        ).astype(o_ref.dtype)


def _latent_chunk_heads(heads: int, chunk: int, nope: int, rope: int,
                        tail: int, v: int, itemsize: int) -> int:
    """Heads a grid step holds: the most that divide ``heads``, up to
    ``LATENT_CHUNK_HEADS``, whose queries and outputs (two buffers each, the
    pipeline's), head-major queries and float32 softmax state fit half the
    kernel's VMEM; of those, one whose ``q_rope`` side by side are whole
    lane tiles where there is one (the others have it padded)."""
    a_head = chunk * (itemsize * (2 * (nope + tail) + nope + tail + 2 * v)
                      + 4 * (v + 2 * LANES))
    fit = max(1, min(LATENT_CHUNK_HEADS, (LATENT_CHUNK_VMEM // 2) // a_head))
    steps = [g for g in range(1, fit + 1) if heads % g == 0]
    return max([g for g in steps if g * rope % LANES == 0] or steps)


@functools.partial(jax.jit, static_argnames=(
    "heads", "block_size", "rank", "stretch", "heads_step", "q_tile",
    "scale", "interpret"))
def _latent_chunk_call(table_row, start, layer, q_nope, q_rope, wk, wv, pool,
                       bias=None, *, heads, block_size, rank, stretch,
                       heads_step, q_tile, scale, interpret):
    """The chunk kernel's call: a jitted function of its own with the layer
    as a prefetched scalar, so the layers of a program share one lowering.
    The operands are 2-D as the model holds them, ``heads`` side by side.
    With ``bias`` (T, S) float32 — 0 where a query attends a row, ``NEG_INF``
    where not — the kernel walks the same stretches and attends under it
    (``name="masked_latent_chunk_attn"``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = q_nope.shape[0]
    nope, rope, v = (x.shape[1] // heads for x in (q_nope, q_rope, wv))
    width = pool.shape[-1]
    g, dtype = heads_step, q_nope.dtype
    q_width = nope + width - rank

    def a_step(rows, lanes):
        return pl.BlockSpec((rows, g * lanes), lambda i, *_: (0, i))

    kernel = functools.partial(
        _latent_chunk_kernel, block_size=block_size, rank=rank,
        q_tile=q_tile, scale=scale)
    operands = (q_nope, q_rope, wk, wv, pool)
    hbm, more = [pl.BlockSpec(memory_space=pl.ANY)], []
    if bias is not None:
        def kernel(*refs, plain=kernel):
            # (3 scalars, 4 blocks, the pool, the bias, the output, the ten
            # scratch buffers, the bias's two)
            *most, bias_buf, bias_sem = refs
            return plain(*most[:8], *most[9:],
                         bias=(most[8], bias_buf, bias_sem))

        operands += (bias,)
        hbm = hbm * 2
        more = [pltpu.VMEM((2, t, stretch), jnp.float32),
                pltpu.SemaphoreType.DMA((2,))]
    return pl.pallas_call(
        kernel,
        name="latent_chunk_attn" if bias is None
        else "masked_latent_chunk_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(heads // g,),
            in_specs=[a_step(t, nope), a_step(t, rope), a_step(rank, nope),
                      a_step(rank, v), *hbm],
            out_specs=a_step(t, v),
            scratch_shapes=[
                pltpu.VMEM((2, stretch, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2, stretch // block_size)),
                pltpu.VMEM((g, t, q_width), dtype),
                pltpu.VMEM((g, rank, nope), dtype),
                pltpu.VMEM((g, rank, v), dtype),
                pltpu.VMEM((stretch, q_width), dtype),
                pltpu.VMEM((stretch, v), dtype),
                pltpu.VMEM((g, t, LANES), jnp.float32),
                pltpu.VMEM((g, t, LANES), jnp.float32),
                pltpu.VMEM((g, t, v), jnp.float32),
                *more,
            ]),
        out_shape=jax.ShapeDtypeStruct((t, heads * v), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=LATENT_CHUNK_VMEM),
        interpret=interpret,
    )(table_row, start, layer, *operands)


def paged_latent_chunk_formulation(block_size: int, width: int, rank: int,
                                   chunk: int, impl: str = "auto") -> str:
    """Which formulation :func:`paged_latent_chunk_attention` takes:
    ``"latent_chunk_attn"`` (the kernel) or ``"plain"`` (the loop whose
    scores go through HBM).  The kernel wants a stretch of whole blocks and
    whole lane tiles, ``c_kv`` and the rest of the row (``k_rope`` and its
    zero lanes) each whole lane tiles, and a chunk of whole bf16 sublane
    tiles that its query tiles divide."""
    fits = (LATENT_STRETCH % block_size == 0 and LATENT_STRETCH % LANES == 0
            and rank % LANES == 0 and width > rank
            and (width - rank) % LANES == 0 and chunk % 16 == 0
            and chunk % min(chunk, LATENT_CHUNK_QUERIES) == 0)
    return "latent_chunk_attn" if use_kernel(impl) and fits else "plain"


def paged_latent_chunk_attention(
    q_nope: jax.Array,       # (T, H, nope): one slot's chunk of queries
    q_rope: jax.Array,       # (T, H, rope), rotated
    start,                   # int32 scalar: position of the first query
    pool: jax.Array,         # (L_group, rows, >= rank + rope) latent rows
    table_row: jax.Array,    # (max_blocks,) the slot's page-table row
    *,
    w_uk: jax.Array,         # (rank, H, nope)
    w_uv: jax.Array,         # (rank, H, v)
    layer: int,
    block_size: int,
    scale: float,
    impl: str = "auto",
    interpret: bool | None = None,
    bias: jax.Array | None = None,
) -> jax.Array:
    """Chunk-prefill latent attention of one slot against its latent pages
    (the chunk's own rows already written), ``(T, H, v)``: the context up to
    the chunk's end in stretches of ``LATENT_STRETCH`` rows (whole blocks
    of them), each decompressed with ``W_UK`` / ``W_UV`` (above) and
    folded into a running softmax — bf16 operands, float32 accumulation,
    ``k_nope``, the values and the probabilities rounded to the stored type.

    The kernel (``name="latent_chunk_attn"``; the page-table row, ``start``
    and the layer prefetched into SMEM, the pool left in HBM; grid over
    groups of ``LATENT_CHUNK_HEADS`` heads, all ``T`` queries of each
    resident) walks the stretches in a loop of ``ceil((start + T) /
    stretch)`` trips: a stretch's blocks are copied into one of two VMEM
    buffers, the next stretch's copies started before this one's arithmetic;
    it is decompressed once a head (``c_kv W_UK_h``, ``c_kv W_UV_h``), and
    each tile of ``LATENT_CHUNK_QUERIES`` queries forms its scores ``[q_nope
    | q_rope] . [k_nope | k_rope]`` as one product, the running maximum, sum
    and accumulator in VMEM scratch: no score goes to HBM.  Only the
    stretches that reach past the chunk's first query are masked (and a
    query tile that precedes such a stretch skips it); blocks past the
    chunk's end are not copied.  Other shapes
    (:func:`paged_latent_chunk_formulation`), the CPU and ``impl="xla"``
    take the plain loop.  Scope ``paged_attn``."""
    t, h, nope = q_nope.shape
    rank, v = w_uk.shape[0], w_uv.shape[-1]
    width = pool.shape[-1]
    form = paged_latent_chunk_formulation(block_size, width, rank, t, impl)
    with jax.named_scope("paged_attn"):
        if form == "plain":
            return _plain_latent_chunk(
                q_nope, q_rope, start, pool, table_row, w_uk=w_uk, w_uv=w_uv,
                layer=layer, block_size=block_size, scale=scale)
        if interpret is None:
            interpret = not on_tpu()
        # the operands as the model holds them, the heads side by side (a
        # reshape): nothing but the published widths' no-op pads around the
        # call.  Heads and values of no whole lane tile are padded to one,
        # q_rope where the step's heads side by side are not whole tiles
        nope_p, v_p = -(-nope // LANES) * LANES, -(-v // LANES) * LANES
        rope, tail = q_rope.shape[-1], width - rank
        g = _latent_chunk_heads(h, t, nope_p, rope, tail, v_p,
                                q_nope.dtype.itemsize)
        rope_p = rope if g * rope % LANES == 0 else tail

        def flat(x, to):
            return _pad_lanes(x, to).reshape(x.shape[0], h * to)

        if bias is not None:
            # whole stretches of it (nothing at the cells' context)
            lanes = table_row.shape[0] * block_size
            bias = jnp.pad(
                bias, ((0, 0), (0, -lanes % LATENT_STRETCH)),
                constant_values=NEG_INF)
        out = _latent_chunk_call(
            table_row.astype(jnp.int32),
            jnp.reshape(start, (1,)).astype(jnp.int32),
            jnp.full((1,), layer, jnp.int32), flat(q_nope, nope_p),
            flat(q_rope, rope_p), flat(w_uk, nope_p), flat(w_uv, v_p), pool,
            bias, heads=h, block_size=block_size, rank=rank,
            stretch=LATENT_STRETCH, heads_step=g,
            q_tile=min(t, LATENT_CHUNK_QUERIES), scale=scale,
            interpret=interpret)
        return out.reshape(t, h, v_p)[..., :v]


def paged_latent_formulation(block_size: int, width: int, rank: int,
                             impl: str = "auto") -> str:
    """Which formulation :func:`paged_latent_decode_attention` takes:
    ``"paged_latent_attn"`` (the kernel) or ``"plain"`` (the gather of every
    table column)."""
    fits = (PAGED_ROWS % block_size == 0 and rank % LANES == 0
            and width > rank)
    return "paged_latent_attn" if use_kernel(impl) and fits else "plain"


def _plain_latent_decode(q, pool, block_tables, attend_lens, *, layer,
                         block_size, rank, scale):
    """The plain formulation: every table column gathered, ``(B, H, rank)``
    in float32.  The kernel's yardstick and the path off the TPU."""
    b, max_blocks = block_tables.shape
    num_layers, rows, width = pool.shape
    x = pool.reshape(num_layers, rows // block_size, block_size,
                     width)[layer, block_tables].reshape(b, -1, width)
    s = jnp.einsum("bhw,bkw->bhk", q, x,
                   preferred_element_type=jnp.float32) * scale
    valid = (jnp.arange(x.shape[1])[None, :] < attend_lens[:, None])[:, None]
    # a slot that attends nothing returns zeros, as the kernel's does
    w = jnp.where(valid, jax.nn.softmax(jnp.where(valid, s, NEG_INF),
                                        axis=-1), 0.0)
    return jnp.einsum("bhk,bkr->bhr", w.astype(q.dtype), x[..., :rank],
                      preferred_element_type=jnp.float32)


def _paged_latent_kernel(tables_ref, lens_ref, layer_ref, q_ref, pool_hbm,
                         o_ref, buf, sem, walked, m_sc, l_sc, acc_sc, *,
                         block_size, rank, scale):
    """A grid step is a slot; its rows are walked in a loop of ``ceil(rows /
    stretch)`` trips, read from the prefetched lengths, over two buffers: a
    trip starts the next stretch's copies — this slot's, or the first of the
    next slot's — before it waits for its own, so they run under its
    arithmetic.  The buffers, the semaphores and ``walked`` (stretches of the
    slots before: the buffer's parity) outlive a grid step; every copy
    started is waited for by the trip that folds it.  A slot that holds
    nothing (a length of 0: a slot nobody holds, as the decode programs hand
    it over) takes no trip: it starts the next slot's first stretch and
    closes an empty running softmax, which is zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    slots = tables_ref.shape[0]
    stretch, rows = buf.shape[1], PAGED_ROWS
    parts = stretch // rows
    heads = q_ref.shape[1]
    n, layer = lens_ref[s], layer_ref[0]
    trips = (n + stretch - 1) // stretch

    @pl.when(s == 0)
    def _():
        # rows past a slot's last are masked, not skipped: keep them finite
        buf[...] = jnp.zeros_like(buf)
        walked[0] = 0

    before = walked[0]
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)

    def copies(of, c, into, start):
        """Start, or wait for, the copies of stretch ``c`` of slot ``of``
        into buffer ``into``: the blocks of a part (``rows`` rows) together
        and without a branch each, if the part holds a row the slot attends;
        past the slot's last column its last block is copied again (the mask
        discards the rows).  A slot past the last has no rows."""
        row = jnp.minimum(of, slots - 1)
        live = jnp.where(of < slots, lens_ref[row], 0)
        last = jnp.maximum(live - 1, 0) // block_size

        def a_part(part):
            for j in range(rows // block_size):
                at = part * rows + j * block_size
                blk = tables_ref[row, jnp.minimum(
                    (c * stretch + at) // block_size, last)] if start else 0
                cp = pltpu.make_async_copy(
                    pool_hbm.at[layer, pl.ds(blk * block_size, block_size)],
                    buf.at[into, pl.ds(at, block_size)], sem.at[into, part])
                if start:
                    cp.start()
                else:
                    cp.wait()

        for part in range(parts):
            pl.when(c * stretch + part * rows < live)(
                functools.partial(a_part, part))

    # the walk's first stretch is slot 0's; a slot that holds nothing starts
    # the next slot's, which its last trip would have
    @pl.when((s == 0) | (trips == 0))
    def _():
        copies(s + (trips == 0).astype(jnp.int32), 0, jax.lax.rem(before, 2),
               True)

    def a_trip(c, into):
        more = c + 1 < trips
        copies(jnp.where(more, s, s + 1), jnp.where(more, c + 1, 0),
               1 - into, True)
        copies(s, c, into, False)
        for part in range(parts):
            first = c * stretch + part * rows

            @pl.when(first < n)
            def _(part=part, first=first):
                here = pl.ds(part * rows, rows)
                kpos = first + jax.lax.broadcasted_iota(
                    jnp.int32, (heads, rows), 1)
                valid = kpos < n
                sc = jax.lax.dot_general(
                    q_ref[0], buf[into, here, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (H, rows)
                sc = jnp.where(valid, sc, NEG_INF)
                m_prev = m_sc[...]
                m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
                l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
                m_sc[...] = m_new
                pv = p.astype(buf.dtype)
                for tile in range(rank // LANES):
                    lanes = pl.ds(tile * LANES, LANES)
                    acc_sc[:, lanes] = alpha * acc_sc[:, lanes] + jnp.dot(
                        pv, buf[into, here, lanes],
                        preferred_element_type=jnp.float32)

    def trip(c, _):
        # a trip's code once a buffer: a copy's VMEM and semaphore addresses
        # are then constants (6 % of the kernel at both cells' shapes)
        parity = jax.lax.rem(before + c, 2)
        for into in range(2):
            pl.when(parity == into)(functools.partial(a_trip, c, into))

    jax.lax.fori_loop(0, trips, trip, None)
    walked[0] = before + trips

    inv = 1.0 / jnp.maximum(l_sc[...], 1e-30)
    for tile in range(rank // LANES):
        lanes = pl.ds(tile * LANES, LANES)
        o_ref[0, :, lanes] = (acc_sc[:, lanes] * inv).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_size", "rank", "scale", "interpret"))
def _paged_latent_call(tables, lens, layer, q, pool, *, block_size, rank,
                       scale, interpret):
    """The kernel's call: a jitted function of its own with the layer as a
    prefetched scalar, so the layers of a program share one lowering."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, width = q.shape
    stretch = PAGED_LATENT_STRETCH
    return pl.pallas_call(
        functools.partial(
            _paged_latent_kernel, block_size=block_size, rank=rank,
            scale=scale),
        name="paged_latent_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[pl.BlockSpec((1, heads, width), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, rank), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, stretch, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2, stretch // PAGED_ROWS)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, PAGED_ROWS), jnp.float32),
                pltpu.VMEM((heads, PAGED_ROWS), jnp.float32),
                pltpu.VMEM((heads, rank), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, lens, layer, q, pool)


def paged_latent_decode_attention(
    q_nope: jax.Array,        # (B, H, nope) one query a slot
    q_rope: jax.Array,        # (B, H, rope), rotated
    pool: jax.Array,          # (L_group, rows, >= rank + rope) latent rows
    block_tables: jax.Array,  # (B, max_blocks) int32
    attend_lens: jax.Array,   # (B,) rows a slot attends, this step's included
    *,
    w_uk: jax.Array,          # (rank, H, nope)
    w_uv: jax.Array,          # (rank, H, v)
    layer: int,
    block_size: int,
    scale: float,
    impl: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token latent attention in the absorbed form, ``(B, H, v)``:
    reads only the blocks a slot holds, each once for all the heads.

    The kernel (``name="paged_latent_attn"``; page tables and lengths
    prefetched into SMEM, the pool left in HBM; grid ``(slot,)``) walks a
    slot's rows in a loop of ``ceil(rows / PAGED_LATENT_STRETCH)`` trips read
    from its length — a table column no slot holds costs nothing — over two
    VMEM buffers: a trip starts the next stretch's block copies, this slot's
    or the next slot's first, before it waits for its own, and folds its 4 x
    128 rows, 128 at a time, into a running softmax: the scores of all ``H``
    heads are one ``(H, width) x (width, 128)`` product — the heads are the
    rows of the left operand, so 32 heads fill the MXU's rows where
    grouped-query attention brings 8 — and the values are the first ``rank``
    lanes of the same rows, a 128-lane tile at a time.  A slot that attends
    nothing — every inactive slot of a decode program — returns zeros, in
    both formulations, and costs the kernel a grid step without a trip.
    Other shapes and ``impl="xla"`` take the plain
    gather.  Scopes ``absorb``, ``paged_attn``, ``v_up``."""
    rank, width = w_uk.shape[0], pool.shape[-1]
    q = _latent_queries(q_nope, q_rope, w_uk, width)
    lens = attend_lens.astype(jnp.int32)
    with jax.named_scope("paged_attn"):
        if paged_latent_formulation(block_size, width, rank, impl) == "plain":
            o_lat = _plain_latent_decode(
                q, pool, block_tables, lens, layer=layer,
                block_size=block_size, rank=rank, scale=scale)
        else:
            if interpret is None:
                interpret = not on_tpu()
            o_lat = _paged_latent_call(
                block_tables.astype(jnp.int32), lens,
                jnp.full((1,), layer, jnp.int32), q, pool,
                block_size=block_size, rank=rank, scale=scale,
                interpret=interpret)
    return _latent_values(o_lat, w_uv, q_nope.dtype)


# ---------------------------------------------------------------------------
# Sparse latent attention: a learned indexer picks the rows a query attends
# ---------------------------------------------------------------------------
#
# A latent layer with an indexer (DeepSeek sparse attention, ``models.joyai``
# with ``index_topk``) caches, beside the latent row, one *index key* a token
# (``index_dim`` values, shared by the indexer's heads) in a second pool of
# the same group.  A query at position ``t`` scores every cached key of its
# slot, ``I(t, s) = sum_j w_tj relu(qI_tj . kI_s)`` in float32, keeps the
# ``min(topk, t + 1)`` positions of largest score (ties to the lowest
# position: ``lax.top_k``'s order) and attends those rows of the latent pool
# alone, in the absorbed form (every head reads the gathered row as it lies).
# One formulation serves a prefill chunk (``T`` queries of one slot) and a
# decode step (one query a slot): queries ``(N, ...)``, each with the page
# table row of its slot.  Where nothing past ``topk`` is cached the selection
# is every row, and a prefill chunk takes the dense formulation above.

#: index keys a step of the indexer kernel scores, and the plain loop's
#: stretch: the (queries, heads, stretch) float32 products of a stretch are
#: all the plain loop holds at a time
INDEX_STRETCH = 512
#: queries of a prefill chunk a step of the indexer kernel holds
INDEX_QUERIES = 256
#: queries whose gathered rows are held at a time (128 x 2048 rows of 1,280 B
#: are 335 MB)
SPARSE_QUERIES = 128


def _plain_index_scores(q, w, keys):
    """``sum_j w[n, t, j] * relu(q[n, t, j] . keys[n, s])`` in float32, ``(N,
    T, S)``: a loop over stretches of ``INDEX_STRETCH`` keys."""
    n, t, _, _ = q.shape
    s = keys.shape[1]
    stretch = min(s, INDEX_STRETCH)
    parts = -(-s // stretch)
    keys = jnp.pad(keys, ((0, 0), (0, parts * stretch - s), (0, 0)))

    def one(ks):                            # (N, stretch, D)
        dots = jnp.einsum("nthd,nsd->nths", q, ks,
                          preferred_element_type=jnp.float32)
        return (jnp.maximum(dots, 0.0) * w[..., None]).sum(2)

    out = jax.lax.map(one, keys.reshape(n, parts, stretch, -1)
                      .transpose(1, 0, 2, 3))   # (parts, N, T, stretch)
    return out.transpose(1, 2, 0, 3).reshape(n, t, parts * stretch)[..., :s]


def _index_chunk_kernel(end_ref, q_ref, w_ref, k_ref, o_ref, *, heads):
    """A tile of a chunk's queries against a stretch of keys: a head at a
    time, ``(queries, D) x (D, stretch)`` on the MXU, the weighted sum of the
    rectified products in float32.  A stretch past the chunk's last query
    scores nothing (zeros: the selection masks it)."""
    from jax.experimental import pallas as pl

    stretch = k_ref.shape[0]
    dim = k_ref.shape[1]

    @pl.when(pl.program_id(1) * stretch >= end_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(pl.program_id(1) * stretch < end_ref[0])
    def _():
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for h in range(heads):
            dots = jax.lax.dot_general(
                q_ref[:, h * dim:(h + 1) * dim], k_ref[...],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            acc = acc + w_ref[:, h:h + 1] * jnp.maximum(dots, 0.0)
        o_ref[...] = acc


def _index_step_kernel(lens_ref, q_ref, w_ref, k_ref, o_ref):
    """One query a slot: its heads are the rows of one ``(heads, D) x (D,
    stretch)`` product.  A stretch past the slot's rows scores nothing."""
    from jax.experimental import pallas as pl

    stretch = k_ref.shape[1]
    live = pl.program_id(1) * stretch < lens_ref[pl.program_id(0)]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        dots = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (heads, stretch)
        o_ref[0] = (w_ref[0] * jnp.maximum(dots, 0.0)).sum(
            axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_scores_call(lens, q, w, keys, *, interpret):
    """The indexer kernel's call (``name="index_scores"``): ``q`` (N, T, H,
    D), ``w`` (N, T, H) float32, ``keys`` (N, S, D) and ``lens`` (N,), the
    rows of ``keys`` that hold a key some query of the slot may attend."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, heads, dim = q.shape
    s = keys.shape[1]
    stretch = INDEX_STRETCH
    if t == 1:
        return pl.pallas_call(
            _index_step_kernel, name="index_scores",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(n, s // stretch),
                in_specs=[
                    pl.BlockSpec((1, heads, dim), lambda b, c, *_: (b, 0, 0)),
                    pl.BlockSpec((1, heads, 1), lambda b, c, *_: (b, 0, 0)),
                    # a stretch past the slot's rows is not fetched: the
                    # block index stays at the last live one
                    pl.BlockSpec((1, stretch, dim), lambda b, c, lens: (
                        b, jnp.minimum(
                            c, jnp.maximum(lens[b] - 1, 0) // stretch), 0))],
                out_specs=pl.BlockSpec((1, 1, stretch),
                                       lambda b, c, *_: (b, 0, c))),
            out_shape=jax.ShapeDtypeStruct((n, 1, s), jnp.float32),
            interpret=interpret,
        )(lens, q[:, 0], w[:, 0, :, None], keys)
    tile = min(t, INDEX_QUERIES)
    return pl.pallas_call(
        functools.partial(_index_chunk_kernel, heads=heads),
        name="index_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(t // tile, s // stretch),
            in_specs=[
                pl.BlockSpec((tile, heads * dim), lambda i, c, *_: (i, 0)),
                pl.BlockSpec((tile, heads), lambda i, c, *_: (i, 0)),
                pl.BlockSpec((stretch, dim), lambda i, c, end: (
                    jnp.minimum(c, (end[0] - 1) // stretch), 0))],
            out_specs=pl.BlockSpec((tile, stretch), lambda i, c, *_: (i, c))),
        out_shape=jax.ShapeDtypeStruct((t, s), jnp.float32),
        interpret=interpret,
    )(lens, q[0].reshape(t, heads * dim), w[0], keys[0])[None]


def index_formulation(queries: int, slots: int, rows: int, dim: int,
                      impl: str = "auto") -> str:
    """Which formulation :func:`index_scores` takes: ``"index_scores"`` (the
    kernel) or ``"plain"``.  The kernel wants keys of whole lane tiles, a
    context of whole stretches, and either one query a slot or one slot's
    chunk of whole query tiles."""
    fits = (dim % LANES == 0 and rows % INDEX_STRETCH == 0
            and (queries == 1 or (slots == 1 and queries % 16 == 0 and
                                  queries % min(queries, INDEX_QUERIES) == 0)))
    return "index_scores" if use_kernel(impl) and fits else "plain"


def index_scores(q, w, keys, lens, *, impl: str = "auto",
                 interpret: bool | None = None):
    """The indexer's scores ``(N, T, S)`` in float32 of queries ``q`` (N, T,
    H, D) with head weights ``w`` (N, T, H) float32 against ``keys`` (N, S,
    D): ``sum_j w_j relu(q_j . k)``.  ``lens`` (N,) bounds the rows worth
    scoring (the kernel leaves zeros past them; the caller masks by position
    in any case).  Scope ``indexer``."""
    n, t, _, dim = q.shape
    with jax.named_scope("indexer"):
        if index_formulation(t, n, keys.shape[1], dim, impl) == "plain":
            return _plain_index_scores(q, w, keys)
        if interpret is None:
            interpret = not on_tpu()
        return _index_scores_call(lens.astype(jnp.int32), q, w, keys,
                                  interpret=interpret)


def select_rows(scores, counts, k: int):
    """The ``k`` positions of largest ``scores`` (N, S) among each query's
    first ``counts`` (N,), best first, ties to the lowest position
    (``lax.top_k``); a query with fewer than ``k`` candidates gets them all,
    and the rest of its ``k`` entries lie past ``counts`` (the attention
    masks them: its second result is how many are real).  Scope
    ``select``."""
    with jax.named_scope("select"):
        s = scores.shape[-1]
        ok = jnp.arange(s, dtype=jnp.int32)[None, :] < counts[:, None]
        # a sum of rectified products may be -0.0, which orders below 0.0
        # as bits and equal to it as a number: one zero
        scores = jnp.where(scores == 0.0, 0.0, scores)
        _, pos = jax.lax.top_k(jnp.where(ok, scores, -jnp.inf), k)
        return pos.astype(jnp.int32), jnp.minimum(counts, k).astype(jnp.int32)


#: queries a step of the selection kernel holds at most, their scores
#: resident.  A pass ends in a chain of ~180 ns a step whatever it counted
#: (the sum across lanes, the threshold's next bit, its spread back over the
#: lanes), which 32 queries share where 8 did
SELECT_QUERIES = 32
#: positions by which the selection kernel's walks differ in length: a step
#: takes the walk that ends with the stretch holding its last candidate.  A
#: walk is straight-line code over its positions (a loop over stretches with
#: a trip count read at run time pays ~85 ns an iteration on a v5e, the
#: work of 512 positions 5), and every walk is compiled: 4 at the cells'
#: 33,792 (17 of 2,048 ran three times slower than 8 of 4,224 or these 4,
#: and under 8,448 positions a step waits for its copies anyway: PERF.md
#: section 6, PR 53)
SELECT_STRETCH = 8448
_INT_MIN = -2 ** 31


def _plain_select_bias(scores, counts, k: int):
    """``lax.top_k`` and a scatter: the selection kernel's yardstick."""
    pos, real = select_rows(scores, counts, k)
    ok = jnp.arange(k, dtype=jnp.int32)[None, :] < real[:, None]
    n = scores.shape[0]
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(n)[:, None], pos].max(ok)
    return jnp.where(picked, 0.0, NEG_INF).astype(jnp.float32)


def select_walk(extent: int, rows: int) -> int:
    """The positions of a query's ``rows`` scores that the selection kernel
    walks in a step whose largest ``counts`` is ``extent``."""
    return min(-(-extent // SELECT_STRETCH) * SELECT_STRETCH, rows)


def _select_kernel(ext_ref, c_ref, s_ref, o_ref, key_sc, *, k, stretch):
    """The exact top ``k`` of each row's first ``counts`` scores without a
    sort: the scores as order-preserving integers, the ``k``-th largest found
    a bit at a time by counting (32 passes), then, among the entries equal to
    it, the position up to which they are taken (ties to the lowest
    position: one more bisection, over positions).

    ``ext_ref[step]``, the largest ``counts`` of the tile's rows, bounds
    every pass: the tile takes the walk that ends with the stretch of
    ``stretch`` positions holding position ``extent - 1``.  What lies past
    that stretch is neither read nor counted, and is ``NEG_INF`` in the
    result.  The second bisection (``bit_length(end - 1)`` passes) runs only
    where some row of the tile has more scores equal to its threshold than
    it still wants: a row with exactly as many takes them all."""
    from jax.experimental import pallas as pl

    s = key_sc.shape[1]
    counts = c_ref[...]                                 # (rows, 1)
    want = jnp.minimum(counts, k).astype(jnp.float32)

    def count(hit):
        return jnp.where(hit, 1.0, 0.0).sum(axis=1, keepdims=True)

    def walk(end):
        """The selection among the first ``end`` (static) positions."""
        x = s_ref[:, :end]
        x = jnp.where(x == 0.0, 0.0, x)                 # -0.0 -> 0.0
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        pos = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
        key_sc[:, :end] = jnp.where(pos < counts, key, jnp.int32(_INT_MIN))

        def score_bit(b, theta):
            # theta: the threshold's bits in the unsigned order, built from
            # the top; the largest value with at least ``want`` keys >= it
            cand = theta | (jnp.int32(1) << (31 - b))
            enough = count(
                key_sc[:, :end] >= (cand ^ jnp.int32(_INT_MIN))) >= want
            return jnp.where(enough, cand, theta)

        theta = jax.lax.fori_loop(
            0, 32, score_bit, jnp.zeros(counts.shape, jnp.int32)) \
            ^ jnp.int32(_INT_MIN)
        above = key_sc[:, :end] > theta
        left = want - count(above)      # of the equal ones, this many: >= 1
        top = max(1, (end - 1).bit_length())

        def position_bit(b, p):
            # the largest p with fewer than ``left`` equal keys before it:
            # the position of the last one taken
            cand = p | (jnp.int32(1) << (top - 1 - b))
            few = count((key_sc[:, :end] == theta) & (pos < cand)) < left
            return jnp.where(few, cand, p)

        last = jax.lax.cond(
            jnp.max(count(key_sc[:, :end] == theta) - left) > 0.0,
            lambda: jax.lax.fori_loop(0, top, position_bit,
                                      jnp.zeros(counts.shape, jnp.int32)),
            lambda: jnp.full(counts.shape, end, jnp.int32))
        picked = above | ((key_sc[:, :end] == theta) & (pos <= last))
        o_ref[:, :end] = jnp.where(picked, 0.0, NEG_INF)
        if end < s:
            o_ref[:, end:] = jnp.full((o_ref.shape[0], s - end), NEG_INF,
                                      o_ref.dtype)

    which = jnp.maximum(ext_ref[pl.program_id(0)] - 1, 0) // stretch
    for i in range(-(-s // stretch)):
        pl.when(which == i)(functools.partial(
            walk, min((i + 1) * stretch, s)))


@functools.partial(jax.jit, static_argnames=("k", "stretch", "interpret"))
def _select_bias_call(scores, counts, *, k, stretch, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, s = scores.shape
    rows = min(n, SELECT_QUERIES)
    counts = jnp.minimum(counts.astype(jnp.int32), s)
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k, stretch=stretch),
        name="select_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // rows,),
            in_specs=[pl.BlockSpec((rows, 1), lambda i, *_: (i, 0)),
                      pl.BlockSpec((rows, s), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((rows, s), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((n, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(counts.reshape(n // rows, rows).max(axis=1), counts.reshape(n, 1),
      scores)


def select_formulation(queries: int, impl: str = "auto") -> str:
    """Which formulation :func:`select_bias` takes: ``"select_rows"`` (the
    kernel: whole sublane tiles of queries that its tiles divide) or
    ``"plain"`` (``lax.top_k`` and a scatter)."""
    fits = queries % 8 == 0 and queries % min(queries, SELECT_QUERIES) == 0
    return "select_rows" if use_kernel(impl) and fits else "plain"


def select_bias(scores, counts, k: int, *, impl: str = "auto",
                interpret: bool | None = None):
    """The selection of :func:`select_rows` as a bias ``(N, S)`` float32: 0
    at the ``min(k, counts)`` selected positions of each query, ``NEG_INF``
    elsewhere (at and past ``counts`` always).  Scope ``select``.

    The kernel (``name="select_rows"``) walks a tile of ``SELECT_QUERIES``
    queries' scores up to the stretch of ``SELECT_STRETCH`` positions that
    holds the tile's largest ``counts`` and no further — a score at or past
    a query's ``counts`` may be anything, NaN too — and breaks ties by
    position only in a tile that has one across some query's cut
    (:func:`_select_kernel`)."""
    with jax.named_scope("select"):
        n, s = scores.shape
        if select_formulation(n, impl) == "plain":
            return _plain_select_bias(scores, counts, k)
        if interpret is None:
            interpret = not on_tpu()
        # whole lane tiles of positions (nothing at the cells' context)
        scores = jnp.pad(scores, ((0, 0), (0, -s % LANES)))
        return _select_bias_call(scores, counts, k=k, stretch=SELECT_STRETCH,
                                 interpret=interpret)[:, :s]


def _plain_sparse_latent(q, x, counts, *, rank, scale):
    """``q`` (N, H, W) against each query's own gathered rows ``x`` (N, K,
    W), of which the first ``counts`` are real: ``(N, H, rank)`` float32."""
    s = jnp.einsum("nhw,nkw->nhk", q, x,
                   preferred_element_type=jnp.float32) * scale
    ok = (jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
          < counts[:, None])[:, None]
    # a query with no real row returns zeros, as the kernel's does
    p = jnp.where(ok, jax.nn.softmax(jnp.where(ok, s, NEG_INF), axis=-1), 0.0)
    return jnp.einsum("nhk,nkr->nhr", p.astype(q.dtype), x[..., :rank],
                      preferred_element_type=jnp.float32)


def _sparse_latent_kernel(counts_ref, q_ref, x_ref, o_ref, *, rank, scale):
    from jax.experimental import pallas as pl

    x = x_ref[0]                                          # (K, W)
    s = jax.lax.dot_general(q_ref[0], x, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
        < counts_ref[pl.program_id(0)]
    s = jnp.where(ok, s, NEG_INF)
    p = jnp.where(ok, jnp.exp(s - s.max(axis=1, keepdims=True)), 0.0)
    inv = 1.0 / jnp.maximum(p.sum(axis=1, keepdims=True), 1e-30)
    pv = p.astype(x.dtype)
    for tile in range(rank // LANES):
        lanes = pl.ds(tile * LANES, LANES)
        o_ref[0, :, lanes] = (jnp.dot(
            pv, x_ref[0, :, lanes], preferred_element_type=jnp.float32)
            * inv).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def _sparse_latent_call(counts, q, x, *, rank, scale, interpret):
    """The sparse kernel's call (``name="sparse_latent_attn"``): a query a
    grid step, its heads the rows of one ``(H, W) x (W, K)`` product against
    its own ``K`` gathered rows, the values the first ``rank`` lanes of the
    same rows: no score goes to HBM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, heads, width = q.shape
    k = x.shape[1]
    return pl.pallas_call(
        functools.partial(_sparse_latent_kernel, rank=rank, scale=scale),
        name="sparse_latent_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n,),
            in_specs=[pl.BlockSpec((1, heads, width), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec((1, k, width), lambda i, *_: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, heads, rank), lambda i, *_: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((n, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(counts, q, x)


def sparse_latent_formulation(width: int, rank: int, k: int,
                              impl: str = "auto") -> str:
    """Which formulation :func:`sparse_latent_attention` takes:
    ``"sparse_latent_attn"`` (the kernel) or ``"plain"`` (the batched
    products whose scores go through HBM).  The kernel wants rows and
    ``c_kv`` of whole lane tiles and whole lane tiles of selected rows."""
    fits = rank % LANES == 0 and width % LANES == 0 and k % LANES == 0
    return "sparse_latent_attn" if use_kernel(impl) and fits else "plain"


def sparse_latent_attention(q, pool, rows, counts, *, layer, rank, scale,
                            impl: str = "auto",
                            interpret: bool | None = None):
    """Absorbed latent attention of queries ``q`` (N, H, W) each over its own
    pool rows ``rows`` (N, K) (row ids of ``pool[layer]``, the first
    ``counts`` real), ``(N, H, rank)`` float32.  The rows are gathered
    ``SPARSE_QUERIES`` queries at a time.  Scope ``paged_attn``."""
    n, k = rows.shape
    form = sparse_latent_formulation(pool.shape[-1], rank, k, impl)
    if interpret is None:
        interpret = not on_tpu()

    def some(args):
        qs, rs, cs = args
        x = pool[layer, rs]                               # (n, K, W)
        if form == "plain":
            return _plain_sparse_latent(qs, x, cs, rank=rank, scale=scale)
        return _sparse_latent_call(cs, qs, x, rank=rank, scale=scale,
                                   interpret=interpret)

    with jax.named_scope("paged_attn"):
        if n <= SPARSE_QUERIES or n % SPARSE_QUERIES:
            return some((q, rows, counts))
        parts = n // SPARSE_QUERIES
        out = jax.lax.map(some, (
            q.reshape(parts, SPARSE_QUERIES, *q.shape[1:]),
            rows.reshape(parts, SPARSE_QUERIES, k),
            counts.reshape(parts, SPARSE_QUERIES)))
        return out.reshape(n, *out.shape[2:])


# ---------------------------------------------------------------------------
# What a cached row is: the forms a layer group's pools take
# ---------------------------------------------------------------------------
#
# A model's configuration says which (``cfg.cache_rows``); ``serve.kv_cache``
# makes a group's pools ``widths`` wide (of which ``values`` are stored
# values, the rest lane padding) and ``serve.model``'s programs write the
# rows a block hands ``attend`` and read the pages back through the form's
# formulations.  Each puts its page walk in scope ``paged_attn``.


@dataclasses.dataclass(frozen=True)
class KVRows:
    """A token's K of all K/V heads in one pool, its V in another; a block
    calls ``attend(q, k, v)``, or ``attend(q, k, v, sink=b)`` where a learned
    bias a query head joins the softmax as a key with no value.  A V head
    may be narrower than a K head (``value_dim``; the output is as wide)."""

    heads: int
    kv_heads: int
    head_dim: int
    value_dim: int | None = None        # None: as wide as a K head
    #: each K/V head has rows of its own
    shared_row = False

    @property
    def widths(self) -> tuple[int, ...]:
        return (self.kv_heads * self.head_dim,
                self.kv_heads * (self.value_dim or self.head_dim))

    values = widths

    def stored(self, k, v) -> tuple:
        """The rows as the pools hold them: K's heads split at a lane tile
        where a head is wider than one (:func:`lay_heads`), V's as they
        come."""
        return lay_heads(k), v

    def decode_formulation(self, block_size: int, impl: str) -> str:
        return paged_decode_formulation(
            self.heads, self.kv_heads, self.head_dim, block_size, impl,
            self.value_dim)

    def chunk_formulation(self, block_size: int, chunk: int,
                          impl: str) -> str:
        return paged_chunk_formulation(self.heads, self.kv_heads, self.head_dim,
                                       self.value_dim, block_size, chunk, impl)

    def chunk(self, q, start, pools, table_row, **kw):
        with jax.named_scope("paged_attn"):
            return paged_window_chunk_attention(
                q, start, *pools, table_row, **kw)

    def decode(self, q, pools, tables, attend_lens, **kw):
        with jax.named_scope("paged_attn"):
            return paged_window_decode_attention(
                q, *pools, tables, attend_lens, **kw)

    def verify(self, q, pools, tables, attend_lens, **kw):
        with jax.named_scope("paged_attn"):
            return paged_verify_attention(
                q, *pools, tables, attend_lens, **kw)


@dataclasses.dataclass(frozen=True)
class LatentRows:
    """One row ``[c_kv | k_rope]`` a token in one pool, shared by every head;
    a block calls ``attend((q_nope, q_rope), row, w_uk=, w_uv=)`` and gets
    ``(T, H, v)`` back."""

    rank: int
    rope_dim: int
    scale: float
    #: every head attends the one row
    shared_row = True

    @staticmethod
    def stored(*rows) -> tuple:
        """The rows as the pools hold them: as the block hands them."""
        return rows

    @property
    def values(self) -> tuple[int, ...]:
        return (self.rank + self.rope_dim,)

    @property
    def widths(self) -> tuple[int, ...]:
        """The row padded with zeros to whole 128-lane tiles: the TPU lays a
        576-wide bf16 pool out 640 wide in HBM whatever its shape says
        (``memref<..x640xbf16, tiled<(8,128)(2,1)>``) and Mosaic slices no
        part of a tile, so the pad costs nothing the layout had not taken."""
        return (-(-self.values[0] // LANES) * LANES,)

    def decode_formulation(self, block_size: int, impl: str) -> str:
        return paged_latent_formulation(
            block_size, self.widths[0], self.rank, impl)

    def chunk_formulation(self, block_size: int, chunk: int,
                          impl: str) -> str:
        return paged_latent_chunk_formulation(
            block_size, self.widths[0], self.rank, chunk, impl)

    def _no_window(self, window):
        if window is not None:
            raise ValueError("latent attention over a window is not "
                             "implemented")

    def chunk(self, q, start, pools, table_row, *, window=None, **kw):
        self._no_window(window)
        return paged_latent_chunk_attention(
            *q, start, *pools, table_row, scale=self.scale, **kw)

    def decode(self, q, pools, tables, attend_lens, *, window=None, **kw):
        self._no_window(window)
        return paged_latent_decode_attention(
            *q, *pools, tables, attend_lens, scale=self.scale, **kw)


@dataclasses.dataclass(frozen=True)
class SparseLatentRows(LatentRows):
    """A latent row AND an index key a token, in two pools of one group; a
    block calls ``attend((q_nope, q_rope, q_index, w_index), row, index_key,
    w_uk=, w_uv=)``: ``q_index`` (T, index_heads, index_dim) and the float32
    head weights ``w_index`` (T, index_heads) score the slot's cached keys,
    the ``topk`` best positions are selected a query, and only those latent
    rows are attended ("Sparse latent attention", above)."""

    index_dim: int = 128
    topk: int = 2048

    @property
    def values(self) -> tuple[int, ...]:
        return (self.rank + self.rope_dim, self.index_dim)

    @property
    def widths(self) -> tuple[int, ...]:
        return (*LatentRows.widths.fget(self), self.index_dim)

    def _sparse(self, impl: str) -> str:
        return sparse_latent_formulation(self.widths[0], self.rank,
                                         self.topk, impl)

    def decode_formulation(self, block_size: int, impl: str) -> str:
        return self._sparse(impl)

    def chunk_formulation(self, block_size: int, chunk: int,
                          impl: str) -> str:
        """The formulation past ``topk`` positions — the dense kernel under
        the selection's mask where it and the selection kernel fit, else the
        gathered rows — and, after a ``+``, the dense one that a chunk
        ending before them takes."""
        dense = LatentRows.chunk_formulation(self, block_size, chunk, impl)
        if dense != "plain" and select_formulation(chunk, impl) != "plain":
            return f"masked_{dense}+{dense}"
        return f"{self._sparse(impl)}+{dense}"

    def _gathered(self, q_nope, q_rope, scores, counts, pool, table_of, *,
                  w_uk, w_uv, layer, block_size, impl):
        """``N`` queries, query ``i`` selecting among the first ``counts[i]``
        of its slot's ``scores[i]`` and attending those rows of the pool,
        gathered by index; ``table_of(blocks)`` looks up its page table."""
        pos, real = select_rows(scores, counts, min(self.topk,
                                                    scores.shape[-1]))
        rows = table_of(pos // block_size) * block_size + pos % block_size
        o_lat = sparse_latent_attention(
            _latent_queries(q_nope, q_rope, w_uk, pool.shape[-1]), pool,
            rows, real, layer=layer, rank=self.rank, scale=self.scale,
            impl=impl)
        return _latent_values(o_lat, w_uv, q_nope.dtype)

    @staticmethod
    def _keys(index_pool, layer, tables, block_size):
        """The index keys of every table column, ``(..., S, D)``."""
        dim = index_pool.shape[-1]
        with jax.named_scope("indexer"):
            return index_pool.reshape(
                index_pool.shape[0], -1, block_size,
                dim)[layer, tables].reshape(*tables.shape[:-1], -1, dim)

    def chunk(self, q, start, pools, table_row, *, window=None, layer,
              block_size, impl="auto", w_uk, w_uv):
        self._no_window(window)
        pool, index_pool = pools
        q_nope, q_rope, q_index, w_index = q
        t = q_nope.shape[0]
        kw = dict(w_uk=w_uk, w_uv=w_uv, layer=layer, block_size=block_size,
                  impl=impl)

        def dense(bias=None):
            return paged_latent_chunk_attention(
                q_nope, q_rope, start, pool, table_row, scale=self.scale,
                bias=bias, **kw)

        def sparse():
            keys = self._keys(index_pool, layer, table_row, block_size)
            scores = index_scores(
                q_index[None], w_index[None], keys[None],
                jnp.reshape(start + t, (1,)), impl=impl)[0]
            counts = start + 1 + jnp.arange(t, dtype=jnp.int32)
            if self.chunk_formulation(block_size, t, impl).startswith(
                    "masked_"):
                # a chunk's 2 M selected rows gathered by index cost 40 ms a
                # layer whatever the context (PERF.md section 6, PR 39): the
                # dense walk of the slot's pages under the selection's mask
                # attends the same rows
                return dense(select_bias(
                    scores, counts, min(self.topk, scores.shape[-1]),
                    impl=impl))
            return self._gathered(q_nope, q_rope, scores, counts, pool,
                                  lambda blocks: table_row[blocks], **kw)

        if table_row.shape[0] * block_size <= self.topk:
            return dense()
        # every row is selected while none lies past topk: the same sum
        return jax.lax.cond(start + t <= self.topk, dense, sparse)

    def decode(self, q, pools, tables, attend_lens, *, window=None, layer,
               block_size, impl="auto", w_uk, w_uv):
        self._no_window(window)
        pool, index_pool = pools
        q_nope, q_rope, q_index, w_index = q
        lens = attend_lens.astype(jnp.int32)
        scores = index_scores(
            q_index[:, None], w_index[:, None],
            self._keys(index_pool, layer, tables, block_size), lens,
            impl=impl)[:, 0]
        return self._gathered(
            q_nope, q_rope, scores, lens, pool,
            lambda blocks: jnp.take_along_axis(tables, blocks, axis=1),
            w_uk=w_uk, w_uv=w_uv, layer=layer, block_size=block_size,
            impl=impl)


# ---------------------------------------------------------------------------
# A prefill chunk over K/V rows: the page walk in a kernel
# ---------------------------------------------------------------------------
#
# What ``paged_chunk_attention`` (above, the plain loop) computes, with a
# chunk's scores kept in VMEM: the K/V rows' counterpart of the latent rows'
# ``latent_chunk_attn``.  (It stands below the row forms that call it: a
# Mosaic body carries its source lines, so the kernels above lower byte for
# byte as they did before it was added.)

#: context rows a step of the K/V chunk kernel copies and folds into the
#: running softmax (the plain loop's ``kv_chunk`` is as many)
KV_CHUNK_STRETCH = 512
#: query heads of one K/V head a grid step holds at most, all the chunk's
#: queries of each resident: a stretch is copied once a step
KV_CHUNK_HEADS = 8
#: query rows of a head whose scores are formed at a time
KV_CHUNK_QUERIES = 512
#: query rows a K/V head (the heads that read it x the chunk) under which a
#: chunk stays on the plain loop: not an MXU pass of them
KV_CHUNK_MIN_ROWS = 128
#: VMEM the chunk kernel may take (a v5e has 128 MiB, 16 of them scoped by
#: default): the resident queries, outputs and softmax state of its heads
KV_CHUNK_VMEM = 96 << 20


def _kv_chunk_kernel(table_ref, start_ref, lo_ref, layer_ref, q_ref, k_hbm,
                     v_hbm, *refs, block_size, q_tile, scale, window,
                     rest_at, from_lo=False, causal=True, with_lse=False):
    """``rest_at``: where a K head is wider than its whole tiles
    (:func:`lay_heads`), the lane at which the heads' remainders start in
    the K row, two of 64 a tile: the head's whole tiles and the remainder
    tile it shares are copied side by side, and the query holds zeros in
    the neighbour's lanes.  With a sink, ``refs`` starts with its (1,
    heads, 128) float32 block.  ``from_lo``: rows before ``lo`` are masked
    too (a tumbling window starts anywhere in its first stretch; a sliding
    one's rows before ``lo`` fall to the window's mask).  ``causal`` false:
    every query attends every row before ``start + T`` (the rows are not the
    queries' own positions: chunk summaries).  ``with_lse``: a second output,
    the log of each query's denominator across a head's 128 lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lse_ref = refs[-8] if with_lse else None
    sink_ref = refs[0] if len(refs) == 9 + with_lse else None
    o_ref = refs[-8 - with_lse]
    kbuf, vbuf, sem, q_sc, m_sc, l_sc, acc_sc = refs[-7:]
    heads, t, dq = q_sc.shape
    stretch, dv = vbuf.shape[1], vbuf.shape[2]
    whole = dq if rest_at is None else dq - LANES   # lanes of whole K tiles
    bps = stretch // block_size                # blocks a stretch
    nb = table_ref.shape[0]
    kv = pl.program_id(0)
    start, lo, layer = start_ref[0], lo_ref[0], layer_ref[0]
    end = start + t
    n = (end + stretch - 1) // stretch         # stretches to the chunk's end
    dtype = q_sc.dtype

    # blocks the chunk does not attend are not copied and leave their rows
    # as they were: keep them finite (their probabilities are zeros)
    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    # the queries come as the model holds them, a head's values side by side
    # with the next head's: a head's own, head-major, once a step
    for h in range(heads):
        q_sc[h] = q_ref[:, h * dq:(h + 1) * dq]

    def copies(c, slot, go):
        """``go`` every copy of stretch ``c`` into buffer ``slot`` whose
        block holds a row the chunk attends: this K/V head's lanes only."""
        for j in range(bps):
            b0 = c * stretch + j * block_size
            blk = table_ref[jnp.minimum(b0 // block_size, nb - 1)]
            src = pl.ds(blk * block_size, block_size)
            dst = pl.ds(j * block_size, block_size)
            parts = [
                (k_hbm.at[layer, src,
                          pl.ds(pl.multiple_of(kv * whole, LANES), whole)],
                 kbuf.at[slot, dst, pl.ds(0, whole)]),
                (v_hbm.at[layer, src,
                          pl.ds(pl.multiple_of(kv * dv, LANES), dv)],
                 vbuf.at[slot, dst])]
            if rest_at is not None:
                parts.append((
                    k_hbm.at[layer, src, pl.ds(pl.multiple_of(
                        rest_at + kv // 2 * LANES, LANES), LANES)],
                    kbuf.at[slot, dst, pl.ds(whole, LANES)]))

            @pl.when((b0 < end) & (b0 + block_size > lo))
            def _():
                for i, (src_ref, dst_ref) in enumerate(parts):
                    go(pltpu.make_async_copy(src_ref, dst_ref,
                                             sem.at[slot, i, j]))

    def fold(h, i, first, masked, slot):
        """Query tile ``i`` of head ``h`` against the stretch in ``slot``."""
        rows = pl.ds(pl.multiple_of(i * q_tile, q_tile), q_tile)
        s = jax.lax.dot_general(
            q_sc[h, rows, :], kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (q_tile, stretch)
        if masked:
            qpos = start + i * q_tile + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            ok = kpos <= qpos if causal else kpos < end
            if window is not None:
                ok &= kpos > qpos - window
            if from_lo:
                ok &= kpos >= lo
            s = jnp.where(ok, s, NEG_INF)
        # the running maximum and sum are kept replicated across 128 lanes
        m_prev = m_sc[h, rows, :]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - jnp.concatenate([m_new] * (stretch // LANES), axis=1))
        if masked:
            p = jnp.where(ok, p, 0.0)
        l_sc[h, rows, :] = alpha * l_sc[h, rows, :] \
            + p.sum(axis=1, keepdims=True)
        m_sc[h, rows, :] = m_new
        pv = jnp.dot(p.astype(dtype), vbuf[slot],
                     preferred_element_type=jnp.float32)
        acc_sc[h, rows, :] = acc_sc[h, rows, :] * jnp.concatenate(
            [alpha] * (dv // LANES), axis=1) + pv

    def heads_of(first, slot, masked):
        """Every head of the step against stretch ``first`` in ``slot``."""
        def head(h, _):
            def tile(i, _):
                if not masked or not causal:
                    return fold(h, i, first, masked, slot)
                # a tile whose last query precedes the stretch, or whose
                # first query's window starts past it, attends none of it
                some = first <= start + (i + 1) * q_tile - 1
                if window is not None:
                    some &= first + stretch - 1 > start + i * q_tile - window
                pl.when(some)(lambda: fold(h, i, first, True, slot))

            jax.lax.fori_loop(0, t // q_tile, tile, None)

        jax.lax.fori_loop(0, heads, head, None)

    c0 = lo // stretch                         # the first attended stretch
    copies(c0, jax.lax.rem(c0, 2), lambda cp: cp.start())

    def stretch_body(c, _):
        slot = jax.lax.rem(c, 2)
        first = c * stretch

        # the next stretch's copies run under this stretch's arithmetic
        @pl.when(c + 1 < n)
        def _():
            copies(c + 1, 1 - slot, lambda cp: cp.start())

        copies(c, slot, lambda cp: cp.wait())
        # only a stretch that reaches past the chunk's first query, or
        # before the window of its last, is masked
        masked = (first + stretch - 1 > start if causal
                  else first + stretch > end)
        if window is not None:
            masked |= first < end - window
        if from_lo:
            masked |= first < lo
        pl.when(masked)(lambda: heads_of(first, slot, True))
        pl.when(jnp.logical_not(masked))(
            lambda: heads_of(first, slot, False))

    jax.lax.fori_loop(c0, n, stretch_body, None)

    for h in range(heads):
        l = l_sc[h]
        if sink_ref is not None:
            # the key without a value: one more term of the denominator
            l = l + jnp.exp(sink_ref[0, pl.ds(h, 1), :] - m_sc[h])
        o_ref[:, h * dv:(h + 1) * dv] = (acc_sc[h] / jnp.concatenate(
            [jnp.maximum(l, 1e-30)] * (dv // LANES), axis=1)
        ).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[:, h * LANES:(h + 1) * LANES] = m_sc[h] + jnp.log(
                jnp.maximum(l, 1e-30))


def _kv_chunk_heads(group: int, chunk: int, dq: int, dv: int,
                    itemsize: int, with_lse: bool = False) -> int:
    """Query heads a grid step holds: the most that divide the ``group``
    that reads a K/V head, up to ``KV_CHUNK_HEADS``, whose queries and
    outputs (two buffers each, the pipeline's; the log-denominators' too
    where they go out), head-major queries and float32 softmax state fit
    half the kernel's VMEM."""
    a_head = chunk * (itemsize * (3 * dq + 2 * dv) + 4 * (dv + 2 * LANES)
                      + 8 * LANES * with_lse)
    fit = max(1, min(KV_CHUNK_HEADS, (KV_CHUNK_VMEM // 2) // a_head))
    return max(g for g in range(1, fit + 1) if group % g == 0)


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "block_size", "stretch", "heads_step", "q_tile", "scale",
    "window", "rest_at", "interpret", "from_lo", "causal", "with_lse"))
def _kv_chunk_call(table_row, start, lo, layer, q, k_pool, v_pool, sink=None,
                   *, kv_heads, block_size, stretch, heads_step, q_tile,
                   scale, window, rest_at, interpret, from_lo=False,
                   causal=True, with_lse=False):
    """The chunk kernel's call: a jitted function of its own with the layer
    as a prefetched scalar, so the layers of a group share one lowering.
    ``q`` is 2-D, (T, H * dq): the heads side by side, each as wide as the
    lanes of its K/V head that the kernel copies."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = q.shape[0]
    dv = v_pool.shape[-1] // kv_heads
    whole = k_pool.shape[-1] // kv_heads // LANES * LANES
    dq = whole if rest_at is None else whole + LANES
    heads = q.shape[1] // dq
    g, steps = heads_step, heads // kv_heads // heads_step

    def a_step(lanes):
        return pl.BlockSpec((t, g * lanes),
                            lambda kv, j, *_: (0, kv * steps + j))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    sink_spec = [] if sink is None else [pl.BlockSpec(
        (1, g, LANES), lambda kv, j, *_: (kv * steps + j, 0, 0))]
    out_specs = a_step(dv)
    out_shape = jax.ShapeDtypeStruct((t, heads * dv), q.dtype)
    if with_lse:
        out_specs = [out_specs, a_step(LANES)]
        out_shape = [out_shape, jax.ShapeDtypeStruct((t, heads * LANES),
                                                     jnp.float32)]
    return pl.pallas_call(
        functools.partial(
            _kv_chunk_kernel, block_size=block_size, q_tile=q_tile,
            scale=scale, window=window, rest_at=rest_at, from_lo=from_lo,
            causal=causal, with_lse=with_lse),
        name="kv_chunk_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(kv_heads, steps),
            in_specs=[a_step(dq), hbm, hbm, *sink_spec],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((2, stretch, dq), k_pool.dtype),
                pltpu.VMEM((2, stretch, dv), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 3, stretch // block_size)),
                pltpu.VMEM((g, t, dq), q.dtype),
                pltpu.VMEM((g, t, LANES), jnp.float32),
                pltpu.VMEM((g, t, LANES), jnp.float32),
                pltpu.VMEM((g, t, dv), jnp.float32),
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=KV_CHUNK_VMEM),
        interpret=interpret,
    )(table_row, start, lo, layer, q, k_pool, v_pool,
      *(() if sink is None else (sink,)))


def paged_chunk_formulation(heads: int, kv_heads: int, head_dim: int,
                            value_dim: int | None, block_size: int,
                            chunk: int, impl: str = "auto") -> str:
    """Which formulation :func:`paged_window_chunk_attention` takes at these
    shapes: ``"kv_chunk_attn"`` (the kernel) or ``"plain"`` (the loop whose
    scores go through HBM).  The kernel wants a stretch of whole blocks and
    whole lane tiles; a K head of whole lane tiles, or of whole tiles and
    half a tile more with the remainders two a tile (:func:`lay_heads`); a V
    head of whole tiles; a chunk of whole bf16 sublane tiles that its query
    tiles divide; and an MXU pass of query rows a K/V head.  A test of
    shapes and of ``impl`` alone, so a program can say what it was built
    with (``serve.model``)."""
    rest = head_dim % LANES
    fits = (KV_CHUNK_STRETCH % block_size == 0
            and KV_CHUNK_STRETCH % LANES == 0
            and heads % kv_heads == 0 and head_dim >= LANES
            and (rest == 0 or (rest == LANES // 2 and kv_heads % 2 == 0))
            and (value_dim or head_dim) % LANES == 0
            and chunk % 16 == 0
            and chunk % min(chunk, KV_CHUNK_QUERIES) == 0
            and heads // kv_heads * chunk >= KV_CHUNK_MIN_ROWS)
    return "kv_chunk_attn" if use_kernel(impl) and fits else "plain"


def paged_window_chunk_attention(
    q: jax.Array,            # (T, H, D): one slot's chunk of queries
    start,                   # int32 scalar: position of q[0]
    k_pool: jax.Array,       # (L_group, rows, Hkv * D)
    v_pool: jax.Array,
    table_row: jax.Array,    # (max_blocks,) the slot's page-table row
    *,
    layer: int,
    block_size: int,
    window: int | None = None,
    sink: jax.Array | None = None,   # (H,) a key without a value a head
    impl: str = "auto",
    interpret: bool | None = None,
    lo=None,                 # int32 scalar: the first row a query attends
    causal: bool = True,
    with_lse: bool = False,
) -> jax.Array:
    """Chunk-prefill attention of one slot against its pages that keeps a
    chunk's scores in VMEM (``lo``, ``causal`` and ``with_lse`` are at the
    end of this text): what :func:`paged_chunk_attention` computes (its
    loop is the plain formulation here: the tests' yardstick, the path off
    the TPU, for ``impl="xla"`` and at shapes that do not fit,
    :func:`paged_chunk_formulation`), bf16 operands, float32 scores and
    softmax state, the probabilities rounded to the stored type.

    The kernel (``name="kv_chunk_attn"``; the page-table row, ``start``, the
    first attended row and the layer prefetched into SMEM, the pools left in
    HBM; grid over K/V heads and groups of ``KV_CHUNK_HEADS`` of the query
    heads that read one, all ``T`` queries of each resident) walks the
    stretches of ``KV_CHUNK_STRETCH`` rows from the first one a query
    attends (``max(start - window + 1, 0)``, 0 on a full layer) to the
    chunk's end: a stretch's blocks — this K/V head's lanes of them: its
    whole K tiles, the remainder tile it shares where a K head is a tile
    and a half (the query holds zeros in the neighbour's lanes, as
    ``paged_attn``'s does) and its V tiles — are copied into one of two
    VMEM buffers, the next stretch's copies started before this one's
    arithmetic, and each tile of ``KV_CHUNK_QUERIES`` queries of each head
    folds it into the running maximum, sum and accumulator in VMEM scratch:
    no score goes to HBM.  Only a stretch that reaches past the chunk's
    first query, or before the window of its last, is masked, and a query
    tile that attends none of such a stretch skips it; blocks past the
    chunk's end or before the first attended row are not copied.  ``sink``
    joins each head's denominator once, at the end.

    The kernel's alone, for a caller that merges several walks under one
    softmax (:func:`eva_chunk_attention`): ``lo`` names the first attended
    row itself (a tumbling window's start, masked where it lies inside a
    stretch); ``causal=False`` has every query attend every row before
    ``start + T`` (rows that are not the queries' own positions); ``with_lse``
    returns ``(o, lse)``, the log of each query's denominator (T, H)."""
    t, h, d = q.shape
    h_kv = k_pool.shape[-1] // d
    dv = v_pool.shape[-1] // h_kv
    if paged_chunk_formulation(h, h_kv, d, dv, block_size, t,
                               impl) == "plain":
        if lo is not None or not causal or with_lse:
            raise ValueError(
                "lo=, causal=False and with_lse= are the kv_chunk_attn "
                "kernel's")
        return paged_chunk_attention(
            q, start, k_pool, v_pool, table_row, layer=layer,
            block_size=block_size, window=window, sink=sink)
    if interpret is None:
        interpret = not on_tpu()
    g = h // h_kv
    whole, rest_at = d // LANES * LANES, None
    if d > whole:
        # a head's whole tiles, then its last 64 values in its own half of
        # the remainder tile
        rest_at = h_kv * whole
        own = (jnp.arange(h_kv)[:, None] % 2
               == jnp.arange(2)[None, :])[:, None, :, None]
        qh = q.reshape(t, h_kv, g, d)
        rest = jnp.where(own, qh[:, :, :, None, whole:], 0)
        q = jnp.concatenate(
            [qh[..., :whole], rest.reshape(t, h_kv, g, LANES)], axis=-1)
    dq = whole + (LANES if rest_at is not None else 0)
    heads_step = _kv_chunk_heads(g, t, dq, dv, q.dtype.itemsize, with_lse)
    start = jnp.reshape(start, (1,)).astype(jnp.int32)
    from_lo = lo is not None
    if from_lo:
        lo = jnp.reshape(lo, (1,)).astype(jnp.int32)
    else:
        lo = (jnp.zeros_like(start) if window is None
              else jnp.maximum(start - window + 1, 0))
    if sink is not None:
        # a head's bias across the lanes, as the running maximum lies
        sink = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(h // heads_step, heads_step, 1),
            (h // heads_step, heads_step, LANES))
    out = _kv_chunk_call(
        table_row.astype(jnp.int32), start, lo,
        jnp.full((1,), layer, jnp.int32), q.reshape(t, h * dq), k_pool,
        v_pool, sink, kv_heads=h_kv, block_size=block_size,
        stretch=KV_CHUNK_STRETCH, heads_step=heads_step,
        q_tile=min(t, KV_CHUNK_QUERIES), scale=d ** -0.5, window=window,
        rest_at=rest_at, interpret=interpret, from_lo=from_lo, causal=causal,
        with_lse=with_lse)
    if with_lse:
        # a query's log-denominator lies across a head's lanes: one of them
        out, lse = out
        return out.reshape(t, h, dv), lse.reshape(t, h, LANES)[..., 0]
    return out.reshape(t, h, dv)


# ---------------------------------------------------------------------------
# EVA: an exact tumbling window and chunk summaries under one softmax
# ---------------------------------------------------------------------------
#
# An EVA layer (Zheng et al., "Efficient Attention via Control Variates", as
# EvaByte simplifies it; ``models.evabyte``) attends, under ONE softmax, the
# exact keys of the query's own window — windows tumble: ``[w i, w (i + 1))``
# — and one summary key/value for every chunk of ``c`` tokens of every
# *earlier* window.  So a layer's rows live in TWO pools that advance at two
# rates: token rows in a ring that is reused in place when a window closes
# (group ``"window"``), and summary rows, one a ``c`` tokens, that are kept
# for ever (group ``"full"``).  Both pools hold a K/V pair a row, so each
# walk is the K/V rows' walk (``paged_attn`` / ``kv_chunk_attn``, or their
# plain formulations) and the two are merged by their log-sum-exp.


def chunk_summaries(k, v, mu, phi, chunk_size: int):
    """The summary key and value of every whole chunk of ``chunk_size`` rows
    of ``k``, ``v`` (..., n * chunk_size, H, D): ``k~ = sum_m softmax_m(s
    <k_m, mu_h>) k_m`` and ``v~ = sum_m softmax_m(s <k_m, phi_h>) v_m`` over
    the chunk's rows ``m``, ``s = D ** -0.5``, with learned ``mu``, ``phi``
    (H, D).  Float32 scores, softmax and sums on the stored rows; back in
    the rows' type, (..., n, H, D) each."""
    *lead, t, h, d = k.shape
    f32 = jnp.float32
    kc = k.reshape(*lead, t // chunk_size, chunk_size, h, d).astype(f32)
    vc = v.reshape(*lead, t // chunk_size, chunk_size, h, d).astype(f32)
    scale = d ** -0.5

    def pooled(rows, by, w):
        p = jax.nn.softmax((by * w.astype(f32)).sum(-1) * scale, axis=-2)
        return (p[..., None] * rows).sum(-3)

    return (pooled(kc, kc, mu).astype(k.dtype),
            pooled(vc, kc, phi).astype(v.dtype))


def merge_softmax_parts(parts, dtype):
    """Outputs of one query over disjoint sets of keys, each normalised over
    its own set with the log of its denominator (``lse``, ``(...,)`` beside
    ``o`` ``(..., D)``), as the output over all the sets: each part weighs
    ``exp(lse - lse_all)``.  A part over no key has ``lse`` at ``NEG_INF``
    or under and weighs nothing."""
    lse = jnp.stack([l for _, l in parts])
    top = lse.max(0)
    w = jnp.exp(lse - top)
    w = w / w.sum(0)
    return sum(o.astype(jnp.float32) * wi[..., None]
               for (o, _), wi in zip(parts, w)).astype(dtype)


def _eva_span(pos, window: int, chunk_size: int):
    """Of a query at ``pos``: the first position of its window, and the
    summary rows it sees (every chunk of every earlier window)."""
    first = pos // window * window
    return first, first // chunk_size


def _plain_eva_scores(q, k, ok):
    """Masked float32 scores of queries (..., T, H, D) on keys (..., K, H,
    D) under ``ok`` (..., T, K), (..., H, T, K)."""
    s = jnp.einsum("...qhd,...khd->...hqk", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    return jnp.where(ok[..., None, :, :], s, NEG_INF)


def softmax_over_parts(q, parts):
    """One softmax of queries (..., T, H, D) over several sets of keys:
    ``parts`` is ``[(k, v, ok)]``, rows (..., K, H, D) and the mask (..., T,
    K) of what each query attends."""
    s = jnp.concatenate([_plain_eva_scores(q, k, ok) for k, _, ok in parts],
                        axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    v = jnp.concatenate([v for _, v, _ in parts], axis=-3)
    return jnp.einsum("...hqk,...khd->...qhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _window_blocks(table, first, window: int, block_size: int):
    """The table's columns (last axis) that hold the window from ``first``
    (a scalar, or one a row of ``table``): ``window // block_size`` of
    them, past the table's width its last column (masked by position)."""
    cols = jnp.asarray(first)[..., None] // block_size + jnp.arange(
        -(-window // block_size), dtype=jnp.int32)
    cols = jnp.minimum(cols, table.shape[-1] - 1)
    return jnp.take_along_axis(table, cols, axis=-1)


def _block_rows(pool, layer, blocks, block_size: int, head_dim: int):
    """Rows of ``blocks`` (..., n) of layer ``layer`` as heads, (..., n *
    block_size, H, D): whole blocks gathered, the heads split on what was
    gathered only."""
    x = pool.reshape(pool.shape[0], -1, block_size,
                     pool.shape[-1])[layer, blocks]
    return _row_heads(x, (*blocks.shape[:-1], blocks.shape[-1] * block_size),
                      head_dim)


def eva_decode_attention(
    q: jax.Array,              # (B, H, D) one query a slot
    ring_pools: tuple,         # (k_pool, v_pool) of token rows, the ring
    summary_pools: tuple,      # (k_pool, v_pool) of summary rows
    ring_tables: jax.Array,    # (B, columns a token block) int32
    summary_tables: jax.Array,  # (B, columns a block of summary rows)
    attend_lens: jax.Array,    # (B,) tokens a slot holds, this step's too
    *,
    layer: int,
    block_size: int,
    window: int,
    chunk_size: int,
    impl: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """Decode attention of an EVA layer: the query at ``attend_lens - 1``
    attends the token rows from its window's start to itself and the summary
    rows of every earlier window's chunks, one softmax over both.

    On the TPU two walks of the ``paged_attn`` kernel — the ring's blocks of
    the open window, then the summary pool's blocks that hold a visible row
    — each of which also returns the log of its denominator, merged
    (:func:`merge_softmax_parts`); the plain formulation gathers the open
    window's columns of the ring's table and every column of the summary
    table, and takes one softmax over both.  A slot of length 0 attends
    nothing, in either pool: zeros."""
    b, h, d = q.shape
    lens = attend_lens.astype(jnp.int32)
    # a slot that holds nothing (length 0) has no query position: its span
    # is position 0's, of which it attends no row
    pos = jnp.maximum(lens - 1, 0)
    first, seen = _eva_span(pos, window, chunk_size)
    if paged_decode_formulation(h, h, d, block_size, impl) == "paged_attn":
        walk = functools.partial(
            paged_window_decode_attention, q, layer=layer,
            block_size=block_size, impl=impl, interpret=interpret,
            with_lse=True)
        # ``window`` bounds the ring's walk: ``lo`` says where it starts
        return merge_softmax_parts(
            [walk(*ring_pools, ring_tables, lens, lo=first, window=window),
             walk(*summary_pools, summary_tables, seen)], q.dtype)
    blocks = _window_blocks(ring_tables, first, window, block_size)
    kpos = first[:, None] + jnp.arange(blocks.shape[1] * block_size)
    local = tuple(_block_rows(p, layer, blocks, block_size, d)
                  for p in ring_pools)
    summ = tuple(_block_rows(p, layer, summary_tables, block_size, d)
                 for p in summary_pools)
    out = softmax_over_parts(q[:, None], [
        (*local, (kpos < lens[:, None])[:, None]),
        (*summ, (jnp.arange(summ[0].shape[1])[None] < seen[:, None])[:, None]),
    ])[:, 0]
    # a slot that attends nothing returns zeros, as the kernel's walks do
    return jnp.where((lens > 0)[:, None, None], out, 0)


def eva_chunk_attention(
    q: jax.Array,              # (T, H, D): one slot's chunk of queries
    start,                     # int32 scalar: position of q[0]
    ring_pools: tuple,
    summary_pools: tuple,
    ring_row: jax.Array,       # the slot's row of the ring's table
    summary_row: jax.Array,    # the slot's row of the summary table
    *,
    layer: int,
    block_size: int,
    window: int,
    chunk_size: int,
    impl: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """Chunk-prefill attention of an EVA layer, the chunk's own token rows
    already written: a chunk lies inside one window (the chunk grid divides
    the window grid), so its queries attend the window's rows up to
    themselves, causally, and — every one the same — the summary rows of the
    earlier windows.  On the TPU two walks of ``kv_chunk_attn`` (the causal
    one from the window's first row; one without a mask over the visible
    summary rows), merged by their log-sum-exp; the plain formulation gathers
    the window's blocks and the whole summary table under one softmax."""
    t, h, d = q.shape
    start = jnp.asarray(start, jnp.int32)
    first, seen = _eva_span(start, window, chunk_size)
    if paged_chunk_formulation(h, h, d, d, block_size, t,
                               impl) == "kv_chunk_attn":
        walk = functools.partial(
            paged_window_chunk_attention, q, layer=layer,
            block_size=block_size, impl=impl, interpret=interpret,
            with_lse=True)
        return merge_softmax_parts(
            [walk(start, *ring_pools, ring_row, lo=first),
             walk(seen - t, *summary_pools, summary_row, causal=False)],
            q.dtype)
    blocks = _window_blocks(ring_row, first, window, block_size)
    kpos = first + jnp.arange(blocks.shape[0] * block_size)
    qpos = start + jnp.arange(t)
    local = tuple(_block_rows(p, layer, blocks, block_size, d)
                  for p in ring_pools)
    summ = tuple(_block_rows(p, layer, summary_row, block_size, d)
                 for p in summary_pools)
    return softmax_over_parts(q, [
        (*local, kpos[None, :] <= qpos[:, None]),
        (*summ, jnp.broadcast_to(
            jnp.arange(summ[0].shape[0])[None] < seen, (t, summ[0].shape[0]))),
    ])


@dataclasses.dataclass(frozen=True)
class TumblingKVRows(KVRows):
    """The K/V pair a token of a layer that attends its own *tumbling*
    window exactly: a ring that lets a whole window go when it closes
    (``serve.kv_cache.WindowKVGroup``)."""

    tumbling = True


@dataclasses.dataclass(frozen=True)
class SummaryKVRows(KVRows):
    """One K/V pair a chunk of ``tokens_per_row`` tokens, written when the
    chunk is complete and kept for ever; a query sees the rows of the
    windows before its own (``window`` tokens each)."""

    tokens_per_row: int = 16
    window: int = 2048

    def visible_rows(self, positions):
        """Summary rows a query at each of ``positions`` attends."""
        return positions // self.window * (self.window // self.tokens_per_row)


@dataclasses.dataclass(frozen=True)
class EvaRows:
    """What an EVA layer caches, in two groups at two rates: the K/V pair a
    token in group ``token_group`` (a tumbling ring of ``window`` rows) and
    one summary K/V pair a ``chunk_size`` tokens in group ``summary_group``.
    A block calls ``attend(q, k, v, mu=, phi=)``; the programs
    (``serve.model``) write the token rows, form and write the summaries of
    the chunks they complete, and read both pools back through ``chunk`` and
    ``decode`` here."""

    heads: int
    head_dim: int
    chunk_size: int
    window: int
    token_group = "window"
    summary_group = "full"

    @property
    def groups(self) -> dict:
        """``{group: the form of the rows it stores}``."""
        return {
            self.token_group: TumblingKVRows(
                self.heads, self.heads, self.head_dim),
            self.summary_group: SummaryKVRows(
                self.heads, self.heads, self.head_dim,
                tokens_per_row=self.chunk_size, window=self.window)}

    def summarise(self, k, v, mu, phi):
        with jax.named_scope("summarise"):
            return chunk_summaries(k, v, mu, phi, self.chunk_size)

    def chunk(self, q, start, pools: dict, table_rows: dict, **kw):
        with jax.named_scope("paged_attn"):
            return eva_chunk_attention(
                q, start, pools[self.token_group], pools[self.summary_group],
                table_rows[self.token_group], table_rows[self.summary_group],
                window=self.window, chunk_size=self.chunk_size, **kw)

    def decode(self, q, pools: dict, tables: dict, attend_lens, **kw):
        with jax.named_scope("paged_attn"):
            return eva_decode_attention(
                q, pools[self.token_group], pools[self.summary_group],
                tables[self.token_group], tables[self.summary_group],
                attend_lens, window=self.window, chunk_size=self.chunk_size,
                **kw)
