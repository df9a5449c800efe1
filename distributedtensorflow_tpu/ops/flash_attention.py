"""Pallas TPU flash attention.

The compiled-kernel replacement for the reference stack's fused-attention
needs (SURVEY.md §2.4 native-code obligations): attention scores never hit
HBM — each (q-block, k-block) grid step computes its (block_q, block_k)
score tile in VMEM, does the softmax in fp32, and writes only the
(block_q, D) output plus the log-sum-exp rows needed by the backward pass.
Block pairs wholly above the causal diagonal (or below a window's band) are
skipped.  In the tile kernels of :func:`flash_attention_qkv` a block ON the
diagonal is not computed as a square either: its q rows are walked in
static sub-tiles of ``T`` = :func:`causal_tile` rows, and sub-tile ``r``
forms scores, exponentials and products over the ``(r + 1) * T`` keys up
to its own diagonal only — (T, (r+1)*T) tiles, :func:`causal_share` of the
square in all (0.625 at 1024 / 256), the masks on the last ``T`` columns.
At sequence 1024, one block a sequence, that is every grid step.

Forward: one Pallas kernel, grid (batch, heads, q_blocks); K/V live in VMEM
per (batch, head) — at BERT/long-context head dims (64..128) a full K/V head
fits VMEM comfortably up to ~8k tokens, which is also the per-device shard
regime ring attention (``parallel/ring_attention.py``) operates in.

Backward: two Pallas kernels (the standard TPU flash-attention split) —
a dq kernel sweeping k-blocks innermost and a dk/dv kernel sweeping
q-blocks innermost, both recomputing the p-tile in VMEM from the saved
LSE so no (S, S) score tile ever reaches HBM.  An XLA blockwise-recompute
fallback (`_flash_backward_xla`) is kept as the golden reference; select
with ``BACKWARD_IMPL``.

Two entries, one set of masks and one softmax:

- :func:`flash_attention` takes q, k and v as (B, S, H, D) arrays (BSHD, to
  match ``ops.attention``) and runs the kernels on (B, H, S, D) copies of
  them: Mosaic wants a block's trailing two dimensions tile-aligned, and
  (S, D) are.  The transposes there and back are whole-tensor copies for
  XLA (ten a block pass at GPT-2 medium's training shapes, 241 ms of a
  1995 ms step: ``PERF.md`` section 6, PR 35).
  BERT, GQA, the ring / Ulysses chunks (``parallel/ring_attention.py``),
  and the ``"xla"`` backward call it.
- :func:`flash_attention_qkv` takes a block's fused projection (B, S,
  3*H*D) as the matmul wrote it: the kernels pick 128-lane tiles of the q,
  k and v thirds through their index maps (two heads of 64 a tile), rotate
  q and k in VMEM and write o and d``qkv`` as lane tiles of (B, S, H*D) /
  (B, S, 3*H*D).  Nothing is moved between the projection and the kernel.
  The training block (``models/gpt.py:CausalSelfAttention``) calls it
  where :func:`qkv_layout` says the shape fills lane tiles.
"""

from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..parallel import mesh as mesh_lib
from ..parallel.sharding import kernel_axes, shard_kernel
from ..runtime import on_tpu

NEG_INF = -1e9

#: Block tiling: the first of the default chain.  The kernel is VPU/softmax-
#: bound, not matmul-bound, so fewer and bigger grid steps amortize the
#: per-step scalar/DMA overhead; (1024, 1024) fp32 score tiles (+temps) are
#: the largest that fit Mosaic's 16 MB stack (1024x2048 does not compile).
DEFAULT_BLOCK_Q = 1024


def _env_block(name: str) -> int | None:
    """On-chip sweep override for a block size (read per call so one
    process can A/B several tilings; ``tools/flash_forms.py`` reports it)."""
    import os

    v = os.environ.get(name)
    if not v:
        return None
    try:
        n = int(v)
    except ValueError as e:
        raise ValueError(f"{name}={v!r}: expected a positive integer") from e
    if n <= 0:
        raise ValueError(f"{name}={v!r}: expected a positive integer")
    return n


def _env_divisible(name: str, seq_len: int) -> int | None:
    """The env-override block when set AND it divides the sequence; a
    non-dividing override warns (``warnings.warn`` — NOT a bare print:
    tools parse this process's stdout/stderr as JSON) and falls through to
    the next resolution tier."""
    o = _env_block(name)
    if not o:
        return None
    if seq_len % o == 0:
        return o
    warnings.warn(
        f"flash_attention: {name}={o} does not divide seq {seq_len}; "
        "using the default chain",
        stacklevel=3,
    )
    return None


def _default_chain(seq_len: int, first: int) -> int | None:
    for b in (first, 512, 256, 128, 64, 32, 16, 8):
        if seq_len % b == 0:
            return b
    return None


def _pick_block_q(seq_len: int) -> int | None:
    o = _env_divisible("DTFT_FLASH_BLOCK_Q", seq_len)
    return o or _default_chain(seq_len, DEFAULT_BLOCK_Q)


#: Auto-dispatch threshold: the shortest sequence ``supported`` hands to the
#: kernels.  Below it the score tensors are small enough that XLA's fused
#: dense attention is competitive and the kernels' fixed overhead dominates.
#: Mutable module global, re-read at each trace (tests monkeypatch it).
MIN_SEQ_FOR_PALLAS = int(os.environ.get("DTF_MIN_SEQ_FOR_PALLAS", "1024"))


def _gqa_ok(qshape, kshape) -> bool:
    """Same (B, S, D) and q heads an integer multiple of kv heads."""
    return (
        qshape[0] == kshape[0] and qshape[1] == kshape[1]
        and qshape[3] == kshape[3] and kshape[2] > 0
        and qshape[2] % kshape[2] == 0
    )


def _auto_takes(seq: int, dtype) -> bool:
    """Auto-dispatch's part that reads no operand: on the TPU, a sequence
    past the evidenced threshold that the blocks divide, a type the
    kernels were measured in."""
    return (
        on_tpu() and seq >= MIN_SEQ_FOR_PALLAS
        and _pick_block_q(seq) is not None
        and dtype in (jnp.bfloat16, jnp.float32)
    )


def supported(q, k, v, *, mask=None, segment_ids=None) -> bool:
    """True when auto-dispatch should take the Pallas kernel for this call."""
    if q.ndim != 4 or k.shape != v.shape or not _gqa_ok(q.shape, k.shape):
        return False
    if not _auto_takes(q.shape[1], q.dtype):
        return False
    if segment_ids is not None and not _is_segment_ids(segment_ids, q.shape):
        return False
    return mask is None or _is_padding_mask(mask, q.shape)


def _is_padding_mask(mask, qshape) -> bool:
    """Accept (B, S) or its broadcast form (B, 1, 1, S)."""
    b, s = qshape[0], qshape[1]
    return tuple(mask.shape) in ((b, s), (b, 1, 1, s))


def _as_padding_mask(mask, qshape):
    if mask is None:
        return None
    b, s = qshape[0], qshape[1]
    return mask.reshape(b, s).astype(jnp.bool_)


def _is_segment_ids(segment_ids, qshape) -> bool:
    """(B, S) integer ids: tokens attend only within their own segment
    (packed-sequence / example-packing semantics, BERT-style pretraining)."""
    return (
        tuple(segment_ids.shape) == (qshape[0], qshape[1])
        and jnp.issubdtype(segment_ids.dtype, jnp.integer)
    )


# --- Forward kernel ---------------------------------------------------------


DEFAULT_BLOCK_K = 1024  # see the DEFAULT_BLOCK_Q sweep note


def _pick_block_k(seq_len: int) -> int | None:
    o = _env_divisible("DTFT_FLASH_BLOCK_K", seq_len)
    return o or _default_chain(seq_len, DEFAULT_BLOCK_K)


def _tuned_blocks(batch: int, heads: int, seq: int, depth: int, dtype,
                  layout: str = "bhsd") -> tuple[int, int] | None:
    """Autotune-cache consult (ops/flash_tuning.py): the (block_q,
    block_k) a sweep or XPlane analysis recorded for this (shape, dtype,
    platform) and kernel form, or None.  Never raises — a broken cache
    must degrade to the default chain, not break the kernel."""
    try:
        from . import flash_tuning

        return flash_tuning.lookup(
            platform=jax.default_backend(),
            dtype=jnp.dtype(dtype).name,
            seq=seq, depth=depth, batch=batch, heads=heads, layout=layout,
        )
    except Exception:
        return None


def _resolve_blocks(batch: int, heads: int, seq: int, depth: int, dtype,
                    block_q: int | None, block_k: int | None,
                    layout: str = "bhsd") -> tuple[int, int]:
    """The kernel's block tiling, resolved: explicit argument > env
    override > autotune cache > retuned default chain.  Callers
    validated divisibility of explicit args; env/cache tiers self-skip
    when they don't divide.  ``layout`` names the kernel form: a tiling
    recorded for one form's kernels is not taken for the other's."""
    if block_q is not None and block_k is not None:
        return block_q, block_k
    env_q = _env_divisible("DTFT_FLASH_BLOCK_Q", seq)
    env_k = _env_divisible("DTFT_FLASH_BLOCK_K", seq)
    tuned = None
    if (block_q or env_q) is None or (block_k or env_k) is None:
        tuned = _tuned_blocks(batch, heads, seq, depth, dtype, layout)
    bq = (block_q or env_q or (tuned[0] if tuned else None)
          or _default_chain(seq, DEFAULT_BLOCK_Q))
    bk = (block_k or env_k or (tuned[1] if tuned else None)
          or _default_chain(seq, DEFAULT_BLOCK_K))
    return bq, bk


def _segment_mask(s, qseg_ref, kseg_ref, rows=slice(None),
                  cols=slice(None)):
    """Mask score tile entries whose q and k tokens are in different packed
    segments (qseg: (block_q,), kseg: (block_k,); ``rows`` / ``cols``: the
    part of the block the tile holds)."""
    qseg = qseg_ref[0, 0, rows]
    kseg = kseg_ref[0, 0, cols]
    return jnp.where(qseg[:, None] == kseg[None, :], s, NEG_INF)


def _masked_scores(q, k, qi, kj, *, scale, block_q, block_k, causal,
                   have_mask, mask_ref, qseg_ref, kseg_ref, window=None):
    """The (block_q, block_k) fp32 score tile with every mask applied.

    THE shared recompute of all four kernels (fwd, dq, dkv, fused bwd):
    qk^T contraction, causal iota mask, sliding-window lower edge,
    padding mask, packed-segment mask.  One definition so a
    masking-semantics change cannot desynchronize the forward from one
    of the backward variants.  ``window`` (static) keeps only keys in
    ``(q_pos - window, q_pos]``."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if scale != 1.0:  # 1.0: the caller folded it into q (_tiles_rope)
        s = s * scale
    if causal or window is not None:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if causal:
            keep = q_pos >= k_pos
            if window is not None:
                keep &= k_pos > q_pos - window
        else:
            keep = k_pos > q_pos - window
        s = jnp.where(keep, s, NEG_INF)
    if have_mask:
        keep = mask_ref[0, 0, :]  # (block_k,)
        s = jnp.where(keep[None, :], s, NEG_INF)
    if qseg_ref is not None:
        s = _segment_mask(s, qseg_ref, kseg_ref)
    return s


#: Rows of a sub-tile of a block on the causal diagonal (:func:`causal_tile`).
#: One attention block of GPT-2 medium alone on the v5e (64 x 1024 tokens, 16
#: heads of 64, one 1024 x 1024 block a grid step, forward + backward under
#: ``jax.checkpoint``; ``tools/flash_forms.py``, PERF.md section 6, PR 40):
#: the block whole 26.10 ms; sub-tiles of 128 rows (56 % of the square,
#: eight a block) 23.59, of 256 (62.5 %, four) **22.45**, of 512 (75 %, two)
#: 22.81 (the kernels alone, forward + backward: 3.29 + 8.62, 3.07 + 6.51,
#: 2.68 + 6.16, 2.60 + 6.69).  Narrower sub-tiles skip more of the square
#: and pay for it in unrolled products of fewer rows; 256 is the width of
#: the table's minimum.
#: Mutable module global, read when a call is traced (the tool and the
#: tests set it; 0 takes every block whole).
CAUSAL_TILE = 256


def causal_tile(block_q: int, block_k: int, causal: bool) -> int | None:
    """Rows of the sub-tiles the tile kernels walk a block on the causal
    diagonal in, or None where they take the block whole: a call that is
    not causal, q and k blocks of unequal size (the diagonal then crosses a
    block anywhere), a block that is not at least two sub-tiles.  By what
    the kernel can see of the call, nothing else."""
    if (not causal or block_q != block_k or not CAUSAL_TILE
            or block_q % CAUSAL_TILE or block_q < 2 * CAUSAL_TILE):
        return None
    return CAUSAL_TILE


def causal_share(block: int, tile: int | None) -> float:
    """The share of a diagonal block's square that is computed: row
    sub-tile ``r`` of ``n = block / tile`` takes ``r + 1`` of the ``n``
    column tiles, ``(n + 1) / 2n`` in all (0.625 at 1024 / 256)."""
    if not tile:
        return 1.0
    n = block // tile
    return (n + 1) / (2 * n)


def _diagonal_scores(q, k, r, tile, *, scale, have_mask, mask_ref, qseg_ref,
                     kseg_ref, window=None):
    """:func:`_masked_scores` of row sub-tile ``r`` of a block ON the causal
    diagonal (``block_q == block_k``, ``qi == kj``): ``q`` holds the block's
    rows ``[r*tile, (r+1)*tile)`` and ``k`` its first ``(r+1)*tile`` keys,
    every key a row of the sub-tile can see.  The keys past them are the
    ones the whole-block tile masks to ``NEG_INF`` (an exact 0.0 after the
    exponential), so the row maximum, the row sum and every product are the
    whole block's up to the order of the float32 additions.  Only the last
    ``tile`` columns (the piece the diagonal crosses) take the iota /
    compare / select passes; the positions inside a diagonal block differ
    by ``r*tile + i - j`` whatever the block.  The padding and segment rows
    are read by the sub-tile's slices of the block's refs.  A row whose
    every visible key is masked (padding on the left) has p = 1 a computed
    key as in the whole block: finite, l > 0, the average of v over the
    sub-tile's keys where the whole block averaged over the block's."""
    n = (r + 1) * tile
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if scale != 1.0:
        s = s * scale
    i = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    diag = jnp.where(i >= j, s[:, r * tile:], NEG_INF)
    s = diag if r == 0 else jnp.concatenate([s[:, :r * tile], diag], axis=1)
    if window is not None and window < n:  # the farthest key is n - 1 back
        back = (r * tile
                + jax.lax.broadcasted_iota(jnp.int32, (tile, n), 0)
                - jax.lax.broadcasted_iota(jnp.int32, (tile, n), 1))
        s = jnp.where(back < window, s, NEG_INF)
    if have_mask:
        s = jnp.where(mask_ref[0, 0, :n][None, :], s, NEG_INF)
    if qseg_ref is not None:
        s = _segment_mask(s, qseg_ref, kseg_ref,
                          slice(r * tile, (r + 1) * tile), slice(0, n))
    return s


def _straddles_diagonal(qi, kj, block_q, block_k):
    """Traced scalar: does this running (q-block, k-block) pair cross the
    causal diagonal?  A running pair that does NOT (its last k position
    <= its first q position) is fully visible, so the per-element iota/
    compare/select causal passes are pure VPU waste — at 8 blocks per
    axis only 8 of the 36 running pairs straddle.  Callers split the
    step body on this scalar with ``pl.when`` so the off-diagonal
    majority skips the masking entirely."""
    return kj * block_k + block_k - 1 > qi * block_q


def _straddles_window(qi, kj, block_q, block_k, window):
    """Traced scalar: does the pair cross the sliding-window LOWER edge
    (some k in the block is <= some q's q_pos - window)?  Fully-inside
    pairs (min k > max q - window) need no lower-edge mask."""
    return kj * block_k <= qi * block_q + block_q - 1 - window


def _band_run(qi, kj, block_q, block_k, causal, window):
    """Python-or-traced: does this block pair contribute at all?

    Upper cut (causal): first k <= last q position.  Lower cut (window):
    last k position >= first q position - (window - 1) — a pair entirely
    below the band is all-masked, so its matmuls are skipped outright
    (this is what turns O(S^2) into O(S*window) at long sequence)."""
    run = True
    if causal:
        run = kj * block_k <= qi * block_q + block_q - 1
    if window is not None:
        in_band = kj * block_k + block_k - 1 >= qi * block_q - (window - 1)
        run = in_band if run is True else (run & in_band)
    return run


def _causal_step_split(qi, kj, run, *, block_q, block_k, causal, step,
                       window=None):
    """Run ``step(apply_causal, apply_window)`` under the band split.

    ``step`` is the kernel body parameterized on which mask passes are
    emitted; identical numerics either way (skipping is only legal for
    pairs fully inside the respective edge).  Pairs needing neither
    edge (the band interior) run completely unmasked; with no window
    and no causal flag there is a single unmasked body (``run`` is the
    Python literal True there — every block pair runs)."""
    if not causal and window is None:
        step(False, False)
        return
    need_diag = (
        _straddles_diagonal(qi, kj, block_q, block_k) if causal
        else jnp.bool_(False)
    )
    need_win = (
        _straddles_window(qi, kj, block_q, block_k, window)
        if window is not None else jnp.bool_(False)
    )

    @pl.when(run & need_diag & need_win)
    def _():
        step(True, True)

    @pl.when(run & need_diag & jnp.logical_not(need_win))
    def _():
        step(True, False)

    @pl.when(run & jnp.logical_not(need_diag) & need_win)
    def _():
        step(False, True)

    @pl.when(run & jnp.logical_not(need_diag) & jnp.logical_not(need_win))
    def _():
        step(False, False)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, block_q, block_k, causal,
                have_mask, mask_ref=None, qseg_ref=None, kseg_ref=None,
                window=None):
    """One (q-block, k-block) grid step of online-softmax accumulation.

    Grid is (B, H, n_q, n_k) with k innermost; the m/l/acc state for the
    current q-block lives in VMEM scratch across the k sweep (the classic
    flash-attention recurrence).  Fully-causally-masked k-blocks are skipped.
    """
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[:, :] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:, :] = jnp.zeros_like(l_scr)
        acc_scr[:, :] = jnp.zeros_like(acc_scr)

    # A k-block strictly above the causal diagonal or entirely below the
    # sliding-window band contributes nothing — skip its matmuls entirely
    # (halves causal FLOPs; makes windowed cost O(S*window)).
    run = _band_run(qi, kj, block_q, block_k, causal, window)

    def _step(apply_causal, apply_window):
        q = q_ref[0, 0, :, :]  # (block_q, D)
        k = k_ref[0, 0, :, :]  # (block_k, D)
        v = v_ref[0, 0, :, :]  # (block_k, D)
        s = _masked_scores(
            q, k, qi, kj, scale=scale, block_q=block_q, block_k=block_k,
            causal=apply_causal, have_mask=have_mask, mask_ref=mask_ref,
            qseg_ref=qseg_ref, kseg_ref=kseg_ref,
            window=window if apply_window else None,
        )
        m_prev = m_scr[:, :1]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:, :] = acc_scr[:, :] * alpha + pv
        m_scr[:, :] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:, :] = jnp.broadcast_to(l_new, l_scr.shape)

    _causal_step_split(qi, kj, run, block_q=block_q, block_k=block_k,
                       causal=causal, step=_step, window=window)

    @pl.when(kj == n_k - 1)
    def _finalize():
        # l is always > 0: even a fully-masked row has p = exp(NEG_INF -
        # NEG_INF) = 1 per entry, so such rows output the uniform average of
        # V — identical to the XLA softmax path's behavior.
        l = l_scr[:, :1]
        o_ref[0, 0, :, :] = (acc_scr[:, :] / l).astype(o_ref.dtype)
        lse_ref[0, 0, 0, pl.ds(qi * block_q, block_q)] = (
            m_scr[:, 0] + jnp.log(l_scr[:, 0])
        )


def _fwd_kernel_1k(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q,
                   block_k, causal, have_mask, mask_ref=None,
                   qseg_ref=None, kseg_ref=None, window=None):
    """Single-k-block forward: the softmax in one pass, no online state.

    When the whole K/V sequence fits one k block (seq <= 1024 under the
    1024x1024 tiling, where the kernel is VPU-bound), the online-softmax
    recurrence degenerates to a
    plain row softmax: the m/l/acc scratch buffers, their init pass, the
    alpha rescale of the accumulator, and the (block_q, 128) broadcast
    writes are all dead work this kernel simply does not emit.  Same
    reduction order and masked-row semantics as :func:`_fwd_kernel` with
    n_k == 1 (a fully-masked row averages V, l = exp(0)*block_k > 0), so
    outputs are bit-identical.
    """
    qi = pl.program_id(2)
    # With K spanning the sequence, every causal q block straddles the
    # diagonal — no point splitting on it (see _causal_step_split).
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    v = v_ref[0, 0, :, :]
    s = _masked_scores(
        q, k, qi, 0, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, have_mask=have_mask, mask_ref=mask_ref,
        qseg_ref=qseg_ref, kseg_ref=kseg_ref, window=window,
    )
    m = jnp.max(s, axis=-1, keepdims=True)       # (block_q, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0, 0, :, :] = (pv / l).astype(o_ref.dtype)
    lse_ref[0, 0, 0, pl.ds(qi * block_q, block_q)] = (
        m[:, 0] + jnp.log(l[:, 0])
    )


def _extra_specs_and_args(mask, segment_ids, batch, seq, block_q, block_k,
                          mem, *, swap_grid=False, kv_segment_ids=None):
    """(in_specs, args, ref_names) for the optional mask / segment-id inputs.

    ``swap_grid``: the dkv kernel's grid is (B, H, n_k, n_q) — its index_map
    axis roles are swapped relative to the fwd/dq grids.
    ``kv_segment_ids``: distinct key/value-side segment array (ring
    attention rotates K/V chunks, so their segments differ from the local
    q shard's); defaults to ``segment_ids`` (self-attention).
    """
    if swap_grid:
        kidx = lambda b, h, j, i: (b, 0, j)
        qidx = lambda b, h, j, i: (b, 0, i)
    else:
        kidx = lambda b, h, i, j: (b, 0, j)
        qidx = lambda b, h, i, j: (b, 0, i)
    specs, args, names = [], [], []
    if mask is not None:
        specs.append(pl.BlockSpec((1, 1, block_k), kidx, memory_space=mem))
        args.append(mask.reshape(batch, 1, seq))
        names.append("mask_ref")
    if segment_ids is not None:
        qseg3 = segment_ids.reshape(batch, 1, seq).astype(jnp.int32)
        kseg = segment_ids if kv_segment_ids is None else kv_segment_ids
        kseg3 = kseg.reshape(batch, 1, seq).astype(jnp.int32)
        specs.append(pl.BlockSpec((1, 1, block_q), qidx, memory_space=mem))
        args.append(qseg3)
        names.append("qseg_ref")
        specs.append(pl.BlockSpec((1, 1, block_k), kidx, memory_space=mem))
        args.append(kseg3)
        names.append("kseg_ref")
    return specs, args, names


def _wrap_kernel(inner, n_fixed_in, extra_names, **kw):
    """Adapt ``inner(*fixed_refs, *outs_and_scratch, **extras, **kw)`` to the
    positional ref list pallas_call passes (fixed inputs, extra inputs,
    outputs+scratch)."""
    n_extra = len(extra_names)

    def kernel(*refs):
        fixed = refs[:n_fixed_in]
        extras = dict(zip(extra_names, refs[n_fixed_in:n_fixed_in + n_extra]))
        rest = refs[n_fixed_in + n_extra:]
        inner(*fixed, *rest, have_mask="mask_ref" in extras, **extras, **kw)

    return kernel


def _flash_forward(q, k, v, mask, segment_ids, kv_segment_ids=None, *,
                   causal, interpret, window=None,
                   block_q=None, block_k=None):
    # Mosaic needs the trailing two block dims tile-aligned or full-size:
    # this entry runs the kernels on (B, H, S, D) copies, so that (seq,
    # depth) are the trailing dims (flash_attention_qkv moves nothing).
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))

    o, lse, _ = _flash_forward_bhsd(qt, kt, vt, mask, segment_ids,
                                    kv_segment_ids, causal=causal,
                                    interpret=interpret, window=window,
                                    block_q=block_q, block_k=block_k)
    return o, lse


def _flash_forward_bhsd(qt, kt, vt, mask, segment_ids, kv_segment_ids=None,
                        *, causal, interpret, window=None,
                        block_q=None, block_k=None):
    """Forward on already-BHSD operands; returns (o BSHD, lse, o BHSD).

    The BHSD output is handed back so the custom VJP can save the
    transposed operands as residuals — the backward kernels consume
    BHSD, and re-deriving it there from BSHD residuals would re-emit
    the relayouts the forward already paid for.

    GQA (kt/vt with fewer heads): the kv index map sends q-head grid
    step ``h`` to kv head ``h // group`` — every q head in a group reads
    the SAME kv tile, so the sharing is zero-copy (no (B, Hq, S, D)
    broadcast ever exists in HBM)."""
    batch, heads, seq, depth = qt.shape
    group = heads // kt.shape[1]
    block_q, block_k = _resolve_blocks(
        batch, heads, seq, depth, qt.dtype, block_q, block_k
    )
    scale = 1.0 / (depth ** 0.5)
    grid = (batch, heads, seq // block_q, seq // block_k)
    mem = pl.ANY if interpret else pltpu.VMEM

    qspec = pl.BlockSpec(
        (1, 1, block_q, depth), lambda b, h, i, j: (b, h, i, 0),
        memory_space=mem,
    )
    kvspec = pl.BlockSpec(
        (1, 1, block_k, depth), lambda b, h, i, j: (b, h // group, j, 0),
        memory_space=mem,
    )
    extra_specs, extra_args, extra_names = _extra_specs_and_args(
        mask, segment_ids, batch, seq, block_q, block_k, mem,
        kv_segment_ids=kv_segment_ids,
    )
    one_k = seq // block_k == 1
    kernel = _wrap_kernel(
        _fwd_kernel_1k if one_k else _fwd_kernel, 3, extra_names,
        scale=scale, block_q=block_q, block_k=block_k, causal=causal,
        window=window,
    )

    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[qspec, kvspec, kvspec, *extra_specs],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, depth),
                         lambda b, h, i, j: (b, h, i, 0), memory_space=mem),
            # (B, H, 1, S) keeps the trailing block dims (1, S) tile-legal
            pl.BlockSpec((1, 1, 1, seq), lambda b, h, i, j: (b, h, 0, 0),
                         memory_space=mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, qt.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, seq), jnp.float32),
        ],
        scratch_shapes=[] if one_k else [
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, depth), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt, *extra_args)
    return o.transpose(0, 2, 1, 3), lse[:, :, 0, :], o


# --- Backward: Pallas kernels (fused single sweep, or dq + dkv split) -------

#: "pallas" (default: the fused single-sweep kernel when the dq scratch
#: fits VMEM, else the split pair), "pallas_split" (force the two-kernel
#: dq/dkv path), or "xla" — the XLA blockwise recompute kept as the
#: golden reference for A/B numerics and as an escape hatch.  Read at TRACE
#: time: a function jitted before flipping this keeps its compiled backward
#: (jit caching) — for a reliable A/B pass ``backward_impl=`` to
#: :func:`flash_attention` and re-jit instead of mutating mid-run.
BACKWARD_IMPL = "pallas"

#: The fused backward keeps the WHOLE (S, D) fp32 dq for the current
#: (batch, head) in VMEM scratch; above this budget the split pair runs
#: instead (at D=64 the cutoff is seq 8192).  2 MiB, not 4: the scratch
#: shares the 16 MB VMEM with the (1024, 1024) fp32 score/p/dp/ds tiles,
#: and a 4 MiB scratch compiled but OOM'd AT RUN TIME on the v5e at
#: seq 16384 (8192 runs).
FUSED_BWD_DQ_SCRATCH_BYTES = 2 * 2**20


def _bwd_fused_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_all_scr, dk_scr, dv_scr,
                      *, scale, block_q, block_k, causal,
                      have_mask, mask_ref=None, qseg_ref=None,
                      kseg_ref=None, window=None):
    """dq, dk and dv in ONE sweep — the p-tile is recomputed once.

    The split pair pays 7 matmuls + 2 exp-of-score-tile passes per
    (q-block, k-block) pair (each kernel recomputes s and p); this kernel
    pays 5 matmuls + 1 exp.  Grid (B, H, n_k, n_q), q innermost:

    - dk/dv accumulate per-k-block in scratch, flushed at the last
      q-block — the same consecutive-revisit pattern as the split dkv
      kernel;
    - dq accumulates into a full (S, D) fp32 scratch for the current
      (b, h) (zeroed at the slice's first grid step).  Its output block
      is indexed by the INNER axis, so every visit writes the running
      partial sum unconditionally — Pallas flushes an output buffer
      whenever its index changes, and a visit that skipped the write
      (e.g. under the causal guard) would flush stale bytes from the
      previous q-block.  The final sweep (j == n_k-1) overwrites every
      block with the completed sum.
    """
    j = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dq_all_scr[:, :] = jnp.zeros_like(dq_all_scr)

    @pl.when(i == 0)
    def _init_dkv():
        dk_scr[:, :] = jnp.zeros_like(dk_scr)
        dv_scr[:, :] = jnp.zeros_like(dv_scr)

    run = _band_run(i, j, block_q, block_k, causal, window)

    def _step(apply_causal, apply_window):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        gq = g_ref[0, 0, :, :]
        s = _masked_scores(
            q, k, i, j, scale=scale, block_q=block_q, block_k=block_k,
            causal=apply_causal, have_mask=have_mask, mask_ref=mask_ref,
            qseg_ref=qseg_ref, kseg_ref=kseg_ref,
            window=window if apply_window else None,
        )
        lse = lse_ref[0, 0, 0, :]  # (block_q,)
        p = jnp.exp(s - lse[:, None])
        dv_scr[:, :] = dv_scr[:, :] + jax.lax.dot_general(
            p.astype(gq.dtype), gq, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, D)
        dp = jax.lax.dot_general(
            gq, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        delta = delta_ref[0, 0, 0, :]
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_scr[:, :] = dk_scr[:, :] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, D)
        row = pl.ds(i * block_q, block_q)
        dq_all_scr[row] = dq_all_scr[row] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, D)

    _causal_step_split(i, j, run, block_q=block_q, block_k=block_k,
                       causal=causal, step=_step, window=window)

    # Unconditional writes: see the docstring on flush semantics.
    dq_ref[0, 0, :, :] = dq_all_scr[pl.ds(i * block_q, block_q)].astype(
        dq_ref.dtype
    )
    n_q = pl.num_programs(3)

    @pl.when(i == n_q - 1)
    def _flush_dkv():
        dk_ref[0, 0, :, :] = dk_scr[:, :].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:, :].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, block_q, block_k, causal,
                   have_mask, mask_ref=None, qseg_ref=None, kseg_ref=None,
                   window=None):
    """dq for one q-block, accumulated over the k sweep (k innermost).

    Recomputes the p-tile from the saved LSE:
      p  = exp(q k^T * scale - lse)
      ds = p * (g v^T - delta) * scale
      dq = sum_k ds @ k
    """
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:, :] = jnp.zeros_like(dq_scr)

    run = _band_run(qi, kj, block_q, block_k, causal, window)

    def _step(apply_causal, apply_window):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        gq = g_ref[0, 0, :, :]
        s = _masked_scores(
            q, k, qi, kj, scale=scale, block_q=block_q, block_k=block_k,
            causal=apply_causal, have_mask=have_mask, mask_ref=mask_ref,
            qseg_ref=qseg_ref, kseg_ref=kseg_ref,
            window=window if apply_window else None,
        )
        lse = lse_ref[0, 0, 0, :]  # (block_q,)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            gq, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        delta = delta_ref[0, 0, 0, :]  # (block_q,)
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[:, :] = dq_scr[:, :] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _causal_step_split(qi, kj, run, block_q=block_q, block_k=block_k,
                       causal=causal, step=_step, window=window)

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[:, :].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block_q,
                    block_k, causal, have_mask, mask_ref=None,
                    qseg_ref=None, kseg_ref=None, window=None):
    """dk/dv for one k-block, accumulated over the q sweep (q innermost).

      dv = sum_q p^T @ g
      dk = sum_q ds^T @ q
    """
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:, :] = jnp.zeros_like(dk_scr)
        dv_scr[:, :] = jnp.zeros_like(dv_scr)

    # A q-block strictly above the causal diagonal (all q < all k) never
    # attends to this k-block; one entirely below the window band neither.
    run = _band_run(qi, kj, block_q, block_k, causal, window)

    def _step(apply_causal, apply_window):
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        gq = g_ref[0, 0, :, :]
        s = _masked_scores(
            q, k, qi, kj, scale=scale, block_q=block_q, block_k=block_k,
            causal=apply_causal, have_mask=have_mask, mask_ref=mask_ref,
            qseg_ref=qseg_ref, kseg_ref=kseg_ref,
            window=window if apply_window else None,
        )
        lse = lse_ref[0, 0, 0, :]  # (block_q,)
        p = jnp.exp(s - lse[:, None])  # (block_q, block_k)
        dv_scr[:, :] = dv_scr[:, :] + jax.lax.dot_general(
            p.astype(gq.dtype), gq, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, D)
        dp = jax.lax.dot_general(
            gq, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = delta_ref[0, 0, 0, :]
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[:, :] = dk_scr[:, :] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, D)

    _causal_step_split(qi, kj, run, block_q=block_q, block_k=block_k,
                       causal=causal, step=_step, window=window)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:, :].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:, :].astype(dv_ref.dtype)


def _flash_backward_pallas(res, g, *, causal, interpret, force_split=False,
                           window=None, block_q=None, block_k=None):
    """Backward from the custom-VJP residuals (BHSD operands + BHSD o).

    GQA residuals hold K/V compact (Hkv heads).  The forward shares
    tiles zero-copy via its index map; the backward instead broadcasts
    K/V to Hq for the unchanged kernels and group-sums dk/dv afterwards
    — a deliberate simplicity trade: training-side GQA gains are in the
    QKV projection, not here, while the decode path (where the cache
    stream IS the bound) gets native grouping in ops.attention."""
    qt, kt, vt, mask, segment_ids, ot, lse = res
    heads, kv_heads = qt.shape[1], kt.shape[1]
    if kv_heads != heads:
        group = heads // kv_heads
        kt, vt = (
            jnp.repeat(x, group, axis=1) for x in (kt, vt)
        )
    gt = g.transpose(0, 2, 1, 3)
    # delta = rowsum(dO * O): cheap elementwise+reduce, XLA fuses it.
    delta = jnp.einsum(
        "bhqd,bhqd->bhq", gt.astype(jnp.float32), ot.astype(jnp.float32)
    )
    dqt, dkt, dvt = _flash_backward_pallas_bhsd(
        qt, kt, vt, gt, mask, lse, delta, segment_ids=segment_ids,
        causal=causal, interpret=interpret, force_split=force_split,
        window=window, block_q=block_q, block_k=block_k,
    )
    if kv_heads != heads:
        b, _, s, d = dkt.shape
        dkt = dkt.reshape(b, kv_heads, group, s, d).sum(axis=2)
        dvt = dvt.reshape(b, kv_heads, group, s, d).sum(axis=2)
    bsdh = lambda x: x.transpose(0, 2, 1, 3)
    return bsdh(dqt), bsdh(dkt), bsdh(dvt)


def _flash_backward_pallas_core(q, k, v, mask, g, lse, delta, *,
                                segment_ids=None, kv_segment_ids=None,
                                causal, interpret, force_split=False,
                                window=None):
    """dq/dk/dv kernels from externally-supplied LSE and delta rows.

    BSHD entry kept for ring attention (``parallel/ring_attention.py``),
    which drives the same kernels per K/V chunk with the *global*
    (cross-chunk) LSE.  ``lse``/``delta`` are (B, H, S) fp32.
    """
    qt, kt, vt, gt = (x.transpose(0, 2, 1, 3) for x in (q, k, v, g))
    dqt, dkt, dvt = _flash_backward_pallas_bhsd(
        qt, kt, vt, gt, mask, lse, delta, segment_ids=segment_ids,
        kv_segment_ids=kv_segment_ids, causal=causal, interpret=interpret,
        force_split=force_split, window=window,
    )
    bsdh = lambda x: x.transpose(0, 2, 1, 3)
    return bsdh(dqt), bsdh(dkt), bsdh(dvt)


def _flash_backward_pallas_bhsd(qt, kt, vt, gt, mask, lse, delta, *,
                                segment_ids=None, kv_segment_ids=None,
                                causal, interpret, force_split=False,
                                window=None, block_q=None, block_k=None):
    """The dq/dk/dv kernels on BHSD operands; grads returned BHSD.

    Dispatch: the fused single-sweep kernel (one p-recompute) when the
    (S, D) fp32 dq scratch fits ``FUSED_BWD_DQ_SCRATCH_BYTES``, else —
    or under ``force_split`` — the original dq + dkv pair.
    """
    batch, heads, seq, depth = qt.shape
    block_q, block_k = _resolve_blocks(
        batch, heads, seq, depth, qt.dtype, block_q, block_k
    )
    scale = 1.0 / (depth ** 0.5)
    mem = pl.ANY if interpret else pltpu.VMEM

    # (B, H, 1, S) keeps kernel blocks' trailing dims tile-legal like lse.
    delta = delta[:, :, None, :]
    lse4 = lse[:, :, None, :]  # (B, H, 1, S)

    if not force_split and seq * depth * 4 <= FUSED_BWD_DQ_SCRATCH_BYTES:
        fused_specs = [
            pl.BlockSpec((1, 1, block_q, depth),
                         lambda b, h, j, i: (b, h, i, 0),
                         memory_space=mem),  # q
            pl.BlockSpec((1, 1, block_k, depth),
                         lambda b, h, j, i: (b, h, j, 0),
                         memory_space=mem),  # k
            pl.BlockSpec((1, 1, block_k, depth),
                         lambda b, h, j, i: (b, h, j, 0),
                         memory_space=mem),  # v
            pl.BlockSpec((1, 1, block_q, depth),
                         lambda b, h, j, i: (b, h, i, 0),
                         memory_space=mem),  # g
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, j, i: (b, h, 0, i),
                         memory_space=mem),  # lse
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, j, i: (b, h, 0, i),
                         memory_space=mem),  # delta
        ]
        extra_specs, extra_args, extra_names = _extra_specs_and_args(
            mask, segment_ids, batch, seq, block_q, block_k, mem,
            swap_grid=True, kv_segment_ids=kv_segment_ids,
        )
        kernel = _wrap_kernel(
            _bwd_fused_kernel, 6, extra_names,
            scale=scale, block_q=block_q, block_k=block_k, causal=causal,
            window=window,
        )
        dqt, dkt, dvt = pl.pallas_call(
            kernel,
            name="flash_bwd",
            grid=(batch, heads, seq // block_k, seq // block_q),
            in_specs=fused_specs + extra_specs,
            out_specs=[
                pl.BlockSpec((1, 1, block_q, depth),
                             lambda b, h, j, i: (b, h, i, 0),
                             memory_space=mem),
                pl.BlockSpec((1, 1, block_k, depth),
                             lambda b, h, j, i: (b, h, j, 0),
                             memory_space=mem),
                pl.BlockSpec((1, 1, block_k, depth),
                             lambda b, h, j, i: (b, h, j, 0),
                             memory_space=mem),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                jax.ShapeDtypeStruct(vt.shape, vt.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((seq, depth), jnp.float32),     # dq, whole (b,h)
                pltpu.VMEM((block_k, depth), jnp.float32),  # dk
                pltpu.VMEM((block_k, depth), jnp.float32),  # dv
            ],
            interpret=interpret,
        )(qt, kt, vt, gt, lse4, delta, *extra_args)
        return dqt, dkt, dvt

    # --- dq kernel: grid (B, H, n_q, n_k), k innermost ---
    dq_in_specs = [
        pl.BlockSpec((1, 1, block_q, depth), lambda b, h, i, j: (b, h, i, 0),
                     memory_space=mem),  # q
        pl.BlockSpec((1, 1, block_k, depth), lambda b, h, i, j: (b, h, j, 0),
                     memory_space=mem),  # k
        pl.BlockSpec((1, 1, block_k, depth), lambda b, h, i, j: (b, h, j, 0),
                     memory_space=mem),  # v
        pl.BlockSpec((1, 1, block_q, depth), lambda b, h, i, j: (b, h, i, 0),
                     memory_space=mem),  # g
        pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i),
                     memory_space=mem),  # lse
        pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i),
                     memory_space=mem),  # delta
    ]
    extra_specs, extra_args, extra_names = _extra_specs_and_args(
        mask, segment_ids, batch, seq, block_q, block_k, mem,
        kv_segment_ids=kv_segment_ids,
    )
    dq_in_specs += extra_specs
    dq_args = [qt, kt, vt, gt, lse4, delta, *extra_args]
    dq_kernel = _wrap_kernel(
        _bwd_dq_kernel, 6, extra_names,
        scale=scale, block_q=block_q, block_k=block_k, causal=causal,
        window=window,
    )

    dqt = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(batch, heads, seq // block_q, seq // block_k),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, depth),
                               lambda b, h, i, j: (b, h, i, 0),
                               memory_space=mem),
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, depth), jnp.float32)],
        interpret=interpret,
    )(*dq_args)

    # --- dk/dv kernel: grid (B, H, n_k, n_q), q innermost ---
    dkv_in_specs = [
        pl.BlockSpec((1, 1, block_q, depth), lambda b, h, j, i: (b, h, i, 0),
                     memory_space=mem),  # q
        pl.BlockSpec((1, 1, block_k, depth), lambda b, h, j, i: (b, h, j, 0),
                     memory_space=mem),  # k
        pl.BlockSpec((1, 1, block_k, depth), lambda b, h, j, i: (b, h, j, 0),
                     memory_space=mem),  # v
        pl.BlockSpec((1, 1, block_q, depth), lambda b, h, j, i: (b, h, i, 0),
                     memory_space=mem),  # g
        pl.BlockSpec((1, 1, 1, block_q), lambda b, h, j, i: (b, h, 0, i),
                     memory_space=mem),  # lse
        pl.BlockSpec((1, 1, 1, block_q), lambda b, h, j, i: (b, h, 0, i),
                     memory_space=mem),  # delta
    ]
    extra_specs2, extra_args2, extra_names2 = _extra_specs_and_args(
        mask, segment_ids, batch, seq, block_q, block_k, mem, swap_grid=True,
        kv_segment_ids=kv_segment_ids,
    )
    dkv_in_specs += extra_specs2
    dkv_args = [qt, kt, vt, gt, lse4, delta, *extra_args2]
    dkv_kernel = _wrap_kernel(
        _bwd_dkv_kernel, 6, extra_names2,
        scale=scale, block_q=block_q, block_k=block_k, causal=causal,
        window=window,
    )

    dkt, dvt = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(batch, heads, seq // block_k, seq // block_q),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_k, depth),
                         lambda b, h, j, i: (b, h, j, 0), memory_space=mem),
            pl.BlockSpec((1, 1, block_k, depth),
                         lambda b, h, j, i: (b, h, j, 0), memory_space=mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kt.shape, kt.dtype),
            jax.ShapeDtypeStruct(vt.shape, vt.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, depth), jnp.float32),
            pltpu.VMEM((block_k, depth), jnp.float32),
        ],
        interpret=interpret,
    )(*dkv_args)

    return dqt, dkt, dvt


# --- Backward (blockwise XLA recompute from LSE — golden fallback) ----------


def _flash_backward_xla(res, g, *, causal, window=None):
    q, k, v, mask, segment_ids, o, lse = res
    batch, seq, heads, depth = q.shape
    # Fixed 128-row blocks, deliberately NOT _pick_block_q: this path's
    # per-scan-step (B, H, block_q, S) fp32 score/p/ds temporaries scale
    # with block_q, and the 1024-block Pallas retune (or a sweep env
    # override) would inflate them 8x — at 32k seq that is ~1.6 GB per
    # live temporary, an HBM OOM on exactly the long sequences this
    # recompute fallback exists to fit.
    block_q = next(
        (b for b in (128, 64, 32, 16, 8) if seq % b == 0), None
    )
    scale = 1.0 / (depth ** 0.5)
    n_blocks = seq // block_q

    # fp32 working copies, BHSD-free: keep BSHD, contract with einsum strings
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    of = o.astype(jnp.float32)
    delta = jnp.einsum("bqhd,bqhd->bhq", gf, of)  # rowsum(dO * O)

    def reblock(x):  # (B, S, H, D) -> (n, B, bq, H, D)
        return x.reshape(batch, n_blocks, block_q, heads, depth).transpose(
            1, 0, 2, 3, 4
        )

    q_blocks = reblock(qf)
    g_blocks = reblock(gf)
    lse_blocks = lse.reshape(batch, heads, n_blocks, block_q).transpose(2, 0, 1, 3)
    delta_blocks = delta.reshape(batch, heads, n_blocks, block_q).transpose(2, 0, 1, 3)
    k_pos = jnp.arange(seq)
    seg_blocks = (
        segment_ids.reshape(batch, n_blocks, block_q).transpose(1, 0, 2)
        if segment_ids is not None else jnp.zeros((n_blocks, batch, 1), jnp.int32)
    )

    def body(carry, xs):
        dk_acc, dv_acc = carry
        qb, gb, lseb, deltab, segb, blk = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kf) * scale
        if causal or window is not None:
            q_pos = blk * block_q + jnp.arange(block_q)
            keep = (
                q_pos[:, None] >= k_pos[None, :] if causal
                else jnp.ones((block_q, seq), bool)
            )
            if window is not None:
                keep &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(keep[None, None, :, :], s, NEG_INF)
        if mask is not None:
            s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        if segment_ids is not None:
            s = jnp.where(
                segb[:, None, :, None] == segment_ids[:, None, None, :],
                s, NEG_INF,
            )
        p = jnp.exp(s - lseb[:, :, :, None])  # (B, H, bq, S)
        dv_acc = dv_acc + jnp.einsum("bhqk,bqhd->bkhd", p, gb)
        dp = jnp.einsum("bqhd,bkhd->bhqk", gb, vf)
        ds = p * (dp - deltab[:, :, :, None]) * scale
        dqb = jnp.einsum("bhqk,bkhd->bqhd", ds, kf)
        dk_acc = dk_acc + jnp.einsum("bhqk,bqhd->bkhd", ds, qb)
        return (dk_acc, dv_acc), dqb

    zeros = jnp.zeros_like(kf)
    (dk, dv), dq_blocks = jax.lax.scan(
        body, (zeros, zeros),
        (q_blocks, g_blocks, lse_blocks, delta_blocks, seg_blocks,
         jnp.arange(n_blocks)),
    )
    dq = dq_blocks.transpose(1, 0, 2, 3, 4).reshape(batch, seq, heads, depth)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --- Public entry with custom VJP -------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, mask, segment_ids, causal, interpret, backward_impl,
           window, block_q, block_k):
    o, _ = _flash_forward(q, k, v, mask, segment_ids, causal=causal,
                          interpret=interpret, window=window,
                          block_q=block_q, block_k=block_k)
    return o


def _flash_fwd(q, k, v, mask, segment_ids, causal, interpret, backward_impl,
               window, block_q, block_k):
    # Residuals are saved in the BHSD layout the kernels consume: the
    # forward already paid for these relayouts, and saving the BSHD
    # originals instead would make the backward re-emit all four (whole-
    # tensor copies each: PERF.md section 6, PR 35).
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    o, lse, ot = _flash_forward_bhsd(qt, kt, vt, mask, segment_ids,
                                     causal=causal, interpret=interpret,
                                     window=window,
                                     block_q=block_q, block_k=block_k)
    return o, (qt, kt, vt, mask, segment_ids, ot, lse)


def _flash_bwd(causal, interpret, backward_impl, window, block_q, block_k,
               res, g):
    impl = backward_impl or BACKWARD_IMPL
    if impl in ("pallas", "pallas_split"):
        dq, dk, dv = _flash_backward_pallas(
            res, g, causal=causal, interpret=interpret,
            force_split=(impl == "pallas_split"), window=window,
            block_q=block_q, block_k=block_k,
        )
    else:
        qt, kt, vt, mask, segment_ids, ot, lse = res
        q, k, v, o = (t.transpose(0, 2, 1, 3) for t in (qt, kt, vt, ot))
        heads, kv_heads = q.shape[2], k.shape[2]
        if kv_heads != heads:  # GQA: broadcast for the equal-head fallback
            group = heads // kv_heads
            k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        dq, dk, dv = _flash_backward_xla(
            (q, k, v, mask, segment_ids, o, lse), g, causal=causal,
            window=window,
        )
        if kv_heads != heads:
            b, s, _, d = dk.shape
            dk = dk.reshape(b, s, kv_heads, group, d).sum(axis=3)
            dv = dv.reshape(b, s, kv_heads, group, d).sum(axis=3)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _check_rows(qshape, mask, segment_ids, causal, window, block_q,
                block_k):
    """The public entries' checks of what is not q, k or v, for a (B, S,
    H, D) query shape: ``(padding mask (B, S) or None, segment_ids,
    window)`` as the kernels take them, or a ValueError."""
    if _pick_block_q(qshape[1]) is None:
        raise ValueError(
            f"sequence length {qshape[1]} not divisible by any supported "
            "q-block size (multiple of 8 required)"
        )
    if mask is not None and not _is_padding_mask(mask, qshape):
        raise ValueError(
            f"mask shape {mask.shape} unsupported: need (B, S) or "
            "(B, 1, 1, S) padding mask"
        )
    if segment_ids is not None and not _is_segment_ids(segment_ids, qshape):
        raise ValueError(
            f"segment_ids shape/dtype unsupported: need int (B, S), got "
            f"{segment_ids.shape} {segment_ids.dtype}"
        )
    if window is not None:
        if not causal:
            raise ValueError(
                "window (sliding-window attention) requires causal=True — "
                "a lower-edge-only band has unbounded lookahead"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if window >= qshape[1]:
            window = None  # full causal attention; skip the dead masking
    for name, b in (("block_q", block_q), ("block_k", block_k)):
        if b is not None and (b <= 0 or qshape[1] % b):
            raise ValueError(
                f"{name}={b} must be a positive divisor of seq "
                f"{qshape[1]}"
            )
    return _as_padding_mask(mask, qshape), segment_ids, window


def flash_attention(q, k, v, *, mask=None, segment_ids=None, causal=False,
                    interpret=None, backward_impl=None, window=None,
                    block_q=None, block_k=None):
    """Flash attention, BSHD layout; differentiable.

    ``mask`` is a padding mask (B, S) or (B, 1, 1, S), True = attend.
    ``segment_ids`` is an int (B, S) array for packed sequences (BERT-style
    example packing): tokens attend only within their own segment; composes
    with ``mask`` and ``causal``.
    ``interpret=None`` auto-selects interpreter mode off-TPU (for tests).
    ``backward_impl`` picks the backward: None = module ``BACKWARD_IMPL``
    default, "pallas" = fused single-sweep kernel (split pair when the dq
    scratch exceeds VMEM budget), "pallas_split" = force the dq + dkv
    pair, "xla" = blockwise-recompute golden path.
    ``window`` (int, requires ``causal=True``) enables sliding-window
    attention: token i attends keys in ``(i - window, i]``.  Block pairs
    entirely below the band are skipped outright, so cost scales
    O(S * window) instead of O(S^2); ``window >= seq`` degrades to plain
    causal.
    ``block_q`` / ``block_k`` pin the kernel tiling explicitly (the sweep
    driver ``tools/autotune_flash.py`` and A/B benches use this); left
    None, the tiling resolves env override > autotune cache
    (``ops/flash_tuning.py``, keyed on shape/dtype/platform) > the
    retuned default chain.
    Raises ValueError for shapes/masks the kernel cannot handle (callers
    wanting silent fallback should go through
    ``ops.attention.dot_product_attention`` with ``implementation="auto"``).
    """
    if q.ndim != 4 or k.shape != v.shape or not _gqa_ok(q.shape, k.shape):
        raise ValueError(
            f"flash_attention needs BSHD q/k/v with matching (B, S, D) and "
            f"q heads a multiple of kv heads (GQA), got {q.shape} "
            f"{k.shape} {v.shape}"
        )
    pad, segment_ids, window = _check_rows(
        q.shape, mask, segment_ids, causal, window, block_q, block_k)
    if interpret is None:
        interpret = not on_tpu()

    def local(q, k, v, pad, segment_ids):
        return _flash(q, k, v, pad, segment_ids, causal, interpret,
                      backward_impl, window, block_q, block_k)

    # batch over the data axes, heads over the tensor-parallel axis (kv
    # heads decide: each shard must keep whole GQA groups); a spec on an
    # absent (None) row operand applies to nothing
    batch = kernel_axes(mesh_lib.BATCH_AXES, q.shape[0])
    heads = kernel_axes((mesh_lib.AXIS_MODEL,), k.shape[2])
    qkv = P(batch, None, heads, None)
    row = P(batch, None)
    return shard_kernel(local, (qkv, qkv, qkv, row, row), qkv)(
        q, k, v, pad, segment_ids
    )


# --- The fused projection read as it lies: lane tiles of qkv ----------------
#
# ``qkv`` is the block's one projection, (B, S, 3*H*D) with the q, k and v
# thirds side by side in the lane dimension.  The kernels below take it three
# times, each through an index map that picks lane tile ``p`` of one third
# (a tile is 128 lanes: two heads of 64, four of 32, or one head of a
# multiple of 128), do their tile's heads one after the other and write o,
# dq, dk and dv as the same lane tiles of (B, S, H*D) arrays.  The rotary
# embedding is applied in VMEM.  Between the projection and the kernel q, k
# and v take no trip through HBM: no split, no reshape to heads, no
# transpose to (B, H, S, D), no rotary product.  The softmax, the masks and
# the order of every sum inside a head are those of the kernels above.

LANES = 128

#: Scoped VMEM of the tile kernels.  A grid step holds what a step of the
#: (B, H, S, D) kernels holds (a (1024, 1024) float32 score tile and its
#: temporaries, by Mosaic's 16 MiB default) and beside it the rotation's
#: four table blocks, double-buffered, and the second head's running state:
#: four heads of 32, or a window's two masks at 1024 x 1024, overflow the
#: default (compiled for a described v5e, tests/test_kernel_export.py).
#: A v5e core has 128 MiB; GPT-2 medium's step is no slower for the room
#: (1561 ms against 1580 under the default, the same at 64 and 100 MiB: my
#: chip runs, PR 35).
TILES_VMEM_LIMIT_BYTES = 32 * 2**20


def tile_heads(heads: int, kv_heads: int, depth: int) -> int | None:
    """Heads a lane tile of the fused projection holds, or None where its
    lane tiles do not hold whole heads of q, k and v alike (GQA, a head
    width that neither divides 128 nor is a multiple of it, a head count
    that leaves a tile half full) or the rotation has no halves to swap."""
    if heads != kv_heads or depth % 2:
        return None
    if depth % LANES == 0:
        return 1
    if LANES % depth or heads % (LANES // depth):
        return None
    return LANES // depth


def _head_lanes(shape, a, depth):
    """(rows, tile) mask of the lanes head ``a`` of the tile holds; None
    when the tile is one head."""
    if shape[-1] == depth:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= a * depth) & (lane < (a + 1) * depth)


def _only_head(x, a, depth):
    """``x`` with the lanes of the tile's other heads zeroed: a contraction
    over the whole tile is then head ``a``'s contraction over exact zeros
    (the same MXU passes as a ``depth``-deep one, the same sums)."""
    lanes = _head_lanes(x.shape, a, depth)
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _put_head(tile, new, a, depth):
    """``tile`` with head ``a``'s lanes taken from ``new``."""
    lanes = _head_lanes(new.shape, a, depth)
    return new if lanes is None else jnp.where(lanes, new, tile)


def _half_swap(x, depth):
    """Every head's halves swapped, (rows, tile) float32: two lane rolls
    and a select (one roll when the tile is one head).  Its own inverse
    and its own transpose."""
    tile, half = x.shape[-1], depth // 2
    if tile == depth:
        return pltpu.roll(x, half, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane % depth < half,
                     pltpu.roll(x, tile - half, 1), pltpu.roll(x, half, 1))


def _rotate(x, cos_ref, sin_ref, depth, rows=slice(None)):
    """Rotary embedding of a (rows, tile) block, ``x*cos + swap(x)*sin`` in
    float32 with ``sin`` sign-folded, rounded once to ``x``'s type; ``x``
    itself when the call rotates nothing.  ``rows``: the rows of the
    tables' block that ``x`` holds."""
    if cos_ref is None:
        return x
    x32 = x.astype(jnp.float32)
    out = (x32 * cos_ref[0, rows, :].astype(jnp.float32)
           + _half_swap(x32, depth) * sin_ref[0, rows, :].astype(jnp.float32))
    return out.astype(x.dtype)


def _softmax_pv(s, v):
    """One-pass softmax of a score tile whose rows see all their keys at
    once, times ``v``: ``(o (rows, tile) float32, log-sum-exp (rows, 1))``."""
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return pv / l, m + jnp.log(l)


def _store_lse(lse_ref, columns, rows):
    """Write the heads' log-sum-exp ``columns`` (a (rows, 1) or (rows,
    lanes) float32 column a head, one value a q row) into ``rows`` of the
    (1, hp, 1, S) block, which wants them along the lanes.  The heads are
    packed into the lanes of one (rows, 128) tile, head ``a`` in lane
    ``a``, and turned by one transpose; its first ``hp`` rows are the
    block's.  Storing a head's ``column[:, 0]`` leaves the turn to Mosaic's
    relayout of a (rows,) vector, which took 1.0 ms of the forward's 3.65 a
    layer at GPT-2 medium's shapes (my chip runs, PR 40: PERF.md section 6);
    the same bits either way.  Rows that fill no 128-lane tile keep that
    store."""
    n_rows = columns[0].shape[0]
    if n_rows % LANES:
        for a, column in enumerate(columns):
            lse_ref[0, a, 0, rows] = column[:, 0]
        return
    pack = jnp.broadcast_to(columns[0], (n_rows, LANES))
    if len(columns) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, pack.shape, 1)
        for a, column in enumerate(columns[1:], 1):
            pack = jnp.where(lane == a, column, pack)
    lse_ref[0, :, 0, rows] = pack.T[:len(columns), :]


def _rotated_k(k_ref, k_scr, cos_ref, sin_ref, depth):
    """The grid step's rotated k block as a ref the sub-tiles slice: the
    scratch ``k_scr`` (a one-element tuple) filled with the rotation, or
    the input block itself where the call rotates nothing."""
    if cos_ref is None:
        return k_ref.at[0]
    (k_all,) = k_scr
    k_all[:, :] = _rotate(k_ref[0], cos_ref, sin_ref, depth)
    return k_all


def _sub_tiles(block, tile):
    """``(r, rows, cols)`` of a diagonal block's row sub-tiles: static
    slices of the block's rows and of the keys up to the sub-tile's own
    diagonal."""
    return [(r, pl.ds(r * tile, tile), pl.ds(0, (r + 1) * tile))
            for r in range(block // tile)]


def _unrotate(dx, cos_ref, sin_ref, depth):
    """The rotation's transpose on a float32 gradient block:
    ``dx*cos + swap(dx*sin)``."""
    if cos_ref is None:
        return dx
    return (dx * cos_ref[0].astype(jnp.float32)
            + _half_swap(dx * sin_ref[0].astype(jnp.float32), depth))


def _fwd_tiles_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      q_scr, m_scr, l_scr, acc_scr, *k_scr, depth, scale,
                      block_q, block_k, causal, have_mask, mask_ref=None,
                      qseg_ref=None, kseg_ref=None, window=None,
                      cq_ref=None, sq_ref=None, ck_ref=None, sk_ref=None,
                      causal_tile=None):
    """:func:`_fwd_kernel` on one lane tile of ``qkv``: grid (B, tiles, n_q,
    n_k), the running max and sum a head, one (block_q, tile) accumulator;
    q is rotated once a q block, k once a visit.  With ``causal_tile`` the
    step on the diagonal runs the recurrence a row sub-tile at a time over
    the keys up to the sub-tile's diagonal (``k_scr``: the visit's rotated
    k, where the call rotates)."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)
    hp = q_ref.shape[-1] // depth

    @pl.when(kj == 0)
    def _init():
        q_scr[:, :] = _rotate(q_ref[0], cq_ref, sq_ref, depth)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[:, :] = jnp.zeros_like(acc_scr)

    run = _band_run(qi, kj, block_q, block_k, causal, window)

    rows_kw = dict(scale=scale, have_mask=have_mask, mask_ref=mask_ref,
                   qseg_ref=qseg_ref, kseg_ref=kseg_ref)

    def _accumulate(q, k, v, rows, scores):
        """The recurrence over the block's ``rows``, a head at a time."""
        for a in range(hp):
            s = scores(_only_head(q, a, depth), k)
            m_prev = m_scr[a, rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_scr[a, rows, :1] * alpha + jnp.sum(p, axis=-1,
                                                         keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = acc_scr[rows, :]
            acc_scr[rows, :] = _put_head(acc, acc * alpha + pv, a, depth)
            m_scr[a, rows, :] = jnp.broadcast_to(
                m_new, (m_new.shape[0], m_scr.shape[2]))
            l_scr[a, rows, :] = jnp.broadcast_to(
                l_new, (l_new.shape[0], l_scr.shape[2]))

    def _step(apply_causal, apply_window):
        win = window if apply_window else None
        if not (apply_causal and causal_tile):
            _accumulate(
                q_scr[:, :], _rotate(k_ref[0], ck_ref, sk_ref, depth),
                v_ref[0], slice(None),
                lambda q, k: _masked_scores(
                    q, k, qi, kj, block_q=block_q, block_k=block_k,
                    causal=apply_causal, window=win, **rows_kw))
            return
        k_all = _rotated_k(k_ref, k_scr, ck_ref, sk_ref, depth)
        for r, rows, cols in _sub_tiles(block_q, causal_tile):
            _accumulate(
                q_scr[rows, :], k_all[cols, :], v_ref[0, cols, :], rows,
                lambda q, k, r=r: _diagonal_scores(
                    q, k, r, causal_tile, window=win, **rows_kw))

    _causal_step_split(qi, kj, run, block_q=block_q, block_k=block_k,
                       causal=causal, step=_step, window=window)

    @pl.when(kj == n_k - 1)
    def _finalize():
        acc = acc_scr[:, :]
        o = acc
        for a in range(hp):
            o = _put_head(o, acc / l_scr[a, :, :1], a, depth)
        _store_lse(lse_ref, [m_scr[a] + jnp.log(l_scr[a]) for a in range(hp)],
                   pl.ds(qi * block_q, block_q))
        o_ref[0] = o.astype(o_ref.dtype)


def _fwd_tiles_kernel_1k(q_ref, k_ref, v_ref, o_ref, lse_ref, *k_scr,
                         depth, scale, block_q, block_k, causal, have_mask,
                         mask_ref=None, qseg_ref=None, kseg_ref=None,
                         window=None, cq_ref=None, sq_ref=None, ck_ref=None,
                         sk_ref=None, causal_tile=None):
    """:func:`_fwd_kernel_1k` on one lane tile of ``qkv``: the whole K/V
    sequence is one k block, so each head's softmax is one pass.  With
    ``causal_tile`` (the one block is on the diagonal) the pass is made a
    row sub-tile at a time over the keys up to the sub-tile's diagonal: a
    row still sees all its keys at once, and the upper triangle's scores,
    exponentials and products are never formed.  k is rotated once a grid
    step either way (into ``k_scr``, which the sub-tiles slice)."""
    qi = pl.program_id(2)
    hp = q_ref.shape[-1] // depth
    rows_kw = dict(scale=scale, have_mask=have_mask, mask_ref=mask_ref,
                   qseg_ref=qseg_ref, kseg_ref=kseg_ref, window=window)

    def _heads(q, k, v, scores, lse_rows):
        o, lses = None, []
        for a in range(hp):
            oa, lse = _softmax_pv(scores(_only_head(q, a, depth), k), v)
            o = oa if o is None else _put_head(o, oa, a, depth)
            lses.append(lse)
        _store_lse(lse_ref, lses, lse_rows)
        return o.astype(o_ref.dtype)

    if not causal_tile:
        o_ref[0] = _heads(
            _rotate(q_ref[0], cq_ref, sq_ref, depth),
            _rotate(k_ref[0], ck_ref, sk_ref, depth), v_ref[0],
            lambda q, k: _masked_scores(
                q, k, qi, 0, block_q=block_q, block_k=block_k, causal=causal,
                **rows_kw),
            pl.ds(qi * block_q, block_q))
        return
    k_all = _rotated_k(k_ref, k_scr, ck_ref, sk_ref, depth)
    for r, rows, cols in _sub_tiles(block_q, causal_tile):
        o_ref[0, rows, :] = _heads(
            _rotate(q_ref[0, rows, :], cq_ref, sq_ref, depth, rows),
            k_all[cols, :], v_ref[0, cols, :],
            lambda q, k, r=r: _diagonal_scores(
                q, k, r, causal_tile, **rows_kw),
            rows)


def _bwd_tiles_head(a, q, k, v, g, go, lse, scores, *, depth, scale):
    """One head of a backward step on a lane tile: ``(p, ds, q_a, k_a,
    g_a)`` with the p tile recomputed from the saved LSE (``lse``, the
    head's rows) as in the (B, H, S, D) kernels, the operands zeroed
    outside the head's lanes so that every product lands in them.
    ``scores(q_a, k)`` is the masked score tile of the rows and keys the
    operands hold (a block, or a diagonal block's row sub-tile); ``go`` is
    dO * O over the tile, float32: a head's delta is its sum over the
    head's lanes."""
    qa, ka, ga = (_only_head(x, a, depth) for x in (q, k, g))
    p = jnp.exp(scores(qa, k) - lse[:, None])
    dp = jax.lax.dot_general(
        ga, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    delta = jnp.sum(_only_head(go, a, depth), axis=-1, keepdims=True)
    ds = p * (dp - delta)
    if scale != 1.0:
        ds = ds * scale
    return p, ds, qa, ka, ga


def _dot_t(x, y):
    """``x^T @ y`` in float32."""
    return jax.lax.dot_general(
        x, y, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _dot(x, y):
    return jax.lax.dot_general(
        x, y, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _tile_sender(dqkv_ref):
    """``send(value, stage, third, block, sem)`` for this grid step: copy a
    finished (rows, tile) gradient block into rows ``block * rows`` of lane
    tile ``p`` of ``third`` (0 q, 1 k, 2 v) of d``qkv`` in HBM, through the
    VMEM block ``stage``.  d``qkv`` is one array, the projection's cotangent
    as its backward takes it: three outputs would have to be concatenated, a
    pass over all of it.

    **A copy is waited for where its staging block is next needed, not where
    it was started**: ``send`` first waits for the copy that last left
    ``stage`` (on ``sem``, which only that stage's copies signal), then
    stages and starts its own, and returns with it in flight.  The rows land
    256 bytes at a stride of the projection's width, with the MXU idle where
    the step waits for them; the next flush of the same output is a grid
    step of products later at least, so the copy runs under them (the
    backward alone 6.12 -> 5.48 ms a layer at GPT-2 medium's shapes, 5.41
    with no copy at all; a second staging block an output, swapped by the
    flush's parity, reads 5.49: my chip runs, PR 47, PERF.md section 6).
    One was started unless this is the output's first flush of the grid:
    sequence 0, lane tile 0, ``block`` 0 — every kernel flushes an output
    once a block, in the order of the blocks.  **The grid's last step waits
    for its own copies too**: each output's last block is flushed there,
    nothing runs after it, and the projection's backward reads d``qkv`` as
    soon as the kernel returns.  The carry is sound only while the grid's
    steps run in order on one core (``dimension_semantics`` all
    ``arbitrary`` in :func:`_tiles_backward`).  (The grid position is read
    here, at the kernel's top level, which is where interpret mode has
    it.)"""
    ids = [pl.program_id(axis) for axis in range(4)]
    b, p, tiles = ids[0], ids[1], pl.num_programs(1)
    first_tile = (b == 0) & (p == 0)
    last_step = functools.reduce(
        jnp.logical_and,
        [i == pl.num_programs(axis) - 1 for axis, i in enumerate(ids)])

    def send(value, stage, third, block, sem):
        rows, tile = stage.shape
        lanes = pl.ds(pl.multiple_of((third * tiles + p) * tile, tile), tile)
        copy = pltpu.make_async_copy(
            stage, dqkv_ref.at[b, pl.ds(block * rows, rows), lanes], sem)
        pl.when(jnp.logical_not(first_tile & (block == 0)))(copy.wait)
        stage[:, :] = value.astype(stage.dtype)
        copy.start()
        pl.when(last_step)(copy.wait)

    return send


def _bwd_tiles_fused_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                            dqkv_ref, k_scr, dq_all_scr, dk_scr, dv_scr,
                            q_out, k_out, v_out, sems, *, depth, scale,
                            block_q, block_k, causal, have_mask,
                            mask_ref=None, qseg_ref=None, kseg_ref=None,
                            window=None, cq_ref=None, sq_ref=None,
                            ck_ref=None, sk_ref=None, causal_tile=None):
    """:func:`_bwd_fused_kernel` on one lane tile of ``qkv``: grid (B,
    tiles, n_k, n_q), q innermost.  The rotated q and k are recomputed from
    the raw tiles (k once a k block), the accumulators hold the gradients
    of the rotated q and k, and what is written, once an accumulator is
    final, is their rotation back.  With ``causal_tile`` the step on the
    diagonal (at one block a sequence, every step) walks the q rows in
    sub-tiles: p, dp and ds of sub-tile ``r`` span the keys up to its own
    diagonal, and dv, dk and dq take its products by slices of the scratch;
    the steps off the diagonal are the whole block's."""
    j = pl.program_id(2)
    i = pl.program_id(3)
    n_k = pl.num_programs(2)
    n_q = pl.num_programs(3)
    hp = q_ref.shape[-1] // depth
    send = _tile_sender(dqkv_ref)

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dq_all_scr[:, :] = jnp.zeros_like(dq_all_scr)

    @pl.when(i == 0)
    def _init_dkv():
        k_scr[:, :] = _rotate(k_ref[0], ck_ref, sk_ref, depth)
        dk_scr[:, :] = jnp.zeros_like(dk_scr)
        dv_scr[:, :] = jnp.zeros_like(dv_scr)

    run = _band_run(i, j, block_q, block_k, causal, window)
    row = pl.ds(i * block_q, block_q)

    rows_kw = dict(scale=scale, have_mask=have_mask, mask_ref=mask_ref,
                   qseg_ref=qseg_ref, kseg_ref=kseg_ref)

    def _accumulate(rows, cols, dq_rows, scores):
        """The block's ``rows`` against its keys ``cols``, a head at a
        time, into the slices of the three accumulators they touch."""
        q = _rotate(q_ref[0, rows, :], cq_ref, sq_ref, depth, rows)
        k, v, g = k_scr[cols, :], v_ref[0, cols, :], g_ref[0, rows, :]
        go = g.astype(jnp.float32) * o_ref[0, rows, :].astype(jnp.float32)
        for a in range(hp):
            p, ds, qa, ka, ga = _bwd_tiles_head(
                a, q, k, v, g, go, lse_ref[0, a, 0, rows], scores,
                depth=depth, scale=scale)
            ds = ds.astype(q.dtype)
            dv_scr[cols, :] = dv_scr[cols, :] + _dot_t(p.astype(ga.dtype), ga)
            dk_scr[cols, :] = dk_scr[cols, :] + _dot_t(ds, qa)
            dq_all_scr[dq_rows] = dq_all_scr[dq_rows] + _dot(ds, ka)

    def _step(apply_causal, apply_window):
        win = window if apply_window else None
        if not (apply_causal and causal_tile):
            _accumulate(
                slice(None), slice(None), row,
                lambda q, k: _masked_scores(
                    q, k, i, j, block_q=block_q, block_k=block_k,
                    causal=apply_causal, window=win, **rows_kw))
            return
        for r, rows, cols in _sub_tiles(block_q, causal_tile):
            _accumulate(
                rows, cols,
                pl.ds(i * block_q + r * causal_tile, causal_tile),
                lambda q, k, r=r: _diagonal_scores(
                    q, k, r, causal_tile, window=win, **rows_kw))

    _causal_step_split(i, j, run, block_q=block_q, block_k=block_k,
                       causal=causal, step=_step, window=window)

    @pl.when(j == n_k - 1)
    def _flush_dq():
        send(_unrotate(dq_all_scr[row], cq_ref, sq_ref, depth), q_out, 0, i,
             sems.at[0])

    @pl.when(i == n_q - 1)
    def _flush_dkv():
        send(_unrotate(dk_scr[:, :], ck_ref, sk_ref, depth), k_out, 1, j,
             sems.at[1])
        send(dv_scr[:, :], v_out, 2, j, sems.at[2])


def _bwd_tiles_dq_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                         dqkv_ref, q_scr, dq_scr, q_out, sems, *, depth,
                         scale, block_q, block_k, causal, have_mask,
                         mask_ref=None, qseg_ref=None, kseg_ref=None,
                         window=None, cq_ref=None, sq_ref=None, ck_ref=None,
                         sk_ref=None):
    """:func:`_bwd_dq_kernel` on one lane tile of ``qkv`` (k innermost):
    writes the q third of d``qkv``."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)
    hp = q_ref.shape[-1] // depth
    send = _tile_sender(dqkv_ref)

    @pl.when(kj == 0)
    def _init():
        q_scr[:, :] = _rotate(q_ref[0], cq_ref, sq_ref, depth)
        dq_scr[:, :] = jnp.zeros_like(dq_scr)

    run = _band_run(qi, kj, block_q, block_k, causal, window)

    def _step(apply_causal, apply_window):
        q, v, g = q_scr[:, :], v_ref[0], g_ref[0]
        k = _rotate(k_ref[0], ck_ref, sk_ref, depth)
        go = g.astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        scores = functools.partial(
            _masked_scores, qi=qi, kj=kj, scale=scale, block_q=block_q,
            block_k=block_k, causal=apply_causal, have_mask=have_mask,
            mask_ref=mask_ref, qseg_ref=qseg_ref, kseg_ref=kseg_ref,
            window=window if apply_window else None)
        for a in range(hp):
            _, ds, _, ka, _ = _bwd_tiles_head(
                a, q, k, v, g, go, lse_ref[0, a, 0, :], scores, depth=depth,
                scale=scale)
            dq_scr[:, :] = dq_scr[:, :] + _dot(ds.astype(k.dtype), ka)

    _causal_step_split(qi, kj, run, block_q=block_q, block_k=block_k,
                       causal=causal, step=_step, window=window)

    @pl.when(kj == n_k - 1)
    def _finalize():
        send(_unrotate(dq_scr[:, :], cq_ref, sq_ref, depth), q_out, 0, qi,
             sems.at[0])


def _bwd_tiles_dkv_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, lse_ref,
                          _dq_done_ref, dqkv_ref, k_scr, dk_scr, dv_scr,
                          k_out, v_out, sems, *, depth, scale, block_q,
                          block_k, causal, have_mask, mask_ref=None,
                          qseg_ref=None, kseg_ref=None, window=None,
                          cq_ref=None, sq_ref=None, ck_ref=None,
                          sk_ref=None):
    """:func:`_bwd_dkv_kernel` on one lane tile of ``qkv`` (q innermost):
    writes the k and v thirds of the d``qkv`` whose q third the dq kernel
    wrote (``_dq_done_ref``, the same buffer)."""
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    n_q = pl.num_programs(3)
    hp = q_ref.shape[-1] // depth
    send = _tile_sender(dqkv_ref)

    @pl.when(qi == 0)
    def _init():
        k_scr[:, :] = _rotate(k_ref[0], ck_ref, sk_ref, depth)
        dk_scr[:, :] = jnp.zeros_like(dk_scr)
        dv_scr[:, :] = jnp.zeros_like(dv_scr)

    run = _band_run(qi, kj, block_q, block_k, causal, window)

    def _step(apply_causal, apply_window):
        q = _rotate(q_ref[0], cq_ref, sq_ref, depth)
        k, v, g = k_scr[:, :], v_ref[0], g_ref[0]
        go = g.astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        scores = functools.partial(
            _masked_scores, qi=qi, kj=kj, scale=scale, block_q=block_q,
            block_k=block_k, causal=apply_causal, have_mask=have_mask,
            mask_ref=mask_ref, qseg_ref=qseg_ref, kseg_ref=kseg_ref,
            window=window if apply_window else None)
        for a in range(hp):
            p, ds, qa, _, ga = _bwd_tiles_head(
                a, q, k, v, g, go, lse_ref[0, a, 0, :], scores, depth=depth,
                scale=scale)
            dv_scr[:, :] = dv_scr[:, :] + _dot_t(p.astype(ga.dtype), ga)
            dk_scr[:, :] = dk_scr[:, :] + _dot_t(ds.astype(q.dtype), qa)

    _causal_step_split(qi, kj, run, block_q=block_q, block_k=block_k,
                       causal=causal, step=_step, window=window)

    @pl.when(qi == n_q - 1)
    def _finalize():
        send(_unrotate(dk_scr[:, :], ck_ref, sk_ref, depth), k_out, 1, kj,
             sems.at[0])
        send(dv_scr[:, :], v_out, 2, kj, sems.at[1])


def _tile_specs(tiles, hp, tile, block_q, block_k, mem, *, swap_grid=False):
    """The block specs of the tile kernels, by the role of the operand:
    ``q`` / ``k`` / ``v`` pick lane tile ``p`` of their third of ``qkv``,
    ``qside`` lane tile ``p`` of a (B, S, H*D) array by q block (o, dO; as
    ``q`` is of the first third) and ``row`` the tile's heads of a (B, H,
    1, S) array by q block.  ``swap_grid``: the grid is (B, tiles, n_k,
    n_q)."""
    def spec(shape, index):
        if swap_grid:
            return pl.BlockSpec(shape, lambda b, p, j, i: index(b, p, i, j),
                                memory_space=mem)
        return pl.BlockSpec(shape, index, memory_space=mem)

    qb, kb = (1, block_q, tile), (1, block_k, tile)
    q = spec(qb, lambda b, p, i, j: (b, i, p))
    return {
        "q": q,
        "k": spec(kb, lambda b, p, i, j: (b, j, tiles + p)),
        "v": spec(kb, lambda b, p, i, j: (b, j, 2 * tiles + p)),
        "qside": q,
        "row": spec((1, hp, 1, block_q), lambda b, p, i, j: (b, p, 0, i)),
    }


def _rope_specs_and_args(rope, block_q, block_k, mem, *, swap_grid=False):
    """(in_specs, args, ref_names) of the rotation's tables, like
    :func:`_extra_specs_and_args`: ``rope`` is :func:`_tiles_rope`'s
    ``(cos_q, sin_q, cos_k, sin_k)``, each (1 or B, S, tile), q's taken by
    q block and k's by k block."""
    if rope is None:
        return [], [], []
    per_row = rope[0].shape[0] > 1
    tile = rope[0].shape[-1]

    def spec(block, by_q):
        def index(b, p, i, j):
            if swap_grid:
                i, j = j, i
            return (b if per_row else 0, i if by_q else j, 0)
        return pl.BlockSpec((1, block, tile), index, memory_space=mem)

    return (
        [spec(block_q, True), spec(block_q, True),
         spec(block_k, False), spec(block_k, False)],
        list(rope),
        ["cq_ref", "sq_ref", "ck_ref", "sk_ref"],
    )


def _tiles_rope(rope, depth):
    """``(tables, scale)`` for the tile kernels: with a rotation, q's copy
    of the tables carries the softmax scale ``1 / sqrt(depth)``, so the
    kernels multiply no (block_q, block_k) score tile by it (and, in the
    backward, no ds tile: dk takes it from the scaled q, dq from the
    rotation back through q's tables) — one pass of the vector unit a tile
    less in the forward, two in the backward.  A power of two (heads of 64,
    16, 256) scales exactly: the scores are bit for bit what scaling them
    afterwards gives.  Without a rotation the kernels scale the tile."""
    scale = 1.0 / (depth ** 0.5)
    if rope is None:
        return None, scale
    cos, sin = rope
    return (cos * scale, sin * scale, cos, sin), 1.0


def _tiles_block_memory(interpret):
    """Where the tile kernels' blocks live: VMEM on the chip and under the
    TPU interpreter (``interpret`` a ``pltpu.InterpretParams``, which models
    the memory spaces and performs a copy at its wait), anywhere under the
    plain one (``True``)."""
    return pl.ANY if interpret is True else pltpu.VMEM


def _tiles_geometry(qkv, heads):
    batch, seq, width = qkv.shape
    depth = width // (3 * heads)
    hp = tile_heads(heads, heads, depth)
    return batch, seq, depth, hp, heads // hp, hp * depth


@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "interpret", "window", "block_q", "block_k",
    "causal_tile"))
def _tiles_forward(qkv, rope, mask, segment_ids, *, heads, causal, interpret,
                   window, block_q, block_k, causal_tile=None):
    """Forward over the fused projection: ``(o (B, S, H*D), lse (B, H, 1,
    S))``.  A jitted function of its own (as ``ops.attention.
    _paged_attn_call``): the layers of a step call it at the same shapes and
    share one trace and one lowering of the kernel's body, where every call
    site of a bare ``pallas_call`` is lowered again (72 a step of GPT-2
    medium; the body of two heads a tile is the longer one)."""
    batch, seq, depth, hp, tiles, tile = _tiles_geometry(qkv, heads)
    mem = _tiles_block_memory(interpret)
    specs = _tile_specs(tiles, hp, tile, block_q, block_k, mem)
    rope, scale = _tiles_rope(rope, depth)
    extra_specs, extra_args, extra_names = (
        x + y for x, y in zip(
            _extra_specs_and_args(mask, segment_ids, batch, seq, block_q,
                                  block_k, mem),
            _rope_specs_and_args(rope, block_q, block_k, mem)))
    one_k = seq // block_k == 1
    kernel = _wrap_kernel(
        _fwd_tiles_kernel_1k if one_k else _fwd_tiles_kernel, 3, extra_names,
        depth=depth, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, causal_tile=causal_tile,
    )
    # the sub-tiles slice the step's rotated k out of VMEM
    k_scr = [pltpu.VMEM((block_k, tile), qkv.dtype)] if (
        causal_tile and rope is not None) else []
    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(batch, tiles, seq // block_q, seq // block_k),
        in_specs=[specs["q"], specs["k"], specs["v"], *extra_specs],
        out_specs=[
            specs["qside"],
            pl.BlockSpec((1, hp, 1, seq), lambda b, p, i, j: (b, p, 0, 0),
                         memory_space=mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, heads * depth), qkv.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, seq), jnp.float32),
        ],
        scratch_shapes=k_scr if one_k else [
            pltpu.VMEM((block_q, tile), qkv.dtype),        # rotated q
            pltpu.VMEM((hp, block_q, 128), jnp.float32),   # running max m
            pltpu.VMEM((hp, block_q, 128), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, tile), jnp.float32),      # accumulator
            *k_scr,
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=TILES_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(qkv, qkv, qkv, *extra_args)


@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "interpret", "force_split", "window", "block_q",
    "block_k", "causal_tile"))
def _tiles_backward(qkv, rope, mask, segment_ids, o, lse, g, *, heads,
                    causal, interpret, force_split, window, block_q,
                    block_k, causal_tile=None):
    """d``qkv`` (B, S, 3*H*D) from the saved projection, o and LSE.  The
    kernels form delta = rowsum(dO * O) themselves, from the o tile, and
    copy each finished gradient block into its place in the one d``qkv``
    (an output left in HBM): XLA runs nothing over an activation here.
    A block's copy is in flight while the next grid steps run and is waited
    for when its staging block (one an output) is written again; the grid's
    last step waits for what it started, so d``qkv`` is whole when the call
    returns (:func:`_tile_sender`).  That carry of a semaphore from step to
    step needs the steps in order on one core: every grid dimension is
    ``arbitrary``.  Jitted for the reason :func:`_tiles_forward` is."""
    batch, seq, depth, hp, tiles, tile = _tiles_geometry(qkv, heads)
    mem = _tiles_block_memory(interpret)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    rope, scale = _tiles_rope(rope, depth)
    kw = dict(depth=depth, scale=scale, block_q=block_q, block_k=block_k,
              causal=causal, window=window)
    f32 = jnp.float32

    def stage(block):
        return pltpu.VMEM((block, tile), qkv.dtype)

    def call(inner, name, swap_grid, scratch, dq_done=None, **more):
        specs = _tile_specs(tiles, hp, tile, block_q, block_k, mem,
                            swap_grid=swap_grid)
        extra_specs, extra_args, extra_names = (
            x + y for x, y in zip(
                _extra_specs_and_args(mask, segment_ids, batch, seq, block_q,
                                      block_k, mem, swap_grid=swap_grid),
                _rope_specs_and_args(rope, block_q, block_k, mem,
                                     swap_grid=swap_grid)))
        n = (seq // block_k, seq // block_q) if swap_grid else (
            seq // block_q, seq // block_k)
        done = [] if dq_done is None else [dq_done]
        return pl.pallas_call(
            _wrap_kernel(inner, 6 + len(done), extra_names, **kw, **more),
            name=name,
            grid=(batch, tiles, *n),
            in_specs=[specs["q"], specs["k"], specs["v"], specs["qside"],
                      specs["qside"], specs["row"], *[hbm] * len(done),
                      *extra_specs],
            out_specs=hbm,
            out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
            scratch_shapes=scratch,
            input_output_aliases={6: 0} if done else {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 4,
                vmem_limit_bytes=TILES_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(qkv, qkv, qkv, g, o, lse, *done, *extra_args)

    if not force_split and seq * tile * 4 <= FUSED_BWD_DQ_SCRATCH_BYTES:
        return call(
            _bwd_tiles_fused_kernel, "flash_bwd", True,
            [stage(block_k),                      # rotated k
             pltpu.VMEM((seq, tile), f32),        # dq, the whole tile
             pltpu.VMEM((block_k, tile), f32),    # dk
             pltpu.VMEM((block_k, tile), f32),    # dv
             stage(block_q), stage(block_k), stage(block_k),
             pltpu.SemaphoreType.DMA((3,))],
            causal_tile=causal_tile)  # the split pair takes blocks whole
    dq_done = call(
        _bwd_tiles_dq_kernel, "flash_bwd_dq", False,
        [stage(block_q), pltpu.VMEM((block_q, tile), f32), stage(block_q),
         pltpu.SemaphoreType.DMA((1,))])
    return call(
        _bwd_tiles_dkv_kernel, "flash_bwd_dkv", True,
        [stage(block_k), pltpu.VMEM((block_k, tile), f32),
         pltpu.VMEM((block_k, tile), f32), stage(block_k), stage(block_k),
         pltpu.SemaphoreType.DMA((2,))],
        dq_done=dq_done)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_qkv(qkv, rope, mask, segment_ids, heads, causal, interpret,
               backward_impl, window, block_q, block_k, causal_tile):
    o, _ = _tiles_forward(qkv, rope, mask, segment_ids, heads=heads,
                          causal=causal, interpret=interpret, window=window,
                          block_q=block_q, block_k=block_k,
                          causal_tile=causal_tile)
    return o


#: ``jax.ad_checkpoint.checkpoint_name`` of the two residuals the forward
#: kernel itself made, o and the log-sum-exp.  A ``jax.checkpoint`` around
#: the caller that saves them (``save_only_these_names``) runs the backward
#: kernels on the forward pass's own bits and does not call ``flash_fwd``
#: again; the projection, which the backward reads too, is the caller's to
#: rebuild.  ``models.gpt.remat_block`` is that checkpoint.
RESIDUAL_O = "flash_qkv_o"
RESIDUAL_LSE = "flash_qkv_lse"


def _flash_qkv_fwd(qkv, rope, mask, segment_ids, heads, causal, interpret,
                   backward_impl, window, block_q, block_k, causal_tile):
    o, lse = _tiles_forward(qkv, rope, mask, segment_ids, heads=heads,
                            causal=causal, interpret=interpret, window=window,
                            block_q=block_q, block_k=block_k,
                            causal_tile=causal_tile)
    o = checkpoint_name(o, RESIDUAL_O)
    lse = checkpoint_name(lse, RESIDUAL_LSE)
    return o, (qkv, rope, mask, segment_ids, o, lse)


def _flash_qkv_bwd(heads, causal, interpret, backward_impl, window, block_q,
                   block_k, causal_tile, res, g):
    qkv, rope, mask, segment_ids, o, lse = res
    dqkv = _tiles_backward(
        qkv, rope, mask, segment_ids, o, lse, g, heads=heads, causal=causal,
        interpret=interpret,
        force_split=(backward_impl or BACKWARD_IMPL) == "pallas_split",
        window=window, block_q=block_q, block_k=block_k,
        causal_tile=causal_tile)
    return dqkv, None, None, None


_flash_qkv.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


def qkv_layout(seq: int, heads: int, kv_heads: int, depth: int, dtype, *,
               implementation: str = "auto",
               backward_impl: str | None = None) -> str:
    """The form causal self-attention over a block's fused projection
    lowers to, by what can be observed of the call: ``"qkv_tiles"`` (the
    kernels read the projection as it lies, :func:`flash_attention_qkv`),
    ``"bhsd"`` (q, k and v are split, rotated and transposed to the
    (B, H, S, D) kernels, :func:`flash_attention`) or ``"xla"`` (no
    kernel).  Read under the context mesh, as the call itself is traced: a
    ``model`` axis that splits heads splits the fused lane dimension into
    shards that hold no whole q, k and v, and takes ``"bhsd"``.  The
    fall-back is silent, so the trainer reports this at start-up."""
    if implementation not in ("auto", "pallas") or (
            implementation == "auto" and not _auto_takes(seq, dtype)):
        return "xla"
    if (
        tile_heads(heads, kv_heads, depth) is None
        or (backward_impl or BACKWARD_IMPL) == "xla"
        or kernel_axes((mesh_lib.AXIS_MODEL,), kv_heads) is not None
    ):
        return "bhsd"
    return "qkv_tiles"


def qkv_causal_tile(batch: int, seq: int, heads: int, depth: int, dtype
                    ) -> tuple[int | None, float | None]:
    """``(T, share)`` of a causal :func:`flash_attention_qkv` call over a
    shard of ``batch`` rows at the blocks it resolves to:
    :func:`causal_tile` and :func:`causal_share` (256 and 0.625 at one
    1024 x 1024 block), ``(None, None)`` where its blocks are taken whole.
    The sub-tiling is static, so the trainer reports it at start-up beside
    :func:`qkv_layout`."""
    block_q, block_k = _resolve_blocks(
        batch, heads, seq, depth, dtype, None, None, layout="qkv_tiles")
    tile = causal_tile(block_q, block_k, True)
    return tile, causal_share(block_q, tile) if tile else None


def flash_attention_qkv(qkv, heads: int, *, rope=None, mask=None,
                        segment_ids=None, causal=False, interpret=None,
                        backward_impl=None, window=None, block_q=None,
                        block_k=None):
    """Flash attention over a fused projection ``qkv`` (B, S, 3*H*D), as the
    matmul wrote it: the q, k and v thirds side by side, heads major within
    each.  Returns o as (B, S, H*D), what the output projection takes;
    differentiable in ``qkv``.

    ``rope`` is ``(cos, sin)``, the rotation's tables as lane tiles: (1 or
    B, S, max(128, D)) with every head's ``[cos, cos]`` and sign-folded
    ``[-sin, sin]`` repeated across a tile; q and k are rotated in VMEM
    (float32, one rounding).  None rotates nothing.  The other arguments
    are :func:`flash_attention`'s; ``backward_impl`` "xla" is not taken
    here.  The shapes this form takes are :func:`tile_heads`'s; callers
    choose between it and :func:`flash_attention` by :func:`qkv_layout`.

    The backward reads ``qkv`` again, o and the forward's log-sum-exp
    (B, H, 1, S) float32.  The last two carry the names ``RESIDUAL_O`` /
    ``RESIDUAL_LSE``: under a ``jax.checkpoint`` whose policy saves them
    (``models.gpt.remat_block``) the forward kernel runs once, for
    ``B*S*(H*D*itemsize + H*4)`` bytes kept a call; under one that does
    not, it runs again in the backward.
    """
    if qkv.ndim != 3 or qkv.shape[2] % (3 * heads):
        raise ValueError(
            f"flash_attention_qkv needs a (B, S, 3*H*D) projection, got "
            f"{qkv.shape} for {heads} heads")
    batch, seq, width = qkv.shape
    depth = width // (3 * heads)
    hp = tile_heads(heads, heads, depth)
    if hp is None:
        raise ValueError(
            f"{heads} heads of {depth} do not fill lane tiles of {LANES}: "
            "split the projection and call flash_attention")
    if (backward_impl or BACKWARD_IMPL) not in ("pallas", "pallas_split"):
        raise ValueError(
            "flash_attention_qkv has the Pallas backwards only; the \"xla\" "
            "backward takes flash_attention's (B, S, H, D) operands")
    if rope is not None and any(
            t.ndim != 3 or t.shape[0] not in (1, batch)
            or t.shape[1:] != (seq, hp * depth) for t in rope):
        raise ValueError(
            f"rope tables must be (1 or {batch}, {seq}, {hp * depth}) lane "
            f"tiles, got {[t.shape for t in rope]}")
    shape = (batch, seq, heads, depth)
    mask, segment_ids, window = _check_rows(shape, mask, segment_ids, causal,
                                            window, block_q, block_k)
    if interpret is None:
        interpret = not on_tpu()

    def local(qkv, rope, pad, segment_ids):
        # resolved here, a call: the jitted kernels below are cached by
        # their arguments and would not see the environment change
        bq, bk = _resolve_blocks(
            qkv.shape[0], heads, seq, depth, qkv.dtype, block_q, block_k,
            layout="qkv_tiles")
        return _flash_qkv(qkv, rope, pad, segment_ids, heads, causal,
                          interpret, backward_impl, window, bq, bk,
                          causal_tile(bq, bk, causal))

    # batch over the data axes; the lane dimension whole (qkv_layout sends
    # a head-sharded call the other way)
    batch_axes = kernel_axes(mesh_lib.BATCH_AXES, batch)
    act = P(batch_axes, None, None)
    row = P(batch_axes, None)
    tabs = None if rope is None else tuple(
        P(batch_axes if t.shape[0] > 1 else None, None, None) for t in rope)
    return shard_kernel(local, (act, tabs, row, row), act)(
        qkv, rope, mask, segment_ids)
