"""Pallas fused linear + softmax cross-entropy: the LM-head hot op.

:func:`ops.xent.chunked_softmax_xent` already keeps the full ``(B*S, V)``
logits out of the *residual* set, but every chunk's ``(C, V)`` logits tile
still round-trips HBM — materialized by the matmul, re-read by logsumexp,
re-materialized and re-read twice more in the checkpointed backward: ~20 GB
of HBM traffic per GPT-2-small step at B=16, S=1024 (``estimate_hbm_bytes``).

This module fuses the head end-to-end in Pallas so the fp32 logits live
only in VMEM, tile by tile, and HBM sees ``x``, ``wte``, the O(N) outputs
and — once written, once read, in the compute type — the backward's
rounded dlogits (~9.7 GB/step for the same shapes at the on-chip-validated
tile sizes, 3.3 of them the dlogits — 1.7x less than chunked; see
``estimate_hbm_bytes``):

- **forward** — grid (vocab-blocks OUTER, token-blocks inner): the weight
  tile is fetched once per vocab block and stays in VMEM for the whole
  token sweep; per-token online-logsumexp state (m, s) and the gathered
  target logit accumulate in VMEM scratch sized (n_token_blocks, block_n)
  across the outer sweeps.  Logits are computed TRANSPOSED — (block_v,
  block_n), vocab on sublanes, tokens on lanes — so every per-token
  reduction lands as a lane-major (1, block_n) row that indexes straight
  into the scratch with no relayout.
- **backward** — two kernels over token chunks of
  :func:`dlog_chunk_tokens` rows, mirroring the flash-attention dq/dkv
  split (`ops/flash_attention.py`).  ``dx`` with token-blocks outer
  recomputes the logits tile from the saved (x, wte, lse) — softmax
  probabilities are ``exp(logit - lse)``, no renormalization pass needed
  —, forms ``dlog = c (p - onehot)`` rounded to the compute type, feeds
  it to its own product (the dx tile accumulates in scratch over the
  vocab sweep) and stores it to a ``(Vp, C)`` buffer in HBM.  ``dwte``
  with vocab-blocks outer is then ONE product of that stored tile with
  the ``x`` tile, accumulating directly into its output block, which is
  revisited consecutively across the inner token sweep — the only revisit
  pattern Pallas TPU guarantees stays resident in VMEM — and starts from
  the sum of the chunks before (aliased in and out).  So a step's head is
  four ``tokens x d x V`` MXU products (:data:`PRODUCTS_PER_STEP`), the
  floor of a two-pass head that keeps no logits: until PR 42 both
  backward kernels formed the same tile, five products.  The buffer costs
  2 x Vp x N x itemsize bytes of HBM traffic a step (13.3 GB at 64 x 1024
  tokens of GPT-2's vocabulary) under kernels the MXU bounds, for one
  product (34.5 ms there at a v5e's peak) and a 512 x 512 ``exp`` a grid
  step.

Semantics match :func:`ops.xent.chunked_softmax_xent` exactly (same
masked-mean reduction; out-of-range targets contribute zero weight);
``tests/test_fused_xent.py`` asserts value and gradient equivalence in
interpret mode.

Reference anchor: the reference stack has no such op — Keras
``SparseCategoricalCrossentropy`` materializes full logits (SURVEY.md
§2.3 Keras trainer row).  This is the TPU-first "Pallas kernels for the
hot ops" obligation (SURVEY.md §2.4 native-code notes) applied to the
LM head.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import shard_kernel, token_spec
from ..runtime import on_tpu
from .flash_attention import NEG_INF

def _env_int(name: str, default: int) -> int:
    """Debug override for a tile size (read once at import).

    The defaults below are VMEM-budget reasoning, not measurements; the
    ``DTFT_XENT_*`` envs let an on-chip sweep retune them without code
    edits."""
    import os

    return int(os.environ.get(name, default))


#: Default tile sizes.  The binding constraint is Mosaic's 16 MB scoped-
#: VMEM stack: the (block_v, block_n) fp32 logits tile plus its
#: elementwise temporaries (iota/mask/exp) dominate, alongside the
#: double-buffered operand blocks.  block_v=2048 x block_n=512 compiles to
#: a 16.71 MB stack — 724 KB OVER the limit; 1024 x 512 fits with ~2x
#: headroom.  The trade is NOT free: the w table streams once per token
#: chunk regardless of block_v, but x restreams once PER VOCAB BLOCK
#: (vocab-outer sweep), so halving block_v doubles the fwd x-restream
#: (``estimate_hbm_bytes``), against a kernel that otherwise does not
#: compile at all.
BLOCK_TOKENS = _env_int("DTFT_XENT_BLOCK_TOKENS", 512)
BLOCK_VOCAB = _env_int("DTFT_XENT_BLOCK_VOCAB", 1024)
#: dx backward uses a bigger token tile: its dominant HBM cost is the full
#: weight-table re-read per token block, so fewer/bigger token sweeps win.
#: Its vocab tile is the smallest: the dx kernel carries the most live
#: fp32 temporaries (p, dlog, the fp32-cast weight tile, the fp32 dx
#: accumulator), so it hits the same 16 MB stack wall soonest.
#: A token tile of 2048 has a ~18 MB Mosaic stack that only fits in SOME
#: surrounding programs — it compiled inside a seq-1024 train step yet
#: fails in isolation AND inside a seq-8192 step with the SAME padded
#: (16384, 768) operands (scoped-stack accounting is context-dependent);
#: 1024 compiles everywhere.
BLOCK_TOKENS_DX = _env_int("DTFT_XENT_BLOCK_TOKENS_DX", 1024)
BLOCK_VOCAB_DX = _env_int("DTFT_XENT_BLOCK_VOCAB_DX", 512)


#: dw backward tiles (tokens, vocab), at every d.  The kernel is one
#: product of the stored ``dlog`` tile with the ``x`` tile, accumulated
#: into the resident fp32 output block: no fp32 temporaries, and each pass
#: over that block (a read, an add and a write of block_v x d floats a
#: grid step) is paid once a token tile, so the token tile wants to be
#: long.  On the chip at GPT-2 medium's head (65,536 tokens, d 1024,
#: 50,688 rows, chunks of 8,192; the backward alone, PR 42): 512 x 512
#: 115.1 ms, 1024 x 512 110.8, **2048 x 512 109.2**, 4096 x 512 108.3,
#: 8192 x 512 107.8; a taller vocab tile moves nothing (2048 x 1024
#: 110.0, 2048 x 2048 110.2).  20 MB of VMEM at d 1024 with the double
#: buffers, hence :data:`BWD_VMEM_LIMIT_BYTES`.
BLOCK_TOKENS_DW = 2048
BLOCK_VOCAB_DW = 512


def _blocks_for_dim(d: int) -> tuple[int, int, int, int, int, int]:
    """(block_tokens, block_vocab, block_tokens_dx, block_vocab_dx,
    block_tokens_dw, block_vocab_dw) for hidden size ``d``.

    Every kernel tile is (block, d)- or (block_v, block_n)-shaped, so the
    VMEM stack scales with d: the d<=768 defaults above (on-chip-tuned at
    GPT-2-small) VMEM-OOM at d=1024 (GPT-2-medium), where the fitting set
    is 512 across the board.  Env overrides win
    unconditionally at every d; the dw kernel's tiles do not depend on d."""
    if d <= 768:
        # The module constants above ARE the d<=768 defaults (env already
        # applied at import) — single source of truth for the tuned set.
        defaults = (BLOCK_TOKENS, BLOCK_VOCAB, BLOCK_TOKENS_DX,
                    BLOCK_VOCAB_DX)
    else:
        defaults = (512, 512, 512, 512)
    names = ("DTFT_XENT_BLOCK_TOKENS", "DTFT_XENT_BLOCK_VOCAB",
             "DTFT_XENT_BLOCK_TOKENS_DX", "DTFT_XENT_BLOCK_VOCAB_DX")
    return tuple(_env_int(n, v) for n, v in zip(names, defaults)) + (
        BLOCK_TOKENS_DW, BLOCK_VOCAB_DW)


def _resolve_blocks(d: int, *explicit: int | None) -> tuple[int, ...]:
    """:func:`_blocks_for_dim` under a caller's ``explicit`` tiles (in its
    order; None or 0 leaves the default)."""
    return tuple(e or b for e, b in zip(explicit, _blocks_for_dim(d)))


def _transposed_logits(w_ref, x_ref):
    """(block_v, block_n) fp32 logits tile: rows = vocab, cols = tokens."""
    return jax.lax.dot_general(
        w_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


#: Sublane depth of the forward scratch accumulators.  The per-token-block
#: state lives in (n_token_blocks, _SUB, block_n) scratch: the dynamically
#: indexed dimension is the UNTILED leading one (tiling applies to the
#: trailing (_SUB, block_n) = (8, lanes) pair), so ``pl.ds(i, 1)`` never
#: asks Mosaic for an unaligned dynamic sublane slice — which interpret
#: mode would happily accept and the real TPU lowering may not.
_SUB = 8


def _fwd_kernel(x_ref, w_ref, t_ref, lse_ref, tgt_ref, m_sc, s_sc, g_sc,
                *, block_v, v_true):
    j = pl.program_id(0)   # vocab block (outer)
    i = pl.program_id(1)   # token block (inner)
    n_j = pl.num_programs(0)

    def read(sc):          # (1, block_n) row of token-block i's state
        return sc[pl.ds(i, 1)][0, :1, :].reshape(1, -1)

    def write(sc, val):    # broadcast the (1, block_n) row over _SUB
        sc[pl.ds(i, 1)] = jnp.broadcast_to(val, (1, _SUB, val.shape[-1]))

    @pl.when(j == 0)
    def _init():
        write(m_sc, jnp.full((1, m_sc.shape[-1]), NEG_INF, m_sc.dtype))
        write(s_sc, jnp.zeros((1, s_sc.shape[-1]), s_sc.dtype))
        write(g_sc, jnp.zeros((1, g_sc.shape[-1]), g_sc.dtype))

    logits = _transposed_logits(w_ref, x_ref)  # (block_v, block_n)
    row = j * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    logits = jnp.where(row < v_true, logits, NEG_INF)

    t = t_ref[...]                      # (1, block_n) int32
    match = row == t                    # broadcasts over sublanes
    # Out-of-range targets (ignore labels) match no row of any block: the
    # gathered logit stays 0 and the caller's weight for the row is 0.
    g_part = jnp.sum(jnp.where(match, logits, 0.0), axis=0, keepdims=True)

    m_prev = read(m_sc)                 # (1, block_n)
    s_prev = read(s_sc)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=0, keepdims=True))
    s_new = s_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=0, keepdims=True
    )
    write(m_sc, m_new)
    write(s_sc, s_new)
    write(g_sc, read(g_sc) + g_part)

    @pl.when(j == n_j - 1)
    def _finalize():
        lse_ref[...] = read(m_sc) + jnp.log(read(s_sc))
        tgt_ref[...] = read(g_sc)


def _bwd_dx_kernel(x_ref, w_ref, t_ref, lse_ref, c_ref, dx_ref, dlog_ref,
                   acc_sc, *, block_v, v_true):
    i = pl.program_id(0)   # token block (outer)
    j = pl.program_id(1)   # vocab block (inner)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    logits = _transposed_logits(w_ref, x_ref)
    row = j * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    logits = jnp.where(row < v_true, logits, NEG_INF)
    p = jnp.exp(logits - lse_ref[...])          # (block_v, block_n)
    match = row == t_ref[...]
    # dlog drops to the operand compute dtype (bf16 in training) so the
    # two products it feeds run native MXU passes instead of the
    # ~4x-slower fp32 emulation; accumulation stays fp32.  This matches
    # standard mixed-precision (dlogits are bf16 wherever logits are), and
    # bf16's fp32-sized exponent keeps the tiny c*(p-match) magnitudes exact
    # in scale.  fp32 operands are left untouched.  The rounded tile is
    # formed HERE ONLY: it goes to HBM for the dw kernel as it is handed to
    # this kernel's own product.
    dlog = (c_ref[...] * (p - match.astype(jnp.float32))).astype(w_ref.dtype)
    dlog_ref[...] = dlog
    # dx_i += sum_j dlogits_ji * wte_j : contract the vocab sublanes.
    acc_sc[...] += jax.lax.dot_general(
        dlog, w_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == n_j - 1)
    def _finalize():
        dx_ref[...] = acc_sc[...]


def _bwd_dw_kernel(dlog_ref, x_ref, dw_in_ref, dw_ref):
    i = pl.program_id(1)   # token block (inner); vocab block j is outer

    # dwte_j += sum_i dlogits_ji * x_i : contract the token lanes of the
    # stored tile.  The output block's index depends only on j (outer), so
    # the accumulation target stays resident across the whole inner sweep;
    # it starts from the sum of the chunks before this one (``dw_in``,
    # aliased to the output), so the sweep's order over all the tokens is
    # that of one call over all of them.
    part = jax.lax.dot_general(
        dlog_ref[...], x_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == 0)
    def _first():
        dw_ref[...] = dw_in_ref[...] + part

    @pl.when(i != 0)
    def _rest():
        dw_ref[...] = dw_ref[...] + part


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


#: VMEM budget for the forward's per-token-block scratch accumulators.
#: The three (n_i, _SUB, block_n) fp32 buffers cost 96 B per token, i.e.
#: O(N) — unbounded, a 64x8192-token long-context head would ask for
#: ~48 MB of VMEM and fail to compile.  Token super-chunks of at most
#: ``budget // (3*_SUB*block_n*4)`` blocks keep scratch bounded; each
#: extra chunk re-reads the weight table once (~77 MB bf16 at GPT-2
#: vocab), which at the default 4 MiB budget (~43k tokens/chunk) stays
#: far below the ~20 GB logits round-trip the kernel exists to avoid.
#: Override: ``DTFT_XENT_FWD_SCRATCH_BYTES`` (read per call, testable).
FWD_SCRATCH_BUDGET_BYTES = 4 * 2**20


def _max_fwd_token_blocks(block_n: int) -> int:
    import os

    budget = int(
        os.environ.get("DTFT_XENT_FWD_SCRATCH_BYTES", FWD_SCRATCH_BUDGET_BYTES)
    )
    return max(1, budget // (3 * _SUB * block_n * 4))


def _fused_fwd_arrays(x, w, t, *, block_n, block_v, v_true, interpret):
    """Run the forward kernel on padded 2-D operands.

    x (N, D) compute-dtype, w (Vp, D) compute-dtype, t (N,) int32; N, Vp
    already padded to the block sizes.  Returns (lse, tgt) fp32 (N,).

    Token super-chunking: the per-token-block online-softmax state lives
    in VMEM scratch, so one pallas_call is bounded to
    :func:`_max_fwd_token_blocks` token blocks; larger N runs as a host
    loop of identical calls (at most two distinct shapes, so at most two
    kernel compiles) whose outputs concatenate.
    """
    n, d = x.shape
    vp = w.shape[0]
    n_j = vp // block_v
    mem = pl.ANY if interpret else pltpu.VMEM

    def one_call(xc, tc):
        n_c = xc.shape[0]
        n_i = n_c // block_n
        # Row operands/outputs are laid out (1, N) with block (1, block_n):
        # a (1, block_n) block over an (n_i, block_n) array would put a
        # sublane block of 1 over an array dim > 1, which the real Mosaic
        # lowering rejects ("block shape ... divisible by 8 and 128") even
        # though interpret mode accepts it.
        lse, tgt = pl.pallas_call(
            functools.partial(_fwd_kernel, block_v=block_v, v_true=v_true),
            name="fused_xent_fwd",
            grid=(n_j, n_i),
            in_specs=[
                pl.BlockSpec((block_n, d), lambda j, i: (i, 0),
                             memory_space=mem),
                pl.BlockSpec((block_v, d), lambda j, i: (j, 0),
                             memory_space=mem),
                pl.BlockSpec((1, block_n), lambda j, i: (0, i),
                             memory_space=mem),
            ],
            out_specs=[
                pl.BlockSpec((1, block_n), lambda j, i: (0, i),
                             memory_space=mem),
                pl.BlockSpec((1, block_n), lambda j, i: (0, i),
                             memory_space=mem),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, n_c), jnp.float32),
                jax.ShapeDtypeStruct((1, n_c), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((n_i, _SUB, block_n), jnp.float32)] * 3,
            interpret=interpret,
        )(xc, w, tc.reshape(1, n_c))
        return lse.reshape(n_c), tgt.reshape(n_c)

    chunk_tokens = _max_fwd_token_blocks(block_n) * block_n
    if n <= chunk_tokens:
        return one_call(x, t)
    lses, tgts = [], []
    for s in range(0, n, chunk_tokens):
        lse_c, tgt_c = one_call(x[s:s + chunk_tokens], t[s:s + chunk_tokens])
        lses.append(lse_c)
        tgts.append(tgt_c)
    return jnp.concatenate(lses), jnp.concatenate(tgts)


#: HBM budget for the backward's ``dlog`` buffer: the rounded
#: ``(Vp, C)`` tile of dlogits the dx kernel writes and the dw kernel
#: reads, C tokens a chunk.  All N tokens at once would be the logits
#: themselves (6.6 GB in bf16 at 64 x 1024 tokens of GPT-2's vocabulary);
#: token chunks of at most ``budget // (Vp * itemsize)`` tokens bound it,
#: as :data:`FWD_SCRATCH_BUDGET_BYTES` bounds the forward's scratch.
#: Each extra chunk costs the dx kernel nothing (its grid is token-outer)
#: and the dw kernel one read and one write of the fp32 table gradient
#: (2 x 208 MB at GPT-2 medium), under its MXU time: on the chip the
#: backward alone takes 109.3 / 109.1 / 109.1 ms at chunks of 4,096 /
#: 8,192 / 16,384 tokens (PR 42).  The buffer lives only across the head's
#: backward, before any block's recomputation does, but it can be the
#: step's peak: compiled for a v5e, GPT-2 medium's step on a shard of four
#: holds 12.75 GB at 4,096 tokens a chunk, as without the buffer, and
#: 13.11 at 8,192.  448 MiB is 4,096 tokens of GPT-2's padded vocabulary
#: in bf16 (415 MB): sixteen chunks a step of 65,536.
DLOG_BUDGET_BYTES = 448 * 2**20

#: Scoped-VMEM limit of the two backward kernels, above Mosaic's 16 MB
#: default.  dw's fp32 output block and the aliased block it starts from
#: are double-buffered beside the ``dlog`` and ``x`` tiles (20 MB at d
#: 1024); dx at the d<=768 tiles was within a megabyte of the default
#: before it had the ``dlog`` tile to store, and is 16.93 MB with it.
BWD_VMEM_LIMIT_BYTES = 64 * 2**20

#: MXU products of one forward + backward of the head: the logits tile in
#: the forward, and in the backward the logits tile again, ``dlog x W``
#: and ``dlog x x``.
PRODUCTS_PER_STEP = 4


def dlog_chunk_tokens(n_tokens: int, d: int, v: int, itemsize: int = 2,
                      blocks: tuple[int, ...] | None = None) -> int:
    """Tokens a chunk of the head's backward over ``n_tokens`` rows of
    width ``d`` against ``v`` vocabulary rows: the C of the ``(Vp, C)``
    ``dlog`` buffer (``itemsize`` bytes an element, the compute type's).

    The fewest equal chunks whose buffer fits :data:`DLOG_BUDGET_BYTES`,
    in whole token blocks of both backward kernels (``blocks`` as
    :func:`_blocks_for_dim` gives them); the last chunk is padded to the
    others' size with rows of weight zero, so each kernel is lowered once
    (under ``lax.scan``) whatever the number of chunks."""
    blocks = blocks or _blocks_for_dim(d)
    block_n = math.lcm(blocks[2], blocks[4])
    vp = v + (-v) % math.lcm(blocks[3], blocks[5])
    n_blocks = -(-n_tokens // block_n)
    max_blocks = max(1, DLOG_BUDGET_BYTES // (vp * itemsize * block_n))
    n_chunks = -(-n_blocks // max_blocks)
    return -(-n_blocks // n_chunks) * block_n


def _bwd_dx_call(x, w, t, lse, c, *, block_n, block_v, v_true, interpret):
    """dx (C, D) fp32 and the rounded dlog (Vp, C) of one token chunk.

    Row operands ride as (1, C) for the same Mosaic sublane-tiling reason
    as the forward (see ``one_call`` above)."""
    n, d = x.shape
    vp = w.shape[0]
    mem = pl.ANY if interpret else pltpu.VMEM

    row = pl.BlockSpec((1, block_n), lambda i, j: (0, i), memory_space=mem)
    return pl.pallas_call(
        functools.partial(_bwd_dx_kernel, block_v=block_v, v_true=v_true),
        name="fused_xent_bwd_dx",
        grid=(n // block_n, vp // block_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0), memory_space=mem),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0), memory_space=mem),
            row, row, row,
        ],
        out_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0), memory_space=mem),
            # vocab on sublanes, tokens on lanes: as the kernel computes
            # the tile, and as ``dlog x x`` contracts it
            pl.BlockSpec((block_v, block_n), lambda i, j: (j, i),
                         memory_space=mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((vp, n), w.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=BWD_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, w, t.reshape(1, n), lse.reshape(1, n), c.reshape(1, n))


def _bwd_dw_call(dlog, x, dw, *, block_n, block_v, interpret):
    """``dw + dlog @ x`` over one token chunk: (Vp, D) fp32, in place."""
    vp, n = dlog.shape
    d = x.shape[1]
    mem = pl.ANY if interpret else pltpu.VMEM
    return pl.pallas_call(
        _bwd_dw_kernel,
        name="fused_xent_bwd_dw",
        grid=(vp // block_v, n // block_n),
        in_specs=[
            pl.BlockSpec((block_v, block_n), lambda j, i: (j, i),
                         memory_space=mem),
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0), memory_space=mem),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0), memory_space=mem),
        ],
        out_specs=pl.BlockSpec((block_v, d), lambda j, i: (j, 0),
                               memory_space=mem),
        out_shape=jax.ShapeDtypeStruct((vp, d), jnp.float32),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=BWD_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(dlog, x, dw)


def _fused_bwd_arrays(x, w, t, lse, c, *, chunk_tokens, block_n_dx,
                      block_v_dx, block_n_dw, block_v_dw, v_true, interpret):
    """dx (N, D) and dw (Vp, D), both fp32, from padded operands.

    The tokens go ``chunk_tokens`` at a time (N a multiple of it): the dx
    kernel leaves the chunk's rounded ``dlog`` in HBM and the dw kernel
    adds its product with the chunk's ``x`` into the table gradient it
    carries, so the logits tile, its ``exp`` and its masks are formed once
    in the backward.  One ``lax.scan`` body whatever the number of chunks:
    each kernel is lowered once."""
    n, d = x.shape
    n_chunks = n // chunk_tokens

    def chunk(dw, operands):
        xc, tc, lsec, cc = operands
        dx, dlog = _bwd_dx_call(
            xc, w, tc, lsec, cc, block_n=block_n_dx, block_v=block_v_dx,
            v_true=v_true, interpret=interpret)
        dw = _bwd_dw_call(dlog, xc, dw, block_n=block_n_dw,
                          block_v=block_v_dw, interpret=interpret)
        return dw, dx

    dw, dx = jax.lax.scan(
        chunk, jnp.zeros((w.shape[0], d), jnp.float32),
        (x.reshape(n_chunks, chunk_tokens, d),
         *(r.reshape(n_chunks, chunk_tokens) for r in (t, lse, c))))
    return dx.reshape(n, d), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused(hidden2d, wte, t, w_row, compute_dtype, block_sizes, interpret):
    out, _ = _fused_fwd(hidden2d, wte, t, w_row, compute_dtype, block_sizes,
                        interpret)
    return out


def _fused_fwd(hidden2d, wte, t, w_row, compute_dtype, block_sizes,
               interpret):
    block_n, block_v = block_sizes[0], block_sizes[1]
    n, _ = hidden2d.shape
    v = wte.shape[0]
    xc = _pad_to(hidden2d.astype(compute_dtype), block_n, 0)
    wc = _pad_to(wte.astype(compute_dtype), block_v, 0)
    tp = _pad_to(t, block_n, 0)
    lse, tgt = _fused_fwd_arrays(
        xc, wc, tp, block_n=block_n, block_v=block_v, v_true=v,
        interpret=interpret,
    )
    lse, tgt = lse[:n], tgt[:n]
    nll_sum = jnp.sum((lse - tgt) * w_row)
    return nll_sum, (hidden2d, wte, t, w_row, lse)


def _fused_bwd(compute_dtype, block_sizes, interpret, res, g):
    hidden2d, wte, t, w_row, lse = res
    block_n_dx, block_v_dx, block_n_dw, block_v_dw = block_sizes[2:]
    n, d = hidden2d.shape
    v = wte.shape[0]
    # whole dlog chunks of tokens; the padding rows weigh nothing (c = 0)
    chunk_tokens = dlog_chunk_tokens(
        n, d, v, jnp.dtype(compute_dtype).itemsize, block_sizes)
    xc = _pad_to(hidden2d.astype(compute_dtype), chunk_tokens, 0)
    wc = _pad_to(wte.astype(compute_dtype),
                 math.lcm(block_v_dx, block_v_dw), 0)
    tp = _pad_to(t, chunk_tokens, 0)
    c = g * w_row                               # (N,) fp32
    cp = _pad_to(c.astype(jnp.float32), chunk_tokens, 0)
    lsep = _pad_to(lse, chunk_tokens, 0)
    dx, dw = _fused_bwd_arrays(
        xc, wc, tp, lsep, cp,
        chunk_tokens=chunk_tokens,
        block_n_dx=block_n_dx, block_v_dx=block_v_dx,
        block_n_dw=block_n_dw, block_v_dw=block_v_dw,
        v_true=v, interpret=interpret,
    )
    dx = dx[:n].astype(hidden2d.dtype)
    dw = dw[:v].astype(wte.dtype)
    # d(nll_sum)/d(w_row) = g * (lse - tgt); training never
    # differentiates wrt the mask, so skip the extra tgt residual and
    # return a zero cotangent of the right shape.
    return dx, dw, None, jnp.zeros_like(w_row)


_fused.defvjp(_fused_fwd, _fused_bwd)


def _walk_fetches(grid, index_map) -> int:
    """Block (re)fetches of one operand across a row-major grid walk.

    Pallas TPU keeps exactly the current block of each operand resident:
    consecutive grid steps with the SAME block index reuse it (no HBM
    traffic); an index change is one block fetch.  Counting index changes
    over the kernel's actual grid order therefore gives the kernel's HBM
    read traffic in blocks — the same model the module docstring's
    "~4.2 GB/step" claim rests on, now computed instead of asserted.
    """
    import itertools

    fetches = 0
    prev = None
    for idx in itertools.product(*[range(g) for g in grid]):
        bi = index_map(*idx)
        if bi != prev:
            fetches += 1
            prev = bi
    return fetches


def estimate_hbm_bytes(
    n_tokens: int,
    d: int,
    v: int,
    *,
    block_tokens: int | None = None,
    block_vocab: int | None = None,
    block_tokens_dx: int | None = None,
    block_vocab_dx: int | None = None,
    block_tokens_dw: int | None = None,
    block_vocab_dw: int | None = None,
    compute_bytes: int = 2,  # bf16 operands
) -> dict:
    """Analytic HBM traffic of one fused fwd+bwd head pass, in bytes.

    Derived by replaying each kernel's (grid, index_map) pairs — the same
    shapes handed to ``pl.pallas_call`` — through :func:`_walk_fetches`,
    so the number moves if the kernel's tiling or loop order changes.
    Outputs are counted symmetrically (an output-block index change =
    one block flush).  Token super-chunking (the VMEM scratch budget,
    :func:`_max_fwd_token_blocks`) is modeled: every extra forward chunk
    re-reads the weight table once.  So are the backward's ``dlog``
    chunks (:func:`dlog_chunk_tokens`): the tile is written by dx and
    read by dw once (``dlog_bytes``, inside the two kernels' counts), and
    every chunk carries the fp32 table gradient in and out of dw.

    Returns a dict with per-kernel and total byte counts plus
    ``chunked_head_bytes``, the corresponding traffic of the chunked
    (logits-materializing) head for the same shapes: logits tiles are
    written+read in fwd, and the checkpointed bwd recomputes (write) and
    reads them twice more (softmax grad + matmul operands) → 5 passes
    over an (N, V) fp32 array, plus the same x/w streams the fused path
    pays.  ``tests/test_fused_xent.py`` pins the headline-config ratio.

    Block defaults resolve through :func:`_blocks_for_dim` — the SAME
    selection ``fused_softmax_xent`` makes — so the estimate models the
    tiling the kernel actually runs at this ``d`` (the d=768 defaults
    would describe a nonexistent, VMEM-OOM config at d=1024).
    """
    blocks = _resolve_blocks(d, block_tokens, block_vocab, block_tokens_dx,
                             block_vocab_dx, block_tokens_dw, block_vocab_dw)
    (block_tokens, block_vocab, block_tokens_dx, block_vocab_dx,
     block_tokens_dw, block_vocab_dw) = blocks

    def pad(x, m):
        return x + (-x) % m

    # Padding mirrors the real call path exactly: forward pads to ITS
    # block sizes only (`_fused_fwd` -> `_pad_to(..., block_n)`), while
    # backward pads the tokens to whole ``dlog`` chunks and the vocabulary
    # to the lcm of the dx and dw tilings (`_fused_bwd`).
    n_fwd = pad(n_tokens, block_tokens)
    vp_fwd = pad(v, block_vocab)
    dlog_chunk = dlog_chunk_tokens(n_tokens, d, v, compute_bytes, blocks)
    n_dlog_chunks = pad(n_tokens, dlog_chunk) // dlog_chunk
    vp = pad(v, math.lcm(block_vocab_dx, block_vocab_dw))
    row_b = 4  # fp32 (1, block_n) rows: t/lse/tgt/c
    out = {}

    # forward (per token super-chunk): grid (n_j, n_i), j outer
    chunk_tokens = _max_fwd_token_blocks(block_tokens) * block_tokens
    fwd = 0
    for s in range(0, n_fwd, chunk_tokens):
        n_c = min(chunk_tokens, n_fwd - s)
        n_i, n_j = n_c // block_tokens, vp_fwd // block_vocab
        grid = (n_j, n_i)
        x_f = _walk_fetches(grid, lambda j, i: (i, 0))
        w_f = _walk_fetches(grid, lambda j, i: (j, 0))
        t_f = _walk_fetches(grid, lambda j, i: (0, i))
        o_f = _walk_fetches(grid, lambda j, i: (0, i))  # lse and tgt
        fwd += (
            x_f * block_tokens * d * compute_bytes
            + w_f * block_vocab * d * compute_bytes
            + t_f * block_tokens * row_b
            + 2 * o_f * block_tokens * row_b
        )
    out["fwd_bytes"] = fwd

    # backward dx, a ``dlog`` chunk: grid (n_i, n_j), i outer
    n_i, n_j = dlog_chunk // block_tokens_dx, vp // block_vocab_dx
    grid = (n_i, n_j)
    out["bwd_dx_bytes"] = n_dlog_chunks * (
        _walk_fetches(grid, lambda i, j: (i, 0)) * block_tokens_dx * d
        * compute_bytes
        + _walk_fetches(grid, lambda i, j: (j, 0)) * block_vocab_dx * d
        * compute_bytes
        + 3 * _walk_fetches(grid, lambda i, j: (0, i)) * block_tokens_dx
        * row_b                                        # t, lse, c rows
        + _walk_fetches(grid, lambda i, j: (i, 0)) * block_tokens_dx * d
        * 4                                            # dx out, fp32
        + _walk_fetches(grid, lambda i, j: (j, i)) * block_vocab_dx
        * block_tokens_dx * compute_bytes              # dlog out, rounded
    )

    # backward dw, a ``dlog`` chunk: grid (n_j, n_i), j outer
    n_i, n_j = dlog_chunk // block_tokens_dw, vp // block_vocab_dw
    grid = (n_j, n_i)
    out["bwd_dw_bytes"] = n_dlog_chunks * (
        _walk_fetches(grid, lambda j, i: (j, i)) * block_vocab_dw
        * block_tokens_dw * compute_bytes              # dlog in
        + _walk_fetches(grid, lambda j, i: (i, 0)) * block_tokens_dw * d
        * compute_bytes
        + 2 * _walk_fetches(grid, lambda j, i: (j, 0)) * block_vocab_dw * d
        * 4                                  # dw carried in and out, fp32
    )
    n = n_dlog_chunks * dlog_chunk
    out["dlog_bytes"] = 2 * vp * n * compute_bytes

    out["total_bytes"] = fwd + out["bwd_dx_bytes"] + out["bwd_dw_bytes"]
    # chunked head: 5 full passes over fp32 logits + one x/w stream each
    # for fwd, recompute, and the two bwd matmuls (dx, dw).
    out["chunked_head_bytes"] = (
        5 * n * vp * 4
        + 4 * (n * d + vp * d) * compute_bytes
    )
    return out


def fused_softmax_xent(
    hidden: jax.Array,   # (B, S, D) or (N, D) final hidden states
    wte: jax.Array,      # (V, D) tied embedding / output head
    targets: jax.Array,  # (B, S) / (N,) int labels
    mask: jax.Array | None = None,  # same shape as targets; 1 = count
    *,
    compute_dtype: jnp.dtype | None = None,
    block_tokens: int | None = None,
    block_vocab: int | None = None,
    block_tokens_dx: int | None = None,
    block_vocab_dx: int | None = None,
    block_tokens_dw: int | None = None,
    block_vocab_dw: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Mean masked next-token NLL; logits never leave VMEM.

    Drop-in for :func:`ops.xent.chunked_softmax_xent` — same reduction,
    same out-of-range-target semantics, Pallas execution.  ``interpret``
    defaults to auto (interpreter off-TPU so CPU tests and the virtual
    mesh work).
    """
    if interpret is None:
        interpret = not on_tpu()
    v = wte.shape[0]
    d = hidden.shape[-1]
    tokens = hidden.shape[:-1]
    t = targets.reshape(tokens).astype(jnp.int32)
    w_row = (
        mask.reshape(tokens).astype(jnp.float32) if mask is not None
        else jnp.ones(tokens, jnp.float32)
    )
    w_row = w_row * ((t >= 0) & (t < v)).astype(jnp.float32)
    op_dtype = compute_dtype or jnp.result_type(hidden, wte)
    blocks = _resolve_blocks(d, block_tokens, block_vocab, block_tokens_dx,
                             block_vocab_dx, block_tokens_dw, block_vocab_dw)

    def local(x, w, t, w_row):
        # one partial sum per shard, laid out like the token dimensions
        # it reduced, and added up outside by the partitioner
        nll_sum = _fused(x.reshape(-1, d), w, t.reshape(-1),
                         w_row.reshape(-1), op_dtype, blocks, interpret)
        return nll_sum.reshape((1,) * len(tokens))

    row = token_spec(tokens)
    nll_sums = shard_kernel(
        local, (token_spec(tokens, trailing=1), P(), row, row), row
    )(hidden, wte, t, w_row)
    return jnp.sum(nll_sums) / jnp.maximum(jnp.sum(w_row), 1.0)
