"""Memory-efficient softmax cross-entropy for large-vocabulary LM heads.

The naive LM loss materializes fp32 logits ``(B, S, V)`` plus a
``log_softmax`` copy — for GPT-2-small at B=16, S=1024, V=50257 that is
~3.3 GB *per copy*, and the train step becomes HBM-bandwidth-bound on
tensors that are immediately reduced away.  The reference stack has no
equivalent (Keras ``SparseCategoricalCrossentropy`` materializes logits
the same way); this is TPU-first design, not a port.

:func:`chunked_softmax_xent` computes the same loss streaming over token
chunks inside a ``lax.scan`` whose body is ``jax.checkpoint``-ed:

- forward: per chunk, logits ``(C, V)`` are built, reduced to
  ``logsumexp`` and the target logit, then discarded — peak extra memory
  is ``C x V`` fp32 instead of ``B x S x V``;
- backward: the chunk's logits are *recomputed*, so the full logits
  tensor never exists in the residual set either.

Gradients match the naive loss exactly (same math, same reduction
order up to fp associativity); ``tests/test_gpt.py`` asserts equivalence.

Tensor-parallel note: under a vocab-sharded table (``gpt_layout`` puts
``model`` on wte dim 0) GSPMD partitions this head cleanly — verified on
an 8-way model mesh that the compiled fwd+bwd HLO contains ZERO
all-gathers, only per-chunk ``(C,)``-sized all-reduces for the logsumexp
and target-gather combines.  No hand-written vocab-parallel (shard_map)
head is needed; see also ``ops/fused_xent.py`` for the single-shard
Pallas fusion.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: Tokens per scan chunk.  4096 keeps the transient logits tile at
#: 4096 x V fp32 (~0.8 GB for GPT-2's vocab) — large enough for full MXU
#: tiles, small enough to never pressure HBM.
DEFAULT_CHUNK_TOKENS = 4096


def chunked_argmax(
    hidden: jax.Array,   # (B, S, D) final hidden states
    wte: jax.Array,      # (V, D) tied table
    *,
    chunk_tokens: int = DEFAULT_CHUNK_TOKENS,
    compute_dtype: jnp.dtype | None = None,
) -> jax.Array:
    """Greedy token ids from the tied head WITHOUT full logits.

    The eval-side sibling of :func:`chunked_softmax_xent`: argmax needs
    the whole vocab row per token but not the whole (B, S, V) tensor —
    streaming (C, V) tiles through a scan keeps eval's peak memory at the
    training step's level (a sidecar evaluator must never OOM where the
    trainer fits).  Returns int32 (B, S).
    """
    b, s, d = hidden.shape
    n = b * s
    x = hidden.reshape(n, d)
    op_dtype = compute_dtype or jnp.result_type(hidden, wte)
    wte_t = wte.T.astype(op_dtype)

    c = min(chunk_tokens, n)
    n_chunks = -(-n // c)
    pad = n_chunks * c - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))

    def body(_, x_c):
        logits = jnp.matmul(
            x_c.astype(op_dtype), wte_t,
            preferred_element_type=jnp.float32,
        )
        return None, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    _, ids = lax.scan(body, None, x.reshape(n_chunks, c, d))
    return ids.reshape(n_chunks * c)[:n].reshape(b, s)


def tied_head_logits(
    x: jax.Array,    # (..., D) hidden states (fp32 post-ln_f)
    wte: jax.Array,  # (V, D) tied embedding table
    compute_dtype: jnp.dtype | None = None,
) -> jax.Array:
    """Full logits for a tied-embedding head, fp32 output.

    THE dtype recipe for every vocab matmul in the framework — operands in
    ``compute_dtype`` (bf16 = full MXU rate; an fp32 x fp32 vocab matmul
    runs at a fraction of it), fp32 accumulation via
    ``preferred_element_type``.  :func:`chunked_softmax_xent` uses the
    identical path per chunk, so the dense and chunked heads agree; model
    files must call this rather than hand-rolling the matmul."""
    dt = compute_dtype or jnp.result_type(x, wte)
    return jnp.matmul(
        x.astype(dt), wte.T.astype(dt),
        preferred_element_type=jnp.float32,
    )


def chunked_softmax_xent(
    hidden: jax.Array,   # (B, S, D) final hidden states (post-ln_f)
    wte: jax.Array,      # (V, D) tied embedding / output head
    targets: jax.Array,  # (B, S) int labels
    mask: jax.Array | None = None,  # (B, S) 1 = count this position
    *,
    chunk_tokens: int = DEFAULT_CHUNK_TOKENS,
    compute_dtype: jnp.dtype | None = None,
    logits_dtype: jnp.dtype | None = None,
) -> jax.Array:
    """Mean masked next-token NLL without materializing full logits.

    Returns the scalar mean of ``logsumexp(h @ wte.T) - logit[target]``
    over unmasked positions.  ``targets`` outside ``[0, V)`` (e.g. a
    -100-style ignore label a caller forgot to mask) contribute ZERO
    weight — matching optax's integer-label xent — rather than being
    silently attributed to a clipped token id.

    ``logits_dtype=bfloat16`` materializes each chunk's ``(C, V)`` logits
    tile in bf16 (the cast fuses into the matmul epilogue), HALVING the
    head's HBM traffic — the dominant cost of the chunked head on TPU.
    Reductions still run fp32 (logsumexp upcasts on read).  Logit
    magnitudes are O(10), so bf16's ~3 significant digits cost ~1e-2 in
    the per-token NLL — the standard LM-training trade (most stacks emit
    bf16 logits); keep the fp32 default where exact parity matters.
    """
    b, s, d = hidden.shape
    n = b * s
    v = wte.shape[0]
    x = hidden.reshape(n, d)
    t_raw = targets.reshape(n)
    t = jnp.clip(t_raw, 0, v - 1)
    w = (
        mask.reshape(n).astype(jnp.float32)
        if mask is not None
        else jnp.ones((n,), jnp.float32)
    )
    w = w * ((t_raw >= 0) & (t_raw < v)).astype(jnp.float32)

    c = min(chunk_tokens, n)
    n_chunks = -(-n // c)
    pad = n_chunks * c - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        t = jnp.pad(t, (0, pad))
        w = jnp.pad(w, (0, pad))  # padded rows weigh 0

    # compute_dtype picks the MATMUL operand dtype for the (C, V) logits
    # tile; accumulation/reductions stay fp32 via preferred_element_type.
    # Pass the model's compute dtype (bf16) here: hidden arrives fp32 from
    # the fp32 ln_f, and an fp32 x fp32 matmul runs at a fraction of the
    # MXU's bf16 rate — on the v5e this head was the single largest cost
    # of the GPT-2-small step (the 50k-vocab matmul is ~30% of model
    # FLOPs).  None = the operands' own dtypes (exact-parity tests).
    op_dtype = compute_dtype or jnp.result_type(hidden, wte)
    out_dtype = logits_dtype or jnp.float32
    wte_t = wte.T.astype(op_dtype)

    def body(carry, inp):
        nll_sum, w_sum = carry
        x_c, t_c, w_c = inp
        logits = jnp.matmul(
            x_c.astype(op_dtype), wte_t,
            preferred_element_type=jnp.float32,
        ).astype(out_dtype)  # (C, V); fp32 accumulate, out_dtype store
        # Upcasts fuse into the reductions (no fp32 copy of the tile).
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[:, None], axis=1)[:, 0]
        nll = lse - tgt.astype(jnp.float32)
        return (nll_sum + jnp.sum(nll * w_c), w_sum + jnp.sum(w_c)), None

    xs = (
        x.reshape(n_chunks, c, d),
        t.reshape(n_chunks, c),
        w.reshape(n_chunks, c),
    )
    (nll_sum, w_sum), _ = lax.scan(
        jax.checkpoint(body), (jnp.zeros((), jnp.float32),) * 2, xs
    )
    return nll_sum / jnp.maximum(w_sum, 1.0)
