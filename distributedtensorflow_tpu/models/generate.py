"""Autoregressive generation for the GPT decoder LM (serving path).

No serving/inference loop exists in the reference's training harness; this
completes the decoder-LM story: KV-cache incremental decoding
(``GPTLM(decode=True)`` — one-token steps against a static ``max_seq``
cache), greedy or temperature/top-k sampling, ragged right-padded prompts.

TPU-first: the whole generate loop is ONE ``lax.scan`` inside ``jit`` —
static shapes (prompt buffer padded to ``prompt_pad + max_new_tokens``),
the KV cache as scan carry, no host round-trips per token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .gpt import GPTConfig, GPTLM


def _sample(logits, rng, temperature, *, greedy: bool, top_k: int,
            top_p: float = 1.0):
    """(B, V) logits -> (B,) token ids.  ``temperature`` is traced (no
    recompile per value); greedy/top_k/top_p change the compiled program."""
    if greedy:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(temperature, 1e-6)
    sorted_desc = None
    if top_k > 0:
        topv, _ = jax.lax.top_k(logits, top_k)  # O(V log k), no full sort
        kth = topv[:, -1][:, None]
        logits = jnp.where(logits < kth, -1e9, logits)
        sorted_desc = topv  # the only survivors; already descending
    if top_p < 1.0:
        # nucleus sampling: keep the smallest descending-prob prefix with
        # cumulative mass >= top_p (the first token is always kept).  After
        # top_k only the k survivors can be in the nucleus, so reuse them
        # instead of a full O(V log V) sort per decoded token; the -1e9
        # masked tail's softmax mass is ~0, so probs match the full-vocab
        # softmax over survivors.
        if sorted_desc is None:
            sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
        kept = exclusive_cum < top_p
        cutoff = jnp.min(
            jnp.where(kept, sorted_desc, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, -1e9, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def prefill(params, tokens, positions, *, cfg: GPTConfig, cache=None):
    """Teacher-forced multi-token step through the KV cache.

    Runs the decode-mode model on a token chunk (causal within the chunk,
    attending everything already in ``cache``) and returns ``(logits,
    cache)`` with the chunk's K/V appended.  ``cache=None`` creates the
    cache collection (flax mutable-apply priming); pass the returned cache
    back to continue — chunked prefill is a loop of fixed-width calls, so
    one compiled program covers any prompt length.  This is
    :func:`generate`'s priming step, and the plain reference the serving
    engine's tests hold its paged programs to.  Pure function: traceable
    under jit and scan, caller owns the cache pytree.
    """
    model = GPTLM(cfg, decode=True)
    variables = {"params": params}
    if cache is not None:
        variables["cache"] = cache
    logits, vars_out = model.apply(
        variables, tokens, positions=positions, mutable=["cache"]
    )
    return logits, vars_out["cache"]


def decode_step(params, tokens, positions, cache, *, cfg: GPTConfig):
    """One-token decode step against an existing KV cache.

    ``tokens``/``positions`` are ``(B, 1)``; returns ``(logits, cache)``
    with the new token's K/V written at the cache index.  The single-step
    specialization of :func:`prefill` (the cache must already exist) —
    the body of :func:`generate`'s scan and the dense-cache counterpart of
    the serving engine's paged decode program.
    """
    return prefill(params, tokens, positions, cfg=cfg, cache=cache)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "greedy", "top_k", "top_p",
                     "eos_token_id"),
)
def _generate_impl(params, prompt, prompt_lens, rng, temperature, *,
                   cfg: GPTConfig, max_new_tokens: int, greedy: bool,
                   top_k: int, top_p: float, eos_token_id: int):
    b, prompt_pad = prompt.shape
    total = prompt_pad + max_new_tokens

    tokens = jnp.concatenate(
        [prompt, jnp.zeros((b, max_new_tokens), prompt.dtype)], axis=1
    )

    # First token primes the cache (flax creates the cache collection on a
    # mutable apply); the scan then carries it functionally.
    logits0, cache = prefill(
        params, tokens[:, :1], jnp.zeros((b, 1), jnp.int32), cfg=cfg
    )

    done0 = jnp.zeros((b,), bool)

    def step(carry, t):
        tokens, cache, rng, logits, done = carry
        rng, sub = jax.random.split(rng)
        sampled = _sample(logits[:, -1], sub, temperature, greedy=greedy,
                          top_k=top_k, top_p=top_p)
        # While t+1 is still inside this sequence's prompt, feed the prompt
        # token; afterwards feed the sample (teacher-forced prefill and
        # decode in one uniform loop — no separate prefill program).
        in_prompt = (t + 1) < prompt_lens  # (B,)
        prompt_tok = jax.lax.dynamic_slice_in_dim(tokens, t + 1, 1, axis=1)[:, 0]
        if eos_token_id >= 0:
            # a finished sequence keeps emitting eos (shapes stay static;
            # "early stop" = the output is frozen from the eos on)
            sampled = jnp.where(done, eos_token_id, sampled)
        nxt = jnp.where(in_prompt, prompt_tok, sampled).astype(tokens.dtype)
        if eos_token_id >= 0:
            done = done | (~in_prompt & (nxt == eos_token_id))
        tokens = jax.lax.dynamic_update_slice_in_dim(
            tokens, nxt[:, None], t + 1, axis=1
        )
        logits, cache = decode_step(
            params, nxt[:, None], jnp.full((b, 1), t + 1, jnp.int32),
            cache, cfg=cfg,
        )
        return (tokens, cache, rng, logits, done), None

    (tokens, _, _, _, _), _ = jax.lax.scan(
        step, (tokens, cache, rng, logits0, done0), jnp.arange(total - 1)
    )
    return tokens


def generate(
    params,
    prompt: jax.Array,  # (B, P) right-padded token ids
    *,
    cfg: GPTConfig,
    max_new_tokens: int,
    prompt_lens: jax.Array | None = None,  # (B,) true lengths; default P
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_token_id: int | None = None,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Generate continuations; returns (B, P + max_new_tokens) token ids.

    ``temperature=0`` is greedy; otherwise softmax sampling at the given
    temperature, optionally truncated to the ``top_k`` highest logits
    and/or the ``top_p`` nucleus (smallest probability mass >= top_p).
    ``eos_token_id`` freezes a sequence once it samples that token (it
    keeps emitting eos; shapes stay static).
    The KV cache needs ``cfg.max_seq >= P + max_new_tokens``.
    """
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if eos_token_id is not None and eos_token_id < 0:
        raise ValueError(
            f"eos_token_id must be a valid token id, got {eos_token_id} "
            "(pass None to disable eos handling)"
        )
    b, p = prompt.shape
    total = p + max_new_tokens
    if cfg.max_seq < total:
        raise ValueError(
            f"cfg.max_seq={cfg.max_seq} < prompt+new={total}; raise max_seq"
        )
    if prompt_lens is None:
        prompt_lens = jnp.full((b,), p, jnp.int32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    return _generate_impl(
        params, prompt.astype(jnp.int32), prompt_lens.astype(jnp.int32), rng,
        jnp.asarray(temperature, jnp.float32),
        cfg=cfg, max_new_tokens=max_new_tokens,
        greedy=float(temperature) <= 0.0, top_k=int(top_k),
        top_p=float(top_p),
        eos_token_id=-1 if eos_token_id is None else int(eos_token_id),
    )
