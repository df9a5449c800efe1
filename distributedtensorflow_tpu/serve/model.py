"""The serving engine's two compiled programs: chunked prefill + paged decode.

Prefill/decode disaggregation: a serving step is either (a) teacher-forced
ingestion of a prompt chunk — big matmuls, compute-bound — or (b) one
token for every active slot — cache streaming, memory-bound.  Fusing them
(the ``models.generate`` whole-batch scan) forces every request in the
batch to the same phase; splitting them lets the scheduler admit a new
prompt while other slots keep decoding.  Both programs have fully static
shapes, so a serving process compiles **exactly two** XLA executables:

- :func:`make_prefill_fn` — one ``prefill_chunk``-wide slice of one
  prompt through :func:`models.generate.prefill` (the dense flax cache
  path, so prefill math is byte-identical to training-side decode), plus
  a scatter of the chunk's K/V into the paged pool.  Any prompt length =
  a Python loop of these fixed-width calls.
- :func:`make_decode_fn` — one token for all ``max_slots`` slots against
  the paged pool (``ops.attention.paged_decode_attention``).  The forward
  is rebuilt here from the raw param tree (flax's cache collection owns a
  dense per-slot buffer and can't address a shared pool); equivalence
  with ``GPTLM`` is pinned by tests/test_serve.py, and every dtype choice
  (bf16 matmuls, fp32 layernorm/softmax/logits) mirrors ``models/gpt.py``
  line for line.
- :func:`make_gather_cache_fn` — rebuild the dense prefill cache for one
  slot from its pool blocks (gather through the page-table row).  This is
  what makes chunked prefill *stateless*: any slot's next chunk can run
  at any time by re-materializing its cache from the pool, so the
  scheduler can interleave prefill chunks of several requests with
  decode steps (ISSUE 14 budgeted prefill), and a request admitted onto
  a cached prefix starts from the shared blocks without a special load
  path.  The gathered values are the exact bytes prefill scattered out
  (or that an earlier request with the same prefix scattered), so the
  chunk math stays byte-identical to an uninterrupted prefill.

(There is also a tiny pool-level block-copy program in ``serve.kv_cache``
— the copy-on-write path — compiled only if a CoW ever fires.)

The pool arrays are donated: steady-state serving does not allocate.  All
of these programs take a pool in the one form ``serve.kv_cache`` stores it
in — ``(layers, (num_blocks + 1) * block_size, Hkv * D)``, token rows with
the heads folded into the minor dimension — and none reshapes it: a K/V
write scatters ``(tokens, Hkv * D)`` rows at ``block * block_size +
offset``, the page-table walk gathers whole blocks of rows with the layer
as an index of the same gather, and heads are split only on what was
gathered.  So the donated input aliases the output and XLA adds no
pool-sized copy on entry or exit (with a ``(..., block, Hkv, D)`` pool it
converted all of it both ways on every call: PERF.md §5, PR 25);
``serve.pool_check`` reads that off the compiled programs.

Every stage of the programs sits in a ``jax.named_scope`` (``embed``,
``cast_params``, per layer ``h<i>/{ln,qkv,kv_write,paged_attn,proj,mlp}``,
``head``, ``sample``; ``kv_write`` in the prefill program, ``gather_cache``
for the gather): metadata only, so a profiler trace can say which stage a
device operation belongs to.  The arithmetic is unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..models.generate import prefill
from ..models.gpt import GPTConfig, rope, rope_tables
from ..ops.attention import paged_decode_attention, paged_verify_attention
from ..ops.layernorm import layer_norm
from ..ops.xent import tied_head_logits
from .sampling import sample_burst

__all__ = [
    "make_prefill_cache",
    "make_prefill_fn",
    "make_decode_fn",
    "make_fused_decode_fn",
    "make_gather_cache_fn",
    "reset_cache_index",
]


def _check_servable(cfg: GPTConfig) -> None:
    if cfg.attn_window is not None:
        raise ValueError(
            "the paged decode program does not implement sliding-window "
            "masking yet; serve with attn_window=None"
        )
    if cfg.dropout_rate:
        raise ValueError("serving is deterministic; set dropout_rate=0")


def make_prefill_cache(cfg: GPTConfig):
    """Zeroed dense prefill cache, structurally identical to the flax
    ``"cache"`` collection ``GPTLM(decode=True)`` would create — built by
    hand so the engine never traces a third (cache-creating) program.
    One buffer serves every admission: :func:`reset_cache_index` rewinds
    it and stale K/V beyond the index is masked by the decode-mode
    validity rule (``k_idx <= q_pos``)."""
    head_dim = cfg.hidden_size // cfg.num_heads
    kv = (1, cfg.kv_heads, cfg.max_seq, head_dim)
    return {
        f"h{i}": {"attn": {
            "cached_key": jnp.zeros(kv, cfg.dtype),
            "cached_value": jnp.zeros(kv, cfg.dtype),
            "cache_index": jnp.zeros((), jnp.int32),
        }}
        for i in range(cfg.num_layers)
    }


def reset_cache_index(cache):
    """Rewind a prefill cache to position 0 for the next admission (host
    dict rebuild; the K/V buffers are reused in place)."""
    return {
        name: {"attn": {**layer["attn"],
                        "cache_index": jnp.zeros((), jnp.int32)}}
        for name, layer in cache.items()
    }


def make_prefill_fn(cfg: GPTConfig, *, chunk: int, block_size: int):
    """Compiled program (a): one fixed-width prompt chunk.

    ``fn(params, k_pool, v_pool, cache, tokens, start, table_row,
    last_ix) -> (last_logits, cache, k_pool, v_pool)`` where ``tokens``
    is ``(1, chunk)``, ``start`` the chunk's first absolute position,
    ``table_row`` the slot's ``(blocks_per_slot,)`` page-table row, and
    ``last_ix`` the in-chunk index whose logits the engine wants (the
    final prompt token's, clamped into range on non-final chunks whose
    logits are discarded).  The chunk's K/V are sliced out of the dense
    flax cache and scattered, as ``(chunk, Hkv * D)`` token rows, to the
    slot's pool blocks."""
    _check_servable(cfg)

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def prefill_chunk(params, k_pool, v_pool, cache, tokens, start,
                      table_row, last_ix):
        positions = (start + jnp.arange(chunk, dtype=jnp.int32))[None, :]
        logits, cache = prefill(params, tokens, positions, cfg=cfg,
                                cache=cache)
        num_layers, _, width = k_pool.shape

        def chunk_rows(name):
            # per layer (1, Hkv, max_seq, D) -> the chunk's (chunk, Hkv * D)
            return jnp.stack([
                jax.lax.dynamic_slice_in_dim(
                    cache[f"h{i}"]["attn"][name], start, chunk, axis=2,
                )[0].transpose(1, 0, 2).reshape(chunk, width)
                for i in range(num_layers)
            ])  # (L, chunk, Hkv * D)

        with jax.named_scope("kv_write"):
            pos = start + jnp.arange(chunk)
            idx = table_row[pos // block_size] * block_size \
                + pos % block_size  # (chunk,) pool rows
            # (layer, row) index pairs, not ``.at[:, idx]``: for a scatter
            # over a whole leading dimension XLA re-lays the operand out
            # rows-major and copies the pool in and out (serve.pool_check)
            layers = jnp.arange(num_layers)[:, None]
            k_pool = k_pool.at[layers, idx].set(chunk_rows("cached_key"))
            v_pool = v_pool.at[layers, idx].set(chunk_rows("cached_value"))
        return logits[0, last_ix], cache, k_pool, v_pool

    return prefill_chunk


def make_gather_cache_fn(cfg: GPTConfig, *, block_size: int):
    """Compiled program: rebuild one slot's dense prefill cache from the
    paged pool.

    ``fn(k_pool, v_pool, cache, table_row, start) -> cache`` gathers ALL
    ``max_seq`` positions through ``table_row`` into the (donated) dense
    cache buffer and sets ``cache_index = start`` — the position the next
    prefill chunk writes at.  Positions >= ``start`` gather garbage
    (scratch / stale blocks) but are exactly the positions the decode-mode
    validity rule masks (``k_idx <= q_pos``) until a chunk overwrites
    them, so no dynamic-shape masking is needed and the program stays
    static.  Positions < ``start`` reproduce bit-for-bit the K/V a
    straight-line prefill would have left in the cache (the pool holds
    the same bytes the dense cache was sliced into)."""
    _check_servable(cfg)
    num_layers = cfg.num_layers

    @functools.partial(jax.jit, donate_argnums=(2,))
    @jax.named_scope("gather_cache")
    def gather_cache(k_pool, v_pool, cache, table_row, start):
        pos = jnp.arange(cfg.max_seq)
        idx = table_row[pos // block_size] * block_size + pos % block_size

        def dense(pool, i):
            # the slot's (max_seq, Hkv * D) rows of layer i -> (1, Hkv,
            # max_seq, D), the flax decode-cache layout
            # make_prefill_cache builds.
            return pool[i, idx].reshape(
                cfg.max_seq, cfg.kv_heads, -1).transpose(1, 0, 2)[None]

        return {
            f"h{i}": {"attn": {
                "cached_key": dense(k_pool, i),
                "cached_value": dense(v_pool, i),
                "cache_index": start.astype(jnp.int32),
            }}
            for i in range(num_layers)
        }

    return gather_cache


def _cast(param, dtype):
    """A stored (fp32) parameter in the compute dtype: the conversion the
    decode programs repeat every iteration, under a scope of its own."""
    with jax.named_scope("cast_params"):
        return param.astype(dtype)


def make_decode_fn(cfg: GPTConfig, *, block_size: int):
    """Compiled program (b): one decode token for every slot.

    ``fn(params, k_pool, v_pool, tokens, block_tables, seq_lens, active)
    -> (logits, k_pool, v_pool)`` with ``tokens`` ``(max_slots,)`` (each
    slot's last sampled token), ``seq_lens`` the resident token counts
    (the new token is written at that position, then attends ``seq_len +
    1`` positions), and ``active`` masking unoccupied slots: their write
    lands in the reserved scratch block and their logits are discarded by
    the engine, so the program shape never depends on occupancy."""
    _check_servable(cfg)
    num_layers = cfg.num_layers
    n_heads = cfg.num_heads
    h_kv = cfg.kv_heads
    head_dim = cfg.hidden_size // n_heads
    hidden = cfg.hidden_size
    kv_width = h_kv * head_dim

    def _ln(x, p, out_dtype=None):
        return layer_norm(x, p["scale"], p["bias"], eps=1e-6,
                          out_dtype=out_dtype or x.dtype)

    def _dense(x, kernel):
        # flax nn.Dense(dtype=cfg.dtype, use_bias=False): both operands
        # cast to the compute dtype, default accumulation.
        return x @ _cast(kernel, cfg.dtype)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def decode(params, k_pool, v_pool, tokens, block_tables, seq_lens,
               active):
        b = tokens.shape[0]
        bs = block_size
        scratch_row = k_pool.shape[1] - bs  # first row of the scratch block
        with jax.named_scope("embed"):
            x = _cast(params["wte"]["embedding"],
                      cfg.dtype)[tokens][:, None, :]
        positions = seq_lens.astype(jnp.int32)[:, None]  # (B, 1)
        tabs = rope_tables(positions, head_dim, cfg.rope_theta, cfg.dtype)
        # Write coordinates for the new token: active slots append at
        # seq_len inside their own pages; inactive slots hit scratch.
        blk = jnp.take_along_axis(
            block_tables, (seq_lens // bs)[:, None], axis=1
        )[:, 0]
        idx = jnp.where(active, blk * bs + seq_lens % bs, scratch_row)
        attend_lens = jnp.where(active, seq_lens + 1, 1)
        for layer in range(num_layers):
            p = params[f"h{layer}"]
            with jax.named_scope(f"h{layer}"):
                with jax.named_scope("ln"):
                    h = _ln(x, p["ln1"])
                with jax.named_scope("qkv"):
                    qkv = _dense(h, p["attn"]["qkv"]["kernel"])
                    q = qkv[..., :hidden].reshape(b, 1, n_heads, head_dim)
                    k = qkv[..., hidden:hidden + kv_width].reshape(
                        b, 1, h_kv, head_dim)
                    v = qkv[..., hidden + kv_width:].reshape(
                        b, 1, h_kv, head_dim)
                    q = rope(q, positions, cfg.rope_theta, tabs)
                    k = rope(k, positions, cfg.rope_theta, tabs)
                with jax.named_scope("kv_write"):
                    k_pool = k_pool.at[layer, idx].set(
                        k.reshape(b, kv_width))
                    v_pool = v_pool.at[layer, idx].set(
                        v.reshape(b, kv_width))
                with jax.named_scope("paged_attn"):
                    out = paged_decode_attention(
                        q[:, 0], k_pool, v_pool, block_tables,
                        attend_lens, layer=layer, block_size=bs,
                    ).reshape(b, 1, hidden).astype(cfg.dtype)
                with jax.named_scope("proj"):
                    x = x + _dense(out, p["attn"]["proj"]["kernel"])
                with jax.named_scope("ln"):
                    h = _ln(x, p["ln2"])
                with jax.named_scope("mlp"):
                    m = _dense(jax.nn.gelu(_dense(h, p["fc_in"]["kernel"])),
                               p["fc_out"]["kernel"])
                    x = x + m
        with jax.named_scope("head"):
            xf = _ln(x, params["ln_f"], out_dtype=jnp.float32)
            logits = tied_head_logits(
                xf[:, 0], params["wte"]["embedding"], cfg.dtype
            )
        return logits, k_pool, v_pool

    return decode


def make_fused_decode_fn(cfg: GPTConfig, *, block_size: int, draft: int = 0):
    """Compiled program (b'): the decode **fast path** — forward, K/V
    append, AND sampling in one dispatch; optionally speculative.

    ``fn(params, k_pool, v_pool, tokens, draft_lens, block_tables,
    seq_lens, active, keys, prompt_lens, temperature, top_k) ->
    (packed, next_feed, k_pool, v_pool)`` with ``T = draft + 1`` query
    positions per slot: column 0 is each slot's last
    committed token, columns ``1..draft_lens`` its n-gram draft
    proposals (``serve.draft``), the rest padding.  The program writes
    K/V for the committed token and every draft at consecutive
    positions (pad/inactive writes land in the scratch block), runs ONE
    multi-token paged attention pass
    (:func:`ops.attention.paged_verify_attention`) with causal masking
    inside the draft window, and applies the fused sampler
    (:func:`serve.sampling.sample_burst`): greedy / temperature+top-k
    with per-slot PRNG keys resident in ``keys``, generalized to
    rejection-sampled draft verification — the emitted distribution is
    exactly the target model's, and greedy output is token-for-token
    the sequential path's.

    Versus :func:`make_decode_fn` + host sampling, the host round-trip
    per token collapses to one small ``(out_tokens, n_emitted)`` fetch
    per *iteration* (EOS/logging), ``next_feed`` stays device-resident
    as the next step's input, and with ``draft > 0`` one dispatch can
    emit up to ``draft + 1`` tokens per slot.  ``draft=0`` (``T = 1``)
    is the non-speculative fused program — same signature, so the
    engine swaps between the two without a third code path.

    Every forward-pass dtype choice mirrors :func:`make_decode_fn` line
    for line; the accepted-token logits are therefore the same numbers
    the one-token program would have produced (parity pinned by
    tests/test_serve_spec.py, incl. bf16).
    """
    _check_servable(cfg)
    num_layers = cfg.num_layers
    n_heads = cfg.num_heads
    h_kv = cfg.kv_heads
    head_dim = cfg.hidden_size // n_heads
    hidden = cfg.hidden_size
    kv_width = h_kv * head_dim
    t_width = draft + 1

    def _ln(x, p, out_dtype=None):
        return layer_norm(x, p["scale"], p["bias"], eps=1e-6,
                          out_dtype=out_dtype or x.dtype)

    def _dense(x, kernel):
        return x @ _cast(kernel, cfg.dtype)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def fused_decode(params, k_pool, v_pool, tokens, draft_lens,
                     block_tables, seq_lens, active, keys, prompt_lens,
                     temperature, top_k):
        b = tokens.shape[0]
        bs = block_size
        scratch_row = k_pool.shape[1] - bs  # first row of the scratch block
        nb_table = block_tables.shape[1]
        with jax.named_scope("embed"):
            x = _cast(params["wte"]["embedding"],
                      cfg.dtype)[tokens]                        # (B, T, H)
        positions = (seq_lens[:, None]
                     + jnp.arange(t_width, dtype=jnp.int32)[None, :])
        tabs = rope_tables(positions, head_dim, cfg.rope_theta, cfg.dtype)
        # Write coordinates: the committed token (column 0) and the real
        # drafts append at consecutive positions inside the slot's pages;
        # pad columns and inactive slots hit scratch.  Rejected drafts
        # leave garbage PAST the committed seq_len — masked by the
        # validity rule until a later write overwrites it (the K/V-level
        # rollback; the host-side retreat is kv_cache.rollback).
        valid_w = active[:, None] & (
            jnp.arange(t_width)[None, :] <= draft_lens[:, None]
        )
        blk = jnp.take_along_axis(
            block_tables, jnp.clip(positions // bs, 0, nb_table - 1), axis=1
        )
        idx = jnp.where(valid_w, blk * bs + positions % bs,
                        scratch_row).reshape(-1)                # (B * T,)
        attend_lens = jnp.where(active, seq_lens + 1, 1)
        for layer in range(num_layers):
            p = params[f"h{layer}"]
            with jax.named_scope(f"h{layer}"):
                with jax.named_scope("ln"):
                    h = _ln(x, p["ln1"])
                with jax.named_scope("qkv"):
                    qkv = _dense(h, p["attn"]["qkv"]["kernel"])
                    q = qkv[..., :hidden].reshape(
                        b, t_width, n_heads, head_dim)
                    k = qkv[..., hidden:hidden + kv_width].reshape(
                        b, t_width, h_kv, head_dim)
                    v = qkv[..., hidden + kv_width:].reshape(
                        b, t_width, h_kv, head_dim)
                    q = rope(q, positions, cfg.rope_theta, tabs)
                    k = rope(k, positions, cfg.rope_theta, tabs)
                with jax.named_scope("kv_write"):
                    k_pool = k_pool.at[layer, idx].set(
                        k.reshape(b * t_width, kv_width))
                    v_pool = v_pool.at[layer, idx].set(
                        v.reshape(b * t_width, kv_width))
                with jax.named_scope("paged_attn"):
                    out = paged_verify_attention(
                        q, k_pool, v_pool, block_tables,
                        attend_lens, layer=layer, block_size=bs,
                    ).reshape(b, t_width, hidden).astype(cfg.dtype)
                with jax.named_scope("proj"):
                    x = x + _dense(out, p["attn"]["proj"]["kernel"])
                with jax.named_scope("ln"):
                    h = _ln(x, p["ln2"])
                with jax.named_scope("mlp"):
                    m = _dense(jax.nn.gelu(_dense(h, p["fc_in"]["kernel"])),
                               p["fc_out"]["kernel"])
                    x = x + m
        with jax.named_scope("head"):
            xf = _ln(x, params["ln_f"], out_dtype=jnp.float32)
            logits = tied_head_logits(
                xf, params["wte"]["embedding"], cfg.dtype
            )                                                   # (B, T, V)
        # Emitted-token index of each slot's next sample, derived
        # on-device (decode invariant: seq_len = prompt + emitted - 1)
        # so the host ships nothing per step that it can avoid —
        # prompt_lens changes only at admission.
        with jax.named_scope("sample"):
            sample_pos = jnp.maximum(seq_lens - prompt_lens + 1, 0)
            out_tokens, n_emitted, next_feed = sample_burst(
                logits, tokens, draft_lens, keys, sample_pos, temperature,
                top_k, active,
            )
        # out_tokens and n_emitted packed into ONE array so the host
        # pays a single small device->host fetch per iteration;
        # next_feed keeps the feed shape (B, 1) so the next T=1 call
        # consumes it with zero host-side reshaping.
        packed = jnp.concatenate([out_tokens, n_emitted[:, None]], axis=1)
        return packed, next_feed[:, None], k_pool, v_pool

    return fused_decode
