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
  the paged pool (``ops.attention.paged_window_decode_attention``: on the
  TPU a kernel that reads only the blocks a slot holds).  The forward
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

from ..models import afmoe
from ..models.generate import prefill
from ..models.gpt import GPTConfig, rope, rope_tables
from ..ops.attention import (
    paged_chunk_attention,
    paged_decode_formulation,
    paged_verify_attention,
    paged_window_decode_attention,
)
from ..ops.layernorm import layer_norm
from ..ops.xent import tied_head_logits
from ..obs import tracing as obs_tracing
from .sampling import sample_burst

__all__ = [
    "make_prefill_cache",
    "make_prefill_fn",
    "make_decode_fn",
    "make_fused_decode_fn",
    "make_gather_cache_fn",
    "make_family_prefill_fn",
    "make_family_decode_fn",
    "make_programs",
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
                    out = paged_window_decode_attention(
                        q[:, 0], k_pool, v_pool, block_tables,
                        attend_lens, layer=layer, block_size=bs,
                        impl=cfg.attn_impl,
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


# ---------------------------------------------------------------------------
# The programs of a family given by its layer functions (``models.afmoe``)
# ---------------------------------------------------------------------------
#
# The GPT-2 programs above are that forward written out by hand around a
# dense flax prefill cache.  A family that brings its own layer functions
# (``family``: a module with ``embed``, ``block(p, x, cfg, layer, positions,
# attend)`` and ``head``, as ``models.afmoe``) needs no second copy of them
# here: a program is the family's embedding, its blocks
# and its head, with an ``attend`` that writes the new K/V rows into the
# layer's group pool and reads the pages back (``ops.attention``'s
# ``paged_chunk_attention`` / ``paged_window_decode_attention``).  So there
# is no dense cache and no gather program: a prefill chunk reads the slot's
# earlier chunks through its page-table rows, which is what
# ``make_gather_cache_fn`` re-materialises for GPT-2.  Weights are stored in
# the compute type; nothing is cast per iteration.
#
# ``pools`` is ``{group: (k_pool, v_pool)}`` and ``tables`` ``{group:
# page table}`` (``serve.kv_cache.GroupedKVCache``); ``layers`` maps a
# group to the model layers it holds, in pool order.


def _group_of(layers: dict[str, tuple[int, ...]]) -> dict[int, tuple]:
    return {layer: (name, i) for name, ls in layers.items()
            for i, layer in enumerate(ls)}


def make_family_prefill_fn(family, cfg, *, chunk: int, block_size: int,
                           layers: dict[str, tuple[int, ...]]):
    """``fn(params, pools, tokens (chunk,), start, table_rows, last_ix) ->
    (last_logits, pools)``: one fixed-width prompt chunk of one slot; the
    pools are donated."""
    where = _group_of(layers)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_chunk(params, pools, tokens, start, table_rows, last_ix):
        pools = dict(pools)
        positions = start + jnp.arange(chunk, dtype=jnp.int32)
        x = family.embed(params, tokens, cfg)
        for layer in range(cfg.num_layers):
            name, li = where[layer]
            row = table_rows[name]

            def attend(q, k, v, name=name, li=li, row=row, layer=layer):
                k_pool, v_pool = pools[name]
                with jax.named_scope("kv_write"):
                    idx = row[positions // block_size] * block_size \
                        + positions % block_size
                    k_pool = k_pool.at[li, idx].set(k.reshape(chunk, -1))
                    v_pool = v_pool.at[li, idx].set(v.reshape(chunk, -1))
                pools[name] = (k_pool, v_pool)
                return paged_chunk_attention(
                    q, start, k_pool, v_pool, row, layer=li,
                    block_size=block_size, window=cfg.window_of(layer))

            with jax.named_scope(f"h{layer}"):
                x, _ = family.block(params[f"h{layer}"], x, cfg, layer,
                                   positions, attend)
        last = jax.lax.dynamic_slice_in_dim(x, last_ix, 1, 0)
        return family.head(params, last, cfg)[0], pools

    return prefill_chunk


def make_family_decode_fn(family, cfg, *, block_size: int,
                          layers: dict[str, tuple[int, ...]]):
    """``fn(params, pools, tokens (slots,), tables, seq_lens, active) ->
    (logits, pools, routed)``: one token for every slot.  ``routed`` is
    int32 ``(3,)``: over the expert layers, the routed (token, choice)
    pairs that landed on held experts (sum), the held experts hit (sum)
    and the largest load of one expert (max) — active slots only."""
    where = _group_of(layers)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, pools, tokens, tables, seq_lens, active):
        pools = dict(pools)
        bs = block_size
        positions = seq_lens.astype(jnp.int32)
        attend_lens = jnp.where(active, positions + 1, 1)
        x = family.embed(params, tokens, cfg)
        routed = []
        for layer in range(cfg.num_layers):
            name, li = where[layer]

            def attend(q, k, v, name=name, li=li, layer=layer):
                k_pool, v_pool = pools[name]
                with jax.named_scope("kv_write"):
                    blk = jnp.take_along_axis(
                        tables[name], (positions // bs)[:, None], axis=1
                    )[:, 0]
                    idx = jnp.where(active, blk * bs + positions % bs,
                                    k_pool.shape[1] - bs)   # else: scratch
                    k_pool = k_pool.at[li, idx].set(
                        k.reshape(k.shape[0], -1))
                    v_pool = v_pool.at[li, idx].set(
                        v.reshape(v.shape[0], -1))
                pools[name] = (k_pool, v_pool)
                return paged_window_decode_attention(
                    q, k_pool, v_pool, tables[name], attend_lens, layer=li,
                    block_size=bs, window=cfg.window_of(layer),
                    impl=cfg.kernel_impl)

            with jax.named_scope(f"h{layer}"):
                x, counters = family.block(
                    params[f"h{layer}"], x, cfg, layer, positions, attend,
                    token_mask=active)
            if counters is not None:
                routed.append(counters)
        if routed:
            stat = jnp.stack([
                sum(c["pairs"] for c in routed),
                sum(c["experts_hit"] for c in routed),
                functools.reduce(jnp.maximum,
                                 [c["max_load"] for c in routed]),
            ]).astype(jnp.int32)
        else:
            stat = jnp.zeros((3,), jnp.int32)
        return family.head(params, x, cfg), pools, stat

    return decode


# ---------------------------------------------------------------------------
# What the engine drives: one set of programs a configuration
# ---------------------------------------------------------------------------
#
# The engine holds layer groups (``serve.kv_cache.GroupedKVCache``) and a
# set of programs over them, and knows no family: ``pools`` is ``{group:
# (k_pool, v_pool)}``, ``tables`` / ``table_rows`` ``{group: page table /
# one slot's row}``.  A set of programs answers
#
# - ``prefill(params, pools, tokens (chunk,) on the host, start, table_rows,
#   last_ix, slot) -> (last_logits, pools)``: one prompt chunk of ``slot``;
# - ``decode(params, pools, tokens (slots,), tables, seq_lens, active) ->
#   (logits, pools, routed)``: one token a slot; ``routed`` the expert
#   layers' counters of the iteration, None where there are none;
# - ``fused(draft)``: the sampled (``draft`` = 0) or verify program,
#   ``fn(params, pools, *feeds) -> (packed, next_feed, pools)``, or a
#   ``ValueError`` that says it is not implemented;
# - ``forget(slot)``: the slot has a new tenant;
# - ``decode_attention``: the formulation the decode programs in use attend
#   the pages with, ``"paged_attn"`` (the kernel that reads only the blocks
#   a slot holds) or ``"plain"`` (the gather of every table column): the
#   fallback is silent, so the engine reports it (``Engine.state()``).


class GPTPrograms:
    """The GPT-2 programs above over their one full group.  The dense
    prefill cache belongs here: it is re-materialised from the slot's pool
    blocks (:func:`make_gather_cache_fn`) unless it already holds exactly
    that slot's K/V through the chunk's start — which makes chunks
    stateless and freely interleavable across requests."""

    def __init__(self, cfg: GPTConfig, *, chunk: int, block_size: int,
                 layers: dict[str, tuple[int, ...]]):
        (self.group,) = layers
        self.cfg, self.chunk, self.block_size = cfg, chunk, block_size
        self._prefill = make_prefill_fn(cfg, chunk=chunk,
                                        block_size=block_size)
        self._decode = make_decode_fn(cfg, block_size=block_size)
        self.decode_attention = paged_decode_formulation(
            cfg.num_heads, cfg.kv_heads, cfg.hidden_size // cfg.num_heads,
            block_size, cfg.attn_impl)
        self._gather = make_gather_cache_fn(cfg, block_size=block_size)
        self._cache = make_prefill_cache(cfg)
        #: (slot, pos): the dense cache holds that slot's K/V for
        #: positions [0, pos).  None = unknown/stale.
        self._cache_state: tuple[int, int] | None = None

    def forget(self, slot: int) -> None:
        """Never alias the dense cache across a slot's tenants."""
        if self._cache_state is not None and self._cache_state[0] == slot:
            self._cache_state = None

    def prefill(self, params, pools, tokens, start: int, table_rows,
                last_ix: int, slot: int):
        k_pool, v_pool = pools[self.group]
        table_row = table_rows[self.group]
        if self._cache_state != (slot, start):
            if start:
                with obs_tracing.span("engine.gather"):
                    self._cache = self._gather(
                        k_pool, v_pool, self._cache, table_row,
                        jnp.int32(start))
            else:
                self._cache = reset_cache_index(self._cache)
        last_logits, self._cache, k_pool, v_pool = self._prefill(
            params, k_pool, v_pool, self._cache, jnp.asarray(tokens[None]),
            jnp.int32(start), table_row, jnp.int32(last_ix))
        self._cache_state = (slot, start + self.chunk)
        return last_logits, {self.group: (k_pool, v_pool)}

    def decode(self, params, pools, tokens, tables, seq_lens, active):
        logits, k_pool, v_pool = self._decode(
            params, *pools[self.group], tokens, tables[self.group],
            seq_lens, active)
        return logits, {self.group: (k_pool, v_pool)}, None

    def fused(self, draft: int):
        fn = make_fused_decode_fn(self.cfg, block_size=self.block_size,
                                  draft=draft)
        group = self.group
        # the engine decodes through these from now on, and they attend
        # with ``paged_verify_attention``
        self.decode_attention = "plain"

        def fused(params, pools, tokens, draft_lens, tables, *feeds):
            packed, next_feed, k_pool, v_pool = fn(
                params, *pools[group], tokens, draft_lens, tables[group],
                *feeds)
            return packed, next_feed, {group: (k_pool, v_pool)}
        return fused


class BlockPrograms:
    """The programs of a family given by its layer functions
    (:func:`make_family_prefill_fn`, :func:`make_family_decode_fn`): no
    dense cache, so nothing to forget."""

    def __init__(self, family, cfg, *, chunk: int, block_size: int,
                 layers: dict[str, tuple[int, ...]]):
        self.family = family.__name__.rsplit(".", 1)[-1]
        self._prefill = make_family_prefill_fn(
            family, cfg, chunk=chunk, block_size=block_size, layers=layers)
        self.decode = make_family_decode_fn(
            family, cfg, block_size=block_size, layers=layers)
        self.decode_attention = paged_decode_formulation(
            cfg.num_heads, cfg.kv_heads, cfg.head_dim, block_size,
            cfg.kernel_impl)

    def forget(self, slot: int) -> None:
        pass

    def prefill(self, params, pools, tokens, start: int, table_rows,
                last_ix: int, slot: int):
        return self._prefill(params, pools, jnp.asarray(tokens),
                             jnp.int32(start), table_rows,
                             jnp.int32(last_ix))

    def fused(self, draft: int):
        raise ValueError(
            f"{'speculate' if draft else 'fused_sampling'} is not "
            f"implemented for the {self.family} family yet (the fused and "
            "verify programs are GPT-2's): serve it without")


#: configuration class -> its programs: the one place that tells the
#: families apart
PROGRAMS = {
    GPTConfig: GPTPrograms,
    afmoe.AfmoeConfig: functools.partial(BlockPrograms, afmoe),
}


def make_programs(cfg, *, chunk: int, block_size: int,
                  layers: dict[str, tuple[int, ...]]):
    """The programs of ``cfg``'s family over the layer groups ``layers``."""
    for kind, make in PROGRAMS.items():
        if isinstance(cfg, kind):
            return make(cfg, chunk=chunk, block_size=block_size,
                        layers=layers)
    raise ValueError(f"no serving programs for a {type(cfg).__name__}")
