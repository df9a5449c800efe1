"""Paged KV cache: refcounted block pool + prefix index + page tables.

The dense cache of the reference (``models.generate``) pins ``max_seq``
tokens of K/V per batch slot for the whole request lifetime — a 16-token
reply in a slot sized for 2048 tokens wastes 99% of the slot's HBM.  This
module is the vLLM-style fix, built on the same sequence-chunking idiom as
``ops/blockwise.py``: K/V live in a pool of fixed-size **blocks** shared
by every slot, each slot's **page table** row names the blocks holding
its sequence, and a refcounted **allocator** hands blocks out per request
— so memory held is proportional to tokens actually resident, and a
finished sequence's blocks return to the pool the moment it is evicted.

**Prefix caching** (ISSUE 14): identical prompt prefixes — system
prompts, few-shot headers — are the dominant redundancy in request
traffic, and re-prefilling them re-computes and re-stores the same K/V
every request.  The pool therefore keeps a **prefix index**: every FULL
token-aligned block of a completed prompt is registered under a chained
content hash (``h_i = hash((h_{i-1}, block_i_tokens))``, so a block's
hash commits to the whole prefix up to it, not just its own tokens).
Admission looks up the longest indexed chain for the new prompt and maps
those blocks into the request's page table at ``refcount + 1`` — prefill
then only runs the uncached tail.  The match is capped at
``(prompt_len - 1) // block_size`` blocks so at least one prompt token
always runs through prefill (the last token's logits seed sampling).

Block states (``BlockAllocator``):

- **free** — on the free list, contents meaningless;
- **active** — refcount >= 1: mapped by that many slot page tables.  A
  block with refcount > 1 is *shared* and must never be written in place
  (copy-on-write below);
- **cached** — refcount 0 but registered in the prefix index: the K/V
  stay warm for future lookups.  Cached blocks form an LRU; ``alloc``
  evicts from it only under pressure (dropping the index entry), and a
  **mapped block is never evicted** — eviction only ever sees
  refcount-0 blocks.

``release`` therefore *decrements* instead of freeing: a registered
block outlives its first request as a cached block, an unregistered one
goes straight back to the free list.

**Copy-on-write**: :meth:`PagedKVCache.ensure_writable` guards every
in-place write position — a shared target block is copied into a fresh
block first (pool-level device copy) and the writer's table re-pointed;
a registered-but-exclusive target is unregistered (the write would
invalidate the indexed content).  In the engine's steady state neither
fires: only FULL prompt blocks are ever registered/shared and all
appends land past the prompt — but the guard is what turns a future
scheduler bug into a local copy instead of silent cross-request cache
corruption.  One deliberate exception: a prefill chunk that straddles
the cached-prefix boundary re-writes the tail of the shared prefix with
**bitwise-identical** K/V (same tokens, same positions, same compiled
program — and causal masking makes positions ``< p`` independent of the
differing suffix), which is benign and keeps the chunk grid anchored at
zero so the admission footprint math is unchanged.

Device-side state is functional (jnp arrays threaded through the
compiled serving programs — see ``serve.model``); this module owns the
HOST-side bookkeeping: the allocator states, the prefix index, the
numpy page tables and sequence lengths the engine mutates between
steps.  Single-writer by design: only the engine loop thread touches a
``PagedKVCache`` (the HTTP threads go through the engine's queue), so
there are no locks here.

Stored form (:func:`pool_shape`): ``(num_layers, (num_blocks + 1) *
block_size, row width)`` per pool — **token rows**, one stacked array for
all layers so the decode program indexes layers without a pytree of leaves.
A group states the rows it stores a token a layer (``rows``, the model's
``cfg.cache_rows``, or its entry for the group where the groups' rows
differ: :func:`group_rows`; one pool a width of ``rows.widths``): the K/V
pair, two pools of ``kv_heads * head_dim`` with the heads folded into the
minor dimension (the V pool ``kv_heads * value_dim`` where values are
narrower than keys, and a K head wider than a lane tile split at the tile:
``ops.attention.lay_heads``); or
one pool of latent rows ``[c_kv | k_rope]`` that every head shares
(``models.joyai``: 512 + 64 values = 1,152 bytes in bf16, stored 640 wide —
five lane tiles, the last 64 lanes zero — because the TPU lays a 576-wide
bf16 array out 640 wide whatever its shape says and Mosaic slices no part of
a tile; there is no V pool).  Physical block ``b`` is rows ``[b *
block_size, (b + 1) * block_size)``.  This is the one form every program
that takes a pool computes in (``serve.model``'s prefill chunk, decode and
fused decode; the block copy below), and none of them reshapes it: a write
scatters ``(tokens, row width)`` rows at ``block * block_size + offset``,
the page-table walk gathers whole blocks of rows, and the heads are split
only on what was gathered.

Why rows and not ``(..., block_size, kv_heads, head_dim)``: on the TPU an
array lives in (8, 128) tiles of its two minor dimensions, a 64-wide
``head_dim`` would fill half of each tile, and so the runtime's layout for
that 5-D bf16 pool made the *block* dimension minor-most
(``{1,4,3,2,0:T(8,128)(2,1)}``).  The programs scatter and gather token
rows, so each converted the whole donated pool to row-major on entry and
back on exit — 36.6 ms of a 121 ms decode iteration and 39 of a prefill
chunk's 40 ms on a v5e with a 3.2 GB pool, and a pool-sized temporary
that kept a pool above a third of HBM from compiling at all (PERF.md §5,
PR 25).  ``kv_heads * head_dim`` is a multiple of 128 for every preset, so
rows need no padding, the resident layout is the computing layout, and the
donated input is the output's buffer.  Any block size and dtype is
correct; a block that is a multiple of the dtype's sublane tile (16 rows
for bf16) is a whole number of tiles, which is what keeps the (block,
offset) split of the row dimension free.  ``serve.pool_check`` reads all
of this off the compiled programs.

The extra physical block at index ``num_blocks`` is the **scratch block**:
inactive slots' writes land there (static-shape decode steps always write
``max_slots`` tokens), and unallocated page-table entries point at it, so
no masking is needed on the write path and garbage reads are confined to
slots whose outputs the engine discards anyway.
"""

from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


class OutOfBlocksError(RuntimeError):
    """Raised on ``free``/refcount/table misuse; ``alloc`` returns None
    instead."""


def pool_shape(num_layers: int, num_blocks: int, block_size: int,
               width: int) -> tuple[int, int, int]:
    """Shape of one pool of ``num_blocks`` blocks plus the scratch block:
    token rows ``width`` wide (module docstring, "Stored form").
    ``num_layers`` is the group's layer slots: its layers, times the passes
    of a stack that is run several times (:func:`layer_groups`)."""
    return (num_layers, (num_blocks + 1) * block_size, width)


@functools.lru_cache(maxsize=None)
def _copy_block_fn(block_size: int):
    """Compiled pool-level block copy (the copy-on-write program): the
    ``block_size`` rows of block ``src`` over those of ``dst``, in every
    layer, in place in the donated pools.

    Compiled lazily on the first CoW — steady-state serving with
    full-block prefix sharing never triggers it (see module docstring)."""

    def copy_rows(pool, src, dst):
        rows = jax.lax.dynamic_slice_in_dim(pool, src * block_size,
                                            block_size, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(pool, rows,
                                                   dst * block_size, axis=1)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def copy_block(pools, src, dst):
        return tuple(copy_rows(pool, src, dst) for pool in pools)

    return copy_block


class BlockAllocator:
    """Refcounted allocator over ``num_blocks`` uniform physical blocks.

    ``alloc(n)`` is all-or-nothing (a request is admitted only when its
    whole worst-case footprint fits — no mid-flight OOM, see
    ``serve.engine``) and may evict LRU *cached* (refcount-0, registered)
    blocks to satisfy the grant — a mapped (refcount >= 1) block is never
    evicted.  ``free``/:meth:`decref` decrement and reject double-frees
    loudly (an over-decrement means two slots think they own a block's
    last reference — silent cache corruption).  Blocks are uniform so
    there is no external fragmentation; the waste mode is *internal*
    (allocated-but-unused tokens inside a request's last block and its
    not-yet-generated tail), reported by :meth:`PagedKVCache.stats`.
    """

    def __init__(self, num_blocks: int, on_evict=None):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, -1, -1))  # pop() -> block 0 first
        self._ref: dict[int, int] = {}
        #: refcount-0 registered blocks, insertion order = LRU order.
        self._cached: collections.OrderedDict[int, None] = \
            collections.OrderedDict()
        self._registered: set[int] = set()
        self._on_evict = on_evict
        self.evictions = 0

    # -- state census --------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Blocks with refcount >= 1 (mapped by some page table)."""
        return len(self._ref)

    @property
    def cached_blocks(self) -> int:
        """Refcount-0 blocks kept warm for the prefix index (evictable)."""
        return len(self._cached)

    @property
    def allocatable_blocks(self) -> int:
        """Blocks ``alloc`` could grant right now (free + evictable)."""
        return len(self._free) + len(self._cached)

    @property
    def total_refs(self) -> int:
        """Sum of refcounts (> used_blocks means prefix sharing is live)."""
        return sum(self._ref.values())

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def is_registered(self, block: int) -> bool:
        return block in self._registered

    # -- grant / return ------------------------------------------------------

    def alloc(self, n: int) -> list[int] | None:
        """``n`` physical block ids at refcount 1, or None when fewer than
        ``n`` are grantable (all-or-nothing: never a partial grant).
        Evicts LRU cached blocks only as needed — never a mapped block."""
        if n < 0:
            raise ValueError(f"alloc({n}) is negative")
        if n > self.allocatable_blocks:
            return None
        while len(self._free) < n:
            self._evict_lru()
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        return blocks

    def incref(self, block: int) -> None:
        """Map a block into one more page table (prefix reuse).  A cached
        block is reactivated (leaves the eviction LRU)."""
        if block in self._ref:
            self._ref[block] += 1
        elif block in self._cached:
            del self._cached[block]
            self._ref[block] = 1
        else:
            raise OutOfBlocksError(
                f"incref({block}): block is neither active nor cached"
            )

    def decref(self, block: int) -> None:
        """Drop one reference.  At refcount 0 a registered block parks in
        the cached LRU (contents stay lookup-able); an unregistered one
        returns to the free list."""
        if block not in self._ref:
            raise OutOfBlocksError(
                f"decref({block}): block is not allocated (double free or "
                "foreign id)"
            )
        self._ref[block] -= 1
        if self._ref[block]:
            return
        del self._ref[block]
        if block in self._registered:
            self._cached[block] = None  # MRU end of the eviction LRU
        else:
            self._free.append(block)

    def free(self, blocks: list[int]) -> None:
        """Drop one reference per block (the release path)."""
        for b in blocks:
            self.decref(b)

    # -- prefix-index hooks --------------------------------------------------

    def register(self, block: int) -> None:
        """Mark an active block as holding indexed prefix content: when
        its refcount drops to 0 it becomes cached instead of free."""
        if block not in self._ref:
            raise OutOfBlocksError(
                f"register({block}): block is not active"
            )
        self._registered.add(block)

    def unregister(self, block: int) -> None:
        """Forget a block's indexed status (a write is about to change
        its contents, or the index dropped it)."""
        self._registered.discard(block)
        if block in self._cached:
            # no references AND no longer indexed: nothing can reach it
            del self._cached[block]
            self._free.append(block)

    def _evict_lru(self) -> None:
        block, _ = self._cached.popitem(last=False)
        self._registered.discard(block)
        self.evictions += 1
        if self._on_evict is not None:
            self._on_evict(block)
        self._free.append(block)


@dataclasses.dataclass
class SlotPages:
    """One slot's page-table bookkeeping (host side)."""

    blocks: list[int]          # physical block ids, logical order
    capacity_tokens: int       # positions whose rows the blocks hold
    prefix_tokens: int = 0     # tokens mapped from the prefix cache at admit


class PagedKVCache:
    """Block-pool KV storage for ``max_slots`` concurrent sequences.

    Device arrays (``pools``: one array a stored row, each
    :func:`pool_shape`) are created once and threaded functionally through
    the serving programs, which donate them and update them in place; the
    engine assigns the updated arrays back after every call.  Host state
    (page tables, lengths, the prefix index) advances in lockstep on the
    engine thread.

    ``rows`` states what the group stores a token a layer (``ops.attention``:
    a ``KVRows`` or a ``LatentRows``, the model's ``cfg.cache_rows``): one
    pool a width of ``rows.widths``, of which ``rows.values`` are stored
    values (the rest zeros that pad a row to whole lane tiles), and whether
    every head shares the one row (``rows.shared_row``).
    """

    def __init__(self, *, num_layers: int, rows, max_slots: int,
                 num_blocks: int, block_size: int, max_context: int,
                 dtype=jnp.float32):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if max_context % block_size:
            raise ValueError(
                f"max_context={max_context} must be a multiple of "
                f"block_size={block_size}"
            )
        self.block_size = block_size
        self.max_slots = max_slots
        self.max_context = max_context
        #: tokens that make one row: 1, or the chunk a summary row stands for
        self.tokens_per_row = getattr(rows, "tokens_per_row", 1)
        self.blocks_per_slot = self.blocks_for(max_context)
        self.scratch_block = num_blocks  # reserved physical block
        self.allocator = BlockAllocator(num_blocks, on_evict=self._on_evict)
        self.rows = rows
        self.pools = tuple(
            jnp.zeros(pool_shape(num_layers, num_blocks, block_size, width),
                      dtype) for width in rows.widths)
        # Unallocated entries point at the scratch block (always a legal
        # physical index; reads through it are masked by seq_lens).
        self.block_tables = np.full(
            (max_slots, self.blocks_per_slot), self.scratch_block, np.int32
        )
        #: bumped on every page-table mutation (admit/release/CoW
        #: repoint) so the engine can cache the device copy of
        #: ``block_tables`` across the many decode steps between
        #: admissions instead of re-shipping it per step.
        self.tables_version = 0
        #: K/V positions written so far, a slot
        self.seq_lens = np.zeros((max_slots,), np.int32)
        self.pages: list[SlotPages | None] = [None] * max_slots
        #: ``pages[slot].capacity_tokens`` as an array (-1: no pages), so
        #: that a whole batch's writes are bounded in one comparison
        self._capacity = np.full((max_slots,), -1, np.int64)
        # prefix index: chained content hash -> (physical block, the
        # block's token tuple), + reverse map for eviction.  The tokens
        # are stored so every lookup VERIFIES them — hash() is 64-bit
        # and non-cryptographic, and an unverified chain collision would
        # silently map another prompt's K/V into a new request (the
        # vLLM prefix-cache CVE class).  Verifying each matched block's
        # own tokens suffices: a wrong mapping would need a colliding
        # parent hash at some earlier step WITH equal tokens at every
        # step up to it — and token-equal at every step IS the same
        # prefix.
        self._hash_to_block: dict[int, tuple[int, tuple[int, ...]]] = {}
        self._block_hash: dict[int, int] = {}
        # admission-time accounting (the engine mirrors these into the
        # obs registry; stats() derives hit rate / occupancy from them)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_cached_tokens = 0
        self.cow_copies = 0

    @property
    def row_bytes(self) -> int:
        """Bytes of values the group stores a token a layer, over its pools
        (lane padding not counted: ``rows.widths`` has it)."""
        return sum(self.rows.values) * self.pools[0].dtype.itemsize

    @property
    def census(self) -> dict:
        """What the group stores, for the start-up row and ``stats()``: the
        form's name, its K/V heads (None where every head shares the one
        row), and the bytes a token a layer, of values and as the pools are
        laid out (lane padding with it)."""
        size = self.pools[0].dtype.itemsize
        return {"form": type(self.rows).__name__,
                "kv_heads": getattr(self.rows, "kv_heads", None),
                "row_bytes": self.row_bytes,
                "row_bytes_laid_out": sum(self.rows.widths) * size}

    def _on_evict(self, block: int) -> None:
        h = self._block_hash.pop(block, None)
        if h is not None:
            self._hash_to_block.pop(h, None)

    # -- prefix index (engine thread only) -----------------------------------

    def _chained_hashes(self, tokens):
        """(chained hash, block token tuple) per FULL block of
        ``tokens`` — each hash commits to the entire prefix through its
        block."""
        h = 0
        bs = self.block_size
        for i in range(len(tokens) // bs):
            tok = tuple(tokens[i * bs:(i + 1) * bs])
            h = hash((h, tok))
            yield h, tok

    def lookup_prefix(self, tokens) -> list[int]:
        """Longest indexed chain of full blocks matching ``tokens``,
        capped so at least one prompt token remains for prefill (the
        final token's logits must be computed to sample from).  Every
        matched entry's stored tokens are compared, so a hash collision
        degrades to a cache miss, never to serving another prompt's
        K/V.  Pure lookup: no state change, no refcounts taken."""
        limit = (len(tokens) - 1) // self.block_size
        blocks: list[int] = []
        for i, (h, tok) in enumerate(self._chained_hashes(tokens)):
            if i >= limit:
                break
            entry = self._hash_to_block.get(h)
            if entry is None or entry[1] != tok:
                break
            blocks.append(entry[0])
        return blocks

    def register_prefix(self, slot: int, tokens) -> int:
        """Index every FULL block of a slot's freshly prefilled prompt.
        First writer wins: a hash already indexed (necessarily the block
        this slot mapped at admission, or a concurrent identical prompt
        that prefilled its own copy) keeps its existing entry.  Returns
        the number of newly indexed blocks."""
        pages = self.pages[slot]
        if pages is None:
            raise OutOfBlocksError(f"slot {slot} has no pages")
        added = 0
        for i, (h, tok) in enumerate(self._chained_hashes(tokens)):
            b = pages.blocks[i]
            if h in self._hash_to_block:
                continue
            self._hash_to_block[h] = (b, tok)
            self._block_hash[b] = h
            self.allocator.register(b)
            added += 1
        return added

    # -- admission / eviction (engine thread only) ---------------------------

    def blocks_for(self, tokens: int) -> int:
        """Physical blocks needed to hold the rows of ``tokens`` positions:
        a row a token, or one a whole ``tokens_per_row`` of them."""
        return -(-(tokens // self.tokens_per_row) // self.block_size)

    def _capacity_tokens(self, blocks: int) -> int:
        """Positions ``blocks`` blocks hold the rows of."""
        per = self.tokens_per_row
        return (blocks * self.block_size + 1) * per - 1

    def rows_attended(self, positions):
        """Rows of this group a query at each of ``positions`` attends."""
        seen = getattr(self.rows, "visible_rows", None)
        return positions + 1 if seen is None else seen(positions)

    def span_attended(self, positions):
        """``(first, end)``: the rows ``[first, end)`` of a slot's table row
        a query at each of ``positions`` attends."""
        end = self.rows_attended(positions)
        return np.zeros_like(end), end

    def reservation(self, tokens: int) -> int:
        """Blocks admission grants a slot of ``tokens`` positions."""
        return self.blocks_for(tokens)

    def prepare_write(self, slot: int, end: int) -> None:
        """Every block of the reservation is mapped at admission: nothing
        to do before a write (a window group maps as it goes)."""

    def admit(self, slot: int, tokens: int, prompt=None) -> SlotPages | None:
        """Reserve a slot's worst-case footprint (``tokens`` positions).

        With ``prompt`` (the token list), the longest indexed prefix is
        mapped into the page table at refcount+1 and only the remaining
        blocks are freshly allocated — the all-or-nothing contract then
        covers the worst-case footprint MINUS the mapped prefix.  Returns
        the slot's :class:`SlotPages` (``prefix_tokens`` tells how much
        was mapped) or None under pool pressure — a failed grant rolls
        the prefix mappings back.  The slot must be empty (engine
        invariant)."""
        if self.pages[slot] is not None:
            raise OutOfBlocksError(f"slot {slot} is already occupied")
        if tokens > self.max_context:
            raise ValueError(
                f"{tokens} tokens exceed max_context={self.max_context}"
            )
        prefix_blocks: list[int] = []
        if prompt is not None:
            prefix_blocks = self.lookup_prefix(prompt)
        n = self.blocks_for(tokens)
        for b in prefix_blocks:
            self.allocator.incref(b)  # pinned: alloc's eviction can't touch
        fresh = self.allocator.alloc(n - len(prefix_blocks))
        if fresh is None:
            for b in prefix_blocks:
                self.allocator.decref(b)
            return None
        # counted on SUCCESS only — a pool-pressure head retries admission
        # every scheduler iteration and must not inflate the denominator
        prefix_tokens = len(prefix_blocks) * self.block_size
        if prompt is not None:
            self.prefix_lookups += 1
        if prefix_blocks:
            self.prefix_hits += 1
            self.prefix_cached_tokens += prefix_tokens
        blocks = prefix_blocks + fresh
        pages = SlotPages(blocks, self._capacity_tokens(n),
                          prefix_tokens=prefix_tokens)
        self.pages[slot] = pages
        self._capacity[slot] = pages.capacity_tokens
        self.block_tables[slot, :] = self.scratch_block
        self.block_tables[slot, : len(blocks)] = blocks
        self.tables_version += 1
        self.seq_lens[slot] = prefix_tokens
        return pages

    def release(self, slot: int) -> None:
        """Drop the slot's block references (eviction path): registered
        blocks park in the cached LRU, the rest return to the pool."""
        pages = self.pages[slot]
        if pages is None:
            return
        self.allocator.free(pages.blocks)
        self.pages[slot] = None
        self._capacity[slot] = -1
        self.block_tables[slot, :] = self.scratch_block
        self.tables_version += 1
        self.seq_lens[slot] = 0

    def ensure_writable(self, slot: int, pos: int) -> str | None:
        """Copy-on-write guard for an in-place write at ``pos``.

        Returns ``"cow"`` when the target block was shared (refcount > 1)
        and has been copied into a fresh exclusive block (page table
        re-pointed), ``"unregistered"`` when it was exclusive but indexed
        (the entry is dropped — the write would invalidate the cached
        content), or None when the write was already safe.  Raises under
        pool pressure if a copy is needed but no block is grantable (the
        engine's admission contract makes that unreachable: appends land
        past the prompt, and only full prompt blocks are ever shared)."""
        pages = self.pages[slot]
        if pages is None:
            raise OutOfBlocksError(f"slot {slot} has no pages")
        li = pos // self.block_size
        if li >= len(pages.blocks):
            raise OutOfBlocksError(
                f"slot {slot}: write at {pos} exceeds reserved capacity "
                f"{pages.capacity_tokens}"
            )
        b = pages.blocks[li]
        if self.allocator.refcount(b) > 1:
            fresh = self.allocator.alloc(1)
            if fresh is None:
                raise OutOfBlocksError(
                    f"slot {slot}: copy-on-write at position {pos} needs a "
                    "block but the pool is exhausted"
                )
            dst = fresh[0]
            self.pools = _copy_block_fn(self.block_size)(
                self.pools, jnp.int32(b), jnp.int32(dst))
            self.allocator.decref(b)
            pages.blocks[li] = dst
            self.block_tables[slot, li] = dst
            self.tables_version += 1
            self.cow_copies += 1
            return "cow"
        if self.allocator.is_registered(b):
            self._on_evict(b)  # drop the index entry
            self.allocator.unregister(b)
            return "unregistered"
        return None

    def ensure_writable_range(self, slot: int, start: int, end: int) -> int:
        """Copy-on-write guard over every block a multi-token write
        ``[start, end)`` touches (the speculative verify program appends
        the committed token plus all drafts in one dispatch).  Returns
        the number of blocks that needed a CoW copy or an unregister —
        steady state 0, same as the single-position guard."""
        if end <= start:
            return 0
        fixed = 0
        bs = self.block_size
        for li in range(start // bs, (end - 1) // bs + 1):
            if self.ensure_writable(slot, li * bs) is not None:
                fixed += 1
        return fixed

    def rollback(self, slot: int, tokens: int) -> None:
        """Retreat a slot's resident-token count to ``tokens`` (rejected
        or discarded speculative drafts: the K/V past the new extent is
        dead and will be overwritten by the next append).

        Two hard rules.  (1) **Never into the mapped prefix**: positions
        below ``prefix_tokens`` are another request's cached content
        mapped refcount+1 — retreating "past" them would claim the slot
        re-owns positions it never wrote.  (2) **No block is freed**:
        the admission contract reserved the slot's whole worst-case
        footprint all-or-nothing, and handing blocks back on a retreat
        would let another admission claim them and force a mid-flight
        re-alloc (the OOM class admission control exists to prevent)
        when this slot's generation advances again.  As belt and braces
        the retreat also refuses to cross any *shared* (refcount > 1)
        block — the engine only ever speculates past the prompt, so a
        shared block inside the retreat window means scheduler state
        went inconsistent and silently continuing would corrupt the
        shared content's accounting."""
        pages = self.pages[slot]
        if pages is None:
            raise OutOfBlocksError(f"slot {slot} has no pages")
        used = int(self.seq_lens[slot])
        if tokens > used:
            raise OutOfBlocksError(
                f"slot {slot}: rollback target {tokens} exceeds resident "
                f"{used} (rollback only retreats)"
            )
        if tokens < pages.prefix_tokens:
            raise OutOfBlocksError(
                f"slot {slot}: rollback to {tokens} would retreat into the "
                f"mapped shared prefix ({pages.prefix_tokens} tokens)"
            )
        if tokens == used:
            return  # empty retreat window
        bs = self.block_size
        for li in range(tokens // bs,
                        min((used - 1) // bs + 1, len(pages.blocks))):
            if self.allocator.refcount(pages.blocks[li]) > 1:
                raise OutOfBlocksError(
                    f"slot {slot}: rollback window covers shared block "
                    f"{pages.blocks[li]} (refcount "
                    f"{self.allocator.refcount(pages.blocks[li])})"
                )
        self.seq_lens[slot] = tokens

    def note_written(self, slots, tokens) -> None:
        """Advance the resident-token counts of ``slots`` to ``tokens``
        (after a program wrote K/V): two arrays of one length — a decode
        iteration's slots in one call — or one slot and its count.  Bounded
        by the reservations so a scheduler bug trips here, not as silent
        cross-slot corruption."""
        slots, tokens = np.atleast_1d(slots), np.atleast_1d(tokens)
        over = tokens > self._capacity[slots]
        if over.any():
            slot, n = int(slots[over][0]), int(tokens[over][0])
            if self.pages[slot] is None:
                raise OutOfBlocksError(f"slot {slot} has no pages")
            raise OutOfBlocksError(
                f"slot {slot}: {n} tokens exceed reserved capacity "
                f"{self.pages[slot].capacity_tokens}"
            )
        self.seq_lens[slots] = tokens

    # -- introspection -------------------------------------------------------

    def billed_blocks(self, slot: int) -> float:
        """Refcount-weighted block footprint of one slot: each mapped
        block charged at ``1/refcount``, so a prefix block shared by N
        slots costs each of them 1/N and summing over all occupied slots
        can never exceed the pool's mapped-block count (the step log's
        ``kv_blocks_billed``).  Engine thread only, like all host-side
        page-table state."""
        pages = self.pages[slot]
        if pages is None:
            return 0.0
        if not self._block_hash:
            # a block is shared only through the prefix index, and stays
            # indexed while it is: none indexed, so each is billed whole —
            # a count, not a walk over refcounts (at 64 slots of hundreds
            # of blocks that walk was the engine's largest host cost a
            # step: PERF.md §6, PR 28)
            return float(len(pages.blocks))
        alloc = self.allocator
        return sum(1.0 / alloc.refcount(b) for b in pages.blocks)

    def stats(self) -> dict:
        """Pool occupancy, internal fragmentation, and prefix-cache
        occupancy/hit-rate (for ``GET /generatez``, the registry gauges,
        and the engine's metrics.jsonl rows)."""
        used = [p for p in self.pages if p is not None]
        allocated_tokens = sum(p.capacity_tokens for p in used)
        used_tokens = int(self.seq_lens[self._capacity >= 0].sum())
        alloc = self.allocator
        return {
            "block_size": self.block_size,
            "blocks_total": alloc.num_blocks,
            "blocks_free": alloc.free_blocks,
            "blocks_used": alloc.used_blocks,
            "blocks_cached": alloc.cached_blocks,
            "block_refs": alloc.total_refs,
            "slots_occupied": len(used),
            "allocated_tokens": allocated_tokens,
            "resident_tokens": used_tokens,
            # 0 = every allocated token holds real K/V; 1 = all waste.
            "fragmentation": (
                1.0 - used_tokens / allocated_tokens if allocated_tokens
                else 0.0
            ),
            # prefix cache: share of the pool holding indexed content
            # (mapped-shared OR parked cached), and the admission hit rate
            "prefix_blocks_indexed": len(self._hash_to_block),
            "prefix_occupancy": len(self._hash_to_block) / alloc.num_blocks,
            "prefix_lookups": self.prefix_lookups,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": (
                self.prefix_hits / self.prefix_lookups
                if self.prefix_lookups else 0.0
            ),
            "prefix_cached_tokens": self.prefix_cached_tokens,
            "prefix_evictions": alloc.evictions,
            "cow_copies": self.cow_copies,
        }


# ---------------------------------------------------------------------------
# Layers in groups by attention kind
# ---------------------------------------------------------------------------
#
# One pool and one page table for all layers makes every layer hold every
# token a slot has: a window layer, which never again reads a key more than
# ``window`` behind the newest, would pin it all the same.  A model whose
# layers are of two kinds (``models.afmoe``: three window layers to a full
# one) gets two GROUPS, each a pool of its own layers in the row form above
# with its own allocator and page table:
#
# - a *full* group is a :class:`PagedKVCache` as it stands;
# - a *window* group (:class:`WindowKVGroup`) reserves, at admission, only
#   what a slot can hold at one time — ``window`` + one prefill chunk + one
#   block, or the whole footprint if that is smaller — maps a logical block
#   when a write first reaches it, and when a block lies wholly behind
#   ``next position - window`` un-maps it (its table entry goes back to the
#   scratch block) and re-uses it for the slot's next block: a ring, by way
#   of the table.  The reservation is all-or-nothing like the full group's,
#   so there is still no mid-flight out-of-blocks;
# - a *state* group (:class:`StateGroup`) holds the layers that keep a
#   fixed-size state a slot and nothing a token (``models.jamba``'s Mamba
#   layers, ``models.lfm2``'s conv layers): arrays ``(layers, slots, ...)``,
#   as many as the family's state form lists, no pages, no allocator.
#
# A layer may live in TWO groups (``cfg.groups_of(layer)``; ``models.evabyte``:
# every layer keeps token rows in a window group AND chunk summaries in the
# full group), and a paged group's rows may advance once a ``tokens_per_row``
# tokens (``rows.tokens_per_row``: a summary row stands for a chunk of 16):
# its lengths, ``blocks_for``, admission and ``note_written`` then count rows,
# not tokens, under the one length a slot that all groups share, and its page
# table has a column a block of rows.  A window group whose rows say
# ``tumbling`` keeps ``[w i, w (i + 1))`` and not the last ``w``: ``keep_from =
# tokens // window * window``, a closed window's blocks all go at once, and —
# the prefill chunk grid lying on the window grid, which
# :func:`make_grouped_cache` insists on — nothing is written ahead, so the
# ring is one window + one block and is overwritten in place.  Such a cache keeps
# nothing of an earlier position: ``rollback`` / ``register_prefix`` raise, as
# over a state group.
#
# :class:`GroupedKVCache` admits against all groups at once and otherwise
# answers the engine as one cache: it is the only cache the engine holds.  A
# GPT-2 configuration is one full group — the :class:`PagedKVCache` the
# engine always had, compiled to the programs it always had — and a cache of
# one group shares prompt prefixes through that group's index.


class WindowKVGroup(PagedKVCache):
    """A layer group whose layers attend ``window`` keys back at most."""

    def __init__(self, *, window: int, write_ahead: int, **kw):
        super().__init__(**kw)
        self.window = window
        #: whether windows tumble (``[w i, w (i + 1))``: a closed window's
        #: rows are read by no one, so all its blocks go at once and the
        #: ring is one window, overwritten in place) or slide
        self.tumbling = getattr(self.rows, "tumbling", False)
        if self.tumbling and window % self.block_size:
            raise ValueError(
                f"a tumbling window of {window} must be whole blocks of "
                f"{self.block_size}: a closed window's blocks go whole")
        self.windows_closed = 0
        #: most tokens one program writes past the resident ones (a
        #: prefill chunk), which the ring must hold beside the window
        self.write_ahead = write_ahead
        self._stock: list[list[int]] = [[] for _ in range(self.max_slots)]
        #: per slot: first logical block still mapped, and the first not
        #: mapped yet (a slot's mapped blocks are [first, next))
        self._first = np.zeros((self.max_slots,), np.int64)
        self._next = np.zeros((self.max_slots,), np.int64)
        self.blocks_recycled = 0

    def reservation(self, tokens: int) -> int:
        """Blocks a slot of ``tokens`` positions holds at one time."""
        ring = self.blocks_for(self.window + self.write_ahead) + 1
        return min(self.blocks_for(tokens), ring)

    def admit(self, slot: int, tokens: int, prompt=None):
        if self.pages[slot] is not None:
            raise OutOfBlocksError(f"slot {slot} is already occupied")
        if tokens > self.max_context:
            raise ValueError(
                f"{tokens} tokens exceed max_context={self.max_context}")
        blocks = self.allocator.alloc(self.reservation(tokens))
        if blocks is None:
            return None
        pages = SlotPages(blocks,
                          self._capacity_tokens(self.blocks_for(tokens)))
        self.pages[slot] = pages
        self._capacity[slot] = pages.capacity_tokens
        self._stock[slot] = list(reversed(blocks))
        self._first[slot] = self._next[slot] = 0
        self.block_tables[slot, :] = self.scratch_block
        self.tables_version += 1
        self.seq_lens[slot] = 0
        return pages

    def release(self, slot: int) -> None:
        super().release(slot)
        self._stock[slot] = []
        self._first[slot] = self._next[slot] = 0

    def prepare_write(self, slot: int, end: int) -> None:
        """Map every logical block a write of positions ``< end`` reaches."""
        stock = self._stock[slot]
        row = self.block_tables[slot]
        for li in range(self._next[slot], self.blocks_for(end)):
            if not stock:
                raise OutOfBlocksError(
                    f"window group: slot {slot} writes block {li} with "
                    "its ring exhausted")
            row[li] = stock.pop()
            self._next[slot] = li + 1
            self.tables_version += 1

    def note_written(self, slots, tokens) -> None:
        """Advance the resident counts, then let go of every block wholly
        behind ``tokens - window``: the next query sits at ``tokens`` at
        the earliest and attends keys ``> tokens - window``.  Only the
        slots that crossed a block edge have a block to let go."""
        super().note_written(slots, tokens)
        slots, tokens = np.atleast_1d(slots), np.atleast_1d(tokens)
        if self.tumbling:
            # the next query sits in the window that holds ``tokens``
            keep_from = tokens // self.window * self.window // self.block_size
            self.windows_closed += int(
                (keep_from > self._first[slots]).sum())
        else:
            keep_from = (np.maximum(tokens - self.window + 1, 0)
                         // self.block_size)
        upto = np.minimum(keep_from, self._next[slots])
        crossed = upto > self._first[slots]
        for slot, end in zip(slots[crossed].tolist(), upto[crossed].tolist()):
            row = self.block_tables[slot]
            for li in range(int(self._first[slot]), end):
                self._stock[slot].append(int(row[li]))
                row[li] = self.scratch_block
                self.blocks_recycled += 1
                self.tables_version += 1
        self._first[slots] = np.maximum(self._first[slots], keep_from)
        self._next[slots] = np.maximum(self._next[slots], self._first[slots])

    def mapped_blocks(self, slot: int) -> int:
        return int(self._next[slot] - self._first[slot])

    def rows_attended(self, positions):
        if self.tumbling:
            return positions % self.window + 1
        return np.minimum(positions + 1, self.window)

    def span_attended(self, positions):
        end = positions + 1
        return end - self.rows_attended(positions), end


class StateGroup:
    """A layer group that keeps a fixed-size state a *slot*, whatever its
    context (``models.jamba``'s Mamba layers: the convolution tail and the
    scan state, ``ops.ssm.SSMState``; ``models.lfm2``'s conv layers: the
    tail alone, ``ops.ssm.ConvTail``): no pages and no allocator.  Its arrays
    are ``(layers, max_slots, ...)`` each, one a state array of ``rows``, every
    slot's provisioned; a slot's "page table" is the one column that names
    the slot itself, which is how a prefill chunk learns whose state it
    scans (``serve.model``).  A slot's state is live from admission to
    release; a new occupant's first chunk (``start == 0``) starts from zeros
    inside the program, so release launches nothing.  There is nothing a
    token to share, copy on write or take back: a state holds no snapshot of
    an earlier position (``GroupedKVCache.rollback`` / ``register_prefix``
    raise where a state group exists)."""

    def __init__(self, *, num_layers: int, rows, max_slots: int,
                 dtype=jnp.float32):
        self.rows = rows
        self.max_slots = max_slots
        self.pools = tuple(
            jnp.zeros((num_layers, max_slots, *shape), dt)
            for shape, dt in rows.arrays(dtype))
        self.block_tables = np.arange(max_slots, dtype=np.int32)[:, None]
        self.tables_version = 0     # the table never changes
        self.live = np.zeros((max_slots,), bool)
        #: bytes a slot over the group's layers
        self.slot_bytes = num_layers * rows.slot_bytes(dtype)

    def admit(self, slot: int) -> None:
        if self.live[slot]:
            raise OutOfBlocksError(f"slot {slot} is already occupied")
        self.live[slot] = True

    def release(self, slot: int) -> None:
        self.live[slot] = False

    def stats(self) -> dict:
        return {"slots_total": self.max_slots,
                "slots_live": int(self.live.sum()),
                "slot_bytes": self.slot_bytes,
                "bytes_total": self.slot_bytes * self.max_slots}


class GroupedKVCache:
    """Several layer groups behind the one interface the engine drives.

    ``groups`` maps a name (``"full"``, ``"window"``, ``"state"``) to a
    :class:`PagedKVCache`, a :class:`WindowKVGroup` or a
    :class:`StateGroup`; ``layers`` maps the same names to the model layers
    each holds, in pool order.  Admission is all-or-nothing over all groups;
    everything else fans out, the page bookkeeping to the groups that have
    pages (``paged``).  Prompt prefixes are shared by a cache of one group,
    through that group's index (several groups would need an index each,
    kept in step, and a state group a snapshot a block): see
    ``shares_prefixes``."""

    def __init__(self, groups: dict[str, PagedKVCache],
                 layers: dict[str, tuple[int, ...]]):
        self.groups = groups
        self.layers = layers
        #: the group of per-slot state, or None
        self.state: StateGroup | None = groups.get("state")
        #: the groups that hold pages
        self.paged = {name: g for name, g in groups.items()
                      if g is not self.state}
        first = next(iter(self.paged.values()))
        self.block_size = first.block_size
        self.max_slots = first.max_slots
        self.max_context = first.max_context
        self.seq_lens = first.seq_lens
        for g in self.paged.values():
            g.seq_lens = self.seq_lens      # one length a slot, shared
        #: the one group whose prefix index this cache shares through, or
        #: None: no block of any group is then ever shared between slots
        self._sharing = first if len(groups) == 1 else None
        #: the groups whose rows stand for several tokens each (a chunk's
        #: summary), and the rows they have gained since the start
        self._chunked = [g for g in self.paged.values()
                         if g.tokens_per_row > 1]
        self.summary_rows_written = 0
        #: layers whose group stores one row a token that every head shares,
        #: not the K/V pair
        self.latent_layers = sum(
            len(layers[name]) for name, g in self.paged.items()
            if g.rows.shared_row)
        #: the rows a query of those layers attends at most, where an
        #: indexer selects them (``ops.attention.SparseLatentRows``); None
        #: where every cached row is attended
        self.index_topk = next(
            (g.rows.topk for g in self.paged.values()
             if hasattr(g.rows, "topk")), None)

    @property
    def shares_prefixes(self) -> bool:
        return self._sharing is not None

    #: the census the engine's gauges read: the full group's if there is one
    @property
    def allocator(self) -> BlockAllocator:
        return self.paged.get(
            "full", next(iter(self.paged.values()))).allocator

    @property
    def tables_version(self) -> int:
        return sum(g.tables_version for g in self.groups.values())

    @property
    def num_blocks_total(self) -> int:
        return sum(g.allocator.num_blocks for g in self.paged.values())

    @property
    def cow_copies(self) -> int:
        return sum(g.cow_copies for g in self.paged.values())

    def pools(self) -> dict:
        """``{group: its pools}`` (``(k_pool, v_pool)``, the one pool of
        latent rows, or a state group's arrays), as the programs take them
        (and donate them: hand the updated ones back through
        ``set_pools``)."""
        return {name: g.pools for name, g in self.groups.items()}

    def set_pools(self, pools: dict) -> None:
        for name, group_pools in pools.items():
            self.groups[name].pools = tuple(group_pools)

    @property
    def row_bytes(self) -> int:
        """Bytes stored a token over all layers of all groups (a state
        group stores none a token: ``state.slot_bytes`` a slot)."""
        return sum(g.row_bytes * len(self.layers[name]) // g.tokens_per_row
                   for name, g in self.paged.items())

    @property
    def layer_slots(self) -> int:
        """Layer slots a token keeps rows in, over the paged groups: their
        layers, each once a pass of a stack that is run several times
        (:func:`layer_groups`: 192 for Ouro-2.6B's 48 layers x 4 passes)."""
        return sum(len(self.layers[name]) for name in self.paged)

    def check_fits(self, tokens: int) -> None:
        """Raise ``ValueError`` if no pool state could ever hold a request
        of ``tokens`` positions (it would wedge the FIFO head forever)."""
        for name, g in self.paged.items():
            if g.reservation(tokens) > g.allocator.num_blocks:
                raise ValueError(
                    f"request footprint {tokens} tokens needs "
                    f"{g.reservation(tokens)} KV blocks of group {name!r} "
                    f"but its pool has {g.allocator.num_blocks}")

    def admit(self, slot: int, tokens: int, prompt=None):
        """All groups' reservations or none.  ``prompt`` maps the longest
        indexed prefix (``PagedKVCache.admit``) where prefixes are shared,
        and is not looked at where they are not."""
        if self._sharing is not None:
            return self._sharing.admit(slot, tokens, prompt)
        for g in self.paged.values():
            if g.reservation(tokens) > g.allocator.allocatable_blocks:
                return None
        pages = None
        for g in self.paged.values():
            pages = g.admit(slot, tokens)
            if pages is None:       # unreachable: checked above
                raise OutOfBlocksError("group admission raced its check")
        if self.state is not None:
            self.state.admit(slot)
        return pages

    def release(self, slot: int) -> None:
        for g in self.groups.values():
            g.release(slot)

    def prepare_write(self, slot: int, end: int) -> None:
        for g in self.paged.values():
            g.prepare_write(slot, end)

    def note_written(self, slots, tokens) -> None:
        if self._chunked:
            before = self.seq_lens[np.atleast_1d(slots)]
            for g in self._chunked:
                self.summary_rows_written += int(
                    (np.atleast_1d(tokens) // g.tokens_per_row
                     - before // g.tokens_per_row).sum())
        for g in self.paged.values():
            g.note_written(slots, tokens)

    # -- prefix sharing: the one group's, and nothing to guard without ----

    def _no_state(self, what: str) -> None:
        if self.state is not None:
            raise ValueError(
                f"{what} is not implemented over a state group: a state "
                "keeps no snapshot of an earlier position to go back to or "
                "to share")
        if self._chunked:
            raise ValueError(
                f"{what} is not implemented over a group of chunk summaries "
                "beside a tumbling ring: a summary once written and a ring "
                "row once reused keep nothing of an earlier position to go "
                "back to or to share")

    def register_prefix(self, slot: int, tokens) -> int:
        self._no_state("register_prefix")
        return self._sharing.register_prefix(slot, tokens)

    def ensure_writable(self, slot: int, pos: int):
        if self._sharing is not None:
            return self._sharing.ensure_writable(slot, pos)
        return None

    def ensure_writable_range(self, slot: int, start: int, end: int) -> int:
        return self._sharing.ensure_writable_range(slot, start, end)

    def rollback(self, slot: int, tokens: int) -> None:
        self._no_state("rollback")
        self._sharing.rollback(slot, tokens)

    def billed_blocks(self, slot: int) -> float:
        """Blocks the slot holds over all groups, a shared one at its
        share (``PagedKVCache.billed_blocks``)."""
        return sum(g.billed_blocks(slot) for g in self.paged.values())

    @property
    def blocks_recycled(self) -> int:
        return sum(getattr(g, "blocks_recycled", 0)
                   for g in self.paged.values())

    @property
    def windows_closed(self) -> int:
        return sum(getattr(g, "windows_closed", 0)
                   for g in self.paged.values())

    def stats(self) -> dict:
        """The full group's census at the top level (the keys every reader
        of ``stats()`` knows), every group's under ``"groups"``."""
        per_group = {name: g.stats() for name, g in self.paged.items()}
        top = dict(per_group.get("full", next(iter(per_group.values()))))
        top["groups"] = {
            name: {"blocks_total": s["blocks_total"],
                   "blocks_used": s["blocks_used"],
                   "blocks_free": s["blocks_free"],
                   **self.paged[name].census}
            for name, s in per_group.items()}
        top["blocks_recycled"] = self.blocks_recycled
        if self.state is not None:
            top["state"] = self.state.stats()
        return top


def layer_groups(cfg) -> dict[str, tuple[int, ...]]:
    """``{group: the model layers it holds, in pool order}``, by what the
    config says a layer keeps: ``"state"`` for the layers that keep a state a
    slot (``cfg.keeps_state(layer)``, where a config has it), and of the
    others ``"full"`` and ``"window"`` by attention kind
    (``cfg.window_of(layer)``); a group with no layer is left out.  A layer
    whose rows live in several groups (``cfg.groups_of(layer)``, where a
    config has it: ``models.evabyte`` keeps token rows in a ring and chunk
    summaries in a pool that grows) is listed in each.

    A config that runs its stack of layers several times a token
    (``cfg.stack_passes``: ``models.ouro``, 4 passes) keeps the rows of every
    pass — pass ``u`` of a layer attends what *pass u* wrote for the earlier
    tokens —, so each group lists its layers once a pass, pass-major: a
    group of ``n`` layers holds ``passes * n`` **layer slots**, pass ``u`` of
    the layer at index ``i`` in slot ``u * n + i`` (48 layers x 4 passes = 192
    slots, 1,572,864 B a token at Ouro-2.6B's widths).  Everything that
    counts a group's layers — the pools' leading dimension, ``row_bytes``,
    the census, admission — counts these."""
    keeps_state = getattr(cfg, "keeps_state", lambda layer: False)
    groups_of = getattr(cfg, "groups_of", lambda layer: (
        "state" if keeps_state(layer) else
        "full" if cfg.window_of(layer) is None else "window",))
    kinds = {"full": [], "window": [], "state": []}
    for i in range(cfg.num_layers):
        for name in groups_of(i):
            kinds[name].append(i)
    passes = getattr(cfg, "stack_passes", 1)
    return {name: tuple(ls) * passes for name, ls in kinds.items() if ls}


def group_rows(cfg, name: str):
    """What group ``name`` of ``cfg``'s layers caches a token a layer
    (``ops.attention``: a ``KVRows``, ``LatentRows``, ...): ``cfg.cache_rows``
    is the one form of every group, or ``{group: form}`` where the groups'
    rows differ (MiMo-V2: 8 K/V heads a window layer, 4 a full one), or a
    form of rows in several groups that names each group's (``rows.groups``:
    ``ops.attention.EvaRows``)."""
    rows = cfg.cache_rows
    rows = getattr(rows, "groups", rows)
    return rows[name] if isinstance(rows, dict) else rows


def _check_tumbling_chunk(window: int, chunk: int, summaries) -> None:
    """Refuse, with the reason, a prefill chunk off the grid of a tumbling
    window or one that is not whole summary chunks.  On the grid a chunk
    never reaches past the window it starts in and a closed window's rows
    are overwritten in place: the ring holds nothing beside the window."""
    per = getattr(summaries, "tokens_per_row", 1)
    if window % chunk or chunk % per:
        raise ValueError(
            f"prefill_chunk={chunk} does not fit a tumbling window of "
            f"{window} summarised in chunks of {per}: a prefill chunk must "
            f"be a multiple of {per} that divides {window} or equals it, so "
            "that no chunk crosses a window's end (its queries would see two "
            "windows' rows) or completes part of a summary chunk")


def make_grouped_cache(cfg, *, max_slots: int, block_size: int,
                       max_context: int, num_blocks: dict[str, int | None],
                       write_ahead: int) -> GroupedKVCache:
    """The groups of a model (:func:`layer_groups`): a ``"full"`` and a
    ``"window"`` group, whichever exist (GPT-2 is one full group), each
    storing the rows the config names for it (:func:`group_rows`: the K/V
    pair, or one latent row), and a ``"state"`` group of what
    ``cfg.state_rows`` names.
    ``num_blocks[name] = None`` provisions every slot's worst
    case (full provisioning; fewer oversubscribes — paged memory is the
    point — and admission control, not OOM, then absorbs the pressure)."""
    layers = layer_groups(cfg)
    if set(layers) == {"state"}:
        raise ValueError(
            "serving a model of state layers only is not implemented yet: "
            "the slots' lengths and admission live with a paged group")
    if "full" not in layers:
        raise ValueError(
            "serving a model of window layers only is not implemented yet "
            "(a lone group is the one prefixes are shared and blocks copied "
            "on write through, and a ring is neither): serve it with full "
            "attention, attn_window=None")
    per_slot = max_context // block_size
    groups = {}
    for name, ls in layers.items():
        if name == "state":
            groups[name] = StateGroup(
                num_layers=len(ls), rows=cfg.state_rows, max_slots=max_slots,
                dtype=cfg.dtype)
            continue
        kw = dict(num_layers=len(ls), rows=group_rows(cfg, name),
                  max_slots=max_slots, block_size=block_size,
                  max_context=max_context, dtype=cfg.dtype)
        if name == "window":
            window = cfg.window_of(ls[0])
            if getattr(kw["rows"], "tumbling", False):
                _check_tumbling_chunk(window, write_ahead,
                                      group_rows(cfg, "full"))
                write_ahead = 0
            ring = -(-(window + write_ahead) // block_size) + 1
            n = num_blocks.get(name) or max_slots * min(per_slot, ring)
            groups[name] = WindowKVGroup(
                window=window, write_ahead=write_ahead, num_blocks=n, **kw)
        else:
            n = num_blocks.get(name) or max_slots * per_slot
            groups[name] = PagedKVCache(num_blocks=n, **kw)
    return GroupedKVCache(groups, layers)
