"""The serving engine's compiled programs: chunked prefill, paged decode, and
the fused decode / verify fast path — one set for every model family.

Prefill/decode disaggregation: a serving step is either (a) teacher-forced
ingestion of a prompt chunk — big matmuls, compute-bound — or (b) one
token for every active slot — cache streaming, memory-bound.  Fusing them
(the reference's whole-batch scan, ``models.generate``) forces every request
in the batch to the same phase; splitting them lets the scheduler admit a new
prompt while other slots keep decoding.  All programs have fully static
shapes, so a serving process compiles each once.

A family is a module of layer functions (``models.gpt``, ``models.afmoe``,
``models.joyai``, ``models.jamba``, ``models.mimo``, ``models.lfm2``,
``models.evabyte``, ``models.ling``, ``models.nemotron_h``,
``models.qwen3_next``, ``models.ouro``):
``embed(params, ids, cfg)``, ``block(p,
x, cfg, layer, positions, attend, token_mask=None) -> (x, counters)`` and
``head(params, x, cfg)``, over activations ``(T, d)``, plus
``init_params(cfg, key)``.  Its
configuration says how many times its stack of layers is run a token
(``cfg.stack_passes``, one attribute, 1 where a config does not say:
``models.ouro`` runs its 48 layers 4 times over the same weights); such a
family also has ``end_pass(params, x, cfg) -> (x, gate)``, what every pass
ends with (the final norm, and the exit gate's logit a token), and its
programs run the passes under ONE device loop (:func:`_through_passes`:
scope ``ut_loop``, the body — the layers, ``ut_norm``, ``ut_gate`` — traced
and lowered once, the activations and the donated pools its carry), pass
``u`` of the layer at index ``l`` of a group writing and attending pool layer
``u * layers + l``, a traced scalar that the kernels take as they take a
Python one (``_paged_attn_call`` and ``_kv_chunk_call`` prefetch it,
``_write_rows`` indexes with it).  The head runs once, after the loop.  Its
configuration says what a cached row is (``cfg.cache_rows``, an
``ops.attention.KVRows``, ``LatentRows`` or ``SparseLatentRows``: the widths
of the group's pools and the paged formulations over them; ``{group:
form}`` where the groups' rows differ — mimo's window layers cache 8 K/V
heads a token and its full layers 4 —, ``serve.kv_cache.group_rows``), and
its block calls ``attend(q, *rows, **weights)`` with the rows to store, one a
pool: ``attend(q, k, v)`` where a token's K and V of all heads are cached
(``attend(q, k, v, sink=b)`` on a layer whose heads have a learned sink; the
form lays a head wider than a lane tile out as it stores it, ``stored``),
``attend((q_nope, q_rope), row, w_uk=, w_uv=)`` where one latent row is and
the query comes in two parts, ``attend((q_nope, q_rope, q_index, w_index),
row, index_key, w_uk=, w_uv=)`` where an indexer selects the latent rows a
query attends (joyai with ``index_topk``: GLM-5) and its key is cached in a
second pool of the same group, ``attend(q, k, v, mu=, phi=)`` where a layer's
rows live in TWO groups at two rates (evabyte's EVA layers,
``ops.attention.EvaRows``: the K/V pair a token in a tumbling ring, group
``"window"``, and one summary pair a ``chunk_size`` tokens in group ``"full"``,
a row a ``tokens_per_row`` tokens; :class:`_TwoPools` is that hook: it writes
the token rows, forms and writes the summaries of the chunks the program
completes — a decode step's for the slots at ``t % chunk_size == chunk_size
- 1`` only, the others' to the scratch block — and reads a prefix of the
summary pool shorter than what is written, the chunks of *closed* windows;
such a family's prefill chunk takes the count of real tokens too, since a
padded chunk gets no summary).  A program is the family's embedding, its blocks and its head, with an
``attend`` that writes the rows into the layer's group pools and reads the
slot's pages back through the form — so the block is written once a family,
and the three programs differ only in where the rows live:

- :func:`make_prefill_fn` — one ``prefill_chunk``-wide slice of one
  prompt; the chunk's rows go straight to the slot's pool blocks and its
  queries attend the slot's earlier chunks through the page-table row
  (``ops.attention.paged_window_chunk_attention`` or
  ``paged_latent_chunk_attention``, a running softmax over the context up to
  the chunk's end, in VMEM on the TPU).  There is no dense cache, so for the families whose
  layers keep only rows a token (gpt, afmoe, joyai) a chunk is
  *stateless*: any slot's next chunk can run at any time, the scheduler can
  interleave chunks of several requests with decode steps (ISSUE 14
  budgeted prefill), and a request admitted onto a cached prefix starts
  from the shared blocks without a special load path.  Any prompt length =
  a Python loop of these fixed-width calls; the head is applied to the one
  row the engine wants.  For a family with a *state group* (jamba, lfm2:
  layers that keep a fixed-size state a slot, ``cfg.keeps_state(layer)``) a
  chunk is not stateless: it scans from the state the slot's last chunk left
  in the slot's row of the group's arrays (zeros at ``start == 0``) and
  stores the state back, so a slot's chunks still interleave freely with
  other slots' work, in order; the program takes the count of real tokens,
  since a pad step must be the identity, and hands every block the mask of
  them (``token_mask``: a pad token reaches no expert, lfm2).  On such a
  layer the block is handed a :class:`_SlotState` in ``attend``'s place.
- :func:`make_decode_fn` — one token for all ``max_slots`` slots against
  the paged pool (``ops.attention.paged_window_decode_attention`` or
  ``paged_latent_decode_attention``: on the TPU a kernel that reads only the
  blocks a slot holds).
- :func:`make_fused_decode_fn` — the decode fast path: ``draft + 1`` tokens
  a slot, K/V append, multi-token attention
  (``ops.attention.paged_verify_attention``) and sampling in one dispatch.

(There is also a tiny pool-level block-copy program in ``serve.kv_cache``
— the copy-on-write path — compiled only if a CoW ever fires.)

``pools`` is ``{group: its pools}`` (``(k_pool, v_pool)``, the one pool
of latent rows, or a state group's arrays — ``(convolution tails, scan
states)`` for jamba, ``(convolution tails,)`` for lfm2, ``(q tails, k tails,
v tails, matrix states)`` for ling, ``(convolution tails, matrix states)``
for nemotron_h and qwen3_next: what ``cfg.state_rows.arrays`` lists —,
``(layers, slots, ...)`` each) and ``tables`` ``{group: page
table}`` (a state group's is the one column that names the slot;
``serve.kv_cache.GroupedKVCache``: layers in groups by attention
kind; GPT-2 is one full group); ``layers`` maps a group to the model layers
it holds, in pool order.  The pools are donated: steady-state serving does
not allocate.  All of these programs take a pool in the one form
``serve.kv_cache`` stores it in — ``(layers, (num_blocks + 1) * block_size,
row width)``, token rows (``Hkv * D``: the heads folded into the minor
dimension; or a latent row's five lane tiles) — and
none reshapes it: a write scatters ``(tokens, row width)`` rows at
``block * block_size + offset``, the page-table walk gathers whole blocks
of rows with the layer as an index of the same gather, and heads are split
only on what was gathered.  So the donated input aliases the output and XLA
adds no pool-sized copy on entry or exit (with a ``(..., block, Hkv, D)``
pool it converted all of it both ways on every call: PERF.md §5, PR 25);
``serve.pool_check`` reads that off the compiled programs.

Every stage of the programs sits in a ``jax.named_scope``: ``kv_rows`` (the
pool rows the new tokens go to, a group: the same in every layer of it, so
computed once — a scatter that works its rows out itself costs GPT-2
medium's ``jit_decode`` 0.23 ms of 3.24 over 24 layers; my chip run, PR 30),
``embed``, per layer ``h<i>/{ln,qkv,kv_write,paged_attn,proj,mlp}``
(``kv_write`` is the programs' own and ``paged_attn`` the form's, siblings,
under whatever scope the family's block calls ``attend`` in: ``h<i>`` for
GPT-2, ``h<i>/window_attn`` or ``h<i>/full_attn`` for afmoe,
``h<i>/latent_attn`` for joyai, whose form adds ``absorb`` and ``v_up``
beside them and, with an indexer, ``indexer`` (the index projections in the
block, the slot's keys gathered and scored in the form) and ``select`` (the
top ``index_topk`` positions a query); ``kv_write`` then holds the index
key's write too, ``h<i>/attn`` for jamba, whose Mamba layers have
``h<i>/{state_read,state_write}`` and ``h<i>/mamba/{in_proj,conv,x_proj,
dt_proj,scan|ssm_step,gate,out_proj}``, and for lfm2, whose attention layers
add ``h<i>/attn/{qk_norm,rope}`` and whose conv layers have
``h<i>/{state_read,state_write}`` and ``h<i>/conv/{in_proj,gate_in,conv,
gate_out,out_proj}``, ``h<i>/latent_attn`` again for ling's MLA layers (with
``out_gate``), whose KDA layers have ``h<i>/{state_read,state_write}`` and
``h<i>/kda/{proj,conv,gate,scan|step,out_proj}``, ``h<i>/attn`` for
nemotron_h, whose Mamba-2 layers have ``h<i>/{state_read,state_write}`` and
``h<i>/mamba2/{in_proj,conv,scan|step,gated_norm,out_proj}`` and whose expert
layers ``h<i>/moe/{latent_down,latent_up}`` around the experts (a layer of
that family is one part, and an expert layer is in no cache group: it calls
no hook), ``h<i>/attn`` again for qwen3_next's gated attention layers
(``proj``, ``gate`` and ``out_proj`` the block's own), whose Gated DeltaNet
layers have ``h<i>/{state_read,state_write}`` and ``h<i>/gdn/{proj,conv,gate,
scan|step,gated_norm,out_proj}`` and every layer ``h<i>/moe/shared_gate``
beside the router, the experts and the shared expert, ``h<i>/eva_attn`` for
evabyte, whose hook adds
``summarise`` and ``summary_write`` beside ``kv_write`` and ``paged_attn``
(the block's own are ``qkv``, ``rope`` and ``proj``), and under ``ut_loop``
(the device loop over the passes; a profiler's path reads
``ut_loop/while/body/h<i>/...``) ouro's ``h<i>/{norm_in,attn/{qkv,rope,
kv_write,paged_attn,proj},norm_attn_out,norm_mlp_in,mlp,norm_mlp_out}`` and,
once a pass, ``ut_norm`` and ``ut_gate``; an expert layer's FFN is
``h<i>/{router,experts}``, a dense one's ``h<i>/mlp``), ``head``, ``sample``,
and ``cast_params`` wherever a family casts a stored weight at its use.
Metadata only, so a profiler trace can say which stage a device operation
belongs to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..models import (afmoe, evabyte, gpt, jamba, joyai, lfm2, ling, mimo,
                      nemotron_h, ouro, qwen3_next)
from ..ops.kda import kda_chunk_scan, kda_step
from ..ops.ssd import ssd_chunk_scan, ssd_step
from ..ops.ssm import causal_conv, conv_step, ssm_chunk_scan, ssm_step
from .kv_cache import group_rows
from .sampling import sample_burst

__all__ = [
    "make_prefill_fn",
    "make_decode_fn",
    "make_fused_decode_fn",
    "make_programs",
    "family_of",
]


def _group_of(layers: dict[str, tuple[int, ...]]) -> dict[int, tuple]:
    """``{layer: (its group, its index in the group's pools)}``; of a layer
    in several groups (:class:`_TwoPools`) the first that lists it."""
    where = {}
    for name, ls in layers.items():
        for i, layer in enumerate(ls):
            where.setdefault(layer, (name, i))
    return where


#: where a layer in no cache group lives (``models.nemotron_h``'s expert
#: layers: a part that mixes no tokens keeps nothing, and calls no hook)
_NO_GROUP = (None, 0)


def _two_pool_form(cfg):
    """``cfg.cache_rows`` where a layer's rows live in two groups at two
    rates (``ops.attention.EvaRows``), else None."""
    form = cfg.cache_rows
    return form if hasattr(form, "summary_group") else None


def _forms_of(cfg, layers: dict[str, tuple[int, ...]]) -> dict:
    """``{group: the form of its rows}`` over the paged groups."""
    return {name: group_rows(cfg, name) for name in layers
            if name != "state"}


def _write_rows(form, pools: tuple, li: int, at, rows: tuple,
                scope: str = "kv_write") -> tuple:
    """The group's pools with ``rows`` (one array a pool, a row a token, as
    the block handed them to ``attend``) written at pool rows ``at`` of
    layer ``li``, laid out as the form stores them."""
    with jax.named_scope(scope):
        return tuple(pool.at[li, at].set(r.reshape(at.shape[0], -1))
                     for pool, r in zip(pools, form.stored(*rows)))


def _attend_lens(seq_lens, active):
    """The rows each slot's first query attends, its own included: ``seq_len
    + 1`` of a slot that decodes, 0 of an inactive one — a slot nobody holds
    attends nothing (every form returns zeros for a length of 0, and the
    decode kernels spend a grid step of no trip on it); only its write goes
    somewhere, to the scratch block.  The one rule of every program that
    masks by ``active``."""
    return jnp.where(active, seq_lens.astype(jnp.int32) + 1, 0)


def _passes(cfg) -> int:
    """How many times ``cfg``'s stack of layers is run a token
    (``cfg.stack_passes``: ``models.ouro``); 1 where a config does not say."""
    return getattr(cfg, "stack_passes", 1)


def _slots_a_pass(cfg, layers: dict[str, tuple[int, ...]]) -> dict[str, int]:
    """``{group: the layer slots one pass of the stack fills in its pools}``:
    a group of a looped config lists its layers once a pass
    (``serve.kv_cache.layer_groups``), pass-major, so pass ``u`` of the layer
    at index ``li`` of the group keeps its rows in slot ``u * slots + li``
    (``u`` is a Python 0 where the stack runs once: the index it had).
    Refuses, with the reason, the groups no loop is written for."""
    passes = _passes(cfg)
    if passes > 1 and ("state" in layers or _two_pool_form(cfg) is not None):
        raise ValueError(
            "a stack run several times over a state group or over rows at "
            "two rates is not implemented: which pass's state a slot keeps "
            "between tokens is a question no served family answers yet")
    return {name: len(ls) // passes for name, ls in layers.items()}


def _through_passes(family, cfg, params, stack, x, pools, weigh=None):
    """``(x, pools, mass)``: ``x`` and the donated pools through the
    config's stack, ``stack(x, pools, u) -> (x, pools)`` one pass of all its
    layers with the pool layer slots of pass ``u``.

    A config that runs its stack once gets ``stack(x, pools, 0)``, a Python
    ``0``: the program it had.  One that runs it ``stack_passes`` times gets
    ONE device loop over the pass (scope ``ut_loop``) whose body — the
    layers, then ``family.end_pass`` (the final norm and the exit gate:
    ``ut_norm``, ``ut_gate``) — is traced and lowered once, ``u`` a traced
    scalar; the activations and the pools are the loop's carry, so the pools
    stay the donated buffers.  ``mass`` is float32 ``(passes,)``, the exit
    distribution ``p_u`` (``models.ouro``) weighed by ``weigh`` ((T,), summing
    to 1), or None where ``weigh`` is or the stack runs once."""
    passes = _passes(cfg)
    if passes == 1:
        return (*stack(x, pools, 0), None)
    t = x.shape[0]

    def body(u, carry):
        x, pools, stay, mass = carry
        x, pools = stack(x, pools, u)
        x, gate = family.end_pass(params, x, cfg)
        if weigh is not None:
            with jax.named_scope("ut_gate"):
                lam = jax.nn.sigmoid(gate)
                leave = jnp.where(u == passes - 1, stay, lam * stay)
                mass = mass.at[u].set((leave * weigh).sum())
                stay = stay * (1.0 - lam)
        return x, pools, stay, mass

    with jax.named_scope("ut_loop"):
        x, pools, _, mass = jax.lax.fori_loop(
            0, passes, body, (x, pools, jnp.ones((t,), jnp.float32),
                              jnp.zeros((passes,), jnp.float32)))
    return x, pools, (None if weigh is None else mass)


class _TwoPools:
    """The ``attend`` hook of a layer whose rows live in two groups at two
    rates (``models.evabyte``): the programs' own.  It writes the token rows
    to the ring (``kv_write``), forms the summaries of the chunks this
    program completes (``summarise``: in a prefill chunk from the chunk's own
    rows, in a decode step from the slot's last ``chunk_size`` ring rows,
    this step's among them) and writes them to the summary pool
    (``summary_write``) — a chunk the program does not complete, a pad
    position's and an inactive slot's, goes to the scratch block —, then
    reads both pools back through the form (``paged_attn``).  The summary is
    written in the program that wrote the chunk's last row, so before the
    ring row it came from can be reused.  ``pools`` is the program's dict of
    pools, updated in place; ``rows`` the pool rows the writes go to, a group,
    and ``last`` (decode) the ring rows of each slot's last chunk."""

    def __init__(self, form, pools: dict, layers: dict, layer: int,
                 rows: dict, read, last=None):
        self.form, self.pools, self.rows = form, pools, rows
        self.read, self.last = read, last
        self.li = {name: layers[name].index(layer)
                   for name in (form.token_group, form.summary_group)}

    def __call__(self, q, k, v, *, mu, phi):
        form, pools = self.form, self.pools
        tok, summ = form.token_group, form.summary_group
        forms = form.groups
        pools[tok] = _write_rows(forms[tok], pools[tok], self.li[tok],
                                 self.rows[tok], (k, v))
        if self.last is not None:
            with jax.named_scope("summarise"):
                heads = (*self.last.shape, *k.shape[1:])
                k, v = (pool[self.li[tok], self.last].reshape(heads)
                        for pool in pools[tok])
        pools[summ] = _write_rows(
            forms[summ], pools[summ], self.li[summ], self.rows[summ],
            tuple(r.reshape(-1, *r.shape[-2:])       # a row a chunk
                  for r in form.summarise(k, v, mu, phi)),
            scope="summary_write")
        return self.read(q, pools, self.li[tok])


class _SlotState:
    """The ``mixer`` hook of a state layer (``models.jamba``,
    ``models.lfm2``, ``models.ling``, ``models.nemotron_h``,
    ``models.qwen3_next``): the programs' own, as ``attend`` is.  ``conv``,
    ``scan``, ``delta`` and ``ssd`` read the layer's state out of the group's
    arrays (scope ``state_read``), run the form (``<scope>/conv``;
    ``mamba/scan`` or ``mamba/ssm_step``; ``kda/scan`` or ``kda/step``, under
    ``gdn`` for qwen3_next's scalar gate; ``mamba2/scan`` or ``mamba2/step``)
    and store the state back (``state_write``) — a convolution tail is array
    ``which`` (0 where the layer has one convolution: jamba, lfm2, nemotron_h,
    qwen3_next; ling's q, k and v are 0, 1, 2) and the scan or matrix state
    the last of ``cfg.state_rows.arrays`` (a family that keeps the tail alone,
    lfm2, calls none of the three).  ``pools`` is the program's dict of pools,
    updated in place."""

    def __init__(self, pools: dict, li: int, impl: str = "auto"):
        self.pools, self.li, self.impl = pools, li, impl

    def _store(self, which: int, value) -> None:
        arrays = list(self.pools["state"])
        arrays[which] = self._put(arrays[which], value)
        self.pools["state"] = tuple(arrays)

    def conv(self, u, w, b, scope: str = "mamba", which: int = 0):
        """The causal convolution of ``u`` after the layer's tail ``which``,
        under the family's own scope ``<scope>/conv``."""
        with jax.named_scope("state_read"):
            tail = self._get(self.pools["state"][which])
        with jax.named_scope(scope), jax.named_scope("conv"):
            out, tail = self._conv(u, tail, w, b)
        with jax.named_scope("state_write"):
            self._store(which, tail)
        return out

    def scan(self, u, delta, a, b, c, d):
        with jax.named_scope("state_read"):
            state = self._get(self.pools["state"][-1])
        with jax.named_scope("mamba"), jax.named_scope(self.scan_scope):
            y, state = self._scan(u, delta, a, b, c, d, state)
        with jax.named_scope("state_write"):
            self._store(-1, state)
        return y


class _ChunkState(_SlotState):
    """One slot's state through a prefill chunk of which ``valid`` tokens are
    real: from zeros at ``start == 0`` (a slot's new occupant needs no reset
    launch), pad positions identity steps, the tail ending at the last real
    token."""

    scan_scope = "scan"

    def __init__(self, pools, li, slot, start, valid, impl):
        super().__init__(pools, li, impl)
        self.slot, self.start, self.valid = slot, start, valid

    def _get(self, array):
        mine = jax.lax.dynamic_index_in_dim(array[self.li], self.slot, 0,
                                            keepdims=False)
        return jnp.where(self.start == 0, jnp.zeros_like(mine), mine)

    def _put(self, array, value):
        return array.at[self.li, self.slot].set(value.astype(array.dtype))

    def _conv(self, u, tail, w, b):
        return causal_conv(u, tail, w, b, self.valid)

    def _scan(self, u, delta, a, b, c, d, state):
        return ssm_chunk_scan(u, delta, a, b, c, d, state, self.valid,
                              impl=self.impl)

    def delta(self, q, k, v, g, beta, scope: str = "kda"):
        """The gated delta rule through the chunk, from the slot's matrix
        state (``ops.kda``), under the family's own scope ``<scope>/scan``."""
        with jax.named_scope("state_read"):
            state = self._get_slot(self.pools["state"][-1])
        with jax.named_scope(scope), jax.named_scope("scan"):
            o, state = kda_chunk_scan(q, k, v, g, beta, state, self.valid)
        with jax.named_scope("state_write"):
            self._store(-1, state)
        return o

    def _get_slot(self, array):
        """``_get`` with layer and slot sliced in one step: ``array[layer]``
        first is a copy of every slot's rows (537 MB a layer of
        nemotron3_super_ep4's matrices at 128 slots: 1.6 ms a layer a chunk,
        and the compiler re-computing other work to make room for it; my chip
        run, PR 54)."""
        mine = jax.lax.dynamic_slice(
            array, (self.li, self.slot) + (0,) * (array.ndim - 2),
            (1, 1) + array.shape[2:])[0, 0]
        return jnp.where(self.start == 0, jnp.zeros_like(mine), mine)

    def ssd(self, x, dt, a, b, c, d):
        """Mamba-2's scalar-decay recurrence through the chunk, from the
        slot's matrix state (``ops.ssd``)."""
        with jax.named_scope("state_read"):
            state = self._get_slot(self.pools["state"][-1])
        with jax.named_scope("mamba2"), jax.named_scope("scan"):
            y, state = ssd_chunk_scan(x, dt, a, b, c, d, state, self.valid)
        with jax.named_scope("state_write"):
            self._store(-1, state)
        return y


class _StepState(_SlotState):
    """Every slot's state through one decode step; an inactive slot's (free,
    or between two of its prefill chunks) stays as it is, bit for bit."""

    scan_scope = "ssm_step"

    def __init__(self, pools, li, active, impl):
        super().__init__(pools, li, impl)
        self.active = active

    def _get(self, array):
        return array[self.li]

    def _put(self, array, value):
        keep = self.active.reshape((-1,) + (1,) * (value.ndim - 1))
        return array.at[self.li].set(
            jnp.where(keep, value.astype(array.dtype), array[self.li]))

    def _conv(self, u, tails, w, b):
        return conv_step(u, tails, w, b)

    def _scan(self, u, delta, a, b, c, d, states):
        return ssm_step(u, delta, a, b, c, d, states)

    def delta(self, q, k, v, g, beta, scope: str = "kda"):
        """One token of the gated delta rule a slot, in place in the group's
        array of matrix states: an inactive slot's step is made the identity
        (no decay, no correction), so no pass over the array selects after
        it."""
        with jax.named_scope(scope), jax.named_scope("step"):
            arrays = list(self.pools["state"])
            o, arrays[-1] = kda_step(
                q, k, v, jnp.where(self.active[:, None, None], g, 0.0),
                jnp.where(self.active[:, None], beta, 0.0), arrays[-1],
                self.li, impl=self.impl)
            self.pools["state"] = tuple(arrays)
        return o

    def ssd(self, x, dt, a, b, c, d):
        """One token of Mamba-2's recurrence a slot, in place in the group's
        array of matrix states: an inactive slot's ``dt`` is 0, the identity
        (``ops.ssd.ssd_step``), so no pass over the array selects after it."""
        with jax.named_scope("mamba2"), jax.named_scope("step"):
            arrays = list(self.pools["state"])
            y, arrays[-1] = ssd_step(
                x, jnp.where(self.active[:, None], dt, 0.0), a, b, c, d,
                arrays[-1], self.li, impl=self.impl)
            self.pools["state"] = tuple(arrays)
        return y


def make_prefill_fn(family, cfg, *, chunk: int, block_size: int,
                    layers: dict[str, tuple[int, ...]]):
    """``fn(params, pools, tokens (chunk,), start, table_rows, last_ix) ->
    (last_logits, pools)``: one fixed-width prompt chunk of one slot, from
    absolute position ``start``; ``table_rows`` the slot's page-table row a
    group and ``last_ix`` the in-chunk index whose logits the engine wants
    (the final prompt token's, clamped into range on non-final chunks whose
    logits are discarded).  Over a state group the program takes one more
    argument, ``valid``: how many of the chunk's tokens are real (pad K/V
    rows are harmless, a pad step of a recurrence is not, and a pad token
    routed to an expert is work and a count: every block gets the mask of
    the real ones); the slot's state is row ``table_rows["state"][0]`` of the
    group's arrays.  The pools are donated."""
    where, forms = _group_of(layers), _forms_of(cfg, layers)
    a_pass = _slots_a_pass(cfg, layers)
    two = _two_pool_form(cfg)
    #: the groups whose rows are not one a token: a state, chunk summaries
    by_chunk = {"state"} | ({two.summary_group} if two else set())

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_chunk(params, pools, tokens, start, table_rows, last_ix,
                      *valid):
        pools = dict(pools)
        positions = start + jnp.arange(chunk, dtype=jnp.int32)
        real = (jnp.arange(chunk, dtype=jnp.int32) < valid[0]
                if valid else None)
        bs = block_size
        with jax.named_scope("kv_rows"):
            rows = {name: row[positions // bs] * bs + positions % bs
                    for name, row in table_rows.items()
                    if name not in by_chunk}
            if two is not None:
                # a summary row a chunk the prompt fills: the others (the
                # pad positions' and the one the prompt ends in, which a
                # decode step completes) go to the scratch block
                per, row = two.chunk_size, table_rows[two.summary_group]
                at = start // per + jnp.arange(chunk // per, dtype=jnp.int32)
                whole = (jnp.arange(1, chunk // per + 1) * per) <= valid[0]
                rows[two.summary_group] = jnp.where(
                    whole, row[jnp.minimum(at // bs, row.shape[0] - 1)] * bs
                    + at % bs, pools[two.summary_group][0].shape[1] - bs)

            def read(q, pools, li):
                return two.chunk(q, start, pools, table_rows, layer=li,
                                 block_size=bs, impl=cfg.kernel_impl)
        x = family.embed(params, tokens, cfg)

        def stack(x, pools, u):
            pools = dict(pools)
            for layer in range(cfg.num_layers):
                name, li = where.get(layer, _NO_GROUP)
                li = u * a_pass.get(name, 0) + li

                def attend(q, *stored, name=name, li=li, layer=layer,
                           **weights):
                    form = forms[name]
                    pools[name] = _write_rows(form, pools[name], li,
                                              rows[name], stored)
                    return form.chunk(
                        q, start, pools[name], table_rows[name], layer=li,
                        block_size=block_size, window=cfg.window_of(layer),
                        impl=cfg.kernel_impl, **weights)

                mixer = attend if name != "state" else _ChunkState(
                    pools, li, table_rows[name][0], start, *valid,
                    cfg.kernel_impl)
                if two is not None:
                    mixer = _TwoPools(two, pools, layers, layer, rows, read)
                with jax.named_scope(f"h{layer}"):
                    x, _ = family.block(params[f"h{layer}"], x, cfg, layer,
                                       positions, mixer, token_mask=real)
            return x, pools

        x, pools, _ = _through_passes(family, cfg, params, stack, x, pools)
        last = jax.lax.dynamic_slice_in_dim(x, last_ix, 1, 0)
        return family.head(params, last, cfg)[0], pools

    return prefill_chunk


def make_decode_fn(family, cfg, *, block_size: int,
                   layers: dict[str, tuple[int, ...]]):
    """``fn(params, pools, tokens (slots,), tables, seq_lens, active) ->
    (logits, greedy, pools, routed)``: one token for every slot.  ``tokens``
    is each slot's last sampled token, ``seq_lens`` the resident token counts
    (the new token is written at that position, then attends ``seq_len + 1``
    positions), and ``active`` masks unoccupied slots: their write lands in
    the reserved scratch block, they attend nothing (:func:`_attend_lens`: a
    length of 0, zeros from every form) and their logits are discarded by
    the engine, so the program shape never depends on occupancy (a state
    group has no scratch: an inactive slot's state is written back as it
    was).  ``greedy``
    is int32
    ``(slots,)``, the arg-max of each row of the float32 ``logits`` (the
    first of equal maxima, as ``np.argmax`` takes it): all the host needs of
    an iteration in which nobody samples, so the logits can stay on the
    device (``Engine._run_decode_step``).  ``routed`` is int32
    ``(3,)``: over the expert layers, the routed (token, choice) pairs that
    landed on held experts (sum), the held experts hit (sum) and the largest
    load of one expert (max) — active slots only; None from a model without
    expert layers.  From a config whose stack is run several times
    (``cfg.stack_passes`` > 1) that fourth output is instead float32
    ``(passes,)``: the passes' exit mass, the mean over the active slots of
    ``p_u`` (``models.ouro``), which sums to 1."""
    where, forms = _group_of(layers), _forms_of(cfg, layers)
    passes, a_pass = _passes(cfg), _slots_a_pass(cfg, layers)
    two = _two_pool_form(cfg)
    #: the groups whose rows are not one a token: a state, chunk summaries
    by_chunk = {"state"} | ({two.summary_group} if two else set())

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, pools, tokens, tables, seq_lens, active):
        pools = dict(pools)
        bs = block_size
        positions = seq_lens.astype(jnp.int32)
        attend_lens = _attend_lens(seq_lens, active)
        with jax.named_scope("kv_rows"):
            rows = {}
            for name, table in tables.items():
                if name in by_chunk:
                    continue
                blk = jnp.take_along_axis(
                    table, (positions // bs)[:, None], axis=1)[:, 0]
                rows[name] = jnp.where(
                    active, blk * bs + positions % bs,
                    pools[name][0].shape[1] - bs)           # else: scratch
            last = None
            if two is not None:
                # the slots whose token completes a chunk write its summary,
                # formed from the chunk's rows in the ring (a chunk lies in
                # one window: all of them are still mapped); the others'
                # goes to the scratch block
                per, tok, summ = (two.chunk_size, two.token_group,
                                  two.summary_group)
                closes = active & (positions % per == per - 1)
                rows[summ] = jnp.where(
                    closes, jnp.take_along_axis(
                        tables[summ], (positions // per // bs)[:, None],
                        axis=1)[:, 0] * bs + positions // per % bs,
                    pools[summ][0].shape[1] - bs)
                back = jnp.maximum(
                    positions[:, None] - (per - 1) + jnp.arange(per), 0)
                last = jnp.where(
                    closes[:, None], jnp.take_along_axis(
                        tables[tok], back // bs, axis=1) * bs + back % bs,
                    pools[tok][0].shape[1] - bs)

            def read(q, pools, li):
                return two.decode(q, pools, tables, attend_lens, layer=li,
                                  block_size=bs, impl=cfg.kernel_impl)
        x = family.embed(params, tokens, cfg)
        routed = []

        def stack(x, pools, u):
            pools = dict(pools)
            for layer in range(cfg.num_layers):
                name, li = where.get(layer, _NO_GROUP)
                li = u * a_pass.get(name, 0) + li

                def attend(q, *stored, name=name, li=li, layer=layer,
                           **weights):
                    form = forms[name]
                    pools[name] = _write_rows(form, pools[name], li,
                                              rows[name], stored)
                    return form.decode(
                        q, pools[name], tables[name], attend_lens, layer=li,
                        block_size=bs, window=cfg.window_of(layer),
                        impl=cfg.kernel_impl, **weights)

                mixer = attend if name != "state" else _StepState(
                    pools, li, active, cfg.kernel_impl)
                if two is not None:
                    mixer = _TwoPools(two, pools, layers, layer, rows, read,
                                      last)
                with jax.named_scope(f"h{layer}"):
                    x, counters = family.block(
                        params[f"h{layer}"], x, cfg, layer, positions, mixer,
                        token_mask=active)
                if counters is not None:
                    routed.append(counters)
            return x, pools

        weigh = None
        if passes > 1:      # the exit mass: the active slots' mean
            live = active.astype(jnp.float32)
            weigh = live / jnp.maximum(live.sum(), 1.0)
        x, pools, stat = _through_passes(family, cfg, params, stack, x,
                                         pools, weigh)
        if routed:
            stat = [sum(c["pairs"] for c in routed),
                    sum(c["experts_hit"] for c in routed),
                    functools.reduce(jnp.maximum,
                                     [c["max_load"] for c in routed])]
            if "groups_hit" in routed[0]:
                stat.append(sum(c["groups_hit"] for c in routed))
            stat = jnp.stack(stat).astype(jnp.int32)
        logits = family.head(params, x, cfg)
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, greedy, pools, stat

    return decode


def make_fused_decode_fn(family, cfg, *, block_size: int,
                         layers: dict[str, tuple[int, ...]], draft: int = 0):
    """The decode **fast path** — forward, K/V append, AND sampling in one
    dispatch; optionally speculative.

    ``fn(params, pools, tokens, draft_lens, tables, seq_lens, active, keys,
    prompt_lens, temperature, top_k) -> (packed, next_feed, pools)`` with
    ``T = draft + 1`` query positions per slot: column 0 of ``tokens``
    ``(slots, T)`` is each slot's last committed token, columns
    ``1..draft_lens`` its n-gram draft proposals (``serve.draft``), the rest
    padding.  The program writes K/V for the committed token and every draft
    at consecutive positions (pad/inactive writes land in the scratch
    block), runs ONE multi-token paged attention pass
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

    The forward is the family's block, as in :func:`make_decode_fn`: the
    accepted-token logits are the numbers the one-token program would have
    produced (parity pinned by tests/test_serve_spec.py, incl. bf16).
    ``paged_verify_attention`` masks no window: full layers only.
    """
    where, forms = _group_of(layers), _forms_of(cfg, layers)
    a_pass = _slots_a_pass(cfg, layers)
    t_width = draft + 1

    @functools.partial(jax.jit, donate_argnums=(1,))
    def fused_decode(params, pools, tokens, draft_lens, tables, seq_lens,
                     active, keys, prompt_lens, temperature, top_k):
        pools = dict(pools)
        b = tokens.shape[0]
        bs = block_size
        positions = (seq_lens[:, None]
                     + jnp.arange(t_width, dtype=jnp.int32)[None, :])
        # Write coordinates: the committed token (column 0) and the real
        # drafts append at consecutive positions inside the slot's pages;
        # pad columns and inactive slots hit scratch.  Rejected drafts
        # leave garbage PAST the committed seq_len — masked by the
        # validity rule until a later write overwrites it (the K/V-level
        # rollback; the host-side retreat is kv_cache.rollback).
        valid_w = active[:, None] & (
            jnp.arange(t_width)[None, :] <= draft_lens[:, None]
        )
        attend_lens = _attend_lens(seq_lens, active)
        with jax.named_scope("kv_rows"):
            rows = {}
            for name, table in tables.items():
                blk = jnp.take_along_axis(
                    table, jnp.clip(positions // bs, 0, table.shape[1] - 1),
                    axis=1)
                rows[name] = jnp.where(
                    valid_w, blk * bs + positions % bs,
                    pools[name][0].shape[1] - bs).reshape(-1)  # else: scratch
        x = family.embed(params, tokens.reshape(-1), cfg)      # (B * T, d)

        def stack(x, pools, u):
            pools = dict(pools)
            for layer in range(cfg.num_layers):
                name, li = where[layer]
                li = u * a_pass[name] + li

                def attend(q, *stored, name=name, li=li, **weights):
                    form = forms[name]
                    pools[name] = _write_rows(form, pools[name], li,
                                              rows[name], stored)
                    out = form.verify(
                        q.reshape(b, t_width, *q.shape[1:]), pools[name],
                        tables[name], attend_lens, layer=li, block_size=bs,
                        **weights)
                    return out.reshape(b * t_width, *out.shape[2:])

                with jax.named_scope(f"h{layer}"):
                    x, _ = family.block(
                        params[f"h{layer}"], x, cfg, layer,
                        positions.reshape(-1), attend,
                        token_mask=valid_w.reshape(-1))
            return x, pools

        x, pools, _ = _through_passes(family, cfg, params, stack, x, pools)
        logits = family.head(params, x, cfg).reshape(b, t_width, -1)
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
        return packed, next_feed[:, None], pools

    return fused_decode


# ---------------------------------------------------------------------------
# What the engine drives: one set of programs a configuration
# ---------------------------------------------------------------------------
#
# The engine holds layer groups (``serve.kv_cache.GroupedKVCache``) and a
# set of programs over them, and knows no family.

#: configuration class -> its family module: the one place that tells the
#: families apart
PROGRAMS = {
    gpt.GPTConfig: gpt,
    afmoe.AfmoeConfig: afmoe,
    joyai.JoyaiConfig: joyai,
    jamba.JambaConfig: jamba,
    mimo.MimoConfig: mimo,
    lfm2.Lfm2Config: lfm2,
    evabyte.EvaByteConfig: evabyte,
    ling.LingConfig: ling,
    nemotron_h.NemotronHConfig: nemotron_h,
    qwen3_next.Qwen3NextConfig: qwen3_next,
    ouro.OuroConfig: ouro,
}

#: the families served through the fused and verify programs: those whose
#: tokens the parity tests of tests/test_serve_spec.py pin to the one-token
#: path's.  Another family's would run unchecked (afmoe's window layers not
#: at all: ``paged_verify_attention`` masks no window; joyai's draft module,
#: which predicts several tokens for self-speculation, is not built and its
#: latent rows — with or without an index key beside them — have no verify
#: formulation), so it is refused until it has
#: such tests and a cell of its own.  Over a state group there is more in
#: the way than tests: a rejected draft's steps cannot be rolled back out of
#: a state, which keeps no earlier position (jamba, lfm2, ling, nemotron_h,
#: qwen3_next).  ouro keeps only rows a token, so nothing but the missing
#: tests is in its way: :func:`make_fused_decode_fn` runs its passes under the
#: same device loop as the other two programs.
FUSED = (gpt,)

#: the families a request may be admitted for onto cached prefix blocks: those
#: with a test that holds such a request to the uncached logits
#: (tests/test_serve.py).  (A cache of several layer groups shares no prefixes
#: whatever the family: ``serve.engine``; a state group would need a snapshot
#: of the state at every shared block's end.)
PREFIX = (gpt, afmoe)


#: what stands in an option's way over a state group, whatever tests exist
_STATE_LACKS = {
    "prefix_cache": "a shared prefix has no snapshot of the state at its end",
    "fused_sampling": "its sampled program is the verify program at no "
                      "draft, which has no state formulation",
    "speculate": "a rejected draft cannot be rolled back out of a state",
}


#: what stands in an option's way where a layer keeps a ring that is reused
#: in place beside chunk summaries (``ops.attention.EvaRows``)
_TWO_POOL_LACKS = {
    "prefix_cache": "a prefix shared at a block's end would need the ring's "
                    "rows of its open window beside its summaries, and a "
                    "ring is reused in place",
    "fused_sampling": "its sampled program is the verify program at no "
                      "draft, which has no formulation over a ring and a "
                      "summary pool",
    "speculate": "a rejected draft that completed a chunk has written its "
                 "summary, and one past a window's end has reused a ring "
                 "row: neither can be rolled back",
}


def _one_or_each(names) -> str:
    """The formulation every group takes, or ``"a|b"`` in group order where
    the groups' differ."""
    return "|".join(dict.fromkeys(names))


def family_of(cfg):
    """The family module of ``cfg``: its layer functions and ``init_params``."""
    for kind, family in PROGRAMS.items():
        if isinstance(cfg, kind):
            return family
    raise ValueError(f"no serving programs for a {type(cfg).__name__}")


class Programs:
    """The programs of a family module over the layer groups ``layers``:

    - ``prefill(params, pools, tokens (chunk,) on the host, start,
      table_rows, last_ix) -> (last_logits, pools)``: one prompt chunk of
      one slot (:func:`make_prefill_fn`);
    - ``decode(params, pools, tokens (slots,), tables, seq_lens, active) ->
      (logits, greedy, pools, routed)``: one token a slot, and the arg-max of
      its logits (:func:`make_decode_fn`); where ``passes`` > 1 the fourth
      output is the passes' exit mass;
    - ``fused(draft)``: the sampled (``draft`` = 0) or verify program
      (:func:`make_fused_decode_fn`), or a ``ValueError`` that says it is
      not implemented;
    - ``decode_attention``: the formulation the decode programs in use
      attend the pages with, ``"paged_attn"`` or ``"paged_latent_attn"``
      (the kernel that reads only the blocks a slot holds, over K/V rows or
      latent rows), ``"sparse_latent_attn"`` (the kernel over the rows an
      indexer selected, gathered by index) or ``"plain"`` (the gather of
      every table column; of the selected rows, their scores through HBM):
      the fallback is silent, so the engine reports it (``Engine.state()``);
    - ``chunk_attention``: the same of ``prefill``: ``"kv_chunk_attn"`` /
      ``"latent_chunk_attn"`` (the kernel over K/V / latent rows that keeps a
      chunk's scores in VMEM), ``"plain"`` (the loop whose scores go through
      HBM: the CPU, ``"xla"``, GPT-2's heads of 64), or with an indexer ``"<sparse>+<dense>"``: the formulation of a
      chunk that ends past ``index_topk`` and the one of a chunk that does
      not (every row selected: the dense sum);
    - ``chunk_scan``: the form ``prefill`` scans a state group's layers
      with: ``"ssm_chunk_scan"`` (the kernel that holds the state in VMEM),
      ``"chunked"`` (ling, qwen3_next, nemotron_h: the delta rule's, or the
      scalar decay's, chunked mathematics in plain ``jax.numpy``) or ``"plain"``
      (``lax.scan``); None where no
      layer keeps a state, or the state has no scan (lfm2);
    - ``state_form``: what a state layer keeps a slot, the names of the
      group's arrays joined by ``+``: ``"conv_tail+scan_state"`` (jamba),
      ``"conv_tail"`` (lfm2), ``"q_tail+k_tail+v_tail+delta_state"`` (ling),
      ``"conv_tail+ssd_state"`` (nemotron_h), ``"conv_tail+delta_state"``
      (qwen3_next);
      None where no layer keeps a state."""

    def __init__(self, family, cfg, *, chunk: int, block_size: int,
                 layers: dict[str, tuple[int, ...]]):
        self.family, self.cfg = family, cfg
        self.chunk, self.block_size, self.layers = chunk, block_size, layers
        self.prefill_chunk = make_prefill_fn(
            family, cfg, chunk=chunk, block_size=block_size, layers=layers)
        self.decode = make_decode_fn(
            family, cfg, block_size=block_size, layers=layers)
        self._fused = False

    @property
    def passes(self) -> int:
        """How many times the stack of layers is run a token
        (``cfg.stack_passes``, 1 where a config does not say): above 1 the
        programs loop on the device and ``decode``'s fourth output is the
        passes' exit mass."""
        return _passes(self.cfg)

    @property
    def decode_attention(self) -> str:
        # the engine decodes through the fused programs once it has asked
        # for them, and they attend with ``paged_verify_attention``
        if self._fused:
            return "plain"
        return _one_or_each(f["decode"] for f in self.formulations.values())

    @property
    def chunk_attention(self) -> str:
        return _one_or_each(f["chunk"] for f in self.formulations.values())

    @property
    def formulations(self) -> dict[str, dict[str, str]]:
        """``{group: {"decode": ..., "chunk": ...}}``: what the one-token
        program and a prefill chunk attend each paged group's pages with."""
        impl = self.cfg.kernel_impl
        return {name: {
            "decode": form.decode_formulation(self.block_size, impl),
            "chunk": form.chunk_formulation(self.block_size, self.chunk,
                                            impl)}
            for name, form in _forms_of(self.cfg, self.layers).items()}

    @property
    def chunk_scan(self) -> str | None:
        if "state" not in self.layers:
            return None
        return self.cfg.state_rows.chunk_formulation(
            self.chunk, self.cfg.kernel_impl)

    @property
    def state_form(self) -> str | None:
        if "state" not in self.layers:
            return None
        return "+".join(self.cfg.state_rows.names)

    def prefill(self, params, pools, tokens, start: int, table_rows,
                real: int):
        """The chunk at ``start`` of which the first ``real`` tokens are the
        prompt's (the rest padding): the logits are those of the last real
        one."""
        counted = "state" in self.layers or _two_pool_form(self.cfg)
        valid = (jnp.int32(real),) if counted else ()
        return self.prefill_chunk(params, pools, jnp.asarray(tokens),
                             jnp.int32(start), table_rows,
                             jnp.int32(max(real - 1, 0)), *valid)

    def _refuse(self, option: str, lacking: str):
        if "state" in self.layers:
            lacking = _STATE_LACKS[option]
        elif _two_pool_form(self.cfg) is not None:
            lacking = _TWO_POOL_LACKS[option]
        raise ValueError(
            f"{option} is not implemented for the "
            f"{self.family.__name__.rsplit('.', 1)[-1]} family yet "
            f"({lacking}): serve it without")

    def check_prefix_cache(self) -> None:
        """A ``ValueError`` if this family is not served with shared
        prefixes."""
        if self.family not in PREFIX:
            self._refuse("prefix_cache", "no test holds a request admitted on "
                         "cached blocks of its rows to the uncached logits")

    def fused(self, draft: int):
        if self.family not in FUSED:
            self._refuse("speculate" if draft else "fused_sampling",
                         "no parity tests of its fused and verify programs")
        self._fused = True
        return make_fused_decode_fn(
            self.family, self.cfg, block_size=self.block_size,
            layers=self.layers, draft=draft)


def make_programs(cfg, *, chunk: int, block_size: int,
                  layers: dict[str, tuple[int, ...]]) -> Programs:
    """The programs of ``cfg``'s family over the layer groups ``layers``."""
    return Programs(family_of(cfg), cfg, chunk=chunk, block_size=block_size,
                    layers=layers)
