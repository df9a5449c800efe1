"""The gated delta rule over a matrix state a head (Kimi Delta Attention,
Gated DeltaNet), in three forms of one mathematics.

A head keeps, a sequence, a state ``S`` of ``key_dim x value_dim`` float32
values whatever the context (:class:`ops.ssm.DeltaState` states it beside the
three convolution tails of KDA's q, k and v, :class:`ops.ssm.GatedDeltaState`
beside Gated DeltaNet's one).  A token ``t`` **decays** the state, **corrects**
it by a rank-one delta and **reads** it::

    S'  = Diag(exp(g_t)) S_{t-1}                  g_t <= 0
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T      beta_t in (0, 1) a head
    o_t = S_t^T q_t

The gate ``g`` has **two forms**, told apart by its last dimension:

- a key **channel** (``(T, H, key_dim)``; KDA, ``models.ling``): rows of the
  state mix through the delta (``selective_scan``'s state never mixes
  channels).  **Precondition of the chunked form: ``g >= -5`` a token** (KDA's
  published lower bound, ``kda_lower_bound``): :func:`_chunk` splits a decay
  ratio into two factors around a sub-block's middle and the first
  overflows float32 under a stronger gate.  The recurrence and the step take
  any ``g <= 0``;
- one **scalar** a head (``(T, H, 1)``; Gated DeltaNet, ``models.qwen3_next``),
  **unbounded below**: the chunked form (:func:`_chunk_scalar`) forms the
  ratio itself, ``exp(G_t - G_s) <= 1``, one ``C x C`` matrix a head, so
  nothing is clamped and no gate is too strong.  Here ``q`` and ``k`` may
  have fewer heads than ``v`` (``Hk`` dividing ``H``): value head ``h`` reads
  q/k head ``h // (H / Hk)``, and a chunk's products ``K K^T`` and ``Q K^T``
  are a key head's, formed once for its value heads.

The state is stored **transposed**, ``(heads, value_dim, key_dim)``: the key's
channels across lanes, so the decay and both rank-one factors of the key
broadcast along sublanes, and the chunked form's products with the state
contract lanes.

Every form takes ``q, k`` ``(T, Hk, D)``, ``v`` ``(T, H, Dv)`` — ``q`` and ``k``
already L2-normalised, ``q`` scaled —, ``g`` in one of its two forms, ``beta``
``(T, H)`` and the state, computes in float32 and returns ``(o (T, H,
value_dim) float32, state out)``.  A token with ``g = 0`` and ``beta = 0`` is
the identity on the state: that is how positions past ``valid`` and inactive
slots are padded, in every form.

- :func:`kda_recurrent`: the recurrence by ``lax.scan`` over tokens: the
  yardstick of the tests and the plain form on any backend;
- :func:`kda_step`: one token for every slot against the layer's rows of the
  group's array (decode).  On the kernel path (``name="kda_step"``) a Pallas
  kernel over ``(slot, heads / STEP_HEADS)`` streams each head's state through
  VMEM once — read, update, write back in place (the array is aliased in and
  out: no copy of the pool) — and everything else it reads is a few rows a
  head: bandwidth-bound;
- :func:`kda_chunk_scan`: a prefill chunk of one slot in chunks of
  :data:`CHUNK` tokens (the WY / UT-transform form), plain ``jax.numpy``: a
  ``lax.scan`` over the chunks, the heads batched.  Inside a chunk, with
  ``G`` the running sum of ``g`` from the chunk's start::

      A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s < t
      B[t, s] =        sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <= t
      U = (I + A)^-1 (beta (V - (K exp(G)) S_0))
      O = (Q exp(G)) S_0 + B U
      S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

  (a channel gate; a scalar gate's ``exp(G_t[c] - G_s[c])`` leaves the sum:
  ``A = beta (K K^T) * D``, ``B = (Q K^T) * D`` with ``D[t, s] = exp(G_t -
  G_s)``, and ``exp(G)`` scales rows.)  For a channel gate
  ``exp(G_t - G_s)`` is formed as ``exp(G_t - r) exp(r - G_s)`` with ``r`` the
  sum at the middle of ``t``'s sub-block of :data:`SUB` tokens, so under KDA's
  lower bound of -5 a token (the precondition above) both factors of a pair inside the sub-block lie in
  ``e^-40 .. e^40``: inside float32 with room on both sides (around the
  sub-block's *start* the factors reach e^-80 and e^80, and a ``q`` or ``k``
  value under 6e-4 times e^-80 is a denormal, flushed to zero: a term lost);
  a pair across sub-blocks has a second factor under 1, and where it
  underflows the ratio is under e^-47.  ``(I + A)^-1`` is built
  by doubling — the inverse of ``[[P, 0], [R, Q]]`` is ``[[P^-1, 0], [-Q^-1 R
  P^-1, Q^-1]]``, blocks of 1, 2, 4 ... 32 — which is block forward
  substitution written as products for the MXU: as stable as the loop (the
  Neumann product ``(I - A)(I + A^2)...`` is not: its powers grow
  binomially where keys repeat).  No kernel: this body as a Pallas kernel
  over ``(head, chunk)`` with the state resident in VMEM reads 5.0 ms a layer
  a 2,048 tokens on a v5e where XLA's batching of it over the heads reads 3.6
  (PERF.md section 6; ROADMAP S16b has what a faster kernel would do).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..runtime import on_tpu, use_kernel

LANES = 128
#: tokens a chunk of the chunked form
CHUNK = 64
#: tokens a sub-block whose decay ratios are formed around one reference
SUB = 16
#: the largest exponent a decay ratio's second factor is formed at: what half
#: a sub-block at KDA's lower bound of -5 a token reaches (a masked entry's,
#: past the diagonal, would overflow without)
GATE_CLAMP = 40.0
#: heads a grid step of the step kernel (a 1 MB block of state at 128 x 128)
STEP_HEADS = 16

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=_F32)


def _mm_nt(a, b):
    """``a @ b.T``: both contract their last (lane) dimension."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                           preferred_element_type=_F32)


def _mm_tn(a, b):
    """``a.T @ b``: both contract their first dimension."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=_HI,
                           preferred_element_type=_F32)


def _pad_identity(g, beta, valid):
    """``g`` and ``beta`` with the tokens at ``t >= valid`` made identity
    steps."""
    if valid is None:
        return g, beta
    real = jnp.arange(g.shape[0], dtype=jnp.int32) < valid
    return (jnp.where(real[:, None, None], g, 0.0),
            jnp.where(real[:, None], beta, 0.0))


def _share_heads(q, k, heads: int):
    """``q`` and ``k`` (..., Hk, K) as every value head reads them, (...,
    heads, K): value head ``h`` reads head ``h // (heads / Hk)``."""
    per = heads // q.shape[-2]
    if per == 1:
        return q, k
    return jnp.repeat(q, per, axis=-2), jnp.repeat(k, per, axis=-2)


# -- the recurrence ----------------------------------------------------------

def _token(st, q, k, v, g, beta):
    """One token of every head (or slot and head): ``st`` (..., V, K), ``q``,
    ``k`` (..., K), ``g`` (..., K) or (..., 1), ``v`` (..., V), ``beta``
    (...)."""
    st = st * jnp.exp(g)[..., None, :]
    pred = (st * k[..., None, :]).sum(-1)
    u = beta[..., None] * (v - pred)
    st = st + u[..., :, None] * k[..., None, :]
    return st, (st * q[..., None, :]).sum(-1)


def kda_recurrent(q, k, v, g, beta, state, valid=None):
    """The plain form: ``q, k`` (T, Hk, K), ``v`` (T, H, V), ``g`` (T, H, K)
    or (T, H, 1), ``beta`` (T, H), ``state`` (H, V, K) float32 -> ``(o (T, H,
    V) float32, state out)``.  Steps at ``t >= valid`` leave the state as it
    is."""
    g, beta = _pad_identity(g.astype(_F32), beta.astype(_F32), valid)
    q, k = _share_heads(q, k, v.shape[1])

    def step(st, xs):
        return _token(st, *xs)

    state, o = lax.scan(step, state.astype(_F32), (
        q.astype(_F32), k.astype(_F32), v.astype(_F32), g, beta))
    return o, state


# -- the chunked form --------------------------------------------------------

def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` (C, C), C a power
    of two, by doubling: products only."""
    c = a.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    t = (row == col).astype(_F32)
    b = 1
    while b < c:
        # the quadrant below and left of the diagonal in each pair of
        # b-blocks: rows of the odd block, columns of the even one
        pair = (row | (2 * b - 1)) == (col | (2 * b - 1))
        quad = pair & ((row & b) != 0) & ((col & b) == 0)
        t = t - _mm(t, _mm(jnp.where(quad, a, 0.0), t))
        b *= 2
    return t


def _chunk(q, k, v, g, beta, st):
    """One chunk of one head: ``q, k, g`` (C, K), ``v`` (C, V), ``beta`` (C,
    1), ``st`` (V, K) -> ``(o (C, V), st out)``; module text.  Products and
    elementwise operations on whole tiles only."""
    c = q.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    gcum = _mm((col <= row).astype(_F32), g)        # the running sum
    kb = k * beta
    a_rows, b_rows = [], []
    for i in range(c // SUB):
        rows = slice(i * SUB, (i + 1) * SUB)
        mid = i * SUB + SUB // 2 - 1
        ref = gcum[mid:mid + 1]
        into = jnp.exp(gcum[rows] - ref)            # e^-40 .. e^35
        out_of = jnp.exp(jnp.minimum(ref - gcum, GATE_CLAMP))
        p = _mm_nt(jnp.concatenate([kb[rows] * into, q[rows] * into]),
                   k * out_of)                      # (2 SUB, C)
        a_rows.append(p[:SUB])
        b_rows.append(p[SUB:])
    a = jnp.where(col < row, jnp.concatenate(a_rows), 0.0)
    b = jnp.where(col <= row, jnp.concatenate(b_rows), 0.0)
    gam = jnp.exp(gcum)
    u = _mm(_unit_lower_inverse(a), v * beta - _mm_nt(kb * gam, st))
    o = _mm_nt(q * gam, st) + _mm(b, u)
    last = gcum[c - 1:c]
    st = st * jnp.exp(last) + _mm_tn(u, k * jnp.exp(last - gcum))
    return o, st


def _chunk_scalar(q, k, v, gcum, beta, st):
    """One chunk of one key head and the ``R`` value heads that read it, the
    gate a scalar a value head: ``q, k`` (C, K), ``v`` (R, C, V), ``gcum`` (R,
    C, 1) the running sum of ``g`` from the chunk's start, ``beta`` (R, C, 1),
    ``st`` (R, V, K) -> ``(o (R, C, V), st out)``; module text.  Every
    exponent is ``<= 0``: no sub-blocks, nothing to clamp."""
    c = q.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    kk, qk = _mm_nt(k, k), _mm_nt(q, k)        # the key head's, once

    def value_head(v, gcum, beta, st):
        # D[t, s] = exp(G_t - G_s) for s <= t: masked before the exponential
        d = jnp.exp(jnp.where(col <= row, gcum - gcum.T, -jnp.inf))
        a = jnp.where(col < row, beta * kk * d, 0.0)
        gam = jnp.exp(gcum)
        u = _mm(_unit_lower_inverse(a), beta * (v - gam * _mm_nt(k, st)))
        o = gam * _mm_nt(q, st) + _mm(qk * d, u)
        last = gcum[c - 1:c]
        return o, st * jnp.exp(last) + _mm_tn(u, k * jnp.exp(last - gcum))

    return jax.vmap(value_head)(v, gcum, beta, st)


def kda_chunked(q, k, v, g, beta, state, valid=None):
    """The chunked form in plain ``jax.numpy``: :func:`_chunk` (a channel
    gate) or :func:`_chunk_scalar` (``g`` (T, H, 1)) over the heads, a
    ``lax.scan`` over the chunks.  Same arguments and results as
    :func:`kda_recurrent`; ``T`` a multiple of :data:`CHUNK`."""
    t, h = v.shape[:2]
    g, beta = _pad_identity(g.astype(_F32), beta.astype(_F32), valid)
    if g.shape[-1] == 1:
        return _chunked_scalar(q, k, v, g, beta, state)

    def chunks(x):              # (T, H, D) -> (T / C, H, C, D)
        return x.astype(_F32).reshape(t // CHUNK, CHUNK, h, -1).swapaxes(1, 2)

    def step(st, xs):
        o, st = jax.vmap(_chunk)(*xs, st)
        return st, o

    state, o = lax.scan(step, state.astype(_F32), (
        chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta[..., None])))
    return o.swapaxes(1, 2).reshape(t, h, -1), state


def _chunked_scalar(q, k, v, g, beta, state):
    """:func:`kda_chunked` under a scalar gate: ``q, k`` (T, Hk, K), ``v`` (T,
    H, V), ``g`` (T, H, 1) and ``beta`` (T, H) float32 with the pad steps made
    identities, ``state`` (H, V, K)."""
    (t, hk, _), h = q.shape, v.shape[1]
    n, per = t // CHUNK, h // hk

    def chunks(x, heads):       # (T, heads, D) -> (T / C, heads, C, D)
        return x.astype(_F32).reshape(n, CHUNK, heads, -1).swapaxes(1, 2)

    def shared(x):              # (T, H, D) -> (T / C, Hk, R, C, D)
        return chunks(x, h).reshape(n, hk, per, CHUNK, -1)

    # the running sum inside each chunk, every head at once: one product
    lower = jnp.tril(jnp.ones((CHUNK, CHUNK), _F32))
    gcum = jnp.einsum("ts,nsh->nth", lower, g.reshape(n, CHUNK, h),
                      precision=_HI).reshape(t, h, 1)

    def step(st, xs):
        o, st = jax.vmap(_chunk_scalar)(*xs, st)
        return st, o

    state, o = lax.scan(
        step, state.astype(_F32).reshape(hk, per, *state.shape[1:]), (
            chunks(q, hk), chunks(k, hk), shared(v), shared(gcum),
            shared(beta[..., None])))
    # (T / C, Hk, R, C, V) -> (T, H, V)
    return (o.reshape(n, h, CHUNK, -1).swapaxes(1, 2).reshape(t, h, -1),
            state.reshape(h, *state.shape[2:]))


def chunk_scan_formulation(chunk: int) -> str:
    """Which form :func:`kda_chunk_scan` takes at a chunk of ``chunk``
    tokens: ``"chunked"`` or, where the chunk is not whole chunks of
    :data:`CHUNK`, ``"plain"`` (the recurrence)."""
    return "plain" if chunk % CHUNK else "chunked"


def kda_chunk_scan(q, k, v, g, beta, state, valid):
    """A prefill chunk of one slot, from ``state`` (H, V, K): the form
    :func:`chunk_scan_formulation` names.  Same arguments and results as
    :func:`kda_recurrent`."""
    if chunk_scan_formulation(q.shape[0]) == "plain":
        return kda_recurrent(q, k, v, g, beta, state, valid)
    return kda_chunked(q, k, v, g, beta, state, valid)


# -- one token a slot --------------------------------------------------------

def step_formulation(heads: int, key_dim: int, value_dim: int,
                     impl: str = "auto") -> str:
    """Which form :func:`kda_step` takes: ``"kda_step"`` (the kernel) or
    ``"plain"``."""
    fits = (key_dim == LANES and value_dim == LANES
            and heads % STEP_HEADS == 0)
    return "kda_step" if use_kernel(impl) and fits else "plain"


def _step_kernel(rows_ref, v_ref, s_ref, o_ref, s_out_ref):
    """``STEP_HEADS`` heads of one slot.  ``rows_ref`` (heads, 8, K): a head's
    rows ``alpha k``, ``alpha q``, ``alpha``, ``beta k`` and ``beta k . q`` in
    every lane; ``v_ref`` (V, heads): a head's value down a column, as the
    state's rows lie (transposed outside: 2 MB a layer, where a (128, 128)
    transpose in here a grid step cost 0.4 ms a layer of 128 slots; my chip
    run, PR 52).  With the state ``(V, K)``: ``w = v - S (alpha k)``, ``S' =
    alpha S + w (beta k)^T``, ``o = S (alpha q) + w (beta k . q)``: both sums
    read the state as it came in, so a head's state passes the registers
    once."""
    for i in range(s_ref.shape[0]):
        st = s_ref[i]
        r = rows_ref[i]
        pred = (st * r[0:1]).sum(axis=1, keepdims=True)
        read = (st * r[1:2]).sum(axis=1, keepdims=True)
        w = v_ref[:, i:i + 1] - pred
        s_out_ref[i] = st * r[2:3] + w * r[3:4]
        o_ref[:, i:i + 1] = read + w * r[4:5, 0:1]


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _kda_step_call(rows, vt, pool, *, layer, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, h, dv, dk = pool.shape[1:]
    hb = STEP_HEADS
    state = pl.BlockSpec((None, None, hb, dv, dk),
                         lambda b, j: (layer, b, j, 0, 0))
    cols = pl.BlockSpec((None, None, dv, hb), lambda b, j: (b, j, 0, 0))
    o, pool = pl.pallas_call(
        _step_kernel, name="kda_step", grid=(slots, h // hb),
        in_specs=[pl.BlockSpec((None, hb, 8, dk), lambda b, j: (b, j, 0, 0)),
                  cols, state],
        out_specs=[cols, state],
        out_shape=[jax.ShapeDtypeStruct((slots, h // hb, dv, hb), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(rows, vt, pool)
    return o, pool


def kda_step(q, k, v, g, beta, pool, layer: int, *, impl="auto",
             interpret: bool | None = None):
    """One token a slot: ``q, k`` (B, Hk, K), ``v`` (B, H, V), ``g`` (B, H,
    K) or (B, H, 1), ``beta`` (B, H), against rows ``layer`` of ``pool``
    (layers, B, H, V, K) float32 -> ``(o (B, H, V) float32, pool)``.  The
    kernel takes the whole array and touches only the layer's blocks (aliased
    in and out); the plain form reads the layer and sets it back.  A scalar
    gate is broadcast along the key's channels into the kernel's rows (2 MB a
    layer of 128 slots beside the states themselves)."""
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    q, k = _share_heads(q, k, v.shape[1])
    slots, h, dk = q.shape
    dv = v.shape[-1]
    if step_formulation(h, dk, dv, impl) == "plain":
        st, o = _token(pool[layer], q, k, v, g, beta)
        return o, pool.at[layer].set(st)
    if interpret is None:
        interpret = not on_tpu()
    alpha = jnp.broadcast_to(jnp.exp(g), q.shape)
    bk = beta[..., None] * k
    kq = jnp.broadcast_to((bk * q).sum(-1, keepdims=True), q.shape)
    rows = jnp.stack([alpha * k, alpha * q, alpha, bk, kq,
                      *[jnp.zeros_like(q)] * 3], axis=2)     # (B, H, 8, K)
    hb = STEP_HEADS
    vt = v.reshape(slots, h // hb, hb, dv).swapaxes(2, 3)
    o, pool = _kda_step_call(rows, vt, pool, layer=layer, interpret=interpret)
    return o.swapaxes(2, 3).reshape(slots, h, dv), pool
