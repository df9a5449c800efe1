"""Mamba-2's state-space duality: a matrix state a head under a *scalar* decay
a head, in three forms of one mathematics.

A Mamba-2 head keeps, a sequence, a state ``S`` of ``head_dim x d_state``
float32 values whatever the context (:class:`ops.ssm.SSDState` states it
beside the one convolution tail of its ``[x | B | C]``).  ``H`` heads of ``P =
head_dim``, ``G`` groups whose ``B`` and ``C`` (``N = d_state`` wide) the ``H /
G`` consecutive heads of a group share; a token ``t``, in float32::

    a_t = exp(dt_t * A)                       dt_t > 0, A < 0: scalars a head
    S_t = a_t S_{t-1} + dt_t x_t (outer) B_t  S: (P, N)
    y_t = S_t C_t + D x_t

Mamba-1's decay is a (channel, state) and has no matmul form
(``ops.ssm.selective_scan``); a scalar a head does: the state after a run of
tokens is a product of the run's inputs with decay ratios, and the ratios are
differences of one running log-sum.  The state is stored ``(H, P, N)``: the
``N`` states across lanes, so ``B`` and ``C`` broadcast along sublanes and the
chunked form's products with the state contract lanes.

Every form takes ``x`` ``(T, H, P)``, ``dt`` ``(T, H)`` (after its softplus),
``a`` ``(H,)`` (``A``, negative), ``b``, ``c`` ``(T, G, N)``, ``d`` ``(H,)``
and the state, computes in float32 and returns ``(y (T, H, P) float32, state
out)``.  A token with ``dt = 0`` is the identity on the state (``a = 1``, an
update of 0): that is how positions past ``valid`` and inactive slots are
padded, in every form.

- :func:`ssd_recurrent`: the recurrence by ``lax.scan`` over tokens: the
  yardstick of the tests and the plain form on any backend;
- :func:`ssd_step`: one token for every slot against the layer's rows of the
  group's array (decode).  On the kernel path (``name="ssd_step"``) a Pallas
  kernel over ``(slot, group)`` streams the state of a group's heads through
  VMEM once — read, update, write back in place (the array is aliased in and
  out: no copy of the pool) — and everything else it reads is a row a group
  and a column a head.  The plain form reads the layer and sets it back, and
  XLA passes over the state twice (the update, then the output's reduction:
  2.44 ms a layer at 128 slots of 128 x 64 x 128 where the kernel reads 2.26,
  58 % of HBM: a head of 64 rows costs it a lane broadcast and a lane
  reduction a vreg of state; my chip runs, PR 54);
- :func:`ssd_chunked`: a prefill chunk of one slot in chunks of :data:`CHUNK`
  tokens (the model's ``chunk_size``), plain ``jax.numpy``.  With ``l_t`` the
  running sum of ``dt A`` from a chunk's start (non-positive, falling) and
  ``S_0`` the state the chunk starts from::

      Y = ((C B^T) * L * dt) X + exp(l) (C S_0^T)     L[t, s] = exp(l_t - l_s), s <= t
      S_Q = exp(l_Q) S_0 + (X * dt exp(l_Q - l))^T B

  ``C B^T`` is a group's (16 heads share one ``(Q, Q)`` product), ``L`` a
  head's; every exponent is a difference ``l_t - l_s`` with ``s <= t``, so at
  most 0: nothing overflows and what underflows is a ratio under e^-87.  The
  chunks' own terms are batched products over ``(chunk, head)``; only ``S_0``
  of each chunk is carried, by a ``lax.scan`` of one multiply-add a chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..runtime import on_tpu, use_kernel

LANES = 128
#: tokens a chunk of the chunked form: the published ``chunk_size``
CHUNK = 128

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def _pad_identity(dt, valid):
    """``dt`` (T, H) with the tokens at ``t >= valid`` made identity steps."""
    if valid is None:
        return dt
    real = jnp.arange(dt.shape[0], dtype=jnp.int32) < valid
    return jnp.where(real[:, None], dt, 0.0)


# -- the recurrence ----------------------------------------------------------

def _token(st, x, dt, a, b, c, d):
    """One token of every head (or slot and head): ``st`` (..., G, H/G, P, N),
    ``x`` (..., G, H/G, P), ``dt`` (..., G, H/G), ``a``, ``d`` (G, H/G), ``b``,
    ``c`` (..., G, N)."""
    decay = jnp.exp(dt * a)
    st = decay[..., None, None] * st \
        + (dt[..., None] * x)[..., None] * b[..., None, None, :]
    y = (st * c[..., None, None, :]).sum(-1) + d[..., None] * x
    return st, y


def ssd_recurrent(x, dt, a, b, c, d, state, valid=None):
    """The plain form: ``x`` (T, H, P), ``dt`` (T, H), ``a``, ``d`` (H,), ``b``,
    ``c`` (T, G, N), ``state`` (H, P, N) float32 -> ``(y (T, H, P) float32,
    state out)``.  Steps at ``t >= valid`` leave the state as it is."""
    t, h, p = x.shape
    g = b.shape[1]
    dt = _pad_identity(dt.astype(_F32), valid)
    ag, dg = (v.astype(_F32).reshape(g, h // g) for v in (a, d))

    def step(st, xs):
        x_t, dt_t, b_t, c_t = xs
        return _token(st, x_t, dt_t, ag, b_t, c_t, dg)

    state, y = lax.scan(
        step, state.astype(_F32).reshape(g, h // g, p, -1),
        (x.astype(_F32).reshape(t, g, h // g, p), dt.reshape(t, g, h // g),
         b.astype(_F32), c.astype(_F32)))
    return y.reshape(t, h, p), state.reshape(h, p, -1)


# -- the chunked form --------------------------------------------------------

def ssd_chunked(x, dt, a, b, c, d, state, valid=None, chunk: int = CHUNK):
    """The chunked form in plain ``jax.numpy`` (module text).  Same arguments
    and results as :func:`ssd_recurrent`; ``T`` a multiple of ``chunk``."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    q, k, i = chunk, t // chunk, h // g
    dt = _pad_identity(dt.astype(_F32), valid)
    # chunks and heads lead, so that every product is one batched matmul
    xs = x.astype(_F32).reshape(k, q, g, i, p).transpose(0, 2, 3, 1, 4)
    dts = dt.reshape(k, q, g, i).transpose(0, 2, 3, 1)      # (k, g, i, q)
    bs, cs = (v.astype(_F32).reshape(k, q, g, n).swapaxes(1, 2)
              for v in (b, c))                              # (k, g, q, n)
    # the running log-sum of the decay from a chunk's start: <= 0, falling
    run = jnp.cumsum(dts * a.astype(_F32).reshape(g, i, 1), axis=-1)
    last = run[..., -1]                                     # (k, g, i)

    # inside a chunk: ((C B^T) * L * dt) X
    cb = jnp.einsum("kgtn,kgsn->kgts", cs, bs, precision=_HI)
    ratio = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)),
                              run[..., :, None] - run[..., None, :], -jnp.inf))
    y = jnp.einsum("kgits,kgisp->kgitp",
                   cb[:, :, None] * ratio * dts[..., None, :], xs,
                   precision=_HI)

    # what a chunk adds to the state it ends with, and how much it keeps of
    # the one it starts from
    into = jnp.exp(last[..., None] - run) * dts             # (k, g, i, q)
    local = jnp.einsum("kgiqp,kgqn->kgipn", xs * into[..., None], bs,
                       precision=_HI)

    def carry(st, chunk_):
        keep, add = chunk_
        return keep[..., None, None] * st + add, st

    state, starts = lax.scan(carry, state.astype(_F32).reshape(g, i, p, n),
                             (jnp.exp(last), local))
    # from the state a chunk starts with: exp(l) (C S_0^T)
    y = y + jnp.einsum("kgqn,kgipn->kgiqp", cs, starts, precision=_HI) \
        * jnp.exp(run)[..., None]
    y = y.transpose(0, 3, 1, 2, 4).reshape(t, h, p)
    return y + d.astype(_F32)[:, None] * x.astype(_F32), state.reshape(h, p, n)


def chunk_scan_formulation(chunk: int) -> str:
    """Which form :func:`ssd_chunk_scan` takes at a prefill chunk of ``chunk``
    tokens: ``"chunked"`` or, where it is not whole chunks of :data:`CHUNK`,
    ``"plain"`` (the recurrence)."""
    return "plain" if chunk % CHUNK else "chunked"


def ssd_chunk_scan(x, dt, a, b, c, d, state, valid):
    """A prefill chunk of one slot, from ``state`` (H, P, N): the form
    :func:`chunk_scan_formulation` names.  Same arguments and results as
    :func:`ssd_recurrent`."""
    if chunk_scan_formulation(x.shape[0]) == "plain":
        return ssd_recurrent(x, dt, a, b, c, d, state, valid)
    return ssd_chunked(x, dt, a, b, c, d, state, valid)


# -- one token a slot --------------------------------------------------------

def step_formulation(heads: int, head_dim: int, groups: int, d_state: int,
                     impl: str = "auto") -> str:
    """Which form :func:`ssd_step` takes: ``"ssd_step"`` (the kernel) or
    ``"plain"``."""
    fits = d_state == LANES and head_dim % 8 == 0 and heads % groups == 0
    return "ssd_step" if use_kernel(impl) and fits else "plain"


def _step_kernel(b_ref, c_ref, decay_ref, u_ref, s_ref, y_ref, s_out_ref):
    """The heads of one group of one slot.  ``b_ref``, ``c_ref`` (G, N): the
    slot's ``B`` and ``C``, a row a group; ``decay_ref`` (1, heads): ``exp(dt
    A)``; ``u_ref`` (P, heads): a head's ``dt x`` down a column, as the
    state's rows lie (transposed outside, as ``ops.kda``'s step takes its
    values); ``s_ref`` (heads, P, N).  ``S' = decay S + u B^T``, ``y = S' C``:
    a head's state passes the registers once."""
    from jax.experimental import pallas as pl

    group = pl.program_id(1)
    b = b_ref[pl.ds(group, 1), :]
    c = c_ref[pl.ds(group, 1), :]
    for i in range(s_ref.shape[0]):
        st = s_ref[i] * decay_ref[:, i:i + 1] + u_ref[:, i:i + 1] * b
        s_out_ref[i] = st
        y_ref[:, i:i + 1] = (st * c).sum(axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _ssd_step_call(b, c, decay, ut, pool, *, layer, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, h, p, n = pool.shape[1:]
    g = b.shape[1]
    per = h // g
    state = pl.BlockSpec((None, None, per, p, n),
                         lambda s, j: (layer, s, j, 0, 0))
    rows = pl.BlockSpec((None, g, n), lambda s, j: (s, 0, 0))
    cols = pl.BlockSpec((None, None, p, per), lambda s, j: (s, j, 0, 0))
    y, pool = pl.pallas_call(
        _step_kernel, name="ssd_step", grid=(slots, g),
        in_specs=[rows, rows,
                  pl.BlockSpec((None, None, 1, per),
                               lambda s, j: (s, j, 0, 0)),
                  cols, state],
        out_specs=[cols, state],
        out_shape=[jax.ShapeDtypeStruct((slots, g, p, per), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(b, c, decay, ut, pool)
    return y, pool


def ssd_step(x, dt, a, b, c, d, pool, layer: int, *, impl="auto",
             interpret: bool | None = None):
    """One token a slot: ``x`` (B, H, P), ``dt`` (B, H), ``b``, ``c`` (B, G,
    N), against rows ``layer`` of ``pool`` (layers, B, H, P, N) float32 ->
    ``(y (B, H, P) float32, pool)``.  A slot with ``dt = 0`` keeps its state
    bit for bit (``1 * S + 0``), so the caller masks ``dt`` and no pass over
    the array selects afterwards.  The kernel takes the whole array and
    touches only the layer's blocks (aliased in and out); the plain form
    reads the layer and sets it back."""
    slots, h, p = x.shape
    g, n = b.shape[1:]
    x, dt, a, b, c, d = (v.astype(_F32) for v in (x, dt, a, b, c, d))
    if step_formulation(h, p, g, n, impl) == "plain":
        st, y = _token(
            pool[layer].reshape(slots, g, h // g, p, n),
            x.reshape(slots, g, h // g, p), dt.reshape(slots, g, h // g),
            a.reshape(g, h // g), b, c, d.reshape(g, h // g))
        return y.reshape(slots, h, p), pool.at[layer].set(
            st.reshape(pool.shape[1:]))
    if interpret is None:
        interpret = not on_tpu()
    per = h // g
    decay = jnp.exp(dt * a).reshape(slots, g, 1, per)
    ut = (dt[..., None] * x).reshape(slots, g, per, p).swapaxes(2, 3)
    y, pool = _ssd_step_call(b, c, decay, ut, pool, layer=layer,
                             interpret=interpret)
    return y.swapaxes(2, 3).reshape(slots, h, p) + d[:, None] * x, pool
