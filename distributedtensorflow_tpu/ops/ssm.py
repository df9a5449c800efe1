"""Selective state-space scan (Mamba-1): what a slot's state is, and three
forms of one mathematics.

A Mamba layer keeps, a sequence, a *fixed-size* state whatever the context:
the scan state ``s`` of ``d_state`` values a channel, and the last ``d_conv -
1`` inputs of its causal depthwise convolution (the *convolution tail*).
:class:`SSMState` states both (a model's ``cfg.state_rows``, beside
``ops.attention.KVRows`` / ``LatentRows`` for what a layer caches a token);
:class:`ConvTail` is the state of a layer that keeps the tail and has no scan
(``models.lfm2``'s gated short convolution); :class:`DeltaState` that of a
Kimi-Delta-Attention layer (``models.ling``): three tails and a matrix a head,
whose forms are ``ops.kda``'s; :class:`GatedDeltaState` that of a Gated
DeltaNet layer (``models.qwen3_next``): one tail over ``[q | k | v]`` and a
matrix a value head, the same forms under a scalar gate; :class:`SSDState`
that of a Mamba-2 layer (``models.nemotron_h``): one tail and a matrix a head,
whose forms are ``ops.ssd``'s.

The recurrence, a token ``t``, channels ``c`` and states ``n``, in float32::

    s_t[n, c] = exp(delta_t[c] * A[n, c]) * s_{t-1}[n, c]
                + delta_t[c] * u_t[c] * B_t[n]
    y_t[c]    = sum_n s_t[n, c] * C_t[n] + D[c] * u_t[c]

The decay is per (channel, state), so there is no matmul form (the chunked
products of Mamba-2 need a scalar decay a head, and ``ops.ssd`` has them); a
parallel form materialises ``(T, N, C)`` float32 operands in HBM.  The state
is laid out ``(N, C)``: **channels across lanes, states across sublanes**
(``A`` is stored so too, the published ``A_log`` transposed).  A step with
``delta = 0`` is the identity on the state (``exp(0) = 1``, ``0 * u * B =
0``): that is how positions past ``valid`` are padded, in every form.

- :func:`selective_scan`: the plain ``lax.scan`` over tokens: the yardstick
  of the tests and the path off the TPU;
- :func:`ssm_step`: one token for every slot, ``(slots, N, C)`` states
  (decode; XLA fuses it);
- :func:`ssm_chunk_scan` on the kernel path (``name="ssm_chunk_scan"``): a
  chunk of one slot.  Grid over blocks of ``SCAN_ROWS`` tokens; the state
  stays in VMEM (the resident output block) through the whole chunk and in
  vector registers through a block, 512 channels at a time; ``exp(delta *
  A)`` and ``delta * u * B`` are formed in registers and never reach HBM.
  ``B`` and ``C`` come in already spread over the 128 lanes (``(T, N, 128)``:
  16 MB of float32 a chunk of 1024 for both, written once by XLA and read
  once by the kernel: a lane broadcast of a sublane column a step costs the
  kernel more).  Takes the state in and gives the state out.

:func:`causal_conv` / :func:`conv_step` are the convolution with its tail.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..runtime import on_tpu, use_kernel
from . import kda, ssd

LANES = 128
#: tokens a grid step of the kernel (a multiple of 8)
SCAN_ROWS = 64
#: channels whose state a loop of the kernel holds in registers
SCAN_LANES = 512


def _tail_array(channels: int, d_conv: int, dtype):
    """The convolution tail a slot a layer: ``d_conv - 1`` inputs of
    ``channels`` in the activations' type, stored as one row (the oldest
    input first: with the slots of a layer on the sublanes every tile is
    full, where ``(d_conv - 1, channels)`` a slot would pad 3 rows to a
    tile's 16)."""
    return (((d_conv - 1) * channels,), jnp.dtype(dtype))


class _SlotArrays:
    """What the state forms share: ``arrays(dtype)`` lists a slot's
    arrays a layer in pool order, ``names`` names them in the same order."""

    def slot_bytes(self, dtype) -> int:
        """Bytes a slot a layer."""
        return sum(math.prod(shape) * dt.itemsize
                   for shape, dt in self.arrays(dtype))


@dataclasses.dataclass(frozen=True)
class SSMState(_SlotArrays):
    """What a Mamba layer keeps a slot: the convolution tail
    (:func:`_tail_array`) and the scan state ``(d_state, channels)`` in
    float32."""

    channels: int
    d_state: int
    d_conv: int

    names = ("conv_tail", "scan_state")

    def arrays(self, dtype) -> tuple[tuple[tuple[int, ...], jnp.dtype], ...]:
        """(shape a slot a layer, dtype) of each state array, in pool order:
        the convolution tail, the scan state."""
        return (_tail_array(self.channels, self.d_conv, dtype),
                ((self.d_state, self.channels), jnp.dtype(jnp.float32)))

    def chunk_formulation(self, chunk: int, impl: str) -> str:
        return chunk_scan_formulation(self.channels, self.d_state, chunk,
                                      impl)


@dataclasses.dataclass(frozen=True)
class ConvTail(_SlotArrays):
    """What a layer that is a short causal convolution and nothing else
    keeps a slot (``models.lfm2``): the convolution tail alone, one array.
    There is no scan, so a chunk has no scan formulation."""

    channels: int
    d_conv: int

    names = ("conv_tail",)

    def arrays(self, dtype) -> tuple[tuple[tuple[int, ...], jnp.dtype], ...]:
        return (_tail_array(self.channels, self.d_conv, dtype),)

    def chunk_formulation(self, chunk: int, impl: str) -> None:
        return None


@dataclasses.dataclass(frozen=True)
class DeltaState(_SlotArrays):
    """What a Kimi-Delta-Attention layer keeps a slot (``ops.kda``): the
    convolution tails of its q, k and v (``heads * key_dim`` channels for q
    and k, ``heads * value_dim`` for v) and the matrix state a head in
    float32, stored transposed: ``(heads, value_dim, key_dim)``, the key's
    channels across lanes."""

    heads: int
    key_dim: int
    value_dim: int
    d_conv: int

    names = ("q_tail", "k_tail", "v_tail", "delta_state")

    def arrays(self, dtype) -> tuple[tuple[tuple[int, ...], jnp.dtype], ...]:
        qk = _tail_array(self.heads * self.key_dim, self.d_conv, dtype)
        return (qk, qk,
                _tail_array(self.heads * self.value_dim, self.d_conv, dtype),
                ((self.heads, self.value_dim, self.key_dim),
                 jnp.dtype(jnp.float32)))

    def chunk_formulation(self, chunk: int, impl: str) -> str:
        return kda.chunk_scan_formulation(chunk)

    def step_formulation(self, impl: str) -> str:
        return kda.step_formulation(self.heads, self.key_dim, self.value_dim,
                                    impl)


@dataclasses.dataclass(frozen=True)
class GatedDeltaState(_SlotArrays):
    """What a Gated DeltaNet layer keeps a slot (``ops.kda`` under a scalar
    gate): the tail of its one convolution over ``[q | k | v]`` (``2 *
    key_heads * key_dim + heads * value_dim`` channels) and the matrix state a
    *value* head in float32, stored transposed as :class:`DeltaState`'s:
    ``(heads, value_dim, key_dim)``."""

    key_heads: int
    heads: int
    key_dim: int
    value_dim: int
    d_conv: int

    names = ("conv_tail", "delta_state")

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_heads * self.key_dim + self.heads * self.value_dim

    def arrays(self, dtype) -> tuple[tuple[tuple[int, ...], jnp.dtype], ...]:
        return (_tail_array(self.conv_channels, self.d_conv, dtype),
                ((self.heads, self.value_dim, self.key_dim),
                 jnp.dtype(jnp.float32)))

    def chunk_formulation(self, chunk: int, impl: str) -> str:
        return kda.chunk_scan_formulation(chunk)

    def step_formulation(self, impl: str) -> str:
        return kda.step_formulation(self.heads, self.key_dim, self.value_dim,
                                    impl)


@dataclasses.dataclass(frozen=True)
class SSDState(_SlotArrays):
    """What a Mamba-2 layer keeps a slot (``ops.ssd``): the convolution tail
    of its ``[x | B | C]`` (``heads * head_dim + 2 * groups * d_state``
    channels) and the matrix state a head in float32, ``(heads, head_dim,
    d_state)``: the states across lanes."""

    heads: int
    head_dim: int
    groups: int
    d_state: int
    d_conv: int

    names = ("conv_tail", "ssd_state")

    @property
    def conv_channels(self) -> int:
        return self.heads * self.head_dim + 2 * self.groups * self.d_state

    def arrays(self, dtype) -> tuple[tuple[tuple[int, ...], jnp.dtype], ...]:
        return (_tail_array(self.conv_channels, self.d_conv, dtype),
                ((self.heads, self.head_dim, self.d_state),
                 jnp.dtype(jnp.float32)))

    def chunk_formulation(self, chunk: int, impl: str) -> str:
        return ssd.chunk_scan_formulation(chunk)

    def step_formulation(self, impl: str) -> str:
        return ssd.step_formulation(self.heads, self.head_dim, self.groups,
                                    self.d_state, impl)


# -- the convolution and its tail --------------------------------------------

def causal_conv(u, tail, w, b, valid):
    """Causal depthwise convolution of a chunk ``u`` (T, C) that continues
    ``tail`` ((K - 1) * C,), the inputs before it, oldest first: ``out[t] = b
    + sum_k w[k] * x[t + k]`` over ``x = [tail; u]``, ``w`` (K, C).  Returns
    ``(out (T, C), new tail)``: the ``K - 1`` inputs that end at token ``valid
    - 1`` (rows of the old tail where ``valid < K - 1``), so pad positions
    leave no trace."""
    k, (t, c) = w.shape[0], u.shape
    x = jnp.concatenate([tail.reshape(k - 1, c).astype(u.dtype), u], axis=0)
    out = b.astype(jnp.float32)
    for i in range(k):
        out = out + w[i].astype(jnp.float32) * x[i:i + t].astype(jnp.float32)
    new_tail = jax.lax.dynamic_slice_in_dim(x, valid, k - 1, axis=0)
    return out.astype(u.dtype), new_tail.reshape(-1).astype(tail.dtype)


def conv_step(u, tails, w, b):
    """One token a slot: ``u`` (B, C) after ``tails`` (B, (K - 1) * C).
    Returns ``(out (B, C), new tails)``.  Whole-lane slices only: the tails
    are never reshaped."""
    k, c = w.shape[0], u.shape[1]
    f32 = jnp.float32
    out = b.astype(f32) + w[k - 1].astype(f32) * u.astype(f32)
    for i in range(k - 1):
        out = out + w[i].astype(f32) * tails[:, i * c:(i + 1) * c].astype(f32)
    new_tails = jnp.concatenate([tails[:, c:], u.astype(tails.dtype)], axis=1)
    return out.astype(u.dtype), new_tails


# -- the scan: plain forms ---------------------------------------------------

def selective_scan(u, delta, a, b, c, d, state, valid=None):
    """The plain form: ``u``, ``delta`` (T, C), ``a`` (N, C), ``b``, ``c``
    (T, N), ``d`` (C,), ``state`` (N, C) float32 -> ``(y (T, C) float32, state
    out)``.  Steps at ``t >= valid`` leave the state as it is."""
    if valid is not None:
        real = jnp.arange(delta.shape[0], dtype=jnp.int32) < valid
        delta = jnp.where(real[:, None], delta, 0.0)
    a = a.astype(jnp.float32)

    def step(s, xs):
        u_t, dt, b_t, c_t = xs
        s = jnp.exp(dt[None, :] * a) * s + (dt * u_t)[None, :] * b_t[:, None]
        return s, (s * c_t[:, None]).sum(0)

    f32 = jnp.float32
    state, y = jax.lax.scan(
        step, state.astype(f32),
        (u.astype(f32), delta.astype(f32), b.astype(f32), c.astype(f32)))
    return y + d.astype(f32) * u.astype(f32), state


def ssm_step(u, delta, a, b, c, d, states):
    """One token a slot: ``u``, ``delta`` (B, C), ``b``, ``c`` (B, N),
    ``states`` (B, N, C) float32 -> ``(y (B, C) float32, states out)``."""
    f32 = jnp.float32
    u, delta = u.astype(f32), delta.astype(f32)
    decay = jnp.exp(delta[:, None, :] * a.astype(f32)[None])
    states = decay * states + (delta * u)[:, None, :] \
        * b.astype(f32)[:, :, None]
    y = (states * c.astype(f32)[:, :, None]).sum(1)
    return y + d.astype(f32) * u, states


# -- the scan: the kernel ----------------------------------------------------

def chunk_scan_formulation(channels: int, d_state: int, chunk: int,
                           impl: str = "auto") -> str:
    """Which form :func:`ssm_chunk_scan` takes at these shapes:
    ``"ssm_chunk_scan"`` (the kernel) or ``"plain"`` (``lax.scan``).  A test
    of shapes and of ``impl`` alone, so a program can say what it was built
    with (``serve.model``)."""
    fits = (channels % LANES == 0 and d_state % 8 == 0
            and chunk % SCAN_ROWS == 0)
    return "ssm_chunk_scan" if use_kernel(impl) and fits else "plain"


def _scan_kernel(valid_ref, u_ref, dt_ref, bx_ref, cx_ref, a_ref, d_ref,
                 s0_ref, y_ref, s_ref, *, lane_chunk):
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    rows, channels = u_ref.shape
    valid = valid_ref[0]

    @pl.when(step == 0)
    def _():
        s_ref[...] = s0_ref[...]

    for c0 in range(0, channels, lane_chunk):
        width = min(lane_chunk, channels - c0)
        lanes = [pl.ds(c0 + i * LANES, LANES) for i in range(width // LANES)]
        a = [a_ref[:, ln] for ln in lanes]
        dd = [d_ref[:, ln] for ln in lanes]

        def group(g, s, lanes=lanes, a=a, dd=dd):
            r0 = pl.multiple_of(g * 8, 8)
            here = pl.ds(r0, 8)
            t = step * rows + r0 + jax.lax.broadcasted_iota(
                jnp.int32, (8, LANES), 0)
            u8 = [u_ref[here, ln] for ln in lanes]
            # a step past ``valid`` has delta 0: the identity on the state
            dt8 = [jnp.where(t < valid, dt_ref[here, ln], 0.0)
                   for ln in lanes]
            du8 = [x * y for x, y in zip(dt8, u8)]
            ys = [[] for _ in lanes]
            s = list(s)
            for j in range(8):
                bj, cj = bx_ref[r0 + j], cx_ref[r0 + j]    # (N, 128)
                for i in range(len(lanes)):
                    s[i] = jnp.exp(dt8[i][j:j + 1] * a[i]) * s[i] \
                        + du8[i][j:j + 1] * bj
                    ys[i].append((s[i] * cj).sum(axis=0, keepdims=True))
            for i, ln in enumerate(lanes):
                y_ref[here, ln] = jnp.concatenate(ys[i], axis=0) \
                    + dd[i] * u8[i]
            return tuple(s)

        s = jax.lax.fori_loop(0, rows // 8, group,
                              tuple(s_ref[:, ln] for ln in lanes))
        for i, ln in enumerate(lanes):
            s_ref[:, ln] = s[i]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(valid, u, delta, bx, cx, a, d, state, *, interpret):
    """The kernel's call: a jitted function of its own, so that the layers
    of a program that call it at the same shapes share one lowering."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, channels = u.shape
    n = a.shape[0]
    rows = pl.BlockSpec((SCAN_ROWS, channels), lambda i, *_: (i, 0))
    spread = pl.BlockSpec((SCAN_ROWS, n, LANES), lambda i, *_: (i, 0, 0))
    whole = pl.BlockSpec((n, channels), lambda i, *_: (0, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, lane_chunk=SCAN_LANES),
        name="ssm_chunk_scan",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(t // SCAN_ROWS,),
            in_specs=[rows, rows, spread, spread, whole,
                      pl.BlockSpec((1, channels), lambda i, *_: (0, 0)),
                      whole],
            out_specs=[rows, whole]),
        out_shape=[jax.ShapeDtypeStruct((t, channels), jnp.float32),
                   jax.ShapeDtypeStruct((n, channels), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(valid, u, delta, bx, cx, a, d, state)


def ssm_chunk_scan(u, delta, a, b, c, d, state, valid, *, impl="auto",
                   interpret: bool | None = None):
    """A chunk of one slot, from ``state`` (N, C): the kernel where
    :func:`chunk_scan_formulation` says so, else :func:`selective_scan`.
    Same arguments and results as the plain form."""
    t, channels = u.shape
    n = a.shape[0]
    if chunk_scan_formulation(channels, n, t, impl) == "plain":
        return selective_scan(u, delta, a, b, c, d, state, valid)
    if interpret is None:
        interpret = not on_tpu()
    f32 = jnp.float32

    def spread(x):      # (T, N) -> (T, N, 128): a state's value in every lane
        return jnp.broadcast_to(x.astype(f32)[:, :, None], (t, n, LANES))

    y, state = _scan_call(
        jnp.asarray(valid, jnp.int32).reshape(1), u.astype(f32),
        delta.astype(f32), spread(b), spread(c), a.astype(f32),
        d.astype(f32).reshape(1, channels), state.astype(f32),
        interpret=interpret)
    return y, state
