"""Fused LayerNorm: one-pass Pallas kernels, bf16 IO, fp32 statistics.

The reference stack's LayerNorm is Keras ``LayerNormalization``
(keras/src/layers/normalization/layer_normalization.py) compiled by XLA
as separate reduce + apply fusions.  Our models' pre-LN trunks ran the
same way (flax ``nn.LayerNorm(dtype=float32)``): the input is read once
for the statistics reduce and again for the normalize, with an fp32
promotion in between (the multiply_reduce / convert_reduce fusion
families of a profile).

These kernels read each ``(block_n, D)`` tile ONCE: mean/var/normalize
happen VMEM-resident in fp32 and only the normalized output returns to
HBM.  The backward recomputes the row statistics from the saved input
instead of storing them — per-row mean/rstd live on the sublane axis,
where flushing them to an (N,) output would cost a lane relayout per
tile, while recomputing them is two lane-reductions over a tile the
backward already holds.

Semantics match ``nn.LayerNorm(dtype=float32)`` followed by a cast to
``out_dtype``: statistics and normalization in fp32 regardless of input
dtype, one rounding at the end.  ``tests/test_layernorm.py`` pins value
and gradient equivalence against the flax reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import shard_kernel, token_spec
from ..runtime import on_tpu

#: Token rows per grid step.  VMEM: the fp32 x tile plus 2-3 fp32
#: temporaries at (block_n, D) — 512 x 768 keeps the bundle ~7 MB,
#: comfortably inside Mosaic's 16 MB scoped stack at GPT-2 widths.
BLOCK_TOKENS = 512


def _env_block() -> int:
    import os

    return int(os.environ.get("DTFT_LN_BLOCK_TOKENS", BLOCK_TOKENS))


def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (xc * rstd) * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(x_ref, g_ref, dy_ref, dx_ref, dg_ref, db_ref, *, eps):
    """dx for this token block; dγ/dβ accumulated into the single
    (1, D) output blocks, whose index is constant across the grid — the
    consecutive-revisit pattern Pallas TPU keeps resident (same as the
    fused-xent dw kernel)."""
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    dy = dy_ref[...].astype(jnp.float32)
    a = dy * g_ref[...].astype(jnp.float32)
    c1 = jnp.mean(a, axis=1, keepdims=True)
    c2 = jnp.mean(a * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (a - c1 - xhat * c2)).astype(dx_ref.dtype)
    pg = jnp.sum(dy * xhat, axis=0, keepdims=True)  # (1, D)
    pb = jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _first():
        dg_ref[...] = pg
        db_ref[...] = pb

    @pl.when(i != 0)
    def _rest():
        dg_ref[...] = dg_ref[...] + pg
        db_ref[...] = db_ref[...] + pb


def _pad_rows(x, block):
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x


def _row_specs(block_n, d, mem):
    return [
        pl.BlockSpec((block_n, d), lambda i: (i, 0), memory_space=mem),
        pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=mem),
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ln(x2, g, b, eps, out_dtype, interpret):
    y, _ = _fused_ln_fwd(x2, g, b, eps, out_dtype, interpret)
    return y


def _fused_ln_fwd(x2, g, b, eps, out_dtype, interpret):
    n, d = x2.shape
    block = _env_block()
    xp = _pad_rows(x2, block)
    np_ = xp.shape[0]
    mem = pl.ANY if interpret else pltpu.VMEM
    y = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        name="layer_norm_fwd",
        grid=(np_ // block,),
        in_specs=_row_specs(block, d, mem)
        + [pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=mem)],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0),
                               memory_space=mem),
        out_shape=jax.ShapeDtypeStruct((np_, d), out_dtype),
        interpret=interpret,
    )(xp, g.reshape(1, d), b.reshape(1, d))
    return y[:n], (x2, g)


def _fused_ln_bwd(eps, out_dtype, interpret, res, dy):
    x2, g = res
    n, d = x2.shape
    block = _env_block()
    xp = _pad_rows(x2, block)
    dyp = _pad_rows(dy.astype(jnp.float32), block)
    np_ = xp.shape[0]
    mem = pl.ANY if interpret else pltpu.VMEM
    dx, dg, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        name="layer_norm_bwd",
        grid=(np_ // block,),
        in_specs=_row_specs(block, d, mem)
        + [pl.BlockSpec((block, d), lambda i: (i, 0), memory_space=mem)],
        out_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0), memory_space=mem),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=mem),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, d), x2.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(xp, g.reshape(1, d), dyp)
    return (dx[:n], dg.reshape(d).astype(g.dtype),
            db.reshape(d).astype(g.dtype))


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def _xla_layer_norm(x, scale, bias, eps, out_dtype):
    """Reference path (off-TPU and golden tests): fp32 statistics and
    normalize, one rounding to ``out_dtype`` — the exact semantics of
    ``nn.LayerNorm(dtype=float32)(x).astype(out_dtype)``."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(out_dtype)


def layer_norm(
    x: jax.Array,            # (..., D)
    scale: jax.Array,        # (D,)
    bias: jax.Array,         # (D,)
    *,
    eps: float = 1e-6,  # matches flax nn.LayerNorm
    out_dtype=None,          # None = x.dtype
    impl: str = "auto",      # "auto" | "xla" | "pallas"
    interpret: bool | None = None,
) -> jax.Array:
    """LayerNorm over the last axis; fp32 stats, one output rounding.

    ``impl="auto"`` takes the Pallas kernel on TPU and the XLA reference
    elsewhere (interpret-mode Pallas on CPU is for tests, not the
    training path — models run the XLA form there at full speed).
    """
    out_dtype = out_dtype or x.dtype
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl == "xla":
        return _xla_layer_norm(x, scale, bias, eps, out_dtype)
    if interpret is None:
        interpret = not on_tpu()

    def local(x, g, b):
        y = _fused_ln(x.reshape(-1, x.shape[-1]), g, b, eps, out_dtype,
                      interpret)
        return y.reshape(x.shape)

    x_spec = token_spec(x.shape[:-1], trailing=1)
    return shard_kernel(local, (x_spec, P(), P()), x_spec)(
        x, scale.astype(jnp.float32), bias.astype(jnp.float32)
    )

