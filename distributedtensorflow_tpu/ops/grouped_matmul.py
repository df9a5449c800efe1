"""Grouped matmuls of an expert layer: every row tile belongs to one expert.

``parallel.moe.group_plan`` sorts the routed (token, choice) pairs by
expert and pads each expert's rows to whole tiles, so the row buffer is a
sequence of ``tile``-row tiles, ``tile_expert[i]`` naming the expert whose
matrices tile ``i`` multiplies, the first ``tiles_used`` of them real.  The
two kernels here walk that list with it prefetched into SMEM: the weight
block of a grid step is picked by ``tile_expert`` in the index map, so an
expert no tile names is never read, an expert several tiles name is read
once a tile, and the steps behind ``tiles_used`` ask for the block the last
used step held — which the pipeline does not fetch again — and write zeros.

``moe_grouped_up``: ``silu(x @ Wg[e]) * (x @ Wu[e])``, both products in
one pass over ``x``, or for an ungated expert (``grouped_relu2``)
``relu(x @ Wu[e]) ** 2``, one product; ``moe_grouped_down``: ``h @ Wd[e]``.  The contraction is
whole in one block (``K`` is a model width, a few thousand), so there is no
accumulator; the output is tiled along ``N``.  At decode a tile holds one
or two real rows: the kernels are bound by the weight bytes they stream,
which is the point — they stream the hit experts and nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import runtime

__all__ = ["grouped_swiglu", "grouped_relu2"]

#: N-width of one weight block: 3072 x 256 bf16 is 1.5 MB, two operands
#: double-buffered 6 MB, inside the 16 MB of scoped VMEM.
BLOCK_N = 256


def _up_kernel(te_ref, used_ref, x_ref, wg_ref, wu_ref, o_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i < used_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _up_relu2_kernel(te_ref, used_ref, x_ref, wu_ref, o_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i < used_ref[0])
    def _():
        u = jnp.maximum(jnp.dot(x_ref[...], wu_ref[0],
                                preferred_element_type=jnp.float32), 0.0)
        o_ref[...] = (u * u).astype(o_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _down_kernel(te_ref, used_ref, x_ref, w_ref, o_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i < used_ref[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _grouped_call(kernel, name, x_rows, weights, tile_expert, tiles_used,
                  tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x_rows.shape
    n = weights[0].shape[-1]
    bn = BLOCK_N if n % BLOCK_N == 0 else n
    n_blocks = n // bn
    n_tiles = rows // tile

    def x_map(i, j, te, used):
        return (jnp.minimum(i, jnp.maximum(used[0] - 1, 0)), 0)

    def w_map(i, j, te, used):
        # behind the used tiles: the block the last used step held
        return (te[i], 0, jnp.where(i < used[0], j, n_blocks - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, n_blocks),
        in_specs=[pl.BlockSpec((tile, k), x_map)]
        + [pl.BlockSpec((1, k, bn), w_map) for _ in weights],
        out_specs=pl.BlockSpec((tile, bn), lambda i, j, te, used: (i, j)),
    )
    return pl.pallas_call(
        kernel, name=name, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), x_rows.dtype),
        interpret=interpret,
    )(tile_expert, jnp.reshape(tiles_used, (1,)).astype(jnp.int32),
      x_rows, *weights)


def _grouped_ffn(up_kernel, x_rows, w_ups, w_down, tile_expert, tiles_used,
                 tile, interpret):
    if interpret is None:
        interpret = not runtime.on_tpu()
    call = functools.partial(_grouped_call, tile_expert=tile_expert,
                             tiles_used=tiles_used, tile=tile,
                             interpret=interpret)
    hid = call(up_kernel, "moe_grouped_up", x_rows, w_ups)
    return call(_down_kernel, "moe_grouped_down", hid, [w_down])


def grouped_swiglu(x_rows, w_gate, w_up, w_down, tile_expert, tiles_used, *,
                   tile: int, interpret: bool | None = None):
    """``(silu(x @ Wg[e]) * (x @ Wu[e])) @ Wd[e]`` for every row tile, ``e``
    the tile's expert: ``x_rows`` (rows, d), ``w_gate``/``w_up`` (E, d, m),
    ``w_down`` (E, m, d), ``tile_expert`` (rows // tile,) int32,
    ``tiles_used`` scalar int32.  Rows of tiles behind ``tiles_used`` come
    back zero."""
    return _grouped_ffn(_up_kernel, x_rows, [w_gate, w_up], w_down,
                        tile_expert, tiles_used, tile, interpret)


def grouped_relu2(x_rows, w_up, w_down, tile_expert, tiles_used, *,
                  tile: int, interpret: bool | None = None):
    """``relu(x @ Wu[e]) ** 2 @ Wd[e]`` for every row tile: the ungated
    expert (``w_up`` (E, d, m), ``w_down`` (E, m, d)); otherwise as
    :func:`grouped_swiglu`, whose down kernel it shares."""
    return _grouped_ffn(_up_relu2_kernel, x_rows, [w_up], w_down,
                        tile_expert, tiles_used, tile, interpret)
