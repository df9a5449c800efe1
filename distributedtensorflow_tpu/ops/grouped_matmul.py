"""Grouped matmuls of an expert layer: every row tile belongs to one expert.

``parallel.moe.group_plan`` sorts the routed (token, choice) pairs by
expert and pads each expert's rows to whole tiles, so the row buffer is a
sequence of ``tile``-row tiles, ``tile_expert[i]`` naming the expert whose
matrices tile ``i`` multiplies, the first ``tiles_used`` of them real.  The
two kernels here walk that list with it prefetched into SMEM: the weight
block of a grid step is picked by ``tile_expert`` in the index map, so an
expert no tile names is never read and an expert several tiles name is read
once a tile.  **The grid ends with the tiles in use** (its first dimension
is ``tiles_used``, read when the kernel is launched): the tiles behind them
cost nothing and are not written, so what lies there in a kernel's output is
whatever the buffer held.  A caller reads the used tiles' rows (the padding
rows of a used tile come back as the product of what the caller put there:
zeros for zeros) and, behind the buffer, the one tile of zeros the down
kernel writes in a step of its own — row ``rows``, where a pair that has no
row points.  ``parallel.moe._grouped_ffn_xla``, the plain form, returns
zeros behind the used tiles and no such tile; ``combine_rows`` reads no row
behind the used ones.

``moe_grouped_up``: ``silu(x @ Wg[e]) * (x @ Wu[e])``, both products in
one pass over ``x``, or for an ungated expert (``grouped_relu2``)
``relu(x @ Wu[e]) ** 2``, one product; ``moe_grouped_down``: ``h @ Wd[e]``.  The contraction is
whole in one block (``K`` is a model width, a few thousand), so there is no
accumulator; the output is tiled along ``N``.  At decode a tile holds one
or two real rows: the kernels are bound by the weight bytes they stream,
which is the point — they stream the hit experts and nothing else.

``moe_pick`` (:func:`combine_rows`): the weighted sum of a token's experts'
rows, taken from the rows in use where the plain form gathers a row a
routed pair, most of them a zero row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import runtime

__all__ = ["grouped_swiglu", "grouped_relu2", "combine_rows"]

#: N-width of one weight block: 3072 x 256 bf16 is 1.5 MB, two operands
#: double-buffered 6 MB, inside the 16 MB of scoped VMEM.
BLOCK_N = 256
#: rows of the buffer a grid step of ``combine_rows`` walks
COMBINE_ROWS = 512
#: bytes of its float32 accumulator, a block of the output's columns for all
#: the tokens: it lives in VMEM twice (the pipeline's two output buffers)
COMBINE_ACC_BYTES = 8 << 20
#: its scoped VMEM: the two accumulators, two blocks of rows and their
#: float32 copy
COMBINE_VMEM = 48 << 20


def _up_kernel(te_ref, x_ref, wg_ref, wu_ref, o_ref):
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _up_relu2_kernel(te_ref, x_ref, wu_ref, o_ref):
    u = jnp.maximum(jnp.dot(x_ref[...], wu_ref[0],
                            preferred_element_type=jnp.float32), 0.0)
    o_ref[...] = (u * u).astype(o_ref.dtype)


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
                  tile, interpret, zero_tile=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x_rows.shape
    n = weights[0].shape[-1]
    bn = BLOCK_N if n % BLOCK_N == 0 else n
    n_tiles, n_blocks = rows // tile, n // bn
    used = jnp.reshape(tiles_used, (1,)).astype(jnp.int32)
    if not zero_tile:
        maps = (lambda i, j, te: (i, 0), lambda i, j, te: (te[i], 0, j),
                lambda i, j, te: (i, j))
        scalars = (tile_expert,)
    else:
        # one step more than the tiles in use: it asks for the operands the
        # last of them held (nothing is fetched) and writes the zero tile
        # behind the buffer
        def at(i, used):
            return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))

        def block(i, j, used):
            return jnp.where(i < used[0], j, n_blocks - 1)

        maps = (lambda i, j, te, used: (at(i, used), 0),
                lambda i, j, te, used: (te[at(i, used)], 0,
                                        block(i, j, used)),
                lambda i, j, te, used: (jnp.where(i < used[0], i, n_tiles),
                                        j))
        scalars = (tile_expert, used)
    # the grid ends with the tiles in use: its length is the plan's own
    # count, read when the kernel is launched
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(used[0] + zero_tile, n_blocks),
        in_specs=[pl.BlockSpec((tile, k), maps[0])]
        + [pl.BlockSpec((1, k, bn), maps[1]) for _ in weights],
        out_specs=pl.BlockSpec((tile, bn), maps[2]),
    )
    return pl.pallas_call(
        kernel, name=name, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows + zero_tile * tile, n),
                                       x_rows.dtype),
        interpret=interpret,
    )(*scalars, x_rows, *weights)


def _grouped_ffn(up_kernel, x_rows, w_ups, w_down, tile_expert, tiles_used,
                 tile, interpret):
    if interpret is None:
        interpret = not runtime.on_tpu()
    call = functools.partial(_grouped_call, tile_expert=tile_expert,
                             tiles_used=tiles_used, tile=tile,
                             interpret=interpret)
    hid = call(up_kernel, "moe_grouped_up", x_rows, w_ups)
    return call(_down_kernel, "moe_grouped_down", hid, [w_down],
                zero_tile=True)


def grouped_swiglu(x_rows, w_gate, w_up, w_down, tile_expert, tiles_used, *,
                   tile: int, interpret: bool | None = None):
    """``(silu(x @ Wg[e]) * (x @ Wu[e])) @ Wd[e]`` for every row tile, ``e``
    the tile's expert: ``x_rows`` (rows, d), ``w_gate``/``w_up`` (E, d, m),
    ``w_down`` (E, m, d), ``tile_expert`` (rows // tile,) int32,
    ``tiles_used`` scalar int32.  Returns (rows + tile, d): the tiles in
    use, then tiles that are not written (their rows hold whatever the
    buffer held), then one tile of zeros, which a pair without a row may
    point at (row ``rows``)."""
    return _grouped_ffn(_up_kernel, x_rows, [w_gate, w_up], w_down,
                        tile_expert, tiles_used, tile, interpret)


def grouped_relu2(x_rows, w_up, w_down, tile_expert, tiles_used, *,
                  tile: int, interpret: bool | None = None):
    """``relu(x @ Wu[e]) ** 2 @ Wd[e]`` for every row tile: the ungated
    expert (``w_up`` (E, d, m), ``w_down`` (E, m, d)); otherwise as
    :func:`grouped_swiglu`, whose down kernel it shares."""
    return _grouped_ffn(_up_relu2_kernel, x_rows, [w_up], w_down,
                        tile_expert, tiles_used, tile, interpret)


def _combine_kernel(src_ref, pair_ref, used_ref, w_ref, y_ref, o_ref, yf_ref,
                    *, block_rows):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    base = i * block_rows

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    yf_ref[...] = y_ref[...].astype(jnp.float32)

    def row(r, carry):
        t = src_ref[base + r]

        @pl.when(t < o_ref.shape[0])
        def _():
            o_ref[pl.ds(t, 1), :] += (
                w_ref[pair_ref[base + r]] * yf_ref[pl.ds(r, 1), :])

        return carry

    jax.lax.fori_loop(0, jnp.minimum(block_rows, used_ref[0] - base), row, 0)


def combine_rows(y_rows, src, pair, w, rows_used, *,
                 interpret: bool | None = None):
    """``out[t] = sum of w[t, j] * y_rows[r]`` over the rows ``r <
    rows_used`` that hold a pair ``(t, j)``, in float32: the weighted sum of
    a token's experts' rows, read where they lie.  ``y_rows`` (rows, d);
    ``src`` (rows,) int32 the token of a row (T where it holds no pair) and
    ``pair`` (rows,) its pair ``t * k + j``, as :func:`parallel.moe.
    group_plan` returns them; ``w`` (T, k) float32; ``rows_used`` scalar
    int32.  Returns (T, d) float32, a token's terms added in the order of
    its rows.  The kernel ``moe_pick`` streams the blocks of rows in use
    through VMEM once (its grid ends with them: the rows behind are neither
    fetched nor looked at and may hold anything) and adds each row that holds a pair
    into its token's row of an accumulator that stays there; the index and
    the weights are in SMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not runtime.on_tpu()
    rows, d = y_rows.shape
    tokens = w.shape[0]
    bd = d
    while bd % 256 == 0 and tokens * bd * 4 > COMBINE_ACC_BYTES:
        bd //= 2
    block_rows = min(COMBINE_ROWS, rows)

    # the grid ends with the rows in use (one step where there are none: it
    # writes the zeros)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d // bd, jnp.maximum(pl.cdiv(rows_used, block_rows), 1)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((block_rows, bd), lambda j, i, *_: (i, j))],
        out_specs=pl.BlockSpec((tokens, bd), lambda j, i, *_: (0, j)),
        scratch_shapes=[pltpu.VMEM((block_rows, bd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_combine_kernel, block_rows=block_rows),
        name="moe_pick", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=COMBINE_VMEM),
        interpret=interpret,
    )(src, pair, jnp.reshape(rows_used, (1,)).astype(jnp.int32),
      w.reshape(-1).astype(jnp.float32), y_rows)
