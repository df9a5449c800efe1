"""``flash_attention_qkv`` on the causal diagonal: a block walked in row
sub-tiles against the whole block and the plain reference, the work the
sub-tiles skip, and the tile backward's copies in flight under the TPU
interpreter.

Run in Pallas interpreter mode on CPU (``test_flash_attention_qkv.py`` has
the fused projection's golden tests).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flash_qkv_cases import fused_case, split_heads


# --- a causal diagonal block walked in row sub-tiles ------------------------

_LONG_WINDOW = 300   # crosses sub-tiles of 256: rows 300+ lose their oldest keys

SUB_TILE_CASES = [
    # depth, heads (one lane tile), rotation, rows, seq
    (64, 2, True, None, 1024),
    (64, 2, False, None, 1024),
    (128, 1, True, None, 1024),
    (32, 4, True, None, 1024),
    (64, 2, True, "mask", 1024),
    (128, 1, False, "segments", 1024),
    (32, 4, False, "window", 1024),
    # a row whose every visible key is masked (left padding): finite, and
    # the rows that see a key are what they were
    (64, 2, True, "leftpad", 1024),
    # two blocks a side: the diagonal steps sub-tiled, the step below the
    # diagonal whole, one running softmax and one dq over both
    (64, 2, True, None, 2048),
    (128, 1, False, "mask", 2048),
    (32, 4, True, "segments", 2048),
    (64, 2, False, "window", 2048),
]


def _sub_tile_rows(rows, s):
    """``(mask, segment_ids, window, live)`` of a case over ``s`` tokens."""
    pos = np.arange(s)[None, :]
    live = np.ones((1, s), bool)
    mask = seg = window = None
    if rows == "mask":
        mask = live = pos < s - 90
    elif rows == "leftpad":
        mask = live = pos >= 70
    elif rows == "segments":
        seg = (pos >= 410).astype(np.int32) + (pos >= 1500)
    elif rows == "window":
        window = _LONG_WINDOW
    return (None if mask is None else jnp.asarray(mask),
            None if seg is None else jnp.asarray(seg), window,
            jnp.asarray(live))


def _dense_o_and_lse(qkv, pos, h, *, mask, segment_ids, window):
    """The plain float32 reference: split, ``rope``, the (S, S) scores of
    every head under the masks, softmax; o (B, S, H*D) and the log-sum-exp
    (B, H, 1, S) as the kernels lay them out."""
    from distributedtensorflow_tpu.models.gpt import rope

    q, k, v = split_heads(qkv, h)
    if pos is not None:
        q, k = rope(q, pos, 1e4), rope(k, pos, 1e4)
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    keep = (back >= 0) if window is None else (back >= 0) & (back < window)
    keep = keep[None, None]
    if mask is not None:
        keep = keep & mask[:, None, None, :]
    if segment_ids is not None:
        keep = keep & (segment_ids[:, :, None]
                       == segment_ids[:, None, :])[:, None]
    scores = jnp.where(keep, scores, -1e9)
    lse = jax.nn.logsumexp(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(scores - lse[..., None]), v)
    return o.reshape(qkv.shape[0], s, -1), lse[:, :, None, :]


@pytest.mark.parametrize(
    "d,h,rotate,rows,s", SUB_TILE_CASES,
    ids=[f"d{c[0]}-h{c[1]}-{'rope' if c[2] else 'norope'}-"
         f"{c[3] or 'norows'}-s{c[4]}" for c in SUB_TILE_CASES])
def test_causal_sub_tiles_match_whole_blocks_and_the_reference(
        d, h, rotate, rows, s, monkeypatch):
    """A block on the causal diagonal walked in row sub-tiles of 256 that
    end at their own diagonal (``causal_tile``) against the same kernels
    taking the block whole, and against the plain float32 reference: o,
    the log-sum-exp and d``qkv``.  A skipped score was an exact 0.0 after
    the exponential, so the two kernels differ by the order of float32
    additions alone (row sums and products of <= 1024 terms: 1e-5, relative
    and absolute; interpreted on the CPU they come out equal); the
    reference is held to what the whole-block kernels are held to."""
    import distributedtensorflow_tpu.ops.flash_attention as fa

    qkv, pos, tabs = fused_case(d, h, s=s, b=1)
    if not rotate:
        pos = tabs = None
    mask, seg, window, live = _sub_tile_rows(rows, s)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, s, h * d))
    weight = weight * live[:, :, None]
    kw = dict(heads=h, causal=True, interpret=True, window=window,
              block_q=1024, block_k=1024)

    def forward(tile):
        return fa._tiles_forward(qkv, tabs, mask, seg, causal_tile=tile,
                                 **kw)

    def grad(tile):
        monkeypatch.setattr(fa, "CAUSAL_TILE", tile)
        return jax.grad(lambda x: jnp.sum(fa.flash_attention_qkv(
            x, h, rope=tabs, mask=mask, segment_ids=seg, causal=True,
            window=window, interpret=True, block_q=1024, block_k=1024)
            * weight))(qkv)

    assert fa.causal_tile(1024, 1024, True) == 256
    (o, lse), (o_whole, lse_whole) = forward(256), forward(None)
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(lse)).all()
    rows_live = live[:, :, None]
    np.testing.assert_allclose(o * rows_live, o_whole * rows_live,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(lse * live[:, None, None, :],
                               lse_whole * live[:, None, None, :],
                               atol=1e-5, rtol=1e-5)
    o_ref, lse_ref = _dense_o_and_lse(qkv, pos, h, mask=mask,
                                      segment_ids=seg, window=window)
    np.testing.assert_allclose(o * rows_live, o_ref * rows_live,
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse * live[:, None, None, :],
                               lse_ref * live[:, None, None, :],
                               atol=2e-5, rtol=2e-5)
    got, whole = grad(256), grad(0)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, whole, atol=1e-5, rtol=1e-5)
    ref = jax.grad(lambda x: jnp.sum(_dense_o_and_lse(
        x, pos, h, mask=mask, segment_ids=seg, window=window)[0]
        * weight))(qkv)
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def _dot_flops(jaxpr):
    """The multiply-adds x 2 of every ``dot_general`` in ``jaxpr`` and the
    jaxprs its equations hold."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            depth = int(np.prod([eqn.invars[0].aval.shape[a]
                                 for a in contract]))
            total += 2 * depth * int(np.prod(eqn.outvars[0].aval.shape))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += _dot_flops(sub)
    return total


def _kernel_body(fn, *args):
    """The jaxpr of the one ``pallas_call`` ``fn(*args)`` traces to."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.params["jaxpr"]
            for value in eqn.params.values():
                sub = getattr(value, "jaxpr", value)
                if hasattr(sub, "eqns") and (body := find(sub)) is not None:
                    return body
        return None

    return find(jax.make_jaxpr(fn)(*args).jaxpr)


def test_causal_sub_tiles_skip_the_upper_triangles_work():
    """The work is really skipped: the ``dot_general`` FLOPs in the traced
    kernel bodies at 1024 / 256 are ``causal_share`` = 0.625 of the
    whole-block bodies' (+- 2 %), forward and backward.  The backward's
    body holds a step for the diagonal and one for the blocks below it
    (``pl.when`` branches, with and without the window's edge): the steps
    on the diagonal shrink, the others are the whole block's."""
    import functools

    import distributedtensorflow_tpu.ops.flash_attention as fa

    h, d, s = 2, 64, 1024
    qkv, _, tabs = fused_case(d, h, s=s, b=1)
    share = fa.causal_share(s, fa.causal_tile(s, s, True))
    assert share == 0.625 and fa.causal_share(s, None) == 1.0
    kw = dict(heads=h, causal=True, interpret=True, window=None,
              block_q=s, block_k=s)

    def bodies(tile):
        fwd = _kernel_body(functools.partial(
            fa._tiles_forward, causal_tile=tile, **kw), qkv, tabs, None,
            None)
        o, lse = fa._tiles_forward(qkv, tabs, None, None, **kw)
        bwd = _kernel_body(functools.partial(
            fa._tiles_backward, causal_tile=tile, force_split=False, **kw),
            qkv, tabs, None, None, o, lse, o)
        steps = [f for f in (
            _dot_flops(eqn.params["branches"][-1].jaxpr)
            for eqn in bwd.eqns if eqn.primitive.name == "cond") if f]
        return _dot_flops(fwd), steps

    (fwd, steps), (fwd_whole, steps_whole) = bodies(256), bodies(None)
    square = 2 * s * s * 128          # one product over the whole square
    assert fwd_whole == h * 2 * square
    assert fwd == pytest.approx(share * fwd_whole, rel=0.02)
    # (diagonal, diagonal + window edge, window edge, neither): five
    # products a head a step
    assert steps_whole == [h * 5 * square] * 4
    assert steps[:2] == pytest.approx([share * h * 5 * square] * 2, rel=0.02)
    assert steps[2:] == steps_whole[2:]


def test_causal_tile_is_chosen_by_what_the_call_shows():
    import distributedtensorflow_tpu.ops.flash_attention as fa

    assert fa.causal_tile(1024, 1024, True) == 256
    assert fa.causal_tile(512, 512, True) == 256
    assert fa.causal_tile(1024, 1024, False) is None   # not causal
    assert fa.causal_tile(512, 1024, True) is None     # unequal blocks
    assert fa.causal_tile(256, 256, True) is None      # one sub-tile
    assert fa.causal_tile(128, 128, True) is None
    assert fa.causal_share(512, 256) == 0.75
    assert fa.qkv_causal_tile(64, 1024, 16, 64, jnp.bfloat16) == (256, 0.625)
    assert fa.qkv_causal_tile(8, 128, 4, 64, jnp.float32) == (None, None)


# --- the backward's copies in flight (the TPU interpreter) ------------------

_IN_FLIGHT_PAD = np.arange(128)[None, :] < np.array([[100], [128], [57]])

IN_FLIGHT_CASES = [
    # depth, heads (two lane tiles), backward, blocks, causal, sub-tile,
    # window, padding mask
    (64, 4, "pallas", None, True, 32, None, False),
    (64, 4, "pallas", (32, 64), True, 0, None, False),
    (64, 4, "pallas", (64, 32), False, 0, None, False),
    (64, 4, "pallas", None, False, 0, None, True),
    (128, 2, "pallas", (64, 64), True, 32, 40, False),
    (128, 2, "pallas", (32, 64), True, 0, 33, True),
    (64, 4, "pallas_split", None, True, 0, None, False),
    (64, 4, "pallas_split", (32, 64), True, 0, None, True),
    (128, 2, "pallas_split", (64, 32), False, 0, None, False),
    (128, 2, "pallas_split", (64, 32), True, 0, 70, False),
]


@pytest.mark.parametrize(
    "d,h,backward,blocks,causal,tile,window,pad", IN_FLIGHT_CASES,
    ids=[f"d{c[0]}-{c[2]}-{'x'.join(map(str, c[3])) if c[3] else 'oneblock'}"
         f"-{'causal' if c[4] else 'full'}-t{c[5]}-w{c[6]}-"
         f"{'mask' if c[7] else 'nomask'}" for c in IN_FLIGHT_CASES])
def test_backward_copies_in_flight_land_before_the_kernel_returns(
        d, h, backward, blocks, causal, tile, window, pad, monkeypatch):
    """The tile backward leaves a finished block's copy into d``qkv`` in
    flight and waits for it where the staging block is written again; the
    grid's last step waits for what is left (``_tile_sender``).  The plain
    interpreter finishes a copy at its start and cannot tell a kernel that
    waits from one that never does.  The TPU interpreter performs a copy
    at its wait, fills memory nobody wrote with NaN and follows reads and
    writes for races: a block that was never waited for is NaN in d``qkv``,
    one re-staged under its copy lands in the wrong place.  Three
    sequences of two lane tiles: the carry crosses a tile and a sequence,
    and nothing but the last step's wait lands the last blocks.  o and
    d``qkv`` are the plain interpreter's bit for bit."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    import distributedtensorflow_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "CAUSAL_TILE", tile)
    bq, bk = blocks or (128, 128)
    assert fa.causal_tile(bq, bk, causal) == (tile or None)
    qkv, _, tabs = fused_case(d, h, b=3)
    mask = jnp.asarray(_IN_FLIGHT_PAD) if pad else None
    g = jax.random.normal(jax.random.PRNGKey(9), (3, 128, h * d))
    if pad:
        g = g * mask[:, :, None]

    def o_and_dqkv(interpret):
        o, vjp = jax.vjp(lambda x: fa.flash_attention_qkv(
            x, h, rope=tabs, mask=mask, causal=causal, window=window,
            interpret=interpret, backward_impl=backward, block_q=bq,
            block_k=bk), qkv)
        return np.asarray(o), np.asarray(vjp(g)[0])

    o, dqkv = o_and_dqkv(pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True,
        uninitialized_memory="nan"))
    races = interpret_pallas_call.races   # the last kernel's: the backward
    assert races is None or not races.races_found
    assert not np.isnan(dqkv).any() and not np.isnan(o).any()
    o_plain, dqkv_plain = o_and_dqkv(True)
    np.testing.assert_array_equal(o, o_plain)
    np.testing.assert_array_equal(dqkv, dqkv_plain)
