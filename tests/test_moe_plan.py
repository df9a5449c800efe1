"""The dispatch around the grouped kernels (``parallel.moe.group_plan``, the
row buffer and the combine of ``dropless_moe``, ``ops.grouped_matmul.
combine_rows``): the plan against a plain loop that places the pairs one by
one, the combine kernel (interpreted) against the plain pick, the layer
against the form it had with an appended zero row, and no scatter in any
of it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.ops import grouped_matmul
from distributedtensorflow_tpu.parallel import moe


def _choices(rng, tokens, k, published):
    return np.stack([rng.permutation(published)[:k]
                     for _ in range(tokens)]).astype(np.int32)


def _plain_plan(idx, held, mask, tile):
    """The pairs placed one by one: expert by expert, a token after the
    tokens before it, an expert's rows padded to whole tiles."""
    t, k = idx.shape
    first, count = held
    rows = -(-t * k // tile) * tile + count * tile
    members = [[] for _ in range(count)]
    for token in range(t):
        for j in range(k):
            e = idx[token, j] - first
            if 0 <= e < count and (mask is None or mask[token]):
                members[e].append((token, j))
    src = np.full((rows,), t, np.int32)
    dest = np.full((t, k), rows, np.int32)
    tile_expert, r = [], 0
    for e, pairs in enumerate(members):
        for token, j in pairs:
            src[r], dest[token, j] = token, r
            r += 1
        tile_expert += [e] * -(-len(pairs) // tile)
        r = len(tile_expert) * tile
    used = len(tile_expert)
    tile_expert += [tile_expert[-1] if used else count - 1] * (
        rows // tile - used)
    loads = [len(pairs) for pairs in members]
    return {"rows": rows, "src": src, "dest": dest,
            "tile_expert": np.asarray(tile_expert, np.int32),
            "tiles_used": used, "pairs": sum(loads),
            "experts_hit": sum(n > 0 for n in loads), "max_load": max(loads)}


def _case(name):
    """``(idx, held, token_mask, tile)`` of a named load."""
    rng = np.random.default_rng(len(name))
    mask = None
    if name == "nemotron_chunk_in_small":       # top 22 of 512, 128 held
        idx, held, tile = _choices(rng, 96, 22, 512), (0, 128), 64
    elif name == "decode_batch_small_tile":
        idx, held, tile = _choices(rng, 16, 22, 512), (128, 128), 16
    elif name == "every_expert_held":            # lfm2's form
        idx, held, tile = _choices(rng, 40, 4, 64), (0, 64), 16
    elif name == "no_pair_on_this_chip":
        idx, held, tile = 8 + _choices(rng, 24, 4, 24), (0, 8), 16
    elif name == "one_expert_takes_every_pair":
        idx = 8 + _choices(rng, 70, 4, 24)
        idx[:, 2] = 5
        held, tile = (0, 8), 64
    elif name == "every_pair_of_every_token_held":
        # the load a held-share bound must not drop: 4 x the uniform share
        idx, held, tile = _choices(rng, 64, 6, 8), (0, 8), 64
    elif name == "whole_tokens_masked":
        idx, held, tile = _choices(rng, 48, 4, 16), (0, 8), 16
        mask = rng.random(48) < 0.5
    elif name == "held_not_from_zero":
        idx, held, tile = _choices(rng, 33, 3, 32), (20, 6), 16
    return idx, held, mask, tile


CASES = ["nemotron_chunk_in_small", "decode_batch_small_tile",
         "every_expert_held", "no_pair_on_this_chip",
         "one_expert_takes_every_pair", "every_pair_of_every_token_held",
         "whole_tokens_masked", "held_not_from_zero"]


@pytest.mark.parametrize("name", CASES)
def test_plan_places_the_pairs_as_a_plain_loop_does(name):
    idx, held, mask, tile = _case(name)
    want = _plain_plan(idx, held, mask, tile)
    got = jax.jit(lambda idx, mask: moe.group_plan(idx, held, mask, tile))(
        jnp.asarray(idx), None if mask is None else jnp.asarray(mask))
    assert got["rows"] == want["rows"]
    for key in ("src", "dest", "tile_expert", "tiles_used", "pairs",
                "experts_hit", "max_load"):
        np.testing.assert_array_equal(np.asarray(got[key]), want[key], key)
    # the row's pair, which the combine reads a weight by
    pair = np.asarray(got["pair"])
    filled = pair < idx.size
    assert (pair[~filled] == idx.size).all()
    np.testing.assert_array_equal(
        want["dest"].reshape(-1)[pair[filled]], np.flatnonzero(filled))
    if name == "no_pair_on_this_chip":
        assert int(got["tiles_used"]) == 0 and not filled.any()
    if name == "every_pair_of_every_token_held":
        assert int(got["pairs"]) == idx.size


def _layer(tokens, k, published, held, d, d_in, m, gated, dtype, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    count = held[1]
    experts = {"w_up": jax.random.normal(key[2], (count, d_in, m), dtype) / 8,
               "w_down": jax.random.normal(key[3], (count, m, d_in), dtype) / 8}
    if gated:
        experts["w_gate"] = jax.random.normal(
            key[4], (count, d_in, m), dtype) / 8
    return dict(
        h=jax.random.normal(key[0], (tokens, d), dtype),
        router_kernel=jax.random.normal(key[1], (d, published)),
        select_bias=jnp.zeros((published,)), experts=experts, held=held,
        top_k=k, experts_in=None if d_in == d else jax.random.normal(
            key[5], (tokens, d_in), dtype))


def _layer_with_a_zero_row(h, router_kernel, select_bias, experts, *, held,
                           top_k, experts_in, token_mask=None):
    """``dropless_moe`` under ``impl="xla"`` as it stood before the combine
    kernel: a zero row appended to the tokens and to the experts' rows, the
    rows without a pair and the pairs without a row pointed at it."""
    tile = moe.group_tile(h.shape[0], top_k, router_kernel.shape[-1])
    idx, w = moe.sigmoid_topk_route(h, router_kernel, select_bias,
                                    top_k=top_k)
    plan = moe.group_plan(idx, held, token_mask, tile)
    x = h if experts_in is None else experts_in
    x_rows = jnp.concatenate(
        [x, jnp.zeros((1, x.shape[-1]), x.dtype)])[plan["src"]]
    weights = [experts[n] for n in ("w_gate", "w_up", "w_down")
               if n in experts]
    y_rows = moe._grouped_ffn_xla(x_rows, weights, plan["tile_expert"],
                                  plan["tiles_used"], tile)
    y_rows = jnp.concatenate(
        [y_rows, jnp.zeros((1, y_rows.shape[-1]), y_rows.dtype)])
    picked = y_rows[plan["dest"]].astype(jnp.float32)
    return (picked * w[..., None]).sum(1).astype(x.dtype)


LAYERS = {
    # nemotron's chunk in small: ungated latent experts, the wide tile
    "latent_top22_of_512": (96, 22, 512, (128, 128), 48, 128, 256, False,
                            jnp.bfloat16),
    "gated_every_expert_held": (40, 4, 16, (0, 16), 128, 128, 256, True,
                                jnp.bfloat16),
    "gated_float32_an_eighth_held": (300, 8, 64, (8, 8), 128, 128, 128, True,
                                     jnp.float32),
    "decode_batch": (16, 2, 8, (6, 2), 128, 128, 128, True, jnp.float32),
    # the bias sends every token to held expert 9 first: 150 rows of one
    # expert, three wide tiles, beside a uniform share of the others
    "one_held_expert_crowded": (150, 4, 32, (8, 8), 128, 128, 128, True,
                                jnp.float32),
}


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_layer_keeps_its_bits_and_the_kernels_their_tolerance(name):
    """Under ``impl="xla"`` the layer returns what it returned with the zero
    rows, bit for bit; under ``impl="pallas"`` (the grouped kernels and the
    combine kernel, interpreted) the same to a rounding of the output."""
    args = _layer(*LAYERS[name])
    h = args.pop("h")
    dtype = LAYERS[name][-1]
    if name == "one_held_expert_crowded":
        args["select_bias"] = args["select_bias"].at[9].set(100.0)
    mask = jnp.arange(h.shape[0]) % 5 != 3
    want = jax.jit(lambda h: _layer_with_a_zero_row(
        h, token_mask=mask, **args))(h)
    got, counters = jax.jit(lambda h: moe.dropless_moe(
        h, impl="xla", token_mask=mask, **args))(h)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    kernel, counters2 = jax.jit(lambda h: moe.dropless_moe(
        h, impl="pallas", token_mask=mask, **args))(h)
    assert {k: int(v) for k, v in counters.items()} \
        == {k: int(v) for k, v in counters2.items()}
    if name == "one_held_expert_crowded":
        assert int(counters["max_load"]) == int(mask.sum())
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    np.testing.assert_allclose(
        np.asarray(kernel, np.float32), np.asarray(want, np.float32), rtol=0,
        atol=scale * (2 ** -7 if dtype == jnp.bfloat16 else 1e-6))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunk_shaped_layer_lowers_without_a_scatter(impl):
    """A chunk of 2,048 tokens top 22 of 512 with 128 held (the widths
    small): nothing in the plan, the row buffer or the combine scatters."""
    args = _layer(2048, 22, 512, (128, 128), 64, 128, 128, False,
                  jnp.bfloat16)
    h = args.pop("h")
    text = jax.jit(lambda h: moe.dropless_moe(
        h, impl=impl, **args)).lower(h).as_text()
    assert "scatter" not in text
    # the plan's sort by expert; the pairs' rows (a second sort) are the
    # plain pick's, and the combine kernel's path does not compute them
    assert text.count("stablehlo.sort") == (2 if impl == "xla" else 1)
    plan = jax.jit(lambda idx: moe.group_plan(
        idx, (128, 128), None, 64)["src"]).lower(
            jnp.zeros((2048, 22), jnp.int32)).as_text()
    assert "scatter" not in plan


PICKS = {
    # (tokens, k, held share of the pairs, tile)
    "mixed": (50, 6, 0.3, 16),
    "all_absent": (24, 4, 0.0, 16),
    "all_held": (40, 3, 1.0, 64),
    "more_rows_than_a_block": (300, 8, 0.5, 64),
}


@pytest.mark.parametrize("name", sorted(PICKS))
def test_combine_kernel_is_the_plain_pick(name):
    """``combine_rows`` interpreted against ``(y_rows[dest] * w).sum(1)`` at
    float32: tokens whose pairs are all absent, all held, and mixed; the
    rows behind the used ones hold NaN, which nothing may read."""
    tokens, k, share, tile = PICKS[name]
    rng = np.random.default_rng(3)
    published = 32
    count = round(published * share)
    idx = _choices(rng, tokens, k, published)
    if share in (0.0, 1.0):
        idx = idx % 8 + (8 if share == 0.0 else 0)
        count = 8
    plan = moe.group_plan(jnp.asarray(idx), (0, count), None, tile)
    rows, d = plan["rows"], 256
    used = int(plan["tiles_used"]) * tile
    y_rows = rng.standard_normal((rows, d)).astype(np.float32)
    y_rows[used:] = np.nan
    w = rng.random((tokens, k)).astype(np.float32)
    dest = np.asarray(plan["dest"])
    padded = np.concatenate([y_rows, np.zeros((1, d), np.float32)])
    want = (padded[dest] * w[..., None]).sum(1)
    got = grouped_matmul.combine_rows(
        jnp.asarray(y_rows), plan["src"], plan["pair"], jnp.asarray(w),
        plan["tiles_used"] * tile, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (tokens, d)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-6 * k)
    if name == "all_absent":
        assert used == 0 and not np.asarray(got).any()
    if name == "all_held":
        assert (dest < rows).all()
    if name == "more_rows_than_a_block":
        assert used > grouped_matmul.COMBINE_ROWS


def test_grouped_kernels_write_the_used_tiles_and_a_zero_tile():
    """Behind ``tiles_used`` the kernels write nothing but one tile of zeros
    at the buffer's end: a caller may read a used tile's rows and that tile.
    The plain loop keeps its zeros."""
    rng = np.random.default_rng(0)
    tile, tiles, used, d, m = 16, 6, 2, 128, 256
    x = jnp.asarray(rng.standard_normal((tiles * tile, d)), jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((3, d, m)), jnp.float32) / 8
    w_down = jnp.asarray(rng.standard_normal((3, m, d)), jnp.float32) / 8
    tile_expert = jnp.asarray([0, 2, 2, 2, 2, 2], jnp.int32)
    want = moe._grouped_ffn_xla(x, [w_up, w_down], tile_expert,
                                jnp.int32(used), tile)
    assert not np.asarray(want)[used * tile:].any()
    for n_used in (used, 0, tiles):
        got = np.asarray(grouped_matmul.grouped_relu2(
            x, w_up, w_down, tile_expert, jnp.int32(n_used), tile=tile,
            interpret=True))
        assert got.shape == ((tiles + 1) * tile, d)
        assert not got[tiles * tile:].any()
        if n_used == used:
            np.testing.assert_allclose(got[:used * tile],
                                       np.asarray(want)[:used * tile],
                                       atol=1e-4)
