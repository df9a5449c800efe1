"""Expert parallelism: Switch-style MoE with all_to_all token dispatch.

New capability absent from the reference stack (SURVEY.md §2.4 EP row).
Experts are sharded over the ``expert`` mesh axis; tokens are routed top-1
with a capacity limit, dispatched to their expert's device via a pair of
``lax.all_to_all`` s (the MoE idiom on the ICI torus), processed by the
local experts, and combined back weighted by the router probability.

Everything is fixed-shape (dispatch/combine are one-hot einsum contractions,
dropped tokens pass through on the residual path), so the whole layer jits
into one SPMD program — no data-dependent shapes (XLA requirement).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import runtime
from . import mesh as mesh_lib

PyTree = Any


def _capacity_slots(pos: jax.Array, mask: jax.Array, capacity: int) -> jax.Array:
    """(T, E) 1-based queue positions + assignment mask → (T, E, C) one-hot
    dispatch, dropping assignments past ``capacity``."""
    keep = (pos <= capacity) & (mask > 0)
    slot = jnp.clip(pos - 1.0, 0, capacity - 1).astype(jnp.int32)
    return keep[..., None] * jax.nn.one_hot(slot, capacity, dtype=jnp.float32)


def _masked_fracs(assign: jax.Array, probs: jax.Array,
                  token_mask: jax.Array | None):
    """(frac_tokens, frac_probs) per expert, averaged over VALID tokens
    only — with padding present, pads must not dilute the aux loss."""
    if token_mask is None:
        return jnp.mean(assign, axis=0), jnp.mean(probs, axis=0)
    w = token_mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w), 1.0)
    # assign is already zeroed at pad rows by the caller
    return jnp.sum(assign, axis=0) / denom, \
        jnp.sum(probs * w[:, None], axis=0) / denom


def top1_route(
    logits: jax.Array,  # (T, E) router logits
    capacity: int,
    token_mask: jax.Array | None = None,  # (T,) 1 = real token, 0 = pad
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-1 routing with capacity (Switch Transformer recipe).

    Returns ``(dispatch, combine, aux_loss)``:
    - dispatch: (T, E, C) one-hot — token t occupies slot c of expert e;
    - combine: (T, E, C) — dispatch weighted by the router probability;
    - aux_loss: scalar load-balancing loss (mean_frac_tokens · mean_probs · E).

    ``token_mask`` excludes padding: pad tokens consume NO capacity slot
    (they ride the residual path) and do not dilute the aux-loss means.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # (T,)
    expert_onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (T, E)
    if token_mask is not None:
        expert_onehot = expert_onehot * token_mask.astype(jnp.float32)[:, None]
    # position of each token within its expert's queue
    pos_in_expert = jnp.cumsum(expert_onehot, axis=0) * expert_onehot  # 1-based
    dispatch = _capacity_slots(pos_in_expert, expert_onehot, capacity)
    gate = jnp.sum(probs * expert_onehot, axis=-1, keepdims=True)  # (T, 1)
    combine = dispatch * gate[..., None]
    # Switch aux loss: encourages uniform token/prob mass over experts
    frac_tokens, frac_probs = _masked_fracs(expert_onehot, probs, token_mask)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


def top2_route(
    logits: jax.Array,  # (T, E) router logits
    capacity: int,
    token_mask: jax.Array | None = None,  # (T,) 1 = real token, 0 = pad
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-2 routing with capacity (GShard recipe).

    Each token goes to its two highest-probability experts; the two gates
    are renormalized to sum to 1.  Top-2 assignments queue AFTER all top-1
    assignments per expert (GShard's priority rule: second choices only
    take leftover capacity).  Same return contract (and the same
    pad-exclusion semantics for ``token_mask``) as :func:`top1_route`.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx1 = jnp.argmax(probs, axis=-1)
    mask1 = jax.nn.one_hot(idx1, e, dtype=jnp.float32)
    probs2 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=jnp.float32)
    if token_mask is not None:
        w = token_mask.astype(jnp.float32)[:, None]
        mask1, mask2 = mask1 * w, mask2 * w

    g1 = jnp.sum(probs * mask1, axis=-1)
    g2 = jnp.sum(probs * mask2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    # Queue positions: top-1 first, then top-2 behind ALL top-1 of that
    # expert (so capacity preempts second choices, never first choices).
    pos1 = jnp.cumsum(mask1, axis=0) * mask1  # 1-based
    count1 = jnp.sum(mask1, axis=0, keepdims=True)  # (1, E)
    pos2 = (jnp.cumsum(mask2, axis=0) + count1) * mask2

    d1 = _capacity_slots(pos1, mask1, capacity)  # (T, E, C)
    d2 = _capacity_slots(pos2, mask2, capacity)
    dispatch = d1 + d2
    combine = d1 * g1[:, None, None] + d2 * g2[:, None, None]
    # GShard aux loss over the FIRST choice (same form as Switch).
    frac_tokens, frac_probs = _masked_fracs(mask1, probs, token_mask)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


def expert_choice_route(
    logits: jax.Array,  # (T, E) router logits
    capacity: int,
    token_mask: jax.Array | None = None,  # (T,) 1 = real token, 0 = pad
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Expert-choice routing (Zhou et al. 2022): each EXPERT selects its
    top-``capacity`` tokens by router probability — the inverted assignment.

    Load balance is perfect *by construction* (every expert processes
    exactly ``capacity`` tokens), so no auxiliary loss is needed:
    ``aux_loss`` is a constant 0.  Tokens may be chosen by zero experts
    (they ride the residual path) or by several (their outputs sum,
    weighted by the selecting experts' probabilities).  Same return
    contract as :func:`top1_route`.

    **Not causal**: whether token t is selected depends on every other
    token's router score — including future positions.  Use only in
    encoder / non-autoregressive settings (the EC paper's domain);
    ``models/gpt_moe.py`` rejects it for the causal LM —
    ``models/bert_moe.py`` is the encoder workload that uses it.

    **Pool semantics under expert parallelism**: inside ``make_moe_fn``'s
    shard_map region each token SHARD routes its own pool, so the top-k
    selection is per-shard (the EC paper's per-device setting), not a
    global top-k — EC outputs are therefore layout-DEPENDENT by design,
    unlike the per-token top1/top2 routers.
    """
    t, e = logits.shape
    capacity = min(capacity, t)  # an expert cannot pick more tokens than exist
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    if token_mask is not None:
        # pads rank strictly below every real token (softmax probs are
        # strictly positive); any pad that still lands in a top-k (more
        # capacity than real tokens) is zeroed via the keep mask below.
        w = token_mask.astype(jnp.float32)[:, None]
        probs = probs * w - (1.0 - w)
    gates, token_idx = jax.lax.top_k(probs.T, capacity)  # (E, C) both
    keep = (gates > 0.0).astype(jnp.float32)  # (E, C)
    dispatch = jax.nn.one_hot(token_idx, t, dtype=jnp.float32) * keep[..., None]
    dispatch = dispatch.transpose(2, 0, 1)  # (T, E, C)
    combine = dispatch * jnp.maximum(gates, 0.0)[None, :, :]
    return dispatch, combine, jnp.zeros((), jnp.float32)


ROUTERS = {
    "top1": top1_route,
    "top2": top2_route,
    "expert_choice": expert_choice_route,
}
#: assignments per token, for capacity scaling (GShard: top-2 needs 2x slots;
#: expert-choice capacity is the EC paper's k = cf * T / E).
_ASSIGNMENTS = {"top1": 1, "top2": 2, "expert_choice": 1}


def expert_parallel_moe(
    tokens: jax.Array,  # (T, d) — this shard's tokens
    router_kernel: jax.Array,  # (d, E)
    expert_params: PyTree,  # leaves (E_local, ...) — local experts
    expert_fn: Callable[[PyTree, jax.Array], jax.Array],  # (params,(N,d))->(N,d)
    *,
    axis_name: str = mesh_lib.AXIS_EXPERT,
    capacity_factor: float = 1.25,
    router: str = "top1",
    token_mask: jax.Array | None = None,  # (T,) 1 = real token, 0 = pad
) -> tuple[jax.Array, jax.Array]:
    """MoE layer body (shard_map-internal). Returns (out, aux_loss).

    ``router``: "top1" (Switch), "top2" (GShard), or "expert_choice"
    (encoder-only — see :func:`expert_choice_route`).  ``expert_params``
    leading dim is the local expert count; global expert count
    E = E_local * axis_size.  Dropped-over-capacity tokens contribute 0
    here (caller keeps them on the residual path).
    """
    if router not in ROUTERS:
        raise ValueError(
            f"unknown router {router!r}; expected one of {list(ROUTERS)}"
        )
    n = lax.axis_size(axis_name)
    t, d = tokens.shape
    e = router_kernel.shape[-1]
    if e % n:
        raise ValueError(
            f"n_experts={e} not divisible by expert axis size {n}"
        )
    # Scale capacity by assignments-per-token: top-2 produces 2T assignments,
    # so capacity_factor=1.0 still means "room for every assignment" under a
    # uniform router (the GShard 2*cf*T/E convention).
    capacity = max(
        1, int(t * capacity_factor * _ASSIGNMENTS[router] / e)
    )

    logits = tokens.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
    dispatch, combine, aux = ROUTERS[router](logits, capacity, token_mask)

    # (T, E, C) x (T, d) -> (E, C, d): expert-major send buffer
    send = jnp.einsum("tec,td->ecd", dispatch, tokens.astype(jnp.float32))
    # all_to_all: split experts across devices, gather every shard's slots
    # (E, C, d) -> (E_local, n*C, d)
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=1,
                          tiled=True)
    out = jax.vmap(expert_fn)(expert_params, recv.astype(tokens.dtype))
    out = out.astype(jnp.float32)
    # route results back: (E_local, n*C, d) -> (E, C, d)
    back = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                          tiled=True)
    combined = jnp.einsum("tec,ecd->td", combine, back)
    # aux loss is per-shard; mean over shards for a global scalar
    aux = lax.pmean(aux, axis_name)
    return combined.astype(tokens.dtype), aux


def init_expert_params(
    init_one: Callable[[jax.Array], PyTree],
    n_experts: int,
    rng: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = mesh_lib.AXIS_EXPERT,
) -> PyTree:
    """Stack per-expert params on a leading dim sharded over ``expert``."""
    rngs = jax.random.split(rng, n_experts)
    stacked = jax.vmap(init_one)(rngs)
    specs = jax.tree.map(lambda _: P(), jax.eval_shape(init_one, rng))
    sharding = jax.tree.map(
        lambda spec: NamedSharding(mesh, P(axis_name, *spec)), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.device_put(stacked, sharding)


def make_moe_fn(
    mesh: Mesh,
    expert_fn: Callable[[PyTree, jax.Array], jax.Array],
    *,
    capacity_factor: float = 1.25,
    axis_name: str = mesh_lib.AXIS_EXPERT,
    router: str = "top1",
) -> Callable:
    """Un-jitted shard_map MoE region for use INSIDE a jitted model.

    ``fn(tokens (N, d), router_kernel, expert_params) -> (out, aux)`` —
    tokens are sharded over (batch axes + expert axis) so each expert shard
    routes its local tokens; expert params are expert-axis sharded.  The
    model-level embedding (``models/gpt_moe.py``) drops this into its MLP
    the same way ring attention drops into ``attn_fn``.
    """
    if router not in ROUTERS:  # eager: fail here, not inside the jit trace
        raise ValueError(
            f"unknown router {router!r}; expected one of {list(ROUTERS)}"
        )
    batch_axes = mesh_lib.data_axes(mesh)
    tok_axes = tuple(batch_axes) + (axis_name,)

    def run(tokens, router_kernel, expert_params, token_mask=None):
        if token_mask is None:  # keep the shard_map arity static
            token_mask = jnp.ones((tokens.shape[0],), jnp.float32)

        def body(toks, rk, ep, tmask):
            out, aux = expert_parallel_moe(
                toks, rk, ep, expert_fn=expert_fn, axis_name=axis_name,
                capacity_factor=capacity_factor, router=router,
                token_mask=tmask,
            )
            if batch_axes:  # make the aux loss a true global scalar
                aux = lax.pmean(aux, batch_axes)
            return out, aux

        param_specs = jax.tree.map(lambda _: P(axis_name), expert_params)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(tok_axes), P(), param_specs, P(tok_axes)),
            out_specs=(P(tok_axes), P()),
            check_vma=False,
        )(tokens, router_kernel, expert_params, token_mask)

    return run


def make_moe_layer(
    mesh: Mesh,
    expert_fn: Callable[[PyTree, jax.Array], jax.Array],
    *,
    capacity_factor: float = 1.25,
    axis_name: str = mesh_lib.AXIS_EXPERT,
    router: str = "top1",
) -> Callable:
    """Jit-compiled global entry around :func:`make_moe_fn`."""
    return jax.jit(make_moe_fn(
        mesh, expert_fn, capacity_factor=capacity_factor,
        axis_name=axis_name, router=router,
    ))


def with_moe_layout(base) -> "LayoutMap":
    """``base`` layout rules + the expert-parallel sharding for MoEMLP
    params (expert stacks over the ``expert`` axis, router replicated) —
    THE single definition shared by every MoE model's layout."""
    from .sharding import LayoutMap  # noqa: PLC0415 (avoid cycle at import)

    rules = LayoutMap([
        (r".*moe_mlp/experts_in", P("expert", None, None)),
        (r".*moe_mlp/experts_out", P("expert", None, None)),
        (r".*moe_mlp/router", P()),
    ])
    for pat, spec in base._rules:
        rules._rules.append((pat, spec))
    return rules


def bind_expert_parallel_model(cfg, mesh: Mesh, model_ctor,
                               expert_fn) -> Any:
    """``model_ctor(cfg, moe_fn)`` with the all_to_all dispatch region
    bound when the mesh has a real ``expert`` axis; local (replicated)
    experts otherwise — the single bind used by every MoE model family."""
    if dict(mesh.shape).get(mesh_lib.AXIS_EXPERT, 1) > 1:
        moe_fn = make_moe_fn(
            mesh, expert_fn,
            capacity_factor=cfg.capacity_factor, router=cfg.router,
        )
        return model_ctor(cfg, moe_fn)
    return model_ctor(cfg, None)


def local_moe(
    tokens: jax.Array,  # (T, d)
    router_kernel: jax.Array,  # (d, E)
    expert_params: PyTree,  # leaves (E, ...) — ALL experts, replicated
    expert_fn: Callable[[PyTree, jax.Array], jax.Array],
    *,
    capacity_factor: float = 1.25,
    router: str = "top1",
    token_mask: jax.Array | None = None,  # (T,) 1 = real token, 0 = pad
) -> tuple[jax.Array, jax.Array]:
    """Single-device MoE (no collectives): every expert lives locally.

    Same routing/capacity math as :func:`expert_parallel_moe` with axis
    size 1 — the golden reference for EP tests and the fallback when the
    mesh has no real ``expert`` axis.
    """
    t, d = tokens.shape
    e = router_kernel.shape[-1]
    capacity = max(1, int(t * capacity_factor * _ASSIGNMENTS[router] / e))
    logits = tokens.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
    dispatch, combine, aux = ROUTERS[router](logits, capacity, token_mask)
    send = jnp.einsum("tec,td->ecd", dispatch, tokens.astype(jnp.float32))
    out = jax.vmap(expert_fn)(expert_params, send.astype(tokens.dtype))
    combined = jnp.einsum("tec,ecd->td", combine, out.astype(jnp.float32))
    return combined.astype(tokens.dtype), aux


# ---------------------------------------------------------------------------
# Dropless token-choice routing over a router wider than the experts held
# ---------------------------------------------------------------------------
#
# The capacity-slot routers above build (T, E, C) one-hots and drop what
# overflows a slot; neither survives k = 4 of 256.  The layer below routes
# over the router's FULL width, sorts the (token, choice) pairs that land on
# the experts this chip *holds* by expert, multiplies each expert's rows by
# its matrices once (grouped matmul: every row tile belongs to one expert)
# and leaves the absent experts' terms out — in an expert-parallel
# deployment those are other chips' terms, and the sum over the shares plus
# the shared expert, once, is the whole layer.  No token loses an expert at
# any load: the row buffer has room for every routed pair plus one tile of
# padding an expert, whatever share of them this chip holds.  What is
# *done* follows the pairs held here: the plan is one sort and no scatter,
# the grouped kernels fetch and write the tiles in use, and the combine
# reads the rows that hold a pair (``ops.grouped_matmul.combine_rows``; the
# plain form, a gather a routed pair, is the CPU's and the reference).

#: rows of one tile of the grouped matmuls (the bf16 sublane tile)
GROUP_TILE = 16
#: rows of a tile where an expert expects more than ``GROUP_TILE`` rows
#: (timed at 16 / 32 / 64 / 128 on a chunk of 1024 tokens top 8 of 256:
#: PERF.md section 4)
GROUP_TILE_WIDE = 64


def group_tile(tokens: int, top_k: int, published: int) -> int:
    """Rows of a tile for a batch of ``tokens``: the grouped kernels read an
    expert's matrices once a tile, so where uniform routing would give an
    expert more rows than ``GROUP_TILE`` (a prefill chunk of 1024 tokens top
    8 of 256: 32) a tile of 16 reads it two or three times, and one of 64
    holds all an expert's rows but a crowded one's.  Decode batches and
    chunks that spread thinner keep the small tile, whose padding an expert
    (``tile - 1`` rows at most) is what the row buffer is sized by."""
    return (GROUP_TILE if tokens * top_k <= GROUP_TILE * published
            else GROUP_TILE_WIDE)


def sigmoid_topk_route(h: jax.Array, router_kernel: jax.Array,
                       select_bias: jax.Array, *, top_k: int,
                       route_norm: bool = True, route_scale: float = 1.0,
                       route_norm_eps: float = 1e-20, n_group: int = 1,
                       topk_group: int = 1):
    """``(idx, weight)`` of shape ``(T, top_k)``: float32 sigmoid scores
    over the router's full width, the top ``top_k`` of ``score + bias``
    (the bias selects only), weights ``score[top] / (sum +
    route_norm_eps) * route_scale`` (the families publish 1e-20; lfm2
    1e-6).  With ``n_group`` > 1 the selection is group-limited
    (DeepSeek-V3's ``noaux_tc``): the experts lie in ``n_group`` groups of
    consecutive ids, a group scores the sum of its two largest ``score +
    bias``, and the ``top_k`` are taken inside the ``topk_group`` best
    groups; one group is the plain selection, bit for bit."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    choice = scores + select_bias.astype(jnp.float32)
    if n_group > 1:
        t, e = choice.shape
        best_two, _ = lax.top_k(choice.reshape(t, n_group, e // n_group), 2)
        _, keep = lax.top_k(best_two.sum(-1), topk_group)
        kept = (keep[:, :, None] == jnp.arange(n_group)).any(1)
        choice = jnp.where(jnp.repeat(kept, e // n_group, axis=1), choice,
                           -jnp.inf)
    _, idx = lax.top_k(choice, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if route_norm:
        w = w / (w.sum(-1, keepdims=True) + route_norm_eps)
    return idx, w * route_scale


def softmax_topk_route(h: jax.Array, router_kernel: jax.Array, *,
                       top_k: int, route_norm: bool = True):
    """``(idx, weight)`` of shape ``(T, top_k)`` as :func:`sigmoid_topk_route`
    gives them, under a softmax router (Qwen3-Next): float32 probabilities
    over the router's full width, the ``top_k`` largest, their weights
    renormalised over the chosen where ``route_norm`` (``norm_topk_prob``).
    No selection bias, no groups, no scale."""
    probs = jax.nn.softmax(jnp.dot(
        h.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    w, idx = lax.top_k(probs, top_k)
    if route_norm:
        w = w / w.sum(-1, keepdims=True)
    return idx, w


def group_plan(idx: jax.Array, held: tuple[int, int], token_mask=None,
               tile: int = GROUP_TILE) -> dict:
    """Where each routed pair goes in the row buffer of the grouped
    matmuls.  ``idx`` (T, k) are published expert ids; ``held = (first,
    count)``.  Pairs on held experts are sorted by expert, each expert's
    rows padded to whole tiles; the rest (and masked tokens' pairs) get
    no row.  Returns ``rows`` (static row count), ``src`` (rows,) token of
    each row (T where empty), ``dest`` (T, k) row of each pair (``rows``
    where it has none), ``tile_expert`` (rows // tile,) local expert of
    each tile (the last used tile's expert repeated behind it),
    ``tiles_used``, and the counters ``pairs``, ``experts_hit``,
    ``max_load``; also ``pair`` (rows,), the pair ``token * k + choice`` of
    each row (T k where empty: ``src`` is ``pair // k``).

    One sort and no scatter: a pair's expert, token and choice are packed
    into one word (the expert in the high bits: shifts, where a division by
    ``k`` is emulated on the chip), so one single-operand sort gives the
    held pairs first, grouped by expert in the order of the pairs; an
    expert's first sorted position is a count of smaller keys, a row's pair
    is read back through its tile's expert, and a pair's row through a
    second sort by pair (the plain pick's: a caller that reads no ``dest``
    pays for none)."""
    t, k = idx.shape
    n = t * k
    first, count = held
    local = idx - first
    here = (local >= 0) & (local < count)
    if token_mask is not None:
        here &= token_mask[:, None]
    key = jnp.where(here, local, count).astype(jnp.int32)     # (T, k)
    rows = -(-n // tile) * tile + count * tile
    j_bits = max(1, (k - 1).bit_length())
    low = max(1, (t - 1).bit_length()) + j_bits
    code = (jnp.arange(t, dtype=jnp.int32)[:, None] << j_bits
            | jnp.arange(k, dtype=jnp.int32)[None, :])         # token | choice
    if (count + 1) << low >= 2 ** 31:
        raise ValueError(f"{count} experts over {t} tokens top {k} do not "
                         "pack into the 31 bits of the plan's sort key")
    both = lax.sort((key << low | code).reshape(-1))
    skey, pairs_sorted = both >> low, both & ((1 << low) - 1)
    # starts[e]: the sorted position of expert e's first pair
    starts = (skey[None, :] < jnp.arange(count + 1)[:, None]).sum(
        1, dtype=jnp.int32)
    first_sorted, counts = starts[:count], starts[1:] - starts[:count]
    padded = -(-counts // tile) * tile
    ends = jnp.cumsum(padded)
    begins = ends - padded
    tiles_used = ends[-1] // tile
    # a tile's expert is the one whose padded rows hold its first row; the
    # tiles behind the used ones repeat the last used tile's
    tile_start = jnp.minimum(jnp.arange(rows // tile) * tile,
                             jnp.maximum(ends[-1] - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right",
                         method="compare_all").astype(jnp.int32), count - 1)
    # a tile of expert e holds the sorted pairs from first_sorted[e] + (its
    # first row - begins[e]) on, as many as are e's.  An element gathered
    # alone costs the chip what a row of 128 does, so a tile gathers the two
    # rows of 128 sorted pairs its own lie in and picks them out by position
    e_begins, e_counts, e_first = jnp.stack(
        [begins, counts, first_sorted], 1)[tile_expert].T
    row0 = jnp.arange(rows // tile, dtype=jnp.int32) * tile - e_begins
    at = jnp.clip(e_first + row0, 0, n)
    lanes = 128 * -(-tile // 128)
    table = jnp.pad(pairs_sorted,
                    (0, -n % lanes + 2 * lanes)).reshape(-1, lanes)
    window = jnp.concatenate(
        [table[at // lanes], table[at // lanes + 1]], axis=1)
    want = (at % lanes)[:, None] + jnp.arange(tile)[None, :]   # (tiles, tile)
    tile_code = jnp.where(
        want[:, :, None] == jnp.arange(2 * lanes)[None, None, :],
        window[:, None, :], 0).sum(-1)
    filled = jnp.arange(tile)[None, :] < (e_counts - row0)[:, None]
    src = jnp.where(filled, tile_code >> j_bits, t).reshape(-1)
    pair = src * k + jnp.where(
        filled, tile_code & ((1 << j_bits) - 1), 0).reshape(-1)
    # a sorted pair's row: its position plus the padding of the experts
    # before its own (a sum of comparisons, where a lookup is a gather)
    pos = jnp.arange(n, dtype=jnp.int32)
    dest_sorted = jnp.where(
        pos < starts[count],
        pos + ((pos[None, :] >= starts[1:, None])
               * (padded - counts)[:, None]).sum(0, dtype=jnp.int32), rows)
    _, dest = lax.sort((pairs_sorted, dest_sorted), num_keys=1)
    return {"rows": rows, "src": src, "pair": pair,
            "dest": dest.reshape(t, k), "tile_expert": tile_expert,
            "tiles_used": tiles_used.astype(jnp.int32),
            "pairs": counts.sum(), "experts_hit": (counts > 0).sum(),
            "max_load": counts.max()}


def _grouped_ffn_xla(x_rows, weights, tile_expert, tiles_used, tile):
    """Plain formulation of the grouped expert feed-forward: one loop turn a
    used tile, the tile's expert sliced out of the stacked matrices; the
    rows behind the used tiles come back zero (the kernels leave them
    unwritten: a caller reads neither).
    ``weights`` is ``(w_gate, w_up, w_down)``, the SwiGLU ``silu(x Wg) * (x
    Wu)``, or ``(w_up, w_down)``, the ungated ``relu(x Wu)^2``."""
    *w_ups, w_down = weights

    def body(i, out):
        e = tile_expert[i]
        x = lax.dynamic_slice_in_dim(x_rows, i * tile, tile, 0)
        ups = [jnp.dot(x, lax.dynamic_index_in_dim(w, e, 0, False),
                       preferred_element_type=jnp.float32) for w in w_ups]
        if len(ups) == 2:
            hid = jax.nn.silu(ups[0]) * ups[1]
        else:
            hid = jnp.square(jnp.maximum(ups[0], 0.0))
        y = jnp.dot(hid.astype(x_rows.dtype),
                    lax.dynamic_index_in_dim(w_down, e, 0, False),
                    preferred_element_type=jnp.float32)
        return lax.dynamic_update_slice_in_dim(
            out, y.astype(out.dtype), i * tile, 0)

    return lax.fori_loop(0, tiles_used, body, jnp.zeros(
        (x_rows.shape[0], w_down.shape[-1]), x_rows.dtype))


def dropless_moe(
    h: jax.Array,                 # (T, d)
    router_kernel: jax.Array,     # (d, E_published)
    select_bias: jax.Array | None,  # (E_published,); a softmax router has none
    experts: dict,                # w_gate, w_up (E_held, d_in, m), w_down (E_held, m, d_in)
    *,
    held: tuple[int, int],
    top_k: int,
    router: str = "sigmoid",
    route_norm: bool = True,
    route_scale: float = 1.0,
    route_norm_eps: float = 1e-20,
    n_group: int = 1,
    topk_group: int = 1,
    token_mask: jax.Array | None = None,
    impl: str = "auto",
    experts_in: jax.Array | None = None,    # (T, d_in)
) -> tuple[jax.Array, dict]:
    """The held experts' share of a token-choice MoE layer, dropless.

    Routes every token over the router's full width (``router``:
    ``"sigmoid"``, :func:`sigmoid_topk_route`, or ``"softmax"``,
    :func:`softmax_topk_route`, which reads ``top_k`` and ``route_norm``
    alone: a softmax router has no selection bias, no groups and no scale),
    computes ``sum_j w_j
    * Expert_{top_j}(x)`` over the choices that are held here, and returns
    it with the counters of :func:`group_plan` (``pairs``, ``experts_hit``,
    ``max_load``; under group-limited routing, ``n_group`` > 1, also
    ``groups_hit``: the groups the real tokens' choices fall in, summed over
    the tokens).  The experts read ``x = h``, the router's input, or
    ``experts_in`` where the caller gives one (a latent expert layer: the
    router reads the token, the experts a projection of it ``d_in`` wide, and
    the sum comes back ``d_in`` wide).  ``experts`` holds a SwiGLU expert's
    ``w_gate``, ``w_up`` (E_held, d_in, m) and ``w_down`` (E_held, m, d_in),
    or without a ``w_gate`` the ungated ``relu(x W_up)^2 W_down``.  The
    shared expert is the caller's, added once.  On
    one chip nothing is exchanged and nothing stands in for the absent
    experts.  ``impl``: ``"pallas"`` (``ops.grouped_matmul``: the grouped
    kernels, which write the tiles in use and nothing behind them, and the
    combine kernel, which reads the rows that hold a pair), ``"xla"`` (the
    plain loop and a gather a routed pair: the same sum, a token's terms in
    the order of its choices where the kernel adds them in the order of
    their rows), or ``"auto"`` (the kernels on a TPU)."""
    from ..ops import grouped_matmul as gmm

    tile = group_tile(h.shape[0], top_k, router_kernel.shape[-1])
    with jax.named_scope("router"):
        if router == "softmax":
            idx, w = softmax_topk_route(h, router_kernel, top_k=top_k,
                                        route_norm=route_norm)
        elif router == "sigmoid":
            idx, w = sigmoid_topk_route(
                h, router_kernel, select_bias, top_k=top_k,
                route_norm=route_norm, route_scale=route_scale,
                route_norm_eps=route_norm_eps, n_group=n_group,
                topk_group=topk_group)
        else:
            raise ValueError(f"router {router!r}: \"sigmoid\" or \"softmax\"")
        plan = group_plan(idx, held, token_mask, tile)
    x = h if experts_in is None else experts_in
    with jax.named_scope("experts"):
        # a row that holds no pair reads the zero row behind the tokens
        x_rows = jnp.concatenate(
            [x, jnp.zeros((1, x.shape[-1]), x.dtype)])[plan["src"]]
        weights = [experts[name] for name in ("w_gate", "w_up", "w_down")
                   if name in experts]
        kernel = runtime.use_kernel(impl)
        if kernel:
            grouped = gmm.grouped_swiglu if len(weights) == 3 \
                else gmm.grouped_relu2
            y_rows = grouped(x_rows, *weights, plan["tile_expert"],
                             plan["tiles_used"], tile=tile)
        else:
            y_rows = _grouped_ffn_xla(x_rows, weights, plan["tile_expert"],
                                      plan["tiles_used"], tile)
        if kernel and held[1] < router_kernel.shape[-1]:
            # most routed pairs are other chips': walk the rows held here
            out = gmm.combine_rows(y_rows, plan["src"], plan["pair"], w,
                                   plan["tiles_used"] * tile)
        else:
            # the plain pick, a gather a routed pair (every pair has a row
            # where every expert is held, but a masked token's): a pair
            # without a row reads a zero row, the first of the tile of them
            # the down kernel leaves behind the buffer
            if not kernel:
                y_rows = jnp.concatenate(
                    [y_rows, jnp.zeros((1, y_rows.shape[-1]), y_rows.dtype)])
            picked = y_rows[plan["dest"]].astype(jnp.float32)  # (T, k, d)
            out = (picked * w[..., None]).sum(1)
        out = out.astype(x.dtype)
    counters = {k: plan[k] for k in ("pairs", "experts_hit", "max_load")}
    if n_group > 1:
        group = idx // (router_kernel.shape[-1] // n_group)
        hit = (group[:, :, None] == jnp.arange(n_group)).any(1)  # (T, G)
        if token_mask is not None:
            hit &= token_mask[:, None]
        counters["groups_hit"] = hit.sum()
    return out, counters

