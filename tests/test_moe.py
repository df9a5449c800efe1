"""Expert-parallel MoE tests.

Golden reference: the same layer on an expert-axis-of-1 mesh (pure local
computation) must match the expert=4 all_to_all-dispatched run exactly when
capacity is ample.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
from distributedtensorflow_tpu.parallel.moe import (
    init_expert_params,
    make_moe_layer,
    top1_route,
    top2_route,
)

D = 8
E = 8


class ExpertMLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(D, name="out")(nn.relu(nn.Dense(2 * D, name="in")(x)))


def expert_fn(params, x):
    return ExpertMLP().apply({"params": params}, x)


def init_one(r):
    return ExpertMLP().init(r, jnp.zeros((1, D)))["params"]


def test_top1_route_invariants():
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, E))
    dispatch, combine, aux = top1_route(logits, capacity=4)
    assert dispatch.shape == (16, E, 4)
    # each token occupies at most one slot
    per_token = dispatch.sum(axis=(1, 2))
    assert ((per_token == 0) | (per_token == 1)).all()
    # no slot is used twice
    per_slot = dispatch.sum(axis=0)
    assert (per_slot <= 1).all()
    assert np.isfinite(float(aux))


def test_capacity_drops_tokens():
    # all tokens want expert 0; capacity 2 keeps exactly 2
    logits = jnp.zeros((10, E)).at[:, 0].set(10.0)
    dispatch, _, _ = top1_route(logits, capacity=2)
    assert float(dispatch.sum()) == 2.0


def test_top2_route_invariants():
    logits = jax.random.normal(jax.random.PRNGKey(1), (16, E))
    # capacity = num tokens: ample under ANY logits draw (the PRNG stream
    # differs across jax versions, so a merely-probably-ample capacity
    # made the every-token-fully-routed invariant below seed-dependent)
    dispatch, combine, aux = top2_route(logits, capacity=16)
    assert dispatch.shape == (16, E, 16)
    # each token occupies at most two slots (its two experts)
    per_token = dispatch.sum(axis=(1, 2))
    assert (per_token <= 2).all()
    # ample capacity: every token gets both choices
    assert (per_token == 2).all()
    # no slot used twice
    assert (dispatch.sum(axis=0) <= 1).all()
    # gates renormalize: combine mass per fully-routed token sums to 1
    np.testing.assert_allclose(
        np.asarray(combine.sum(axis=(1, 2))), 1.0, rtol=1e-5
    )
    assert np.isfinite(float(aux))


def test_top2_second_choice_preempted_first():
    """GShard priority: top-1 assignments beat top-2 for scarce capacity."""
    # every token's top-1 is expert 0 (huge logit), top-2 is expert 1
    logits = jnp.zeros((6, E)).at[:, 0].set(10.0).at[:, 1].set(5.0)
    dispatch, _, _ = top2_route(logits, capacity=4)
    # expert 0 gets its 4 slots filled by top-1 choices
    assert float(dispatch[:, 0].sum()) == 4.0
    # expert 1 has room for all 6 second choices? capacity 4 -> only 4
    assert float(dispatch[:, 1].sum()) == 4.0


def test_moe_layer_top2_runs(devices):
    mesh = build_mesh(MeshSpec(data=1, expert=4), devices[:4])
    rng = jax.random.PRNGKey(0)
    params = init_expert_params(init_one, E, rng, mesh)
    moe = make_moe_layer(mesh, expert_fn, capacity_factor=2.0, router="top2")
    tokens = jax.random.normal(rng, (32, D))
    router_kernel = jax.random.normal(jax.random.PRNGKey(2), (D, E)) * 0.1
    out, aux = moe(tokens, router_kernel, params)
    assert out.shape == tokens.shape
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("expert_axis", [1, 4])
def test_moe_runs_and_matches_across_meshes(devices, expert_axis):
    mesh = build_mesh(MeshSpec(data=2, expert=expert_axis),
                      devices[: 2 * expert_axis])
    params = init_expert_params(init_one, E, jax.random.PRNGKey(0), mesh)
    layer = make_moe_layer(mesh, expert_fn, capacity_factor=float(E))
    tokens = jax.random.normal(jax.random.PRNGKey(1), (64, D))
    router = jax.random.normal(jax.random.PRNGKey(2), (D, E)) * 0.1
    out, aux = layer(tokens, router, params)
    assert out.shape == tokens.shape
    assert np.isfinite(np.asarray(out)).all()
    # stash for cross-mesh comparison
    test_moe_runs_and_matches_across_meshes.results[expert_axis] = (
        np.asarray(out), float(aux),
    )


test_moe_runs_and_matches_across_meshes.results = {}


def test_moe_cross_mesh_agreement():
    res = test_moe_runs_and_matches_across_meshes.results
    if len(res) < 2:
        pytest.skip("parametrized runs incomplete")
    (o1, a1), (o4, a4) = res[1], res[4]
    np.testing.assert_allclose(o1, o4, atol=1e-5, rtol=1e-5)
    # aux is a per-shard load-balance statistic (mean of per-shard products);
    # it is an estimator, not shard-count-invariant — only roughly equal
    np.testing.assert_allclose(a1, a4, rtol=0.2)


def test_moe_indivisible_experts_raises(devices):
    mesh = build_mesh(MeshSpec(data=2, expert=4), devices)
    params = init_expert_params(init_one, E, jax.random.PRNGKey(0), mesh)
    layer = make_moe_layer(mesh, expert_fn)
    tokens = jax.random.normal(jax.random.PRNGKey(1), (64, D))
    router = jax.random.normal(jax.random.PRNGKey(2), (D, 6))  # 6 % 4 != 0
    with pytest.raises(ValueError, match="not divisible"):
        layer(tokens, router, params)


def test_moe_gradients_flow(devices):
    mesh = build_mesh(MeshSpec(data=2, expert=4), devices)
    params = init_expert_params(init_one, E, jax.random.PRNGKey(0), mesh)
    layer = make_moe_layer(mesh, expert_fn, capacity_factor=float(E))
    tokens = jax.random.normal(jax.random.PRNGKey(1), (64, D))
    router = jax.random.normal(jax.random.PRNGKey(2), (D, E)) * 0.1

    def loss(params, router):
        out, aux = layer(tokens, router, params)
        return jnp.sum(out ** 2) + 0.01 * aux

    grads, grouter = jax.grad(loss, argnums=(0, 1))(params, router)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert gnorm > 0
    assert float(jnp.sum(jnp.abs(grouter))) > 0


def test_expert_choice_route_invariants():
    from distributedtensorflow_tpu.parallel.moe import expert_choice_route

    logits = jax.random.normal(jax.random.PRNGKey(0), (16, E))
    dispatch, combine, aux = expert_choice_route(logits, capacity=3)
    assert dispatch.shape == (16, E, 3)
    # PERFECT load balance: every (expert, slot) is filled exactly once
    per_slot = dispatch.sum(axis=0)  # (E, C)
    np.testing.assert_array_equal(np.asarray(per_slot), 1.0)
    # no aux loss needed (balance holds by construction)
    assert float(aux) == 0.0
    # combine weights are the selecting experts' softmax probabilities
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    cw = np.asarray(combine).sum(axis=2)  # (T, E)
    picked = np.asarray(dispatch).sum(axis=2).astype(bool)
    np.testing.assert_allclose(cw[picked],
                               probs[picked], atol=1e-6)
    # capacity clamps to T (an expert cannot pick more tokens than exist)
    d2, _, _ = expert_choice_route(logits[:2], capacity=5)
    assert d2.shape == (2, E, 2)


def test_expert_choice_skewed_router_stays_balanced():
    from distributedtensorflow_tpu.parallel.moe import expert_choice_route

    # every token prefers expert 0 — token-choice would overflow it;
    # expert choice still fills every expert's slots
    logits = jnp.zeros((32, E)).at[:, 0].set(10.0)
    dispatch, _, _ = expert_choice_route(logits, capacity=4)
    np.testing.assert_array_equal(np.asarray(dispatch.sum(axis=0)), 1.0)


def test_expert_choice_cross_mesh_machinery(devices):
    """Dispatch/combine machinery is mesh-layout invariant in the dense
    limit (capacity = T: every expert takes every token, so per-shard
    routing decisions coincide).  With realistic capacity the per-shard
    top-k decisions legitimately differ across layouts — that regime is
    covered by the invariant tests above, not by cross-mesh equality."""
    outs = {}
    for expert_axis in (1, 4):
        mesh = build_mesh(MeshSpec(data=2, expert=expert_axis),
                          devices[: 2 * expert_axis])
        params = init_expert_params(init_one, E, jax.random.PRNGKey(0), mesh)
        layer = make_moe_layer(mesh, expert_fn, capacity_factor=float(E),
                               router="expert_choice")
        tokens = jax.random.normal(jax.random.PRNGKey(1), (64, D))
        router = jax.random.normal(jax.random.PRNGKey(2), (D, E)) * 0.1
        out, aux = layer(tokens, router, params)
        assert np.isfinite(np.asarray(out)).all()
        assert float(aux) == 0.0
        outs[expert_axis] = np.asarray(out)
    np.testing.assert_allclose(outs[1], outs[4], atol=1e-5, rtol=1e-5)


def test_routers_exclude_padding_tokens():
    """token_mask semantics (round-3 advisor finding): pad tokens must
    neither consume expert capacity (displacing real tokens) nor dilute
    the aux-loss means — for all three routers."""
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu.parallel.moe import (
        expert_choice_route,
        top1_route,
        top2_route,
    )

    rng = np.random.default_rng(0)
    t, e, cap = 16, 2, 4
    logits = jnp.asarray(rng.standard_normal((t, e)) * 2, jnp.float32)
    # half the tokens are pads, interleaved so pads would often outrank
    # real tokens if routed
    mask = jnp.asarray(np.arange(t) % 2 == 0, jnp.float32)

    for route in (top1_route, top2_route, expert_choice_route):
        dispatch, combine, aux = route(logits, cap, mask)
        d = np.asarray(dispatch)  # (T, E, C)
        # every pad row has zero dispatch and zero combine weight
        pads = np.arange(t)[np.asarray(mask) == 0]
        assert d[pads].sum() == 0.0, route.__name__
        assert np.asarray(combine)[pads].sum() == 0.0, route.__name__
        assert np.isfinite(float(aux))

    # displacement check (the actual bug scenario): with capacity for
    # every real token, masked top1 dispatches ALL real tokens, while
    # unmasked routing of the same logits can drop some behind pads.
    d_masked, _, _ = top1_route(logits, t // 2, mask)
    reals = np.arange(t)[np.asarray(mask) == 1]
    assert np.asarray(d_masked)[reals].sum() == len(reals)

    # aux means ignore pads: doubling the pad count must not change aux
    big_logits = jnp.concatenate([logits, logits])
    big_mask = jnp.concatenate([mask, jnp.zeros((t,), jnp.float32)])
    _, _, aux_small = top1_route(logits, cap, mask)
    _, _, aux_big = top1_route(big_logits, cap, big_mask)
    np.testing.assert_allclose(float(aux_big), float(aux_small), rtol=1e-6)


# -- the softmax router of a dropless layer (Qwen3-Next) ----------------------

@pytest.mark.parametrize("route_norm", [True, False])
def test_softmax_topk_route_is_the_dense_softmax(route_norm):
    """``(idx, weight)`` as ``sigmoid_topk_route`` gives them: the ``top_k``
    largest of a float32 softmax over the router's full width, renormalised
    over the chosen where ``route_norm``."""
    from distributedtensorflow_tpu.parallel import moe

    k = jax.random.split(jax.random.PRNGKey(7), 2)
    h = jax.random.normal(k[0], (9, 32), jnp.bfloat16)
    router = jax.random.normal(k[1], (32, 16))
    idx, w = moe.softmax_topk_route(h, router, top_k=4,
                                    route_norm=route_norm)
    probs = np.asarray(jax.nn.softmax(
        np.asarray(h, np.float32) @ np.asarray(router), -1))
    want = np.sort(probs, -1)[:, ::-1][:, :4]
    assert idx.shape == w.shape == (9, 4) and w.dtype == jnp.float32
    np.testing.assert_allclose(
        np.take_along_axis(probs, np.asarray(idx), -1), want, atol=1e-6)
    if route_norm:
        want = want / want.sum(-1, keepdims=True)
    np.testing.assert_allclose(w, want, atol=1e-6)


def test_dropless_moe_routes_by_the_router_it_is_told():
    """``router="softmax"`` is the softmax router, which has no bias; the
    default is the sigmoid one, as every caller before it: the two give
    different sums from one router, the softmax one the by-hand sum of its
    own route, and another name is refused."""
    from distributedtensorflow_tpu.models.afmoe import swiglu
    from distributedtensorflow_tpu.parallel import moe

    k = jax.random.split(jax.random.PRNGKey(8), 6)
    d, m, e = 32, 24, 8
    h = jax.random.normal(k[0], (10, d))
    router = jax.random.normal(k[1], (d, e))
    experts = {"w_gate": jax.random.normal(k[2], (4, d, m)) * 0.2,
               "w_up": jax.random.normal(k[3], (4, d, m)) * 0.2,
               "w_down": jax.random.normal(k[4], (4, m, d)) * 0.2}
    kw = dict(held=(2, 4), top_k=3, impl="xla")
    soft, counters = moe.dropless_moe(h, router, None, experts,
                                      router="softmax", **kw)
    sig, _ = moe.dropless_moe(h, router, jnp.zeros((e,)), experts, **kw)
    with pytest.raises(ValueError, match="\"sigmoid\" or \"softmax\""):
        moe.dropless_moe(h, router, None, experts, router="top1", **kw)
    assert np.abs(np.asarray(soft - sig)).max() > 1e-2
    idx, w = moe.softmax_topk_route(h, router, top_k=3)
    want = np.zeros((10, d), np.float32)
    for t in range(10):
        for j in range(3):
            local = int(idx[t, j]) - 2
            if 0 <= local < 4:
                one = jax.tree.map(lambda a: a[local], experts)
                want[t] += float(w[t, j]) * np.asarray(swiglu(one, h[t:t + 1]))[0]
    np.testing.assert_allclose(soft, want, atol=1e-5)
    assert int(counters["pairs"]) == int(
        ((np.asarray(idx) >= 2) & (np.asarray(idx) < 6)).sum())
