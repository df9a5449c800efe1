"""SPMD engine tests: end-to-end learning, accumulation equivalence, sharding.

Reference analogue: strategy conformance suite (``strategy_test_lib.py`` —
SURVEY.md §4) — the same train-step body must behave identically across mesh
shapes (OneDevice / Mirrored / MultiWorker are mesh shapes here).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributedtensorflow_tpu.models import LeNet5
from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
from distributedtensorflow_tpu.train import (
    accumulate_gradients,
    classification_eval,
    classification_loss,
    create_sharded_state,
    make_eval_step,
    make_train_step,
    split_microbatches,
)


def synthetic_batch(rng, n=32, classes=10):
    k1, k2 = jax.random.split(jax.random.PRNGKey(rng))
    labels = jax.random.randint(k2, (n,), 0, classes)
    # class-dependent images so the task is learnable
    images = (
        jax.random.normal(k1, (n, 28, 28, 1)) * 0.1
        + labels[:, None, None, None] / classes
    )
    return {"image": images, "label": labels}


def make_lenet_setup(mesh, lr=0.1):
    model = LeNet5()
    init_fn = lambda r: model.init(r, jnp.zeros((1, 28, 28, 1)))
    state, specs = create_sharded_state(
        init_fn, optax.sgd(lr, momentum=0.9), mesh, jax.random.PRNGKey(0)
    )
    return model, state, specs


@pytest.mark.parametrize(
    "spec,ndev",
    [
        (MeshSpec(data=1), 1),
        (MeshSpec(data=-1), 8),
        (MeshSpec(data=2, fsdp=2, model=2), 8),
    ],
)
def test_training_reduces_loss_across_mesh_shapes(devices, spec, ndev):
    mesh = build_mesh(spec, devices[:ndev])
    model, state, specs = make_lenet_setup(mesh)
    step = make_train_step(classification_loss(model), mesh, specs)
    rng = jax.random.PRNGKey(42)
    batch = synthetic_batch(0)
    first = None
    for i in range(10):
        state, metrics = step(state, synthetic_batch(i), rng)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first
    assert int(state.step) == 10


def test_mesh_shapes_agree(devices):
    """Same data, same seeds -> (near-)identical params on 1-device vs 8-device mesh."""
    results = []
    for spec, devs in [(MeshSpec(data=1), devices[:1]), (MeshSpec(data=-1), devices)]:
        mesh = build_mesh(spec, devs)
        model, state, specs = make_lenet_setup(mesh)
        step = make_train_step(classification_loss(model), mesh, specs)
        rng = jax.random.PRNGKey(7)
        for i in range(3):
            state, metrics = step(state, synthetic_batch(i), rng)
        results.append(jax.device_get(state.params))
    flat1 = jax.tree.leaves(results[0])
    flat2 = jax.tree.leaves(results[1])
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_gradient_accumulation_matches_full_batch(dp_mesh):
    """accum_steps=4 must match the single full-batch step (linear loss)."""
    model, state, specs = make_lenet_setup(dp_mesh)
    loss_fn = classification_loss(model)
    batch = synthetic_batch(3, n=64)
    rng = jax.random.PRNGKey(0)

    g1, m1, _ = accumulate_gradients(
        loss_fn, state.params, state.model_state, batch, rng, 1
    )
    g4, m4, _ = accumulate_gradients(
        loss_fn, state.params, state.model_state, batch, rng, 4
    )
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(m1["loss"], m4["loss"], rtol=1e-5)


def test_split_microbatches_shapes():
    batch = {"x": jnp.zeros((8, 3)), "y": jnp.zeros((8,))}
    out = split_microbatches(batch, 4)
    assert out["x"].shape == (4, 2, 3)
    assert out["y"].shape == (4, 2)
    with pytest.raises(ValueError):
        split_microbatches({"x": jnp.zeros((7,))}, 2)


def test_eval_step(dp_mesh):
    model, state, specs = make_lenet_setup(dp_mesh)
    ev = make_eval_step(classification_eval(model), dp_mesh, specs)
    metrics = ev(state, synthetic_batch(0))
    assert set(metrics) == {"loss", "accuracy"}
    assert np.isfinite(float(metrics["loss"]))


def test_batchnorm_model_state_updates(dp_mesh):
    """ResNet-20's batch_stats must update through the train step."""
    from distributedtensorflow_tpu.models import ResNet20

    model = ResNet20(dtype=jnp.float32)
    init_fn = lambda r: model.init(r, jnp.zeros((1, 32, 32, 3)))
    state, specs = create_sharded_state(
        init_fn, optax.sgd(0.1), dp_mesh, jax.random.PRNGKey(0)
    )
    assert "batch_stats" in state.model_state
    before = jax.tree.leaves(jax.device_get(state.model_state))
    step = make_train_step(classification_loss(model), dp_mesh, specs)
    batch = {
        "image": jax.random.normal(jax.random.PRNGKey(1), (16, 32, 32, 3)),
        "label": jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 10),
    }
    state, _ = step(state, batch, jax.random.PRNGKey(0))
    after = jax.tree.leaves(jax.device_get(state.model_state))
    assert any(not np.allclose(a, b) for a, b in zip(before, after))


def test_multi_step_matches_single_steps(devices):
    """make_multi_train_step(steps_per_call=K): one dispatch of K scanned
    optimizer steps follows the same trajectory as K single-step
    dispatches (same rng fold-in of the step counter; tolerances cover
    XLA re-fusing the scanned program), with metrics stacked (K, ...).  The host-bound analogue of Keras
    steps_per_execution."""
    from distributedtensorflow_tpu.train import make_multi_train_step

    mesh = build_mesh(MeshSpec(data=2, model=2), devices[:4])
    model, state0, specs = make_lenet_setup(mesh)
    state_a = state_b = state0  # immutable; both runs start identical
    loss_fn = classification_loss(model)
    rng = jax.random.PRNGKey(7)
    k = 4
    batches = [synthetic_batch(i) for i in range(k)]

    single = make_train_step(loss_fn, mesh, specs, donate=False)
    for b in batches:
        state_a, m_single = single(state_a, b, rng)

    multi = make_multi_train_step(loss_fn, mesh, specs, steps_per_call=k,
                                  donate=False)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    state_b, m_multi = multi(state_b, stacked, rng)

    assert int(state_b.step) == int(state_a.step) == k
    assert m_multi["loss"].shape == (k,)
    np.testing.assert_allclose(
        np.asarray(m_multi["loss"][-1]), np.asarray(m_single["loss"]),
        rtol=1e-6,
    )
    for pa, pb in zip(jax.tree.leaves(state_a.params),
                      jax.tree.leaves(state_b.params)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=1e-4, atol=1e-7)


def test_multi_step_one_is_single(devices):
    from distributedtensorflow_tpu.train import make_multi_train_step

    mesh = build_mesh(MeshSpec(data=2), devices[:2])
    model, state, specs = make_lenet_setup(mesh)
    step = make_multi_train_step(
        classification_loss(model), mesh, specs, steps_per_call=1
    )
    state, metrics = step(state, synthetic_batch(0), jax.random.PRNGKey(0))
    assert int(state.step) == 1 and np.isfinite(float(metrics["loss"]))


def _plain_step(loss_fn, accum_steps):
    """A train step as the engine's was before the update became a region
    of its own: ``accumulate_gradients`` + ``state.apply_gradients``, no
    barrier between them."""

    def step(state, batch, rng):
        r = jax.random.fold_in(rng, state.step)
        grads, metrics, mstate = accumulate_gradients(
            loss_fn, state.params, state.model_state, batch, r, accum_steps)
        with jax.named_scope("optimizer"):
            new_state = state.apply_gradients(grads).replace(
                model_state=mstate)
        return new_state, metrics

    return step


@pytest.mark.parametrize(
    "tx,ndev,accum_steps,steps_per_call,zero",
    [
        (optax.adamw(1e-3, weight_decay=0.1), 1, 1, 1, False),
        (optax.adamw(1e-3, weight_decay=0.1), 1, 2, 1, False),
        (optax.adamw(1e-3, weight_decay=0.1), 1, 1, 2, False),
        (optax.adamw(1e-3, weight_decay=0.1), 2, 1, 1, True),
        (optax.sgd(0.1, momentum=0.9), 1, 1, 1, False),
    ],
    ids=["adamw", "accum2", "multi_step2", "zero_2dev", "sgd_momentum"],
)
def test_update_is_a_region_of_its_own_and_the_identity(
        devices, tx, ndev, accum_steps, steps_per_call, zero):
    """The gradients pass one ``optimization_barrier``, all leaves of the
    tree together, between the backward and the ``optimizer`` scope
    (``engine.separate_update``), and the barrier is the identity:
    parameters, optimizer state and the logged loss equal, bit for bit,
    those of a step with no barrier."""
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributedtensorflow_tpu.parallel import sharding as shardlib
    from distributedtensorflow_tpu.parallel.zero import ZeroSharder
    from distributedtensorflow_tpu.train import engine, make_multi_train_step

    mesh = build_mesh(MeshSpec(data=ndev), devices[:ndev])
    model = LeNet5()
    state0, specs = create_sharded_state(
        lambda r: model.init(r, jnp.zeros((1, 28, 28, 1))), tx, mesh,
        jax.random.PRNGKey(0), zero=ZeroSharder(mesh) if zero else None)
    loss_fn = classification_loss(model)
    rng = jax.random.PRNGKey(3)
    leaves = len(jax.tree.leaves(state0.params))

    step = make_multi_train_step(
        loss_fn, mesh, specs, steps_per_call=steps_per_call,
        accum_steps=accum_steps, donate=False)
    plain = _plain_step(loss_fn, accum_steps)
    if steps_per_call > 1:
        one = plain
        plain = lambda s, bs, r: lax.scan(  # noqa: E731
            lambda c, b: one(c, b, r), s, bs)
    shardings = shardlib.named_shardings(mesh, specs)
    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, shardlib.batch_spec(
        mesh, leading_unsharded=int(steps_per_call > 1)))
    plain = jax.jit(plain, in_shardings=(shardings, rows, repl),
                    out_shardings=(shardings, repl))

    def batch(call):
        if steps_per_call == 1:
            return synthetic_batch(call)
        return jax.tree.map(lambda *xs: jnp.stack(xs), *(
            synthetic_batch(call * steps_per_call + i)
            for i in range(steps_per_call)))

    state_a = state_b = state0
    for call in range(-(-3 // steps_per_call)):  # three steps or more
        state_a, m_a = step(state_a, batch(call), rng)
        state_b, m_b = plain(state_b, batch(call), rng)
        np.testing.assert_array_equal(
            np.asarray(m_a["loss"]), np.asarray(m_b["loss"]))
    assert int(state_a.step) == int(state_b.step) >= 3
    for a, b in zip(
            jax.tree.leaves((state_a.params, state_a.opt_state)),
            jax.tree.leaves((state_b.params, state_b.opt_state)),
            strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # one barrier in the lowered step, over every gradient leaf ...
    text = step.lower(state0, batch(0), rng).as_text()
    assert text.count("optimization_barrier") == 1
    # ... and it stands where the backward ends and the update begins
    with jax.sharding.set_mesh(mesh):
        eqns = jax.make_jaxpr(engine._step_body(loss_fn, accum_steps))(
            state0, synthetic_batch(0), rng).eqns
    (at,) = [i for i, e in enumerate(eqns)
             if e.primitive.name == "optimization_barrier"]
    assert len(eqns[at].invars) == leaves
    scoped = ["optimizer" in str(e.source_info.name_stack) for e in eqns]
    assert not any(scoped[:at]) and all(scoped[at + 1:])
    assert engine.optimizer_update(state0.params) == ("separate", leaves)
