"""The Pallas kernels under a multi-device mesh.

On the chip GSPMD refuses to partition a Mosaic call ("Mosaic kernels
cannot be automatically partitioned"), so ``ops/`` runs each kernel per
shard through ``parallel.sharding.shard_kernel`` whenever the engine's
mesh context is set.  Interpret mode lowers to plain HLO and would hide
a missing wrapper, so these tests look at the traced program as well as
at the numbers: under the mesh every ``pallas_call`` must sit inside a
``shard_map`` and see per-shard operand shapes, and values and gradients
must equal the single-device run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributedtensorflow_tpu.ops.flash_attention import flash_attention
from distributedtensorflow_tpu.ops.fused_xent import fused_softmax_xent
from distributedtensorflow_tpu.ops.layernorm import layer_norm
from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
from distributedtensorflow_tpu.parallel.sharding import (
    kernel_axes,
    shard_kernel,
)

B, S, H, D, V = 8, 64, 4, 16, 96


def _inputs():
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return {
        "x": f(B, S, H * D),
        "g": f(H * D) * 0.1 + 1.0,
        "b": f(H * D) * 0.1,
        "q": f(B, S, H, D), "k": f(B, S, H, D), "v": f(B, S, H, D),
        "wte": f(V, H * D) * 0.1,
    }, jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)


def _loss(a, targets):
    """All three kernels in one scalar, so one grad checks every vjp."""
    y = layer_norm(a["x"], a["g"], a["b"], impl="pallas", interpret=True)
    o = flash_attention(a["q"], a["k"], a["v"], causal=True, interpret=True)
    h = y + o.reshape(B, S, H * D)
    return fused_softmax_xent(h, a["wte"], targets, interpret=True,
                              block_tokens=128, block_vocab=128,
                              block_tokens_dx=128, block_vocab_dx=128,
                              block_tokens_dw=128, block_vocab_dw=128)


def _pallas_operand_shapes(jaxpr, inside_shard_map=False, out=None):
    """[(inside a shard_map?, first operand shape)] of every pallas_call."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((inside_shard_map, eqn.invars[0].aval.shape))
        inner = inside_shard_map or eqn.primitive.name == "shard_map"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_operand_shapes(sub, inner, out)
    return out


@pytest.mark.parametrize("spec", [
    MeshSpec(data=4, model=2),
    MeshSpec(data=2, fsdp=2, seq=2),
])
def test_kernels_run_per_shard_and_match_single_device(spec):
    a, targets = _inputs()
    want, want_grads = jax.value_and_grad(_loss)(a, targets)

    mesh = build_mesh(spec)
    batch = NamedSharding(mesh, P(("data", "fsdp")))
    repl = NamedSharding(mesh, P())
    shardings = {k: repl if k in ("g", "b", "wte") else batch for k in a}
    placed = jax.device_put((a, targets), (shardings, batch))
    with jax.sharding.set_mesh(mesh):
        fn = jax.jit(jax.value_and_grad(_loss))
        calls = _pallas_operand_shapes(jax.make_jaxpr(fn)(*placed).jaxpr)
        got, got_grads = fn(*placed)

    assert calls and all(inside for inside, _ in calls), calls
    shape = dict(mesh.shape)
    b_loc = B // (shape["data"] * shape["fsdp"])
    # the flash forward sees (B/dp, H/tp, S, D)-sized operands, never (B, ...)
    assert all(s[0] != B for _, s in calls if len(s) == 4), calls
    assert any(s[0] == b_loc for _, s in calls if len(s) == 4), calls
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name in want_grads:
        np.testing.assert_allclose(
            got_grads[name], want_grads[name], rtol=2e-4, atol=2e-5,
            err_msg=name,
        )


def test_indivisible_batch_is_replicated_not_refused():
    """``init`` traces the model on a two-row batch; on a four-way data
    mesh that cannot be split, so every shard computes all of it."""
    mesh = build_mesh(MeshSpec(data=4), jax.devices()[:4])
    x = jnp.ones((2, 8, 32))
    g = jnp.ones((32,))
    with jax.sharding.set_mesh(mesh):
        assert kernel_axes(("data", "fsdp"), 2) is None
        assert kernel_axes(("data", "fsdp"), 8) == ("data",)
        y = jax.jit(lambda x: layer_norm(
            x, g, g, impl="pallas", interpret=True))(x)
    np.testing.assert_allclose(y, jnp.ones_like(x), atol=1e-5)


def test_no_mesh_context_means_no_wrapper():
    assert kernel_axes(("data",), 8) is None
    f = lambda x: x
    assert shard_kernel(f, P(), P()) is f
