"""Train state: params + mutable model state + optimizer state as one pytree.

Replaces the reference's ``DistributedVariable`` zoo (``values.py`` —
SURVEY.md §2.1): instead of wrapper objects with per-replica copies and
read/write policies, state is a plain pytree of ``jax.Array`` s whose
``NamedSharding`` carries the distribution; mirrored-vs-sharded is a
PartitionSpec, not a class.  ``model_state`` holds non-trainable collections
(batch-norm statistics — the reference's ``SyncOnReadVariable`` role:
cross-replica aggregation happens via a psum inside the step, not via a
read-time policy object).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax.core import FrozenDict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel import sharding as shardlib

PyTree = Any


class TrainState(struct.PyTreeNode):
    """Minimal, engine-agnostic training state."""

    step: jax.Array
    params: PyTree
    model_state: PyTree  # non-trainable collections (batch_stats, ...)
    opt_state: PyTree
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    #: Weight-update sharding policy (parallel.zero.ZeroSharder) or None.
    #: When set, ``opt_state`` lives in the sharder's chunked layout and
    #: ``apply_gradients`` runs the reduce-scatter → sharded-update →
    #: all-gather path instead of the replicated one.
    zero: Any = struct.field(pytree_node=False, default=None)

    def apply_gradients(self, grads: PyTree) -> "TrainState":
        if self.zero is not None:
            return self.zero.apply_gradients(self, grads)
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        new_params = optax.apply_updates(self.params, updates)
        return self.replace(
            step=self.step + 1, params=new_params, opt_state=new_opt_state
        )

    def snapshot(self) -> "TrainState":
        """Deep-copy the device buffers.

        The compiled train step donates its input state, so a reference kept
        across a step (async eval/checkpoint closures) points at deleted
        buffers.  ``snapshot()`` returns a state safe to hand to a
        :class:`~distributedtensorflow_tpu.parallel.Coordinator` closure.
        """
        return jax.tree.map(jnp.copy, self)


def split_variables(variables: PyTree) -> tuple[PyTree, PyTree]:
    """Split a flax ``init`` variables dict into (params, model_state)."""
    if isinstance(variables, (dict, FrozenDict)) and "params" in variables:
        d = dict(variables)
        params = d.pop("params")
        return params, d
    return variables, {}


def _state_plan(init_fn, tx, mesh, rng, rules, fsdp, zero):
    """``(build, state_specs, out_shardings)``: the function of ``rng`` that
    makes the TrainState, its PartitionSpecs and their NamedShardings."""
    var_shapes = jax.eval_shape(init_fn, rng)
    param_shapes, mstate_shapes = split_variables(var_shapes)
    param_specs = shardlib.specs_for_tree(param_shapes, mesh, rules, fsdp=fsdp)
    mstate_specs = shardlib.specs_for_tree(mstate_shapes, mesh, rules)

    if zero is not None:
        zero.bind(param_specs)
        chunked_shapes = jax.eval_shape(zero.chunk_tree, param_shapes)
        opt_shapes = jax.eval_shape(lambda p: tx.init(p), chunked_shapes)
        opt_specs = zero.opt_state_specs(opt_shapes, param_shapes)
    else:
        opt_shapes = jax.eval_shape(lambda p: tx.init(p), param_shapes)
        opt_specs = _opt_state_specs(opt_shapes, param_shapes, param_specs)

    state_specs = TrainState(
        step=P(), params=param_specs, model_state=mstate_specs,
        opt_state=opt_specs, tx=tx, zero=zero,
    )

    def build(r):
        params, model_state = split_variables(init_fn(r))
        opt_state = (
            tx.init(zero.chunk_tree(params)) if zero is not None
            else tx.init(params)
        )
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            model_state=model_state, opt_state=opt_state, tx=tx, zero=zero,
        )

    out_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return build, state_specs, out_shardings


def create_sharded_state(
    init_fn: Callable[[jax.Array], PyTree],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    rng: jax.Array,
    *,
    rules: shardlib.LayoutMap | Callable | None = None,
    fsdp: bool = False,
    zero=None,
) -> tuple[TrainState, "TrainState"]:
    """Initialize a TrainState directly into its target sharding.

    ``init_fn(rng)`` returns a flax-style variables dict (``{"params": ...,
    "batch_stats": ...}``) or a bare params pytree.  Params are produced by
    ``jit`` with ``out_shardings`` so large models initialize shard-local on
    each device — no host-side full copy (the reference initializes under
    ``strategy.scope()`` for the same reason, SURVEY.md §3.3).

    ``zero`` (a :class:`~..parallel.zero.ZeroSharder`) switches the
    optimizer state to cross-replica weight-update sharding: slots are
    initialized in the sharder's chunked ``(degree, chunk)`` layout and
    sharded over the batch axes — each replica holds 1/degree of the
    optimizer state from the first step on, never a full copy.

    Returns ``(state, state_specs)`` where ``state_specs`` is a TrainState of
    PartitionSpecs (for use as jit shardings).
    """
    build, state_specs, out_shardings = _state_plan(
        init_fn, tx, mesh, rng, rules, fsdp, zero)
    # under the mesh: init traces the model, Pallas kernels included
    # (parallel.sharding.shard_kernel reads the mesh from this context)
    with jax.sharding.set_mesh(mesh):
        state = jax.jit(build, out_shardings=out_shardings)(rng)
    return state, state_specs


def abstract_sharded_state(
    init_fn: Callable[[jax.Array], PyTree],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    rng: jax.Array,
    *,
    rules: shardlib.LayoutMap | Callable | None = None,
    fsdp: bool = False,
    zero=None,
) -> tuple[TrainState, "TrainState"]:
    """:func:`create_sharded_state` with nothing placed: the state as
    ``ShapeDtypeStruct`` leaves that carry their shardings, and its specs —
    what a step is lowered against where the mesh's devices are described
    and not attached (``tools/train_step_memory.py``)."""
    build, state_specs, out_shardings = _state_plan(
        init_fn, tx, mesh, rng, rules, fsdp, zero)
    with jax.sharding.set_mesh(mesh):
        shapes = jax.eval_shape(build, rng)
    state = jax.tree.map(
        lambda s, sharding: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding),
        shapes, out_shardings)
    return state, state_specs


def _opt_state_specs(opt_shapes: PyTree, param_shapes: PyTree, param_specs: PyTree) -> PyTree:
    """Shard optimizer slots like their parameters (Adam m/v mirror params).

    Optimizer-state nodes that are param-tree-shaped (momentum, variance,
    trace, ...) inherit the parameter specs; everything else (step counters)
    replicates.  This is the default ZeRO-consistent placement: slots live
    wherever their parameter lives (SURVEY.md §7 step 3).
    """
    param_treedef = jax.tree.structure(param_shapes)

    def specs_for_subtree(sub: PyTree) -> PyTree:
        if jax.tree.structure(sub) == param_treedef:
            shapes = jax.tree.leaves(param_shapes)
            leaves = jax.tree.leaves(sub)
            if all(
                tuple(a.shape) == tuple(b.shape) for a, b in zip(leaves, shapes)
            ):
                return jax.tree.unflatten(
                    jax.tree.structure(sub), jax.tree.leaves(param_specs)
                )
        return jax.tree.map(lambda _: P(), sub)

    def walk(node):
        if isinstance(node, tuple) and not hasattr(node, "shape"):
            children = [walk(c) for c in node]
            if hasattr(node, "_fields"):  # namedtuple (optax state nodes)
                return type(node)(*children)
            return tuple(children)
        return specs_for_subtree(node)

    return walk(opt_shapes)
