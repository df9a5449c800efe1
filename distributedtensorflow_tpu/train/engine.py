"""The SPMD train-step engine — one engine for the whole strategy zoo.

Replaces the reference's L3 sync strategies and L6 trainer plumbing
(SURVEY.md §3.1): where TF builds a cross-replica graph with one Python
thread per replica, a ``merge_call`` barrier, and an explicit
``CollectiveAllReduce`` launch, here the *entire* train step is a single
jitted SPMD program:

- data parallelism comes from sharding the batch over the ``data``/``fsdp``
  mesh axes; XLA's sharding propagation inserts the gradient all-reduce
  (reduce-scatter + all-gather under fsdp) over ICI — the compiled
  equivalent of ``NcclReducer`` (SURVEY.md §2.2);
- cross-replica weight-update sharding (``--zero``, parallel/zero.py)
  changes nothing here: the state carries its ZeroSharder, so the same
  ``apply_gradients`` call inside :func:`_step_body` compiles to
  reduce-scatter → 1/N-sharded optimizer update → all-gather, with the
  chunked optimizer-state shardings arriving via ``state_specs`` like any
  other layout;
- gradient accumulation (the reference's BERT config,
  ``base_optimizer.py:79-108``) is a ``lax.scan`` over microbatches inside
  the same program;
- OneDevice / Mirrored / MultiWorkerMirrored are not code paths — they are
  mesh shapes (SURVEY.md §7 step 4).

Loss-function contract::

    loss_fn(params, model_state, batch, rng)
        -> (scalar_loss, (metrics_dict, new_model_state))

``model_state`` carries non-trainable collections (batch_stats); models
without any pass ``{}`` through unchanged.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..parallel import sharding as shardlib
from .state import TrainState

PyTree = Any

LossFn = Callable[
    [PyTree, PyTree, PyTree, jax.Array],
    tuple[jax.Array, tuple[dict[str, jax.Array], PyTree]],
]


def split_microbatches(batch: PyTree, accum_steps: int) -> PyTree:
    """Reshape each leaf (B, ...) -> (accum_steps, B//accum_steps, ...)."""

    def split(x):
        b = x.shape[0]
        if b % accum_steps:
            raise ValueError(
                f"batch dim {b} not divisible by accum_steps={accum_steps}"
            )
        return x.reshape(accum_steps, b // accum_steps, *x.shape[1:])

    return jax.tree.map(split, batch)


def accumulate_gradients(
    loss_fn: LossFn,
    params: PyTree,
    model_state: PyTree,
    batch: PyTree,
    rng: jax.Array,
    accum_steps: int,
) -> tuple[PyTree, dict[str, jax.Array], PyTree]:
    """Gradient accumulation as a ``lax.scan`` over microbatches.

    Keeps memory flat (one microbatch of activations live at a time) while
    XLA still sees a single fused program — the TPU-idiomatic version of the
    reference's optimizer-level accumulation.  Returns
    ``(grads, metrics, new_model_state)`` with grads/metrics averaged over
    microbatches.
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    if accum_steps <= 1:
        (loss, (metrics, new_mstate)), grads = grad_fn(
            params, model_state, batch, rng
        )
        return grads, dict(metrics, loss=loss), new_mstate

    micro = split_microbatches(batch, accum_steps)
    rngs = jax.random.split(rng, accum_steps)

    def body(carry, xs):
        grads_acc, metrics_acc, mstate = carry
        mb, r = xs
        (loss, (metrics, mstate)), grads = grad_fn(params, mstate, mb, r)
        metrics = dict(metrics, loss=loss)
        grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
        metrics_acc = jax.tree.map(jnp.add, metrics_acc, metrics)
        return (grads_acc, metrics_acc, mstate), None

    zero_grads = jax.tree.map(jnp.zeros_like, params)
    mb0 = jax.tree.map(lambda x: x[0], micro)
    (loss_s, (metrics_s, _)), _ = jax.eval_shape(
        grad_fn, params, model_state, mb0, rngs[0]
    )
    zero_metrics = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), dict(metrics_s, loss=loss_s)
    )

    (grads, metrics, new_mstate), _ = lax.scan(
        body, (zero_grads, zero_metrics, model_state), (micro, rngs)
    )
    inv = 1.0 / accum_steps
    grads = jax.tree.map(lambda g: g * inv, grads)
    metrics = jax.tree.map(lambda m: m * inv, metrics)
    return grads, metrics, new_mstate


def separate_update(grads: PyTree) -> PyTree:
    """``grads`` behind one ``lax.optimization_barrier`` over the whole
    tree: the identity, which XLA may neither fuse nor schedule across.

    An element-wise optimizer's update of a leaf depends on that leaf's
    gradient alone, so on one chip XLA fuses the AdamW arithmetic into the
    output of the weight-gradient product that feeds it, and the fused
    product runs slower than the product and the update apart (GPT-2
    medium, a layer: ``fc_in``'s weights + AdamW 3.99 ms where 2.96 is the
    product alone; PERF.md section 6, PR 49).  Behind the barrier each
    product writes its gradient and the update runs as element-wise fusions
    under the ``optimizer`` scope once the backward has ended: the program
    a data-parallel mesh already runs, where the gradient all-reduce stands
    in the same place.  One barrier over the tree, not one a leaf: a leaf's
    own barrier forbids the fusion too, but lets the scheduler start that
    leaf's update inside the backward, where the update's f32 operands take
    the fast memory the neighbouring products' operands had (31 ms a step
    of 1280, same section); every gradient alive until the backward ends
    costs no memory the step did not already hold.
    """
    return lax.optimization_barrier(grads)


def optimizer_update(params: PyTree) -> tuple[str, int]:
    """``(optimizer_update, optimizer_update_leaves)`` of the trainer's
    start-up row: ``"separate"`` — the update is a region of the step of
    its own (:func:`separate_update`) — and the gradient leaves that pass a
    barrier, one a parameter leaf.  The mechanism is static, so the field,
    not a rate, says it engaged; ``train_optimizer_ms`` is what it costs."""
    return "separate", len(jax.tree.leaves(params))


class _InstrumentedStep:
    """Thin telemetry shim over a jitted step executable.

    Counts dispatches into the obs registry and brackets the first dispatch
    (which pays tracing + XLA compile: the compile log's rows,
    ``obs.tracing``) with two flight events — without touching the
    per-dispatch hot path beyond one counter increment.  ``lower`` is
    forwarded so the AOT path (``step.lower(...).compile()``, as
    ``tools/train_step_memory.py`` calls it)
    keeps working on the wrapped object.

    Every call and ``lower`` runs under ``jax.sharding.set_mesh(mesh)``:
    the Pallas kernels in ``ops/`` read the mesh from that context at
    trace time to run per shard (``parallel.sharding.shard_kernel``) —
    GSPMD cannot partition a Mosaic call on its own.
    """

    __slots__ = ("_jitted", "_mesh", "_label", "_first", "_dispatches")

    def __init__(self, jitted, mesh: Mesh, label: str):
        self._jitted = jitted
        self._mesh = mesh
        self._label = label
        self._first = True
        self._dispatches = obs.counter(
            "engine_dispatches_total",
            "train/eval step dispatches by executable kind",
        )

    def __call__(self, *args):
        with jax.sharding.set_mesh(self._mesh):
            return self._dispatch(*args)

    def _dispatch(self, *args):
        if self._first:
            self._first = False
            # Flight markers: a hang *during* compile looks identical to a
            # stalled collective from outside; a ring whose last event is
            # compile_begin (no matching compile) is the disambiguating
            # post-mortem signature — so the begin marker must land BEFORE
            # the potentially-wedging call.
            obs.record_event("compile_begin", label=self._label)
            t0 = time.perf_counter()
            out = self._jitted(*args)
            obs.record_event(
                "compile", label=self._label,
                seconds=round(time.perf_counter() - t0, 3),
            )
            self._dispatches.inc(kind=self._label)
            return out
        self._dispatches.inc(kind=self._label)
        return self._jitted(*args)

    def lower(self, *args, **kwargs):
        with jax.sharding.set_mesh(self._mesh):
            return self._jitted.lower(*args, **kwargs)

    @property
    def jitted(self):
        return self._jitted


def estimate_step_flops(step, state, batch_abstract, rng) -> float | None:
    """Best-effort per-step FLOPs from XLA's compiled cost analysis.

    AOT-lowers ``step`` against abstract batch shapes and reads
    ``cost_analysis()["flops"]`` — the partitioned (per-device) module's
    count, exactly the per-chip MFU numerator.  Known coarseness: a
    ``lax.scan`` body (grad accumulation, multi-step bundling) is counted
    once regardless of trip count (see ``obs.mfu.mfu_fields``'s
    ``xla_flops_scale`` note).  Returns None when the backend's cost
    analysis can't answer; callers treat that as "no MFU fields".  A step
    that fails to compile raises here as it would at the first dispatch.
    Costs one extra compile — the persistent compilation cache absorbs it
    on reruns.
    """
    # (the compile log's root spans keep this AOT compile in the goodput
    # `compile` bucket: it runs pre-fit, where unattributed time would
    # read as `init`)
    compiled = step.lower(state, batch_abstract, rng).compile()
    return obs.mfu.xla_cost_flops(compiled)


def make_train_step(
    loss_fn: LossFn,
    mesh: Mesh,
    state_specs: TrainState,
    *,
    accum_steps: int = 1,
    donate: bool = True,
    overlap=None,
    dynamics_every: int = 0,
) -> Callable[[TrainState, PyTree, jax.Array], tuple[TrainState, dict[str, jax.Array]]]:
    """Compile the full train step over ``mesh``.

    The returned function has signature ``(state, batch, rng) -> (state,
    metrics)``.  ``batch`` leaves must have a leading global-batch dimension;
    it is sharded over the batch axes.  ``state`` is donated: parameters are
    updated in place in HBM (no double-buffering of the model).

    ``overlap`` (a :class:`~..parallel.overlap.OverlapPlan`) routes the
    parameters through per-layer-group backward tags so each bucket's
    gradient collective is issued inside the backward pass (collective–
    matmul overlap) instead of after it; numerically identity.

    ``dynamics_every > 0`` adds the in-graph training-dynamics stats
    (:func:`~..obs.dynamics.cadence_stats`): ``lax.cond``-gated
    per-module grad/param/update statistics riding the metrics dict
    under ``dynamics/`` keys every that many optimizer steps.
    """
    batch_sharding = NamedSharding(mesh, shardlib.batch_spec(mesh))
    state_shardings = shardlib.named_shardings(mesh, state_specs)
    repl = NamedSharding(mesh, P())
    step = _step_body(loss_fn, accum_steps, overlap, dynamics_every)

    return _InstrumentedStep(
        jax.jit(
            step,
            in_shardings=(state_shardings, batch_sharding, repl),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0,) if donate else (),
        ),
        mesh,
        "train_step",
    )


def _step_body(loss_fn: LossFn, accum_steps: int, overlap=None,
               dynamics_every: int = 0):
    """The one train-step function both engines compile.

    Folds the step counter into the rng (dropout etc. differs per step
    without threading a new key from the host), accumulates gradients over
    microbatches, applies the update as a region of its own
    (:func:`separate_update`).  Shared so the single-step and
    multi-step (scanned) engines can never drift apart semantically.
    ``overlap`` wraps the loss so parameter cotangents flow through the
    plan's bucket tags (see :func:`make_train_step`).  ``dynamics_every``
    merges the cadence-gated dynamics stats into the metrics dict — the
    stats read the pre-update params, the grads, and the post-update
    params, so they must be computed here, before donation recycles the
    old buffers.
    """
    if overlap is not None:
        loss_fn = overlap.wrap_loss_fn(loss_fn)

    def step(state: TrainState, batch: PyTree, rng: jax.Array):
        r = jax.random.fold_in(rng, state.step)
        grads, metrics, new_mstate = accumulate_gradients(
            loss_fn, state.params, state.model_state, batch, r, accum_steps
        )
        grads = separate_update(grads)
        with jax.named_scope("optimizer"):  # a name for the trace
            new_state = state.apply_gradients(grads).replace(
                model_state=new_mstate)
        if dynamics_every > 0:
            from ..obs import dynamics as dynlib

            metrics = dict(metrics, **dynlib.cadence_stats(
                state.params, new_state.params, grads,
                step=state.step, every=dynamics_every,
            ))
        return new_state, metrics

    return step


def make_multi_train_step(
    loss_fn: LossFn,
    mesh: Mesh,
    state_specs: TrainState,
    *,
    steps_per_call: int,
    accum_steps: int = 1,
    donate: bool = True,
    overlap=None,
    dynamics_every: int = 0,
) -> Callable[[TrainState, PyTree, jax.Array], tuple[TrainState, dict[str, jax.Array]]]:
    """Compile ``steps_per_call`` optimizer steps into ONE dispatch.

    A ``lax.scan`` over whole train steps: the batch pytree carries a
    leading ``steps_per_call`` dimension (one full global batch per inner
    step) and the returned metrics are stacked ``(steps_per_call, ...)``.
    Host-side cost — dispatch, Python — is paid once per call
    instead of once per step; the XLA program the chip runs per step is
    identical to :func:`make_train_step`'s.  This is the SPMD analogue of
    the reference's `steps_per_execution` batching (Keras `Model.fit`
    compiles multiple steps into one tf.function call for the same
    host-bound reason — keras/src/trainers/trainer.py `steps_per_execution`).

    The rng folding matches the single-step engine exactly (fold_in of the
    global step counter), so N calls of this follow the same trajectory as
    N*steps_per_call single-step calls — equal up to XLA re-fusing the
    scanned program (measured ~1e-7 after 4 SGD steps;
    ``tests/test_engine.py::test_multi_step_matches_single_steps``).
    """
    if steps_per_call <= 1:
        return make_train_step(
            loss_fn, mesh, state_specs, accum_steps=accum_steps,
            donate=donate, overlap=overlap, dynamics_every=dynamics_every,
        )
    batch_sharding = NamedSharding(
        mesh, shardlib.batch_spec(mesh, leading_unsharded=1)
    )
    state_shardings = shardlib.named_shardings(mesh, state_specs)
    repl = NamedSharding(mesh, P())

    one_step = _step_body(loss_fn, accum_steps, overlap, dynamics_every)

    def multi_step(state: TrainState, batches: PyTree, rng: jax.Array):
        def body(s, b):
            return one_step(s, b, rng)

        return lax.scan(body, state, batches)

    return _InstrumentedStep(
        jax.jit(
            multi_step,
            in_shardings=(state_shardings, batch_sharding, repl),
            out_shardings=(state_shardings, repl),
            donate_argnums=(0,) if donate else (),
        ),
        mesh,
        "multi_train_step",
    )


def make_eval_step(
    metric_fn: Callable[[PyTree, PyTree, PyTree], dict[str, jax.Array]],
    mesh: Mesh,
    state_specs: TrainState,
) -> Callable[[TrainState, PyTree], dict[str, jax.Array]]:
    """Compile an eval step: ``metric_fn(params, model_state, batch)``."""
    batch_sharding = NamedSharding(mesh, shardlib.batch_spec(mesh))
    param_shardings = shardlib.named_shardings(mesh, state_specs.params)
    mstate_shardings = shardlib.named_shardings(mesh, state_specs.model_state)
    repl = NamedSharding(mesh, P())

    jitted = _InstrumentedStep(
        jax.jit(
            metric_fn,
            in_shardings=(param_shardings, mstate_shardings, batch_sharding),
            out_shardings=repl,
        ),
        mesh,
        "eval_step",
    )
    return lambda state, batch: jitted(state.params, state.model_state, batch)
