"""Cross-replica weight-update sharding (ZeRO-style, stage 1).

Implements PAPERS.md "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (arxiv 2004.13336): under pure data parallelism the
optimizer state is fully replicated, so per-chip memory — not math — caps
the model size.  This module shards the optimizer state AND the weight
update itself across the data-parallel replicas:

- gradients are **reduce-scattered** over the batch axes (each replica
  receives the cross-replica sum of only its 1/N shard);
- each replica applies the optimizer update to only its shard of the
  parameters and optimizer state;
- updated parameters are **all-gathered** back before the next forward
  pass (the forward/backward math is unchanged — this is a memory and
  update-bandwidth optimization, not a model-parallel scheme).

Uneven shapes are handled per the paper: every parameter is flattened and
padded to a multiple of the shard count, then viewed as ``(degree,
padded_size // degree)`` so any shape shards evenly (the pad tail carries
zero gradients, so it is inert under elementwise optimizers).

Implementation note: the collectives here are expressed as GSPMD sharding
*constraints* inside the jitted step rather than a partial-manual
``shard_map`` (that form did not lower on the jax this was written
against; revisiting it is ROADMAP D9) — XLA lowers the constraint on the summed gradient to a
reduce-scatter and the constraint back to the parameter layout to an
all-gather, with the same freedom to fuse/overlap it has for every other
collective in the program.  The constraint applications are routed through
:func:`..parallel.collectives.gspmd_reduce_scatter` /
:func:`~.collectives.gspmd_all_gather` so they land in the span tracer and
the ``collective_dispatch_seconds{op=reduce_scatter|all_gather}``
histogram like every other collective wrapper.

Composition: the sharder chunks over the mesh's batch axes
(``data`` × ``fsdp``), so it composes with the :mod:`.sharding` layout
machinery — tensor-parallel (``model``-axis) parameters keep their layout
(the all-gather constrains back to the bound parameter specs, not to full
replication), and ``fsdp=True`` states simply see their already-sharded
parameters rechunked for the update stage.

Correctness contract: exact (up to float reassociation) for *elementwise*
optimizers — sgd/momentum/adam/adamw/adagrad/lion
(:data:`..train.optimizers.ZERO_SAFE`).  Optimizers that compute
cross-parameter norms or shape-factored statistics (lamb, lars, adafactor)
would see per-shard values instead of per-parameter ones; ``train.py``
warns when ``--zero`` is combined with one of those.
"""

from __future__ import annotations

import logging
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import collectives
from . import mesh as mesh_lib

logger = logging.getLogger("distributedtensorflow_tpu")

PyTree = Any

__all__ = [
    "ZeroSharder",
    "chunk_shape",
    "chunk_array",
    "unchunk_array",
    "map_param_slots",
    "saved_opt_layout",
    "restore_step_zero",
    "restore_latest_zero",
]


# --- chunk math (degree-only, shared with checkpoint rechunking) ------------


def chunk_shape(shape: Sequence[int], degree: int) -> tuple[int, int]:
    """The ``(degree, ceil(size / degree))`` view every parameter shards
    into — the paper's flatten-pad-split partitioning, valid for ANY shape
    (scalars included)."""
    size = math.prod(shape) if shape else 1
    return (degree, -(-size // degree))


def chunk_array(x: jax.Array, degree: int) -> jax.Array:
    """Flatten, zero-pad to a multiple of ``degree``, view as
    ``(degree, chunk)``.  Pure reshape/pad — valid under ``jit`` and
    ``eval_shape``."""
    d, c = chunk_shape(x.shape, degree)
    flat = jnp.ravel(x)
    pad = d * c - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(d, c)


def unchunk_array(x: jax.Array, shape: Sequence[int]) -> jax.Array:
    """Inverse of :func:`chunk_array`: drop the pad tail, restore shape."""
    size = math.prod(shape) if shape else 1
    return x.reshape(-1)[:size].reshape(tuple(shape))


def _chunked_shapes(param_shapes: PyTree, degree: int) -> PyTree:
    """Abstract ``(degree, chunk)`` view of every param leaf — the ONE
    derivation the layout probe, rechunk slot-matching, and intermediate
    sharding all share (they must never disagree about the chunk layout)."""
    return jax.eval_shape(
        lambda p: jax.tree.map(lambda x: chunk_array(x, degree), p),
        param_shapes,
    )


def _shapes(tree: PyTree) -> list[tuple[int, ...]]:
    """Sorted leaf shapes — structure-insensitive comparison key (orbax
    metadata trees nest differently from live optax namedtuples)."""
    return sorted(
        tuple(int(d) for d in leaf.shape)
        for leaf in jax.tree.leaves(tree)
        if hasattr(leaf, "shape")
    )


def map_param_slots(
    opt_tree: PyTree,
    param_shapes: PyTree,
    slot_shapes: PyTree,
    slot_fn: Callable[[Any, Any], Any],
    other_fn: Callable[[Any], Any] = lambda leaf: leaf,
) -> PyTree:
    """Map ``slot_fn(slot_leaf, param_shape_leaf)`` over every
    optimizer-state subtree that mirrors the parameters.

    Optax states are (nested) tuples/namedtuples whose param-shaped nodes
    (momentum, variance, trace, ...) have the params' treedef with leaf
    shapes given by ``slot_shapes`` (the params' own shapes for an
    unchunked state, their :func:`chunk_shape` for a ZeRO state).  Nodes
    that don't match — step counters, schedule state — map through
    ``other_fn`` leafwise.  The same walk
    :func:`..train.state._opt_state_specs` uses, generalized so spec
    derivation and checkpoint rechunking cannot disagree about which
    leaves are slots.
    """
    param_treedef = jax.tree.structure(param_shapes)
    expected = [tuple(s.shape) for s in jax.tree.leaves(slot_shapes)]

    def map_subtree(sub: PyTree) -> PyTree:
        if jax.tree.structure(sub) == param_treedef:
            leaves = jax.tree.leaves(sub)
            if all(
                tuple(getattr(l, "shape", ())) == e
                for l, e in zip(leaves, expected)
            ):
                return jax.tree.unflatten(
                    jax.tree.structure(sub),
                    [
                        slot_fn(l, p)
                        for l, p in zip(leaves, jax.tree.leaves(param_shapes))
                    ],
                )
        return jax.tree.map(other_fn, sub)

    def walk(node):
        if isinstance(node, tuple) and not hasattr(node, "shape"):
            children = [walk(c) for c in node]
            if hasattr(node, "_fields"):  # namedtuple (optax state nodes)
                return type(node)(*children)
            return tuple(children)
        return map_subtree(node)

    return walk(opt_tree)


class ZeroSharder:
    """The weight-update sharding policy for one mesh.

    ``axes`` defaults to the mesh's batch axes (``data`` × ``fsdp``) — the
    data-parallel replicas the paper shards across; ``degree`` is their
    size product.  Create once per run and pass to
    :func:`..train.state.create_sharded_state`, which chunks the optimizer
    state at init and binds the parameter specs the post-update all-gather
    restores to.
    """

    def __init__(self, mesh: Mesh, axes: Sequence[str] | None = None):
        self.mesh = mesh
        self.axes: tuple[str, ...] = tuple(axes or mesh_lib.data_axes(mesh))
        if not self.axes:
            raise ValueError(
                f"mesh {dict(mesh.shape)} has no batch axes to shard the "
                "weight update over"
            )
        self.degree = math.prod(mesh.shape[a] for a in self.axes)
        if self.degree <= 1:
            raise ValueError(
                f"ZeRO degree {self.degree} (axes {self.axes} of mesh "
                f"{dict(mesh.shape)}): nothing to shard — run without --zero"
            )
        #: PartitionSpec of a chunked leaf: dim 0 over the batch axes.
        self.chunk_pspec = P(self.axes)
        self._param_specs: PyTree | None = None

    # --- layout -------------------------------------------------------------

    def bind(self, param_specs: PyTree) -> "ZeroSharder":
        """Record the parameters' PartitionSpecs — the layout the
        post-update all-gather constrains back to (replicated under pure
        DP; the tensor-parallel layout when one is in force)."""
        self._param_specs = param_specs
        return self

    def chunk_tree(self, params: PyTree) -> PyTree:
        return jax.tree.map(lambda x: chunk_array(x, self.degree), params)

    def unchunk_tree(self, chunked: PyTree, like: PyTree) -> PyTree:
        return jax.tree.map(
            lambda c, p: unchunk_array(c, p.shape), chunked, like
        )

    def chunk_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.chunk_pspec)

    def opt_state_specs(self, opt_shapes: PyTree,
                        param_shapes: PyTree) -> PyTree:
        """PartitionSpec pytree for a chunked optimizer state: slot leaves
        shard dim 0 over the batch axes, everything else replicates."""
        chunked = _chunked_shapes(param_shapes, self.degree)
        return map_param_slots(
            opt_shapes, param_shapes, chunked,
            slot_fn=lambda leaf, p: self.chunk_pspec,
            other_fn=lambda leaf: P(),
        )

    # --- the sharded update (inside the jitted train step) ------------------

    def apply_gradients(self, state, grads: PyTree):
        """reduce-scatter grads → shard-local optimizer update →
        all-gather params; the drop-in body behind
        ``TrainState.apply_gradients`` when a sharder is attached.

        The optimizer state enters and leaves in chunked ``(degree,
        chunk)`` layout; the parameters enter full/laid-out, are sliced to
        the local chunk for the update (a dynamic-slice of an
        already-replicated value — no communication), and leave full
        again via the all-gather constraint.
        """
        import optax  # noqa: PLC0415 — keep parallel/ import-light

        cshard = self.chunk_sharding()
        cgrads = collectives.gspmd_reduce_scatter(
            self.chunk_tree(grads), cshard
        )
        cparams = jax.tree.map(
            lambda p: jax.lax.with_sharding_constraint(
                chunk_array(p, self.degree), cshard
            ),
            state.params,
        )
        updates, new_opt_state = state.tx.update(
            cgrads, state.opt_state, cparams
        )
        new_cparams = optax.apply_updates(cparams, updates)
        param_specs = self._param_specs
        if param_specs is None:
            param_specs = jax.tree.map(lambda _: P(), state.params)
        new_params = collectives.gspmd_all_gather(
            self.unchunk_tree(new_cparams, state.params),
            jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), param_specs,
                is_leaf=lambda x: isinstance(x, P),
            ),
        )
        return state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt_state
        )


# --- checkpoint interop: restore across ZeRO degrees ------------------------


def _opt_shapes_for_degree(tx, param_shapes: PyTree,
                           degree: int | None) -> PyTree:
    """Abstract optimizer-state tree for ``tx`` over params chunked at
    ``degree`` (``None`` = unchunked / pure data parallel)."""
    if degree is None:
        return jax.eval_shape(lambda p: tx.init(p), param_shapes)
    return jax.eval_shape(
        lambda p: tx.init(p), _chunked_shapes(param_shapes, degree)
    )


def saved_opt_layout(mgr, step: int, tx, param_shapes: PyTree) -> int | None:
    """The ZeRO degree checkpoint ``step``'s optimizer state was saved at.

    Reads the checkpoint's array *metadata* (shapes only — no tensor I/O)
    and matches it against the layouts ``tx`` could have produced: the
    unchunked layout (returns ``None``) or a chunked layout at any degree
    observed in the saved leading dims.  Raises ``ValueError`` when the
    saved shapes match no candidate (a different optimizer family — the
    same failure a plain restore would hit, reported before any I/O).
    """
    opt_meta = mgr.item_metadata(step).get("opt_state")
    if opt_meta is None:
        raise ValueError(f"checkpoint step {step} has no opt_state metadata")
    got = _shapes(opt_meta)
    if got == _shapes(_opt_shapes_for_degree(tx, param_shapes, None)):
        return None
    candidates = sorted({s[0] for s in got if len(s) == 2 and s[0] > 1})
    for d in candidates:
        if got == _shapes(_opt_shapes_for_degree(tx, param_shapes, d)):
            return d
    raise ValueError(
        f"checkpoint step {step} optimizer-state shapes {got[:4]}... match "
        "neither the unchunked layout nor any ZeRO degree in "
        f"{candidates} — was it saved with a different optimizer?"
    )


def _rechunk_opt_state(
    opt_state: PyTree,
    param_shapes: PyTree,
    from_degree: int | None,
    to_sharder: ZeroSharder | None,
) -> PyTree:
    """Convert an optimizer state between ZeRO layouts (host-side math:
    unchunk at the saved degree, rechunk at the target's).  Non-slot
    leaves pass through."""
    slot_shapes = (
        param_shapes if from_degree is None
        else _chunked_shapes(param_shapes, from_degree)
    )

    def convert(leaf, pshape):
        x = leaf if from_degree is None else unchunk_array(leaf, pshape.shape)
        return (
            chunk_array(x, to_sharder.degree) if to_sharder is not None else x
        )

    return map_param_slots(opt_state, param_shapes, slot_shapes, convert)


def _mesh_of(target) -> Mesh | None:
    """The mesh a TrainState's arrays live on (from their NamedShardings),
    or None for host-only/unsharded trees."""
    for leaf in jax.tree.leaves(target.params):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding):
            return sh.mesh
    return None


def restore_step_zero(mgr, step: int, target, mesh: Mesh | None = None,
                      sharder: ZeroSharder | None = None):
    """Layout-aware restore of ONE checkpoint step into ``target``.

    Probes the saved ZeRO degree first; a matching layout restores
    directly with :meth:`~..checkpoint.CheckpointManager.restore`
    semantics (verifies, raises ``CheckpointCorruptError``, no fallback).
    A mismatched layout restores into an intermediate state shaped like
    the *saved* layout — so the CRC32 integrity manifest verifies the
    bytes exactly as written — then rechunks the verified slots into the
    target layout and placement.  ``mesh`` and ``sharder`` default from
    ``target`` (its attached sharder, its arrays' sharding), so callers
    holding only a state template — the sidecar evaluator — stay
    layout-safe across trainer/evaluator topology differences.

    Returns ``(restored_state, rechunked)`` where ``rechunked`` is None
    for a direct restore or ``{"from": degree, "to": degree}``.
    """
    if sharder is None:
        sharder = getattr(target, "zero", None)
    if mesh is None:
        mesh = sharder.mesh if sharder is not None else _mesh_of(target)
    param_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), target.params
    )
    target_degree = sharder.degree if sharder is not None else None
    try:
        saved_degree = saved_opt_layout(mgr, step, target.tx, param_shapes)
    except Exception as e:
        logger.warning(
            "checkpoint step %d: ZeRO layout probe failed (%s); "
            "attempting a direct restore", step, e,
        )
        saved_degree = target_degree
    if saved_degree == target_degree or mesh is None:
        # mesh is None: nowhere to place a rechunk intermediate — the
        # direct restore surfaces the same shape mismatch it always did.
        return mgr.restore(step, target), None
    logger.warning(
        "checkpoint step %d was saved at ZeRO degree %s; rechunking "
        "its optimizer state to degree %s on restore",
        step, saved_degree or 1,
        target_degree or 1,
    )
    repl = NamedSharding(mesh, P())
    mid_opt_shapes = _opt_shapes_for_degree(
        target.tx, param_shapes, saved_degree
    )
    # Shard the intermediate's slot leaves dim-0 over the batch axes
    # when the saved degree divides across them — a replicated
    # intermediate would transiently hold the full per-device
    # optimizer copy --zero exists to avoid.  (A saved UNCHUNKED
    # layout has no shardable leading dim; that direction replicates,
    # costing no more than the run it migrates from.)
    mid_shardings = jax.tree.map(lambda _: repl, mid_opt_shapes)
    if saved_degree is not None:
        axes = (
            sharder.axes if sharder is not None
            else tuple(mesh_lib.data_axes(mesh))
        )
        nshards = math.prod(mesh.shape[a] for a in axes) if axes else 1
        if nshards > 1 and saved_degree % nshards == 0:
            slot_shapes = _chunked_shapes(param_shapes, saved_degree)
            mid_shardings = map_param_slots(
                mid_opt_shapes, param_shapes, slot_shapes,
                slot_fn=lambda leaf, p: NamedSharding(mesh, P(axes)),
                other_fn=lambda leaf: repl,
            )
    mid_opt = jax.jit(
        lambda shapes=mid_opt_shapes: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes
        ),
        out_shardings=mid_shardings,
    )()
    mid = target.replace(opt_state=mid_opt)
    restored = mgr.restore(step, mid)
    out_shardings = jax.tree.map(lambda a: a.sharding, target.opt_state)
    converted = jax.jit(
        lambda opt: _rechunk_opt_state(
            opt, param_shapes, saved_degree, sharder
        ),
        out_shardings=out_shardings,
    )(restored.opt_state)
    rechunked = {"from": saved_degree or 1, "to": target_degree or 1}
    return restored.replace(opt_state=converted), rechunked


def restore_latest_zero(mgr, target, mesh: Mesh | None = None,
                        sharder: ZeroSharder | None = None,
                        *, before_step: int | None = None):
    """Restore the newest *verified* checkpoint into ``target``, converting
    the optimizer state between ZeRO degrees when the saved layout differs
    from the target's.

    ``target`` is a fully-built TrainState whose opt_state layout reflects
    ``sharder`` (chunked at its degree, or unchunked when ``sharder`` is
    None; both default from ``target`` like :func:`restore_step_zero`).
    Every candidate step gets its OWN layout probe — a mixed-layout
    history must not re-try a differently-chunked step against this
    target and mistake the shape mismatch for corruption.  Corrupt steps
    fall back to the next-newest (``restore_latest`` semantics);
    ``before_step`` restricts candidates to strictly earlier steps (the
    supervisor's NaN-recovery contract).  Returns None when no usable
    checkpoint exists.
    """
    from ..checkpoint.integrity import CheckpointCorruptError  # noqa: PLC0415

    steps = sorted(mgr.all_steps(), reverse=True)
    if before_step is not None:
        steps = [s for s in steps if s < before_step]
    rejected: list[dict] = []
    for step in steps:
        try:
            restored, rechunked = restore_step_zero(
                mgr, step, target, mesh, sharder
            )
        except FileNotFoundError:
            continue
        except CheckpointCorruptError as e:
            rejected.append({"step": step, "reason": str(e)[:300]})
            continue
        report = {"restored_step": step, "rejected": rejected}
        if rechunked is not None:
            report["rechunked"] = rechunked
        mgr.last_restore_report = report
        if rejected:
            logger.warning(
                "restored VERIFIED checkpoint step %d after rejecting "
                "%s", step, [r["step"] for r in rejected],
            )
        return restored
    # Overwrite unconditionally (restore_latest semantics): a None return
    # with no candidates must not leave an EARLIER restore's rejections in
    # the report for callers — the supervisor's restart telemetry — to
    # misattribute to this attempt.
    mgr.last_restore_report = {"restored_step": None, "rejected": rejected}
    if rejected:
        logger.error(
            "no verifiable checkpoint left (rejected %s); cold start",
            [r["step"] for r in rejected],
        )
    return None
